"""The mirror drain honours the flusher's watermark: a chunk whose rows
the per-op column flush has put into the trees is registered clean, so
no later flush (a checkpoint's above all) puts those bytes a second
time; a chunk the column path has not reached is registered dirty and
the object path covers it.

reference analogs: storage determinism (storage_checker.zig:55 —
byte-identical checkpoints across replicas and across a restart)."""

import pytest

from tests.test_durable import _policy_flush
from tigerbeetle_tpu import multi_batch
from tigerbeetle_tpu.state_machine import StateMachine
from tigerbeetle_tpu.testing.cluster import Cluster
from tigerbeetle_tpu.types import (Account, Operation, Transfer,
                                   TransferFlags)
from tigerbeetle_tpu.vsr.durable import DurableState, mirror_quiescent
from tigerbeetle_tpu.vsr.replica import _split_root
from tigerbeetle_tpu.vsr.storage import TEST_LAYOUT, MemoryStorage

ACCOUNTS = 12
PER_OP = 24
PENDING = int(TransferFlags.pending)
POST = int(TransferFlags.post_pending_transfer)
VOID = int(TransferFlags.void_pending_transfer)


def _device_machine():
    return StateMachine(engine="device", a_cap=1 << 9, t_cap=1 << 13)


def _oracle_machine():
    return StateMachine(engine="oracle")


def _accounts_body(first: int, count: int) -> bytes:
    return multi_batch.encode(
        [b"".join(Account(id=i, ledger=1, code=1).pack()
                  for i in range(first, first + count))], 128)


def _transfers_body(k: int) -> bytes:
    """Request k of the mix: plain transfers, pendings with and without
    a timeout, posts and voids of the previous request's pendings, and
    one event a request that fails transiently (an orphaned id)."""
    base = 10_000 + k * 1_000
    evs = []
    for j in range(PER_OP):
        dr = 1 + (j + k) % ACCOUNTS
        cr = 1 + (j + k + 1) % ACCOUNTS
        if j % 6 == 0:
            evs.append(Transfer(
                id=base + j, debit_account_id=dr, credit_account_id=cr,
                amount=5 + j, ledger=1, code=1, flags=PENDING,
                timeout=3600 if j % 12 == 0 else 0))
        elif j % 6 == 1 and k:
            evs.append(Transfer(
                id=base + j, pending_id=base - 1_000 + j - 1,
                ledger=1, code=1, flags=POST if j % 12 == 1 else VOID))
        elif j == 5:
            evs.append(Transfer(  # credit account does not exist
                id=base + j, debit_account_id=dr, credit_account_id=999,
                amount=1, ledger=1, code=1))
        else:
            evs.append(Transfer(
                id=base + j, debit_account_id=dr, credit_account_id=cr,
                amount=1 + j, ledger=1, code=1, user_data_64=k,
                user_data_32=j))
    return multi_batch.encode([b"".join(e.pack() for e in evs)], 128)


def _plain(first_id: int, n: int) -> bytes:
    return b"".join(
        Transfer(id=first_id + j, debit_account_id=1 + j % ACCOUNTS,
                 credit_account_id=1 + (j + 1) % ACCOUNTS, amount=1 + j,
                 ledger=1, code=1).pack() for j in range(n))


def _plain_body(k: int) -> bytes:
    return multi_batch.encode([_plain(10_000 + k * 1_000, PER_OP)], 128)


class _Run:
    """One single-replica cluster and its one client."""

    def __init__(self, factory, seed: int = 7):
        self.cluster = Cluster(seed=seed, replica_count=1,
                               state_machine_factory=factory)
        self.client = self.cluster.client(5)
        self.sent = 0

    @property
    def replica(self):
        return self.cluster.replicas[0]

    def drive(self, operation, body) -> None:
        self.client.request(operation, body)
        assert self.cluster.run(4000, until=lambda: self.client.idle), \
            self.cluster.debug_status()

    def transfers(self, requests: int, body=_transfers_body) -> None:
        for _ in range(requests):
            self.drive(Operation.create_transfers, body(self.sent))
            self.sent += 1

    def past_op(self, op: int) -> None:
        """Requests of the mix until `op` is committed. A resolved
        pending with a timeout makes the primary commit a pulse of its
        own, so a request takes one op or two."""
        while self.replica.commit_min < op:
            self.transfers(1)
        assert self.replica.commit_min <= op + 1

    def grid(self):
        """(free set, bytes of every allocated block, checkpoint root):
        what Cluster.check_storage compares between replicas."""
        storage, layout = self.cluster.storages[0], self.cluster.layout
        free = list(self.replica.durable.grid.free)
        bs = layout.grid_block_size
        blocks = tuple(storage.read("grid", b * bs, bs)
                       for b, is_free in enumerate(free) if not is_free)
        sb = self.replica.superblock
        root = storage.read("snapshot",
                            sb.snapshot_slot * layout.snapshot_size_max,
                            sb.snapshot_size)
        return free, blocks, root


def _assert_same_grid(got: _Run, want: _Run) -> None:
    assert got.replica.commit_min == want.replica.commit_min
    assert (got.replica.superblock.op_checkpoint
            == want.replica.superblock.op_checkpoint)
    g_free, g_blocks, g_root = got.grid()
    w_free, w_blocks, w_root = want.grid()
    assert g_free == w_free, "free-set divergence"
    assert g_blocks == w_blocks, "grid divergence"
    assert g_root == w_root, "checkpoint root divergence"


def _started(factory) -> _Run:
    run = _Run(factory)
    run.drive(Operation.create_accounts, _accounts_body(1, ACCOUNTS))
    return run


# ------------------------- (a) a checkpoint puts no row a second time

def test_device_checkpoints_are_byte_identical_to_the_oracle_engines():
    """Two checkpoints, the second at a bar boundary (op 32, where the
    beat has frozen the memtable before the checkpoint fires): no row is
    put twice, so the device engine's grid and root are the oracle
    engine's, and the root opens to every transfer and account."""
    dev, ora = _started(_device_machine), _started(_oracle_machine)
    for run in (dev, ora):
        run.past_op(20)
        # An op that drains the mirror between two checkpoints (the
        # mix's pulses, which expire on the mirror, drain it too).
        run.drive(Operation.create_accounts,
                  _accounts_body(ACCOUNTS + 1, 2))
        run.past_op(32)
        assert run.replica.superblock.op_checkpoint == 32
    rows = dev.replica.durable.rows_put
    assert rows["checkpoints"] == 2
    assert rows["object"] == 0 and rows["object_at_checkpoint"] == 0
    assert rows["column"] == len(ora.replica.state_machine.state.transfers)
    _assert_same_grid(dev, ora)

    forest_root, _ = _split_root(dev.grid()[2])
    opened = DurableState(dev.cluster.storages[0]).open(forest_root)
    want = ora.replica.state_machine.state
    assert len(opened.transfers) == rows["column"]
    assert dict(opened.transfers) == dict(want.transfers)
    assert dict(opened.accounts) == dict(want.accounts)
    assert dict(opened.pending_status) == dict(want.pending_status)
    assert dict(opened.expiry) == dict(want.expiry)
    assert set(opened.orphaned) == set(want.orphaned)


# ------------------- (b) a drain of persisted chunks leaves nothing dirty

def _drain_by_state_read(run: _Run) -> None:
    run.replica.state_machine.state  # what any object-level reader does


def _drain_by_create_accounts(run: _Run) -> None:
    run.drive(Operation.create_accounts, _accounts_body(ACCOUNTS + 1, 1))


@pytest.mark.parametrize("drain", [_drain_by_state_read,
                                   _drain_by_create_accounts])
def test_a_drain_of_persisted_chunks_leaves_the_mirror_quiescent(drain):
    """Between two creates something drains the mirror. Every drained
    chunk lies under the watermark, so the mirror stays quiescent, the
    next create keeps the column path, and a flush of the drained state
    puts no transfer row."""
    run = _started(_device_machine)
    run.transfers(3, _plain_body)
    replica = run.replica
    led = replica.state_machine.led
    assert led._mirror_chunks, "the creates left their chunks queued"
    drain(run)
    assert not led._mirror_chunks
    raw = replica.state_machine.raw_state
    created = 3 * PER_OP
    assert len(raw.transfers) == created
    assert mirror_quiescent(raw, replica.durable.events_persisted)
    assert replica._mirror_quiescent()

    tree = replica.durable.forest.trees["transfers"]
    puts, put, put_run = [], tree.put, tree.put_run
    tree.put = lambda key, value: (puts.append(key), put(key, value))
    tree.put_run = lambda keys, values: (
        puts.extend(k.tobytes() for k in keys), put_run(keys, values))
    _, flushed = replica.durable.flush(replica.state_machine.state)
    assert flushed == [] and puts == []
    run.transfers(1, _plain_body)
    assert len(puts) == PER_OP  # the new rows, once, by the columns
    assert replica.durable.rows_put["column"] == created + PER_OP
    assert replica.durable.rows_put["object"] == 0


# ------------------ (c) chunks over the watermark still go the object way

class _Machine:
    """A state machine over a DurableState, driven without a replica so
    that a test can drain BEFORE the flush."""

    def __init__(self, engine: str, attach: bool = True):
        self.durable = DurableState(MemoryStorage(TEST_LAYOUT))
        self.sm = StateMachine(engine=engine, a_cap=1 << 9, t_cap=1 << 13)
        if attach:
            self.sm.attach_durable(self.durable)
        self.ts = 1_000
        self.commit(Operation.create_accounts, [
            b"".join(Account(id=i, ledger=1, code=1).pack()
                     for i in range(1, ACCOUNTS + 1))], ACCOUNTS)
        _policy_flush(self.sm, self.durable)

    def commit(self, operation, payloads: list, events: int) -> None:
        self.ts += events + 10
        self.sm.commit(operation, multi_batch.encode(payloads, 128),
                       self.ts)


_CLOSING = Transfer(
    id=500, debit_account_id=5, credit_account_id=6, amount=1, ledger=1,
    code=1, flags=int(TransferFlags.closing_debit) | PENDING).pack()
_PENDINGS = b"".join(
    Transfer(id=100 + j, debit_account_id=1 + j % ACCOUNTS,
             credit_account_id=1 + (j + 1) % ACCOUNTS, amount=9, ledger=1,
             code=1, flags=PENDING, timeout=60).pack() for j in range(10))
_RESOLVES = b"".join(
    Transfer(id=200 + j, pending_id=100 + j, ledger=1, code=1,
             flags=POST if j % 2 else VOID).pack() for j in range(10))

# Each case: whether a flusher is attached, the requests (payloads of a
# multi-batch body, events), and what the LAST request's drain has to
# leave dirty: transfers, pending flips, expiry puts or removals.
_OVER_THE_WATERMARK = {
    # No flusher, so no watermark: every drained chunk is dirty.
    "no_durable_attached": (
        False, [([_plain(100, 20)], 20)], (20, 0, 0)),
    # A fast batch and a closing (hard) batch in one request: the hard
    # batch drains the fast one's chunk before any flush has seen it
    # (the interleave of
    # test_column_flush_hard_batch_interleave_matches_oracle, inside
    # one prepare).
    "hard_batch_in_the_same_prepare": (
        True, [([_plain(100, 20), _CLOSING], 21)], (21, 1, 0)),
    "pendings_with_timeouts_then_their_posts": (
        True, [([_PENDINGS], 10), ([_RESOLVES], 10)], (10, 10, 10)),
}


@pytest.mark.parametrize("case", sorted(_OVER_THE_WATERMARK))
def test_chunks_over_the_watermark_are_dirty_and_flushed_as_objects(case):
    """A drain that runs before the op's column flush registers the
    chunk dirty, as it always did, and the object path puts it: the
    trees are the oracle engine's."""
    attach, requests, (n_transfers, n_pending, n_expiry) = \
        _OVER_THE_WATERMARK[case]
    dev, ora = _Machine("device", attach=attach), _Machine("oracle")
    created = 0
    for payloads, events in requests:
        for m in (dev, ora):
            m.commit(Operation.create_transfers, payloads, events)
        raw = dev.sm.state  # drains before the flush has seen the chunk
        assert len(raw.transfers.dirty) == n_transfers
        assert len(raw.pending_status.dirty) == n_pending
        assert len(raw.expiry.dirty) == n_expiry
        assert raw.accounts.dirty
        assert not mirror_quiescent(raw, dev.durable.events_persisted)
        for m in (dev, ora):
            _policy_flush(m.sm, m.durable)
        created += n_transfers
        assert mirror_quiescent(dev.sm.raw_state,
                                dev.durable.events_persisted)
    assert dev.durable.rows_put["object"] == created
    assert dev.durable.rows_put["column"] == 0
    for name, tree in dev.durable.forest.trees.items():
        assert tree.memtable_rows() == \
            ora.durable.forest.trees[name].memtable_rows(), \
            f"tree {name} diverged"


# -------------- (d) a restart replays onto the same bytes as no restart

def test_restart_from_checkpoint_replays_to_the_same_grid_bytes():
    """A replica that crashes past the bar-boundary checkpoint, restarts
    from it and replays the WAL suffix holds the grid of one that never
    stopped, then and at the next checkpoint."""
    steady, crashed = _started(_device_machine), _started(_device_machine)
    for run in (steady, crashed):
        run.past_op(37)
        assert run.replica.superblock.op_checkpoint == 32
    crashed.cluster.crash(0)
    crashed.cluster.restart(0)
    # open() replays the WAL suffix itself: the fresh flusher has put
    # the rows of the ops past the checkpoint, and only those.
    replayed = crashed.replica.durable.rows_put["column"]
    assert 0 < replayed < steady.replica.durable.rows_put["column"]
    _assert_same_grid(crashed, steady)
    for run in (steady, crashed):
        run.past_op(48)
        assert run.replica.superblock.op_checkpoint == 48
    _assert_same_grid(crashed, steady)
    rows = crashed.replica.durable.rows_put
    assert rows["object"] == 0 and rows["object_at_checkpoint"] == 0
