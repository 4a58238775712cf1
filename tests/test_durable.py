"""DurableState: incremental LSM checkpoints under the replica.

reference analogs: checkpoint/resume via copy-on-write grid + superblock
flip (docs/internals/data_file.md:63-94), storage determinism
(storage_checker.zig:55 byte-identical checkpoints)."""

import pytest

from tigerbeetle_tpu import multi_batch
from tigerbeetle_tpu.state_machine import StateMachine
from tigerbeetle_tpu.testing.cluster import Cluster, NetworkOptions
from tigerbeetle_tpu.types import (
    Account,
    Operation,
    Transfer,
    TransferFlags,
)
from tigerbeetle_tpu.vsr import snapshot as snapshot_codec
from tigerbeetle_tpu.vsr.durable import DurableState
from tigerbeetle_tpu.vsr.storage import TEST_LAYOUT, MemoryStorage

MS = 1_000_000


def _rich_state():
    """State covering every persisted container: two-phase, expiry,
    orphaned ids, account events."""
    sm = StateMachine(engine="oracle")
    ts = 1000
    sm.create_accounts(
        [Account(id=i, ledger=1, code=1) for i in (1, 2, 3)], timestamp=ts)
    ts += 100
    sm.create_transfers(
        [Transfer(id=10, debit_account_id=1, credit_account_id=2, amount=50,
                  ledger=1, code=1),
         Transfer(id=11, debit_account_id=1, credit_account_id=2, amount=5,
                  ledger=1, code=1, flags=int(TransferFlags.pending),
                  timeout=3600),
         Transfer(id=12, debit_account_id=2, credit_account_id=3, amount=7,
                  ledger=1, code=1, flags=int(TransferFlags.pending))],
        timestamp=ts)
    ts += 100
    sm.create_transfers(
        [Transfer(id=13, debit_account_id=0, credit_account_id=2, amount=1,
                  ledger=1, code=1),  # fails (non-transient)
         Transfer(id=14, pending_id=12, ledger=1, code=1,
                  flags=int(TransferFlags.post_pending_transfer)),
         Transfer(id=15, debit_account_id=1, credit_account_id=9, amount=1,
                  ledger=1, code=1)],  # transient: orphaned id
        timestamp=ts)
    return sm


class TestDurableRoundtrip:
    def test_checkpoint_open_roundtrip(self):
        sm = _rich_state()
        storage = MemoryStorage(TEST_LAYOUT)
        durable = DurableState(storage)
        root = durable.checkpoint(sm.state)
        assert len(root) <= TEST_LAYOUT.snapshot_size_max

        durable2 = DurableState(storage)
        restored = durable2.open(root)
        assert (snapshot_codec.encode(restored)
                == snapshot_codec.encode(sm.state))
        assert restored.orphaned == {15}
        assert not restored.accounts.dirty and not restored.transfers.dirty

    def test_incremental_flush_only_writes_dirty(self):
        sm = _rich_state()
        storage = MemoryStorage(TEST_LAYOUT)
        durable = DurableState(storage)
        durable.checkpoint(sm.state)
        # After a checkpoint nothing is dirty: a second flush writes nothing.
        trees = durable.forest.trees
        before = {name: len(t.memtable_rows()) for name, t in trees.items()}
        durable.flush(sm.state)
        after = {name: len(t.memtable_rows()) for name, t in trees.items()}
        assert before == after == {name: 0 for name in trees}
        # One more transfer dirties exactly the touched objects.
        sm.create_transfers(
            [Transfer(id=20, debit_account_id=1, credit_account_id=2,
                      amount=1, ledger=1, code=1)], timestamp=10_000)
        durable.flush(sm.state)
        assert len(trees["transfers"].memtable_rows()) == 1
        assert len(trees["accounts"].memtable_rows()) == 2
        assert len(trees["events"].memtable_rows()) == 1

    def test_failed_linked_chain_rollback_flush(self):
        """A rolled-back linked chain leaves dirty keys whose objects were
        removed again — flush must skip them, not crash, and must not write
        tombstones for objects that were never persisted."""
        sm = _rich_state()
        storage = MemoryStorage(TEST_LAYOUT)
        durable = DurableState(storage)
        durable.checkpoint(sm.state)
        results = sm.create_transfers(
            [Transfer(id=30, debit_account_id=1, credit_account_id=2,
                      amount=1, ledger=1, code=1,
                      flags=int(TransferFlags.linked | TransferFlags.pending),
                      timeout=60),
             Transfer(id=31, debit_account_id=1, credit_account_id=99,
                      amount=1, ledger=1, code=1)],
            timestamp=50_000)
        assert results[0].status.name == "linked_event_failed"
        root = durable.checkpoint(sm.state)
        restored = DurableState(storage).open(root)
        assert 30 not in restored.transfers
        assert (snapshot_codec.encode(restored)
                == snapshot_codec.encode(sm.state))
        # The rolled-back pending row never reached the trees: no tombstone.
        assert durable.forest.trees["transfers"].get(
            (30).to_bytes(16, "big")) is None

    def test_root_blob_stays_small_as_state_grows(self):
        sm = StateMachine(engine="oracle")
        storage = MemoryStorage(TEST_LAYOUT)
        durable = DurableState(storage)
        ts = 1000
        sm.create_accounts(
            [Account(id=i, ledger=1, code=1) for i in (1, 2)], timestamp=ts)
        sizes = []
        for round_i in range(8):
            ts += 200
            sm.create_transfers(
                [Transfer(id=100 + round_i * 64 + k, debit_account_id=1,
                          credit_account_id=2, amount=1, ledger=1, code=1)
                 for k in range(64)], timestamp=ts)
            sizes.append(len(durable.checkpoint(sm.state)))
        # Incremental: the root references manifests, not data; growth is
        # table-count bound, far below the object count.
        assert sizes[-1] < 8192
        restored = DurableState(storage).open(durable.checkpoint(sm.state))
        assert len(restored.transfers) == 8 * 64


class TestClusterDurability:
    def test_many_checkpoints_and_restart_replay_determinism(self):
        """Run past several checkpoint/bar boundaries, crash + restart a
        replica mid-interval, and require byte-identical grids (settle()
        runs the storage checker)."""
        cluster = Cluster(seed=42, replica_count=3)
        client = cluster.client(1)

        def drive(op, body):
            client.request(op, body)
            ok = cluster.run(4000, until=lambda: client.idle)
            assert ok, cluster.debug_status()

        drive(Operation.create_accounts, multi_batch.encode(
            [b"".join(Account(id=i, ledger=1, code=1).pack()
                      for i in (1, 2))], 128))
        tid = 100
        for batch in range(20):
            body = multi_batch.encode(
                [b"".join(Transfer(id=tid + k, debit_account_id=1,
                                   credit_account_id=2, amount=1,
                                   ledger=1, code=1).pack()
                          for k in range(3))], 128)
            tid += 3
            drive(Operation.create_transfers, body)
            if batch == 10:
                victim = (cluster.replicas[0].primary_index() + 1) % 3
                cluster.crash(victim)
            if batch == 14:
                cluster.restart(victim)
        cluster.settle()
        assert all(r.superblock.op_checkpoint > 0 for r in cluster.replicas)
        a1 = cluster.replicas[0].state_machine.state.accounts[1]
        assert a1.debits_posted == 60

    @pytest.mark.parametrize("seed", [21, 22])
    def test_chaos_with_checkpoints(self, seed):
        cluster = Cluster(
            seed=seed, replica_count=3,
            network=NetworkOptions(loss_probability=0.05,
                                   duplicate_probability=0.05,
                                   delay_min_ns=1 * MS,
                                   delay_max_ns=30 * MS))
        client = cluster.client(7)
        body_accounts = multi_batch.encode(
            [b"".join(Account(id=i, ledger=1, code=1).pack()
                      for i in (1, 2))], 128)
        client.request(Operation.create_accounts, body_accounts)
        ok = cluster.run(4000, until=lambda: client.idle)
        assert ok, cluster.debug_status()
        for k in range(25):
            body = multi_batch.encode(
                [Transfer(id=1000 + k, debit_account_id=1,
                          credit_account_id=2, amount=1, ledger=1,
                          code=1).pack()], 128)
            client.request(Operation.create_transfers, body)
            ok = cluster.run(6000, until=lambda: client.idle)
            assert ok, cluster.debug_status()
        cluster.settle()
        assert all(r.superblock.op_checkpoint > 0 for r in cluster.replicas)


def _policy_flush(sm, durable):
    """The replica's flush policy (vsr/replica.py): columns against a
    quiescent mirror, else drain + object path."""
    led = sm.led
    cols = led.take_flush_columns() if led is not None else None
    raw = sm.raw_state
    if cols and (raw.accounts.dirty or raw.transfers.dirty
                 or raw.pending_status.dirty or raw.expiry.dirty
                 or raw.orphaned.dirty
                 or durable.events_persisted < (
                     raw.events_base + len(raw.account_events))):
        sm.state  # drain
        cols = None
    flushed = durable.flush(raw, flush_columns=cols)
    sm.cache_upsert(*flushed)
    return flushed


def test_vectorized_column_flush_matches_object_flush():
    """durable.flush's vectorized transfer path (device-engine columns)
    must produce byte-identical trees to the object path (oracle engine)
    over the same commits."""
    import numpy as np

    from tigerbeetle_tpu import multi_batch
    from tigerbeetle_tpu.state_machine import StateMachine
    from tigerbeetle_tpu.types import Operation, TransferFlags
    from tigerbeetle_tpu.vsr.durable import DurableState
    from tigerbeetle_tpu.vsr.storage import TEST_LAYOUT, MemoryStorage

    def build(engine):
        storage = MemoryStorage(TEST_LAYOUT)
        durable = DurableState(storage)
        sm = StateMachine(engine=engine, a_cap=1 << 12, t_cap=1 << 14)
        sm.attach_durable(durable)
        ts = 1000
        accts = [Account(id=i, ledger=1, code=1) for i in range(1, 60)]
        ts += len(accts) + 10
        sm.create_accounts(accts, ts)
        _policy_flush(sm, durable)
        rng = np.random.default_rng(5)
        nb = 300
        next_id = 10**7
        pend = int(TransferFlags.pending)
        post = int(TransferFlags.post_pending_transfer)
        for b in range(3):
            evs = []
            for i in range(nb):
                tid = next_id
                next_id += 1
                if b == 2 and i % 5 == 0:
                    evs.append(Transfer(
                        id=tid, pending_id=10**7 + nb + i,
                        amount=(1 << 128) - 1, flags=post))
                else:
                    dr = int(rng.integers(1, 60))
                    cr = dr % 59 + 1
                    evs.append(Transfer(
                        id=tid, debit_account_id=dr, credit_account_id=cr,
                        amount=int(rng.integers(1, 1000)), ledger=1, code=1,
                        user_data_128=(1 << 100) + i, user_data_64=i % 7,
                        user_data_32=i % 5,
                        flags=pend if i % 4 == 0 else 0,
                        timeout=60 if i % 4 == 0 else 0))
            payload = b"".join(e.pack() for e in evs)
            body = multi_batch.encode([payload], 128)
            ts += nb + 10
            sm.commit(Operation.create_transfers, body, ts)
            _policy_flush(sm, durable)
        return durable

    dev = build("device")
    ora = build("oracle")
    for name in dev.forest.trees:
        t_dev = dev.forest.trees[name]
        t_ora = ora.forest.trees[name]
        assert t_dev.memtable_rows() == t_ora.memtable_rows(), \
            f"tree {name} diverged"


def test_column_flush_hard_batch_interleave_matches_oracle():
    """The hard-regime handoff (review scenario): a closing transfer runs
    on the mirror between fast-path chunks; the policy flush must drain
    and serialize through ONE authority — trees must match the oracle
    twin exactly across the handoff."""
    import numpy as np

    from tigerbeetle_tpu import multi_batch
    from tigerbeetle_tpu.state_machine import StateMachine
    from tigerbeetle_tpu.types import Operation, TransferFlags
    from tigerbeetle_tpu.vsr.durable import DurableState
    from tigerbeetle_tpu.vsr.storage import TEST_LAYOUT, MemoryStorage

    def build(engine):
        storage = MemoryStorage(TEST_LAYOUT)
        durable = DurableState(storage)
        sm = StateMachine(engine=engine, a_cap=1 << 12, t_cap=1 << 14)
        sm.attach_durable(durable)
        ts = 1000
        accts = [Account(id=i, ledger=1, code=1) for i in range(1, 40)]
        ts += len(accts) + 10
        sm.create_accounts(accts, ts)
        _policy_flush(sm, durable)
        rng = np.random.default_rng(9)
        next_id = 10**7

        def commit(evs):
            nonlocal ts
            payload = b"".join(e.pack() for e in evs)
            ts += len(evs) + 10
            sm.commit(Operation.create_transfers,
                      multi_batch.encode([payload], 128), ts)
            _policy_flush(sm, durable)

        def fast_batch(n):
            nonlocal next_id
            evs = []
            for i in range(n):
                dr = int(rng.integers(1, 40))
                evs.append(Transfer(
                    id=next_id, debit_account_id=dr,
                    credit_account_id=dr % 39 + 1,
                    amount=int(rng.integers(1, 100)), ledger=1, code=1))
                next_id += 1
            commit(evs)

        fast_batch(50)
        # HARD batch: closing flags route to the mirror (hard regime).
        commit([Transfer(id=next_id, debit_account_id=5,
                         credit_account_id=6, amount=1, ledger=1, code=1,
                         flags=int(TransferFlags.closing_debit
                                   | TransferFlags.pending))])
        next_id += 1
        # Fast batches again (regime probe -> fast path resumes).
        for _ in range(10):
            fast_batch(20)
        return durable

    dev = build("device")
    ora = build("oracle")
    for name in dev.forest.trees:
        assert dev.forest.trees[name].memtable_rows() == \
            ora.forest.trees[name].memtable_rows(), f"tree {name} diverged"


def test_cache_invalidated_after_column_flush():
    """Review scenario: a cached account must never serve its pre-chunk
    balance after a column-path flush (cache invalidation contract)."""
    import numpy as np

    from tigerbeetle_tpu import multi_batch
    from tigerbeetle_tpu.state_machine import StateMachine
    from tigerbeetle_tpu.types import Operation
    from tigerbeetle_tpu.vsr.durable import DurableState
    from tigerbeetle_tpu.vsr.storage import TEST_LAYOUT, MemoryStorage

    storage = MemoryStorage(TEST_LAYOUT)
    durable = DurableState(storage)
    sm = StateMachine(engine="device", a_cap=1 << 12, t_cap=1 << 14)
    sm.attach_durable(durable)
    ts = 1000
    sm.create_accounts([Account(id=1, ledger=1, code=1),
                        Account(id=2, ledger=1, code=1)], ts)
    _policy_flush(sm, durable)
    got = sm.lookup_accounts([1])  # caches account 1 (balance 0)
    assert got and got[0].debits_posted == 0
    payload = Transfer(id=10, debit_account_id=1, credit_account_id=2,
                       amount=77, ledger=1, code=1).pack()
    ts += 20
    sm.commit(Operation.create_transfers,
              multi_batch.encode([payload], 128), ts)
    _policy_flush(sm, durable)
    got = sm.lookup_accounts([1])
    assert got and got[0].debits_posted == 77, \
        "stale cached balance after column flush"


# ------------------------- the column flush over accounts held in tables

def _accounts_in_tables(engine: str, per_key_reads: bool = False):
    """1,200 accounts and nine writes of 300 transfers, a bar of beats
    after each, so that the accounts tree's rows move into level-0 and
    then level-1 tables and the column flush reads most of its accounts'
    previous rows from tables. `per_key_reads` gives the accounts tree
    PR 38's read, a `Tree.get` a key, in place of `Tree.get_many`.
    Returns the durable state and, before each write, the accounts
    tree's tables at levels 0 and 1."""
    import numpy as np

    from tigerbeetle_tpu.lsm.tree import BAR_LENGTH

    durable = DurableState(MemoryStorage(TEST_LAYOUT))
    sm = StateMachine(engine=engine, a_cap=1 << 12, t_cap=1 << 14)
    sm.attach_durable(durable)
    accounts = durable.forest.trees["accounts"]
    if per_key_reads:
        def get_many(keys, snapshot=None):
            got = {k: accounts.get(k, snapshot) for k in keys}
            return {k: v for k, v in got.items() if v is not None}
        accounts.get_many = get_many
    n, ts, op = 1200, 1000, 0
    ts += n + 10
    sm.create_accounts([Account(id=i, ledger=1, code=1 + i % 3)
                        for i in range(1, n + 1)], ts)
    _policy_flush(sm, durable)
    rng = np.random.default_rng(39)
    levels = []
    for b in range(9):
        dr = rng.integers(1, n + 1, 300)
        evs = [Transfer(id=10**7 + 300 * b + i, debit_account_id=int(d),
                        credit_account_id=int(d) % n + 1,
                        amount=int(rng.integers(1, 1000)), ledger=1,
                        code=1) for i, d in enumerate(dr)]
        levels.append((len(accounts.levels[0]), len(accounts.levels[1])))
        ts += len(evs) + 10
        sm.commit(Operation.create_transfers,
                  multi_batch.encode([b"".join(e.pack() for e in evs)], 128),
                  ts)
        _policy_flush(sm, durable)
        for _ in range(BAR_LENGTH):
            op += 1
            durable.compact_beat(op)
    return durable, levels


@pytest.fixture(scope="module")
def accounts_in_tables():
    return {"batched": _accounts_in_tables("device"),
            "per_key": _accounts_in_tables("device", per_key_reads=True),
            "oracle": _accounts_in_tables("oracle")}


def _contents(durable) -> dict:
    return {name: tree.scan(bytes(tree.key_size), b"\xff" * tree.key_size)
            for name, tree in durable.forest.trees.items()}


@pytest.mark.parametrize("other", ["per_key", "oracle"])
def test_column_flush_over_accounts_in_tables_writes_the_same_rows(
        accounts_in_tables, other):
    """The column flush that reads its accounts' previous rows by the
    value block writes every tree's rows as the per-key read wrote them
    (PR 38's flush), and as the object path writes them (oracle)."""
    durable, levels = accounts_in_tables["batched"]
    assert any(l0 and l1 for l0, l1 in levels), levels
    assert durable.account_reads["flush_reads_from_tables"] > 0
    assert _contents(durable) == _contents(accounts_in_tables[other][0])


def test_column_flush_counts_what_the_per_key_read_counted(
        accounts_in_tables):
    """The shutdown record's account reads: the keys, the keys from
    tables and the tables probed read as PR 38's per-key loop read them
    on the same state; the blocks searched are fewer than the probes."""
    batched = accounts_in_tables["batched"][0].account_reads
    per_key = accounts_in_tables["per_key"][0].account_reads
    for name in ("flush_reads", "flush_reads_from_tables", "table_probes"):
        assert batched[name] == per_key[name], name
    assert per_key["blocks_searched"] == 0
    assert 0 < batched["blocks_searched"] < batched["table_probes"]
