"""The grid's bytes are pinned: the checkpoint root and the grid zone
after three fixed op sequences, on the device engine (column flush) and
on the oracle engine (object flush), hash to digests recorded from the
tree as it stood before the memtable learned to take column runs.
Replicas repair each other's blocks by these bytes, so a faster flush
has to write the same ones.

reference analogs: storage determinism (storage_checker.zig:55 —
byte-identical checkpoints across replicas)."""

import functools
import hashlib

import numpy as np
import pytest

from tests.test_durable import _policy_flush
from tigerbeetle_tpu import multi_batch
from tigerbeetle_tpu.state_machine import StateMachine
from tigerbeetle_tpu.types import (Account, AccountFlags, Operation, Transfer,
                                   TransferFlags)
from tigerbeetle_tpu.vsr.durable import DurableState
from tigerbeetle_tpu.vsr.storage import TEST_LAYOUT, MemoryStorage

ACCOUNTS = 40
CHECKPOINT_INTERVAL = 16
PENDING = int(TransferFlags.pending)
POST = int(TransferFlags.post_pending_transfer)
VOID = int(TransferFlags.void_pending_transfer)


def _plain(ops: int = 130, per_op: int = 60):
    """Plain transfers between random pairs; every indexed field varies,
    ids and user data end in NUL bytes."""
    rng = np.random.default_rng(5)
    next_id = 1 << 40
    for k in range(ops):
        evs = []
        for i in range(per_op):
            dr = int(rng.integers(1, ACCOUNTS + 1))
            evs.append(Transfer(
                id=next_id << 8, debit_account_id=dr,
                credit_account_id=dr % ACCOUNTS + 1,
                amount=int(rng.integers(1, 1000)), ledger=1, code=1 + i % 3,
                user_data_128=(1 << 100) + (i << 16), user_data_64=k % 7,
                user_data_32=i % 5))
            next_id += 1
        yield evs


def _two_phase(ops: int = 130, per_op: int = 24):
    """Pendings with and without a timeout, posts and voids of the
    previous op's pendings, one pending posted inside its own op."""
    for k in range(ops):
        base = 10_000 + k * 1_000
        evs = []
        for j in range(per_op):
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
            elif j == per_op - 1:
                evs.append(Transfer(
                    id=base + j, pending_id=base + 18, amount=3,
                    ledger=1, code=1, flags=POST))
            else:
                evs.append(Transfer(
                    id=base + j, debit_account_id=dr, credit_account_id=cr,
                    amount=1 + j, ledger=1, code=1, user_data_64=k,
                    user_data_32=j))
        yield evs


def _hard_interleave(ops: int = 130, per_op: int = 20):
    """Fast batches with a closing pending transfer every 23rd op, voided
    two ops later. DRAINED_BEFORE_FLUSH has the mirror drained before
    that op's flush, as a hard batch in the same prepare drains it: the
    chunk lies over the watermark and the object path puts it, between
    ops that the column path puts."""
    rng = np.random.default_rng(9)
    next_id = 10**7
    closing = None
    for k in range(ops):
        if k % 23 == 11:
            closing = next_id
            next_id += 1
            yield [Transfer(
                id=closing, debit_account_id=5, credit_account_id=6,
                amount=1, ledger=1, code=1,
                flags=int(TransferFlags.closing_debit) | PENDING)]
            continue
        if closing is not None and k % 23 == 13:
            yield [Transfer(id=next_id, pending_id=closing, ledger=1,
                            code=1, flags=VOID)]
            next_id += 1
            closing = None
            continue
        evs = []
        for _ in range(per_op):
            dr = int(rng.integers(7, ACCOUNTS + 1))
            evs.append(Transfer(
                id=next_id, debit_account_id=dr,
                credit_account_id=7 + (dr - 6) % (ACCOUNTS - 6),
                amount=int(rng.integers(1, 100)), ledger=1, code=1))
            next_id += 1
        yield evs


SEQUENCES = {"plain": _plain, "two_phase": _two_phase,
             "hard_interleave": _hard_interleave}
DRAINED_BEFORE_FLUSH = {"hard_interleave": lambda op: op % 23 in (12, 13)}

# sha256 of (checkpoint root, grid zone) after each sequence, recorded on
# the parent of the change that introduced Tree.put_run. Both engines
# write the same bytes, so one pair a sequence.
PINNED = {
    "plain": (
        "e53573b9b9f32edd8ef7998e7b208a3defc49d1ee7b831bffbf579c10cc36e1b",
        "b0762ba186f1063cf52e715bffa2d65d60eeedb4c8412973735ca24d049644f6"),
    "two_phase": (
        "8d899bb9c431fc1d01d2df3d10a9b02da54a60d80f9a7aa4af9037f4615f098c",
        "3c732612ee1ab3da08a4aa642ed0b139430c34c0b84bb153d705c9483c203d62"),
    "hard_interleave": (
        "692f324a20f8b299e3f1d6a06ce0edc413064cd12698764e5a17ef58d71f9615",
        "5b2feefcec513af70541c82ccc64c235b8a982bcf83fb4a6ec36b2750dabaecc"),
}


@functools.lru_cache(maxsize=None)
def run_sequence(engine: str, sequence: str):
    """(sha256 of the last checkpoint root, sha256 of the grid zone,
    DurableState) after the sequence: a flush and a beat an op, a
    checkpoint every 16 ops, as the replica paces them."""
    storage = MemoryStorage(TEST_LAYOUT)
    durable = DurableState(storage)
    sm = StateMachine(engine=engine, a_cap=1 << 9, t_cap=1 << 13)
    sm.attach_durable(durable)
    ts = 1000 + ACCOUNTS + 10
    sm.create_accounts(
        [Account(id=i, ledger=1, code=1,
                 flags=int(AccountFlags.history) if i % 4 == 0 else 0)
         for i in range(1, ACCOUNTS + 1)], ts)
    _policy_flush(sm, durable)
    root = b""
    drained = DRAINED_BEFORE_FLUSH.get(sequence, lambda op: False)
    for op, evs in enumerate(SEQUENCES[sequence](), start=1):
        ts += len(evs) + 10
        sm.commit(Operation.create_transfers, multi_batch.encode(
            [b"".join(e.pack() for e in evs)], 128), ts)
        if drained(op):
            sm.state
        _policy_flush(sm, durable)
        durable.compact_beat(op)
        if op % CHECKPOINT_INTERVAL == 0:
            state = sm.state  # drains the mirror, as Replica._checkpoint
            if sm.led is not None:
                sm.led.take_flush_columns()
            root = durable.checkpoint(state, op=op)
    layout = storage.layout
    grid = storage.read("grid", 0,
                        layout.grid_block_count * layout.grid_block_size)
    return (hashlib.sha256(root).hexdigest(),
            hashlib.sha256(grid).hexdigest(), durable)


@pytest.mark.parametrize("engine", ["device", "oracle"])
@pytest.mark.parametrize("sequence", sorted(SEQUENCES))
def test_grid_bytes_are_the_pinned_ones(sequence, engine):
    root_sha, grid_sha, durable = run_sequence(engine, sequence)
    assert durable.forest.depth_stats()["deepest_level"] >= 1, \
        "the sequence must reach a compaction"
    assert (root_sha, grid_sha) == PINNED[sequence]


@pytest.mark.parametrize("sequence", sorted(SEQUENCES))
def test_device_and_oracle_engines_write_the_same_bytes(sequence):
    """The column path (runs) and the object path (a put a key) leave
    the same root and the same grid."""
    assert run_sequence("device", sequence)[:2] == \
        run_sequence("oracle", sequence)[:2]


# Trees the column flush reads by key, per sequence: a post or a void
# looks its pending transfer up by timestamp, then by id.
FOLDING_TREES = {"plain": set(), "two_phase": {"transfers", "xfer_by_ts"},
                 "hard_interleave": {"transfers", "xfer_by_ts"}}


@pytest.mark.parametrize("sequence", sorted(SEQUENCES))
def test_column_rows_enter_as_runs_and_only_trees_that_are_read_fold(
        sequence):
    durable = run_sequence("device", sequence)[2]
    rows = durable.rows_put
    assert rows["run"] == rows["column"] > 0
    folded = {name: tree.memtable.rows_folded
              for name, tree in durable.forest.trees.items()
              if tree.memtable.rows_folded}
    assert set(folded) == FOLDING_TREES[sequence]
    assert rows["folded"] == sum(folded.values())
    # A folded run pays per key once: never more than it put.
    assert all(n <= rows["column"] for n in folded.values())
    oracle_rows = run_sequence("oracle", sequence)[2].rows_put
    assert oracle_rows["run"] == oracle_rows["folded"] == 0
