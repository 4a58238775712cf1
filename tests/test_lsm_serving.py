"""LSM-served reads with a bounded object cache (VERDICT r1 #4).

reference: src/lsm/groove.zig:885 (get through the object cache),
:996/:1339 (prefetch), src/lsm/set_associative_cache.zig:1. The serving
read path (attach_durable) must (a) return exactly what the host-index
path returns, (b) hit the LSM on cache miss, (c) bound its memory by
construction even when the data set far exceeds the cache.
"""

import numpy as np
import pytest

from tigerbeetle_tpu.lsm.cache_map import ObjectCache
from tigerbeetle_tpu.lsm.grid import Grid, MemoryDevice
from tigerbeetle_tpu.lsm.tree import BAR_LENGTH, Tree
from tigerbeetle_tpu.state_machine import StateMachine
from tigerbeetle_tpu.types import (
    Account,
    AccountFilter,
    AccountFilterFlags,
    ChangeEventsFilter,
    QueryFilter,
    Transfer,
    TransferFlags,
)
from tigerbeetle_tpu.vsr.durable import DurableState
from tigerbeetle_tpu.vsr.storage import TEST_LAYOUT, MemoryStorage


class TestObjectCache:
    def test_bounded_with_lru_eviction(self):
        cache = ObjectCache(sets=8, ways=2)  # capacity 16
        for k in range(100):
            cache.put(k, k * 10)
        assert len(cache) <= cache.capacity == 16
        assert cache.evictions >= 100 - 16
        # A re-put of an existing key updates in place (no eviction).
        before = cache.evictions
        live = [k for k in range(100) if cache.get(k) is not None]
        for k in live:
            cache.put(k, k * 11)
        assert cache.evictions == before
        assert all(cache.get(k) == k * 11 for k in live)

    def test_lru_within_set(self):
        cache = ObjectCache(sets=1, ways=2)
        cache.put(1, "a")
        cache.put(2, "b")
        assert cache.get(1) == "a"  # touch 1: now 2 is LRU
        cache.put(3, "c")  # evicts 2
        assert cache.get(2) is None
        assert cache.get(1) == "a" and cache.get(3) == "c"

    def test_eviction_never_loses_an_update_through_the_lsm(self):
        """The no-stash substitution's load-bearing property (see
        cache_map.py docstring): an updated-then-evicted entry must be
        re-readable with its NEW value from the layer below — updates
        only enter the cache after the durable flush, so eviction can
        never lose one (reference keeps a stash for its mid-bar window,
        src/lsm/cache_map.zig:1-40)."""
        attached, detached, _durable = _mk_attached(
            n_accounts=300, cache_sets=4, ways=2)  # capacity 8 << 300
        ids = list(range(1, 301))
        # Read a large working set (heavy eviction churn)...
        first = attached.lookup_accounts(ids)
        assert attached._acct_cache.evictions > 0
        # ...then verify every account STILL reads back with the values
        # the detached twin holds (each miss refills from the LSM;
        # nothing was lost or staled by eviction).
        again = attached.lookup_accounts(ids[:64])
        truth = detached.lookup_accounts(ids[:64])
        for got, want in zip(again, truth):
            assert got.debits_posted == want.debits_posted
            assert got.credits_posted == want.credits_posted
        assert len(first) == 300


def _mk_attached(n_accounts=300, n_transfers=2000, cache_sets=8, ways=2):
    """A durable-attached state machine with data far exceeding the
    object caches (capacity 16 each), plus an identical detached twin."""
    rng = np.random.default_rng(5)
    storage = MemoryStorage(TEST_LAYOUT)
    durable = DurableState(storage)
    attached = StateMachine(engine="oracle")
    attached.attach_durable(durable, cache_sets=cache_sets, ways=ways)
    detached = StateMachine(engine="oracle")

    accts = [Account(id=i, ledger=1, code=1 + i % 3,
                     user_data_64=i % 7)
             for i in range(1, n_accounts + 1)]
    ts = 10**9
    for sm in (attached, detached):
        sm.create_accounts(accts, ts)
    pend = int(TransferFlags.pending)
    evs = []
    for i in range(n_transfers):
        evs.append(Transfer(
            id=10**6 + i,
            debit_account_id=int(rng.integers(1, n_accounts + 1)),
            credit_account_id=int(rng.integers(1, n_accounts + 1)),
            amount=int(rng.integers(1, 100)), ledger=1,
            code=1 + i % 3, user_data_64=i % 5,
            flags=pend if i % 11 == 0 else 0))
    for e in evs:
        if e.debit_account_id == e.credit_account_id:
            e.credit_account_id = e.debit_account_id % n_accounts + 1
    flushed = durable.flush(attached.state)
    attached.cache_upsert(*flushed)
    for lo in range(0, n_transfers, 500):
        chunk = evs[lo:lo + 500]
        ts += 600
        for sm in (attached, detached):
            sm.create_transfers(chunk, ts)
        # The replica flushes + cache-upserts after every commit.
        flushed = durable.flush(attached.state)
        attached.cache_upsert(*flushed)
    return attached, detached, durable


class TestLsmServing:
    def test_reads_differential_and_bounded(self):
        attached, detached, _durable = _mk_attached()
        # Lookups: data set (300 + 2000 objects) >> cache capacity (16).
        ids = list(range(1, 301))
        got = attached.lookup_accounts(ids)
        want = detached.lookup_accounts(ids)
        assert got == want
        assert len(attached._acct_cache) <= attached._acct_cache.capacity
        assert attached._acct_cache.misses > 0, "must have hit the LSM"
        tids = [10**6 + i for i in range(0, 2000, 7)]
        assert attached.lookup_transfers(tids) == \
            detached.lookup_transfers(tids)
        assert len(attached._xfer_cache) <= attached._xfer_cache.capacity

        # Queries route through ForestQuery — exactly the host results.
        f = AccountFilter(
            account_id=17,
            flags=int(AccountFilterFlags.debits | AccountFilterFlags.credits),
            limit=8190)
        assert [t.id for t in attached.get_account_transfers(f)] == \
               [t.id for t in detached.get_account_transfers(f)]
        q = QueryFilter(code=2, user_data_64=3, limit=200)
        assert [t.id for t in attached.query_transfers(q)] == \
               [t.id for t in detached.query_transfers(q)]
        qa = QueryFilter(user_data_64=4, limit=100)
        assert [a.id for a in attached.query_accounts(qa)] == \
               [a.id for a in detached.query_accounts(qa)]
        ce = ChangeEventsFilter(limit=50)
        assert attached.get_change_events(ce) == \
            detached.get_change_events(ce)

    def test_cache_written_through_on_flush(self):
        """A cached account updated by a later batch must serve the NEW
        balances after the flush upsert (the groove write-through
        discipline: no read-side invalidation logic)."""
        attached, detached, durable = _mk_attached(
            n_accounts=10, n_transfers=0)
        a1 = attached.lookup_accounts([1])[0]  # warm the cache
        assert a1.debits_posted == 0
        ts = 10**10
        t = [Transfer(id=5_000_000, debit_account_id=1,
                      credit_account_id=2, amount=42, ledger=1, code=1)]
        for sm in (attached, detached):
            sm.create_transfers(t, ts)
        # Before the flush+upsert the cached copy is the pre-update value.
        stale = attached.lookup_accounts([1])[0]
        assert stale.debits_posted == 0
        flushed = durable.flush(attached.state)
        assert 1 in flushed[0] and 5_000_000 in flushed[1]
        attached.cache_upsert(*flushed)
        fresh = attached.lookup_accounts([1])[0]
        assert fresh.debits_posted == 42
        assert fresh == detached.lookup_accounts([1])[0]
        assert attached.lookup_transfers([5_000_000]) == \
            detached.lookup_transfers([5_000_000])


class TestBatchedPrefetch:
    def test_cold_misses_fan_out_in_few_rounds(self):
        """A cold batch of lookups must reach the device as a few batched
        fan-outs (one per level round), never one synchronous read per id
        (reference: prefetch fan-out, src/lsm/groove.zig:996,1339)."""
        attached, detached, durable = _mk_attached()
        # Pace full compaction bars so the memtables stream into L0+
        # tables — the cold path must resolve from BLOCKS, not host dicts.
        for op in range(1, 129):
            durable.compact_beat(op)
        attached._acct_cache.clear()
        attached._xfer_cache.clear()
        # A cold block cache forces device reads.
        durable.grid.cache = type(durable.grid.cache)()
        dev = durable.grid.device
        seen = {"rounds": 0, "reads": 0}
        orig_rb = dev.read_batch

        def counting_rb(reqs):
            seen["rounds"] += 1
            seen["reads"] += len(reqs)
            return orig_rb(reqs)

        dev.read_batch = counting_rb
        try:
            tids = [10**6 + i for i in range(0, 1000, 2)]
            got = attached.lookup_transfers(tids)
        finally:
            dev.read_batch = orig_rb
        assert [t.id for t in got] == tids
        assert seen["reads"] >= 10, "cold batch must actually hit the device"
        # 500 cold ids, but only a handful of fan-out rounds (levels x
        # candidate rounds), not 500 point reads.
        assert seen["rounds"] <= 10, seen

    def test_get_many_matches_get(self):
        """Tree.get_many == {k: Tree.get(k)} across memtable, immutable,
        levels, and tombstones."""
        attached, detached, durable = _mk_attached()
        for op in range(1, 97):  # flush part of the data into tables
            durable.compact_beat(op)
        tree = durable.forest.trees["transfers"]
        assert any(lv.live for lv in tree.levels), \
            "setup must produce table-resident data"
        keys = [(10**6 + i).to_bytes(16, "big") for i in range(0, 2000, 3)]
        keys += [(5).to_bytes(16, "big")]  # absent id
        batched = tree.get_many(keys)
        for k in keys:
            single = tree.get(k)
            assert batched.get(k) == single, k.hex()


# ------------------------------------------- reads by the value block

def _key(i: int) -> bytes:
    return i.to_bytes(16, "big")


class _History:
    """A tree whose keys lie in every place a point read looks: level-1
    tables, overlapping level-0 tables that hold some keys at several
    ages, tombstones over older live rows, the frozen memtable while its
    flush is in flight, and the mutable memtable. 8 KiB blocks hold 255
    rows of 16 + 16 bytes."""

    BASE = range(10_000, 18_000, 2)  # age 0: 4,000 rows, 16 blocks
    HOT = range(10_000, 10_100, 2)   # rewritten at every age
    FAR = range(50_000, 50_400, 4)   # a second range

    def __init__(self):
        grid = Grid(MemoryDevice(1024 * 8192), block_size=8192,
                    block_count=1024)
        self.tree = tree = Tree(grid, key_size=16, value_size=16)
        rng = np.random.default_rng(39)
        self.op = 0
        base = np.array(self.BASE)
        # Age 1 holds the second range alone: it moves down to level 1
        # beside age 0's table, not into it.
        for age in range(10):
            keys = {0: self.BASE, 1: self.FAR}.get(age) or [
                *self.HOT, *rng.choice(base, 600, replace=False).tolist()]
            if age >= 4:
                keys = [*keys, *self.FAR]
            for i in keys:
                tree.put(_key(i), age.to_bytes(2, "big") * 8)
            if age > 1:
                for i in rng.choice(base, 40, replace=False).tolist():
                    tree.remove(_key(i))
            self.snapshot_a_bar_ago = self.op
            for _ in range(BAR_LENGTH):  # the last beat freezes
                self.op += 1
                tree.compact_beat(self.op)
        for i in rng.choice(base, 50, replace=False).tolist():
            tree.put(_key(i), b"m" * 16)
        tree.remove(_key(self.BASE[1]))
        assert len(tree.levels[0]) >= 2 and len(tree.levels[1]) >= 2
        assert tree._flush is not None and tree._flush.snapshot == self.op
        assert tree.memtable

    def snapshot(self, name: str):
        return {"latest": None, "before_freeze": self.op - 1,
                "at_freeze": self.op,
                "a_bar_ago": self.snapshot_a_bar_ago}[name]

    def keys(self, name: str) -> list:
        tree = self.tree
        if name == "every_row":  # hundreds of keys a block
            return [_key(i) for i in range(9_990, 18_010)] + [
                _key(i) for i in self.FAR]
        if name == "one_a_block":
            return [k for level in tree.levels[:2] for t in level
                    for k in t.block_first_keys]
        if name == "outside":  # below, between and above every range
            return [_key(i) for i in (
                *range(0, 20), 10_001, 12_345, 17_999, 18_000, 30_000,
                49_999, 50_001, 50_397, 50_401, 10**12)] + [
                b"\x00" * 16, b"\xff" * 16]
        assert name == "unsorted_repeated"
        rng = np.random.default_rng(7)
        return [_key(int(i)) for i in rng.integers(9_990, 50_500, 3_000)]


@pytest.fixture(scope="module")
def history():
    return _History()


def _per_key(tree, keys, snapshot) -> dict:
    out = {}
    for k in dict.fromkeys(keys):
        v = tree.get(k, snapshot)
        if v is not None:
            out[k] = v
    return out


@pytest.mark.parametrize("keys", ["every_row", "one_a_block", "outside",
                                  "unsorted_repeated"])
@pytest.mark.parametrize("snapshot", ["latest", "before_freeze",
                                      "at_freeze", "a_bar_ago"])
def test_get_many_by_block_matches_get(history, keys, snapshot):
    """A read by the value block answers what a `get` a key answers, at
    every snapshot, wherever the keys lie and however many share a
    block."""
    name, keys, snap = keys, history.keys(keys), history.snapshot(snapshot)
    want = _per_key(history.tree, keys, snap)
    assert history.tree.get_many(keys, snap) == want
    # No table holds a key outside the ranges; the others find rows.
    assert (want == {}) == (name == "outside")


def _pairs(tree, keys, snapshot) -> set:
    """The (table, block) pairs `get` probes for the keys no memtable
    holds, newest table first until a table holds the key."""
    pairs = set()
    frozen = tree._frozen(snapshot)
    for k in dict.fromkeys(keys):
        if (snapshot is None and tree.memtable.get(k) is not None) or (
                frozen is not None and frozen.get(k) is not None):
            continue
        for level in tree.levels:
            tables = level.lookup(k, snapshot)
            for t in tables:
                pairs.add((id(t), t.block_for(k)[0].index))
                if t.get(k) is not None:
                    break
            else:
                continue
            break
    return pairs


@pytest.mark.parametrize("snapshot", ["latest", "at_freeze"])
@pytest.mark.parametrize("keys", ["every_row", "unsorted_repeated"])
def test_get_many_counts_what_a_get_a_key_counts(history, keys, snapshot):
    """`keys_from_tables` and `table_probes` read what PR 38's loop of
    `get` read over the same keys; `blocks_searched` counts each
    distinct (table, block) pair those probes touched, once: at least
    the pairs, at most the probes."""
    tree = history.tree
    keys, snap = history.keys(keys), history.snapshot(snapshot)
    before = (tree.keys_from_tables, tree.table_probes, tree.blocks_searched)
    tree.get_many(keys, snap)
    from_tables, probes, searched = (
        tree.keys_from_tables - before[0], tree.table_probes - before[1],
        tree.blocks_searched - before[2])
    before = (tree.keys_from_tables, tree.table_probes)
    _per_key(tree, keys, snap)
    assert (from_tables, probes) == (tree.keys_from_tables - before[0],
                                     tree.table_probes - before[1])
    assert probes > 0
    pairs = _pairs(tree, keys, snap)
    assert len(pairs) <= searched <= probes
    assert searched == len(pairs)
