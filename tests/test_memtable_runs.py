"""Tree.put_run: a column of rows put whole is the same writes as a put
per row. Every case feeds one script of writes to two trees — one a key
at a time (`put` / `remove`), one with each run handed over whole — and
holds them to the same reads (mutable, frozen and in flight, at a
snapshot before and after the freeze op), the same grid bytes and the
same checkpoint root.

reference analogs: table_memory.zig (the mutable table sorts once, when
it turns immutable), storage determinism (byte-identical grids)."""

import numpy as np
import pytest

from tigerbeetle_tpu.lsm.forest import Forest
from tigerbeetle_tpu.lsm.grid import Grid, MemoryDevice
from tigerbeetle_tpu.lsm.memtable import Memtable
from tigerbeetle_tpu.lsm.table import TOMBSTONE, table_entry_max
from tigerbeetle_tpu.lsm.tree import BAR_LENGTH

BLOCK = 8 * 1024  # the simulator's layout: tables split at `cap` soonest
ONE = b"\x01"


def _forest(key_size, value_size, blocks=1024):
    grid = Grid(MemoryDevice(blocks * BLOCK), block_size=BLOCK,
                block_count=blocks)
    return Forest(grid, {"t": (key_size, value_size)})


def key(i: int, size: int = 8) -> bytes:
    return i.to_bytes(size, "big")


def val(i: int, size: int = 16) -> bytes:
    return ((i * 7 + 1) % (1 << 8 * size)).to_bytes(size, "little")


def run(ids, key_size=8, value_size=16, one=False):
    """("run", keys, values): values None means the one value b"\\x01"."""
    return ("run", [key(i, key_size) for i in ids],
            None if one else [val(i, value_size) for i in ids])


def _random_script(seed: int, key_size=8, value_size=16):
    rng = np.random.default_rng(seed)
    script = []
    for _ in range(12):
        kind = int(rng.integers(0, 4))
        ids = rng.integers(0, 400, int(rng.integers(1, 120))).tolist()
        if kind == 0:
            script += [("put", key(i, key_size), val(i + 1000, value_size))
                       for i in ids[:20]]
        elif kind == 1:
            script += [("remove", key(i, key_size)) for i in ids[:10]]
        else:
            keys = [key(i, key_size) for i in ids]
            # A key may repeat inside a run: its last row wins.
            script.append(("run", keys, [
                val(int(rng.integers(0, 1 << 30)), value_size)
                for _ in ids]))
    return script


# name -> (key_size, value_size, writes before the freeze, writes after)
CASES = {
    "run_then_put_of_the_same_key": (8, 16, [
        run(range(50)), ("put", key(7), val(700)),
        ("put", key(900), val(900))], []),
    "put_then_run_of_the_same_key": (8, 16, [
        ("put", key(7), val(700)), ("put", key(900), val(900)),
        run(range(50))], []),
    "tombstone_before_a_run": (8, 16, [
        run(range(20)), ("remove", key(5)), ("remove", key(99)),
        run([5, 6])], []),
    "tombstone_after_a_run": (8, 16, [
        ("put", key(5), val(1)), run(range(20)), ("remove", key(5)),
        ("remove", key(6))], []),
    "a_key_twice_in_one_run_and_in_two_runs": (8, 16, [
        ("run", [key(3), key(4), key(3)], [val(1), val(2), val(3)]),
        ("run", [key(4), key(9)], [val(40), val(90)])], []),
    # ids and timestamps do end in NUL bytes; a value may be all NULs.
    "keys_and_values_that_end_in_nul_bytes": (16, 16, [
        ("run", [key(i << 64, 16) for i in range(1, 40)],
         [bytes(16) if i % 3 == 0 else (i << 120).to_bytes(16, "big")
          for i in range(1, 40)]),
        ("put", key(5 << 64, 16), bytes(15) + b"\x07"),
        ("run", [bytes(16)], [bytes(16)])], []),
    "an_index_tree_of_one_value": (24, 1, [
        run(range(0, 300, 3), 24, 1, one=True),
        ("remove", key(6, 24)),
        run(range(0, 300, 5), 24, 1, one=True)], []),
    # 9, 10 and 12 byte keys: the sort pads the last word.
    "a_9_byte_key": (9, 1, [
        run([1 << 64 | i for i in (5, 3, 260, 4)] + [2, 1 << 63], 9, 1,
            one=True), ("put", key(3, 9), ONE)], []),
    "a_10_byte_key": (10, 1, [
        run([i << 72 | 255 - i for i in range(64)] + [7, 1 << 8], 10, 1,
            one=True)], []),
    "a_12_byte_key": (12, 1, [
        run([(i % 5) << 64 | 1000 - i for i in range(200)], 12, 1,
            one=True), ("remove", key(1 << 64 | 999, 12))], []),
    "writes_after_the_freeze_shadow_the_frozen_rows": (8, 16, [
        run(range(100)), ("put", key(200), val(200))], [
        ("run", [key(5), key(300)], [val(55), val(300)]),
        ("remove", key(6)), ("put", key(7), val(77)),
        ("run", [key(7)], [val(78)])]),
    "only_puts": (8, 16, [("put", key(i), val(i)) for i in range(64)],
                  [("put", key(1), val(2))]),
    **{f"random_script_{seed}": (8, 16, _random_script(seed),
                                 _random_script(seed + 100))
       for seed in (1, 2, 3)},
    "random_script_of_an_index_tree": (12, 1, _random_script(4, 12, 1),
                                       _random_script(5, 12, 1)),
}


def _apply(tree, script, whole_runs: bool) -> None:
    for step in script:
        if step[0] == "put":
            tree.put(step[1], step[2])
        elif step[0] == "remove":
            tree.remove(step[1])
        else:
            _, keys, values = step
            if not whole_runs:
                for i, k in enumerate(keys):
                    tree.put(k, ONE if values is None else values[i])
            elif values is None:
                tree.put_run(b"".join(keys), ONE)
            else:
                tree.put_run(
                    np.frombuffer(b"".join(keys), dtype=np.uint8).reshape(
                        len(keys), tree.key_size),
                    np.frombuffer(b"".join(values), dtype=np.uint8).reshape(
                        len(keys), tree.value_size))


def _keys_of(script) -> list:
    keys = []
    for step in script:
        keys += step[1] if step[0] == "run" else [step[1]]
    return list(dict.fromkeys(keys))


def _assert_same_reads(by_key, by_run, keys, snapshots) -> None:
    size = by_key.key_size
    probes = keys + [bytes(size), b"\xff" * size, key(123456789, size)]
    lo, hi = bytes(size), b"\xff" * size
    mid = sorted(keys)[len(keys) // 2]
    for snapshot in snapshots:
        for k in probes:
            assert by_run.get(k, snapshot) == by_key.get(k, snapshot), \
                (k, snapshot)
        assert by_run.get_many(probes, snapshot) == \
            by_key.get_many(probes, snapshot), snapshot
        for key_min, key_max in ((lo, hi), (mid, hi), (lo, mid),
                                 (mid, mid)):
            assert by_run.scan(key_min, key_max, snapshot) == \
                by_key.scan(key_min, key_max, snapshot), snapshot


@pytest.mark.parametrize("case", sorted(CASES))
def test_put_run_is_the_same_writes_as_a_put_per_row(case):
    key_size, value_size, before, after = CASES[case]
    # Rows enough, beside the case's own, to keep the flush in flight for
    # a few beats (a small memtable is one block, written at the freeze).
    before = [run(range(10 ** 6, 10 ** 6 + 4000), key_size, value_size,
                  one=value_size == 1)] + before
    forests = [_forest(key_size, value_size) for _ in range(2)]
    by_key, by_run = (f.trees["t"] for f in forests)
    keys = _keys_of(before + after)
    _apply(by_key, before, whole_runs=False)
    _apply(by_run, before, whole_runs=True)
    # Nothing has read the run-fed tree by key: it folded nothing, and
    # its logical contents are the dict-fed tree's, tombstones included.
    assert by_run.memtable.rows_folded == 0
    assert by_run.memtable_rows() == by_key.memtable_rows()
    assert by_run.memtable_rows()  # every case writes something
    _assert_same_reads(by_key, by_run, keys, [None])
    # Freeze at the bar's first beat; the flush is in flight.
    op = BAR_LENGTH
    for tree in (by_key, by_run):
        tree.compact_beat(op)
        assert tree._flush is not None and tree._flush.snapshot == op
    assert by_run.memtable_rows(frozen=True) == \
        by_key.memtable_rows(frozen=True)
    assert not by_run.memtable_rows()
    _apply(by_key, after, whole_runs=False)
    _apply(by_run, after, whole_runs=True)
    for beat in range(1, BAR_LENGTH):
        if beat in (1, 2, BAR_LENGTH // 2):
            _assert_same_reads(by_key, by_run, keys, [None, op - 1, op])
        op += 1
        for tree in (by_key, by_run):
            tree.compact_beat(op)
    assert by_key._flush is None and by_run._flush is None
    _assert_same_reads(by_key, by_run, keys, [None, BAR_LENGTH - 1, op])
    roots = [f.checkpoint() for f in forests]
    assert roots[0] == roots[1]
    assert forests[0].grid.device.data == forests[1].grid.device.data
    _assert_same_reads(by_key, by_run, keys, [None])


def test_a_run_splits_into_tables_at_cap_on_the_8k_layout():
    """More rows than one table's index holds: the frozen run splits
    where the dict-fed tree's does, block for block."""
    key_size, value_size = 24, 1
    forests = [_forest(key_size, value_size) for _ in range(2)]
    by_key, by_run = (f.trees["t"] for f in forests)
    cap = table_entry_max(forests[0].grid, key_size, value_size)
    n = cap + 1000
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 256, (n, key_size), dtype=np.uint8)
    keys[:, 16:] = np.arange(n, dtype=">u8").view(np.uint8).reshape(n, 8)
    raw = keys.tobytes()
    for p in range(0, len(raw), key_size):
        by_key.put(raw[p:p + key_size], ONE)
    by_run.put_run(keys[:n // 2], ONE)
    by_run.put_run(keys[n // 2:].tobytes(), ONE)
    for tree in (by_key, by_run):
        tree.flush_memtable()
        assert [t.info.entry_count for t in tree.levels[0]] == [cap, 1000]
    assert by_run.levels[0][0].info == by_key.levels[0][0].info
    assert by_run.levels[0][1].info == by_key.levels[0][1].info
    assert forests[0].grid.device.data == forests[1].grid.device.data
    probe = raw[:key_size]
    assert by_run.get(probe) == by_key.get(probe) == ONE
    assert by_run.memtable.rows_folded == 0


def test_the_bar_paces_a_columnar_flush_job():
    """The frozen run streams out a budget of whole blocks a beat; its
    tables install at the job's end, and every beat leaves the grid the
    dict-fed tree's."""
    forests = [_forest(8, 16) for _ in range(2)]
    by_key, by_run = (f.trees["t"] for f in forests)
    n = 20_000
    for i in range(n):
        by_key.put(key(i * 3), val(i))
    ids = np.arange(n, dtype=np.uint64) * np.uint64(3)
    by_run.put_run(
        ids.astype(">u8").view(np.uint8).reshape(n, 8),
        np.frombuffer(b"".join(val(i) for i in range(n)),
                      dtype=np.uint8).reshape(n, 16))
    op = BAR_LENGTH
    for tree in (by_key, by_run):
        tree.compact_beat(op)
    assert by_run._flush_per_beat == by_key._flush_per_beat == \
        -(-n // (BAR_LENGTH - 1))
    assert len(by_run._flush.entries) == n
    positions = []
    while by_run._flush is not None:
        assert by_run._flush.pos == by_key._flush.pos
        assert len(by_run.levels[0]) == 0, "tables install at the end"
        assert forests[0].grid.device.data == forests[1].grid.device.data
        assert by_run.get(key(3 * (n - 1))) == val(n - 1)
        positions.append(by_run._flush.pos)
        op += 1
        for tree in (by_key, by_run):
            tree.compact_beat(op)
    assert len(positions) > 20
    steps = [b - a for a, b in zip(positions, positions[1:])]
    assert min(steps) >= by_run._flush_per_beat  # whole blocks, >= budget
    assert op < 2 * BAR_LENGTH, "the bar's last beat drains the rest"
    assert by_key._flush is None and len(by_run.levels[0]) == 1
    assert forests[0].grid.device.data == forests[1].grid.device.data


def test_a_reserve_failure_leaves_the_tree_unchanged():
    """The reservation comes before the swap: a full grid refuses the
    freeze, and the memtable still holds every run and answers reads."""
    forest = _forest(8, 16, blocks=4)
    tree = forest.trees["t"]
    n = 4000  # 12 value blocks and an index: more than the grid has
    ids = np.arange(n, dtype=np.uint64)
    tree.put_run(ids.astype(">u8").view(np.uint8).reshape(n, 8),
                 np.full((n, 16), 9, dtype=np.uint8))
    tree.put(key(1), val(1))
    before = tree.memtable_rows()
    free = list(forest.grid.free)
    with pytest.raises(RuntimeError, match="cannot reserve"):
        tree.compact_beat(BAR_LENGTH)
    assert tree._flush is None and not tree.memtable_rows(frozen=True)
    assert tree.memtable_rows() == before and len(before) == n
    assert list(forest.grid.free) == free
    assert tree.memtable.rows_folded == 0  # the accessor reads, not folds
    assert tree.get(key(1)) == val(1) and tree.get(key(2)) == b"\x09" * 16
    assert tree.memtable.rows_folded == n


def test_only_a_tree_that_is_read_pays_per_key():
    """`rows_folded` counts the rows of runs that a read by key or range
    folded into the dict; a tree nobody reads folds none, whatever it
    froze and flushed."""
    forest = _forest(8, 16)
    tree = forest.trees["t"]
    _apply(tree, [run(range(100)), run(range(100, 150))], whole_runs=True)
    tree.flush_memtable()
    assert tree.memtable.rows_folded == 0
    _apply(tree, [run(range(200, 260))], whole_runs=True)
    assert tree.get(key(3)) == val(3)  # from the table; the run folds
    assert tree.memtable.rows_folded == 60
    assert tree.get(key(201)) == val(201)
    tree.put(key(5), val(5))  # a dict write: nothing to fold
    assert tree.scan(key(0), key(10 ** 6))
    assert tree.memtable.rows_folded == 60
    _apply(tree, [run(range(300, 310))], whole_runs=True)
    assert len(tree.scan(key(300), key(400))) == 10
    assert tree.memtable.rows_folded == 70
    # A snapshot read sees tables only: it folds nothing.
    _apply(tree, [run(range(400, 405))], whole_runs=True)
    assert tree.get(key(400), snapshot=tree.beat) is None
    assert tree.memtable.rows_folded == 70


def test_a_caller_can_fold_ahead_of_its_reads():
    """`fold()` is what the next read by key would do first, for the
    flush that times it under a span of its own (`memtable_fold`):
    `pending_runs` says whether there is anything to fold, the rows
    count once, and the reads that follow find the same values."""
    tree = _forest(8, 16).trees["t"]
    assert tree.memtable.pending_runs == 0
    tree.memtable.fold()  # nothing waits: nothing happens
    assert tree.memtable.rows_folded == 0
    tree.put(key(1), val(7))
    _apply(tree, [run(range(40)), run(range(30, 60))], whole_runs=True)
    assert tree.memtable.pending_runs == 2
    tree.memtable.fold()
    assert tree.memtable.pending_runs == 0
    assert tree.memtable.rows_folded == 70
    assert [tree.get(key(i)) for i in (1, 35, 59)] == \
        [val(1), val(35), val(59)]
    assert tree.memtable.rows_folded == 70  # the reads folded nothing more
    assert len(tree.scan(key(0), key(10 ** 6))) == 60


@pytest.mark.parametrize("key_size,value_size", [(8, 16), (9, 1), (24, 1)])
def test_the_frozen_run_answers_by_binary_search(key_size, value_size):
    """A memtable that held runs freezes without a dict: point reads and
    ranges over the sorted rows are exact at every boundary."""
    rng = np.random.default_rng(key_size)
    n = 500
    keys = rng.integers(0, 4, (n, key_size), dtype=np.uint8)  # many ties
    values = rng.integers(0, 256, (n, value_size), dtype=np.uint8)
    table = Memtable(key_size, value_size)
    table.put_run(keys, values)
    want = {k.tobytes(): v.tobytes() for k, v in zip(keys, values)}
    frozen = table.freeze()
    assert frozen.lookup is None and len(frozen) == len(want)
    ordered = sorted(want.items())
    assert frozen.between(bytes(key_size), b"\xff" * key_size) == ordered
    assert [frozen.key(i) for i in range(len(frozen))] == \
        [k for k, _ in ordered]
    for k, v in ordered[::7]:
        assert frozen.get(k) == v
    probes = [bytes(key_size), b"\xff" * key_size, b"\x02" * key_size,
              ordered[0][0], ordered[-1][0]]
    for lo in probes:
        assert frozen.get(lo) == want.get(lo)
        for hi in probes:
            assert frozen.between(lo, hi) == \
                [(k, v) for k, v in ordered if lo <= k <= hi]


def test_a_tombstone_run_deletes():
    """A run may carry tombstones: the value is the tree's to read."""
    forest = _forest(8, 1)
    tree = forest.trees["t"]
    _apply(tree, [run(range(10), 8, 1, one=True)], whole_runs=True)
    tree.flush_memtable()
    tree.put_run(b"".join(key(i) for i in (2, 3)), TOMBSTONE)
    assert tree.get(key(2)) is None and tree.get(key(4)) == ONE
    tree.flush_memtable()
    assert [k for k, _ in tree.scan(key(0), key(9))] == \
        [key(i) for i in range(10) if i not in (2, 3)]
