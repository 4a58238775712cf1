"""LSM engine tests: tables, tree semantics across compactions, forest
checkpoint/restore, and byte-determinism of the grid."""

import hashlib
import random
import struct

import numpy as np
import pytest

from tigerbeetle_tpu.lsm.grid import Grid, MemoryDevice
from tigerbeetle_tpu.lsm.table import (Table, release_table, write_table,
                                       write_tables)
from tigerbeetle_tpu.lsm.tree import BAR_LENGTH, LSM_LEVELS, Tree
from tigerbeetle_tpu.lsm.forest import Forest

KEY = 8
VAL = 16


def _grid(blocks=4096, block_size=4096):
    return Grid(MemoryDevice(blocks * block_size), block_size=block_size,
                block_count=blocks)


def k(i):
    return struct.pack(">Q", i)  # big-endian: numeric order == bytes order


def v(i):
    return struct.pack(">QQ", i, i * 7)


def _rows(entries):
    """A sorted (key, value) list as the rows the table writers take:
    uint8[n, key_size + value_size], each row `key || value`."""
    return np.frombuffer(b"".join(k + v for k, v in entries),
                         dtype=np.uint8).reshape(len(entries), -1)


class TestTable:
    def test_write_read_multiblock(self):
        grid = _grid(block_size=4096)
        entries = [(k(i), v(i)) for i in range(2000)]  # ~12 value blocks
        info = write_table(grid, _rows(entries), KEY, VAL)
        table = Table(grid, info, KEY, VAL)
        assert len(table.block_addresses) > 1
        assert table.get(k(0)) == v(0)
        assert table.get(k(1999)) == v(1999)
        assert table.get(k(777)) == v(777)
        assert table.get(k(5000)) is None
        assert list(table.iter_entries()) == entries
        # The same table a block at a time, as the rows it was written
        # from: what a compaction job reads.
        blocks = [table.block_rows(i)
                  for i in range(len(table.block_addresses))]
        assert all(b.dtype == np.uint8 and b.shape[1] == KEY + VAL
                   and not b.flags.writeable for b in blocks)
        assert np.array_equal(np.concatenate(blocks), _rows(entries))

    def test_corruption_detected(self):
        grid = _grid()
        info = write_table(grid, _rows([(k(1), v(1))]), KEY, VAL)
        grid.device.data[info.index_address.index * grid.block_size] ^= 0xFF
        grid.cache.clear()  # cold read (a warm cache legitimately serves
        # the immutable copy; detection is the media-read path's job)
        with pytest.raises(IOError):
            Table(grid, info, KEY, VAL)
        # The scrubber's bypass path detects it even through a warm cache.
        info2 = write_table(grid, _rows([(k(2), v(2))]), KEY, VAL)
        grid.device.data[info2.index_address.index * grid.block_size] ^= 0xFF
        with pytest.raises(IOError):
            grid.read_block(info2.index_address, info2.index_size,
                            bypass_cache=True)
        # While the serving path still reads the cached immutable copy.
        assert grid.read_block(info2.index_address, info2.index_size)


class TestTree:
    def test_put_get_overwrite_remove_across_flushes(self):
        tree = Tree(_grid(), key_size=KEY, value_size=VAL)
        model = {}
        rng = random.Random(3)
        for i in range(2000):
            key = rng.randrange(300)
            if rng.random() < 0.15:
                tree.remove(k(key))
                model.pop(k(key), None)
            else:
                tree.put(k(key), v(i))
                model[k(key)] = v(i)
            tree.compact_beat()
        for key in range(300):
            assert tree.get(k(key)) == model.get(k(key)), key
        got = tree.scan(k(0), k(299))
        assert got == sorted(model.items())
        # Deep levels actually formed.
        assert sum(len(lv) for lv in tree.levels[1:]) > 0

    def test_scan_range(self):
        tree = Tree(_grid(), key_size=KEY, value_size=VAL)
        for i in range(100):
            tree.put(k(i), v(i))
            tree.compact_beat()
        tree.flush_memtable()
        assert [kk for kk, _ in tree.scan(k(10), k(19))] == [
            k(i) for i in range(10, 20)]


class TestForest:
    SCHEMA = {"accounts": (KEY, VAL), "transfers": (KEY, VAL)}

    def test_checkpoint_reopen(self):
        grid = _grid()
        forest = Forest(grid, self.SCHEMA)
        for i in range(200):
            forest.trees["accounts"].put(k(i), v(i))
            forest.trees["transfers"].put(k(1000 + i), v(i))
            forest.compact_beat()
        root = forest.checkpoint()

        # Re-open over the same device bytes.
        grid2 = Grid(grid.device, block_size=grid.block_size,
                     block_count=grid.block_count)
        forest2 = Forest(grid2, self.SCHEMA)
        forest2.open(root)
        for i in range(200):
            assert forest2.trees["accounts"].get(k(i)) == v(i)
            assert forest2.trees["transfers"].get(k(1000 + i)) == v(i)
        # Free set restored: allocations continue without clobbering data.
        for i in range(200, 260):
            forest2.trees["accounts"].put(k(i), v(i))
            forest2.compact_beat()
        forest2.trees["accounts"].flush_memtable()
        assert forest2.trees["accounts"].get(k(0)) == v(0)
        assert forest2.trees["accounts"].get(k(259)) == v(259)

    def test_checkpoint_discards_pending_frees_until_flip(self):
        grid = _grid(blocks=256)
        forest = Forest(grid, {"t": (KEY, VAL)})
        tree = forest.trees["t"]
        for i in range(600):
            tree.put(k(i % 50), v(i))
            tree.compact_beat()
        free_before = sum(grid.free)
        assert grid.freed_pending  # compactions released blocks
        forest.checkpoint()
        assert not grid.freed_pending
        assert sum(grid.free) >= free_before  # frees landed at the flip


def test_grid_byte_determinism():
    """Same op sequence => byte-identical device contents (the property
    replica repair relies on; reference: docs/ARCHITECTURE.md:281-307)."""

    def run():
        grid = _grid(blocks=512)
        forest = Forest(grid, {"a": (KEY, VAL), "b": (KEY, VAL)})
        rng = random.Random(42)
        for i in range(1500):
            tree = forest.trees["a" if rng.random() < 0.7 else "b"]
            key = rng.randrange(200)
            if rng.random() < 0.1:
                tree.remove(k(key))
            else:
                tree.put(k(key), v(i))
            forest.compact_beat()
        root = forest.checkpoint()
        return bytes(grid.device.data), root

    bytes1, root1 = run()
    bytes2, root2 = run()
    assert root1 == root2
    assert bytes1 == bytes2


class TestIncrementalCompaction:
    """VERDICT r1 #5: compaction work must spread across the bar's beats
    (no stop-the-world at bar boundaries), stay deterministic in the op
    sequence, and never expose partial grid state mid-bar."""

    def _loaded_tree(self, n_bars=8, per_bar=200):
        from tigerbeetle_tpu.lsm.tree import BAR_LENGTH, Tree

        grid = _grid()
        tree = Tree(grid, key_size=8, value_size=16, name="t")
        op = 0
        for bar in range(n_bars):
            for beat in range(BAR_LENGTH):
                op += 1
                k = (bar * BAR_LENGTH + beat) % per_bar
                tree.put(k.to_bytes(8, "big"), op.to_bytes(16, "big"))
                tree.compact_beat(op)
        return tree, op

    def test_work_spreads_across_beats(self):
        from tigerbeetle_tpu.lsm.tree import BAR_LENGTH

        tree, op = self._loaded_tree()
        # Force an over-budget L0 so the next bar schedules a job.
        while not tree._jobs:
            op += 1
            tree.put(b"\xff" * 8, op.to_bytes(16, "big"))
            tree.compact_beat(op)
            if op > 10_000:
                raise AssertionError("no job ever scheduled")
        job = tree._jobs[0]
        budget = tree._per_beat
        assert budget * (BAR_LENGTH - 1) >= job.total
        # Each mid-bar beat consumes at most the per-beat budget of
        # input rows (+1 slack).
        consumed_before = tree.compaction["rows_in"]
        progressed = False
        while tree._jobs and op % BAR_LENGTH != BAR_LENGTH - 1:
            op += 1
            tree.compact_beat(op)
            now = tree.compaction["rows_in"]
            assert now - consumed_before <= budget + 1
            progressed = progressed or now > consumed_before
            consumed_before = now
        assert progressed or not tree._jobs
        # By the bar's drain beat every scheduled job has installed (the
        # NEXT bar boundary may legitimately schedule fresh jobs).
        while True:
            op += 1
            tree.compact_beat(op)
            if op % BAR_LENGTH == BAR_LENGTH - 1:
                break
        assert not tree._jobs
        assert tree.compaction["jobs"] >= 1
        assert tree.compaction["rows_in"] >= job.total

    def test_reads_consistent_while_job_in_flight(self):
        tree, op = self._loaded_tree(n_bars=6)
        # Capture ground truth, then advance into a bar with live jobs and
        # verify every key still reads its newest value at every beat.
        want = {k: tree.get(k.to_bytes(8, "big")) for k in range(200)}
        from tigerbeetle_tpu.lsm.tree import BAR_LENGTH

        for _ in range(2 * BAR_LENGTH):
            op += 1
            tree.compact_beat(op)
            for k in (0, 57, 130, 199):
                assert tree.get(k.to_bytes(8, "big")) == \
                    want[k], (k, op)

    def test_deterministic_vs_oneshot_replay(self):
        """Two trees fed the identical op sequence (one with a mid-run
        manifest pack/restore, i.e. a checkpoint+restart) end with the
        identical manifest — physical determinism survives the
        incremental pacing."""
        from tigerbeetle_tpu.lsm.tree import BAR_LENGTH

        def run(checkpoint_at, restart):
            from tigerbeetle_tpu.lsm.tree import Tree

            tree = Tree(_grid(), key_size=8, value_size=16, name="t")
            for op in range(1, 6 * BAR_LENGTH + 1):
                k = op % 100
                tree.put(k.to_bytes(8, "big"), op.to_bytes(16, "big"))
                tree.compact_beat(op)
                if op == checkpoint_at:
                    # Every replica checkpoints at the same op (the
                    # manifest pack flushes the memtable mid-bar on all
                    # of them identically).
                    raw = tree.manifest_pack()
                    if restart:
                        tree.manifest_restore(raw)
            return tree.manifest_pack()

        # Checkpoint-and-continue vs checkpoint-crash-restart-replay must
        # converge to the identical manifest — at a bar boundary AND
        # mid-bar while compaction jobs are in flight (the manifest
        # persists the job plans, so the restored tree resumes the same
        # merges and installs them at the same beat).
        for ckpt in (4 * BAR_LENGTH, 4 * BAR_LENGTH + 3,
                     4 * BAR_LENGTH + 17, 4 * BAR_LENGTH + 30):
            cont = run(ckpt, restart=False)
            rest = run(ckpt, restart=True)
            assert cont == rest, ckpt


def _merge_through_a_job(ks, vs, old, new, *, budget, level=0,
                         old_tables=3, block_size=4096):
    """`old` ({key: value}) as `old_tables` disjoint tables of level
    `level` + 1, `new` as the one table of `level`; one compaction job
    over them advanced `budget` input rows a call (None: drained in
    one), then installed. Returns the tree, the rows of the tables the
    job wrote, and the rows each call consumed."""
    grid = _grid(blocks=1024, block_size=block_size)
    tree = Tree(grid, key_size=ks, value_size=vs)

    def install(lvl, entries):
        (info,) = write_tables(grid, _rows(entries), ks, vs)
        table = Table(grid, info, ks, vs)
        tree.levels[lvl].insert(table, snapshot=0)
        return table

    entries = sorted(old.items())
    step = -(-len(entries) // old_tables) if entries else 1
    overlapping = [install(level + 1, entries[i:i + step])
                   for i in range(0, len(entries), step)]
    table = install(level, sorted(new.items()))
    tree.beat = 1
    job = tree._new_job(level, table, overlapping)
    assert job.total == len(old) + len(new)
    tree._jobs = [job]
    steps = []
    while tree._jobs:
        before = tree.compaction["rows_in"]
        tree._advance_jobs(budget)
        steps.append(tree.compaction["rows_in"] - before)
    assert len(tree.levels[level]) == 0
    out = [pair for t in tree.levels[level + 1] for pair in t.iter_entries()]
    return tree, out, steps


def _random_keys(rng, ks, n):
    """n distinct keys of ks bytes over an alphabet that tells `bytes`
    order from any other: 0x00 and 0xff, bytes either side of the sign
    bit, and keys that differ only in their last byte or only past the
    eighth (the second word of a key wider than 8)."""
    alphabet = bytes([0x00, 0x01, 0x7F, 0x80, 0xFF])
    keys = set()
    while len(keys) < n:
        key = bytes(rng.choice(alphabet) for _ in range(ks))
        keys.add(key)
        keys.add(key[:-1] + bytes([rng.choice(alphabet)]))
        keys.add(key[:8] + bytes(rng.choice(alphabet)
                                 for _ in range(ks - 8)))
    return rng.sample(sorted(keys), n)


class TestColumnarMerge:
    """The compaction job on rows (numpy, no pair and no dict between
    the input block and the output block) against the plain merge it
    replaced: the old tables into a dict, the new table over them,
    sorted."""

    @pytest.mark.parametrize("budget", [1, 7, None])
    @pytest.mark.parametrize("vs", [1, 128])
    @pytest.mark.parametrize("ks", [8, 9, 12, 24])
    def test_equals_a_dict_merge(self, ks, vs, budget):
        rng = random.Random(ks * 1000 + vs)
        keys = _random_keys(rng, ks, 400)
        dead = b"\xff" * vs

        def value():
            return dead if rng.random() < 0.1 else \
                bytes(rng.randrange(255) for _ in range(vs))

        old = {key: value() for key in keys[:300]}
        # A third of the new table's keys are in the old ones too.
        new = {key: value() for key in keys[260:]}
        tree, out, steps = _merge_through_a_job(ks, vs, old, new,
                                                budget=budget)
        # Above the last level a tombstone is a row like any other.
        assert out == sorted({**old, **new}.items())
        assert dead in dict(out).values()
        if budget is not None:
            assert all(used == budget for used in steps[:-1])
            assert steps[-1] < budget
        assert sum(steps) == len(old) + len(new)
        assert tree.compaction == {
            "jobs": 1, "rows_in": len(old) + len(new), "rows_out": len(out),
            "passed_sorted": tree.compaction["passed_sorted"]}
        assert tree.compaction["passed_sorted"] < sum(steps)

    @pytest.mark.parametrize("budget", [1, 7, None])
    @pytest.mark.parametrize("where, all_pass", [
        ("below", True), ("above", True), ("inside", False),
        ("into_nothing", True)])
    def test_ranges_that_do_not_interleave_pass_as_they_stand(
            self, where, all_pass, budget):
        """The new table wholly below, above, or in a gap of the old
        ones, or compacting into an empty range (a tree keyed by
        timestamp): no beat merges anything but the few that straddle
        the gap's edges."""
        old = {k(i): v(i) for i in [*range(100, 150), *range(300, 350)]}
        if where == "into_nothing":
            old = {}
        first = {"below": 0, "above": 400, "inside": 200,
                 "into_nothing": 0}[where]
        new = {k(i): v(i + 1) for i in range(first, first + 60)}
        tree, out, steps = _merge_through_a_job(KEY, VAL, old, new,
                                                budget=budget)
        assert out == sorted({**old, **new}.items())
        stats = tree.compaction
        assert stats["rows_in"] == sum(steps) == len(old) + len(new)
        assert stats["rows_out"] == len(out)
        if all_pass or budget == 1:  # a row against a row: no range
            assert stats["passed_sorted"] == stats["rows_in"]
        elif budget is None:
            assert stats["passed_sorted"] == 0  # one merge of everything
        else:
            assert 0 < stats["passed_sorted"] < stats["rows_in"]

    @pytest.mark.parametrize("budget", [1, 2, 4, 5, None])
    def test_a_key_in_both_that_straddles_a_beats_cut_comes_out_once(
            self, budget):
        """Budget 4 ends a beat on the old row of key 3 and starts the
        next on the new one; the others cut around it."""
        old = {k(i): v(i) for i in range(10)}
        new = {k(3): v(333), k(7): v(777), k(9): v(999)}
        _, out, steps = _merge_through_a_job(KEY, VAL, old, new,
                                             budget=budget, old_tables=1)
        assert out == sorted({**old, **new}.items())
        assert [key for key, _ in out] == [k(i) for i in range(10)]
        assert sum(steps) == 13

    @pytest.mark.parametrize("budget", [7, None])
    @pytest.mark.parametrize("level, dropped", [
        (LSM_LEVELS - 3, False), (LSM_LEVELS - 2, True)])
    def test_tombstones_die_at_the_last_level_only(self, level, dropped,
                                                   budget):
        dead = b"\xff" * VAL
        old = {k(i): v(i) for i in range(0, 200, 2)}
        old.update({k(i): dead for i in range(0, 200, 10)})
        new = {k(i): v(i + 1) for i in range(100, 300, 3)}
        new.update({k(i): dead for i in range(100, 300, 9)})
        # A value that starts like a tombstone and is none.
        new[k(299)] = b"\xff" * (VAL - 1) + b"\x00"
        tree, out, _ = _merge_through_a_job(KEY, VAL, old, new, level=level,
                                            budget=budget)
        want = sorted({**old, **new}.items())
        assert dead in dict(want).values()
        if dropped:
            want = [(key, val) for key, val in want if val != dead]
        assert out == want
        assert tree.compaction["rows_out"] == len(want)

    @pytest.mark.parametrize("budget", [7, None])
    def test_an_output_past_one_tables_capacity_splits(self, budget):
        from tigerbeetle_tpu.lsm.table import table_entry_max

        old = {k(i): v(i) for i in range(0, 1200, 2)}
        new = {k(i): v(i + 1) for i in range(1, 400, 2)}
        tree, out, _ = _merge_through_a_job(KEY, VAL, old, new,
                                            budget=budget, block_size=512)
        cap = table_entry_max(tree.grid, KEY, VAL)
        tables = list(tree.levels[1])
        assert [t.info.entry_count for t in tables] == \
            [cap] * (800 // cap) + [800 % cap] and len(tables) >= 3
        assert out == sorted({**old, **new}.items())
        for t in tables:
            rows = list(t.iter_entries())
            assert (t.info.key_min, t.info.key_max) == \
                (rows[0][0], rows[-1][0])

    def test_a_job_with_nothing_left_writes_no_table(self):
        dead = b"\xff" * VAL
        tree, out, _ = _merge_through_a_job(
            KEY, VAL, {k(1): v(1)}, {k(1): dead}, level=LSM_LEVELS - 2,
            budget=None, old_tables=1)
        assert out == [] and len(tree.levels[LSM_LEVELS - 1]) == 0
        assert tree.compaction["rows_in"] == 2
        assert tree.compaction["rows_out"] == 0


def _forest_run(restart_at=None, checkpoint_at=7 * BAR_LENGTH + 5,
                ops=9 * BAR_LENGTH - 1):
    """Two trees (8 + 16 with keys overwritten, 12 + 1 keyed by a
    prefix and a counter, as an index tree is) fed a fixed op
    sequence, a forest checkpoint at `checkpoint_at` (inside a bar,
    both trees' jobs in flight and part merged) and, with `restart_at`
    equal to it, a restart from that checkpoint: a new grid over the
    same device bytes, `Forest.open`. It ends on a bar's last beat,
    every job installed. Returns the forest, the last root, and the
    jobs' progress (rows merged so far) at the checkpoint."""
    schema = {"t": (8, 16), "u": (12, 1)}
    grid = _grid()
    forest = Forest(grid, schema)
    progress = None
    for op in range(1, ops + 1):
        for i in range(40):
            key = (op * 7919 + i * 104729) % 5000
            forest.trees["t"].put(key.to_bytes(8, "big"),
                                  op.to_bytes(16, "big"))
            forest.trees["u"].put(
                (key % 7).to_bytes(4, "big")
                + (op * 40 + i).to_bytes(8, "big"), b"\x00")
        forest.compact_beat(op)
        if op == checkpoint_at:
            progress = [(len(t._jobs), sum(j.rows_out for j in t._jobs))
                        for t in forest.trees.values()]
            root = forest.checkpoint()
            if restart_at == op:
                grid = Grid(grid.device, block_size=grid.block_size,
                            block_count=grid.block_count)
                forest = Forest(grid, schema)
                forest.open(root)
    return forest, forest.checkpoint(), progress


def _logical(forest):
    """Per tree and level: every live table's entry count, key range
    and rows, in the level's order."""
    return {name: [[(t.info.entry_count, t.info.key_min, t.info.key_max,
                     list(t.iter_entries())) for t in level]
                   for level in tree.levels]
            for name, tree in forest.trees.items()}


def _sha(raw) -> str:
    return hashlib.sha256(bytes(raw)).hexdigest()


class TestRestartInsideABar:
    """A checkpoint inside a bar persists the job plans
    (`manifest_pack`); a restart from it rebuilds the jobs in their
    row form from the same bytes and merges again from zero."""

    # sha256 of the last root and of the device, taken on the parent of
    # the PR that gave the job its row form (e1bb21e, a dict merge a
    # pair at a time) by this file's `_forest_run`: what `manifest_pack`
    # persists and what the grid holds did not change by a byte, with
    # the restart or without. (The two differ from each other, in the
    # parent as here: a restored job reserves its blocks anew from the
    # checkpoint's free set, so its tables take other addresses.)
    PARENT_DIGESTS = {
        False: (
            "30d9d44d007e9a6aadf41b2692ad30aa12b5d0af4b3a599a4ed999e49734ddf1",
            "7f9cd2d82c12b9318a3c57e42aece863374f96e7d58277169a543ee0700de6f5"),
        True: (
            "e4fbf9bc55d6b30f609b8149af7fe6b5823d27a92e7e7161dcaddf5c2077f269",
            "26d6c6662d9f4ce14c98e2afe92894773f0185a579365031af64073917cf18be"),
    }

    def test_a_restarted_forest_holds_what_one_that_ran_on_holds(self):
        cont, _, progress = _forest_run()
        rest, _, _ = _forest_run(restart_at=7 * BAR_LENGTH + 5)
        # The checkpoint met a job of each tree part merged.
        assert [n for n, _ in progress] == [1, 1]
        assert all(rows > 0 for _, rows in progress)
        assert _logical(cont) == _logical(rest)
        for name in cont.trees:
            assert not cont.trees[name]._jobs and not rest.trees[name]._jobs
            assert len(cont.trees[name].levels[1]) >= 1
        # The counters are not persisted: the restarted forest counts
        # the jobs it installed since, the one it merged again among
        # them.
        assert 0 < rest.trees["t"].compaction["jobs"] \
            < cont.trees["t"].compaction["jobs"]

    @pytest.mark.parametrize("restart", [False, True])
    def test_root_and_grid_bytes_are_the_parents(self, restart):
        at = 7 * BAR_LENGTH + 5
        forest, root, _ = _forest_run(restart_at=at if restart else None)
        assert (_sha(root), _sha(forest.grid.device.data)) \
            == self.PARENT_DIGESTS[restart]


class TestMemtableSplit:
    """Mutable/immutable memtable pair (reference: tree.zig:543 swap +
    table_memory.zig): the frozen memtable stays readable while its flush
    job streams it into level-0 tables across the bar's beats."""

    def test_frozen_rows_readable_while_flush_in_flight(self):
        grid = _grid()
        tree = Tree(grid, key_size=8, value_size=16, name="t")
        op = 0
        for i in range(300):
            tree.put(k(i), v(i))
        op += 32
        tree.compact_beat(op)  # bar boundary: freeze, do NOT drain yet
        # Mid-freeze: rows must come from the immutable map (L0 not yet
        # fully installed) and reads must be exact on every beat.
        saw_pending_flush = tree._flush is not None
        for beat in range(1, 32):
            for i in range(0, 300, 37):
                assert tree.get(k(i)) == v(i), (beat, i)
            assert dict(tree.scan(k(0), k(299)))[k(123)] == v(123)
            op += 1
            tree.compact_beat(op)
        assert saw_pending_flush, "freeze must defer the write-out"
        assert tree._flush is None \
            and not tree.memtable_rows(frozen=True)
        assert len(tree.levels[0]) >= 1
        # New puts during the flight went to the NEW mutable memtable.
        tree.put(k(1), v(9999))
        assert tree.get(k(1)) == v(9999)

    def test_flush_work_spreads_across_beats(self):
        grid = _grid()
        tree = Tree(grid, key_size=8, value_size=16, name="t")
        op = 0
        for i in range(2000):
            tree.put(k(i), v(i))
        op += 32
        tree.compact_beat(op)
        job = tree._flush
        assert job is not None
        budget = tree._flush_per_beat
        last = job.pos
        while tree._flush is not None and op % 32 != 31:
            op += 1
            tree.compact_beat(op)
            if tree._flush is not None:
                # Whole value blocks: progress per beat bounded by the
                # budget rounded up to the block size.
                per_block = max(1, (grid.block_size - 4) // 24)
                assert tree._flush.pos - last <= budget + per_block
                last = tree._flush.pos
        # Fully installed by the drain beat at the latest.
        while op % 32 != 31:
            op += 1
            tree.compact_beat(op)
        assert tree._flush is None
        for i in range(0, 2000, 97):
            assert tree.get(k(i)) == v(i)

    def test_snapshot_reads_stable_across_flush_install(self):
        """A snapshot taken while the flush is in flight must answer
        identically before and after the tables install (the frozen rows
        are logically table-visible from the freeze op on)."""
        grid = _grid()
        tree = Tree(grid, key_size=8, value_size=16, name="t")
        for i in range(500):
            tree.put(k(i), v(i))
        tree.compact_beat(32)  # freeze; flush streams over the bar
        assert tree._flush is not None
        s = 33
        before = tree.get(k(123), snapshot=s)
        scan_before = dict(tree.scan(k(100), k(130), snapshot=s))
        for op in range(33, 64):
            tree.compact_beat(op)
        assert tree._flush is None  # installed
        assert tree.get(k(123), snapshot=s) == before == v(123)
        assert dict(tree.scan(k(100), k(130), snapshot=s)) == scan_before
        # A snapshot BEFORE the freeze still excludes those rows.
        assert tree.get(k(123), snapshot=31) is None
