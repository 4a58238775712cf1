"""LSM engine tests: tables, tree semantics across compactions, forest
checkpoint/restore, and byte-determinism of the grid."""

import random
import struct

import pytest

from tigerbeetle_tpu.lsm.grid import Grid, MemoryDevice
from tigerbeetle_tpu.lsm.table import Table, release_table, write_table
from tigerbeetle_tpu.lsm.tree import BAR_LENGTH, Tree
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


class TestTable:
    def test_write_read_multiblock(self):
        grid = _grid(block_size=4096)
        entries = [(k(i), v(i)) for i in range(2000)]  # ~12 value blocks
        info = write_table(grid, entries, KEY, VAL)
        table = Table(grid, info, KEY, VAL)
        assert len(table.block_addresses) > 1
        assert table.get(k(0)) == v(0)
        assert table.get(k(1999)) == v(1999)
        assert table.get(k(777)) == v(777)
        assert table.get(k(5000)) is None
        assert list(table.iter_entries()) == entries

    def test_corruption_detected(self):
        grid = _grid()
        info = write_table(grid, [(k(1), v(1))], KEY, VAL)
        grid.device.data[info.index_address.index * grid.block_size] ^= 0xFF
        grid.cache.clear()  # cold read (a warm cache legitimately serves
        # the immutable copy; detection is the media-read path's job)
        with pytest.raises(IOError):
            Table(grid, info, KEY, VAL)
        # The scrubber's bypass path detects it even through a warm cache.
        info2 = write_table(grid, [(k(2), v(2))], KEY, VAL)
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
        # Each mid-bar beat merges at most the per-beat budget (+1 slack).
        merged_before = len(job.merged)
        progressed = False
        while tree._jobs and op % BAR_LENGTH != BAR_LENGTH - 1:
            op += 1
            tree.compact_beat(op)
            if tree._jobs:
                now = len(tree._jobs[0].merged)
                assert now - merged_before <= budget + 1
                progressed = progressed or now > merged_before
                merged_before = now
        assert progressed or not tree._jobs
        # By the bar's drain beat every scheduled job has installed (the
        # NEXT bar boundary may legitimately schedule fresh jobs).
        while True:
            op += 1
            tree.compact_beat(op)
            if op % BAR_LENGTH == BAR_LENGTH - 1:
                break
        assert not tree._jobs

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
