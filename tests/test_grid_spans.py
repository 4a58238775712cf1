"""What a block costs, where the grid reads, checks and writes it: the
`grid_read` / `grid_verify` spans of a batched read, the trees'
`grid_write`, and the grid's own counters (lsm/grid.py, lsm/tree.py)."""

import struct

import numpy as np
import pytest

import tigerbeetle_tpu.lsm.grid as grid_mod
import tigerbeetle_tpu.lsm.tree as tree_mod
import tigerbeetle_tpu.trace.tracer as tracer_mod
from tigerbeetle_tpu.lsm.forest import Forest
from tigerbeetle_tpu.lsm.grid import Grid, MemoryDevice
from tigerbeetle_tpu.lsm.tree import BAR_LENGTH, Tree
from tigerbeetle_tpu.trace import NullTracer, Tracer

BLOCK = 4096
GRID_SPANS = ("grid_read", "grid_verify", "grid_write")


class _AsyncDevice(MemoryDevice):
    """A memory device with the native engine's read-ahead interface:
    read_submit hands out tokens, read_fetch returns their bytes."""

    def __init__(self, size: int):
        super().__init__(size)
        self._tokens: dict = {}

    def read_submit(self, reqs: list):
        tokens = []
        for off, size in reqs:
            token = len(self._tokens) + 1
            self._tokens[token] = self.read(off, size)
            tokens.append(token)
        return tokens

    def read_fetch(self, token, size: int) -> bytes:
        return self._tokens.pop(token)


def _grid(tracer=None, device=MemoryDevice, **cache):
    return Grid(device(1024 * BLOCK), block_size=BLOCK, block_count=1024,
                tracer=tracer, **cache)


def _spans(tracer, name=None):
    return [e for e in tracer.events if e.get("ph") == "X"
            and (e["name"] == name if name else e["name"] in GRID_SPANS)]


def _identity(grid) -> None:
    assert grid.reads == (grid.cache_hits + grid.readahead_hits
                          + grid.device_reads), grid.held_stats()


def _blocks(grid, n, size=900):
    return [(grid.write_block(bytes([i + 1]) * size), size)
            for i in range(n)]


def test_a_batch_that_misses_opens_one_read_and_one_verify():
    tracer = Tracer()
    grid = _grid(tracer)
    reqs = _blocks(grid, 6)
    assert grid.blocks_written == 6
    # Every block is in the cache (written through): no span at all.
    out = grid.read_blocks(reqs)
    assert [o[:1] for o in out] == [bytes([i + 1]) for i in range(6)]
    assert not _spans(tracer)
    assert (grid.reads, grid.cache_hits, grid.device_reads) == (6, 6, 0)
    _identity(grid)
    # The cache emptied, a batch that asks one block twice: one span of
    # each, over the distinct blocks, and the second asking a cache hit.
    grid.cache.clear()
    out = grid.read_blocks(reqs + reqs[:1])
    assert out[-1] == out[0]
    (read,), (verify,) = _spans(tracer, "grid_read"), \
        _spans(tracer, "grid_verify")
    assert read["args"]["blocks"] == verify["args"]["blocks"] == 6
    assert read["ts"] + read["dur"] <= verify["ts"] + 1e-3
    assert (grid.reads, grid.cache_hits, grid.device_reads) == (13, 7, 6)
    _identity(grid)


def test_single_block_reads_count_but_open_no_span():
    tracer = Tracer()
    grid = _grid(tracer)
    ((address, size),) = _blocks(grid, 1)
    grid.read_block(address, size)
    grid.cache.clear()
    grid.read_block(address, size)
    assert (grid.reads, grid.cache_hits, grid.device_reads) == (2, 1, 1)
    # The scrubber's reads skip the cache and count apart.
    grid.read_block(address, size, bypass_cache=True)
    assert grid.scrub_reads == 1 and grid.reads == 2
    _identity(grid)
    assert not _spans(tracer)


def test_read_ahead_counts_what_was_used_and_what_was_wasted():
    tracer = Tracer()
    grid = _grid(tracer, device=_AsyncDevice)
    reqs = _blocks(grid, 5)
    grid.cache.clear()
    grid.read_block(*reqs[0])  # back in the cache
    before = (grid.reads, grid.cache_hits, grid.cache.hits)
    # The probe of the cached block inside prefetch_async is no read.
    assert grid.prefetch_async(reqs) == 4
    assert (grid.reads, grid.cache_hits) == before[:2]
    assert grid.readahead_submitted == 4
    out = grid.read_blocks(reqs)
    assert [o[:1] for o in out] == [bytes([i + 1]) for i in range(5)]
    assert grid.readahead_hits == 4 and grid.device_reads == 1
    # The read-ahead's collection and its checksums are the batch's
    # device read and verify: one span of each over the four blocks.
    (read,), (verify,) = _spans(tracer, "grid_read"), \
        _spans(tracer, "grid_verify")
    assert read["args"]["blocks"] == verify["args"]["blocks"] == 4
    _identity(grid)
    # A stale read-ahead in a batch: wasted, and the block read again.
    grid.cache.clear()
    grid.prefetch_async(reqs[:1])
    (a0, size), (a1, _) = reqs[0], reqs[1]
    grid._inflight[(a1.checksum << 64) | a1.index] = \
        grid._inflight.pop((a0.checksum << 64) | a0.index)
    before = (grid.readahead_wasted, grid.device_reads)
    assert grid.read_blocks([(a1, size)])[0][:1] == bytes([2])
    assert (grid.readahead_wasted, grid.device_reads) == \
        (before[0] + 1, before[1] + 1)
    _identity(grid)
    # Evicted unread, and failing its checksum (the state a submit then
    # rewrite of the extent leaves): both wasted, and the second block
    # is read again from the device.
    grid.cache.clear()
    wasted = grid.readahead_wasted
    grid.prefetch_inflight_max = 2
    grid.prefetch_async(reqs[:2])
    grid.prefetch_async(reqs[2:3])
    assert grid.readahead_wasted == wasted + 1
    (a2, _), (a3, size) = reqs[2], reqs[3]
    grid._inflight[(a3.checksum << 64) | a3.index] = \
        grid._inflight.pop((a2.checksum << 64) | a2.index)
    device_reads = grid.device_reads
    assert grid.read_block(a3, size)[:1] == bytes([4])
    assert grid.readahead_wasted == wasted + 2
    assert grid.device_reads == device_reads + 1
    _identity(grid)
    stats = grid.held_stats()
    assert {"blocks", "held_peak", "reads", "cache_hits", "readahead_hits",
            "device_reads", "readahead_submitted", "readahead_wasted",
            "blocks_written", "scrub_reads"} <= set(stats)


def _loaded_tree(tracer, bars=7, per_op=40, seed=40):
    """A tree fed random keys a beat over `bars` bars: each bar's freeze
    makes a level-0 table, and past four of them compaction jobs run and
    install. Counts the _advance_flush calls that wrote a block and the
    non-empty write_tables calls."""
    grid = _grid(tracer, cache_sets=2, cache_ways=2)
    tree = Tree(grid, key_size=8, value_size=16, name="t")
    calls = {"flush": 0, "write_tables": 0}
    advance = tree._advance_flush

    def counting_advance(budget):
        written = grid.blocks_written
        advance(budget)
        calls["flush"] += grid.blocks_written > written

    write_tables = tree_mod.write_tables

    def counting_write_tables(grid_, rows, *a, **kw):
        calls["write_tables"] += len(rows) > 0
        return write_tables(grid_, rows, *a, **kw)

    tree._advance_flush = counting_advance
    rng = np.random.default_rng(seed)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(tree_mod, "write_tables", counting_write_tables)
        for op in range(1, bars * BAR_LENGTH + 1):
            for key in rng.integers(0, 5000, per_op).tolist():
                tree.put(struct.pack(">Q", key), struct.pack(">QQ", key, op))
            tree.compact_beat(op)
    return grid, tree, calls


def test_grid_write_opens_once_a_call_that_writes():
    tracer = Tracer()
    grid, tree, calls = _loaded_tree(tracer)
    assert tree.compaction["jobs"] >= 1 and calls["write_tables"] >= 1
    writes = _spans(tracer, "grid_write")
    assert len(writes) == calls["flush"] + calls["write_tables"]
    # Every block the tree wrote lies in exactly one span.
    assert sum(e["args"]["blocks"] for e in writes) == grid.blocks_written
    assert all(e["args"]["blocks"] > 0 for e in writes)


def test_batched_tree_reads_open_a_pair_a_batch_on_the_tracers_clock():
    tracer = Tracer()
    grid, tree, _ = _loaded_tree(tracer, bars=6)
    tracer.events.clear()
    batches = []
    read_blocks = grid.read_blocks

    def counting_read_blocks(reqs):
        before = grid.device_reads
        out = read_blocks(reqs)
        batches.append(grid.device_reads - before)
        return out

    grid.read_blocks = counting_read_blocks
    grid.cache.clear()
    keys = [struct.pack(">Q", k) for k in range(0, 5000, 7)]
    before = tracer.now_ns()
    found = tree.get_many(keys)
    after = tracer.now_ns()
    assert found and all(tree.get(k) == v for k, v in found.items())
    missed = [n for n in batches if n]
    assert missed and len(batches) >= 2
    reads, verifies = _spans(tracer, "grid_read"), \
        _spans(tracer, "grid_verify")
    assert len(reads) == len(verifies) == len(missed)
    assert [e["args"]["blocks"] for e in reads] == missed
    for e in reads + verifies:
        start_ns = e["ts"] * 1000.0 - tracer._epoch_ns
        assert before - 1000 <= start_ns <= after
    _identity(grid)


@pytest.mark.parametrize("block_size,block_count,cache_blocks", [
    (64 * 1024, 8192, 8192),    # under the budget: a slot a block
    (64 * 1024, 16384, 16384),  # at it
    (64 * 1024, 65536, 16384),  # over it: 1 GiB of blocks
    (4096, 1024, 1024),
    (512, 5, 5),                # fewer blocks than a set has ways
])
def test_the_block_cache_holds_the_grid_up_to_a_gib(block_size, block_count,
                                                    cache_blocks):
    assert cache_blocks == min(block_count,
                               grid_mod.GRID_CACHE_BYTES // block_size)
    grid = Grid(None, block_size=block_size, block_count=block_count)
    assert grid.cache.capacity == cache_blocks
    assert grid.held_stats()["cache_blocks"] == cache_blocks
    # A caller that sizes the cache keeps its size.
    sized = Grid(None, block_size=block_size, block_count=block_count,
                 cache_sets=2, cache_ways=3)
    assert (sized.cache.n_sets, sized.cache.ways) == (2, 3)
    assert sized.held_stats()["cache_blocks"] == 6


@pytest.mark.parametrize("cache_sets", [None, 64])
def test_a_grid_cached_whole_reads_and_checks_a_block_once(cache_sets):
    """A tree of about 1,900 blocks, flushed and checkpointed, read twice
    by key: under the default cache (a slot for each of the grid's
    16,384 blocks) the second read is all cache hits and opens no span;
    under the 512 blocks the cache used to hold, it reads and checks its
    blocks again."""
    tracer = Tracer()
    grid = Grid(_AsyncDevice(16384 * 512), block_size=512,
                block_count=16384, cache_sets=cache_sets, tracer=tracer)
    forest = Forest(grid, {"t": (8, 16)})
    tree = forest.trees["t"]
    keys = np.arange(0, 60_000, 3, dtype=">u8")
    tree.put_run(keys.view(np.uint8).reshape(-1, 8),
                 np.repeat(keys, 2).view(np.uint8).reshape(-1, 16))
    for op in range(1, BAR_LENGTH + 1):
        tree.compact_beat(op)  # the bar's flush
    tree.put_run(keys[::2].copy().view(np.uint8).reshape(-1, 8),
                 b"\x01" * 16)
    forest.checkpoint()
    wanted = [struct.pack(">Q", k) for k in keys[::5].tolist()]
    first = tree.get_many(wanted)
    assert len(first) == len(wanted)
    tracer.events.clear()
    before = grid.held_stats()
    assert tree.get_many(wanted) == first
    after = grid.held_stats()
    asked = after["reads"] - before["reads"]
    assert asked > 512
    _identity(grid)
    if cache_sets is None:
        assert grid.cache.capacity == grid.block_count
        assert not _spans(tracer)
        assert after["cache_hits"] - before["cache_hits"] == asked
        for counter in ("device_reads", "readahead_submitted",
                        "cache_evictions"):
            assert after[counter] == before[counter], counter
    else:
        assert _spans(tracer, "grid_read") and _spans(tracer, "grid_verify")
        assert after["cache_evictions"] > before["cache_evictions"]
        assert after["device_reads"] + after["readahead_hits"] > \
            before["device_reads"] + before["readahead_hits"]


class _NoClock:
    def __getattr__(self, name):
        raise AssertionError(f"a grid path read time.{name} with "
                             "tracing off")


def test_under_the_null_tracer_no_grid_path_reads_a_clock(monkeypatch):
    for mod in (grid_mod, tree_mod):
        assert not any(getattr(mod, n, None) is __import__("time")
                       for n in dir(mod)), mod.__name__
    monkeypatch.setattr(tracer_mod, "_time", _NoClock())
    null = NullTracer()
    grid, tree, calls = _loaded_tree(null, bars=6)
    assert grid.tracer is null and calls["flush"] > 0
    grid.cache.clear()
    tree.get_many([struct.pack(">Q", k) for k in range(0, 5000, 3)])
    assert grid.device_reads > 0
    _identity(grid)
