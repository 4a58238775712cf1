"""Unified grid-block header (reference: src/lsm/schema.zig:624): every
grid block self-describes; misdirected or misclassified reads fail
loudly instead of misparsing."""

import pytest

from tigerbeetle_tpu.lsm.forest import Forest
from tigerbeetle_tpu.lsm.grid import Grid, MemoryDevice
from tigerbeetle_tpu.lsm.schema import (
    BLOCK_HEADER_SIZE,
    BlockKind,
    classify,
    unwrap,
    wrap,
)


def _forest():
    grid = Grid(MemoryDevice(8192 * 512), block_size=8192, block_count=512)
    return Forest(grid, {"a": (8, 16), "b": (8, 16)}), grid


def test_wrap_unwrap_roundtrip_and_kind_check():
    payload = b"\x07" * 100
    raw = wrap(BlockKind.value, payload, tree_id=5)
    assert len(raw) == BLOCK_HEADER_SIZE + 100
    assert unwrap(raw, BlockKind.value) == payload
    assert classify(raw) == (BlockKind.value, 5, 100)
    with pytest.raises(ValueError, match="kind"):
        unwrap(raw, BlockKind.index)
    with pytest.raises(ValueError, match="magic"):
        unwrap(b"\x00" * 64, BlockKind.value)


def test_every_grid_block_is_classifiable():
    """After real tree activity + a checkpoint, every allocated block
    carries a valid header with the right kind and tree id."""
    forest, grid = _forest()
    tree_a = forest.trees["a"]
    for i in range(3000):
        tree_a.put(i.to_bytes(8, "big"), bytes(16))
    for op in range(1, 97):
        forest.compact_beat(op)
    forest.checkpoint()
    kinds = set()
    seen_tree_ids = set()
    for index, free in enumerate(grid.free):
        if free:
            continue
        raw = grid.device.read(index * grid.block_size, grid.block_size)
        got = classify(raw)
        assert got is not None, f"block {index} carries no valid header"
        kind, tree_id, _ = got
        kinds.add(kind)
        seen_tree_ids.add(tree_id)
    assert BlockKind.value in kinds and BlockKind.index in kinds
    assert BlockKind.manifest in kinds
    assert 1 in seen_tree_ids  # tree "a" (sorted-name id 1)


def test_misdirected_block_read_fails_loudly():
    """A valid VALUE block served where an INDEX block is expected (the
    misdirected-write shape) must raise, not misparse."""
    import numpy as np

    from tigerbeetle_tpu.lsm.table import Table, TableInfo, write_value_block

    forest, grid = _forest()
    row = np.frombuffer(b"k" * 8 + b"v" * 16, dtype=np.uint8).reshape(1, 24)
    addr, size, _first = write_value_block(grid, row, 8, tree_id=1)
    info = TableInfo(index_address=addr, index_size=size,
                     key_min=b"k" * 8, key_max=b"k" * 8, entry_count=1)
    with pytest.raises(ValueError, match="kind"):
        Table(grid, info, 8, 16)
