"""LSM storage engine (reference: src/lsm/, SURVEY §2.2).

A log-structured merge forest over a copy-on-write block grid:

- grid.py    — block allocator/store with checksummed blocks and an
               EWAH-persisted free set (reference: src/vsr/grid.zig +
               src/vsr/free_set.zig)
- table.py   — immutable sorted runs serialized into grid blocks
               (reference: src/lsm/table.zig)
- memtable.py — a tree's in-memory side: dicts and column runs in
               arrival order, sorted once into a columnar run at the
               freeze (reference: src/lsm/table_memory.zig)
- tree.py    — memtable + leveled tables, growth factor 8, deterministic
               least-overlap compaction (reference: src/lsm/tree.zig,
               compaction.zig, manifest.zig)
- forest.py  — named trees sharing one grid; checkpoint/open
               (reference: src/lsm/forest.zig)

Round-1 scope: the engine is standalone and fully tested (including
byte-determinism across runs); wiring it under the replica's checkpoint
path (replacing snapshot checkpoints) is the next round's work.
"""

from .forest import Forest
from .grid import Grid
from .tree import Tree

__all__ = ["Forest", "Grid", "Tree"]
