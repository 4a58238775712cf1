"""One LSM tree: memtable + leveled immutable tables with deterministic
compaction.

reference: src/lsm/tree.zig (mutable/immutable memtables, 7 levels, growth
factor 8 — src/config.zig:162-163), src/lsm/compaction.zig (incremental
merge paced in bars/beats; deterministic pacing is load-bearing for
replica-identical data files), src/lsm/manifest.zig (least-overlap table
selection, docs/internals/lsm.md:93-108).

Pacing model here (incremental, VERDICT r1 #5 — reference:
src/lsm/compaction.zig:289, docs/internals/lsm.md:37-138): `compact_beat()`
is called once per committed op (the reference's beat). At each bar
boundary the mutable memtable FREEZES (mutable/immutable swap,
tree.zig:543) and one compaction JOB is scheduled per over-budget level;
both kinds of work then spread evenly across the bar's remaining beats.
The frozen memtable streams value blocks to the grid each beat but its
tables INSTALL only at completion; compaction merges in memory and writes
only at its completing beat. Either way no manifest ever references
partial state: checkpoints drain in-flight work first (manifest_pack),
and blocks written by an abandoned mid-bar job are unreferenced (freed at
the next checkpoint). The last beat of the bar drains whatever remains,
so a bar always ends with its scheduled work installed. All decisions are
pure functions of the op sequence — byte-deterministic across replicas
(tested), including across a crash/replay."""

from __future__ import annotations

import dataclasses
import struct
from typing import Optional

import numpy as np

from .grid import Grid
from .manifest_level import SNAPSHOT_LATEST, ManifestLevel
from .memtable import Memtable, SortedRun
from .table import (
    Table,
    TableInfo,
    TOMBSTONE,
    release_table,
    table_block_bound,
    table_entry_max,
    value_block_entry_max,
    write_index_block,
    write_tables,
    write_value_block,
)

LSM_LEVELS = 7
GROWTH_FACTOR = 8
BAR_LENGTH = 32  # ops per bar (reference: lsm_compaction_ops)
L0_TABLES_MAX = 4
# What a tree counts of its compaction (Tree.compaction), cumulative:
# jobs installed, input rows consumed (as a beat consumes them), rows
# written (as a job installs), and the input rows that went through a
# beat with no merge because the beat's two key ranges did not
# interleave.
COMPACTION_COUNTERS = ("jobs", "rows_in", "rows_out", "passed_sorted")


@dataclasses.dataclass
class _FlushJob:
    """The frozen (immutable) memtable being written out incrementally
    (reference: the mutable/immutable memtable pair, src/lsm/tree.zig +
    table_memory.zig — the immutable side streams to disk across the
    bar's beats while staying readable)."""

    entries: SortedRun  # the frozen rows, sorted, as columns
    snapshot: int  # freeze op: installed tables carry this snapshot_min
    pos: int = 0
    # Current table's completed value blocks: (address, size, first_key).
    blocks: list = dataclasses.field(default_factory=list)
    infos: list = dataclasses.field(default_factory=list)
    # Worst-case grid reservation claimed at freeze (free_set.zig:28-35).
    reservation: object = None


def _keys(rows: np.ndarray, key_size: int) -> np.ndarray:
    """The keys of `rows` as one fixed-width byte string a row (dtype
    `S<key_size>`). numpy searches and compares such strings by their
    unsigned bytes over the whole width, so their order is the `bytes`
    order of the keys at every key size of the forest, a trailing zero
    byte included (every key of a tree has the same width, so padding
    never decides)."""
    return np.ascontiguousarray(rows[:, :key_size]).view(
        f"S{key_size}").ravel()


class _SortedInput:
    """One side of a merge as a sorted run: the value blocks of tables
    that are disjoint and in key order, read a block at a time as row
    matrices (`Table.block_rows`) and only as far as a beat asks."""

    def __init__(self, tables: list[Table], entry: int):
        self._blocks = [(t, i) for t in tables
                        for i in range(len(t.block_addresses))]
        self._next = 0  # the first block not read yet
        self._rows = np.empty((0, entry), dtype=np.uint8)  # read, not taken

    def peek(self, n: Optional[int]) -> np.ndarray:
        """The next `n` rows (None: all that are left), fewer only
        where the input ends; they stay until `skip` takes them."""
        parts, have = [self._rows], len(self._rows)
        while (n is None or have < n) and self._next < len(self._blocks):
            table, i = self._blocks[self._next]
            self._next += 1
            parts.append(table.block_rows(i))
            have += len(parts[-1])
        if len(parts) > 1:
            self._rows = np.concatenate(parts) if len(parts[0]) \
                or len(parts) > 2 else parts[1]
        return self._rows if n is None else self._rows[:n]

    def skip(self, n: int) -> None:
        self._rows = self._rows[n:]


@dataclasses.dataclass
class _CompactionJob:
    """One level's in-flight incremental merge, on rows from the input
    block to the output block. The inputs are captured at schedule
    time as two sorted runs: `old`, the overlapping level-(L+1) tables
    (disjoint, in key order), and `new`, the picked level-L table. A
    beat merges a bounded number of input rows, in key order, onto
    `out`; the output is written + installed only at completion."""

    level: int
    table: Table
    overlapping: list[Table]
    total: int  # input entries (pacing estimate, and the output's bound)
    # Worst-case grid reservation claimed at schedule (free_set.zig:28-35).
    reservation: object = None

    def __post_init__(self):
        self.key_size = self.table.key_size
        entry = self.key_size + self.table.value_size
        self.old = _SortedInput(self.overlapping, entry)
        self.new = _SortedInput([self.table], entry)
        # The merged rows so far: out[:rows_out], sorted, unique keys.
        self.out = np.empty((self.total, entry), dtype=np.uint8)
        self.rows_out = 0

    def advance(self, budget: Optional[int]):
        """Merge the next `budget` INPUT rows in key order (None =
        drain). Returns (done, used, passed): done when the inputs ran
        out before the budget did (caller finalizes); used = rows
        consumed, which the caller charges against the beat budget (NOT
        output growth — a key both inputs hold consumes two rows and
        leaves one); passed = `used` where the rows went through as
        they stood, 0 where they were merged."""
        ks = self.key_size
        a, b = self.old.peek(budget), self.new.peek(budget)
        # Where the two ranges do not interleave the rows pass through
        # as they stand, the lower range first (one input exhausted or
        # empty is the common case: a tree keyed by timestamp compacts
        # into an empty range of the next level).
        if not len(a) or not len(b) \
                or a[-1, :ks].tobytes() < b[0, :ks].tobytes():
            b = b if budget is None else b[:budget - len(a)]
            self._emit(a)
            self._emit(b)
            merged = False
        elif b[-1, :ks].tobytes() < a[0, :ks].tobytes():
            a = a if budget is None else a[:budget - len(b)]
            self._emit(b)
            self._emit(a)
            merged = False
        else:
            a, b = self._interleave(a, b, budget)
            merged = True
        self.old.skip(len(a))
        self.new.skip(len(b))
        used = len(a) + len(b)
        return budget is None or used < budget, used, 0 if merged else used

    def _room(self, first: np.ndarray, n: int) -> np.ndarray:
        """The output's next `n` rows, to be filled by the caller with
        rows of which `first` is the lowest. Of equal keys the old row
        goes first and the new one is kept (the rule Memtable.freeze
        applies); where a beat's cut fell between the two, the new row
        takes the old one's place here."""
        ks = self.key_size
        if self.rows_out and self.out[self.rows_out - 1, :ks].tobytes() \
                == first[:ks].tobytes():
            self.rows_out -= 1
        self.rows_out += n
        return self.out[self.rows_out - n:self.rows_out]

    def _emit(self, rows: np.ndarray) -> None:
        if len(rows):
            self._room(rows[0], len(rows))[:] = rows

    def _interleave(self, a: np.ndarray, b: np.ndarray,
                    budget: Optional[int]):
        """Emit the merge of the first `budget` rows, in key order, of
        sorted `a` (old) and `b` (new); returns the rows of each it
        took. No sort: each new row's place among the old ones is one
        binary search, and the old rows between two places move as
        they stand."""
        ks = self.key_size
        keys_a, keys_b = _keys(a, ks), _keys(b, ks)
        if budget is not None and len(a) == budget:
            # No new row at or past the last old one is among the
            # first `budget`.
            b = b[:int(np.searchsorted(keys_b, keys_a[-1:])[0])]
            keys_b = keys_b[:len(b)]
        # Old rows at or below each new row: its merged index is that
        # many plus the new rows before it.
        below = np.searchsorted(keys_a, keys_b, side="right")
        total = len(a) + len(b) if budget is None \
            else min(budget, len(a) + len(b))
        n_b = int(np.searchsorted(below + np.arange(len(b)), total))
        a, b = a[:total - n_b], b[:n_b]
        below, keys_b = below[:n_b], keys_b[:n_b]
        # An old row whose key a new row repeats is superseded.
        same = (keys_a[np.maximum(below, 1) - 1] == keys_b) & (below > 0)
        old = a
        if same.any():
            keep = np.ones(len(a), dtype=bool)
            keep[below[same] - 1] = False
            old = a[keep]
            below = below - np.cumsum(same)
        merged = self._room(b[0] if n_b and not below[0] else old[0],
                            len(old) + n_b)
        at = below + np.arange(n_b)
        from_b = np.zeros(len(merged), dtype=bool)
        from_b[at] = True
        merged[at] = b
        merged[~from_b] = old
        return a, b


class Tree:
    def __init__(self, grid: Grid, *, key_size: int, value_size: int,
                 name: str = "tree", tree_id: int = 0):
        self.grid = grid
        self.key_size = key_size
        self.value_size = value_size
        self.name = name
        # Stamped into every block this tree writes (lsm/schema.py);
        # 0 = standalone. The forest assigns deterministic ids.
        self.tree_id = tree_id
        self.memtable = Memtable(key_size, value_size)
        # The frozen previous memtable is the flush job's `entries`:
        # readable while the job streams it into level-0 tables across
        # the bar's beats.
        self._flush: Optional[_FlushJob] = None
        self._flush_per_beat = 0
        # Per-level manifest structures over (key range x snapshot range)
        # (reference: src/lsm/manifest_level.zig). L0 tables overlap
        # (insertion order, recency decides); deeper levels are disjoint
        # per snapshot (key_min order, binary-searched).
        self.levels: list[ManifestLevel] = [
            ManifestLevel(keep_sorted=(i > 0)) for i in range(LSM_LEVELS)]
        self.beat = 0
        # In-flight incremental compaction jobs (scheduled at bar start,
        # advanced per beat, drained by bar end).
        self._jobs: list[_CompactionJob] = []
        self._per_beat = 0
        # Forest.depth_stats sums the trees'. Not persisted: a restart
        # counts from zero.
        self.compaction = dict.fromkeys(COMPACTION_COUNTERS, 0)
        # Point reads (get / get_many) that no memtable answered, and
        # the tables probed for them: an add a call, nothing a row. Not
        # persisted either; a caller reads the difference around its own
        # reads (DurableState.account_reads).
        self.keys_from_tables = 0
        self.table_probes = 0

    # ------------------------------------------------------------- updates

    def put(self, key: bytes, value: bytes) -> None:
        assert len(key) == self.key_size and len(value) == self.value_size
        self.memtable.put(key, value)

    def put_run(self, keys, values) -> None:
        """Put a column of rows whole: `keys` a uint8[n, key_size] matrix
        (or its bytes), `values` a uint8[n, value_size] matrix or the one
        value of every row. The same writes as n `put` calls in row
        order, with no work per row here: the run waits in the memtable
        until the freeze sorts it, or a read by key folds it."""
        self.memtable.put_run(keys, values)

    def remove(self, key: bytes) -> None:
        assert len(key) == self.key_size
        self.memtable.put(key, TOMBSTONE * self.value_size)

    def memtable_rows(self, frozen: bool = False) -> dict:
        """{key: value} of the mutable memtable (or of the frozen one,
        while its flush is in flight), tombstones included: the logical
        contents, whatever form they are held in."""
        if not frozen:
            run = self.memtable.freeze()
        else:
            run = self._flush.entries if self._flush is not None else None
        if run is None:
            return {}
        return dict(run.between(bytes(self.key_size),
                                b"\xff" * self.key_size))

    def get(self, key: bytes,
            snapshot: Optional[int] = None) -> Optional[bytes]:
        """Point lookup. snapshot=None serves the latest state (memtable
        included); snapshot=s reads the table set visible at op s — a
        point-in-time view that stays consistent while compaction installs
        and removes tables around it (valid within the tree's one-bar
        retention window; reference: manifest snapshot queries,
        src/lsm/manifest_level.zig)."""
        value = self.memtable.get(key) if snapshot is None else None
        if value is None:
            # The frozen memtable became logically table-visible at its
            # freeze op: snapshots at or past it must read it even while
            # the flush job is still streaming it out (otherwise the same
            # (key, snapshot) would answer differently before and after
            # the install).
            frozen = self._frozen(snapshot)
            if frozen is not None:
                value = frozen.get(key)
        if value is None:
            # L0 tables may overlap: newest-first probe; deeper levels
            # yield at most one candidate per snapshot (binary-searched on
            # the live set for the latest snapshot).
            probes = 0
            for level in self.levels:
                for table in level.lookup(key, snapshot):
                    probes += 1
                    value = table.get(key)
                    if value is not None:
                        break
                if value is not None:
                    break
            self.keys_from_tables += 1
            self.table_probes += probes
        if value is None or value == TOMBSTONE * self.value_size:
            return None
        return value

    def get_many(self, keys, snapshot: Optional[int] = None) -> dict:
        """Batched point lookups: per level, every unresolved key's value
        block is issued in ONE concurrent fan-out (Grid.read_blocks),
        then resolved in place — a cold cache costs one round trip per
        level touched, not one per key (reference: the prefetch fan-out,
        src/lsm/groove.zig:996,1339). Returns {key: value} for keys
        found live (tombstoned/missing keys are absent)."""
        found: dict = {}
        remaining = []
        frozen = self._frozen(snapshot)
        for key in keys:
            value = self.memtable.get(key) if snapshot is None else None
            if value is None and frozen is not None:
                value = frozen.get(key)
            if value is not None:
                found[key] = value
            else:
                remaining.append(key)
        self.keys_from_tables += len(remaining)
        plans: dict = {}  # key -> [(table, blk)] planned by the lookahead
        for li, level in enumerate(self.levels):
            if not remaining:
                break
            # Per-key candidate queues (L0 may yield several overlapping
            # tables, newest first; deeper levels at most one). The
            # previous level's lookahead already planned (table, block)
            # pairs for this level — reuse them instead of re-probing.
            active = []
            for key in remaining:
                cand = plans.get(key)
                if cand is None:
                    cand = [(t, t.block_for(key))
                            for t in level.lookup(key, snapshot)]
                if cand:
                    active.append((key, cand))
            # Overlap: submit the NEXT level's candidate blocks (planned
            # read-free) while THIS level's fan-out resolves — a superset
            # read-ahead (keys resolved here waste their submit) bounded
            # by the grid's in-flight cap; no-op on synchronous devices.
            plans = {}
            if li + 1 < len(self.levels) and active:
                lookahead = []
                for key, _ in active:
                    cand2 = [(t, t.block_for(key)) for t in
                             self.levels[li + 1].lookup(key, snapshot)]
                    if cand2:
                        plans[key] = cand2
                        lookahead.extend(
                            b for _, b in cand2 if b is not None)
                if lookahead:
                    self.grid.prefetch_async(lookahead)
            while active:
                reqs, slots, nxt = [], [], []
                for key, cand in active:
                    blk = None
                    while cand and blk is None:
                        table, blk = cand.pop(0)
                    if blk is None:
                        continue
                    reqs.append(blk)
                    slots.append((key, table, cand))
                if not reqs:
                    break
                self.table_probes += len(reqs)
                for (key, table, cand), raw in zip(
                        slots, self.grid.read_blocks(reqs)):
                    value = table.get_in_block(key, raw)
                    if value is not None:
                        found[key] = value  # tombstones shadow deeper levels
                    elif cand:
                        nxt.append((key, cand))
                active = nxt
            remaining = [k for k in remaining if k not in found]
        dead = TOMBSTONE * self.value_size
        return {k: v for k, v in found.items() if v != dead}

    def scan(self, key_min: bytes, key_max: bytes,
             snapshot: Optional[int] = None) -> list[tuple[bytes, bytes]]:
        """Merged range scan, newest version wins (streaming k-way merge
        over memtable + levels — reference: scan_tree.zig; the lazy
        iterator API is lsm/scan.py's TreeScan)."""
        from .scan import TreeScan

        return list(TreeScan(self, key_min, key_max, snapshot=snapshot))

    # ---------------------------------------------------------- compaction

    def compact_beat(self, op: Optional[int] = None) -> None:
        """One beat. At a bar boundary: flush the memtable and SCHEDULE one
        compaction job per over-budget level; on every beat, advance the
        in-flight jobs by a bounded number of merged entries (total work /
        remaining beats), deferring grid writes to each job's completion;
        the bar's last beat drains the rest. Deterministic in the op
        sequence (no clocks, no randomness). When `op` is given, the bar
        phase is derived from the op number itself so a restarted replica
        replaying the WAL suffix hits the exact same flush and merge
        points as one that never crashed (the reference derives compaction
        pacing from op % lsm_compaction_ops the same way,
        docs/internals/lsm.md:37-91)."""
        self.beat = self.beat + 1 if op is None else op
        phase = self.beat % BAR_LENGTH
        if phase == 0:
            self._drain_flush()  # defensive: the previous freeze is done
            self._freeze_memtable()
            self._drain_jobs()  # defensive: a bar never leaves work behind
            # Physically release tables removed at least one full bar ago
            # (snapshot reads within the retention window stay valid; a
            # pure function of the op sequence, so every replica frees the
            # identical block set — physical determinism).
            self._prune(self.beat - BAR_LENGTH)
            self._schedule_jobs()
        if self._flush is not None:
            if phase == BAR_LENGTH - 1:
                self._drain_flush()
            else:
                self._advance_flush(self._flush_per_beat)
        if self._jobs:
            if phase == BAR_LENGTH - 1:
                self._drain_jobs()
            else:
                self._advance_jobs(self._per_beat)

    def flush_memtable(self) -> None:
        """Synchronous freeze + drain (checkpoints and callers that need
        every row table-resident NOW; the beat path streams instead)."""
        self._freeze_memtable()
        self._drain_flush()

    # -------------------------------------------------- memtable flushing

    def _frozen(self, snapshot: Optional[int]) -> Optional[SortedRun]:
        """The frozen memtable, if there is one and it is part of the
        view at `snapshot`."""
        job = self._flush
        if job is None or (snapshot is not None
                           and snapshot < job.snapshot):
            return None
        return job.entries

    def _freeze_memtable(self) -> None:
        """Swap mutable -> immutable (reference tree.zig:543): one numpy
        sort turns the memtable into a columnar sorted run, which stays
        readable as the flush job's `entries` while the job streams it
        into level-0 tables across the bar's beats."""
        if not self.memtable:
            return
        self._drain_flush()  # at most one frozen memtable at a time
        # Reserve BEFORE the swap: a "grid full" reserve failure must
        # leave the tree unchanged (a post-swap failure would strand the
        # frozen rows with no flush job and lose them at the next freeze).
        entries = self.memtable.freeze()
        reservation = self.grid.reserve(table_block_bound(
            self.grid, len(entries), self.key_size, self.value_size))
        self.memtable.clear()
        self._flush = _FlushJob(
            entries=entries,
            snapshot=self.beat,
            reservation=reservation)
        self._flush_per_beat = max(
            1, -(-len(self._flush.entries) // (BAR_LENGTH - 1)))

    def _advance_flush(self, budget: Optional[int]) -> None:
        """Write up to `budget` entries (whole value blocks; None = all).
        Value blocks hit the grid each beat, but tables INSTALL only at
        job completion: the mid-bar blocks stay unreferenced from any
        manifest, and checkpoints drain the job first (manifest_pack ->
        flush_memtable), so no checkpoint ever references a partial
        table."""
        job = self._flush
        if job is None:
            return
        per_block = value_block_entry_max(self.grid, self.key_size,
                                          self.value_size)
        cap = table_entry_max(self.grid, self.key_size, self.value_size)
        while job.pos < len(job.entries):
            if budget is not None and budget <= 0:
                return
            table_end = min(len(job.entries),
                            (job.pos // cap + 1) * cap)
            chunk = job.entries.rows[
                job.pos:min(job.pos + per_block, table_end)]
            job.blocks.append(write_value_block(
                self.grid, chunk, self.key_size,
                reservation=job.reservation, tree_id=self.tree_id))
            job.pos += len(chunk)
            if budget is not None:
                budget -= len(chunk)
            if job.pos == table_end:
                job.infos.append(self._finish_flush_table(job, cap))
        # All entries written: install every produced table.
        for info in job.infos:
            self.levels[0].insert(
                Table(self.grid, info, self.key_size, self.value_size),
                snapshot=job.snapshot)
        if job.reservation is not None:
            self.grid.forfeit(job.reservation)
        self._flush = None

    def _finish_flush_table(self, job: _FlushJob, cap: int) -> TableInfo:
        index_addr, index_size = write_index_block(
            self.grid, job.blocks, reservation=job.reservation,
            tree_id=self.tree_id)
        first_key = job.blocks[0][2]
        # job.pos sits at this table's end; recover its entry range.
        start = (job.pos - 1) // cap * cap
        info = TableInfo(
            index_address=index_addr, index_size=index_size,
            key_min=first_key, key_max=job.entries.key(job.pos - 1),
            entry_count=job.pos - start)
        job.blocks = []
        return info

    def _drain_flush(self) -> None:
        self._advance_flush(None)

    def _prune(self, snapshot_oldest: int) -> None:
        for level in self.levels:
            for table in level.prune(snapshot_oldest):
                release_table(self.grid, table)

    def _level_budget(self, level: int) -> int:
        if level == 0:
            return L0_TABLES_MAX
        return GROWTH_FACTOR ** level

    def _schedule_jobs(self) -> None:
        """One job per over-budget level, inputs captured now (they stay
        installed and readable until the job completes). A level whose
        pick or overlap set intersects an earlier job's captured tables
        is SKIPPED this bar (adjacent over-budget levels would otherwise
        double-release a shared level-(L+1) table); it reschedules next
        bar — deterministic either way."""
        assert not self._jobs
        jobs: list[_CompactionJob] = []
        claimed: set[int] = set()  # id() of captured Table objects
        for level in range(LSM_LEVELS - 1):
            if len(self.levels[level]) > self._level_budget(level):
                table = self._pick_table(level)
                overlapping = [
                    t for t in self.levels[level + 1]
                    if not (t.info.key_max < table.info.key_min
                            or t.info.key_min > table.info.key_max)]
                touched = [table, *overlapping]
                if any(id(t) in claimed for t in touched):
                    continue
                claimed.update(id(t) for t in touched)
                # Warm the first input block of every table now: the
                # device reads run during the beats before the job's
                # first advance (block_rows' read-ahead covers the
                # rest of each table).
                self.grid.prefetch_async(
                    [(t.block_addresses[0], t.block_sizes[0])
                     for t in touched if t.block_addresses])
                jobs.append(self._new_job(level, table, overlapping))
        self._set_jobs(jobs)

    def _new_job(self, level: int, table: Table,
                 overlapping: list[Table]) -> _CompactionJob:
        total = (table.info.entry_count
                 + sum(t.info.entry_count for t in overlapping))
        return _CompactionJob(
            level=level, table=table, overlapping=overlapping, total=total,
            reservation=self.grid.reserve(table_block_bound(
                self.grid, total, self.key_size, self.value_size)))

    def _set_jobs(self, jobs: list[_CompactionJob]) -> None:
        self._jobs = jobs
        total = sum(j.total for j in jobs)
        self._per_beat = max(1, -(-total // (BAR_LENGTH - 1)))

    def _advance_jobs(self, budget: Optional[int]) -> None:
        """Advance the jobs in order by `budget` input rows in all
        (None: drain them), installing each that runs out of input."""
        while self._jobs and (budget is None or budget > 0):
            done, used, passed = self._jobs[0].advance(budget)
            self.compaction["rows_in"] += used
            self.compaction["passed_sorted"] += passed
            if done:
                self._finalize_job(self._jobs.pop(0))
            if budget is not None:
                budget -= max(1, used)

    def _drain_jobs(self) -> None:
        self._advance_jobs(None)
        assert not self._jobs

    def _finalize_job(self, job: _CompactionJob) -> None:
        """Write output tables, install, logically remove inputs — the
        only beat that touches the grid (mid-bar checkpoints therefore
        never see a partially-written compaction). Inputs move to the
        manifest's history (snapshot_max = this op) and stay readable for
        snapshots taken before this beat; their blocks are freed by
        `_prune` a bar later."""
        level = job.level
        self.levels[level].remove(job.table, snapshot=self.beat)
        next_level = self.levels[level + 1]
        for t in job.overlapping:
            next_level.remove(t, snapshot=self.beat)
        rows = job.out[:job.rows_out]
        if level + 1 == LSM_LEVELS - 1:  # tombstones die at the bottom
            dead = np.frombuffer(TOMBSTONE * self.value_size, dtype=np.uint8)
            rows = rows[(rows[:, self.key_size:] != dead).any(axis=1)]
        # A merge output exceeding one table's capacity splits into
        # several disjoint tables (all still inside next_level's range).
        for info in write_tables(self.grid, rows, self.key_size,
                                 self.value_size,
                                 reservation=job.reservation,
                                 tree_id=self.tree_id):
            next_level.insert(Table(
                self.grid, info, self.key_size, self.value_size),
                snapshot=self.beat)
        if job.reservation is not None:
            self.grid.forfeit(job.reservation)
        self.compaction["jobs"] += 1
        self.compaction["rows_out"] += len(rows)

    def _pick_table(self, level: int) -> Table:
        """Selection policy: L0 tables overlap each other, so only the
        OLDEST may move down (a newer table would otherwise be shadowed by
        stale data left behind). Deeper levels are disjoint; pick by least
        overlap with the next level, ties on smallest key_min for
        determinism (reference: docs/internals/lsm.md:93-108)."""
        if level == 0:
            return self.levels[0][0]

        def overlap(table: Table) -> int:
            return sum(
                1 for t in self.levels[level + 1]
                if not (t.info.key_max < table.info.key_min
                        or t.info.key_min > table.info.key_max))

        return min(self.levels[level],
                   key=lambda t: (overlap(t), t.info.key_min))

    # ------------------------------------------------------------ manifest

    def manifest_pack(self) -> bytes:
        """Serialize the level structure AND any in-flight compaction
        jobs (reference: manifest log replay). Persisting the job plans
        is load-bearing for physical determinism: a mid-bar checkpoint
        precedes the bar-end install, so a replica restarting from it
        must resume the SAME merges (same inputs, same install beat) a
        never-crashed replica completes — the merge output is a pure
        function of the inputs, so the grids stay byte-identical even
        though the restarted replica redoes the merge work."""
        self.flush_memtable()
        # The beat is persisted so a restored tree keeps stamping snapshots
        # and pruning on the same op clock (a reset-to-zero beat would
        # invert level-0 recency for post-restore flushes and re-extend
        # the retention window).
        parts = [struct.pack("<QB", self.beat, LSM_LEVELS)]
        for level in self.levels:
            entries = list(level.live) + list(level.history)
            # next_seq is persisted (not re-derived from surviving
            # entries): once the max-seq entry is pruned, a re-derived
            # counter would diverge from never-restarted replicas and
            # break byte-identical checkpoints.
            parts.append(struct.pack("<QI", level.next_seq, len(entries)))
            for e in entries:
                parts.append(struct.pack("<QQQ", e.snapshot_min,
                                         e.snapshot_max, e.seq))
                parts.append(e.table.info.pack())
        parts.append(struct.pack("<I", len(self._jobs)))
        for job in self._jobs:
            parts.append(struct.pack("<BI", job.level, len(job.overlapping)))
            parts.append(job.table.info.pack())
            for t in job.overlapping:
                parts.append(t.info.pack())
        return b"".join(parts)

    def manifest_restore(self, raw: bytes) -> None:
        from .manifest_level import LevelEntry

        beat, n_levels = struct.unpack_from("<QB", raw)
        assert n_levels == LSM_LEVELS
        self.beat = beat
        pos = 9
        self.levels = [ManifestLevel(keep_sorted=(i > 0))
                       for i in range(LSM_LEVELS)]
        for level in range(n_levels):
            next_seq, count = struct.unpack_from("<QI", raw, pos)
            pos += 12
            for _ in range(count):
                snap_min, snap_max, seq = struct.unpack_from(
                    "<QQQ", raw, pos)
                pos += 24
                info, pos = TableInfo.unpack(raw, pos)
                table = Table(self.grid, info, self.key_size,
                              self.value_size)
                if snap_max == SNAPSHOT_LATEST:
                    self.levels[level].insert(table, snapshot=snap_min,
                                              seq=seq)
                else:
                    self.levels[level].history.append(LevelEntry(
                        table=table, snapshot_min=snap_min,
                        snapshot_max=snap_max, seq=seq))
            self.levels[level].next_seq = next_seq
        self.memtable.clear()
        self._flush = None
        # Rebuild in-flight jobs against the RESTORED Table objects
        # (identity matters: finalize removes job tables from the level
        # lists by identity). Merge progress restarts from zero — the
        # output is input-deterministic, so only pacing differs.
        jobs = []
        if pos < len(raw):
            (n_jobs,) = struct.unpack_from("<I", raw, pos)
            pos += 4
            for _ in range(n_jobs):
                level, n_over = struct.unpack_from("<BI", raw, pos)
                pos += 5
                t_info, pos = TableInfo.unpack(raw, pos)
                over_infos = []
                for _ in range(n_over):
                    info, pos = TableInfo.unpack(raw, pos)
                    over_infos.append(info)

                def resident(lvl: int, info: TableInfo) -> Table:
                    for t in self.levels[lvl]:
                        if (t.info.index_address == info.index_address
                                and t.info.index_size == info.index_size):
                            return t
                    raise AssertionError(
                        f"job table missing from restored level {lvl}")

                jobs.append(self._new_job(
                    level, resident(level, t_info),
                    [resident(level + 1, i) for i in over_infos]))
        self._set_jobs(jobs)


