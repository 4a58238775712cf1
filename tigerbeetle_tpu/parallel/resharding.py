"""Elastic shards: crash-safe live resharding with staged handoff.

The partitioned layout (parallel/partitioned.py) fixes each object's
shard with a pure hash — perfect balance for uniform traffic, no answer
when one hash range runs hot or a mesh grows. This module moves an
account-hash range between shards UNDER LIVE TRAFFIC with a five-stage
protocol whose every irreversible step is gated by a digest witness:

  1. SNAPSHOT  — quiesce (caller contract: no in-flight windows),
     fetch the source shard's stores to host, filter the range's rows,
     and verify the filtered pack's position-independent range digest
     (ops/state_epoch.partitioned_range_digest) against the device fold
     — and against the oracle's, when the driver holds one. From here
     the range is FROZEN: the controller treats any window touching it
     as a conflict until double-write activates.
  2. COPY      — stream the snapshot's account/transfer/ring rows to
     the target in bounded chunks (a jitted scatter-append at the
     target's live counts; capacity pre-checked host-side because
     dynamic starts clamp rather than trap). Staged rows are NOT in the
     target's hash tables yet — lookups cannot see a half-copied range.
     The source keeps serving all non-range traffic; a window that
     conflicts with the frozen range drains the remaining chunks
     synchronously (a bounded stall) instead of deferring the window —
     deferral would reorder history against the oracle.
  3. DOUBLE-WRITE — after the last chunk, one finalize kernel restores
     the target shard's canonical row order (argsort by timestamp —
     the shard-then-sort contract the epoch digest pins), REMAPS the
     existing table values through the permutation (bucket choice
     depends only on the key, so values can move without a rebuild),
     and inserts the staged keys + the range's orphan markers. Then
     the ownership overlay activates (shard_utils.OVERLAY_DOUBLE_WRITE)
     and traffic resumes: reads still come from the source, writes
     apply to BOTH copies (owner-masked write-back under `writes_here`),
     so the two copies advance in lockstep for at least
     `min_double_write_windows` commit windows.
  4. FLIP      — at a window boundary (quiesced again), ownership
     switches to the target ONLY if the source and target range digests
     (content + row counts) are bit-equal at the same epoch — plus the
     oracle's, when available. A mismatch aborts: the overlay entry is
     reverted, the staged copy is evicted from the target, and the
     flight recorder freezes a FLIGHT_*_reshard_* artifact. The flip
     itself is one host-side ownership-table swap (generation bump) —
     the routers' step caches key on the overlay entries, so the next
     window simply selects the post-flip lowering.
  5. RETIRE    — immediately after a clean flip, the source's copy of
     the range is evicted (keep-compaction into zeros, table keys
     dropped with per-bucket slot re-compaction, surviving values
     remapped). The overlay entry persists as OVERLAY_MIGRATED — the
     base map is a pure hash, so the entry IS the collapsed override.
     A later `merge_back` runs the same protocol in reverse
     (OVERLAY_RETURNING; its completing flip DROPS the entry).

Crash safety: every stage before FLIP is invisible to ownership — a
crash recovers by reverting the overlay entry (if any) and rebuilding
from the oracle (`PartitionedRouter.resync`), the `reshard_abort`
recovery cause. A crash after FLIP keeps the MIGRATED entry: the resync
packer places the range on the target, so the pre-retire stale source
copy never resurfaces. There is no window in which a crash can lose or
double-apply a committed write: double-write keeps both copies current,
and the flip's digest gate proves it before ownership moves.

Known non-goals, by design:
  - Ring rows carry no object ids, so the device snapshot cannot
    attribute them to a range: they are copied only when the driver
    passes an oracle (packed from its account_events with dump
    pointers — row pointers are non-canonical scope), and the retired
    source's ring rows remain as scratch (the ring is excluded from
    every digest and recycled by serving).
  - The whole-state epoch digest is NOT comparable mid-copy (staged
    rows bump the target's counts): epoch verification must complete
    or abort the migration first (ServingSupervisor does).
  - Stored dr_row/cr_row pointer words go stale when finalize re-sorts
    account rows; they are non-canonical scope — every consumer
    re-derives them from id columns (see partitioned.py docstring).

The HotRangeDetector turns the router's per-shard telemetry into split
proposals (propose-only: enacting is the driver's `--auto-reshard`
decision), including the degenerate verdict — a single account so hot
that no hash range smaller than the whole shard isolates it is
`unsplittable` (the fix is AT2-style lane parallelism WITHIN the
account's commit lane, not placement; see ARCHITECTURE.md).
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp

from ..ops.ev_layout import AC_NCOLS, AC_U64_IDX, EV_NCOLS, XF_NCOLS, \
    XF_U64_IDX, narrow, widen
from ..ops.hash_table import ORPHAN_VAL, SLOTS, ht_lookup, ht_matrix, \
    ht_pack, ht_plan, ht_unpack, ht_write
from ..ops.state_epoch import _range_digest_components, \
    partitioned_range_digest
from ..trace import Event, NullTracer
from .shard_utils import (
    OVERLAY_DOUBLE_WRITE, OVERLAY_MIGRATED, OVERLAY_RETURNING,
    mix_id, mix_int,
)

__all__ = ["ReshardPlan", "ReshardController", "HotRangeDetector",
           "MigrationAborted"]

_U64_MAX = (1 << 64) - 1
_AC_TS = AC_U64_IDX["ts"]
_XF_TS = XF_U64_IDX["ts"]


class MigrationAborted(RuntimeError):
    """A migration aborted pre-flip (digest mismatch, capacity, table
    overflow, recovery). Ownership is already reverted and the staged
    copy evicted when this raises; the range serves from its pre-
    migration owner, bit-identically."""

    def __init__(self, reason: str, detail: str = ""):
        super().__init__(reason + (f": {detail}" if detail else ""))
        self.reason = reason
        self.detail = detail


@dataclass(frozen=True)
class ReshardPlan:
    """One range move: ids whose ownership hash (shard_utils.mix_id)
    falls in [lo, hi] (inclusive) AND whose base owner is `src` migrate
    to `dst`. `kind` is 'migrate'/'split' (forward; split is a migrate
    proposed by the hot-range detector) or 'merge_back' (reverse an
    earlier migration — requires its OVERLAY_MIGRATED entry)."""

    lo: int
    hi: int
    src: int
    dst: int
    kind: str = "migrate"

    def __post_init__(self):
        assert 0 <= self.lo <= self.hi <= _U64_MAX, (self.lo, self.hi)
        assert self.src != self.dst, self
        assert self.kind in ("migrate", "split", "merge_back"), self.kind

    def in_range(self, id128: int, n_shards: int) -> bool:
        h = mix_int(id128)
        return (self.lo <= h <= self.hi
                and (h & (n_shards - 1)) == self.src)


# ------------------------------------------------------ device kernels
# Host-driven control-plane kernels over the stacked partitioned state.
# All are module-level jits (one trace per shape family), donate the
# state, and keep the serving lowerings untouched — resharding never
# adds an op to any window dispatch.


@functools.partial(jax.jit, donate_argnums=0)
def _install_chunk(stacked, shard, a_u64, a_bal, a_n, x_u64, x_n,
                   e_u64, e_n):
    """Scatter-append one copy chunk at the receiving shard's live
    counts (chunks are zero-padded to a fixed row count; pad lanes land
    zeros on the dump row, which is scratch by contract). Counts bump
    by the valid sub-counts only. Capacity is the CALLER's pre-check:
    scatter indices past the dump row would corrupt live rows."""
    out = jax.tree.map(lambda x: x, stacked)

    def append(u64, cnt_vec, rows, n):
        cap = u64.shape[1]
        iota = jnp.arange(rows.shape[0], dtype=jnp.int32)
        idx = jnp.where(iota < n, cnt_vec[shard] + iota,
                        jnp.int32(cap - 1))
        return u64.at[shard, idx].set(rows), cnt_vec.at[shard].add(n)

    acc, xfr, evr = out["accounts"], out["transfers"], out["events"]
    au, a_cnt = append(acc["u32"], acc["count"], narrow(a_u64), a_n)
    iota_a = jnp.arange(a_u64.shape[0], dtype=jnp.int32)
    idx_a = jnp.where(iota_a < a_n,
                      acc["count"][shard] + iota_a,
                      jnp.int32(acc["bal"].shape[1] - 1))
    ab = acc["bal"].at[shard, idx_a].set(narrow(a_bal))
    xu, x_cnt = append(xfr["u32"], xfr["count"], narrow(x_u64), x_n)
    eu, e_cnt = append(evr["u32"], evr["count"], narrow(e_u64), e_n)
    out["accounts"] = dict(u32=au, bal=ab, count=a_cnt)
    out["transfers"] = dict(u32=xu, count=x_cnt)
    out["events"] = dict(u32=eu, count=e_cnt)
    return out


def _remap_table_vals(packed, newpos):
    """Remap every live row-index value in a table's (b+1, 3*SLOTS) u64
    matrix (hash_table.ht_unpack) through the
    row permutation (bucket choice depends only on the key, so values
    move without touching the structure). Orphan markers (< 0) and
    empty slots pass through."""
    kh = packed[:, :SLOTS]
    kl = packed[:, SLOTS:2 * SLOTS]
    v = packed[:, 2 * SLOTS:].astype(jnp.int32)
    nonempty = (kh != 0) | (kl != 0)
    liveval = nonempty & (v >= 0)
    cap = newpos.shape[0]
    v2 = jnp.where(liveval,
                   newpos[jnp.clip(v, 0, cap - 1)].astype(jnp.int32), v)
    return jnp.concatenate(
        [kh, kl, v2.astype(jnp.uint64)], axis=1)


def _sort_store(u64, count, ts_col):
    """Canonical re-sort of one store's live rows by timestamp (commit
    timestamps are unique per store). Returns (sorted u64 with the tail
    zeroed, newpos: old row -> new row)."""
    cap = u64.shape[0]
    iota = jnp.arange(cap, dtype=jnp.uint64)
    live = iota < jnp.asarray(count).astype(jnp.uint64)
    # Tie-break dead rows by original index: fully deterministic order
    # without relying on sort stability.
    key = jnp.where(live, u64[:, ts_col], jnp.uint64(_U64_MAX))
    perm = jnp.lexsort((iota, key)).astype(jnp.int32)
    newpos = jnp.zeros(cap, jnp.int32).at[perm].set(
        jnp.arange(cap, dtype=jnp.int32))
    sorted_u64 = jnp.where(jnp.arange(cap)[:, None] < count,
                           u64[perm], jnp.uint64(0))
    return sorted_u64, perm, newpos


def _insert_missing(table, u64, count, orphan_val=None):
    """Insert every live row id absent from `table` with its row index
    as value (the staged rows finalize pass). Returns (table, ok)."""
    cap = u64.shape[0]
    k_hi, k_lo = u64[:, 0], u64[:, 1]
    iota = jnp.arange(cap, dtype=jnp.int32)
    valid = iota < count
    found, _ = ht_lookup(table, k_hi, k_lo)
    ins = valid & ~found
    pos, ok = ht_plan(table, k_hi, k_lo, ins)
    table = ht_write(table, pos, k_hi, k_lo, iota, ins & ok)
    return table, ok


@functools.partial(jax.jit, donate_argnums=0)
def _finalize_shard(stacked, shard, o_hi, o_lo, o_n):
    """Post-copy finalize of the receiving shard: canonical row order
    restored (appended chunks interleave by timestamp with the shard's
    own rows), existing table values remapped through the permutation,
    staged keys inserted at their new positions, and the range's orphan
    markers carried over. Returns (stacked, ok) — ok False means a
    table overflowed and the caller must abort (nothing else checks)."""
    out = jax.tree.map(lambda x: x, stacked)
    acc, xfr = out["accounts"], out["transfers"]

    au = widen(acc["u32"][shard])
    ab = widen(acc["bal"][shard])
    a_cnt = acc["count"][shard]
    au_s, a_perm, a_newpos = _sort_store(au, a_cnt, _AC_TS)
    cap_a = au.shape[0]
    ab_s = jnp.where(jnp.arange(cap_a)[:, None] < a_cnt,
                     ab[a_perm], jnp.uint64(0))
    aht = {"packed": ht_pack(_remap_table_vals(
        ht_unpack(out["acct_ht"]["packed"][shard]), a_newpos))}
    aht, ok_a = _insert_missing(aht, au_s, a_cnt)

    xu = widen(xfr["u32"][shard])
    x_cnt = xfr["count"][shard]
    xu_s, _x_perm, x_newpos = _sort_store(xu, x_cnt, _XF_TS)
    xht = {"packed": ht_pack(_remap_table_vals(
        ht_unpack(out["xfer_ht"]["packed"][shard]), x_newpos))}
    xht, ok_x = _insert_missing(xht, xu_s, x_cnt)
    # The range's orphan markers (transiently-failed ids with no row):
    # unique, absent from the target, valued ORPHAN_VAL forever.
    o_iota = jnp.arange(o_hi.shape[0], dtype=jnp.int32)
    o_ins = o_iota < o_n
    o_pos, ok_o = ht_plan(xht, o_hi, o_lo, o_ins)
    xht = ht_write(xht, o_pos, o_hi, o_lo,
                   jnp.full(o_hi.shape[0], ORPHAN_VAL, jnp.int32),
                   o_ins & ok_o)

    out["accounts"] = dict(u32=acc["u32"].at[shard].set(narrow(au_s)),
                           bal=acc["bal"].at[shard].set(narrow(ab_s)),
                           count=acc["count"])
    out["transfers"] = dict(u32=xfr["u32"].at[shard].set(narrow(xu_s)),
                            count=xfr["count"])
    out["acct_ht"] = {"packed": out["acct_ht"]["packed"].at[shard].set(
        aht["packed"])}
    out["xfer_ht"] = {"packed": out["xfer_ht"]["packed"].at[shard].set(
        xht["packed"])}
    return out, ok_a & ok_x & ok_o


def _drop_range_keys(packed, lo, hi, base_shard, n_shards):
    """Over a table's u64 matrix (hash_table.ht_unpack): zero every
    table slot whose key's ownership hash is in [lo, hi]
    with base owner `base_shard` (catches orphan markers — they have
    keys but no rows), then re-compact each bucket's slots to a leading
    non-empty prefix (the planner's occupancy invariant)."""
    kh = packed[:, :SLOTS]
    kl = packed[:, SLOTS:2 * SLOTS]
    v = packed[:, 2 * SLOTS:]
    h = mix_id(kh, kl)
    nonempty = (kh != 0) | (kl != 0)
    inr = ((h >= jnp.asarray(lo).astype(jnp.uint64))
           & (h <= jnp.asarray(hi).astype(jnp.uint64))
           & ((h & jnp.uint64(n_shards - 1)).astype(jnp.int32)
              == base_shard))
    drop = nonempty & inr
    kh = jnp.where(drop, jnp.uint64(0), kh)
    kl = jnp.where(drop, jnp.uint64(0), kl)
    v = jnp.where(drop, jnp.uint64(0), v)
    empty = (kh == 0) & (kl == 0)
    slot_iota = jnp.arange(SLOTS, dtype=jnp.int32)[None, :]
    # Unique per-slot keys (empty flag major, slot index minor): any
    # sort gives the same order, no stability assumption.
    order = jnp.argsort(
        empty.astype(jnp.int32) * jnp.int32(SLOTS) + slot_iota, axis=1)
    kh = jnp.take_along_axis(kh, order, axis=1)
    kl = jnp.take_along_axis(kl, order, axis=1)
    v = jnp.take_along_axis(v, order, axis=1)
    return jnp.concatenate([kh, kl, v], axis=1)


@functools.partial(jax.jit, donate_argnums=0, static_argnums=(4,))
def _evict_range(stacked, shard, lo, hi, n_shards, base_shard):
    """Evict a hash range from one shard's stores and tables: retire
    (shard = the migration source) and abort (shard = the receiver —
    staged rows carry the same base owner, so one kernel serves both).
    Kept rows compact preserving canonical order, dropped and tail rows
    zero, table keys drop with per-bucket re-compaction, surviving
    values remap. The ring is untouched (no id columns — documented
    scratch)."""
    out = jax.tree.map(lambda x: x, stacked)
    acc, xfr = out["accounts"], out["transfers"]

    def evict_store(u64, count):
        cap = u64.shape[0]
        iota = jnp.arange(cap, dtype=jnp.int32)
        live = iota < count
        h = mix_id(u64[:, 0], u64[:, 1])
        inr = ((h >= jnp.asarray(lo).astype(jnp.uint64))
               & (h <= jnp.asarray(hi).astype(jnp.uint64))
               & ((h & jnp.uint64(n_shards - 1)).astype(jnp.int32)
                  == base_shard))
        keep = live & ~inr
        # Kept rows first, in their original (canonical) order.
        key = jnp.where(keep, iota, jnp.int32(cap) + iota)
        perm = jnp.argsort(key).astype(jnp.int32)
        new_count = jnp.sum(keep, dtype=jnp.int32)
        new_u64 = jnp.where(iota[:, None] < new_count, u64[perm],
                            jnp.uint64(0))
        newpos = jnp.zeros(cap, jnp.int32).at[perm].set(iota)
        return new_u64, new_count, perm, newpos

    au, a_cnt2, a_perm, a_newpos = evict_store(widen(acc["u32"][shard]),
                                               acc["count"][shard])
    ab = jnp.where(jnp.arange(au.shape[0])[:, None] < a_cnt2,
                   acc["bal"][shard][a_perm], jnp.uint32(0))
    xu, x_cnt2, _xp, x_newpos = evict_store(widen(xfr["u32"][shard]),
                                            xfr["count"][shard])

    aht = _drop_range_keys(ht_unpack(out["acct_ht"]["packed"][shard]),
                           lo, hi, base_shard, n_shards)
    aht = ht_pack(_remap_table_vals(aht, a_newpos))
    xht = _drop_range_keys(ht_unpack(out["xfer_ht"]["packed"][shard]),
                           lo, hi, base_shard, n_shards)
    xht = ht_pack(_remap_table_vals(xht, x_newpos))

    out["accounts"] = dict(
        u32=acc["u32"].at[shard].set(narrow(au)),
        bal=acc["bal"].at[shard].set(ab),
        count=acc["count"].at[shard].set(a_cnt2))
    out["transfers"] = dict(
        u32=xfr["u32"].at[shard].set(narrow(xu)),
        count=xfr["count"].at[shard].set(x_cnt2))
    out["acct_ht"] = {"packed": out["acct_ht"]["packed"].at[shard].set(
        aht)}
    out["xfer_ht"] = {"packed": out["xfer_ht"]["packed"].at[shard].set(
        xht)}
    return out


# --------------------------------------------------------- controller

def _digest_eq(a: dict, b: dict) -> bool:
    return all(int(a[k]) == int(b[k]) for k in a)


class ReshardController:
    """The five-stage migration state machine over a PartitionedRouter.

    Driver contract: construct, `begin(state, plan)` while quiesced,
    then call `on_window(state, batches)` once per commit window BEFORE
    dispatching it (the controller advances one copy chunk per window,
    drains on a range conflict, activates double-write when the copy
    completes, and flips + retires — quiesced, at that same boundary —
    once `min_double_write_windows` windows ran under double-write).
    Every method that touches device state takes and returns the
    stacked state pytree; the caller (DeviceLedger attach mode, or a
    test driving the router directly) owns threading it.

    `batches` may be Transfer-object window batches or SoA ev dicts —
    conflict detection hashes ids either way, bit-identically with the
    device (shard_utils.mix_int / mix_id).

    Aborts raise MigrationAborted AFTER restoring the pre-migration
    world: overlay reverted, staged copy evicted, flight artifact
    frozen (FLIGHT_*_reshard_*). `on_recovery()` is the crash path —
    no device work (the resync rebuild supersedes it), just the
    ownership revert and the `reshard_abort` bookkeeping."""

    STAGES = ("snapshot", "copy", "double_write", "flip", "retire")

    def __init__(self, router, *, tracer=None, chunk_rows: int = 256,
                 min_double_write_windows: int = 2,
                 capacity_margin: int = 8):
        self.router = router
        self.tracer = tracer if tracer is not None \
            else getattr(router, "tracer", None) or NullTracer()
        self.chunk_rows = int(chunk_rows)
        self.min_double_write_windows = int(min_double_write_windows)
        self.capacity_margin = int(capacity_margin)
        self.plan: ReshardPlan | None = None
        self.stage = "idle"
        self.rows_copied = 0
        self.dw_windows = 0
        self.migrations: list = []   # completed-migration records
        self.aborts: list = []       # abort records
        # Test hook: when armed, the next transfer chunk's rows are
        # bit-flipped before install — the flip digest gate must catch
        # it and abort pre-flip (the gate's negative arm).
        self.corrupt_next_chunk = False
        self._snap = None
        self._cursors = None
        self._t0 = None
        self._entry = None

    # -------------------------------------------------------- queries

    @property
    def active(self) -> bool:
        return self.stage in ("copy", "double_write")

    def _pred(self):
        p = self.plan
        n = self.router.n_shards
        lo, hi, src = p.lo, p.hi, p.src
        mask = n - 1

        def inr(id128):
            h = mix_int(id128)
            return lo <= h <= hi and (h & mask) == src

        return inr

    def conflicts(self, batches) -> bool:
        """True if any id a window touches (transfer, pending, debit,
        credit) lies in the frozen range — only meaningful in the copy
        stage (afterwards double-write serves the range live)."""
        if self.stage != "copy" or not batches:
            return False
        inr = self._pred()
        for b in batches:
            if isinstance(b, dict):   # SoA ev dict
                for k in ("id", "pid", "dr", "cr"):
                    hi = np.asarray(b[f"{k}_hi"], dtype=np.uint64)
                    lo = np.asarray(b[f"{k}_lo"], dtype=np.uint64)
                    nz = (hi | lo) != 0
                    h = mix_id(hi[nz], lo[nz])
                    if bool(np.any(
                            (h >= np.uint64(self.plan.lo))
                            & (h <= np.uint64(self.plan.hi))
                            & ((h & np.uint64(self.router.n_shards - 1))
                               == np.uint64(self.plan.src)))):
                        return True
            else:                     # Transfer objects
                for t in b:
                    for i in (t.id, t.pending_id or 0,
                              t.debit_account_id or 0,
                              t.credit_account_id or 0):
                        if i and inr(i):
                            return True
        return False

    # ---------------------------------------------------------- begin

    def begin(self, state, plan: ReshardPlan, oracle=None):
        """SNAPSHOT: verify, freeze, and stage the copy. Returns the
        (unchanged) state. Call quiesced. `oracle` (optional) adds the
        oracle leg to the digest witness and supplies the range's ring
        rows (unattributable from device state alone)."""
        assert self.stage in ("idle", "done", "aborted"), self.stage
        r = self.router
        assert 0 <= plan.src < r.n_shards and 0 <= plan.dst < r.n_shards
        self.plan = plan
        self._t0 = time.monotonic()
        reverse = plan.kind == "merge_back"
        auth = plan.dst if reverse else plan.src   # authoritative copy
        recv = plan.src if reverse else plan.dst   # receiving shard
        if reverse:
            self._entry = self._find_entry(OVERLAY_MIGRATED)
            assert self._entry is not None, \
                "merge_back requires the range's OVERLAY_MIGRATED entry"
        with self.tracer.span(Event.reshard_stage, stage="snapshot",
                              outcome="ok"):
            snap = self._take_snapshot(state, auth, oracle)
            got = partitioned_range_digest(state, plan.lo, plan.hi,
                                           plan.src)[auth]
            if not _digest_eq(got, snap["digest"]):
                self._abort_noop("snapshot_digest",
                                 f"device {got} != snapshot pack")
            if oracle is not None:
                from ..ops.state_epoch import oracle_range_digest
                want = oracle_range_digest(oracle, r.a_cap, plan.lo,
                                           plan.hi, plan.src,
                                           r.n_shards)
                if not _digest_eq(got, want):
                    self._abort_noop("snapshot_oracle_digest",
                                     f"device {got} != oracle {want}")
            self._check_capacity(state, recv, snap)
        self._snap = snap
        self._cursors = dict(a=0, x=0, e=0)
        self.rows_copied = 0
        self.dw_windows = 0
        self.stage = "copy"
        return state

    def _find_entry(self, mode):
        p = self.plan
        for e in self.router.ownership.entries:
            if e[:4] == (p.lo, p.hi, p.src, p.dst) and e[4] == mode:
                return e
        return None

    def _take_snapshot(self, state, auth: int, oracle) -> dict:
        """Fetch the authoritative shard's stores and filter the
        range's rows host-side (canonical order preserved — the source
        store is canonical and the filter is order-stable)."""
        p = self.plan
        n = self.router.n_shards
        sub = jax.device_get(jax.tree.map(lambda x: x[auth], state))

        def sel(u64, count):
            h = mix_id(np.asarray(u64[:, 0], dtype=np.uint64),
                       np.asarray(u64[:, 1], dtype=np.uint64))
            live = np.arange(u64.shape[0]) < int(count)
            inr = ((h >= np.uint64(p.lo)) & (h <= np.uint64(p.hi))
                   & ((h & np.uint64(n - 1)) == np.uint64(p.src)))
            return live & inr

        a_u64 = widen(np.asarray(sub["accounts"]["u32"]))
        a_sel = sel(a_u64, sub["accounts"]["count"])
        x_u64 = widen(np.asarray(sub["transfers"]["u32"]))
        x_sel = sel(x_u64, sub["transfers"]["count"])
        a_rows = a_u64[a_sel]
        a_bal = widen(np.asarray(sub["accounts"]["bal"]))[a_sel]
        x_rows = x_u64[x_sel]
        # Orphan markers ride the transfer table only (no rows): pull
        # them straight out of the fetched packed matrix.
        packed = ht_matrix(sub["xfer_ht"])[:-1]
        kh = packed[:, :SLOTS].reshape(-1)
        kl = packed[:, SLOTS:2 * SLOTS].reshape(-1)
        v = packed[:, 2 * SLOTS:].reshape(-1).astype(
            np.int64).astype(np.int32)
        h = mix_id(kh, kl)
        o_sel = (((kh != 0) | (kl != 0)) & (v < 0)
                 & (h >= np.uint64(p.lo)) & (h <= np.uint64(p.hi))
                 & ((h & np.uint64(n - 1)) == np.uint64(p.src)))
        e_rows = np.zeros((0, EV_NCOLS), dtype=np.uint64)
        if oracle is not None:
            e_rows = self._pack_range_events(oracle)
        digest = {k: int(v2) for k, v2 in _range_digest_components(
            dict(accounts=dict(u32=narrow(a_rows), bal=narrow(a_bal),
                               count=np.int32(len(a_rows))),
                 transfers=dict(u32=narrow(x_rows),
                                count=np.int32(len(x_rows)))),
            np.uint64(p.lo), np.uint64(p.hi), np.uint64(p.src), n,
            np).items()}
        return dict(a_u64=a_rows, a_bal=a_bal, x_u64=x_rows,
                    e_u64=e_rows, o_hi=kh[o_sel], o_lo=kl[o_sel],
                    digest=digest)

    def _pack_range_events(self, sm) -> np.ndarray:
        """The range's account-event ring rows, packed from the oracle
        with dump row pointers (non-canonical scope)."""
        from ..ops.ledger import _pack_event_rows
        from .partitioned import _record_owner_id
        inr = self._pred()
        recs = [rec for rec in sm.account_events
                if inr(_record_owner_id(sm, rec))]
        if not recs:
            return np.zeros((0, EV_NCOLS), dtype=np.uint64)
        a_cap_s = self.router.a_cap // self.router.n_shards
        return widen(_pack_event_rows(recs, {}, {}, a_cap_s)["u32"])

    def _check_capacity(self, state, recv: int, snap: dict) -> None:
        """dynamic scatter starts clamp instead of trapping: the whole
        copy's room on the receiver must be proven BEFORE the first
        chunk (margin covers double-write appends while staged)."""
        counts = jax.device_get(dict(
            a=state["accounts"]["count"], x=state["transfers"]["count"],
            e=state["events"]["count"]))
        caps = dict(a=state["accounts"]["u32"].shape[1] - 1,
                    x=state["transfers"]["u32"].shape[1] - 1,
                    e=state["events"]["u32"].shape[1] - 1)
        need = dict(a=len(snap["a_u64"]), x=len(snap["x_u64"]),
                    e=len(snap["e_u64"]))
        for k in ("a", "x", "e"):
            have = caps[k] - int(np.asarray(counts[k])[recv])
            if need[k] + self.capacity_margin > have:
                self._abort_noop(
                    "capacity",
                    f"store {k}: need {need[k]}+{self.capacity_margin} "
                    f"margin, have {have} on shard {recv}")

    # ----------------------------------------------------------- copy

    def on_window(self, state, batches=None, oracle=None):
        """The per-window tick (call BEFORE dispatching the window,
        quiesced at that boundary). Copy stage: one chunk — or a full
        drain when the window conflicts with the frozen range. Double-
        write stage: count the boundary; flip + retire at the
        threshold. Idle/terminal stages: no-op."""
        if self.stage == "copy":
            if self.conflicts(batches):
                while self.stage == "copy":
                    state = self.copy_chunk(state)
            else:
                state = self.copy_chunk(state)
        elif self.stage == "double_write":
            self.dw_windows += 1
            if self.dw_windows >= self.min_double_write_windows:
                state = self.flip(state, oracle=oracle)
        return state

    def drain(self, state, oracle=None):
        """Run the in-flight migration to completion (or abort): the
        epoch-verify gate and shutdown paths call this — the whole-
        state digest is not comparable while a copy is staged."""
        while self.stage == "copy":
            state = self.copy_chunk(state)
        if self.stage == "double_write":
            state = self.flip(state, oracle=oracle)
        return state

    def copy_chunk(self, state):
        """Install the next bounded chunk; on the last one, finalize
        the receiver and activate double-write."""
        assert self.stage == "copy", self.stage
        p, snap, cur = self.plan, self._snap, self._cursors
        C = self.chunk_rows
        recv = p.src if p.kind == "merge_back" else p.dst

        def take(mat, key, ncols):
            k = min(C, len(mat) - cur[key])
            rows = np.zeros((C, ncols), dtype=np.uint64)
            if k > 0:
                rows[:k] = mat[cur[key]:cur[key] + k]
            cur[key] += k
            return rows, k

        with self.tracer.span(Event.reshard_stage, stage="copy",
                              outcome="ok"):
            a_rows, a_k = take(snap["a_u64"], "a", AC_NCOLS)
            a_bal = np.zeros((C, snap["a_bal"].shape[1]),
                             dtype=np.uint64)
            if a_k > 0:
                a_bal[:a_k] = snap["a_bal"][cur["a"] - a_k:cur["a"]]
            x_rows, x_k = take(snap["x_u64"], "x", XF_NCOLS)
            e_rows, e_k = take(snap["e_u64"], "e", EV_NCOLS)
            if self.corrupt_next_chunk and x_k > 0:
                # Fault injection: flip amount bits in the staged rows
                # only — the source stays correct, so the flip gate
                # sees source != target and must abort.
                x_rows[:x_k, XF_U64_IDX["amt_lo"]] ^= np.uint64(0xA5)
                self.corrupt_next_chunk = False
            state = _install_chunk(
                state, np.int32(recv), a_rows, a_bal, np.int32(a_k),
                x_rows, np.int32(x_k), e_rows, np.int32(e_k))
            copied = a_k + x_k + e_k
            self.rows_copied += copied
            if copied:
                self.tracer.count(Event.reshard_rows_copied,
                                  value=copied)
        done = (cur["a"] >= len(snap["a_u64"])
                and cur["x"] >= len(snap["x_u64"])
                and cur["e"] >= len(snap["e_u64"]))
        if done:
            state = self._activate_double_write(state)
        return state

    def _activate_double_write(self, state):
        """Finalize the receiver and swap in the copy-catchup overlay
        (forward: DOUBLE_WRITE appended; merge-back: the MIGRATED entry
        transitions to RETURNING). Traffic on the range resumes —
        writes now land on BOTH copies."""
        p, snap = self.plan, self._snap
        recv = p.src if p.kind == "merge_back" else p.dst
        o_cap = max(1, 1 << int(np.ceil(np.log2(
            max(1, len(snap["o_hi"]))))))
        o_hi = np.zeros(o_cap, dtype=np.uint64)
        o_lo = np.zeros(o_cap, dtype=np.uint64)
        o_hi[:len(snap["o_hi"])] = snap["o_hi"]
        o_lo[:len(snap["o_lo"])] = snap["o_lo"]
        state, ok = _finalize_shard(state, np.int32(recv), o_hi, o_lo,
                                    np.int32(len(snap["o_hi"])))
        if not bool(jax.device_get(ok)):
            return self._abort_device("table_capacity",
                                      f"receiver shard {recv}", state)
        r = self.router
        if p.kind == "merge_back":
            table = r.ownership.transition(self._entry,
                                           OVERLAY_RETURNING)
            self._entry = (p.lo, p.hi, p.src, p.dst, OVERLAY_RETURNING)
        else:
            table = r.ownership.with_entry(p.lo, p.hi, p.src, p.dst,
                                           OVERLAY_DOUBLE_WRITE)
            self._entry = (p.lo, p.hi, p.src, p.dst,
                           OVERLAY_DOUBLE_WRITE)
        r.set_ownership(table)
        self.tracer.gauge(Event.reshard_overlay_active,
                          len(table.entries))
        self.stage = "double_write"
        return state

    # ----------------------------------------------------------- flip

    def flip(self, state, oracle=None):
        """The witness-gated ownership switch (call quiesced, at a
        window boundary). Source and target range digests — content
        AND row counts — must be bit-equal; the oracle's too when the
        driver holds one. Clean: ownership moves and the stale copy
        retires in the same boundary. Mismatch: abort (overlay
        reverted, staged copy evicted, artifact frozen)."""
        assert self.stage == "double_write", self.stage
        p, r = self.plan, self.router
        comps = partitioned_range_digest(state, p.lo, p.hi, p.src)
        src_d, dst_d = comps[p.src], comps[p.dst]
        if not _digest_eq(src_d, dst_d):
            with self.tracer.span(Event.reshard_stage, stage="flip",
                                  outcome="abort"):
                return self._abort_device(
                    "digest_mismatch",
                    f"src {src_d} != dst {dst_d}", state)
        if oracle is not None:
            from ..ops.state_epoch import oracle_range_digest
            want = oracle_range_digest(oracle, r.a_cap, p.lo, p.hi,
                                       p.src, r.n_shards)
            if not _digest_eq(src_d, want):
                with self.tracer.span(Event.reshard_stage,
                                      stage="flip", outcome="abort"):
                    return self._abort_device(
                        "oracle_digest_mismatch",
                        f"device {src_d} != oracle {want}", state)
        with self.tracer.span(Event.reshard_stage, stage="flip",
                              outcome="ok"):
            if p.kind == "merge_back":
                table = r.ownership.without_entry(self._entry)
            else:
                table = r.ownership.transition(self._entry,
                                               OVERLAY_MIGRATED)
            r.set_ownership(table)
            self.tracer.gauge(
                Event.reshard_overlay_active,
                sum(1 for e in table.entries
                    if e[4] != OVERLAY_MIGRATED))
        return self._retire(state)

    def _retire(self, state):
        """Evict the now-stale copy (source forward, receiver's old
        authority on merge-back) in the same quiesced boundary as the
        flip — no window ever sees both copies as readable."""
        p = self.plan
        stale = p.dst if p.kind == "merge_back" else p.src
        with self.tracer.span(Event.reshard_stage, stage="retire",
                              outcome="ok"):
            state = _evict_range(state, np.int32(stale),
                                 np.uint64(p.lo), np.uint64(p.hi),
                                 self.router.n_shards,
                                 np.int32(p.src))
        self.migrations.append(dict(
            kind=p.kind, lo=p.lo, hi=p.hi, src=p.src, dst=p.dst,
            rows_copied=self.rows_copied,
            double_write_windows=self.dw_windows,
            duration_s=round(time.monotonic() - self._t0, 6)))
        self._reset("done")
        return state

    # ---------------------------------------------------------- abort

    def _abort_noop(self, reason: str, detail: str):
        """Abort before anything was staged on device."""
        self._record_abort(reason, detail)
        raise MigrationAborted(reason, detail)

    def _abort_device(self, reason: str, detail: str, state):
        """Abort with staged rows on the receiver: revert the overlay
        (a RETURNING merge-back reverts to MIGRATED — the pre-copy
        owner), evict the staged copy, freeze the artifact, raise."""
        p, r = self.plan, self.router
        recv = p.src if p.kind == "merge_back" else p.dst
        if self._entry is not None \
                and self._entry in r.ownership.entries:
            if p.kind == "merge_back":
                table = r.ownership.transition(self._entry,
                                               OVERLAY_MIGRATED)
            else:
                table = r.ownership.without_entry(self._entry)
            r.set_ownership(table)
            self.tracer.gauge(
                Event.reshard_overlay_active,
                sum(1 for e in table.entries
                    if e[4] != OVERLAY_MIGRATED))
        state = _evict_range(state, np.int32(recv), np.uint64(p.lo),
                             np.uint64(p.hi), r.n_shards,
                             np.int32(p.src))
        self._record_abort(reason, detail)
        err = MigrationAborted(reason, detail)
        err.state = state
        raise err

    def _record_abort(self, reason: str, detail: str) -> None:
        self.aborts.append(dict(reason=reason, detail=detail[:200],
                                stage=self.stage,
                                rows_copied=self.rows_copied))
        self.router.flight.record(
            window=getattr(self.router, "_window_seq", 0),
            route="reshard_abort", reason=reason, detail=detail[:200],
            stage=self.stage)
        self.router.flight.dump(f"reshard_abort_{reason}")
        self.tracer.count(Event.serving_recoveries,
                          cause="reshard_abort")
        self._reset("aborted")

    def on_recovery(self) -> None:
        """Crash/quarantine mid-migration: revert the overlay entry (a
        pre-flip migration serves from its old owner again) WITHOUT
        device eviction — the caller rebuilds the whole sharded state
        from the oracle (`PartitionedRouter.resync`), which places
        every range by the reverted table. Post-flip there is nothing
        to revert (the MIGRATED entry is the collapsed base override
        and the rebuild honors it)."""
        if not self.active:
            return
        r = self.router
        if self._entry is not None \
                and self._entry in r.ownership.entries:
            if self.plan.kind == "merge_back":
                table = r.ownership.transition(self._entry,
                                               OVERLAY_MIGRATED)
            else:
                table = r.ownership.without_entry(self._entry)
            r.set_ownership(table)
        self._record_abort("recovery", "crash/quarantine mid-migration")

    def _reset(self, terminal: str) -> None:
        self.stage = terminal
        self._snap = None
        self._cursors = None
        self._entry = None
        self.plan = None


# --------------------------------------------------- hot-range detector

@dataclass
class HotRangeDetector:
    """Propose-only split planner: folds per-shard routed-event counts
    (the router's device-telemetry `events_owned` words) and a decayed
    per-account hash histogram into either a split proposal for the
    hottest shard or the degenerate `unsplittable` verdict — ONE
    account carrying the load, which no hash range smaller than the
    whole shard isolates (anti-thrash: no proposal is emitted, the
    verdict names the account hash; the remedy is AT2 lane parallelism
    within the account's commit lane, not placement).

    Enacting a proposal is the driver's decision (`--auto-reshard`);
    the detector never mutates ownership."""

    n_shards: int
    hot_ratio: float = 2.0
    top_frac: float = 0.5
    decay: float = 0.5
    min_events: int = 64
    max_tracked: int = 4096
    cooldown_windows: int = 4
    _loads: np.ndarray = field(default=None, repr=False)
    _hashes: dict = field(default_factory=dict, repr=False)
    _cooldown: int = 0

    def __post_init__(self):
        assert self.n_shards & (self.n_shards - 1) == 0, self.n_shards
        self._loads = np.zeros(self.n_shards, dtype=np.float64)

    def observe_window(self, evs) -> None:
        """Fold one window's account traffic (SoA ev dicts or Transfer
        object batches): every touched account hash lands in the
        per-shard load vector and the hash histogram."""
        hs = []
        for b in evs:
            if isinstance(b, dict):
                for k in ("dr", "cr"):
                    hs.append(mix_id(
                        np.asarray(b[f"{k}_hi"], dtype=np.uint64),
                        np.asarray(b[f"{k}_lo"], dtype=np.uint64)))
            else:
                hs.append(np.array(
                    [mix_int(i) for t in b
                     for i in (t.debit_account_id,
                               t.credit_account_id) if i],
                    dtype=np.uint64))
        if not hs:
            return
        h = np.concatenate([x[x != mix_int(0)] if x.size else x
                            for x in hs])
        if h.size == 0:
            return
        shards = (h & np.uint64(self.n_shards - 1)).astype(np.int64)
        self._loads *= self.decay
        np.add.at(self._loads, shards, 1.0)
        for k in self._hashes:
            self._hashes[k] *= self.decay
        uniq, cnt = np.unique(h, return_counts=True)
        for hv, c in zip(uniq.tolist(), cnt.tolist()):
            self._hashes[hv] = self._hashes.get(hv, 0.0) + c
        if len(self._hashes) > self.max_tracked:
            keep = sorted(self._hashes.items(), key=lambda kv: -kv[1])
            self._hashes = dict(keep[:self.max_tracked // 2])
        if self._cooldown > 0:
            self._cooldown -= 1

    def propose(self) -> dict | None:
        """None while balanced (or cooling down / under-sampled); else
        {"verdict": "split", "plan": ReshardPlan, ...} or
        {"verdict": "unsplittable", ...}."""
        total = float(self._loads.sum())
        if total < self.min_events or self._cooldown > 0:
            return None
        mean = total / self.n_shards
        hot = int(self._loads.argmax())
        if self._loads[hot] < self.hot_ratio * mean:
            return None
        shard_hashes = sorted(
            (hv, w) for hv, w in self._hashes.items()
            if (hv & (self.n_shards - 1)) == hot)
        shard_w = sum(w for _, w in shard_hashes)
        if not shard_hashes or shard_w <= 0:
            return None
        top_hash, top_w = max(shard_hashes, key=lambda kv: kv[1])
        self._cooldown = self.cooldown_windows
        if top_w / shard_w >= self.top_frac:
            return dict(verdict="unsplittable", shard=hot,
                        hot_hash=int(top_hash),
                        fraction=round(top_w / shard_w, 4),
                        note="single hot account dominates: no hash "
                             "range isolates it — needs AT2 lane "
                             "parallelism, not placement")
        # Split at the weighted median hash: ~half the observed load
        # moves. dst = the coldest shard.
        acc = 0.0
        mid = shard_hashes[-1][0]
        for hv, w in shard_hashes:
            acc += w
            if acc >= shard_w / 2:
                mid = hv
                break
        dst = int(self._loads.argmin())
        if dst == hot:
            return None
        plan = ReshardPlan(lo=0, hi=int(mid), src=hot, dst=dst,
                           kind="split")
        return dict(verdict="split", shard=hot, plan=plan,
                    load=float(self._loads[hot]), mean=mean)
