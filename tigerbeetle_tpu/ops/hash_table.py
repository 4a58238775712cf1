"""Device-resident bucketized two-choice hash table for u128 keys.

The TPU-native analog of the reference's groove object cache / cache_map
(src/lsm/cache_map.zig, src/lsm/set_associative_cache.zig): id -> row-index
lookups for accounts and transfers, entirely on device, so prefetch needs no
host round-trip. The bucketized layout is the same shape as the reference's
set-associative cache (src/lsm/set_associative_cache.zig:1 — ways per set),
chosen here for a harder reason: **no data-dependent control flow**. A
linear-probing table needs a probe loop — a `lax.while_loop` whose trip
count depends on the data, which a TPU serializes and which the op-budget
lints forbid on the serving path. Two-choice bucketed hashing bounds every
lookup to exactly two bucket gathers — straight-line data flow.

Layout: B+1 buckets of S = 8 slots, held as ONE FLAT u32 array, STRIDE
words a bucket (see ht_init); bucket B is a write-dump scratch row so
masked-out scatter lanes never alias a live slot.
Key 0 is the empty sentinel — valid object ids are never 0
(id_must_not_be_zero precedes every insert). A key lives in one of two
buckets chosen by independent hashes; inserts fill buckets as prefix of the
slot axis (occupancy == number of leading non-empty slots, an invariant the
planner relies on; the table is insert-only). Two-choice with S = 8 keeps
overflow probability negligible below ~90% load; tables are sized 2x, and
an insert that finds both buckets full reports failure (the caller treats
it as a capacity fallback) instead of probing unboundedly.

All entry points are shape-stable, loop-free, and deterministic: batch
inserts resolve intra-batch bucket contention by ranking contenders with a
stable sort on (bucket, batch index), so table contents are bit-identical
for identical inputs regardless of scheduling.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .ev_layout import narrow, widen

SLOTS = 8
# u32 words of one bucket row: (key_hi | key_lo | val) groups of SLOTS
# u64 words, each as its (low, high) halves.
ROW = 3 * SLOTS * 2
# A bucket starts every STRIDE words, so that two buckets fill one
# 128-lane row of the chip's memory exactly; the words past ROW stay 0.
LANES = 128
STRIDE = LANES // 2

# Sentinel value for orphaned (transiently-failed) transfer ids stored
# inline in the transfer table: the id sets are disjoint forever
# (id_already_failed is permanent), so sign distinguishes a live row
# index (>= 0) from an orphan marker with one probe.
ORPHAN_VAL = -2

_C1 = np.uint64(0x9E3779B97F4A7C15)
_C2 = np.uint64(0xBF58476D1CE4E5B9)
_C3 = np.uint64(0xD6E8FEB86659FD93)
_C4 = np.uint64(0x2545F4914F6CDD1D)


def ht_init(cap: int) -> dict:
    """cap must be a power of two >= 2*SLOTS, sized >= 2x expected live
    keys; B = cap // SLOTS buckets of SLOTS slots (+ one dump bucket).

    Layout: ONE FLAT u32 array. Bucket r is words [r*STRIDE,
    r*STRIDE + ROW): the (key_hi | key_lo | val) groups of SLOTS u64
    words each, every word as its (low, high) u32 halves; `ht_matrix`
    is the host's view of it, the (b+1, 3*SLOTS) u64 matrix. An even
    number of buckets is allocated (b+1 and one never used), so the
    array is whole 128-lane rows.
    Flat, u32 and lane-aligned because of what a v5e does with the
    alternatives (PR 32, PERF.md §6): a u64 table is split and
    recombined whole around every program that takes it; an element
    scatter into a 2-D table, or a reshape of a (b+1, ROW) table to
    1-D, relayouts the whole table. An element scatter into a 1-D u32
    array runs in place, and reshaping a flat array to rows of exactly
    128 lanes moves nothing (the two are the same bytes in the chip's
    tiled memory), so a probe stays ONE row gather of 512 contiguous
    bytes a lane."""
    assert cap & (cap - 1) == 0 and cap >= 2 * SLOTS
    b = cap // SLOTS
    return dict(
        packed=jnp.zeros(((b + 2) * STRIDE,), dtype=jnp.uint32),
    )


def ht_buckets(table: dict) -> int:
    """b: live buckets (the dump bucket b and the spare excluded). The
    word axis is the last one (a partitioned state stacks shards in
    front)."""
    return table["packed"].shape[-1] // STRIDE - 2


def ht_cap(table: dict) -> int:
    return ht_buckets(table) * SLOTS


def ht_matrix(table: dict) -> np.ndarray:
    """Host view of a table: the (b+1, 3*SLOTS) u64 matrix of
    (key_hi | key_lo | val) column groups, one bucket a row."""
    flat = np.ascontiguousarray(np.asarray(table["packed"]))
    return flat.view(np.uint64).reshape(-1, STRIDE // 2)[:-1, :3 * SLOTS]


def ht_unpack(packed):
    """Device counterpart of `ht_matrix` over ONE table's flat array
    (the spare bucket's row included): a pass over the whole table, for
    control-plane kernels (resharding) only — a serving program probes
    with `ht_rows`."""
    return widen(packed.reshape(-1, STRIDE)[:, :ROW])


def ht_pack(matrix):
    """Inverse of `ht_unpack`."""
    return jnp.pad(narrow(matrix), ((0, 0), (0, STRIDE - ROW))).reshape(-1)


def bucket_rows(lanes, buckets):
    """The buckets' rows, (N, 3*SLOTS) u64, out of the table seen as
    128-lane rows (`packed.reshape(-1, LANES)`, two buckets a row): ONE
    row gather, then the bucket's half of each gathered row, widened."""
    g = lanes[buckets >> 1]
    g = jnp.where((buckets & 1).astype(jnp.bool_)[:, None],
                  g[:, STRIDE:], g[:, :STRIDE])
    return widen(g[:, :ROW])


def ht_rows(table: dict, buckets):
    """Gather bucket rows: (N, 3*SLOTS) u64, ONE row gather."""
    return bucket_rows(table["packed"].reshape(-1, LANES), buckets)


def _buckets(k_hi, k_lo, b: int):
    """Two independent bucket choices in [0, b)."""
    h1 = (k_lo ^ (k_hi * _C1)) * _C2
    h1 = h1 ^ (h1 >> jnp.uint64(31))
    h2 = (k_hi ^ (k_lo * _C3)) * _C4
    h2 = h2 ^ (h2 >> jnp.uint64(29))
    mask = jnp.uint64(b - 1)
    return ((h1 & mask).astype(jnp.int32), (h2 & mask).astype(jnp.int32))


def match_bucket(g, k_hi, k_lo, querying):
    """Slot match + value select over one gathered packed-row block
    (N, 3*SLOTS). The ONE source of truth for probe semantics — shared
    by the XLA lookup and the fused Pallas kernel body."""
    s_hi = g[:, :SLOTS]
    s_lo = g[:, SLOTS:2 * SLOTS]
    s_val = g[:, 2 * SLOTS:].astype(jnp.int32)
    match = ((s_hi == k_hi[:, None]) & (s_lo == k_lo[:, None])
             & querying[:, None])
    hit = jnp.any(match, axis=1)
    lane_val = jnp.max(jnp.where(match, s_val, jnp.int32(-1)), axis=1)
    return hit, lane_val


def ht_lookup(table: dict, k_hi, k_lo):
    """Vectorized lookup. Returns (found: bool[N], val: int32[N]).

    Exactly two bucket gathers per query (ONE packed row each); keys
    equal to the sentinel (0) are reported as absent. Absence is
    definitive: a key can only ever reside in one of its two buckets.

    NOTE: negative stored vals (ORPHAN_VAL) surface as -1, not their
    stored value — the miss filler (-1) wins the lane max-reduce. Test
    `found & (val >= 0)` for a live row and `found & (val < 0)` for an
    orphan marker; never compare a lookup val to ORPHAN_VAL itself
    (ht_live_items returns exact stored vals when those are needed)."""
    b = ht_buckets(table)
    querying = ~((k_hi == 0) & (k_lo == 0))
    b1, b2 = _buckets(k_hi, k_lo, b)
    found = jnp.zeros_like(querying)
    val = jnp.full(k_hi.shape, -1, dtype=jnp.int32)
    for rows in (b1, b2):
        hit, lane_val = match_bucket(
            ht_rows(table, rows), k_hi, k_lo, querying)
        found = found | hit
        val = jnp.where(hit, lane_val, val)
    return found, val


def _rank_within(bucket, active, n):
    """Stable rank of each active lane among active lanes with the same
    bucket value (0-based, in batch order). Loop-free: one stable argsort
    of (bucket, lane) with inactive lanes pushed to the end."""
    idx = jnp.arange(n, dtype=jnp.int32)
    big = jnp.int64(1) << jnp.int64(62)
    key = jnp.where(
        active,
        (bucket.astype(jnp.int64) << jnp.int64(32)) | idx.astype(jnp.int64),
        big + idx.astype(jnp.int64))
    order = jnp.argsort(key).astype(jnp.int32)  # stable
    b_sorted = bucket[order]
    a_sorted = active[order]
    is_start = jnp.concatenate([
        jnp.ones(1, dtype=jnp.bool_),
        (b_sorted[1:] != b_sorted[:-1]) | ~a_sorted[:-1]])
    pos = jnp.arange(n, dtype=jnp.int32)
    # associative_scan, not jnp.cumsum: cumsum lowers to reduce-window on
    # TPU, whose scoped-vmem footprint blows the v5e budget (see the
    # fast-kernels _cumsum note). Per-entry segment start = forward-fill
    # of start positions with ONE running max (start positions increase)
    # — not a segment reduce + gather (op budget).
    seg_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, pos, jnp.int32(-1)))
    rank_sorted = pos - seg_start
    rank = jnp.zeros(n, dtype=jnp.int32).at[order].set(rank_sorted)
    return jnp.where(active, rank, jnp.int32(0))


def ht_plan(table: dict, k_hi, k_lo, mask):
    """Plan a batch insert WITHOUT touching the table: returns
    (pos: int32[N] flat slot index, ok: bool scalar). Caller guarantees
    masked keys are unique and absent.

    Round 1 places each key at the tail of its less-loaded bucket, ranking
    intra-batch contenders stably by batch index; lanes that overflow SLOTS
    retry in their other bucket in round 2 (accounting for round-1
    placements). ok=False if any masked lane remains unplaced — the caller
    treats that as a capacity fallback and aborts the batch's writes.

    Separating plan from write lets callers compute a global commit/abort
    decision first and then apply all writes masked — no state copies for
    the abort path."""
    b = ht_buckets(table)
    n = k_hi.shape[0]
    dump = jnp.int32(b * SLOTS)
    b1, b2 = _buckets(k_hi, k_lo, b)

    g1 = ht_rows(table, b1)
    g2 = ht_rows(table, b2)
    occ1 = jnp.sum(
        (g1[:, :SLOTS] != 0) | (g1[:, SLOTS:2 * SLOTS] != 0), axis=1
    ).astype(jnp.int32)
    occ2 = jnp.sum(
        (g2[:, :SLOTS] != 0) | (g2[:, SLOTS:2 * SLOTS] != 0), axis=1
    ).astype(jnp.int32)

    take1 = occ1 <= occ2
    tgt = jnp.where(take1, b1, b2)
    alt = jnp.where(take1, b2, b1)
    occ_t = jnp.where(take1, occ1, occ2)
    occ_a = jnp.where(take1, occ2, occ1)

    # Round 1: rank contenders per target bucket, append after occupancy.
    r1 = _rank_within(tgt, mask, n)
    slot1 = occ_t + r1
    placed1 = mask & (slot1 < SLOTS)

    # Round 2: overflow lanes retry their other bucket. Effective occupancy
    # includes round-1 placements into that bucket.
    retry = mask & ~placed1
    placed1_per_bucket = jax.ops.segment_sum(
        placed1.astype(jnp.int32), jnp.where(placed1, tgt, b),
        num_segments=b + 1)
    r2 = _rank_within(alt, retry, n)
    slot2 = occ_a + placed1_per_bucket[alt] + r2
    placed2 = retry & (slot2 < SLOTS)

    pos = jnp.where(
        placed1, tgt * SLOTS + slot1,
        jnp.where(placed2, alt * SLOTS + slot2, dump))
    ok = jnp.all(placed1 | placed2 | ~mask)
    return pos, ok


def ht_write(table: dict, pos, k_hi, k_lo, vals, mask):
    """Apply a planned insert: ONE masked element scatter into the flat
    table (the dump bucket absorbs masked-out lanes). `pos` is a flat
    bucket*SLOTS+slot index; the word index of a u64 lane's low half is
    bucket*STRIDE + (group*SLOTS + slot)*2, the high half follows it."""
    b = ht_buckets(table)
    wpos = jnp.where(mask, pos, jnp.int32(b * SLOTS))
    bucket = wpos // SLOTS
    slot = wpos % SLOTS
    base = bucket * jnp.int32(STRIDE) + slot * 2
    offsets = np.array([g * SLOTS * 2 + h
                        for g in range(3) for h in range(2)], np.int32)
    # vals are int32 row indexes or negative markers: the stored u64
    # word is their sign extension.
    data = narrow(jnp.stack([k_hi, k_lo, vals.astype(jnp.uint64)], axis=1))
    return {"packed": table["packed"].at[
        (base[:, None] + offsets).reshape(-1)].set(data.reshape(-1))}


def ht_insert(table: dict, k_hi, k_lo, vals, mask):
    """plan + write in one call. Returns (table, ok). On ok=False nothing
    is written (the whole masked set is rejected atomically, matching the
    capacity-fallback contract)."""
    pos, ok = ht_plan(table, k_hi, k_lo, mask)
    table = ht_write(table, pos, k_hi, k_lo, vals, mask & ok)
    return table, ok


def ht_live_items(table: dict):
    """Host helper: (key_hi, key_lo, val) numpy arrays of all live slots
    (dump bucket excluded). val is int32 — negative values are sentinel
    markers (ORPHAN_VAL), non-negative are row indexes."""
    p = ht_matrix(table)[:-1]
    kh = p[:, :SLOTS].reshape(-1)
    kl = p[:, SLOTS:2 * SLOTS].reshape(-1)
    v = p[:, 2 * SLOTS:].reshape(-1).astype(np.int64).astype(np.int32)
    live = (kh != 0) | (kl != 0)
    return kh[live], kl[live], v[live]


# Jitted entry point for host-driven batch inserts (the mirror regime's
# delta pushes call this repeatedly; without jit the sort inside would
# re-trace on every call).
ht_insert_jit = jax.jit(ht_insert, donate_argnums=0)
