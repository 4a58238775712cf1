"""Partitioned ledger state: account-hash sharding with an on-device
event exchange and owner-masked write-back.

parallel/full_sharded.py scales the per-event FLOPs but replicates the
WHOLE ledger on every chip — state size is clamped to one device's HBM.
This module removes that clamp: every store (accounts, transfer rows,
the two hash tables, the event ring) is sharded over the mesh axis by a
deterministic id hash (shard_utils.shard_of_id), so per-device resident
state is ~1/n_shards of the replicated route.

The semantic license is AT2's (PAPERS.md): transfer ordering only
matters per account, so cross-shard coordination is only needed for the
compact per-event bundle — never for state. One `shard_map` body runs
the whole step:

  1. PROBE + EXCHANGE (phase 1, transfers): every shard looks up the
     batch's transfer ids and pending ids in its LOCAL table and
     contributes (encoded hit, masked row) lanes to ONE dense `psum`.
     The partitioned-storage invariant — each key lives on exactly one
     shard — makes the sum a select: afterwards every shard holds the
     global lookup result and the owning shard's row for every lane.
  2. PROBE + EXCHANGE (phase 2, accounts): same exchange for the 4N
     account keys the batch can touch (ev.dr, ev.cr, and the pending
     rows' dr/cr from phase 1), carrying the packed account row and the
     balance limbs.
  3. ASSEMBLE: the exchanged rows are deduplicated (first-occurrence
     over the 128-bit keys) into a replicated O(batch) MINI-STATE —
     init_state-shaped, with its own small hash tables — whose row
     pointers are rewritten mini-locally. This is the narrow two-phase
     join: cross-shard transfers resolve against the assembled bundle,
     not against remote state.
  4. JUDGE: the UNMODIFIED single-chip kernel stack
     (per_event_status + create_transfers_fast, any tier) runs on the
     mini-state, replicated. Bit-exactness vs the single-chip route is
     inherited, not re-proved: the kernel sees exactly the rows it
     would have gathered from the full store.
  5. WRITE-BACK: each shard applies the mini's changes to the rows it
     owns — appended transfer rows and ring rows land at the local
     counts, pending-status flips rewrite the (alone-in-its-column)
     pstat word, touched accounts write back the full packed row +
     limbs, and the new ids plan/write into the local hash table. All
     writes are masked by a psum-combined ok (kernel fallback, local
     capacity, exchange overflow): a failed batch leaves every shard
     bit-identical, preserving the escalation/replay contract.

The five steps above are one prepare's worth of work
(_partitioned_batch_body). Two dispatch forms share it:

  * PER BATCH (make_partitioned_create_transfers): one shard_map
    dispatch per prepare — the escalation unit, and the replay path
    for a window's fallen-back suffix.
  * CHAIN (make_partitioned_chain_create_transfers, the DEFAULT window
    route): the W prepares of a commit window run as a `lax.scan`
    carry over the donated sharded state INSIDE one shard_map
    dispatch, with a rolling poison scalar in the carry — the
    single-chip chain kernel's transitive-poison contract
    (ops/fast_kernels.py _create_transfers_chain), composed with the
    exchange. Collectives run inside the scan body; jaxhound's
    scan_body_census budgets them (body ops == the per-batch
    partitioned tier, whole-program ops flat in W —
    perf/opbudget_r09.json).

Non-canonical columns: transfer `dr_row`/`cr_row` and the ring's row
pointers are SHARD-LOCAL (or mini-scope, for ring rows) under the
partitioned layout. They were already excluded from the state-epoch
digest and re-derived by every consumer (the exchange rewrites them
from the id columns on assembly), so bit-comparability is unaffected.

Fallback/overflow: the exchange has a static per-shard capacity (the
mini-state caps and the per-shard table/row headroom). A breach is a
per-cause host fallback exactly like the replicated router's —
`shard_capacity` / `exchange_overflow` ride out["fb_causes"].
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.ev_layout import (
    AC_NCOLS, EV_NCOLS, EV_P32_POS, XF_NCOLS, XF_PSTAT_COL32, XF_U64_IDX,
    XF_P32_POS, ev_cap, narrow, pack32, widen, with_col32,
)
from ..ops.fast_kernels import (
    _CREATED,
    _TRANSIENT_CODES,
    _cumsum,
    create_transfers_fast,
    imported_batch_ctx,
    per_event_status,
)
from ..ops.hash_table import (
    ORPHAN_VAL, ht_init, ht_insert, ht_lookup, ht_plan, ht_write,
)
from ..ops.ledger import (
    N_PAD, _delta_gather_body, _pad_bucket, pad_transfer_events,
)
from ..trace import Event, FlightRecorder, Histogram, NullTracer
from .full_sharded import MODES, _MODE_KWARGS, ShardedRouter
from .shard_utils import (
    OwnershipTable, owner_read, owner_read_int,
    shard_of_id, shard_of_int, writes_here,
)

__all__ = ["make_partitioned_create_transfers",
           "make_partitioned_chain_create_transfers",
           "stack_partitioned_window", "partitioned_from_oracle",
           "partitioned_state_bytes", "PartitionedRouter", "MODES",
           "TEL_WORDS", "TEL_LAYOUT", "TEL_CAUSES", "decode_telemetry"]

_U64_MAX = np.uint64(0xFFFFFFFFFFFFFFFF)
_XF_DRROW_COL = XF_P32_POS["dr_row"][0]   # ("dr_row","cr_row") word


def _psum_u64(x, axis):
    """psum over u64 lanes, as a signed sum. XLA:TPU lowers a 64-bit
    all-reduce for s64 but not for u64 ("UNIMPLEMENTED: Supported
    lowering only of Sum all reduce" — the fused window step compiled
    for a described v5e 2x2, PR 23). Two's-complement addition is the
    same bits either way, and the exchange's sums are selects (one
    shard contributes, the rest add zero)."""
    return jax.lax.bitcast_convert_type(
        jax.lax.psum(jax.lax.bitcast_convert_type(x, jnp.int64), axis),
        jnp.uint64)


def _uniq_rows(k_hi, k_lo, active):
    """First-occurrence dedupe of 128-bit keys over the exchange lanes.

    Returns (first: bool[N] — the one lane per distinct active key that
    builds the mini row, row: int32[N] — that key's dense mini row on
    EVERY lane carrying it (-1 on inactive lanes), n: int32 — number of
    distinct active keys). Inactive lanes sort to a MAX-key block at
    the end (valid object ids are never 2^128-1), so active runs get
    the dense rank prefix."""
    n = k_hi.shape[0]
    kh = jnp.where(active, k_hi, _U64_MAX)
    kl = jnp.where(active, k_lo, _U64_MAX)
    perm = jnp.lexsort((kl, kh))  # stable: primary kh, secondary kl
    khs, kls = kh[perm], kl[perm]
    first_s = jnp.concatenate([
        jnp.ones((1,), bool),
        (khs[1:] != khs[:-1]) | (kls[1:] != kls[:-1])])
    act_s = active[perm]
    run = _cumsum(first_s.astype(jnp.int32)) - 1
    n_uniq = jnp.sum((first_s & act_s).astype(jnp.int32))
    first = jnp.zeros(n, bool).at[perm].set(first_s & act_s)
    row = jnp.zeros(n, jnp.int32).at[perm].set(run)
    return first, jnp.where(active, row, jnp.int32(-1)), n_uniq


# ------------------------------------------------------- telemetry plane
#
# A fixed-layout u32 block per (shard, prepare), built from values the
# exchange body already computes — elementwise packing only, so the
# heavy-op identity "chain body == per-batch partitioned tier" survives
# (gate-pinned in perf/opbudget_r*.json). Words 0-6 and 10 are
# REPLICATED (equal on every shard: they summarize the replicated mini
# judgment / exchange); words 7-9 and 11 are PER-SHARD.
TEL_LAYOUT = (
    "fix_rounds",            # 0  fixpoint rounds consumed (0 = plain)
    "poison_cause",          # 1  priority-encoded cause, 0 = clean
    "xchg1_occupancy",       # 2  live transfer rows in the 2N phase-1 lanes
    "xchg1_capacity",        # 3  phase-1 lane capacity (2N)
    "xchg2_occupancy",       # 4  distinct account keys in the 4N phase-2 lanes
    "xchg2_capacity",        # 5  phase-2 mini capacity (4N)
    "cross_shard_transfers",  # 6  created transfers whose dr/cr shards differ
    "ring_occupancy",        # 7  event-ring rows after write-back (per shard)
    "writeback_transfers",   # 8  owner-masked rows written (per shard)
    "events_owned",          # 9  valid events routed to this shard
    "exchange_overflow",     # 10 0/1: a phase capacity breached
    "shard_capacity_hit",    # 11 0/1: THIS shard's store/ring/plan capacity
)
TEL_WORDS = len(TEL_LAYOUT)

# poison_cause codes: index+1 into this tuple; the EARLIEST listed cause
# that fired wins (a forced/transitive poison only shows when no
# intrinsic cause explains the prepare). Mirrors out["fb_causes"] plus
# the exchange breaches.
TEL_CAUSES = (
    "e1_hard_flags", "e2_collision", "e3_limit", "e4_overflow",
    "e5_void_closing", "closing", "capacity", "forced",
    "shard_capacity", "exchange_overflow",
)


@functools.partial(jax.jit, inline=False)
def _telemetry_pack(*words):
    """Pack the telemetry words into one u32 vector. Kept as a NAMED
    nested jit (inline=False) so the call survives as a pjit equation
    in the lowered jaxpr: jaxhound.telemetry_census finds it by name
    and counts its input lanes against the committed lane budget."""
    return jnp.stack([jnp.asarray(w).astype(jnp.uint32) for w in words])


def _telemetry_block(out, fbc, *, n_lanes, n_a, n_live, xchg_bad,
                     bad_l, cross_shard, ring_count, n_mine_ok, owned):
    """Assemble the per-(shard, prepare) telemetry vector from the
    body's existing intermediates. Elementwise ops + the pack only —
    zero heavy-op delta, no extra collectives (per-shard words ride the
    sh subtree the shard_map already returns)."""
    cause = jnp.uint32(0)
    for i, name in reversed(list(enumerate(TEL_CAUSES, start=1))):
        cause = jnp.where(fbc[name], jnp.uint32(i), cause)
    return _telemetry_pack(
        out["fix_rounds"], cause,
        n_live, jnp.int32(2 * n_lanes),
        n_a, jnp.int32(4 * n_lanes),
        cross_shard, ring_count, n_mine_ok, owned,
        xchg_bad, bad_l)


def decode_telemetry(tel) -> dict:
    """Host-side decode: [..., TEL_WORDS] u32 -> {name: int array}.
    The leading axes are whatever the harvest kept (shard, or
    shard x W for the fused chain)."""
    arr = np.asarray(tel, dtype=np.uint32)
    assert arr.shape[-1] == TEL_WORDS, arr.shape
    return {name: arr[..., i].astype(np.int64)
            for i, name in enumerate(TEL_LAYOUT)}


def _partitioned_batch_body(sub, ev, timestamp, n, *, axis, n_dev,
                            mode, force_fallback=None, telemetry=True,
                            overlay=()):
    """One prepare against the per-shard state `sub` (UNSTACKED
    leaves): the full exchange -> mini-state -> judge -> write-back
    anatomy of the module docstring, shared VERBATIM by the per-batch
    shard_map body and the chain route's lax.scan body (one scan
    iteration == one per-batch dispatch's ops — the budget identity
    perf/opbudget_r*.json pins).

    `force_fallback` is the chain's rolling poison scalar: threaded
    into the judge it aborts the batch unconditionally, the masked
    write-back leaves every shard bit-identical, and the poison rides
    out through rep["fallback"] — the single-chip chain kernel's
    transitive-poison contract. Returns (new_sub, rep, events_owned,
    tel) where rep is the replicated out dict, events_owned the
    per-shard routed-event count, and tel the TEL_WORDS u32 telemetry
    vector (None when `telemetry` is off — the overhead-probe
    baseline).

    `overlay` is the elastic-shards ownership override table
    (shard_utils OwnershipTable.entries), baked in as a static closure
    constant. The exchange's "each key lives on exactly one shard"
    invariant — which makes each psum a select — breaks while a range
    is mid-migration (its rows exist on BOTH owners), so with a
    non-empty overlay every probe CONTRIBUTION is masked by
    read-ownership (only the authoritative copy feeds the psum) and
    every write-back mask generalizes from `shard_of_id == me` to
    `writes_here` (the copy-catchup owner applies the same rows at its
    own local positions). An EMPTY overlay takes the original code
    paths verbatim — byte-identical lowering, so the pinned op budgets
    and jaxhound signatures never see elastic shards unless one is
    actually live."""
    N = ev["id_lo"].shape[0]
    me = jax.lax.axis_index(axis)
    idxs = jnp.arange(N, dtype=jnp.int32)
    ts_full = (timestamp - n.astype(jnp.uint64)
               + idxs.astype(jnp.uint64) + jnp.uint64(1))
    acc, xfr, evr = (sub["accounts"], sub["transfers"],
                     sub["events"])
    a_dump_l = acc["u32"].shape[0] - 1
    t_dump_l = xfr["u32"].shape[0] - 1
    e_cap_l = ev_cap(evr)

    # ---- phase 1: transfer-key probe + exchange (2N lanes:
    # [ev.id | ev.pid]). Encoding in lane 0 of the exchanged
    # row: 0 = absent, 1 = orphan (ht_lookup reports stored
    # ORPHAN_VAL as val=-1), r+2 = live owner-local row r.
    xk_hi = jnp.concatenate([ev["id_hi"], ev["pid_hi"]])
    xk_lo = jnp.concatenate([ev["id_lo"], ev["pid_lo"]])
    xf_raw, xv_l = ht_lookup(sub["xfer_ht"], xk_hi, xk_lo)
    if overlay:
        # Mid-migration a range's rows exist on BOTH owners: only the
        # READ owner's copy may feed the psum, or the "sum is a
        # select" exchange invariant breaks.
        read_mine_x = owner_read(xk_hi, xk_lo, n_dev, overlay) == me
        xf_l = xf_raw & read_mine_x
    else:
        xf_l = xf_raw
    x_live_l = xf_l & (xv_l >= 0)
    enc_l = jnp.where(
        xf_l, (xv_l + 2).astype(jnp.uint64), jnp.uint64(0))
    # The local copy's rows (u32, as stored): the exchange widens
    # them; the pstat write-back below rewrites them whole. Gathered
    # wherever this shard HOLDS the key, read owner or not.
    xraw_l = xfr["u32"][
        jnp.where(xf_raw & (xv_l >= 0), xv_l, t_dump_l)]
    xdata_l = jnp.where(x_live_l[:, None], widen(xraw_l),
                        jnp.uint64(0))
    g = _psum_u64(
        jnp.concatenate([enc_l[:, None], xdata_l], axis=1), axis)
    g_enc, g_rows = g[:, 0], g[:, 1:]
    x_active = g_enc > 0
    x_live = g_enc >= 2

    # ---- phase 2: account-key probe + exchange (4N lanes:
    # [ev.dr | ev.cr | p.dr | p.cr]; the pending rows' account
    # ids come off the phase-1 exchange). Encoding: 0 = absent,
    # r+1 = owner-local row r. Zero keys (padded lanes, absent
    # pendings) hit the hash table's empty sentinel -> absent.
    p_rows_g = g_rows[N:]
    ak_hi = jnp.concatenate([
        ev["dr_hi"], ev["cr_hi"],
        p_rows_g[:, XF_U64_IDX["dr_hi"]],
        p_rows_g[:, XF_U64_IDX["cr_hi"]]])
    ak_lo = jnp.concatenate([
        ev["dr_lo"], ev["cr_lo"],
        p_rows_g[:, XF_U64_IDX["dr_lo"]],
        p_rows_g[:, XF_U64_IDX["cr_lo"]]])
    af_raw, ar_l = ht_lookup(sub["acct_ht"], ak_hi, ak_lo)
    if overlay:
        read_mine_a = owner_read(ak_hi, ak_lo, n_dev, overlay) == me
        af_l = af_raw & read_mine_a
    else:
        af_l = af_raw
    aenc_l = jnp.where(
        af_l, (ar_l + 1).astype(jnp.uint64), jnp.uint64(0))
    arow_g_l = jnp.where(af_l, ar_l, a_dump_l)
    au_l = jnp.where(af_l[:, None],
                     widen(acc["u32"][arow_g_l]), jnp.uint64(0))
    ab_l = jnp.where(af_l[:, None],
                     widen(acc["bal"][arow_g_l]), jnp.uint64(0))
    ga = _psum_u64(
        jnp.concatenate([aenc_l[:, None], au_l, ab_l], axis=1),
        axis)
    g_aenc = ga[:, 0]
    g_au = ga[:, 1:1 + AC_NCOLS]
    g_ab = ga[:, 1 + AC_NCOLS:]
    a_active = g_aenc > 0

    # ---- assemble the replicated mini-state (O(batch) caps).
    MA, MT, ME = 4 * N, 3 * N, N
    afirst, amrow, n_a = _uniq_rows(ak_hi, ak_lo, a_active)
    mini_au = jnp.zeros((MA + 1, AC_NCOLS), jnp.uint64).at[
        jnp.where(afirst, amrow, MA)].set(g_au).at[MA].set(
        jnp.uint64(0))
    mini_ab = jnp.zeros((MA + 1, 16), jnp.uint64).at[
        jnp.where(afirst, amrow, MA)].set(g_ab).at[MA].set(
        jnp.uint64(0))
    ht_a, ok_a = ht_insert(
        ht_init(8 * N), ak_hi, ak_lo, amrow, afirst)

    xfirst, _, _ = _uniq_rows(xk_hi, xk_lo, x_active)
    lfirst, lrow, n_live = _uniq_rows(xk_hi, xk_lo, x_live)
    mini_xu = jnp.zeros((MT + 1, XF_NCOLS), jnp.uint64).at[
        jnp.where(lfirst, lrow, MT)].set(g_rows).at[MT].set(
        jnp.uint64(0))
    # Mini-local row pointers: rewrite each exchanged row's
    # (dr_row, cr_row) word from its OWN id columns through the
    # mini account table (absent -> mini dump row). Only the
    # pending rows' pointers are ever dereferenced, and their
    # dr/cr are in the phase-2 key set by construction.
    mdr_hi = mini_xu[:, XF_U64_IDX["dr_hi"]]
    mdr_lo = mini_xu[:, XF_U64_IDX["dr_lo"]]
    mcr_hi = mini_xu[:, XF_U64_IDX["cr_hi"]]
    mcr_lo = mini_xu[:, XF_U64_IDX["cr_lo"]]
    fdr, rdr = ht_lookup(ht_a, mdr_hi, mdr_lo)
    fcr, rcr = ht_lookup(ht_a, mcr_hi, mcr_lo)
    has_ids = (mdr_hi | mdr_lo) != 0
    ptr_word = pack32(jnp.where(fdr, rdr, MA),
                      jnp.where(fcr, rcr, MA))
    mini_xu = mini_xu.at[:, _XF_DRROW_COL].set(
        jnp.where(has_ids, ptr_word,
                  mini_xu[:, _XF_DRROW_COL]))
    ht_x, ok_x = ht_insert(
        ht_init(8 * N), xk_hi, xk_lo,
        jnp.where(x_live, lrow, jnp.int32(ORPHAN_VAL)), xfirst)
    xchg_bad = (~ok_a) | (~ok_x) | (n_a > MA) | (n_live > 2 * N)

    # Ring prefill (p_row=-1 / tflags=0xFFFFFFFF) built ON
    # DEVICE by column sets — never as a host closure constant.
    mini_ev = jnp.zeros((ME + 1, 2 * EV_NCOLS), jnp.uint32)
    for name in ("p_row", "tflags"):
        col, half = EV_P32_POS[name]
        mini_ev = mini_ev.at[:, 2 * col + half].set(
            jnp.uint32(0xFFFFFFFF))

    mini = dict(
        accounts=dict(u32=narrow(mini_au), bal=narrow(mini_ab),
                      count=n_a),
        transfers=dict(u32=narrow(mini_xu), count=n_live),
        events=dict(u32=mini_ev, count=jnp.int32(0)),
        acct_ht=ht_a,
        xfer_ht=ht_x,
        # Scalars are stored per shard but hold GLOBAL values.
        acct_key_max=sub["acct_key_max"],
        xfer_key_max=sub["xfer_key_max"],
        pulse_next=sub["pulse_next"],
        commit_ts=sub["commit_ts"],
    )

    # ---- judge: the unmodified single-chip kernel on the
    # mini-state, replicated. The imported tier's account-ts
    # collision is the only batch-context piece that needs the
    # FULL table: each shard probes its sorted local column and
    # the memberships OR-combine over the mesh.
    ictx = None
    if mode == "imported":
        ctx_l = imported_batch_ctx(sub, ev, ts_full,
                                   ev["valid"], idxs)
        ictx = dict(ctx_l)
        ictx["acct_ts_collision"] = jax.lax.psum(
            ctx_l["acct_ts_collision"].astype(jnp.int32),
            axis) > 0
    pe = per_event_status(mini, ev, ts_full, imported_ctx=ictx)
    mini_t0 = n_live
    kw = dict(_MODE_KWARGS[mode])
    if force_fallback is not None:
        kw["force_fallback"] = force_fallback
    new_mini, out = create_transfers_fast(
        mini, ev, timestamp, n, per_event=pe, **kw)

    # ---- per-shard write-back plan + combined ok.
    status = out["r_status"]
    created = ev["valid"] & (status == _CREATED)
    transient = jnp.zeros_like(created)
    for code in _TRANSIENT_CODES:
        transient = transient | (status == code)
    orphan_new = ev["valid"] & transient
    ins_mask = created | orphan_new
    if overlay:
        owner_ev = owner_read(ev["id_hi"], ev["id_lo"], n_dev, overlay)
        wr_ev = writes_here(ev["id_hi"], ev["id_lo"], n_dev, me,
                            overlay)
    else:
        owner_ev = shard_of_id(ev["id_hi"], ev["id_lo"], n_dev)
        wr_ev = owner_ev == me
    mine = created & wr_ev
    ins_mine = ins_mask & wr_ev
    n_mine = jnp.sum(mine.astype(jnp.int32))
    local_rank = _cumsum(mine.astype(jnp.int32)) - mine
    pos, ok_pl = ht_plan(sub["xfer_ht"], ev["id_hi"],
                         ev["id_lo"], ins_mine)
    bad_l = ((xfr["count"] + n_mine > t_dump_l)
             | (evr["count"] + n_mine > e_cap_l)
             | ~ok_pl)
    bad = jax.lax.psum(bad_l.astype(jnp.int32), axis) > 0
    g_ok = (~out["fallback"]) & (~bad) & (~xchg_bad)

    # ---- write-back (every write masked by g_ok; the dump
    # rows absorb masked lanes, exactly the kernel's idiom).
    row_off = _cumsum(created.astype(jnp.int32)) - created
    mini_trow = jnp.clip(mini_t0 + row_off, 0, MT)
    dest_t = jnp.where(mine & g_ok,
                       xfr["count"] + local_rank, t_dump_l)
    new_rows = widen(new_mini["transfers"]["u32"][mini_trow])
    # Stored row pointers become SHARD-LOCAL: resolve the new
    # row's dr/cr against the local table (remote -> dump).
    fdr2, rdr2 = ht_lookup(sub["acct_ht"],
                           ev["dr_hi"], ev["dr_lo"])
    fcr2, rcr2 = ht_lookup(sub["acct_ht"],
                           ev["cr_hi"], ev["cr_lo"])
    new_rows = new_rows.at[:, _XF_DRROW_COL].set(
        pack32(jnp.where(fdr2, rdr2, a_dump_l),
               jnp.where(fcr2, rcr2, a_dump_l)))
    xu_new = xfr["u32"].at[dest_t].set(narrow(new_rows))
    # Pending-status flips on existing owned rows: a ROW scatter of
    # the local copy's rows as gathered in phase 1 (an owned key's
    # dest_p is the row they were gathered from), pstat replaced — an
    # element scatter into the 2-D store would relayout the whole
    # store on TPU (ops/ev_layout.py). Unchanged rows rewrite
    # themselves.
    if overlay:
        # Copy-catchup owners flip their OWN copy's row: the read
        # owner's row index is the exchanged encoding, the other
        # write owner's is its local lookup (absent-here rows — a
        # key outside this shard's tables — mask to the dump row).
        wr_xk = writes_here(xk_hi, xk_lo, n_dev, me, overlay)
        flip = lfirst & wr_xk
        row_here = jnp.where(
            read_mine_x, (g_enc - jnp.uint64(2)).astype(jnp.int32),
            xv_l)
        has_here = read_mine_x | (xf_raw & (xv_l >= 0))
        dest_p = jnp.where(flip & g_ok & has_here, row_here, t_dump_l)
    else:
        owner_xk = shard_of_id(xk_hi, xk_lo, n_dev)
        flip = lfirst & (owner_xk == me)
        dest_p = jnp.where(flip & g_ok,
                           (g_enc - jnp.uint64(2)).astype(jnp.int32),
                           t_dump_l)
    pword = new_mini["transfers"]["u32"][
        jnp.where(x_live, lrow, MT), XF_PSTAT_COL32]
    xu_new = xu_new.at[dest_p].set(jnp.where(
        (dest_p != t_dump_l)[:, None],
        with_col32(xraw_l, XF_PSTAT_COL32, pword), jnp.uint32(0)))

    if overlay:
        wr_ak = writes_here(ak_hi, ak_lo, n_dev, me, overlay)
        wb_a = afirst & wr_ak
        arow_here = jnp.where(
            read_mine_a, (g_aenc - jnp.uint64(1)).astype(jnp.int32),
            ar_l)
        dest_a = jnp.where(wb_a & g_ok & (read_mine_a | af_raw),
                           arow_here, a_dump_l)
    else:
        owner_ak = shard_of_id(ak_hi, ak_lo, n_dev)
        wb_a = afirst & (owner_ak == me)
        dest_a = jnp.where(wb_a & g_ok,
                           (g_aenc - jnp.uint64(1)).astype(jnp.int32),
                           a_dump_l)
    amrow_c = jnp.where(afirst, amrow, MA)
    au_new = acc["u32"].at[dest_a].set(
        new_mini["accounts"]["u32"][amrow_c])
    ab_new = acc["bal"].at[dest_a].set(
        new_mini["accounts"]["bal"][amrow_c])

    dest_e = jnp.where(mine & g_ok,
                       evr["count"] + local_rank, e_cap_l)
    ring_rows = new_mini["events"]["u32"][
        jnp.clip(row_off, 0, ME)]
    eu_new = evr["u32"].at[dest_e].set(ring_rows)

    vals = jnp.where(created, xfr["count"] + local_rank,
                     jnp.int32(ORPHAN_VAL))
    ht_new = ht_write(sub["xfer_ht"], pos, ev["id_hi"],
                      ev["id_lo"], vals, ins_mine & g_ok)

    # int32 pinned: jnp.sum promotes to int64 under x64, and the scan
    # carry requires the counts' dtype to be a fixpoint.
    n_mine_ok = jnp.where(g_ok, n_mine, 0).astype(jnp.int32)

    def adopt(new_v, old_v):
        return jnp.where(g_ok, new_v, old_v)

    new_sub = dict(
        accounts=dict(u32=au_new, bal=ab_new,
                      count=acc["count"]),
        transfers=dict(u32=xu_new,
                       count=xfr["count"] + n_mine_ok),
        events=dict(u32=eu_new,
                    count=evr["count"] + n_mine_ok),
        acct_ht=sub["acct_ht"],
        xfer_ht=ht_new,
        acct_key_max=adopt(new_mini["acct_key_max"],
                           sub["acct_key_max"]),
        xfer_key_max=adopt(new_mini["xfer_key_max"],
                           sub["xfer_key_max"]),
        pulse_next=adopt(new_mini["pulse_next"],
                         sub["pulse_next"]),
        commit_ts=adopt(new_mini["commit_ts"],
                        sub["commit_ts"]),
    )

    # ---- amended out dict: the shard/exchange breaches are
    # host fallbacks (state untouched), never escalations.
    xb = bad | xchg_bad
    rep = dict(out)
    rep["r_status"] = jnp.where(xb, jnp.zeros_like(status),
                                status)
    rep["r_ts"] = jnp.where(xb, jnp.zeros_like(out["r_ts"]),
                            out["r_ts"])
    rep["fallback"] = out["fallback"] | xb
    rep["limit_only"] = out["limit_only"] & ~xb
    rep["created_count"] = jnp.where(xb, 0,
                                     out["created_count"])
    fbc = dict(out["fb_causes"])
    fbc["shard_capacity"] = bad
    fbc["exchange_overflow"] = xchg_bad
    rep["fb_causes"] = fbc
    # Durable flush rides the mini: the appended rows' slice
    # plus the id/p_ts derivations, all mini-resolved (the
    # canonical columns are bit-exact vs the single-chip
    # gather; row-pointer columns are non-canonical scope).
    rep["flush"] = _delta_gather_body(new_mini, mini_t0, 0,
                                      N, N)
    if overlay:
        owner_dr = owner_read(ev["dr_hi"], ev["dr_lo"], n_dev, overlay)
        owner_cr = owner_read(ev["cr_hi"], ev["cr_lo"], n_dev, overlay)
    else:
        owner_dr = shard_of_id(ev["dr_hi"], ev["dr_lo"], n_dev)
        owner_cr = shard_of_id(ev["cr_hi"], ev["cr_lo"], n_dev)
    rep["cross_shard_transfers"] = jnp.sum(
        (created & (owner_dr != owner_cr)).astype(jnp.int32))
    rep["exchange_overflow"] = xchg_bad
    owned = jnp.sum(
        (ev["valid"] & (owner_ev == me)).astype(jnp.int32))
    tel = None
    if telemetry:
        tel = _telemetry_block(
            out, fbc, n_lanes=N, n_a=n_a, n_live=n_live,
            xchg_bad=xchg_bad, bad_l=bad_l,
            cross_shard=rep["cross_shard_transfers"],
            ring_count=evr["count"] + n_mine_ok,
            n_mine_ok=n_mine_ok, owned=owned)
    return new_sub, rep, owned, tel


def make_partitioned_create_transfers(mesh: Mesh, axis: str = "batch",
                                      mode: str = "plain",
                                      telemetry: bool = True,
                                      overlay: tuple = ()):
    """Build the jitted partitioned-state SPMD step over `mesh` for one
    kernel tier (`mode` in MODES).

    Returns step(stacked_state, ev, timestamp, n) -> (new_state, out).
    `stacked_state` is the pytree from partitioned_from_oracle: every
    leaf carries a leading shard axis sharded P(axis); `ev` is the full
    padded batch, replicated. `out` is the single-chip out dict plus
    `flush` (the delta gather of the appended rows, replicated),
    `cross_shard_transfers`, `exchange_overflow`, and
    `shard_stats.events_owned` (per-shard routed-event counts). With
    `telemetry` (the default) `shard_stats.tel` carries the
    [n_shards, TEL_WORDS] device telemetry block; `telemetry=False` is
    the overhead-probe baseline. `overlay` (elastic shards) is the
    static ownership-override tuple baked into the lowering; () — the
    default — lowers byte-identically to the pre-overlay artifact."""
    from jax import shard_map
    assert mode in MODES, mode
    n_dev = mesh.shape[axis]

    def step(state, ev, timestamp, n):
        def body(stacked, ev):
            sub = jax.tree.map(lambda x: x[0], stacked)
            new_sub, rep, owned, tel = _partitioned_batch_body(
                sub, ev, timestamp, n, axis=axis, n_dev=n_dev,
                mode=mode, telemetry=telemetry, overlay=overlay)
            sh = dict(events_owned=owned[None])
            if tel is not None:
                sh["tel"] = tel[None]
            new_stacked = jax.tree.map(lambda x: jnp.asarray(x)[None],
                                       new_sub)
            return new_stacked, {"rep": rep, "sh": sh}

        try:
            smapped = shard_map(
                body, mesh=mesh, in_specs=(P(axis), P()),
                out_specs=(P(axis), {"rep": P(), "sh": P(axis)}),
                check_vma=False)
        except TypeError:  # pre-0.5 jax spells the kwarg check_rep
            smapped = shard_map(
                body, mesh=mesh, in_specs=(P(axis), P()),
                out_specs=(P(axis), {"rep": P(), "sh": P(axis)}),
                check_rep=False)
        new_state, out2 = smapped(state, ev)
        out = dict(out2["rep"])
        out["shard_stats"] = out2["sh"]
        return new_state, out

    # Donation preserved: the sharded buffers are consumed in place
    # (jaxhound's donation audit checks the lowered artifact).
    return jax.jit(step, donate_argnums=0)


def make_partitioned_chain_create_transfers(mesh: Mesh,
                                            axis: str = "batch",
                                            mode: str = "plain",
                                            telemetry: bool = True,
                                            overlay: tuple = ()):
    """Build the FUSED window step: the W prepares of a commit window
    run as a `lax.scan` over the per-batch body INSIDE one shard_map
    dispatch, with the donated sharded state and a rolling poison
    scalar in the scan carry.

    Returns step(stacked_state, ev_stack, ts_stack, n_stack,
    force_fallback) -> (new_state, out). The stacks come from
    stack_partitioned_window: every ev leaf is [W, n_pad] (replicated),
    ts_stack/n_stack are the per-prepare commit timestamp and event
    count. `force_fallback` seeds the poison carry (None = clean), so
    pipelined drivers chain windows exactly like the single-chip chain
    route (DeviceLedger.submit_window).

    Per-prepare fallback granularity is PRESERVED: scan iteration k's
    rep["fallback"] poisons iterations k+1.. (masked writes — their
    shards stay bit-identical), so the clean prefix commits inside the
    one dispatch and out["fallback"] ([W], replicated) tells the host
    which suffix to re-window. Every out leaf gains a leading W axis;
    `shard_stats.events_owned` is [n_shards, W] and (with `telemetry`,
    the default) `shard_stats.tel` is [n_shards, W, TEL_WORDS] — the
    whole window's per-prepare device telemetry harvested in the SAME
    dispatch as the results.

    Why this exists: the per-batch route pays PERF.md's bottleneck #1
    (per-dispatch fixed cost) once per prepare; here the whole window
    is ONE dispatch whose whole-program op count is flat in W (the
    scan body is censused once — partitioned_chain tiers in
    perf/opbudget_r09.json)."""
    from jax import shard_map
    assert mode in MODES, mode
    n_dev = mesh.shape[axis]

    def step(state, ev_stack, ts_stack, n_stack, force_fallback):
        def body(stacked, ev_stack, ts_stack, n_stack):
            sub = jax.tree.map(lambda x: x[0], stacked)
            poisoned0 = (jnp.bool_(False) if force_fallback is None
                         else force_fallback)

            def scan_step(carry, xs):
                st, poisoned = carry
                ev_k, ts_k, n_k = xs
                new_st, rep, owned, tel = _partitioned_batch_body(
                    st, ev_k, ts_k, n_k, axis=axis, n_dev=n_dev,
                    mode=mode, force_fallback=poisoned,
                    telemetry=telemetry, overlay=overlay)
                ys = ((rep, owned, tel) if telemetry
                      else (rep, owned))
                return (new_st, rep["fallback"]), ys

            (new_sub, _), ys_w = jax.lax.scan(
                scan_step, (sub, poisoned0),
                (ev_stack, ts_stack, n_stack))
            if telemetry:
                reps, owned_w, tel_w = ys_w
            else:
                reps, owned_w = ys_w
            sh = dict(events_owned=owned_w[None])
            if telemetry:
                sh["tel"] = tel_w[None]
            new_stacked = jax.tree.map(lambda x: jnp.asarray(x)[None],
                                       new_sub)
            return new_stacked, {"rep": reps, "sh": sh}

        specs = (P(axis), P(), P(), P())
        try:
            smapped = shard_map(
                body, mesh=mesh, in_specs=specs,
                out_specs=(P(axis), {"rep": P(), "sh": P(axis)}),
                check_vma=False)
        except TypeError:  # pre-0.5 jax spells the kwarg check_rep
            smapped = shard_map(
                body, mesh=mesh, in_specs=specs,
                out_specs=(P(axis), {"rep": P(), "sh": P(axis)}),
                check_rep=False)
        new_state, out2 = smapped(state, ev_stack, ts_stack, n_stack)
        out = dict(out2["rep"])
        out["shard_stats"] = out2["sh"]
        return new_state, out

    return jax.jit(step, donate_argnums=0)


def stack_partitioned_window(evs: list[dict], timestamps: list[int],
                             n_pad: int = N_PAD):
    """W prepares -> the chain step's stacked inputs: each unpadded
    transfers_to_arrays SoA dict padded to n_pad and stacked on a
    leading W axis, plus the per-prepare commit-timestamp and
    valid-count vectors the scan body consumes (the partitioned
    sibling of ops/ledger.stack_chain_window — per-prepare (ts, n)
    scalars instead of seg lanes, because the exchange body judges one
    whole prepare per iteration)."""
    assert len(evs) == len(timestamps) and evs
    padded = [pad_transfer_events(e, n_pad) for e in evs]
    ev_stack = {k: np.stack([p[k] for p in padded]) for k in padded[0]}
    ts_stack = np.asarray([int(t) for t in timestamps], dtype=np.uint64)
    n_stack = np.asarray([len(e["id_lo"]) for e in evs],
                         dtype=np.int32)
    return ev_stack, ts_stack, n_stack


# --------------------------------------------------------------- host side

def _chunk_insert(table, keys_vals, n_pad):
    """from_host's batch_insert, shared shape: chunked ht_insert of
    (id, val) pairs with a hard overflow assert."""
    table = jax.tree.map(jnp.asarray, table)
    for lo_i in range(0, len(keys_vals), n_pad):
        chunk = keys_vals[lo_i:lo_i + n_pad]
        hi = np.array([k >> 64 for k, _ in chunk], dtype=np.uint64)
        lo = np.array([k & (1 << 64) - 1 for k, _ in chunk],
                      dtype=np.uint64)
        vals = np.array([v for _, v in chunk], dtype=np.int32)
        table, ok = ht_insert(
            table, jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(vals),
            jnp.ones(len(chunk), dtype=bool))
        assert bool(ok), "hash rebuild overflow: raise capacities"
    return table


def _record_owner_id(sm, rec) -> int:
    """The id that decides a ring row's shard: the creating transfer's
    (commit-timestamp keyed), else the pending transfer's, else the
    debit account's (expiry rows without a commit entry)."""
    tid = sm.transfer_by_timestamp.get(rec.timestamp)
    if tid is not None:
        return tid
    if rec.transfer_pending is not None:
        return rec.transfer_pending.id
    return rec.dr_account.id


def partitioned_from_oracle(sm, mesh: Mesh, axis: str = "batch",
                            a_cap: int = 1 << 12, t_cap: int = 1 << 14,
                            e_cap: int | None = None,
                            overlay: tuple = ()):
    """Build the device-sharded state pytree from a host oracle.

    The partitioned sibling of DeviceLedger.from_host: objects are
    assigned to shards by shard_of_int over the SAME ownership hash the
    kernels use, then packed per shard in the canonical order
    (accounts by applied timestamp, transfers in commit order — the
    shard-then-sort contract the epoch digest pins). Every leaf gains a
    leading shard axis and lands with NamedSharding P(axis); per-shard
    caps are the global caps / n_shards, so per-device resident bytes
    scale ~1/n_shards.

    `overlay` (elastic shards): placement follows the READ owner under
    the override table, so a rebuild mid-overlay (recovery after a
    flip) lands every range on its authoritative shard. Rebuilding
    DURING copy-catchup is a controller bug — the ReshardController
    always reverts (or completes) the in-flight entry before a resync,
    so a double-write range never reaches this packer."""
    from ..ops.ledger import (
        N_PAD, _pack_account_rows, _pack_event_rows, _pack_transfer_rows,
        init_state,
    )
    from ..types import TransferPendingStatus

    n_shards = mesh.shape[axis]
    assert a_cap % n_shards == 0 and t_cap % n_shards == 0, \
        (a_cap, t_cap, n_shards)
    if e_cap is None:
        e_cap = t_cap
    a_cap_s = a_cap // n_shards
    t_cap_s = t_cap // n_shards
    e_cap_s = max(e_cap // n_shards, 1)
    # The replicated default keeps a 2^16 orphan floor for load safety;
    # per shard the floor scales too, keeping the AGGREGATE table the
    # same size (the 1/n_shards byte assertion depends on it).
    orphan_cap_s = max((1 << 16) // n_shards, t_cap_s)

    acct_all = sorted(sm.accounts.values(), key=lambda a: a.timestamp)
    xfer_all = [sm.transfers[tid]
                for tid in sm.transfer_by_timestamp.values()]
    orphan_all = sorted(sm.orphaned)

    def shard_of(id128):
        return owner_read_int(id128, n_shards, overlay)

    subs = []
    for s in range(n_shards):
        accounts = [a for a in acct_all if shard_of(a.id) == s]
        transfers = [t for t in xfer_all if shard_of(t.id) == s]
        orphans = [o for o in orphan_all if shard_of(o) == s]
        records = [r for r in sm.account_events
                   if shard_of(_record_owner_id(sm, r)) == s]
        assert len(accounts) <= a_cap_s and len(transfers) <= t_cap_s \
            and len(records) <= e_cap_s, "shard capacity exceeded"
        st = jax.tree.map(lambda x: np.array(x), init_state(
            a_cap_s, t_cap_s, orphan_cap=orphan_cap_s, e_cap=e_cap_s))

        acct_row = {a.id: r for r, a in enumerate(accounts)}
        xfer_row = {t.id: r for r, t in enumerate(transfers)}
        a_u64, a_bal = _pack_account_rows(accounts)
        st["accounts"]["u32"][:len(accounts)] = narrow(a_u64)
        st["accounts"]["bal"][:len(accounts)] = narrow(a_bal)
        st["accounts"]["count"] = np.int32(len(accounts))
        st["acct_ht"] = jax.tree.map(np.asarray, _chunk_insert(
            st["acct_ht"],
            [(a.id, r) for r, a in enumerate(accounts)], N_PAD))

        u64m = _pack_transfer_rows(
            transfers,
            lambda o: int(sm.pending_status.get(
                o.timestamp, TransferPendingStatus.none)),
            lambda aid, dump: acct_row.get(aid, dump),
            a_cap_s)
        st["transfers"]["u32"][:len(transfers)] = narrow(u64m)
        st["transfers"]["count"] = np.int32(len(transfers))
        st["xfer_ht"] = jax.tree.map(np.asarray, _chunk_insert(
            st["xfer_ht"],
            [(t.id, r) for r, t in enumerate(transfers)]
            + [(o, ORPHAN_VAL) for o in orphans], N_PAD))

        ecols = _pack_event_rows(records, acct_row, xfer_row, a_cap_s)
        st["events"]["u32"][:len(records)] = ecols["u32"]
        st["events"]["count"] = np.int32(len(records))

        # Scalars hold GLOBAL values on every shard (the mini-state and
        # the write-back adopt/replicate them each step).
        st["acct_key_max"] = np.uint64(sm.accounts_key_max or 0)
        st["xfer_key_max"] = np.uint64(sm.transfers_key_max or 0)
        st["pulse_next"] = np.uint64(sm.pulse_next_timestamp)
        st["commit_ts"] = np.uint64(sm.commit_timestamp)
        subs.append(st)

    stacked = jax.tree.map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]), *subs)
    return jax.device_put(stacked, NamedSharding(mesh, P(axis)))


def _host_local(x):
    """device_get that tolerates a multi-host mesh: a leaf sharded over
    the global device list cannot be fetched whole from one process, so
    fall back to the ADDRESSABLE shards — each process accounts the
    rows it hosts (remote rows read as zero here and accumulate on
    their own host's router). Replicated leaves fetch whole either
    way."""
    try:
        return np.asarray(jax.device_get(x))
    except RuntimeError:
        out = np.zeros(x.shape, dtype=x.dtype)
        for s in x.addressable_shards:
            out[s.index] = np.asarray(s.data)
        return out


def partitioned_state_bytes(stacked) -> int:
    """Per-device resident state bytes of a stacked partitioned pytree
    (every leaf's leading dim is the shard axis)."""
    leaves = jax.tree.leaves(stacked)
    n = leaves[0].shape[0]
    total = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                for x in leaves)
    return total // n


def replicated_state_bytes(a_cap: int, t_cap: int,
                           e_cap: int | None = None) -> int:
    """Per-device resident bytes of the REPLICATED route at the same
    caps (every device holds the whole pytree) — the comparison base
    for the ~1/n_shards assertion. Shape-only (eval_shape): nothing is
    allocated."""
    from ..ops.ledger import init_state

    shapes = jax.eval_shape(lambda: init_state(a_cap, t_cap,
                                               e_cap=e_cap))
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(shapes))


class PartitionedRouter:
    """Host-side tier router over the partitioned steps — the sharded-
    state sibling of ShardedRouter. Same flag pre-route, same
    plain -> fixpoint escalation, same per-cause fallback counters,
    plus the exchange diagnostics (events routed per shard, cross-shard
    transfer counts, exchange overflows).

    Window dispatch (step_window) defaults to the PARTITIONED CHAIN:
    one fused shard_map+scan dispatch per eligible commit window, with
    per-prepare fallback — the clean prefix stays committed inside the
    dispatch, the first ineligible prepare replays through the
    per-batch step (which escalates plain -> fixpoint on device), and
    the remainder re-windows. Route counters ride
    stats()["routes"] in the same shape as
    DeviceLedger.fallback_stats()["routes"].

    Shard loss differs STRUCTURALLY from the replicated router: no
    surviving chip holds the lost range, so a single-chip reroute
    cannot serve. Loss quarantines the router until `resync(oracle)`
    rebuilds the sharded state from the last verified oracle — the
    ServingSupervisor recovery path's bounded-replay contract, counted
    under the `shard_resync` recovery cause."""

    def __init__(self, mesh: Mesh, axis: str = "batch", tracer=None,
                 a_cap: int = 1 << 12, t_cap: int = 1 << 14,
                 e_cap: int | None = None, telemetry: bool = True,
                 flight_recorder=None):
        self.mesh = mesh
        self.axis = axis
        self.tracer = tracer if tracer is not None else NullTracer()
        self.a_cap = a_cap
        self.t_cap = t_cap
        self.e_cap = e_cap
        self.n_shards = mesh.shape[axis]
        # Elastic shards: the generation-tagged ownership authority.
        # Step caches key on (mode, overlay entries) — an overlay swap
        # SELECTS a different compiled artifact, it never mutates one.
        self.ownership = OwnershipTable(self.n_shards)
        self._staging_host = None  # DeviceLedger.attach_partitioned
        self._steps: dict = {}
        self._chain_steps: dict = {}
        self.batches = 0
        self.escalations = 0
        self.host_fallbacks = 0
        self.fallback_causes: dict = {}
        self.lost_devices: set = set()
        self.shard_resyncs = 0
        self.cross_shard_transfers = 0
        self.exchange_overflows = 0
        self.events_owned = np.zeros(self.n_shards, dtype=np.int64)
        self.window_routes: dict = {}
        self.chain_batch_fallbacks: dict = {}
        # Device telemetry plane: `telemetry` is a MAKE-TIME switch (it
        # selects which compiled artifact the factories build — the
        # call signatures never change), the aggregates below are what
        # the decoded blocks accumulate into between stats() reads.
        self.telemetry = bool(telemetry)
        self.flight = flight_recorder if flight_recorder is not None \
            else FlightRecorder(pid=jax.process_index(),
                                tracer=self.tracer)
        self._tel_hist = Histogram()    # exchange occupancy, pct
        self._tel_rounds = Histogram()  # fixpoint rounds per prepare
        self.device_poison_causes: dict = {}
        self.writeback_rows = 0
        self.shard_capacity_hits = 0
        self._window_seq = 0

    # Same flag-derived tier precedence as the replicated router.
    route = staticmethod(ShardedRouter.route)

    def from_oracle(self, sm):
        """Build the router's sharded state from a host oracle (under
        the current ownership table — migrated ranges land on their
        read owner)."""
        return partitioned_from_oracle(sm, self.mesh, self.axis,
                                       self.a_cap, self.t_cap,
                                       self.e_cap,
                                       overlay=self.ownership.entries)

    def set_ownership(self, table: OwnershipTable) -> None:
        """Swap in a new ownership table (reshard stage transitions).
        Purely a host-side selection change: the next dispatch picks
        (or traces) the step keyed by the new overlay entries."""
        assert table.n_shards == self.n_shards, table
        assert table.generation >= self.ownership.generation, table
        self.ownership = table

    def _step(self, mode: str):
        key = (mode, self.ownership.entries)
        fn = self._steps.get(key)
        if fn is None:
            fn = self._steps[key] = make_partitioned_create_transfers(
                self.mesh, self.axis, mode=mode,
                telemetry=self.telemetry,
                overlay=self.ownership.entries)
        return fn

    def _chain_step(self, mode: str):
        key = (mode, self.ownership.entries)
        fn = self._chain_steps.get(key)
        if fn is None:
            fn = self._chain_steps[key] = \
                make_partitioned_chain_create_transfers(
                    self.mesh, self.axis, mode=mode,
                    telemetry=self.telemetry,
                    overlay=self.ownership.entries)
        return fn

    def drop_device(self, device, oracle=None):
        """Mark one mesh device lost. The lost range exists NOWHERE
        else on the mesh (partitioned state), so — unlike
        ShardedRouter.drop_device — there is no single-chip reroute:
        the router refuses to serve until resynced. Passing `oracle`
        runs the resync immediately and returns the rebuilt state.

        Quarantine is a flight-recorder dump point: the ring's tail is
        the last-N windows BEFORE the loss — exactly the post-mortem
        question — so freeze it now, while the evidence is fresh."""
        self.lost_devices.add(device)
        self.flight.record(window=self._window_seq, route="quarantined",
                           lost_devices=len(self.lost_devices))
        self.flight.dump("shard_loss_quarantine")
        if oracle is not None:
            return self.resync(oracle)
        return None

    def resync(self, oracle):
        """Bounded oracle-replay resync of the lost range(s): rebuild
        the sharded state from the last verified oracle through the
        supervisor recovery path's event classes (`shard_resync`
        cause). Returns the fresh stacked state.

        Staging is torn down FIRST: a pack staged under the
        pre-quarantine ownership map could otherwise be consumed by
        identity against the rebuilt state (ISSUE 19 satellite fix —
        the staged window's route and pad bucket would match while its
        placement assumptions no longer do)."""
        host = self._staging_host
        if host is not None:
            host.shutdown_staging()
        self.flight.dump("shard_resync")
        with self.tracer.span(Event.serving_recovery_replay,
                              cause="shard_resync"):
            state = self.from_oracle(oracle)
        self.tracer.count(Event.serving_recoveries,
                          cause="shard_resync")
        self.shard_resyncs += 1
        self.lost_devices.clear()
        return state

    def restore_devices(self) -> None:
        """The mesh healed WITHOUT state loss (transient link flap):
        nothing to rebuild."""
        self.lost_devices.clear()

    def _require_serving(self) -> None:
        if self.lost_devices:
            raise RuntimeError(
                "partitioned shard lost: resync(oracle) required — the "
                "single-chip reroute cannot serve a lost range")

    def _absorb_telemetry(self, tel):
        """Decode one harvested telemetry block ([n_shards, W,
        TEL_WORDS] or [n_shards, TEL_WORDS], host-local rows) into
        tracer emissions + the router aggregates, returning the
        per-window summary dict the flight recorder rings (None when
        empty). Replicated words were psum'd on device, so every LOCAL
        shard row carries the same value — max over the shard axis
        recovers them on multi-host meshes where remote rows read zero
        (_host_local); per-shard words stay per shard."""
        tel = np.asarray(tel)
        if tel.ndim == 2:
            tel = tel[:, None, :]
        if tel.shape[1] == 0:
            return None
        d = decode_telemetry(tel)
        rep = {k: d[k].max(axis=0) for k in (
            "fix_rounds", "poison_cause",
            "xchg1_occupancy", "xchg1_capacity",
            "xchg2_occupancy", "xchg2_capacity",
            "cross_shard_transfers", "exchange_overflow")}
        W = tel.shape[1]
        occ_pct = []
        causes = []
        for w in range(W):
            self.tracer.observe(Event.device_fixpoint_rounds,
                                int(rep["fix_rounds"][w]))
            self._tel_rounds.record(float(rep["fix_rounds"][w]))
            for phase, occ, cap in (
                    ("transfers", rep["xchg1_occupancy"][w],
                     rep["xchg1_capacity"][w]),
                    ("accounts", rep["xchg2_occupancy"][w],
                     rep["xchg2_capacity"][w])):
                pct = (100.0 * float(occ) / float(cap)) if cap else 0.0
                pct = round(pct, 3)
                occ_pct.append(pct)
                self.tracer.observe(Event.device_exchange_occupancy,
                                    pct, phase=phase)
                self._tel_hist.record(pct)
            code = int(rep["poison_cause"][w])
            cause = (TEL_CAUSES[code - 1]
                     if 0 < code <= len(TEL_CAUSES)
                     else (f"code_{code}" if code else None))
            causes.append(cause)
            if cause is not None:
                self.device_poison_causes[cause] = (
                    self.device_poison_causes.get(cause, 0) + 1)
                self.tracer.count(Event.device_poison_cause,
                                  cause=cause)
        for s in range(tel.shape[0]):
            for w in range(W):
                self.tracer.observe(Event.device_ring_occupancy,
                                    int(d["ring_occupancy"][s, w]))
        wb = int(d["writeback_transfers"].sum())
        if wb:
            self.writeback_rows += wb
            self.tracer.count(Event.device_writeback_rows, value=wb)
        self.shard_capacity_hits += int(d["shard_capacity_hit"].sum())
        return {
            "prepares": W,
            "fix_rounds": [int(x) for x in rep["fix_rounds"]],
            "poison_causes": causes,
            "exchange_occupancy_pct": occ_pct,
            "cross_shard_transfers": int(
                rep["cross_shard_transfers"].sum()),
            "exchange_overflows": int(rep["exchange_overflow"].sum()),
            "shard_capacity_hits": int(d["shard_capacity_hit"].sum()),
            "writeback_rows": wb,
            "events_owned": [int(x)
                             for x in d["events_owned"].sum(axis=1)],
            "ring_occupancy": [int(x)
                               for x in d["ring_occupancy"][:, -1]],
        }

    def step(self, state, ev: dict, timestamp: int, n: int):
        """Run one padded batch. Returns (new_state, out, fell_back).
        On fell_back=True the state is untouched (masked writes on
        every shard) and the caller owns the exact-path replay."""
        self._require_serving()
        self.batches += 1
        mode = self.route(ev)
        self.tracer.count(Event.dispatch_route,
                          route="partitioned_" + mode)
        with self.tracer.span(Event.shard_exchange, mode=mode):
            new_state, out = self._step(mode)(
                state, ev, np.uint64(timestamp), np.int32(n))
            fallback, limit_only = (bool(x) for x in jax.device_get(
                (out["fallback"], out["limit_only"])))
            if fallback and limit_only and mode == "plain":
                self.escalations += 1
                mode = "fixpoint"
                new_state, out = self._step("fixpoint")(
                    new_state, ev, np.uint64(timestamp), np.int32(n))
                fallback = bool(jax.device_get(out["fallback"]))
        if self.telemetry:
            # The harvested block IS the probe (satellite contract: no
            # host-side recomputation of shard balance) — the shard
            # diagnostics below decode from the same device words the
            # tracer events and the flight recorder see.
            tel = _host_local(out["shard_stats"]["tel"])
            d = decode_telemetry(tel)
            xs = int(d["cross_shard_transfers"].max())
            ov = int(d["exchange_overflow"].max())
            owned = d["events_owned"]
            summary = self._absorb_telemetry(tel)
            self.flight.record(window=self._window_seq,
                               route="partitioned_" + mode,
                               telemetry=summary)
        else:
            xs, ov = (int(x) for x in jax.device_get(
                (out["cross_shard_transfers"],
                 out["exchange_overflow"])))
            owned = _host_local(out["shard_stats"]["events_owned"])
        if int(xs):
            self.cross_shard_transfers += int(xs)
            self.tracer.count(Event.cross_shard_transfers,
                              value=int(xs))
        self.exchange_overflows += int(bool(ov))
        self.events_owned += np.asarray(owned, dtype=np.int64)
        if fallback:
            self.host_fallbacks += 1
            for k, v in jax.device_get(out["fb_causes"]).items():
                if bool(v):
                    self.fallback_causes[k] = (
                        self.fallback_causes.get(k, 0) + 1)
                    self.tracer.count(Event.router_fallback, cause=k)
        return new_state, out, fallback

    # ---- fused window dispatch (the default partitioned route) ----

    def _count_window(self, route: str) -> None:
        self.window_routes[route] = (
            self.window_routes.get(route, 0) + 1)
        self._window_seq += 1

    def stage_operands(self, evs: list[dict], timestamps: list[int],
                       n_pad: int):
        """Pack one fused window's stacked operands and start their
        REPLICATED device transfer (the chain step's in_specs are
        P() for ev_stack/ts_stack/n_stack — state is the only sharded
        input) as a single pytree put. Pure host work + transfer, no
        router state touched: DeviceLedger's background stager calls
        this off the dispatch thread so the pack/transfer overlaps the
        in-flight window; chain_dispatch(staged=...) consumes the
        result."""
        return jax.device_put(
            stack_partitioned_window(evs, timestamps, n_pad),
            NamedSharding(self.mesh, P()))

    def chain_dispatch(self, state, evs: list[dict],
                       timestamps: list[int], n_pad: int | None = None,
                       force_fallback=None, staged=None):
        """ONE fused shard_map+scan dispatch over a whole window,
        UNRESOLVED (every out leaf stays on device with a leading W
        axis). Pipelined drivers (DeviceLedger.submit_window) thread
        out["fallback"][-1] into the next window's force_fallback and
        resolve later; synchronous callers use step_window. Counts the
        window under the partitioned_chain route. `staged` is an
        optional pre-staged (ev_stack, ts_stack, n_stack) payload from
        stage_operands — already packed and resident replicated, so
        the dispatch skips the inline pack entirely."""
        self._require_serving()
        if staged is not None:
            ev_stack, ts_stack, n_stack = staged
        else:
            ns = [len(e["id_lo"]) for e in evs]
            if n_pad is None:
                n_pad = _pad_bucket(max(ns))
            ev_stack, ts_stack, n_stack = stack_partitioned_window(
                evs, timestamps, n_pad)
        self._count_window("partitioned_chain")
        self.tracer.count(Event.dispatch_route,
                          route="partitioned_chain")
        with self.tracer.span(Event.shard_exchange, mode="chain"):
            new_state, out = self._chain_step("plain")(
                state, ev_stack, ts_stack, n_stack, force_fallback)
        return new_state, out

    def absorb_chain_prefix(self, out, k: int, n_prepares: int) -> None:
        """Accumulate one fused dispatch's committed-prefix counters
        ([0, k) prepares) and, when k < n_prepares, the per-prepare
        fallback causes at iteration k (later iterations only carry
        the transitive poison). The replayed suffix counts itself
        through the per-batch step.

        With telemetry on, every counter here decodes from the
        harvested device block — the cross-shard/ownership words, the
        committed prefix's per-prepare rounds and occupancies (tracer
        histograms), and iteration k's poison cause — and the window
        lands one flight-recorder record."""
        self.batches += k
        tel = None
        if self.telemetry and "tel" in out.get("shard_stats", {}):
            tel = _host_local(out["shard_stats"]["tel"])
        if k:
            if tel is not None:
                d = decode_telemetry(tel[:, :k])
                xs = int(d["cross_shard_transfers"].max(axis=0).sum())
                owned = d["events_owned"].sum(axis=1)
                self.exchange_overflows += int(
                    d["exchange_overflow"].max(axis=0).sum())
            else:
                xs = int(np.asarray(jax.device_get(
                    out["cross_shard_transfers"]))[:k].sum())
                owned = _host_local(
                    out["shard_stats"]["events_owned"])[:, :k].sum(
                        axis=1)
            if xs:
                self.cross_shard_transfers += xs
                self.tracer.count(Event.cross_shard_transfers,
                                  value=xs)
            self.events_owned += np.asarray(owned, dtype=np.int64)
        if tel is not None:
            # Emit the committed prefix's per-prepare telemetry; when
            # the window poisoned at k, fold iteration k in too — its
            # decoded cause code is the post-mortem headline (later
            # iterations only carry the transitive `forced` poison).
            upto = min(k + 1, n_prepares) if k < n_prepares else k
            summary = self._absorb_telemetry(tel[:, :upto])
            self.flight.record(
                window=self._window_seq, route="partitioned_chain",
                telemetry=summary, prepares=n_prepares,
                committed_prefix=k)
        if k < n_prepares:
            for cause, v in jax.device_get(out["fb_causes"]).items():
                if bool(np.asarray(v)[k]):
                    self.chain_batch_fallbacks[cause] = (
                        self.chain_batch_fallbacks.get(cause, 0) + 1)

    def _window_per_batch(self, state, evs, timestamps, n_pad,
                          count_route=True):
        """The per-batch window ladder: one shard_map dispatch per
        prepare through step() (plain -> fixpoint escalation on
        device). The replay path for a chain window's fallen-back
        prepare, and the pre-route for windows carrying flags the
        plain chain body cannot serve."""
        if count_route:
            self._count_window("partitioned_per_batch")
        results = []
        for ev, ts in zip(evs, timestamps):
            n_b = len(ev["id_lo"])
            pe = pad_transfer_events(ev, n_pad)
            state, out, _fb = self.step(state, pe, ts, n_b)
            st, rts = jax.device_get((out["r_status"], out["r_ts"]))
            results.append((np.asarray(st)[:n_b],
                            np.asarray(rts)[:n_b]))
        return state, results

    def step_window(self, state, evs: list[dict],
                    timestamps: list[int], n_pad: int | None = None):
        """Commit one window of W prepares (each an UNPADDED
        transfers_to_arrays SoA dict). Returns (new_state, results)
        with one (status u32[n_b], ts u64[n_b]) pair per prepare.

        DEFAULT route: the partitioned CHAIN — ONE fused
        shard_map+lax.scan dispatch for the whole window when every
        prepare pre-routes plain (imported/balancing/closing windows
        take the per-batch ladder, whose steps escalate tiers
        per-flag). Per-prepare fallback preserves PR 6's window
        semantics: the clean prefix [0, k) committed inside the
        dispatch and its results stand; prepare k replays through the
        per-batch step (plain -> fixpoint escalation on device); the
        remainder re-windows recursively."""
        W = len(evs)
        if W == 0:
            return state, []
        self._require_serving()
        ns = [len(e["id_lo"]) for e in evs]
        if n_pad is None:
            n_pad = _pad_bucket(max(ns))
        if W < 2 or any(self.route(e) != "plain" for e in evs):
            return self._window_per_batch(state, evs, timestamps,
                                          n_pad)
        new_state, out = self.chain_dispatch(state, evs, timestamps,
                                             n_pad)
        fb = np.asarray(jax.device_get(out["fallback"]))
        k = int(np.argmax(fb)) if fb.any() else W
        self.absorb_chain_prefix(out, k, W)
        st_all, ts_all = (np.asarray(x) for x in jax.device_get(
            (out["r_status"], out["r_ts"])))
        results = [(st_all[b, :ns[b]], ts_all[b, :ns[b]])
                   for b in range(k)]
        if k == W:
            return new_state, results
        # Prepare k replays per-batch (the device escalation ladder
        # serves limit cascades without a host fallback); the poisoned
        # suffix — whose shards are bit-identical to the prefix state —
        # re-windows through the full ladder.
        new_state, res_k = self._window_per_batch(
            new_state, evs[k:k + 1], timestamps[k:k + 1], n_pad,
            count_route=False)
        results.extend(res_k)
        if k + 1 < W:
            new_state, rest = self.step_window(
                new_state, evs[k + 1:], timestamps[k + 1:], n_pad)
            results.extend(rest)
        return new_state, results

    def stats(self) -> dict:
        total = int(self.events_owned.sum())
        return {
            "batches": self.batches,
            "escalations": self.escalations,
            "host_fallbacks": self.host_fallbacks,
            "causes": dict(self.fallback_causes),
            "lost_devices": len(self.lost_devices),
            "shard_resyncs": self.shard_resyncs,
            "cross_shard_transfers": self.cross_shard_transfers,
            "exchange_overflows": self.exchange_overflows,
            "events_owned": [int(x) for x in self.events_owned],
            "cross_shard_fraction": (
                self.cross_shard_transfers / total if total else 0.0),
            # Dispatch-route record, DeviceLedger.fallback_stats()
            # shape: windows per route (partitioned_chain = the fused
            # default) + per-cause prepares that fell out of a chain
            # window (the prefix stayed committed).
            "routes": {
                "windows": dict(self.window_routes),
                "chain_batch_fallbacks": dict(
                    self.chain_batch_fallbacks),
            },
            # Device telemetry plane: everything below decodes from the
            # fixed-layout u32 block harvested with the outputs —
            # measured on device, never host-side guesswork.
            "telemetry": None if not self.telemetry else {
                "device_poison_causes": dict(self.device_poison_causes),
                "writeback_rows": int(self.writeback_rows),
                "shard_capacity_hits": int(self.shard_capacity_hits),
                "exchange_occupancy": self._tel_hist.to_dict(),
                "fixpoint_rounds": self._tel_rounds.summary(),
                "flight_windows": self.flight.seq,
                "flight_dumps": self.flight.dumps,
            },
        }
