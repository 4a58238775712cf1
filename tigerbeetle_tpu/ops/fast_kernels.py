"""Vectorized create_transfers / create_accounts kernels over a device ledger.

The sequential kernel (ops/create_kernels.py) is the bit-exact baseline: a
lax.fori_loop whose iteration i sees iteration i-1's effects — the direct
image of the reference hot loop (src/state_machine.zig:3002-3213). This
module is the TPU-native fast path: every per-event check evaluated on the
whole batch at once, chains resolved with a segment first-failure broadcast,
and balance application done with carry-safe scatter-adds.

Exactness strategy: a batch is *eligible* for the fast path iff its statuses
are provably order-independent. The kernel verifies eligibility on device
(returns a `fallback` flag and leaves state untouched when set):

  E1  no imported / balancing_debit|credit / closing_debit|credit flags
      (imported regress checks and balance clamps are order-dependent);
  E2  no duplicate ids within the batch, no pending_id referencing an id in
      the batch, no duplicate pending_ids (intra-batch object dependencies);
  E3  every balance-limit-flagged account touched by regular transfers
      provably fits the batch's WORST-CASE load in its pre-batch headroom
      (sum of all candidate amounts, ignoring mid-batch relief): then no
      prefix order can trip exceeds_credits/debits, so the checks are
      order-independent; a potential breach falls back;
  E4  no u128 balance overflow is possible: max touched balance plus the
      exact 160-bit sum of all batch amounts stays below 2^128, so the six
      overflow statuses (src/state_machine.zig:3856-3884) cannot fire;
  E5  a voided pending transfer has no closing flags (void would reopen a
      closed account mid-batch);
  E6  (retired) pulse scheduling no longer constrains eligibility: the
      kernel computes the exact sequential pulse evolution in closed form
      (prefix-min + reset detection — see the pulse block);
  E7  hash/row capacity suffices.

Under E1-E7, statuses depend only on pre-batch state and per-event fields
(plus chain topology), so evaluating them in parallel is exactly the
sequential semantics. Everything else — exists/idempotency, orphaned ids,
two-phase post/void of *committed* pendings, expired pendings, closed
accounts, chains with rollback — is handled natively in parallel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..constants import NS_PER_S, U63_MAX
from . import u128
from .ev_layout import (
    AC_FLAGS_COL32,
    AC_P32_POS,
    AC_U64_IDX,
    BAL_IDX,
    XF_PSTAT_COL32,
    ac_col,
    ac_rows32,
    col64,
    ev_cap,
    ev_rows32,
    narrow,
    pack32,
    widen,
    with_col32,
    xf_col,
    xf_named,
    xf_rows32,
)
from .create_kernels import (
    _A_CLOSED,
    _A_CR_LIMIT,
    _A_DR_LIMIT,
    _A_IMPORTED,
    _A_LINKED,
    _AF_PADDING,
    _AS,
    _CREATED,
    _F_BAL_CR,
    _F_BAL_DR,
    _F_CLOSE_CR,
    _F_CLOSE_DR,
    _F_IMPORTED,
    _F_LINKED,
    _F_PENDING,
    _F_POST,
    _F_VOID,
    _PS_EXPIRED,
    _PS_PENDING,
    _PS_POSTED,
    _PS_VOIDED,
    _TF_PADDING,
    _TRANSIENT_CODES,
    _TS,
    _ct_eval_exists,
    _first_failure,
)

_NSPS = np.uint64(NS_PER_S)
_U63 = np.uint64(U63_MAX)
_M32 = np.uint64(0xFFFFFFFF)
_INF = np.int32(0x7FFFFFFF)


def _flag(flags, bit):
    return (flags & bit) != 0


# --------------------------------------------------- cumulative reductions
# jnp.cumsum / lax.cummin lower to reduce-window on TPU, whose scoped vmem
# scales with O(axis * window): on v5e the (4, 4, 2N) limb cumsum blows the
# 16 MiB scoped-vmem budget at N=64 already (observed: 64.25M requested).
# lax.associative_scan lowers to log2(N) slice+add steps instead — same
# exact integer semantics, vmem-flat.

def _cumsum(x, axis=-1):
    return jax.lax.associative_scan(jnp.add, x, axis=axis % x.ndim)


def _cummin(x, axis=-1):
    return jax.lax.associative_scan(jnp.minimum, x, axis=axis % x.ndim)


def _cummax(x, axis=-1):
    return jax.lax.associative_scan(jnp.maximum, x, axis=axis % x.ndim)


def _shift_scan(op, x, axis=-1):
    """Inclusive prefix `op` (jnp.add or jnp.maximum) along `axis`, as
    log2(n) shift-and-combine passes (Hillis-Steele). Each pass is one
    fused elementwise op on a TPU, where associative_scan's strided
    slices, pads and interleaves come to about 7 ops a level: in a
    while loop's body, which runs its ops as written every iteration,
    that is the difference between ~30 ops a scan and ~100."""
    axis = axis % x.ndim
    n = x.shape[axis]
    fill = 0 if op is jnp.add else jnp.iinfo(x.dtype).min
    d = 1
    while d < n:
        pad = [(0, 0)] * x.ndim
        pad[axis] = (d, 0)
        x = op(x, jnp.pad(jax.lax.slice_in_dim(x, 0, n - d, axis=axis),
                          pad, constant_values=fill))
        d *= 2
    return x


# ------------------------------------------------------------ limb helpers

def _to_limbs(hi, lo):
    """(hi, lo) u64 pair -> 4 x u32-normalized limbs in u64 lanes."""
    return (lo & _M32, lo >> jnp.uint64(32), hi & _M32, hi >> jnp.uint64(32))


def _from_limbs(l0, l1, l2, l3):
    """Normalized limbs -> (hi, lo)."""
    return (l2 | (l3 << jnp.uint64(32)), l0 | (l1 << jnp.uint64(32)))


def _neg_limbs(hi, lo):
    """Limbs of (2^128 - x) mod 2^128: two's complement for scatter-subtract."""
    n_lo = (~lo) + jnp.uint64(1)
    n_hi = (~hi) + jnp.where(lo == 0, jnp.uint64(1), jnp.uint64(0))
    return _to_limbs(n_hi, n_lo)


def _u128_max_reduce(his, los):
    """Exact max over a list of (hi, lo) arrays of equal shape."""
    hi = his[0]
    lo = los[0]
    for h, l in zip(his[1:], los[1:]):
        take = (h > hi) | ((h == hi) & (l > lo))
        hi = jnp.where(take, h, hi)
        lo = jnp.where(take, l, lo)
    mhi = jnp.max(hi)
    mlo = jnp.max(jnp.where(hi == mhi, lo, jnp.uint64(0)))
    return mhi, mlo


def _dup_keys(k_hi, k_lo, tags):
    """True if any two tagged keys are equal. Sort by (key, tagged-first) so
    tagged duplicates are adjacent even when untagged copies of the same key
    sit between them. ONE variadic sort — the tag lane rides the sort as a
    carried operand instead of three post-sort gathers (op budget)."""
    untag = (~tags).astype(jnp.int32)
    s_hi, s_lo, _, s_tag = jax.lax.sort(
        (k_hi, k_lo, untag, tags), num_keys=3, is_stable=True)
    eq = (s_hi[1:] == s_hi[:-1]) & (s_lo[1:] == s_lo[:-1])
    both = s_tag[1:] & s_tag[:-1]
    return jnp.any(eq & both)


def _combined_dup_keys(ev, valid, pv):
    """Legacy combined collision check: any two tagged keys (ids and
    pids in one pool) equal. One cheap sort; cannot distinguish real
    duplicates from in-batch pending references — callers that need the
    split use _dup_and_pend_join."""
    tag = valid & ~((ev["id_hi"] == 0) & (ev["id_lo"] == 0))
    ptag = valid & pv & ~((ev["pid_hi"] == 0) & (ev["pid_lo"] == 0))
    return _dup_keys(
        jnp.concatenate([ev["id_hi"], ev["pid_hi"]]),
        jnp.concatenate([ev["id_lo"], ev["pid_lo"]]),
        jnp.concatenate([tag, ptag]))


def _dup_and_pend_join(ev, valid, pv, idxs, N):
    """Duplicate-key eligibility + in-batch pending join, ONE sort.

    Keys: every tagged id (a potential in-batch pending DEFINITION) and
    every tagged pid (a USE). Same-kind duplicates (two ids, or two pids)
    are the fallback condition E2 — duplicate incoming ids and double
    post/void of one pending stay on the exact host path. A pid matching
    an id is NOT a fallback anymore: it is the in-window pending join
    (reference: post_or_void_pending_transfer resolves against the
    groove which already contains same-batch creations,
    src/state_machine.zig:4053-4112).

    Returns (dups, inwin, didx): dups = any same-kind duplicate; inwin =
    this use has an in-batch definition EARLIER in the stream; didx = the
    definition's event index (0 where absent; always gate on inwin)."""
    tag = valid & ~((ev["id_hi"] == 0) & (ev["id_lo"] == 0))
    ptag = valid & pv & ~((ev["pid_hi"] == 0) & (ev["pid_lo"] == 0))
    k_hi = jnp.concatenate([ev["id_hi"], ev["pid_hi"]])
    k_lo = jnp.concatenate([ev["id_lo"], ev["pid_lo"]])
    tags = jnp.concatenate([tag, ptag])
    kind = jnp.concatenate([jnp.zeros(N, dtype=jnp.int32),
                            jnp.ones(N, dtype=jnp.int32)])
    seq = jnp.concatenate([idxs, idxs])
    untag = (~tags).astype(jnp.int32)
    pos = jnp.arange(2 * N, dtype=jnp.int32)
    # ONE variadic sort: key, tagged-first, defs-before-uses, stream
    # order — tag/kind/seq/pos ride as carried operands (no post-sort
    # gathers; op budget).
    s_hi, s_lo, _, s_kind, s_seq, s_tag, order = jax.lax.sort(
        (k_hi, k_lo, untag, kind, seq, tags, pos),
        num_keys=5, is_stable=True)
    eq = (s_hi[1:] == s_hi[:-1]) & (s_lo[1:] == s_lo[:-1])
    both = s_tag[1:] & s_tag[:-1]
    dups = jnp.any(eq & both & (s_kind[1:] == s_kind[:-1]))
    # Runs of equal TAGGED keys; each run holds <= 1 def (else dups),
    # and the sort puts it FIRST in its run (defs-before-uses key). The
    # run's def index forward-fills with one (run_id, def+1)-packed
    # running max — no segment reduce, no gather.
    run_start = jnp.concatenate([
        jnp.ones(1, dtype=jnp.bool_), ~(eq & both)])
    run_id = _cumsum(run_start.astype(jnp.int32)) - 1
    def_val = jnp.where(s_tag & (s_kind == 0), s_seq, jnp.int32(-1))
    enc = ((run_id.astype(jnp.int64) << jnp.int64(32))
           | (def_val + 1).astype(jnp.int64))
    fill = _cummax(enc)
    didx_sorted = (fill & jnp.int64(0xFFFFFFFF)).astype(jnp.int32) - 1
    same_run = (fill >> jnp.int64(32)).astype(jnp.int32) == run_id
    use_here = s_tag & (s_kind == 1)
    hit_sorted = use_here & same_run & (didx_sorted >= 0)
    # Scatter back to event positions (order is a permutation): hit and
    # didx packed as one (didx+1 | 0) lane -> ONE scatter.
    val_sorted = jnp.where(hit_sorted, didx_sorted + 1, jnp.int32(0))
    val_full = jnp.zeros(2 * N, dtype=jnp.int32).at[order].set(val_sorted)
    inwin = val_full[N:] > 0
    didx = jnp.maximum(val_full[N:] - 1, 0)
    # Sequential truth: only definitions EARLIER in the stream exist at
    # the use's evaluation point (a later def leaves the use
    # pending_transfer_not_found and still creates itself).
    inwin = inwin & (didx < idxs)
    return dups, inwin, jnp.where(inwin, didx, 0)


_FIELDS = ("dp", "dpos", "cp", "cpos")
_FI = {f: i for i, f in enumerate(_FIELDS)}


def _delta_lanes2(ap_reg, ap_pend, ap_pv, ap_post, al, nl):
    """(4 fields, 4 limbs, 2N) per-entry balance delta lanes — debit-side
    entries then credit-side entries — from pre-ANDed application masks.
    Used by the snapshot/application stage. The limit fixpoint builds
    the SAME lanes inline in sorted entry space (see the `fls` stack in
    create_transfers_fast's limit_rounds>1 loop) so it can gather one
    packed-u8 mask per round instead of this whole matrix — any change
    to which lane an amount lands in MUST be applied to both sites.
    All lanes are < 2^32 (u32-normalized limbs incl. the two's-
    complement pv releases), so segment prefix sums stay carry-safe in
    u64."""
    z64 = jnp.uint64(0)

    def ln(cond_pos, limbs, cond_neg=None, nlimbs=None):
        out = []
        for j in range(4):
            lane = jnp.where(cond_pos, limbs[j], z64)
            if cond_neg is not None:
                lane = lane + jnp.where(cond_neg, nlimbs[j], z64)
            out.append(lane)
        return out

    zero4 = [jnp.zeros_like(al[0])] * 4
    dr_side = {
        "dp": ln(ap_pend, al, ap_pv, nl),
        "dpos": ln(ap_reg | ap_post, al),
        "cp": zero4, "cpos": zero4,
    }
    cr_side = {
        "dp": zero4, "dpos": zero4,
        "cp": ln(ap_pend, al, ap_pv, nl),
        "cpos": ln(ap_reg | ap_post, al),
    }
    return jnp.stack([
        jnp.stack([jnp.concatenate([dr_side[f][j], cr_side[f][j]])
                   for j in range(4)])
        for f in _FIELDS])


def _normalize_limbs(limbs):
    """(4, 4, 2N) un-normalized limb stacks -> mod-2^128 u32-normalized
    (3 carry steps; the final carry-out is discarded = mod 2^128)."""
    l0 = limbs[:, 0]; l1 = limbs[:, 1]; l2 = limbs[:, 2]; l3 = limbs[:, 3]
    c = l0 >> jnp.uint64(32); l0 = l0 & _M32
    l1 = l1 + c; c = l1 >> jnp.uint64(32); l1 = l1 & _M32
    l2 = l2 + c; c = l2 >> jnp.uint64(32); l2 = l2 & _M32
    l3 = (l3 + c) & _M32
    return l0, l1, l2, l3


def _packed_perm(rows2, order2, row_cap):
    """Stable (row, event-order) sort permutation via ONE int64 sort:
    rows and event order packed into a single key (a lexsort would cost
    two stable passes). Field widths are static: pb bits each for order
    and the entry-position tiebreak, the rest for the row. Shared by the
    snapshot/application sort and the limit fixpoint so the two can
    never desynchronize."""
    n2 = rows2.shape[0]
    pb = max(17, (n2 - 1).bit_length())  # static; superbatch-safe
    assert 2 * pb + (int(row_cap) - 1).bit_length() <= 62
    pos = jnp.arange(n2, dtype=jnp.int64)
    combined = ((rows2.astype(jnp.int64) << jnp.int64(2 * pb))
                | (order2.astype(jnp.int64) << jnp.int64(pb))
                | pos & jnp.int64((1 << pb) - 1))
    return jnp.argsort(combined).astype(jnp.int32)


def _linked_prev(linked, seg_start=None):
    """Whether the event before each one is linked to it (never across
    a superbatch's sub-batch boundary)."""
    l_prev = jnp.concatenate([jnp.zeros(1, dtype=jnp.bool_), linked[:-1]])
    if seg_start is not None:
        l_prev = l_prev & ~seg_start
    return l_prev


def _chain_ids(linked, l_prev):
    """Each event's chain number: the count of chain starts up to it."""
    return _cumsum((linked & ~l_prev).astype(jnp.int32))


def _chain_pass(status, linked, valid, idxs, n, N, seg_start=None,
                chain_term=None, chain_id=None):
    """Linked-chain first-failure broadcast (reference execute_create
    :3033-3150): returns (status, not_the_failure, my_first, in_chain)
    where not_the_failure marks members overridden to linked_event_failed.
    Pure in `status` — the limit fixpoint re-runs it per round.

    seg_start/chain_term generalize to superbatches (K stacked prepares
    in one dispatch): seg_start marks each sub-batch's first lane (chains
    never span prepares — a trailing open chain must NOT merge with the
    next sub-batch's head) and chain_term marks each sub-batch's last
    VALID event (the open-chain terminator position). Defaults reproduce
    the single-batch semantics. chain_id: the chains' numbering
    (_chain_ids), where the caller has it already."""
    l_prev = _linked_prev(linked, seg_start)
    in_chain = linked | l_prev
    if chain_id is None:
        chain_id = _chain_ids(linked, l_prev)
    is_last = (idxs == (n - 1)) if chain_term is None else chain_term
    chain_open_evt = linked & is_last
    status = jnp.where(chain_open_evt, _TS["linked_event_chain_open"],
                       status)
    fail = in_chain & valid & (status != _CREATED)
    fail_pos = jnp.where(fail, idxs, _INF)
    seg_first = jax.ops.segment_min(fail_pos, chain_id, num_segments=N + 1)
    my_first = seg_first[chain_id]
    broken = in_chain & (my_first != _INF)
    # chain_open is applied AFTER chain_broken in the sequential order
    # (reference execute_create :3096-3104), so the open-chain terminator
    # keeps linked_event_chain_open even when an earlier member failed.
    not_the_failure = broken & (idxs != my_first) & ~chain_open_evt
    status = jnp.where(not_the_failure, _TS["linked_event_failed"], status)
    return status, not_the_failure, my_first, in_chain


# ================================================== create_transfers (fast)

def _worst_case_loads(ral, dr_rowc, cr_rowc, A_rows):
    """Per account row, the sum of the batch's amount limbs `ral`
    ((N, 4) u64, u32-normalized) against it as debit side and as credit
    side: ([4 x u64[A_rows]], [4 x u64[A_rows]]), limb sums not carried.

    ONE segment-sum, accumulating in u32 over a FLAT segment space, as a
    v5e wants it (PERF.md §6, PR 32): the chip has no 64-bit lanes, so a
    u64 scatter-add is two with carries, and a (2 * A_rows, 4) result is
    laid out with its four limbs padded to 128 lanes, 128 MB a half.
    Each 32-bit limb goes in as pieces of w bits, w such that 2N pieces
    cannot overflow a u32; piece q of side s of account r sums at
    (2q + s) * A_rows + r, and the pieces recombine exactly."""
    N = ral.shape[0]
    w = min(16, 32 - (2 * N - 1).bit_length())
    per_limb = -(-32 // w)
    pieces = jnp.stack([
        ((ral[:, j] >> jnp.uint64(k * w))
         & jnp.uint64((1 << w) - 1)).astype(jnp.uint32)
        for j in range(4) for k in range(per_limb)])
    rows2 = jnp.concatenate([dr_rowc, cr_rowc + jnp.int32(A_rows)])
    seg = (jnp.arange(4 * per_limb, dtype=jnp.int32)[:, None]
           * jnp.int32(2 * A_rows) + rows2[None, :])
    sums = jax.ops.segment_sum(
        jnp.concatenate([pieces, pieces], axis=1).reshape(-1),
        seg.reshape(-1), num_segments=8 * per_limb * A_rows)

    def piece(q, side):
        return jax.lax.dynamic_slice_in_dim(
            sums, (2 * q + side) * A_rows, A_rows).astype(jnp.uint64)

    return tuple(
        [sum(piece(j * per_limb + k, side) << jnp.uint64(k * w)
             for k in range(per_limb)) for j in range(4)]
        for side in (0, 1))


# Packed 32-bit account meta positions (ev_layout.AC_P32): ledger is
# the high half of the (ud32|ledger) column, code/flags the halves of
# the next one.
_AC_UL_COL = AC_P32_POS["ud32"][0]
_AC_CF_COL = AC_P32_POS["code"][0]


def _acct_unpack(g_bal, g64, found):
    """Named account fields from pre-gathered row slices (balance limb
    rows + packed u64 meta rows)."""
    def field(name):
        i = BAL_IDX[name]
        return _from_limbs(g_bal[:, i], g_bal[:, i + 1],
                           g_bal[:, i + 2], g_bal[:, i + 3])

    cf = g64[:, _AC_CF_COL]
    return dict(
        exists=found,
        dp=field("dp"),
        dpos=field("dpos"),
        cp=field("cp"),
        cpos=field("cpos"),
        ledger=(g64[:, _AC_UL_COL] >> jnp.uint64(32)).astype(jnp.uint32),
        code=(cf & _M32).astype(jnp.uint32),
        flags=(cf >> jnp.uint64(32)).astype(jnp.uint32),
        ts=g64[:, AC_U64_IDX["ts"]],
    )


def _acct_gather(acc, rows, found):
    """Gather the account fields the kernel needs at `rows` (clamped):
    TWO row gathers total (balance limbs + the meta matrix), widened
    to u64 words after the gather."""
    return _acct_unpack(widen(acc["bal"][rows]), widen(acc["u32"][rows]),
                        found)


def _acct_gather_multi(acc, rows_list, found_list):
    """K account-role gathers as TWO matrix gathers over the
    concatenated row set (2K gathers -> 2). Returns one named dict per
    role."""
    rows = jnp.concatenate(rows_list)
    g_bal = widen(acc["bal"][rows])
    g64 = widen(acc["u32"][rows])
    outs = []
    off = 0
    for r, found in zip(rows_list, found_list):
        n = r.shape[0]
        outs.append(_acct_unpack(g_bal[off:off + n], g64[off:off + n],
                                 found))
        off += n
    return outs


def _xfer_gather(xfr, rows):
    """Row gather of the packed transfers store: ONE matrix gather of
    u32 rows, widened to named columns after the gather."""
    return xf_named({"u32": xfr["u32"][rows]})


def _xfer_gather_multi(xfr, rows_list):
    """K transfer-role gathers as ONE concatenated matrix gather."""
    rows = jnp.concatenate(rows_list)
    g32 = xfr["u32"][rows]
    outs = []
    off = 0
    for r in rows_list:
        n = r.shape[0]
        outs.append(xf_named({"u32": g32[off:off + n]}))
        off += n
    return outs


_IDV_U64 = ("id_hi", "id_lo", "dr_hi", "dr_lo", "cr_hi", "cr_lo",
            "amt_hi", "amt_lo", "pid_hi", "pid_lo", "ud128_hi",
            "ud128_lo", "ud64")
_IDV_32 = ("ud32", "timeout", "ledger", "code", "flags")
# The 32-bit def-side lanes ride PAIR-PACKED (ev_layout.pack32) in the
# same u64 stack as the wide lanes: the whole ~21-lane view is ONE
# stacked matrix gather (round-7 op cut — was two stacked gathers, u64
# lanes + a separate u32 stack, inside every fixpoint-tier lowering).
_IDV_P32 = (("ud32", "timeout"), ("ledger", "code"),
            ("flags", "dr_rowc"), ("cr_rowc",))


def _inwin_def_view(ev, ts_event, didx, dr_rowc, cr_rowc):
    """Pending-transfer view of an in-batch DEFINITION read from its
    event lanes (reference: the groove already holds same-batch
    creations at post_or_void time, src/state_machine.zig:4053-4112).
    Shared by per_event_status's internal substitution and the SPMD
    tail's bundle fixup (create_transfers_fast spmd join path) so the
    two can never drift. dr_rowc/cr_rowc are the per-event account-row
    probe results the definition's rows are gathered from.

    Op-budget discipline: the ~21 def-side lanes gather as ONE stacked
    matrix gather — the 32-bit lanes pair-pack into u64 words
    (_IDV_P32) and unpack after the gather — this view sits inside
    every fixpoint-tier lowering."""
    src32 = {k: ev[k] for k in _IDV_32}
    src32["dr_rowc"] = dr_rowc
    src32["cr_rowc"] = cr_rowc
    g = jnp.stack(
        [ev[k] for k in _IDV_U64] + [ts_event]
        + [pack32(src32[pr[0]], src32[pr[1]] if len(pr) > 1 else None)
           for pr in _IDV_P32])[:, didx]
    out = {k: g[i] for i, k in enumerate(_IDV_U64)}
    base = len(_IDV_U64) + 1
    for j, pr in enumerate(_IDV_P32):
        word = g[base + j]
        for half, name in enumerate(pr):
            v = ((word >> jnp.uint64(32)) if half
                 else (word & _M32)).astype(jnp.uint32)
            out[name] = v
    d_flags = out["flags"]
    d_timeout = out["timeout"]
    d_ts = g[len(_IDV_U64)]
    out.update(
        ts=d_ts,
        expires=jnp.where(
            d_timeout != 0,
            d_ts + jnp.uint64(d_timeout) * _NSPS, jnp.uint64(0)),
        pstat=jnp.where(_flag(d_flags, _F_PENDING),
                        jnp.int32(_PS_PENDING), jnp.int32(0)),
        dr_row=out.pop("dr_rowc").astype(jnp.int32),
        cr_row=out.pop("cr_rowc").astype(jnp.int32),
    )
    return out


def _pv_eval(ev, p, p_found, p_dr, p_cr, ts_event, imported_ctx=None):
    """Post/void evaluation (reference :4053-4112): sentinel amount
    resolution + the ordered check list. ONE definition shared by
    per_event_status and the SPMD tail's in-window substitution fixup
    (create_transfers_fast spmd join path) so the two can never drift.

    Returns (pv_status, pv_status_nf, pv_amt_hi, pv_amt_lo, pv_tail)
    where pv_status_nf is the dead/missing-definition variant (the same
    sequence with the lookup missing) and pv_tail is the post-regress
    tail list — the source of the caller's precedence-override code
    set."""
    flags = ev["flags"]
    pending = _flag(flags, _F_PENDING)
    is_post = _flag(flags, _F_POST)
    is_void = _flag(flags, _F_VOID)
    imported = _flag(flags, _F_IMPORTED)

    # Resolved post/void amount (sentinel resolution, reference :4101-4112).
    pv_amt_hi, pv_amt_lo = u128.select(
        jnp.where(is_void,
                  u128.is_zero(ev["amt_hi"], ev["amt_lo"]),
                  u128.is_max(ev["amt_hi"], ev["amt_lo"])),
        p["amt_hi"], p["amt_lo"], ev["amt_hi"], ev["amt_lo"])

    p_expires_due = (p["timeout"] != 0) & (p["expires"] <= ts_event)
    pid_zero = u128.is_zero(ev["pid_hi"], ev["pid_lo"])
    pid_max = u128.is_max(ev["pid_hi"], ev["pid_lo"])
    pv_checks = [
        (is_post & is_void, _TS["flags_are_mutually_exclusive"]),
        (pending | _flag(flags, _F_BAL_DR) | _flag(flags, _F_BAL_CR)
         | _flag(flags, _F_CLOSE_DR) | _flag(flags, _F_CLOSE_CR),
         _TS["flags_are_mutually_exclusive"]),
        (pid_zero, _TS["pending_id_must_not_be_zero"]),
        (pid_max, _TS["pending_id_must_not_be_int_max"]),
        (u128.eq(ev["pid_hi"], ev["pid_lo"], ev["id_hi"], ev["id_lo"]),
         _TS["pending_id_must_be_different"]),
        (ev["timeout"] != 0, _TS["timeout_reserved_for_pending_transfer"]),
        (~p_found, _TS["pending_transfer_not_found"]),
        (~_flag(p["flags"], _F_PENDING), _TS["pending_transfer_not_pending"]),
        ((~u128.is_zero(ev["dr_hi"], ev["dr_lo"])) &
         ~u128.eq(ev["dr_hi"], ev["dr_lo"], p["dr_hi"], p["dr_lo"]),
         _TS["pending_transfer_has_different_debit_account_id"]),
        ((~u128.is_zero(ev["cr_hi"], ev["cr_lo"])) &
         ~u128.eq(ev["cr_hi"], ev["cr_lo"], p["cr_hi"], p["cr_lo"]),
         _TS["pending_transfer_has_different_credit_account_id"]),
        ((ev["ledger"] != 0) & (ev["ledger"] != p["ledger"]),
         _TS["pending_transfer_has_different_ledger"]),
        ((ev["code"] != 0) & (ev["code"] != p["code"]),
         _TS["pending_transfer_has_different_code"]),
        (u128.lt(p["amt_hi"], p["amt_lo"], pv_amt_hi, pv_amt_lo),
         _TS["exceeds_pending_transfer_amount"]),
        (is_void & u128.lt(pv_amt_hi, pv_amt_lo, p["amt_hi"], p["amt_lo"]),
         _TS["pending_transfer_has_different_amount"]),
        (p["pstat"] == _PS_POSTED, _TS["pending_transfer_already_posted"]),
        (p["pstat"] == _PS_VOIDED, _TS["pending_transfer_already_voided"]),
        (p["pstat"] == _PS_EXPIRED, _TS["pending_transfer_expired"]),
        (p_expires_due, _TS["pending_transfer_expired"]),
    ]
    if imported_ctx is not None:
        # Regress vs STATE (key_max + account-timestamp collision) at
        # the reference's precedence position (create_transfer :4053
        # path, mirrored by the sequential kernel's pv list); the
        # in-batch component is the caller's maxima chain.
        pv_regress = imported & (
            (ev["ts"] <= imported_ctx["key_max"])
            | imported_ctx["acct_ts_collision"])
        pv_checks.append(
            (pv_regress, _TS["imported_event_timestamp_must_not_regress"]))
    # Post-regress tail: ALSO the source of the caller's precedence-
    # override code set (after_regress_codes) — one literal list, so a
    # future check added here is automatically override-eligible.
    pv_tail = [
        (_flag(p_dr["flags"], _A_CLOSED) & ~is_void,
         _TS["debit_account_already_closed"]),
        (_flag(p_cr["flags"], _A_CLOSED) & ~is_void,
         _TS["credit_account_already_closed"]),
    ]
    pv_checks = pv_checks + pv_tail
    pv_status = _first_failure(pv_checks)
    # The use's status when its in-window definition turns out dead
    # (failed creation): the pending transfer does not exist, so the
    # sequential truth is the same check sequence with the lookup
    # missing — earlier-precedence field checks still win.
    pv_status_nf = _first_failure(
        pv_checks[:6] + [(jnp.ones_like(pid_zero),
                          _TS["pending_transfer_not_found"])])
    return pv_status, pv_status_nf, pv_amt_hi, pv_amt_lo, pv_tail


def imported_batch_ctx(state, ev, ts_event, valid, idxs, seg_start=None):
    """imported_ctx for per_event_status (the real imported-event rules,
    reference :3052-3063 wrapper + :3800-3833): per-sub-batch
    homogeneity reference + commit timestamp, account-timestamp
    collision membership, and the state's key_max. Factored out of
    create_transfers_fast so the SPMD driver (parallel/full_sharded.py)
    can compute it replicated and feed the sharded per-event stage."""
    acc = state["accounts"]
    N = idxs.shape[0]
    imp_lane = _flag(ev["flags"], _F_IMPORTED)
    seg_start_arr = (idxs == 0) if seg_start is None else seg_start
    # Per-sub-batch homogeneity reference: the FIRST lane's flag
    # (reference: events[0], execute_create :3052), forward-filled
    # to every lane of the segment.
    start_idx = _cummax(jnp.where(seg_start_arr, idxs, jnp.int32(-1)))
    batch_imported = imp_lane[jnp.maximum(start_idx, 0)]
    # Per-sub-batch commit timestamp (must_not_advance compares the
    # user timestamp against it): max valid ts_event of the segment.
    seg_id = _cumsum(seg_start_arr.astype(jnp.int32)) - 1
    seg_bts = jax.ops.segment_max(
        jnp.where(valid, ts_event, jnp.uint64(0)), seg_id,
        num_segments=N)[seg_id]
    # Account-timestamp collision (reference :3808): membership of
    # the user timestamp in the account table's timestamp column.
    # The column is read PRE-SORTED (round-7 op cut): rows are stored
    # in applied-timestamp order — the canonical row order the state
    # digest and from_host/_push_dirty already pin — so the probe is
    # searchsorted-only; the former per-dispatch jnp.sort of the whole
    # table is gone. Rows at/after count read as u64::MAX, making the
    # live ascending prefix + MAX padding a sorted operand (user
    # timestamps are <= U63, so the padding can never collide).
    # method='sort': the default 'scan' method lowers to a while loop,
    # whose body runs op by op every iteration (jaxhound's serving-path
    # lint holds each entry to the loops it declares).
    acct_ts_sorted = jnp.where(
        jnp.arange(acc["u32"].shape[0], dtype=jnp.int32) < acc["count"],
        ac_col(acc, "ts"), jnp.uint64(0xFFFFFFFFFFFFFFFF))
    pos = jnp.searchsorted(acct_ts_sorted, ev["ts"], method="sort")
    pos = jnp.minimum(pos, acct_ts_sorted.shape[0] - 1)
    coll = imp_lane & (acct_ts_sorted[pos] == ev["ts"]) \
        & (ev["ts"] != 0)
    return dict(
        batch_imported=batch_imported, batch_ts=seg_bts,
        acct_ts_collision=coll, key_max=state["xfer_key_max"])


def per_event_status(state, ev, ts_event, return_gathers=False,
                     inwin=None, didx=None, imported_ctx=None):
    """The per-event phase of create_transfers: hash lookups, row gathers,
    and the order-independent status evaluation (exists/idempotency,
    post/void checks, regular checks, imported/timestamp rules — reference
    create_transfer :3719-3904 minus running-balance effects).

    imported_ctx (imported-mode tiers only): {batch_imported (bool[N],
    per sub-batch homogeneity reference), batch_ts (u64[N], the
    sub-batch commit timestamp for must_not_advance), acct_ts_collision
    (bool[N]), key_max (u64 scalar, the state's max transfer timestamp)}
    — enables the real imported-event rules (reference :3052-3063 +
    :3800-3833) instead of the default "imported unexpected" rejection.
    The ORDER-DEPENDENT part of the regress rule (an imported timestamp
    vs transfers created earlier in the same batch) is NOT handled here:
    the caller runs the left-to-right maxima chain over these statuses
    (see create_transfers_fast imported_mode).

    Pure per event given replicated state: this is the SHARDABLE stage of
    the SPMD kernel. parallel/full_sharded.py runs it on each device's
    slice of the batch and all-gathers this compact result; the global tail
    (eligibility reductions, chains, application) then runs replicated on
    every device — identical by determinism, so the replicated state stays
    bit-exact across the mesh.

    return_gathers=True additionally returns the (dr, cr, p, p_dr, p_cr)
    row gathers for the single-device caller to reuse (the SPMD path must
    NOT ship them — it re-gathers locally to keep the all-gather
    compact)."""
    from .hash_table import ht_lookup

    acc = state["accounts"]
    xfr = state["transfers"]
    A_dump = acc["u32"].shape[0] - 1
    T_dump = xfr["u32"].shape[0] - 1
    # Note: statuses returned here are NOT valid-masked — the tail in
    # create_transfers_fast applies the valid mask after chain handling.

    flags = ev["flags"]
    pending = _flag(flags, _F_PENDING)
    is_post = _flag(flags, _F_POST)
    is_void = _flag(flags, _F_VOID)
    pv = is_post | is_void

    # ---------------- lookups ----------------
    # One batched probe per table (concatenated key sets): 2 lookups
    # instead of 5 — bucket gathers dominate this stage's op count. The
    # transfer table carries ORPHANED (transiently-failed) ids inline
    # with val = ORPHAN_VAL: the two sets are disjoint forever (a
    # transient failure permanently poisons its id — reference
    # id_already_failed, src/state_machine.zig:3734), so one probe of
    # ev.id answers both exists and already-failed.
    N_ev = ev["id_lo"].shape[0]
    a_found, a_row = ht_lookup(
        state["acct_ht"],
        jnp.concatenate([ev["dr_hi"], ev["cr_hi"]]),
        jnp.concatenate([ev["dr_lo"], ev["cr_lo"]]))
    dr_found, cr_found = a_found[:N_ev], a_found[N_ev:]
    dr_row, cr_row = a_row[:N_ev], a_row[N_ev:]
    x_found, x_val = ht_lookup(
        state["xfer_ht"],
        jnp.concatenate([ev["id_hi"], ev["pid_hi"]]),
        jnp.concatenate([ev["id_lo"], ev["pid_lo"]]))
    live = x_val >= 0
    e_found = x_found[:N_ev] & live[:N_ev]
    o_found = x_found[:N_ev] & ~live[:N_ev]
    # A pid pointing at an orphaned id is "pending transfer not found".
    p_found = x_found[N_ev:] & live[N_ev:]
    e_row, p_row = x_val[:N_ev], x_val[N_ev:]

    dr_rowc = jnp.where(dr_found, dr_row, A_dump)
    cr_rowc = jnp.where(cr_found, cr_row, A_dump)
    e_rowc = jnp.where(e_found, e_row, T_dump)
    p_rowc = jnp.where(p_found, p_row, T_dump)

    e, p = _xfer_gather_multi(xfr, [e_rowc, p_rowc])

    # ---- in-window pending substitution (join computed by the caller;
    # reference: the groove already holds same-batch creations at
    # post_or_void time, src/state_machine.zig:4053-4112). A use whose
    # pid matches an EARLIER in-batch definition reads the pending
    # transfer's fields from the definition's EVENT lanes instead of the
    # table gather. Gated off when the definition's id already exists in
    # the table (live or orphaned): then the definition is not-created
    # and the table row with that id is the sequential-truth target.
    if inwin is not None:
        # Def-side table-collision gate: ONE packed-u8 gather for both
        # probe lanes (op budget).
        eo = (e_found.astype(jnp.uint8)
              | (o_found.astype(jnp.uint8) << 1))
        inwin = inwin & (eo[didx] == 0)
        p2 = _inwin_def_view(ev, ts_event, didx, dr_rowc, cr_rowc)
        for key in p:
            p[key] = jnp.where(inwin, p2[key], p[key])
        p_found = p_found | inwin

    dr, cr, p_dr, p_cr = _acct_gather_multi(
        acc, [dr_rowc, cr_rowc, p["dr_row"], p["cr_row"]],
        [dr_found, cr_found, p_found, p_found])

    # ---------------- status evaluation ----------------
    exists_status, exists_ts = _ct_eval_exists(
        {k: ev[k] for k in ev}, e, p)

    imported = _flag(flags, _F_IMPORTED)
    pv_status, pv_status_nf, pv_amt_hi, pv_amt_lo, pv_tail = _pv_eval(
        ev, p, p_found, p_dr, p_cr, ts_event, imported_ctx)
    amt_res_hi = jnp.where(pv, pv_amt_hi, ev["amt_hi"])
    amt_res_lo = jnp.where(pv, pv_amt_lo, ev["amt_lo"])

    pid_zero = u128.is_zero(ev["pid_hi"], ev["pid_lo"])
    dr_zero = u128.is_zero(ev["dr_hi"], ev["dr_lo"])
    dr_max = u128.is_max(ev["dr_hi"], ev["dr_lo"])
    cr_zero = u128.is_zero(ev["cr_hi"], ev["cr_lo"])
    cr_max = u128.is_max(ev["cr_hi"], ev["cr_lo"])
    timeout_ns = jnp.uint64(ev["timeout"]) * _NSPS
    ovf_timeout = ts_event + timeout_ns > _U63
    reg_checks = [
        (dr_zero, _TS["debit_account_id_must_not_be_zero"]),
        (dr_max, _TS["debit_account_id_must_not_be_int_max"]),
        (cr_zero, _TS["credit_account_id_must_not_be_zero"]),
        (cr_max, _TS["credit_account_id_must_not_be_int_max"]),
        (u128.eq(ev["dr_hi"], ev["dr_lo"], ev["cr_hi"], ev["cr_lo"]),
         _TS["accounts_must_be_different"]),
        (~pid_zero, _TS["pending_id_must_be_zero"]),
        (~pending & (ev["timeout"] != 0), _TS["timeout_reserved_for_pending_transfer"]),
        # reference :3761-3763 — inside the same !pending block as the
        # timeout check, before ledger/code.
        (~pending & _flag(flags, jnp.uint32(_F_CLOSE_DR | _F_CLOSE_CR)),
         _TS["closing_transfer_must_be_pending"]),
        (ev["ledger"] == 0, _TS["ledger_must_not_be_zero"]),
        (ev["code"] == 0, _TS["code_must_not_be_zero"]),
        (~dr["exists"], _TS["debit_account_not_found"]),
        (~cr["exists"], _TS["credit_account_not_found"]),
        (dr["ledger"] != cr["ledger"], _TS["accounts_must_have_the_same_ledger"]),
        (ev["ledger"] != dr["ledger"], _TS["transfer_must_have_the_same_ledger_as_accounts"]),
    ]
    if imported_ctx is not None:
        # Imported rules at the reference's precedence position
        # (:3800-3833): regress vs state, postdate both accounts,
        # timeout forbidden. In-batch regress = caller's maxima chain.
        reg_regress = imported & (
            (ev["ts"] <= imported_ctx["key_max"])
            | imported_ctx["acct_ts_collision"])
        reg_checks += [
            (reg_regress, _TS["imported_event_timestamp_must_not_regress"]),
        ]
        reg_post_regress = [
            (imported & (ev["ts"] <= dr["ts"]),
             _TS["imported_event_timestamp_must_postdate_debit_account"]),
            (imported & (ev["ts"] <= cr["ts"]),
             _TS["imported_event_timestamp_must_postdate_credit_account"]),
            (imported & (ev["timeout"] != 0),
             _TS["imported_event_timeout_must_be_zero"]),
        ]
        reg_checks += reg_post_regress
    else:
        reg_post_regress = []
    reg_tail = [
        (_flag(dr["flags"], _A_CLOSED), _TS["debit_account_already_closed"]),
        (_flag(cr["flags"], _A_CLOSED), _TS["credit_account_already_closed"]),
        (ovf_timeout, _TS["overflows_timeout"]),
    ]
    reg_checks += reg_tail
    reg_status = _first_failure(reg_checks)

    inner = jnp.where(
        e_found, exists_status,
        jnp.where(o_found, _TS["id_already_failed"],
                  jnp.where(pv, pv_status, reg_status)))
    pre = _first_failure([
        ((flags & _TF_PADDING) != 0, _TS["reserved_flag"]),
        (u128.is_zero(ev["id_hi"], ev["id_lo"]), _TS["id_must_not_be_zero"]),
        (u128.is_max(ev["id_hi"], ev["id_lo"]), _TS["id_must_not_be_int_max"]),
    ])
    inner = jnp.where(pre != _CREATED, pre, inner)
    ts_inner = jnp.where(e_found & (inner == _TS["exists"]), exists_ts, ts_event)
    if imported_ctx is not None:
        # A created imported event keeps its USER timestamp (the stored
        # row, the result, and the history row all carry it —
        # reference :3800-3833 timestamp_actual = t.timestamp).
        ts_inner = jnp.where((inner == _CREATED) & imported,
                             ev["ts"], ts_inner)

    status = inner
    if imported_ctx is None:
        status = jnp.where(~imported & (ev["ts"] != 0),
                           _TS["timestamp_must_be_zero"], status)
        # Without the context, imported batches fall back (E1) before
        # these statuses can matter; an imported flag here is always a
        # mismatch (reference execute_create :3052-3063).
        status = jnp.where(imported, _TS["imported_event_not_expected"],
                           status)
    else:
        # The real wrapper rules (reference :3033-3104 mirrored by the
        # sequential kernel): per-sub-batch homogeneity, timestamp
        # range, must-not-advance vs the sub-batch commit timestamp.
        batch_imported = imported_ctx["batch_imported"]
        ts_valid = (ev["ts"] >= 1) & (ev["ts"] <= _U63)
        status = jnp.where(~imported & (ev["ts"] != 0),
                           _TS["timestamp_must_be_zero"], status)
        status = jnp.where(
            imported & ts_valid & (ev["ts"] >= imported_ctx["batch_ts"]),
            _TS["imported_event_timestamp_must_not_advance"], status)
        status = jnp.where(imported & ~ts_valid,
                           _TS["imported_event_timestamp_out_of_range"],
                           status)
        status = jnp.where(
            imported != batch_imported,
            jnp.where(imported, _TS["imported_event_not_expected"],
                      _TS["imported_event_expected"]), status)
    ts_actual = jnp.where(status == inner, ts_inner, ts_event)

    # Closed-check-stripped status (closing-native fixpoint tiers): the
    # already_closed decisions are re-evaluated per round against the
    # EVOLVING in-batch closed state, so those tiers need this event's
    # status with only the closed codes removed. First-failure structure
    # makes the strip local: already_closed can only come from reg_tail
    # (where the one check sequenced after it is overflows_timeout,
    # reference :3837 vs :3898) or pv_tail (where it is last).
    is_closed_st = ((status == _TS["debit_account_already_closed"])
                    | (status == _TS["credit_account_already_closed"]))
    status_nc = jnp.where(
        is_closed_st & ~pv & ovf_timeout, _TS["overflows_timeout"],
        jnp.where(is_closed_st, _CREATED, status))

    out = dict(
        status_pre=status, ts_pre=ts_actual, status_nc=status_nc,
        amt_res_hi=amt_res_hi, amt_res_lo=amt_res_lo,
        dr_row=dr_rowc, cr_row=cr_rowc, p_row=p_rowc,
        dr_found=dr_found, cr_found=cr_found, p_found=p_found,
        # Own-id probe results: the SPMD tail's in-window join fixup
        # gates the substitution on the DEFINITION's id being absent
        # from the table (live or orphaned).
        e_found=e_found, o_found=o_found,
    )
    if imported_ctx is not None:
        # Every status code checked AFTER the regress position (the
        # in-batch maxima chain must outrank these — see the caller's
        # precedence override). Derived from the SAME literal lists the
        # statuses come from, so the two can never drift.
        out["after_regress_codes"] = tuple(sorted({
            int(code) for _, code in (reg_post_regress + reg_tail
                                      + pv_tail)}))
    if inwin is not None:
        # Fully-wrapped dead-definition variant (same pre/imported
        # wrapping as status_pre, pv branch replaced by the not-found
        # sequence) for the dependency fixpoint's override.
        inner_nf = jnp.where(
            e_found, exists_status,
            jnp.where(o_found, _TS["id_already_failed"],
                      jnp.where(pv, pv_status_nf, reg_status)))
        inner_nf = jnp.where(pre != _CREATED, pre, inner_nf)
        status_nf = jnp.where(~imported & (ev["ts"] != 0),
                              _TS["timestamp_must_be_zero"], inner_nf)
        status_nf = jnp.where(imported,
                              _TS["imported_event_not_expected"], status_nf)
        out["inwin"] = inwin
        out["didx"] = didx
        out["status_pre_dead"] = status_nf
    if return_gathers:
        out["_gathers"] = (dr, cr, p, p_dr, p_cr)
    return out


def create_transfers_fast(state, ev, timestamp, n, force_fallback=None,
                          per_event=None, limit_rounds=1, seg=None,
                          ring_reset=False, imported_mode=False,
                          balancing_mode=False):
    """One batch against the device ledger. Returns (new_state, out) where
    out = {r_status, r_ts, fallback, limit_only, created_count}. When
    out['fallback'] is set, new_state is the input state unchanged (every
    write is masked to the dump slot, so donated buffers are reusable in
    place); out['limit_only'] marks a fallback whose ONLY cause was the
    balance-limit headroom proof — the caller redispatches those to the
    fixpoint variant instead of the host.

    force_fallback: optional bool scalar that aborts the batch uncondition-
    ally (used by the scan driver to poison batches after a fallback).
    per_event: optional precomputed per_event_status() result (the sharded
    SPMD path computes it per device slice and all-gathers).
    limit_rounds (static): 1 = gate order-dependent balance limits behind
    the worst-case headroom proof (fallback on a potential breach);
    K > 1 = resolve breaches natively with a K-round status fixpoint
    against exact per-event prefix balances (falls back only if the
    limit-decision cascade is deeper than K rounds);
    LIMIT_FIXPOINT_CONVERGE = the same rounds in one while loop until
    they stop changing (at most N + 1, which every cascade fits).
    seg: superbatch descriptor for K stacked prepares executed in ONE
    dispatch (one program launch and one set of state passes for the
    whole window; what that buys on a local chip is not measured):
    {"ts_event": u64[N] per-event commit timestamps,
    "seg_start": bool[N] sub-batch first lanes, "chain_term": bool[N]
    sub-batch last-valid lanes}. The eligibility proofs (E1-E8) are
    already whole-array reductions, so they extend verbatim to the
    concatenated stream; sequential cross-sub-batch effects (dup ids,
    pending posted earlier in the superbatch, headroom, pulse evolution)
    are exactly the intra-batch cases they already cover. timestamp/n
    are ignored when seg is given (timestamps arrive per event). The one
    observable difference vs K sequential dispatches is hash-table slot
    LAYOUT (two-choice placement reads occupancy at plan time); the
    key->row mapping and every derived result are identical
    (tests/test_superbatch.py pins this).

    imported_mode (static): handle imported events natively (reference
    :3052-3063 wrapper + :3800-3833 transfer rules). The ONLY
    order-dependent rule — an imported timestamp must exceed every
    timestamp already applied, including earlier in the batch — has a
    closed form: the applied set is exactly the strict left-to-right
    maxima of the otherwise-valid sequence (a failed event never
    advances the running max, and an event at or below ANY earlier
    otherwise-valid timestamp is also at or below the applied max), so
    one exclusive cummax decides every regress status with no fixpoint.
    Linked chains are the one interaction this form cannot express (a
    chain rollback rewinds the running max — reference chain_key_max),
    so imported batches containing chains fall back to the exact path;
    so do in-window pending references and potential limit breaches
    (the fixpoint tiers are not imported-aware).

    balancing_mode (static, requires limit_rounds > 1): handle
    balancing_debit/credit natively (reference :3840-3853). The clamp
    reads the SAME pre-event balances the limit fixpoint already
    derives each round, so it joins the iteration: round r re-derives
    every balancing event's clamped amount from round r-1's prefix
    balances (always clamping the NOMINAL amount — min composes, no
    ratchet), threads those amounts into the delta lanes and the limit
    checks, and convergence additionally requires amount stability.
    The earliest-disagreeing-event induction is unchanged: an event
    whose prefix is sequential truth gets exact pre-balances, hence the
    exact clamp and statuses, and stays fixed — K rounds still resolve
    any cascade of depth < K. Converged amounts flow into the stored
    rows / event ring / balance application via amt_res. The one new
    hard fallback: an in-window pending reference whose DEFINITION is
    balancing (the substitution reads nominal event lanes, but the
    pending's true stored amount is clamped). The E3/E4 proofs keep
    nominal amounts — a clamp only shrinks, so both stay upper
    bounds."""
    from .hash_table import ORPHAN_VAL, ht_plan, ht_write

    acc = state["accounts"]
    xfr = state["transfers"]
    N = ev["id_lo"].shape[0]
    A_dump = acc["u32"].shape[0] - 1
    T_dump = xfr["u32"].shape[0] - 1
    idxs = jnp.arange(N, dtype=jnp.int32)
    valid = ev["valid"]
    if seg is None:
        nn = n.astype(jnp.uint64)
        ts_event = timestamp - nn + idxs.astype(jnp.uint64) + jnp.uint64(1)
        seg_start = chain_term = None
    else:
        ts_event = seg["ts_event"]
        seg_start = seg["seg_start"]
        chain_term = seg["chain_term"]

    flags = ev["flags"]
    linked = _flag(flags, _F_LINKED) & valid
    pending = _flag(flags, _F_PENDING)
    is_post = _flag(flags, _F_POST)
    is_void = _flag(flags, _F_VOID)
    pv = is_post | is_void
    timeout_ns = jnp.uint64(ev["timeout"]) * _NSPS

    spmd = per_event is not None
    # The in-window join fixup path: a sharded per-event bundle feeding
    # a fixpoint tail — the join is computed here, replicated, and the
    # substitution re-applied to the bundle (parallel/full_sharded.py).
    spmd_join = spmd and limit_rounds > 1 and not imported_mode
    imported_ctx = None
    if imported_mode:
        assert not balancing_mode, \
            "the imported and balancing tiers do not compose"
        assert not (spmd and limit_rounds == 1), \
            "the sharded imported tail always runs the fixpoint rounds"
        if per_event is None:
            imported_ctx = imported_batch_ctx(
                state, ev, ts_event, valid, idxs, seg_start)
    if per_event is None and limit_rounds > 1 and not imported_mode:
        # Fixpoint tiers: the precise dup/join split + in-window pending
        # substitution (~50 extra ops — only these tiers can USE the
        # join, so only they pay for it).
        e2, inwin_raw, didx = _dup_and_pend_join(ev, valid, pv, idxs, N)
        per_event = per_event_status(state, ev, ts_event,
                                     return_gathers=True,
                                     inwin=inwin_raw, didx=didx)
        inwin = per_event["inwin"]
        didx = per_event["didx"]
        status_dead = per_event["status_pre_dead"]
    elif per_event is None:
        # Plain tier (the scan hot path) and the imported tiers: the
        # legacy combined dup check — ONE cheap sort, no join, no
        # substitution. Any collision (same-kind dup OR an in-batch
        # pending reference) sets e2; the plain tier's escalation flag
        # routes e2-only batches to the fixpoint tier, whose precise
        # join then either resolves the pending reference on device or
        # (real duplicates) falls back to host. The imported tiers keep
        # e2 hard (the join's substitution is not imported-aware: an
        # imported definition's stored timestamp is the USER's).
        e2 = _combined_dup_keys(ev, valid, pv)
        per_event = per_event_status(state, ev, ts_event,
                                     return_gathers=True,
                                     imported_ctx=imported_ctx)
        inwin = jnp.zeros(N, dtype=jnp.bool_)
        didx = jnp.zeros(N, dtype=jnp.int32)
        status_dead = per_event["status_pre"]
    elif spmd_join:
        # SPMD fixpoint tail: the bundle was computed per shard WITHOUT
        # the batch-global join — compute the join replicated here and
        # re-apply the substitution to the re-gathered view below. The
        # substitution gate (definition id absent from the table) reads
        # the bundle's own-id probe lanes; a use whose OWN id collides
        # with the table would need the substituted exists evaluation —
        # that vanishing edge stays a hard fallback (folded into e2).
        e2, inwin_raw, didx = _dup_and_pend_join(ev, valid, pv, idxs, N)
        ef_b = per_event["e_found"]
        of_b = per_event["o_found"]
        eo_b = (ef_b.astype(jnp.uint8) | (of_b.astype(jnp.uint8) << 1))
        inwin = inwin_raw & (eo_b[didx] == 0)
        e2 = e2 | jnp.any(inwin_raw & (eo_b != 0))
        didx = jnp.where(inwin, didx, 0)
        # The unsubstituted bundle status IS the dead-definition
        # variant: for a gated in-window use the table lookup missed,
        # which is exactly the missing-definition sequence.
        status_dead = per_event["status_pre"]
    else:
        # SPMD plain/imported tail: per-shard statuses were computed
        # without the batch-global join — any id/pid collision (incl.
        # in-batch pending refs) escalates (plain) or falls back
        # (imported). Same-kind duplicates fall back either way.
        e2 = _combined_dup_keys(ev, valid, pv)
        inwin = jnp.zeros(N, dtype=jnp.bool_)
        didx = jnp.zeros(N, dtype=jnp.int32)
        status_dead = per_event["status_pre"]
    dr_rowc = per_event["dr_row"]
    cr_rowc = per_event["cr_row"]
    p_rowc = per_event["p_row"]
    dr_found = per_event["dr_found"]
    cr_found = per_event["cr_found"]
    p_found = per_event["p_found"]
    amt_res_hi = per_event["amt_res_hi"]
    amt_res_lo = per_event["amt_res_lo"]
    ts_actual = per_event["ts_pre"]
    # Closing-native (every fixpoint tier, imported and SPMD included):
    # closing_debit/closing_credit and void-reopens run on device — the
    # closed-state evolution joins the K-round fixpoint (reference
    # :3837 close gate, :3941-3944 set, :4184-4189 void exception,
    # :4254-4261 reopen). The base status is then the closed-STRIPPED
    # variant; the closed codes are reapplied each round from the
    # evolving in-batch closed state. Eligibility is uniform across
    # single-chip and SPMD: the plain tiers escalate closing to their
    # fixpoint sibling instead of hard-falling-back to the host.
    closing_native = limit_rounds > 1
    status = (per_event["status_nc"] if closing_native
              else per_event["status_pre"])

    if imported_mode and limit_rounds == 1:
        # ---- in-batch regress: the left-to-right maxima chain ----
        # (see the imported_mode docstring for why this closed form is
        # exactly the sequential applied set). actual_ts of an applied
        # event enters the running max whether imported (user ts) or
        # not (ts_event) — reference key_max advances on every created
        # transfer (the sequential kernel's st.key_max).
        imp_lane = _flag(flags, _F_IMPORTED)
        actual_vec = jnp.where(imp_lane, ev["ts"], ts_event)
        base_ok = valid & (status == _CREATED)
        cand = jnp.where(base_ok, actual_vec, jnp.uint64(0))
        run_incl = _cummax(cand)
        run_excl = jnp.maximum(
            state["xfer_key_max"],
            jnp.concatenate([state["xfer_key_max"][None], run_incl[:-1]]))
        chain_low = imp_lane & valid & (ev["ts"] <= run_excl)
        # Precedence: statuses checked AFTER the regress position in the
        # sequential order must yield to regress when the event would
        # also regress in-batch (it can never apply either way, so the
        # maxima chain is unaffected). The code set is derived from the
        # check lists themselves (per_event_status after_regress_codes).
        in_after = jnp.zeros_like(valid)
        for code in per_event["after_regress_codes"]:
            in_after = in_after | (status == jnp.uint32(code))
        override = chain_low & (base_ok | in_after)
        status = jnp.where(
            override, _TS["imported_event_timestamp_must_not_regress"],
            status)
        ts_actual = jnp.where(override, ts_event, ts_actual)

    if "_gathers" in per_event:
        dr, cr, p, p_dr, p_cr = per_event["_gathers"]
    else:
        # SPMD path: re-gather the touched rows locally (cheap O(N)
        # gathers on replicated state; keeps the all-gathered per-event
        # bundle compact).
        (p,) = _xfer_gather_multi(xfr, [p_rowc])
        if spmd_join:
            # Re-apply the in-window pending substitution to the
            # re-gathered view — same builder as per_event_status's
            # internal substitution, so the two cannot drift.
            p2 = _inwin_def_view(ev, ts_event, didx, dr_rowc, cr_rowc)
            p = {k: jnp.where(inwin, p2[k], p[k]) for k in p}
            p_found = p_found | inwin
        dr, cr, p_dr, p_cr = _acct_gather_multi(
            acc, [dr_rowc, cr_rowc, p["dr_row"], p["cr_row"]],
            [dr_found, cr_found, p_found, p_found])
        if spmd_join:
            # Status fixup for substituted lanes: the shard bundle
            # evaluated them against a MISSING pending, so the only
            # possible p-dependent status is pending_transfer_not_found
            # (every check sequenced before it is p-independent, and
            # the wrapper codes are too). Re-run the shared post/void
            # evaluation with the substituted view and replace exactly
            # those lanes; the resolved sentinel amount rides along.
            pv_status_s, _, pv_amt_hi_s, pv_amt_lo_s, _ = _pv_eval(
                ev, p, p_found, p_dr, p_cr, ts_event)
            fix = inwin & pv & (
                status == _TS["pending_transfer_not_found"])
            # This path is always a fixpoint tail (closing-native): the
            # working status is the closed-STRIPPED variant, so strip
            # the substituted code the same way (pv lanes: closed ->
            # CREATED; the rounds re-derive the closed decision).
            is_cl_s = (
                (pv_status_s == _TS["debit_account_already_closed"])
                | (pv_status_s == _TS["credit_account_already_closed"]))
            status = jnp.where(
                fix, jnp.where(is_cl_s, _CREATED, pv_status_s), status)
            amt_res_hi = jnp.where(inwin & pv, pv_amt_hi_s, amt_res_hi)
            amt_res_lo = jnp.where(inwin & pv, pv_amt_lo_s, amt_res_lo)

    # ---------------- eligibility ----------------
    # Scalar-reduction fusion (dispatch-count discipline): e1/e5 and the
    # eight overflow lanes are all length-N bools whose ONLY consumer is
    # the combined `others` OR — they reduce in ONE stacked any below
    # (hard_vecs) instead of three separate reduces.
    if imported_mode:
        # Imported events are native here; balancing stays hard, and
        # closing is ESCALATABLE on the plain imported tier (to the
        # imported fixpoint tier, where it runs native) — uniform
        # closing eligibility across tiers. Chains are the one
        # interaction the maxima chain cannot express (a rollback
        # rewinds the running max — including a NON-imported chain
        # whose members' ts_event entered the max before the rollback),
        # so a dispatch carrying BOTH imported events and links
        # anywhere falls back to exact (scalar gate folded into e1 via
        # broadcast).
        hard_flags = _F_BAL_DR | _F_BAL_CR
        impchain = (jnp.any(valid & _flag(flags, _F_IMPORTED))
                    & jnp.any(linked))
        e1_vec = valid & (_flag(flags, jnp.uint32(hard_flags))
                          | impchain)
    elif balancing_mode:
        assert limit_rounds > 1, \
            "balancing_mode rides the limit fixpoint"
        # Balancing clamps AND closing resolve inside the fixpoint;
        # imported has its own tier. In-window pending defs that are
        # THEMSELVES balancing fall back: the in-window substitution
        # reads the def's nominal event lanes, but its stored (and
        # releasable) amount is the clamp.
        hard_flags = _F_IMPORTED
        e1_vec = valid & (
            _flag(flags, jnp.uint32(hard_flags))
            | (inwin & _flag(flags[didx],
                             jnp.uint32(_F_BAL_DR | _F_BAL_CR))))
    elif closing_native:
        # Plain fixpoint tier: closing is native (closed-state evolution
        # joins the rounds); balancing still needs the balancing tier's
        # amount iteration.
        hard_flags = _F_IMPORTED | _F_BAL_DR | _F_BAL_CR
        e1_vec = valid & _flag(flags, jnp.uint32(hard_flags))
    else:
        # Plain tier, single-chip or sharded: closing flags are
        # RESOLVABLE on the fixpoint tier — they escalate (limit_only
        # redispatch, or the sharded router's fixpoint step) instead of
        # hard-falling-back to the host (e_close_vec below).
        hard_flags = _F_IMPORTED | _F_BAL_DR | _F_BAL_CR
        e1_vec = valid & _flag(flags, jnp.uint32(hard_flags))
    e_close_vec = (valid & _flag(flags, jnp.uint32(_F_CLOSE_DR
                                                   | _F_CLOSE_CR))
                   if limit_rounds == 1
                   else jnp.zeros_like(valid))

    # Eligibility sums below run over the OPTIMISTIC apply set: events
    # whose per-event status is already a failure can never apply (the
    # fixpoint only flips events within this set toward failure), so
    # excluding them keeps every proof a true upper bound — and keeps
    # doomed events' sentinel amounts (e.g. a post-of-post carrying
    # amount=u128max) from tripping the overflow proof spuriously.
    opt = valid & (status == _CREATED)

    # E3 relaxed (headroom proof): balance-limit-flagged accounts no
    # longer force a fallback outright. A limit check
    # (debits_exceed_credits: dp+dpos+amount > cpos — tigerbeetle.zig:34)
    # is order-dependent only if some prefix of the batch could breach
    # it. We admit the batch when, for every limited account, the
    # WORST-CASE load (sum of ALL candidate amounts against it, ignoring
    # any mid-batch relief from credits/voids — both only widen
    # headroom) still fits the pre-batch headroom: then no event can
    # fail the limit in any prefix, so parallel == sequential. Only a
    # potential breach falls back to the exact path.
    reg = opt & ~pv
    A_rows = acc["u32"].shape[0]
    z64 = jnp.uint64(0)
    ral0, ral1, ral2, ral3 = _to_limbs(
        jnp.where(reg, amt_res_hi, z64), jnp.where(reg, amt_res_lo, z64))
    ral = jnp.stack([ral0, ral1, ral2, ral3], axis=1)  # (N, 4)

    aflags_full = ac_col(acc, "flags")
    # The dump row (last) is scratch: failed creates scatter raw flags
    # there and masked transfers scatter-add amounts into its balances —
    # it must never latch a breach. A static iota mask, not a one-slot
    # scatter (op budget).
    not_dump = (jnp.arange(A_rows, dtype=jnp.int32)
                != jnp.int32(A_rows - 1))

    def _breach(load, held1, held2, against1, limit_bit):
        # (held1 + held2 + load) > against1, evaluated in 5 limbs
        # (each limb sum < 2^46: no u64 overflow before normalize).
        # Returns the per-account breach VECTOR; both sides reduce in
        # one stacked any below.
        # Whole limb COLUMNS of the account store (a_cap rows).
        def balm(col):
            return col64(acc["bal"], col)

        h1, h2, ag = BAL_IDX[held1], BAL_IDX[held2], BAL_IDX[against1]
        lft = [balm(h1 + j) + balm(h2 + j) + load[j]
               for j in range(4)]
        c = lft[0] >> jnp.uint64(32); f0 = lft[0] & _M32
        lft[1] = lft[1] + c
        c = lft[1] >> jnp.uint64(32); f1 = lft[1] & _M32
        lft[2] = lft[2] + c
        c = lft[2] >> jnp.uint64(32); f2 = lft[2] & _M32
        lft[3] = lft[3] + c
        l4 = lft[3] >> jnp.uint64(32); f3 = lft[3] & _M32
        left_hi = f2 | (f3 << jnp.uint64(32))
        left_lo = f0 | (f1 << jnp.uint64(32))
        right_hi = balm(ag + 2) | (balm(ag + 3) << jnp.uint64(32))
        right_lo = balm(ag) | (balm(ag + 1) << jnp.uint64(32))
        limited = _flag(aflags_full, limit_bit) & not_dump
        over = (l4 > 0) | u128.lt(right_hi, right_lo, left_hi, left_lo)
        return limited & over

    if balancing_mode:
        # The headroom proof is meaningless under balancing (nominal
        # amounts are near-always AMOUNT_MAX) and its limit_hit output
        # is unread by the balancing route — skip the segment-sum
        # reduction; e3 is unconditionally overridden by the fixpoint
        # convergence outcome below (balancing_mode implies
        # limit_rounds > 1).
        e3 = jnp.bool_(False)
    else:
        # ONE segment-sum covers BOTH sides' worst-case loads and ONE
        # stacked any reduces both breach vectors.
        load = _worst_case_loads(ral, dr_rowc, cr_rowc, A_rows)
        e3 = jnp.any(jnp.stack([
            _breach(load[0], "dp", "dpos", "cpos", _A_DR_LIMIT),
            _breach(load[1], "cp", "cpos", "dpos", _A_CR_LIMIT)]))
    # The headroom-proof outcome, preserved across the fixpoint override
    # below: the adaptive router drops back to the proof-gated kernel only
    # once the PROOF would pass (dropping back on "no actual breach" would
    # oscillate on workloads that sit near their limits without crossing).
    proof_breach = e3
    # Rounds the status fixpoint ACTUALLY consumed (telemetry plane):
    # 0 on the proof-gated plain tier, >=1 on fixpoint tiers. Round 0
    # always runs; a later round only counts when the previous one had
    # not converged — so a batch that settles immediately reads 1, a
    # k-deep limit cascade reads k+1, and an unconverged batch reads
    # the full round budget. Elementwise adds only: no heavy-op delta.
    fix_rounds = jnp.int32(0)

    a_hi = jnp.where(opt, amt_res_hi, jnp.uint64(0))
    a_lo = jnp.where(opt, amt_res_lo, jnp.uint64(0))
    l0, l1, l2, l3 = _to_limbs(a_hi, a_lo)
    # One stacked reduction instead of four (dispatch-count discipline).
    s0, s1, s2, s3 = jnp.sum(jnp.stack([l0, l1, l2, l3]), axis=1)
    # S as 5 limbs (normalized).
    c = s0 >> jnp.uint64(32); s0 &= _M32
    s1 += c; c = s1 >> jnp.uint64(32); s1 &= _M32
    s2 += c; c = s2 >> jnp.uint64(32); s2 &= _M32
    s3 += c; s4 = s3 >> jnp.uint64(32); s3 &= _M32
    s_hi = s2 | (s3 << jnp.uint64(32))
    s_lo = s0 | (s1 << jnp.uint64(32))
    # The tightest overflow statuses are overflows_debits/credits, which sum
    # TWO balance fields plus the amount (reference :3874-3884). Bound them
    # with max over touched accounts of (dp+dpos) and (cp+cpos): any
    # already-overflowing pair sum, or pair-max + S >= 2^128, falls back.
    # Every single-field check is dominated by its pair sum.
    zeros = jnp.zeros_like(ev["amt_hi"])
    pair_his, pair_los, pair_ovfs = [], [], []
    for acct_g in (dr, cr, p_dr, p_cr):
        for f1, f2 in (("dp", "dpos"), ("cp", "cpos")):
            h, l, o = u128.add(acct_g[f1][0], acct_g[f1][1],
                               acct_g[f2][0], acct_g[f2][1])
            pair_his.append(jnp.where(opt, h, zeros))
            pair_los.append(jnp.where(opt, l, zeros))
            pair_ovfs.append(opt & o)
    m_hi, m_lo = _u128_max_reduce(pair_his, pair_los)
    _, _, ovf = u128.add(m_hi, m_lo, s_hi, s_lo)
    e5_vec = (valid & is_void & p_found
              & _flag(p["flags"], jnp.uint32(_F_CLOSE_DR | _F_CLOSE_CR)))
    # ONE reduction for every N-length hard-fallback vector: e1 (hard
    # flags) and the eight pair-overflow lanes — their only consumer is
    # the combined OR. The scalar terms (ovf, s4) join at the OR. e5
    # (void of a closing pending) is never hard anymore: native reopen
    # in the closing-native (fixpoint) tiers, escalatable everywhere
    # else.
    hard_vecs = [e1_vec, *pair_ovfs]
    hard_any = jnp.any(jnp.stack(hard_vecs))
    if balancing_mode:
        # The E4 amount-sum proof is useless under balancing: the
        # idiomatic AMOUNT_MAX nominal ("move everything") always trips
        # it, while the APPLIED amounts are the clamps. The fixpoint
        # instead evaluates the six balance-overflow statuses
        # (reference :3856-3884) EXACTLY each round from the same
        # pre-event balances, with clamped amounts — see the loop. The
        # pair-overflow lanes stay as a (by-invariant never-firing)
        # guard on pre-batch state.
        e145 = hard_any
    else:
        e145 = hard_any | ovf | (s4 > 0)

    if limit_rounds > 1:
        # ---- order-dependent balance limits: K-round status fixpoint ----
        # Sequential semantics: event i's limit check reads the balances
        # produced by every SUCCESSFUL earlier event (incl. pending adds
        # and pv releases). Iterate: start optimistic (no limit failures),
        # each round re-derive chains + applied deltas + exact per-event
        # PRE-event balances (segmented exclusive prefix sums over a
        # status-independent sort), re-evaluate the limit checks, repeat.
        # Each round fixes at least the earliest event whose status
        # disagrees with the sequential truth (its own prefix is already
        # correct and stays correct), so K rounds resolve any batch whose
        # limit-decision cascade is shallower than K; deeper cascades
        # fall back to the exact host path, and the converging form
        # (LIMIT_FIXPOINT_CONVERGE) runs rounds until they stop
        # changing, N + 1 at most. Dependency deaths fold into
        # the SAME round's apply set (a second cheap chain pass), so one
        # round advances a full over->death->relief wave — without the
        # fold the wave costs two rounds (measured: the config4 window
        # workload converges at half the rounds with it).
        alx = _to_limbs(amt_res_hi, amt_res_lo)
        nlx = _neg_limbs(p["amt_hi"], p["amt_lo"])
        frows2 = jnp.concatenate([
            jnp.where(valid, jnp.where(pv, p["dr_row"], dr_rowc), A_dump),
            jnp.where(valid, jnp.where(pv, p["cr_row"], cr_rowc), A_dump),
        ])
        forder = jnp.concatenate([idxs, idxs])
        fperm = _packed_perm(frows2, forder, A_rows)
        frows_sorted = frows2[fperm]
        fstart = jnp.concatenate([
            jnp.ones(1, dtype=jnp.bool_),
            frows_sorted[1:] != frows_sorted[:-1]])
        fseg_id = _cumsum(fstart.astype(jnp.int32)) - 1
        # Per-entry segment-start position: forward-fill of start
        # positions (one running max — start positions increase), not a
        # segment reduce + gather (op budget).
        fseg_start = _cummax(jnp.where(
            fstart, jnp.arange(2 * N, dtype=jnp.int32), jnp.int32(-1)))
        finv = jnp.zeros(2 * N, dtype=jnp.int32).at[fperm].set(
            jnp.arange(2 * N, dtype=jnp.int32))
        fbase = widen(acc["bal"][frows_sorted]).T.reshape(4, 4, 2 * N)
        cand_dr = (valid & ~pv & _flag(dr["flags"], _A_DR_LIMIT)
                   & (status == _CREATED))
        cand_cr = (valid & ~pv & _flag(cr["flags"], _A_CR_LIMIT)
                   & (status == _CREATED))
        # Round-static sorted-space operands: the per-entry amount limbs
        # and the entry side never change across rounds, so they sort
        # ONCE; each round gathers only a packed u8 apply-mask (one
        # 2N-byte gather) instead of permuting the (4,4,2N) u64 delta
        # matrix (256N bytes) — the loop's dominant operand traffic.
        al2_s = [jnp.concatenate([alx[j], alx[j]])[fperm]
                 for j in range(4)]
        nl2_s = [jnp.concatenate([nlx[j], nlx[j]])[fperm]
                 for j in range(4)]
        cr_side_s = (fperm >= N)  # static: entry index N.. = credit side
        z64_ = jnp.uint64(0)

        if closing_native:
            # ---- in-batch closed-state evolution (reference :3837 gate,
            # :3941-3944 set, :4184-4189 void exception, :4254-4261
            # reopen). closed is per-account last-writer-wins state: an
            # applied closing create sets it, an applied void of a
            # closing pending clears it. Per round, the closed value an
            # event observes is the latest applied set/clear op strictly
            # BEFORE it in its account segment (initial = the pre-batch
            # flag) — one segmented exclusive running-max over op
            # positions, riding the same sorted entry space as the
            # balance prefixes (pv entries already carry the pending's
            # accounts, exactly the rows the pv closed checks read).
            # The circularity (closed -> status -> applied -> closed)
            # resolves like limit waves: prefix-stable cascades converge
            # in <= K rounds; chain-rollback interactions (a closing
            # member applied then rolled back mid-batch) oscillate and
            # fall back to the exact host path.
            close_dr_f = _flag(flags, _F_CLOSE_DR)
            close_cr_f = _flag(flags, _F_CLOSE_CR)
            p_cl_dr = _flag(p["flags"], _F_CLOSE_DR)
            p_cl_cr = _flag(p["flags"], _F_CLOSE_CR)
            # Closed-check candidates: the check is reachable iff every
            # earlier-precedence check passed — status_nc (the base
            # `status` here) is CREATED or a code sequenced after the
            # closed position (reg: overflows_timeout; pv: none). Voids
            # are exempt (:4184-4189).
            cand_close = valid & (
                (~pv & ((status == _CREATED)
                        | (status == _TS["overflows_timeout"])))
                | (pv & is_post & (status == _CREATED)))
            # One gather of the meta ROWS serves the round-0 closed
            # view AND the application stage's flag write-back, which
            # rewrites the whole row (no element scatter into a 2-D
            # store: ev_layout).
            meta_s = acc["u32"][frows_sorted]
            base_flags_s = meta_s[:, AC_FLAGS_COL32]
            init_closed_s = _flag(base_flags_s, _A_CLOSED)
            idx2 = jnp.arange(2 * N, dtype=jnp.int32)
            # Round 0: pre-batch closed flags (the per-event gathers).
            cdr_ln = cand_close & _flag(
                jnp.where(pv, p_dr["flags"], dr["flags"]), _A_CLOSED)
            ccr_ln = cand_close & _flag(
                jnp.where(pv, p_cr["flags"], cr["flags"]), _A_CLOSED)
        else:
            cdr_ln = ccr_ln = jnp.zeros_like(valid)

        if balancing_mode:
            # Balancing clamp (reference :3840-3853), evaluated against
            # a pre-event balance view. Always clamps the NOMINAL
            # amount: min(nominal, dr_headroom?, cr_headroom?) — min
            # composes, so recomputing from nominal each round cannot
            # ratchet below the sequential truth.
            bal_dr_ln = valid & ~pv & _flag(flags, _F_BAL_DR)
            bal_cr_ln = valid & ~pv & _flag(flags, _F_BAL_CR)
            bal_ln = bal_dr_ln | bal_cr_ln

            def _bal_clamp(dr_f, cr_f):
                # dr_f/cr_f: field name -> (hi, lo) pre-event balances
                # of the debit / credit account.
                a_hi, a_lo = amt_res_hi, amt_res_lo
                b_hi, b_lo, _ = u128.add(*dr_f("dp"), *dr_f("dpos"))
                av_hi, av_lo = u128.sat_sub(*dr_f("cpos"), b_hi, b_lo)
                m_hi, m_lo = u128.min_(a_hi, a_lo, av_hi, av_lo)
                a_hi = jnp.where(bal_dr_ln, m_hi, a_hi)
                a_lo = jnp.where(bal_dr_ln, m_lo, a_lo)
                b_hi, b_lo, _ = u128.add(*cr_f("cp"), *cr_f("cpos"))
                av_hi, av_lo = u128.sat_sub(*cr_f("dpos"), b_hi, b_lo)
                m_hi, m_lo = u128.min_(a_hi, a_lo, av_hi, av_lo)
                a_hi = jnp.where(bal_cr_ln, m_hi, a_hi)
                a_lo = jnp.where(bal_cr_ln, m_lo, a_lo)
                return a_hi, a_lo

            def _pre_fld(m):
                # 4-limb pre-balance matrix (4 fields, 4 limbs, N) ->
                # (hi, lo) accessor.
                return lambda f: (
                    m[_FI[f], 2] | (m[_FI[f], 3] << jnp.uint64(32)),
                    m[_FI[f], 0] | (m[_FI[f], 1] << jnp.uint64(32)))

            # Round-0 estimate: clamp against PRE-BATCH balances (the
            # dr/cr account gathers) — exact for every event whose
            # touched accounts see no earlier in-batch delta.
            amt_fx_hi, amt_fx_lo = _bal_clamp(
                lambda f: dr[f], lambda f: cr[f])
        else:
            amt_fx_hi, amt_fx_lo = amt_res_hi, amt_res_lo

        def _over(pre_evt, held1, held2, against, amt):
            # (held1_pre + held2_pre + amount) > against_pre, 5 limbs.
            lft = [pre_evt[_FI[held1], j] + pre_evt[_FI[held2], j] + amt[j]
                   for j in range(4)]
            c = lft[0] >> jnp.uint64(32); f0 = lft[0] & _M32
            lft[1] = lft[1] + c
            c = lft[1] >> jnp.uint64(32); f1 = lft[1] & _M32
            lft[2] = lft[2] + c
            c = lft[2] >> jnp.uint64(32); f2 = lft[2] & _M32
            lft[3] = lft[3] + c
            l4 = lft[3] >> jnp.uint64(32); f3 = lft[3] & _M32
            left_hi = f2 | (f3 << jnp.uint64(32))
            left_lo = f0 | (f1 << jnp.uint64(32))
            right_hi = (pre_evt[_FI[against], 2]
                        | (pre_evt[_FI[against], 3] << jnp.uint64(32)))
            right_lo = (pre_evt[_FI[against], 0]
                        | (pre_evt[_FI[against], 1] << jnp.uint64(32)))
            return (l4 > 0) | u128.lt(right_hi, right_lo,
                                      left_hi, left_lo)

        over_dr = jnp.zeros_like(valid)
        over_cr = jnp.zeros_like(valid)
        dead = jnp.zeros_like(valid)
        reg_low = jnp.zeros_like(valid)  # imported: in-batch regress
        ovf_code = jnp.zeros_like(status)  # balancing_mode: exact
        # balance-overflow statuses (:3856-3884), 0 = none.
        fix_converged = jnp.bool_(True)
        if imported_mode:
            # Imported fixpoint tier: the in-batch regress decision (the
            # left-to-right maxima chain — see the imported_mode
            # docstring) is round-dependent here, because the applied
            # set it runs over now evolves with the closed-state /
            # limit decisions. It joins the rounds: same induction, the
            # earliest event whose prefix is sequential truth gets the
            # exact running max and stays fixed.
            imp_lane = _flag(flags, _F_IMPORTED)
            actual_vec = jnp.where(imp_lane, ev["ts"], ts_event)

        converging = limit_rounds == LIMIT_FIXPOINT_CONVERGE
        if converging:
            # A while body runs its ops as written every round: its two
            # scans take the fused shift form, and the chains' numbering,
            # which no round changes, is made once out here.
            round_cumsum = functools.partial(_shift_scan, jnp.add)
            round_cummax = functools.partial(_shift_scan, jnp.maximum)
            chain_id = _chain_ids(linked, _linked_prev(linked, seg_start))
        else:
            round_cumsum, round_cummax, chain_id = _cumsum, _cummax, None

        def _round(carry):
            # One round: the previous round's decisions in, this
            # round's out, and whether they are the same.
            (over_dr, over_cr, dead, cdr_ln, ccr_ln, reg_low, ovf_code,
             amt_fx_hi, amt_fx_lo) = carry
            st_r = jnp.where(ovf_code != 0, ovf_code, status)
            st_r = jnp.where(over_dr, _TS["exceeds_credits"], st_r)
            st_r = jnp.where(over_cr & ~over_dr, _TS["exceeds_debits"],
                             st_r)
            if closing_native:
                # Earlier sequential precedence than the overflow/limit
                # codes (:3837 precedes :3856/:3904) — applied after, so
                # it wins; dr checked before cr.
                st_r = jnp.where(
                    cdr_ln, _TS["debit_account_already_closed"], st_r)
                st_r = jnp.where(
                    ccr_ln & ~cdr_ln,
                    _TS["credit_account_already_closed"], st_r)
            if imported_mode:
                # Regress outranks every code checked after its position
                # (closed / overflow / limit codes — applied above, so
                # this where wins); the override can only hit lanes that
                # could never apply either way, leaving the maxima chain
                # unaffected (same argument as the closed form).
                base_ok_r = valid & (st_r == _CREATED)
                cand_r = jnp.where(base_ok_r, actual_vec, jnp.uint64(0))
                run_incl_r = _cummax(cand_r)
                run_excl_r = jnp.maximum(
                    state["xfer_key_max"],
                    jnp.concatenate([state["xfer_key_max"][None],
                                     run_incl_r[:-1]]))
                chain_low_r = imp_lane & valid & (ev["ts"] <= run_excl_r)
                in_after_r = ((st_r == _TS["exceeds_credits"])
                              | (st_r == _TS["exceeds_debits"]))
                for code in per_event["after_regress_codes"]:
                    in_after_r = in_after_r | (st_r == jnp.uint32(code))
                new_reg_low = chain_low_r & (base_ok_r | in_after_r)
                st_r = jnp.where(
                    new_reg_low,
                    _TS["imported_event_timestamp_must_not_regress"],
                    st_r)
            else:
                new_reg_low = reg_low
            # In-window dependency deaths from the PREVIOUS round's
            # final statuses: a use whose definition did not create
            # reads pending_transfer_not_found (sequential truth).
            st_r = jnp.where(dead, status_dead, st_r)
            st_c, _, my_first_r, in_chain_r = _chain_pass(
                st_r, linked, valid, idxs, n, N, seg_start, chain_term,
                chain_id)
            # Definition liveness AS OF THE USE's execution point: the
            # def is absent iff it failed on its own (pre-chain status)
            # or its chain broke STRICTLY BEFORE the use — a chain whose
            # first failure IS the use itself still had the def applied
            # when the use evaluated (the rollback happens at the use's
            # failure, after its own status code is assigned; reference
            # execute_create :3116-3150). Packed into ONE def-side
            # gather (op budget): -1 = own failure (always < use idx),
            # the chain's first-failure position when in a chain, else
            # +INF (never < use idx).
            dead_enc = jnp.where(
                st_r != _CREATED, jnp.int32(-1),
                jnp.where(in_chain_r, my_first_r, _INF))
            new_dead = inwin & (dead_enc[didx] < idxs)
            # Gauss-Seidel fold: apply the NEW deaths to this round's
            # apply set (chains re-derived over the folded statuses), so
            # the over->death->lost-relief wave completes in ONE round.
            # At a fixpoint new_dead == dead and the fold is an identity,
            # so the converged statuses are unchanged by it.
            st_f = jnp.where(new_dead & ~dead, status_dead, st_r)
            st_c, _, _, _ = _chain_pass(
                st_f, linked, valid, idxs, n, N, seg_start, chain_term,
                chain_id)
            ap_r = valid & (st_c == _CREATED)
            # Delta lanes directly in sorted entry space: one u8 mask
            # gather + fused elementwise selects against the hoisted
            # sorted amount limbs (al2_s/nl2_s). Lane semantics MUST
            # match _delta_lanes2 (the application stage's builder) —
            # see its docstring.
            mask8 = ((ap_r & ~pv & ~pending).astype(jnp.uint8)
                     | ((ap_r & ~pv & pending).astype(jnp.uint8) << 1)
                     | ((ap_r & pv).astype(jnp.uint8) << 2)
                     | ((ap_r & pv & is_post).astype(jnp.uint8) << 3))
            if closing_native:
                # Closed-op bits ride the SAME u8 gather: 4/5 = applied
                # closing create (dr/cr side), 6/7 = applied void of a
                # closing pending (clears the pending's dr/cr account).
                mask8 = (mask8
                         | ((ap_r & ~pv & close_dr_f)
                            .astype(jnp.uint8) << 4)
                         | ((ap_r & ~pv & close_cr_f)
                            .astype(jnp.uint8) << 5)
                         | ((ap_r & pv & is_void & p_cl_dr)
                            .astype(jnp.uint8) << 6)
                         | ((ap_r & pv & is_void & p_cl_cr)
                            .astype(jnp.uint8) << 7))
            m_s = jnp.concatenate([mask8, mask8])[fperm]
            reg_s = (m_s & 1) != 0
            pend_s = (m_s & 2) != 0
            pv_s = (m_s & 4) != 0
            post_s = (m_s & 8) != 0
            if closing_native:
                set_s = jnp.where(cr_side_s, (m_s & 32) != 0,
                                  (m_s & 16) != 0)
                clr_s = jnp.where(cr_side_s, (m_s & 128) != 0,
                                  (m_s & 64) != 0)
                op_pos = jnp.where(set_s | clr_s, idx2, jnp.int32(-1))
                incl_op = round_cummax(op_pos)
                excl_op = jnp.concatenate(
                    [jnp.full((1,), -1, jnp.int32), incl_op[:-1]])
                # In-segment iff the latest op position is at/after my
                # segment's start (the sort is segment-contiguous).
                has_prev = excl_op >= fseg_start
                closed_pre_s = jnp.where(
                    has_prev, set_s[jnp.maximum(excl_op, 0)],
                    init_closed_s)
                closed_pre = closed_pre_s[finv]
                new_cdr = cand_close & closed_pre[:N]
                new_ccr = cand_close & closed_pre[N:]
            else:
                new_cdr, new_ccr = cdr_ln, ccr_ln
            if balancing_mode:
                # Amounts are round-varying (the clamp): one stacked
                # sorted-space gather of the current limbs replaces the
                # hoisted al2_s (identical on non-balancing lanes).
                al_ev = jnp.stack(_to_limbs(amt_fx_hi, amt_fx_lo))
                al_use = jnp.take(
                    jnp.concatenate([al_ev, al_ev], axis=1), fperm,
                    axis=1)
            else:
                al_use = al2_s
            held = [jnp.where(pend_s, al_use[j], z64_)
                    + jnp.where(pv_s, nl2_s[j], z64_) for j in range(4)]
            posted = [jnp.where(reg_s | post_s, al_use[j], z64_)
                      for j in range(4)]
            fls = jnp.stack([
                jnp.stack([jnp.where(cr_side_s, z64_, held[j])
                           for j in range(4)]),       # dp
                jnp.stack([jnp.where(cr_side_s, z64_, posted[j])
                           for j in range(4)]),       # dpos
                jnp.stack([jnp.where(cr_side_s, held[j], z64_)
                           for j in range(4)]),       # cp
                jnp.stack([jnp.where(cr_side_s, posted[j], z64_)
                           for j in range(4)]),       # cpos
            ])
            fcs = round_cumsum(fls, axis=2)
            foff = jnp.where(
                fseg_start > 0,
                jnp.take(fcs, jnp.maximum(fseg_start - 1, 0), axis=2),
                jnp.uint64(0))
            # EXCLUSIVE prefix = pre-event balances (subtract own delta);
            # all lane limbs < 2^32, prefixes < 2^45: carry-safe.
            pre = jnp.stack(_normalize_limbs(fbase + fcs - foff - fls),
                            axis=1)
            pre_ev = jnp.take(pre, finv, axis=2)
            pre_dr = pre_ev[:, :, :N]
            pre_cr = pre_ev[:, :, N:]
            if balancing_mode:
                # Clamp FIRST, then overflow, then the limit checks —
                # all with the clamped amount against the same
                # pre-event balances, exactly the sequential order
                # (reference :3840-3904).
                amt_new_hi, amt_new_lo = _bal_clamp(
                    _pre_fld(pre_dr), _pre_fld(pre_cr))
                alx_r = _to_limbs(amt_new_hi, amt_new_lo)
                amt_stable = jnp.all((amt_new_hi == amt_fx_hi)
                                     & (amt_new_lo == amt_fx_lo))
                amt_fx_hi, amt_fx_lo = amt_new_hi, amt_new_lo

                # The six balance-overflow statuses, exact (the E4
                # amount-sum proof is bypassed in this mode). They sit
                # between the clamp and overflows_timeout in the
                # sequential order, so they override a CREATED or an
                # overflows_timeout pre-status — nothing earlier.
                def _sum_ovf(pre_evt, f1, f2=None):
                    lft = [pre_evt[_FI[f1], j]
                           + (pre_evt[_FI[f2], j] if f2 else z64_)
                           + alx_r[j] for j in range(4)]
                    c = lft[0] >> jnp.uint64(32)
                    c = (lft[1] + c) >> jnp.uint64(32)
                    c = (lft[2] + c) >> jnp.uint64(32)
                    return ((lft[3] + c) >> jnp.uint64(32)) > 0

                ovf_cand = (valid & ~pv
                            & ((status == _CREATED)
                               | (status == _TS["overflows_timeout"])))
                new_ovf = jnp.zeros_like(status)
                for cond, code in reversed([
                    (pending & _sum_ovf(pre_dr, "dp"),
                     _TS["overflows_debits_pending"]),
                    (pending & _sum_ovf(pre_cr, "cp"),
                     _TS["overflows_credits_pending"]),
                    (_sum_ovf(pre_dr, "dpos"),
                     _TS["overflows_debits_posted"]),
                    (_sum_ovf(pre_cr, "cpos"),
                     _TS["overflows_credits_posted"]),
                    (_sum_ovf(pre_dr, "dp", "dpos"),
                     _TS["overflows_debits"]),
                    (_sum_ovf(pre_cr, "cp", "cpos"),
                     _TS["overflows_credits"]),
                ]):
                    new_ovf = jnp.where(ovf_cand & cond, code, new_ovf)
                no_ovf = new_ovf == 0
            else:
                alx_r = alx
                amt_stable = jnp.bool_(True)
                new_ovf = ovf_code
                no_ovf = jnp.bool_(True)
            new_over_dr = (cand_dr & no_ovf
                           & _over(pre_dr, "dp", "dpos", "cpos", alx_r))
            new_over_cr = (cand_cr & no_ovf
                           & _over(pre_cr, "cp", "cpos", "dpos", alx_r))
            fix_converged = jnp.all((new_over_dr == over_dr)
                                    & (new_over_cr == over_cr)
                                    & (new_ovf == ovf_code)
                                    & (new_dead == dead)
                                    & (new_cdr == cdr_ln)
                                    & (new_ccr == ccr_ln)
                                    & (new_reg_low == reg_low)) & amt_stable
            return (new_over_dr, new_over_cr, new_dead, new_cdr, new_ccr,
                    new_reg_low, new_ovf, amt_fx_hi, amt_fx_lo), \
                fix_converged

        carry = (over_dr, over_cr, dead, cdr_ln, ccr_ln, reg_low, ovf_code,
                 amt_fx_hi, amt_fx_lo)
        if converging:
            # Rounds until one leaves every decision as it found it, in
            # one while loop. By the induction above N + 1 rounds always
            # converge; the cap only bounds the loop. A round that
            # brings back the decisions of the round before last is a
            # 2-cycle (the closing chain rollback above), which never
            # converges: the loop stops there, unconverged, and the
            # batch falls back as it would at the cap.
            def _go_on(loop):
                rounds, converged, cycled, _, _ = loop
                return ~converged & ~cycled & (rounds <= N)

            def _next(loop):
                rounds, _, _, before, now = loop
                after, converged = _round(now)
                cycled = functools.reduce(jnp.logical_and, [
                    jnp.all(a == b) for a, b in zip(after, before)])
                return rounds + 1, converged, cycled, now, after

            fix_rounds, fix_converged, _, _, carry = jax.lax.while_loop(
                _go_on, _next, (fix_rounds, jnp.bool_(False),
                                jnp.bool_(False), carry, carry))
        else:
            for _r in range(limit_rounds):
                fix_rounds = fix_rounds + (
                    jnp.int32(1) if _r == 0
                    else (~fix_converged).astype(jnp.int32))
                carry, fix_converged = _round(carry)
        (over_dr, over_cr, dead, cdr_ln, ccr_ln, reg_low, ovf_code,
         amt_fx_hi, amt_fx_lo) = carry
        status = jnp.where(ovf_code != 0, ovf_code, status)
        status = jnp.where(over_dr, _TS["exceeds_credits"], status)
        status = jnp.where(over_cr & ~over_dr, _TS["exceeds_debits"],
                           status)
        if closing_native:
            status = jnp.where(
                cdr_ln, _TS["debit_account_already_closed"], status)
            status = jnp.where(
                ccr_ln & ~cdr_ln,
                _TS["credit_account_already_closed"], status)
        if imported_mode:
            # Regress precedes the closed/overflow/limit positions in
            # the sequential order — applied after them, so it wins; a
            # regress-overridden lane reverts to its event timestamp.
            status = jnp.where(
                reg_low, _TS["imported_event_timestamp_must_not_regress"],
                status)
            ts_actual = jnp.where(reg_low, ts_event, ts_actual)
        status = jnp.where(dead, status_dead, status)
        if imported_mode:
            # ts_pre followed the PER-EVENT status, but the rounds can
            # flip an imported lane either way (closed-stripped base ->
            # applies; in-batch close -> dies): the result/applied
            # timestamp follows the FINAL status — created -> the user
            # timestamp, exists -> the stored row's (ts_pre carries it),
            # any other failure -> the event timestamp.
            ts_actual = jnp.where(
                imp_lane & (status != _TS["exists"]),
                jnp.where(status == _CREATED, ev["ts"], ts_event),
                ts_actual)
        if balancing_mode:
            # Converged clamped amounts become the applied/stored
            # amounts: row inserts, the event ring's amt (areq keeps
            # the nominal), the application delta lanes, and the
            # balancing exists-comparison all read amt_res downstream.
            amt_res_hi = jnp.where(bal_ln, amt_fx_hi, amt_res_hi)
            amt_res_lo = jnp.where(bal_ln, amt_fx_lo, amt_res_lo)
        e3 = ~fix_converged

    # ---------------- chains: segment first-failure broadcast ----------------
    status, not_the_failure, my_first, in_chain = _chain_pass(
        status, linked, valid, idxs, n, N, seg_start, chain_term)
    ts_actual = jnp.where(not_the_failure, ts_event, ts_actual)

    status = jnp.where(valid, status, jnp.uint32(0))
    created = valid & (status == _CREATED)
    # Events applied then rolled back by a chain break: everything before the
    # chain's first failure that had passed validation. pulse_next updates
    # from these survive rollback (reference scope semantics — see oracle
    # _Scope note).
    applied_ever = created | (
        in_chain & valid & (status == _TS["linked_event_failed"])
        & (idxs < my_first))

    # ------- commit/abort decision (fully read-only planning) -------
    # All remaining fallback causes are resolved BEFORE any state write, so
    # the abort path is "mask every scatter to the dump slot" — the donated
    # state buffers are updated in place and never copied.
    row_off = (_cumsum(created.astype(jnp.int32))
               - created.astype(jnp.int32))
    n_created = jnp.sum(created, dtype=jnp.int32)
    new_rows = xfr["count"] + row_off

    e7 = ((xfr["count"] + n_created) > jnp.int32(T_dump))
    # Event-ring capacity (expiry rows pushed from the host can make the
    # events count exceed the transfers count, so it needs its own guard).
    # ring_reset (static): pipelined serving windows consume the event
    # ring from offset 0 each dispatch — the window's delta gather is
    # enqueued BEFORE the next window's kernel, so on the device's FIFO
    # stream the rows are read before they can be overwritten. Keeps the
    # ring a bounded per-window transport without a host-side recycle
    # barrier between pipelined windows.
    ring_base = jnp.int32(0) if ring_reset else state["events"]["count"]
    e8 = ((ring_base + n_created) > jnp.int32(ev_cap(state["events"])))

    transient = jnp.zeros_like(valid)
    for code in _TRANSIENT_CODES:
        transient = transient | (status == code)
    orphan_new = valid & transient

    # Created rows and new orphans are disjoint id sets in the SAME
    # table (orphans carry ORPHAN_VAL): one plan + one write.
    ins_mask = created | orphan_new
    xfer_pos, ins_ok = ht_plan(
        state["xfer_ht"], ev["id_hi"], ev["id_lo"], ins_mask)

    if imported_mode and limit_rounds == 1:
        # Plain imported tier: closing flags, voids of closing pendings
        # and potential limit breaches escalate to the imported
        # FIXPOINT tier (closing/limits run native there — uniform
        # eligibility). Collisions stay hard: the join's in-window
        # substitution is not imported-aware.
        others = e145 | e2 | e7 | e8 | ~ins_ok
        escalatable = (e3
                       | jnp.any(jnp.stack([e_close_vec, e5_vec])))
    elif limit_rounds == 1:
        # Plain tier (single-chip or the sharded plain tail): e2 is the
        # COMBINED collision check — it may be an in-batch pending
        # reference the fixpoint tier can resolve (the sharded fixpoint
        # tail computes the join replicated), so it escalates instead
        # of hard-falling-back. Closing flags and voids of closing
        # pendings (e5) likewise: the fixpoint tier runs them natively.
        others = e145 | e7 | e8 | ~ins_ok
        escalatable = (e3 | e2
                       | jnp.any(jnp.stack([e_close_vec, e5_vec])))
    else:
        # Fixpoint tiers (incl. the SPMD join tail and the imported
        # fixpoint tier): e2 is precise same-kind duplicates (real
        # fallback; for imported/SPMD it also carries the join's hard
        # edges). Only an unconverged cascade escalates (deeper tier).
        others = e145 | e2 | e7 | e8 | ~ins_ok
        escalatable = e3
    if force_fallback is not None:
        others = others | force_fallback
    fallback = others | escalatable
    # A fallback caused ONLY by the balance-limit headroom proof, a key
    # collision (possible in-window pending reference), a closing flag
    # or a void of a closing pending is resolvable on device: the
    # caller redispatches it to the matching fixpoint variant
    # (limit_rounds > 1) instead of the exact host path.
    limit_only = escalatable & ~others & jnp.bool_(limit_rounds == 1)
    ok = ~fallback

    # ---------------- application (all masked by ok) ----------------
    ap = created & ok
    ap_reg = ap & ~pv & ~pending
    ap_pend = ap & ~pv & pending
    ap_pv = ap & pv
    ap_post = ap_pv & is_post

    al0, al1, al2, al3 = _to_limbs(amt_res_hi, amt_res_lo)
    nl0, nl1, nl2, nl3 = _neg_limbs(p["amt_hi"], p["amt_lo"])
    # Balance application happens below, fused into the account_events
    # snapshot computation: the snapshot's segmented prefix sums already
    # produce every touched account's exact post-event balances, and the
    # LAST entry per account row is the post-BATCH balance — one masked
    # scatter per limb replaces per-delta scatter-adds plus a separate
    # carry-normalize pass.

    # Insert created transfer rows (compacted).
    trow = jnp.where(ap, new_rows, T_dump)
    ud128z = u128.is_zero(ev["ud128_hi"], ev["ud128_lo"])
    stores = dict(
        id_hi=ev["id_hi"], id_lo=ev["id_lo"],
        dr_hi=jnp.where(pv, p["dr_hi"], ev["dr_hi"]),
        dr_lo=jnp.where(pv, p["dr_lo"], ev["dr_lo"]),
        cr_hi=jnp.where(pv, p["cr_hi"], ev["cr_hi"]),
        cr_lo=jnp.where(pv, p["cr_lo"], ev["cr_lo"]),
        amt_hi=amt_res_hi, amt_lo=amt_res_lo,
        pid_hi=ev["pid_hi"], pid_lo=ev["pid_lo"],
        ud128_hi=jnp.where(pv & ud128z, p["ud128_hi"], ev["ud128_hi"]),
        ud128_lo=jnp.where(pv & ud128z, p["ud128_lo"], ev["ud128_lo"]),
        ud64=jnp.where(pv & (ev["ud64"] == 0), p["ud64"], ev["ud64"]),
        ud32=jnp.where(pv & (ev["ud32"] == 0), p["ud32"], ev["ud32"]),
        timeout=jnp.where(pv, jnp.uint32(0), ev["timeout"]),
        ledger=jnp.where(pv, p["ledger"], ev["ledger"]),
        code=jnp.where(pv, p["code"], ev["code"]),
        flags=flags,
        # Stored/applied timestamp: the ACTUAL one (imported created
        # rows keep their user timestamp; == ts_event otherwise).
        ts=ts_actual,
        pstat=jnp.where(pending & ~pv, _PS_PENDING, jnp.int32(0)),
        expires=jnp.where(pending & ~pv & (ev["timeout"] != 0),
                          ts_actual + timeout_ns, jnp.uint64(0)),
        dr_row=jnp.where(pv, p["dr_row"], dr_rowc),
        cr_row=jnp.where(pv, p["cr_row"], cr_rowc),
    )
    # Packed row insert: ONE row scatter of u32 rows (ev_layout).
    # Masked lanes write uniform zero rows to the dump slot
    # (duplicate-index scatters stay deterministic only if every
    # duplicate writes one value).
    rows32 = xf_rows32(stores)
    # Pending-status flips on committed pendings: a SECOND ROW scatter
    # that rewrites the whole pending row, as gathered, with its pstat
    # flipped (E2 guarantees unique rows; masked lanes write zero rows
    # to the dump slot). An element scatter into the one column would
    # make XLA:TPU relayout the whole store around it (PERF.md §6,
    # PR 32). An in-window use flips the row its definition is
    # inserting IN THIS DISPATCH — at trow[didx] — so the flip runs
    # AFTER the insert (below) and rewrites the inserted row.
    flip_rows32 = xf_rows32(p)
    if limit_rounds > 1 and not imported_mode:
        # The definition's inserted row and where it goes: ONE gather.
        d = jnp.concatenate(
            [rows32, trow[:, None].astype(jnp.uint32)], axis=1)[didx]
        flip_row = jnp.where(inwin, d[:, -1].astype(jnp.int32), p_rowc)
        flip_rows32 = jnp.where(inwin[:, None], d[:, :-1], flip_rows32)
    else:
        # inwin is statically all-False on these tiers: skip the
        # def-side gather entirely (op budget).
        flip_row = p_rowc
    flip_pos = jnp.where(ap_pv, flip_row, T_dump)
    flip_rows32 = with_col32(
        flip_rows32, XF_PSTAT_COL32,
        jnp.where(is_post, _PS_POSTED, _PS_VOIDED))
    inserted = xfr["u32"].at[trow].set(
        jnp.where(ap[:, None], rows32, jnp.uint32(0)))
    new_xfr = {
        "u32": inserted.at[flip_pos].set(
            jnp.where(ap_pv[:, None], flip_rows32, jnp.uint32(0))),
        "count": xfr["count"] + jnp.where(ok, n_created, 0),
    }

    new_xfer_ht = ht_write(
        state["xfer_ht"], xfer_pos, ev["id_hi"], ev["id_lo"],
        jnp.where(created, new_rows, jnp.int32(ORPHAN_VAL)),
        ins_mask & ok)

    # ------- account_events history ring (reference: account_event(),
    # src/state_machine.zig:4384-4470 — POST-application balance snapshots
    # of both touched accounts per created transfer). Statuses are
    # order-independent under eligibility, but snapshots are prefix sums:
    # event i's snapshot includes every earlier created event's delta on
    # that account. Computed exactly with a sort + segmented limb cumsum.
    evr = state["events"]
    E_dump = ev_cap(evr)
    z64 = jnp.uint64(0)
    al = (al0, al1, al2, al3)
    nl = (nl0, nl1, nl2, nl3)
    fields = _FIELDS
    if limit_rounds > 1:
        # Reuse the fixpoint's sorted entry space WHOLESALE — perm,
        # base limbs, segment structure (it sorted the same (row,
        # event-order) entries; its valid-mask is a superset of the
        # application's ap-mask, and a valid-but-unapplied entry only
        # contributes a ZERO delta, so prefixes and final balances are
        # bit-identical while fully-failed accounts rewrite their own
        # base limbs unchanged). The application is then ONE more round
        # body at the FINAL applied set plus the state writes — the
        # former second sort + base/segment re-derivation lowered the
        # same subcomputation twice (op budget).
        perm = fperm
        rows_sorted = frows_sorted
        is_start = fstart
        seg_id = fseg_id
        seg_start = fseg_start
        inv = finv
        base = fbase
        mask8f = ((ap & ~pv & ~pending).astype(jnp.uint8)
                  | ((ap & ~pv & pending).astype(jnp.uint8) << 1)
                  | ((ap & pv).astype(jnp.uint8) << 2)
                  | ((ap & pv & is_post).astype(jnp.uint8) << 3)
                  | ((ap & ~pv & close_dr_f).astype(jnp.uint8) << 4)
                  | ((ap & ~pv & close_cr_f).astype(jnp.uint8) << 5)
                  | ((ap & pv & is_void & p_cl_dr).astype(jnp.uint8) << 6)
                  | ((ap & pv & is_void & p_cl_cr).astype(jnp.uint8) << 7))
        m_s2 = jnp.concatenate([mask8f, mask8f])[perm]
        reg_s2 = (m_s2 & 1) != 0
        pend_s2 = (m_s2 & 2) != 0
        pv_s2 = (m_s2 & 4) != 0
        post_s2 = (m_s2 & 8) != 0
        if balancing_mode:
            # Amounts include the converged clamps: one stacked gather
            # of the final limbs (the hoisted al2_s is nominal).
            al_ev2 = jnp.stack(al)
            al_use2 = jnp.take(jnp.concatenate([al_ev2, al_ev2], axis=1),
                               perm, axis=1)
        else:
            al_use2 = al2_s
        held_f = [jnp.where(pend_s2, al_use2[j], z64)
                  + jnp.where(pv_s2, nl2_s[j], z64) for j in range(4)]
        posted_f = [jnp.where(reg_s2 | post_s2, al_use2[j], z64)
                    for j in range(4)]
        # Lane semantics MUST match _delta_lanes2 — see its docstring.
        lanes_sorted = jnp.stack([
            jnp.stack([jnp.where(cr_side_s, z64, held_f[j])
                       for j in range(4)]),       # dp
            jnp.stack([jnp.where(cr_side_s, z64, posted_f[j])
                       for j in range(4)]),       # dpos
            jnp.stack([jnp.where(cr_side_s, held_f[j], z64)
                       for j in range(4)]),       # cp
            jnp.stack([jnp.where(cr_side_s, posted_f[j], z64)
                       for j in range(4)]),       # cpos
        ])
    else:
        side_rows = [
            jnp.where(ap, jnp.where(pv, p["dr_row"], dr_rowc), A_dump),
            jnp.where(ap, jnp.where(pv, p["cr_row"], cr_rowc), A_dump),
        ]
        rows2 = jnp.concatenate(side_rows)  # 2N: dr sides then cr sides
        order2 = jnp.concatenate([idxs, idxs])
        perm = _packed_perm(rows2, order2, acc["u32"].shape[0])
        rows_sorted = rows2[perm]
        is_start = jnp.concatenate([
            jnp.ones(1, dtype=jnp.bool_),
            rows_sorted[1:] != rows_sorted[:-1]])
        seg_id = _cumsum(is_start.astype(jnp.int32)) - 1
        # Forward-fill of start positions (one running max), not a
        # segment reduce + gather (op budget).
        seg_start = _cummax(jnp.where(
            is_start, jnp.arange(2 * N, dtype=jnp.int32), jnp.int32(-1)))
        inv = jnp.zeros(2 * N, dtype=jnp.int32).at[perm].set(
            jnp.arange(2 * N, dtype=jnp.int32))
        # Packed-balance base: one row gather, reshaped to
        # [field][limb][entry] (column = field * 4 + limb, matching the
        # `fields` order).
        base = widen(acc["bal"][rows_sorted]).T.reshape(4, 4, 2 * N)
        # Stacked (4 fields, 4 limbs, 2N): ONE sort-gather, ONE cumsum,
        # ONE segment-offset gather, ONE base add — not 16 scalar-lane
        # pipelines. The permute runs on u32 lanes (all delta limbs are
        # u32-normalized) and widens AFTER the gather: half the operand
        # bytes of a u64 permute.
        lanes2 = _delta_lanes2(ap_reg, ap_pend, ap_pv, ap_post, al, nl)
        lanes_sorted = lanes2.astype(jnp.uint32)[:, :, perm].astype(
            jnp.uint64)
    cs = _cumsum(lanes_sorted, axis=2)
    offsets = jnp.where(
        seg_start > 0,
        jnp.take(cs, jnp.maximum(seg_start - 1, 0), axis=2), z64)
    limbs = base + cs - offsets                      # (4, 4, 2N)
    l0, l1, l2, l3 = _normalize_limbs(limbs)
    hi_sorted = l2 | (l3 << jnp.uint64(32))          # (4, 2N)
    lo_sorted = l0 | (l1 << jnp.uint64(32))

    # ---- balance application: the last entry per account row carries the
    # exact post-batch balance — scatter it back. Non-final and masked
    # entries write a uniform 0 to the dump row (duplicate-index scatter-
    # set stays deterministic only if every duplicate writes one value).
    is_final = jnp.concatenate([
        is_start[1:], jnp.ones(1, dtype=jnp.bool_)])  # next start ends me
    real = is_final & (rows_sorted != A_dump)
    tgt = jnp.where(real, rows_sorted, A_dump)
    vals = jnp.stack([l0, l1, l2, l3], axis=1).reshape(16, 2 * N).T
    new_acc = dict(acc)
    new_acc["bal"] = acc["bal"].at[tgt].set(
        narrow(jnp.where(real[:, None], vals, jnp.uint64(0))))
    # Snapshot rows back to entry order: ONE stacked take for the hi and
    # lo halves together (op budget).
    hilo_all = jnp.take(jnp.concatenate([hi_sorted, lo_sorted]),
                        inv, axis=1)                 # (8, 2N)
    snap = {}
    for fi, field in enumerate(fields):
        snap[f"dr_{field}"] = (hilo_all[fi, :N], hilo_all[4 + fi, :N])
        snap[f"cr_{field}"] = (hilo_all[fi, N:], hilo_all[4 + fi, N:])

    eff_dr_flags = jnp.where(pv, p_dr["flags"], dr["flags"])
    eff_cr_flags = jnp.where(pv, p_cr["flags"], cr["flags"])
    if closing_native:
        # ---- closed-flag application + POST-event ring flags. The
        # reference's account_event stores dr_account_NEW (:3948-3963:
        # flags after the event), and the mirror's account write-back
        # (lazy_mirror.apply_account_finals) takes the LAST ring row's
        # flags per account — so the ring must carry the evolved closed
        # bit, and the account store the post-batch value. Same
        # last-op-wins scan as the fixpoint, over the application's own
        # sorted space (whose ops come from the FINAL applied set).
        cl_u = jnp.uint32(_A_CLOSED)
        # Closed-op lanes come out of the SAME packed mask gather the
        # delta lanes ride (bits 4..7 of m_s2) — no extra gathers.
        set2 = jnp.where(cr_side_s, (m_s2 & 32) != 0, (m_s2 & 16) != 0)
        clr2 = jnp.where(cr_side_s, (m_s2 & 128) != 0, (m_s2 & 64) != 0)
        idx2a = jnp.arange(2 * N, dtype=jnp.int32)
        op_pos2 = jnp.where(set2 | clr2, idx2a, jnp.int32(-1))
        incl2 = _cummax(op_pos2)
        # Inclusive (post-event) closed per entry; seg_start here is the
        # (shared) sorted entry space's per-entry segment-start position.
        has2 = incl2 >= seg_start
        closed_incl_s = jnp.where(has2, set2[jnp.maximum(incl2, 0)],
                                  init_closed_s)
        # Post-batch flag word per account: last entry of each real
        # segment; only segments that carried an op write (untouched
        # accounts keep their word byte-identical). The write-back
        # rewrites the meta rows gathered once in the fixpoint setup
        # (meta_s) with the flags column replaced.
        seg_has_op = jax.ops.segment_max(
            op_pos2, seg_id, num_segments=2 * N)[seg_id] >= 0
        wrf = real & seg_has_op
        new_word = jnp.where(closed_incl_s, base_flags_s | cl_u,
                             base_flags_s & ~cl_u)
        new_acc["u32"] = acc["u32"].at[
            jnp.where(wrf, rows_sorted, A_dump)].set(jnp.where(
                wrf[:, None],
                with_col32(meta_s, AC_FLAGS_COL32, new_word),
                jnp.uint32(0)))
        closed_incl = closed_incl_s[inv]
        eff_dr_flags = jnp.where(closed_incl[:N], eff_dr_flags | cl_u,
                                 eff_dr_flags & ~cl_u)
        eff_cr_flags = jnp.where(closed_incl[N:], eff_cr_flags | cl_u,
                                 eff_cr_flags & ~cl_u)

    erow = jnp.where(ap, ring_base + row_off, E_dump)
    stores_ev = dict(
        ts=ts_actual,
        amt_hi=amt_res_hi, amt_lo=amt_res_lo,
        areq_hi=ev["amt_hi"], areq_lo=ev["amt_lo"],
        tflags=flags,
        pstat=jnp.where(pending & ~pv, _PS_PENDING,
                        jnp.where(is_post, _PS_POSTED,
                                  jnp.where(is_void, _PS_VOIDED,
                                            jnp.int32(0)))),
        p_row=jnp.where(ap_pv, flip_row, jnp.int32(-1)),
        dr_row=jnp.where(pv, p["dr_row"], dr_rowc),
        cr_row=jnp.where(pv, p["cr_row"], cr_rowc),
        # Effective-side account flags: already gathered in the per-event
        # stage (dr/cr/p_dr/p_cr) — select, don't re-gather. Closing-
        # native tiers patch the closed bit to its POST-event value.
        dr_flags=eff_dr_flags,
        cr_flags=eff_cr_flags,
    )
    for sside in ("dr", "cr"):
        for field in ("dp", "dpos", "cp", "cpos"):
            hi_arr, lo_arr = snap[f"{sside}_{field}"]
            stores_ev[f"{sside}_{field}_hi"] = hi_arr
            stores_ev[f"{sside}_{field}_lo"] = lo_arr
    # Packed ring append: ONE row scatter of u32 rows (44 logical
    # columns -> 1, ev_layout); masked lanes write uniform zero rows to
    # the dump slot (determinism).
    new_evr = {
        "u32": evr["u32"].at[erow].set(jnp.where(
            ap[:, None], ev_rows32(stores_ev), jnp.uint32(0))),
        "count": jnp.where(ok, ring_base + n_created, evr["count"]),
    }

    # Scalars: both running maxima in ONE stacked reduce.
    last2 = jnp.max(jnp.where(created[None, :],
                              jnp.stack([ts_event, ts_actual]),
                              jnp.uint64(0)), axis=1)
    # key_max tracks the max APPLIED timestamp (imported rows carry user
    # timestamps; == last_ts otherwise) — the regress reference for
    # future imported batches. commit_ts stays prepare-derived.
    last_ts = last2[0]
    last_actual = last2[1]
    key_max = jnp.where(created.any() & ok,
                        jnp.maximum(state["xfer_key_max"], last_actual),
                        state["xfer_key_max"])
    commit_ts = jnp.where(created.any() & ok, last_ts, state["commit_ts"])

    # Pulse scheduling: EXACT sequential evolution in closed form
    # (oracle/state_machine.py:594 min-update, :744 reset). Per applied
    # event in order: a pending-with-timeout does pulse = min(pulse,
    # expires); a post/void of a timed pending resets pulse to
    # TIMESTAMP_MIN iff pulse == expires(p) at that moment. Key facts:
    # once ANY reset fires, pulse is pinned at TIMESTAMP_MIN (mins can't
    # go lower; later resets need pulse == expires > MIN); and absent
    # earlier fires, the pulse seen by event j is min(P0, prefix-min of
    # earlier mins) — one cummin. So: fired_j = applied_pv_j with
    # p.timeout whose expires equals that running value; final is
    # TIMESTAMP_MIN if any fired, else min(P0, all mins). Uses
    # applied_ever, not created: chain rollback does not restore
    # pulse_next (state-machine state, not groove state — the reference
    # keeps the early wake-up, which is safe), for the resets too.
    expires_new = jnp.where(
        applied_ever & pending & (ev["timeout"] != 0),
        ts_event + timeout_ns, jnp.uint64(0xFFFFFFFFFFFFFFFF))
    p0 = state["pulse_next"]
    cm = _cummin(expires_new)
    before_min = jnp.concatenate([
        jnp.full((1,), 0xFFFFFFFFFFFFFFFF, dtype=jnp.uint64), cm[:-1]])
    run_pulse = jnp.minimum(p0, before_min)
    applied_pv = applied_ever & pv
    fired = applied_pv & (p["timeout"] != 0) & (p["expires"] == run_pulse)
    pulse = jnp.where(jnp.any(fired), jnp.uint64(1),
                      jnp.minimum(p0, jnp.min(expires_new)))
    pulse = jnp.where(ok, pulse, state["pulse_next"])

    new_state = dict(
        accounts=new_acc,
        transfers=new_xfr,
        events=new_evr,
        acct_ht=state["acct_ht"],
        xfer_ht=new_xfer_ht,
        acct_key_max=state["acct_key_max"],
        xfer_key_max=key_max,
        pulse_next=pulse,
        commit_ts=commit_ts,
    )
    # Per-cause fallback observability (scalar bools, nonzero only when
    # the batch actually fell back): the host drivers accumulate these
    # into counters so "zero host fallbacks on a mixed window" is a
    # MEASURED invariant (`start`'s shutdown record), not an
    # assumption. `limit`/`closing`/`e5`/`e2` may be escalations the
    # caller resolves on a deeper tier — the drivers count those
    # separately from true host fallbacks.
    fb_causes = {
        "e1_hard_flags": jnp.any(e1_vec),
        "e2_collision": e2,
        "e3_limit": e3,
        "e4_overflow": (jnp.any(jnp.stack(pair_ovfs))
                        | (jnp.bool_(False) if balancing_mode
                           else (ovf | (s4 > 0)))),
        "e5_void_closing": jnp.any(e5_vec),
        "closing": jnp.any(e_close_vec),
        "capacity": e7 | e8 | ~ins_ok,
        "forced": (jnp.bool_(False) if force_fallback is None
                   else force_fallback),
    }
    out = dict(
        r_status=jnp.where(ok, status, jnp.zeros_like(status)),
        r_ts=jnp.where(ok, jnp.where(valid, ts_actual, jnp.uint64(0)),
                       jnp.zeros_like(ts_actual)),
        fallback=fallback,
        limit_only=limit_only,
        fb_causes={k: v & fallback for k, v in fb_causes.items()},
        # Fixpoint variants: the ONLY obstacle was a limit-decision
        # cascade deeper than this variant's round budget — a deeper
        # variant resolves it on device (the caller escalates before
        # touching the host path).
        fix_unconverged=(e3 & ~others & jnp.bool_(limit_rounds > 1)),
        fix_rounds=fix_rounds,
        # Would the headroom proof have failed this batch? The adaptive
        # router drops back to the cheaper proof-gated kernel only once
        # the proof itself would pass again.
        limit_hit=proof_breach,
        created_count=jnp.where(ok, n_created, 0),
    )
    return new_state, out


create_transfers_fast_jit = jax.jit(create_transfers_fast, donate_argnums=0)


def _tier_jit(name: str, fn=create_transfers_fast, **static):
    """jit `fn` with `static` bound, under a name of its own: XLA names
    the program `jit_<name>`, and a bare functools.partial has none, so
    every tier would show in a device trace as `jit__unknown`. The
    per-batch tiers are `create_transfers_<tier>`; the window programs
    keep the leading underscore of their named siblings."""
    tier = functools.partial(fn, **static)
    tier.__name__ = name
    return jax.jit(tier, donate_argnums=0)


# Imported tier (plain eligibility + native imported rules + the
# left-to-right maxima chain for in-batch regress). Selected by the
# ledger's host pre-route when a batch/window carries imported flags.
create_transfers_imported_jit = _tier_jit(
    "create_transfers_imported", imported_mode=True)


def _create_transfers_super_imported(state, ev, seg, force_fallback=None):
    return create_transfers_fast(
        state, ev, jnp.uint64(0), jnp.int32(0),
        force_fallback=force_fallback, seg=seg, imported_mode=True)


create_transfers_super_imported_jit = jax.jit(
    _create_transfers_super_imported, donate_argnums=0)


def _create_transfers_super(state, ev, seg, force_fallback=None):
    return create_transfers_fast(
        state, ev, jnp.uint64(0), jnp.int32(0),
        force_fallback=force_fallback, seg=seg)


# Superbatch entry: K stacked prepares, one dispatch — the fixed
# dispatch overhead is paid once per window instead of once per prepare.
create_transfers_super_jit = jax.jit(
    _create_transfers_super, donate_argnums=0)


def _create_transfers_super_deep(state, ev, seg, force_fallback=None):
    return create_transfers_fast(
        state, ev, jnp.uint64(0), jnp.int32(0),
        force_fallback=force_fallback, seg=seg,
        limit_rounds=LIMIT_FIXPOINT_ROUNDS_WINDOW_DEEP)


def _create_transfers_super_ring(state, ev, seg, force_fallback=None):
    return create_transfers_fast(
        state, ev, jnp.uint64(0), jnp.int32(0),
        force_fallback=force_fallback, seg=seg, ring_reset=True)


def _create_transfers_super_deep_ring(state, ev, seg, force_fallback=None):
    return create_transfers_fast(
        state, ev, jnp.uint64(0), jnp.int32(0),
        force_fallback=force_fallback, seg=seg,
        limit_rounds=LIMIT_FIXPOINT_ROUNDS_WINDOW_DEEP, ring_reset=True)


# Pipelined-serving variants: the event ring resets per window (see
# ring_reset in create_transfers_fast).
create_transfers_super_ring_jit = jax.jit(
    _create_transfers_super_ring, donate_argnums=0)
create_transfers_super_deep_ring_jit = jax.jit(
    _create_transfers_super_deep_ring, donate_argnums=0)


# Deep-fixpoint superbatch: commit windows whose prepares carry
# order-dependent balance limits AND/OR in-window pending references
# (pend in prepare i, post/void in prepare j>i — the config4 shape).
# Resolves both natively: the K-round fixpoint now also propagates
# definition deaths to their dependent uses.
#
# Window round budget: 24 (measured: the config4 window workload at
# bench scale — 8 x 8190-event prepares, 64 limited accounts —
# converges at 24 rounds with the same-round death fold, 6/6 windows).
# An unconverged window falls back to the per-batch ladder whose own
# deep tier keeps the full 32 rounds (single batches cascade shallower
# than windows), so the cut is pure throughput: 25% less round mass on
# the config4-dominant kernel with an on-device escape hatch.
LIMIT_FIXPOINT_ROUNDS_WINDOW_DEEP = 24
create_transfers_super_deep_jit = jax.jit(
    _create_transfers_super_deep, donate_argnums=0)


def _create_transfers_super_balancing(state, ev, seg,
                                      force_fallback=None):
    return create_transfers_fast(
        state, ev, jnp.uint64(0), jnp.int32(0),
        force_fallback=force_fallback, seg=seg,
        limit_rounds=LIMIT_FIXPOINT_ROUNDS_WINDOW_DEEP,
        balancing_mode=True)


def _create_transfers_super_balancing_ring(state, ev, seg,
                                           force_fallback=None):
    return create_transfers_fast(
        state, ev, jnp.uint64(0), jnp.int32(0),
        force_fallback=force_fallback, seg=seg,
        limit_rounds=LIMIT_FIXPOINT_ROUNDS_WINDOW_DEEP,
        balancing_mode=True, ring_reset=True)


# Balancing superbatch tiers: commit windows whose prepares carry
# balancing_debit/credit clamps run natively at the deep-window round
# budget (clamp cascades stack across prepares exactly like limit
# waves; an unconverged window falls back to the per-batch balancing
# ladder). Selected by the window routers' host pre-check.
create_transfers_super_balancing_jit = jax.jit(
    _create_transfers_super_balancing, donate_argnums=0)
create_transfers_super_balancing_ring_jit = jax.jit(
    _create_transfers_super_balancing_ring, donate_argnums=0)

# Round budgets of the fixpoint tiers that unroll their rounds: the
# imported and balancing tiers and their deep escalations (each wave
# needs a limit failure whose rollback flips a LATER event's limit
# outcome; full protocol-max batches over few limited accounts cascade
# 9-32 waves).
LIMIT_FIXPOINT_ROUNDS = 8
LIMIT_FIXPOINT_ROUNDS_DEEP = 32

# The order-dependent-limits tier: resolves headroom-proof breaches
# natively, iterating its rounds in one while loop until they stop
# changing (limit_rounds=LIMIT_FIXPOINT_CONVERGE). Wire-max requests of
# upstream's benchmark load over accounts that cannot overdraw cascade
# far past any budget worth unrolling (27-128 rounds a request on a TPU
# v5e, 66 on the median, over 50 such requests); the
# loop runs what the batch needs, in one dispatch. The value only has to
# differ from every unrolled budget: the loop stops at N + 1 rounds.
LIMIT_FIXPOINT_CONVERGE = 1 << 31
create_transfers_fixpoint_jit = _tier_jit(
    "create_transfers_fixpoint", limit_rounds=LIMIT_FIXPOINT_CONVERGE)

# Imported fixpoint tier: the plain imported tier's escalation target
# (closing flags, voids of closing pendings, potential limit breaches).
# Runs the imported rules AND the closing-native/limit fixpoint in one
# kernel — the in-batch regress maxima chain joins the rounds (the
# applied set it runs over evolves with the closed/limit decisions).
# Uniform closing eligibility across tiers is what lets the SPMD driver
# run mixed imported+closing windows with zero host fallbacks.
create_transfers_imported_fixpoint_jit = _tier_jit(
    "create_transfers_imported_fixpoint", imported_mode=True,
    limit_rounds=LIMIT_FIXPOINT_ROUNDS)
create_transfers_imported_fixpoint_deep_jit = _tier_jit(
    "create_transfers_imported_fixpoint_deep", imported_mode=True,
    limit_rounds=LIMIT_FIXPOINT_ROUNDS_DEEP)

# Balancing tier (reference :3840-3853): balancing_debit/credit clamps
# ride the limit fixpoint — per-round clamped amounts from the exact
# prefix balances (see the balancing_mode docstring). Selected by the
# ledger's host pre-route when a batch carries balancing flags; its
# fallbacks (closing flags, deep cascades, balancing in-window defs) go
# to the exact host path via the same shallow->deep ladder as limits.
create_transfers_balancing_jit = _tier_jit(
    "create_transfers_balancing", limit_rounds=LIMIT_FIXPOINT_ROUNDS,
    balancing_mode=True)
create_transfers_balancing_deep_jit = _tier_jit(
    "create_transfers_balancing_deep",
    limit_rounds=LIMIT_FIXPOINT_ROUNDS_DEEP, balancing_mode=True)

# Tiny on-device accumulator for back-to-back batch drivers: summing
# created_counts on device keeps the dispatch loop free of per-batch host
# syncs (one fetch at the end). Module-level so its compile is absorbed by
# the driver's warmup pass, not the timed region.
_accum_jit = jax.jit(lambda acc, c: acc + c, donate_argnums=0)
# Chain-window variant: the per-iteration counts (W,) sum inside the
# same fused dispatch.
_accum_sum_jit = jax.jit(lambda acc, c: acc + c.sum(), donate_argnums=0)


# ===================================== whole-program window chain (W>=2)

def _create_transfers_chain(state, ev_stack, seg_stack,
                            force_fallback=None, ring_reset=False):
    """W batches (serving: one commit window's prepares; probes: whole
    windows) chained entirely ON DEVICE in one compiled program: a
    lax.scan whose carry is the donated ledger state plus the rolling
    fallback scalar — iteration k's fallback poisons every later
    iteration exactly like the host pipeline's chained force_fallback
    (a poisoned iteration leaves state untouched), so commit order
    survives with ZERO host round-trips inside the chain. Inputs arrive
    stacked on a leading W axis; results (r_status/r_ts/created_count/
    fallback/fb_causes per iteration) come back stacked and are fetched
    once after the whole chain. The scan body is traced ONCE, so the
    program's op count is ~constant in W — the property that makes this
    the default serving dispatch route (DeviceLedger.submit_window /
    create_transfers_window; op mass gated via jaxhound's
    scan_body_census + perf/opbudget_r07.json).

    ring_reset (static; the pipelined-serving variant): the event ring
    is consumed from offset 0 per chain DISPATCH — iterations then
    accumulate within the window, and the window's delta gather
    (enqueued before the next window's kernel on the device FIFO
    stream) reads the rows before a later window can overwrite them. A
    window pre-poisoned by an earlier in-flight fallback leaves the
    ring count untouched (the state-untouched contract the redo path
    relies on).

    This is the shape PERF.md's whole-program model prices at ~4-16M tps
    on local silicon (the reference's analog: the prefetch/execute split
    lets commits run back-to-back with no IO between them,
    docs/ARCHITECTURE.md:424-434). Its value on a local chip is not
    measured yet."""
    poisoned0 = (jnp.bool_(False) if force_fallback is None
                 else force_fallback)
    if ring_reset:
        evr = state["events"]
        state = dict(state, events=dict(
            evr, count=jnp.where(poisoned0, evr["count"], jnp.int32(0))))

    def step(carry, x):
        st, poisoned = carry
        ev, seg = x
        new_st, out = create_transfers_fast(
            st, ev, jnp.uint64(0), jnp.int32(0),
            force_fallback=poisoned, seg=seg)
        keep = {k: out[k] for k in
                ("r_status", "r_ts", "fallback", "created_count")}
        # Per-iteration cause flags ride out stacked (W,) so the route
        # counters can name WHY a window left the chain route.
        keep["fb_causes"] = out["fb_causes"]
        return (new_st, out["fallback"]), keep

    (st, _), outs = jax.lax.scan(step, (state, poisoned0),
                                 (ev_stack, seg_stack))
    return st, outs


create_transfers_chain_jit = jax.jit(
    _create_transfers_chain, donate_argnums=0)
# Pipelined-serving variant: the event ring resets once per chain
# dispatch (see ring_reset above).
create_transfers_chain_ring_jit = _tier_jit(
    "_create_transfers_chain_ring", _create_transfers_chain,
    ring_reset=True)


def _create_transfers_chain_unrolled(state, ev_stack, seg_stack,
                                     force_fallback=None):
    """The same W-window chain with the loop UNROLLED at trace time
    (program op count ~ W x kernel): the fallback variant should scan
    bodies turn out to execute worse than straight-line programs."""
    W = ev_stack["id_lo"].shape[0]
    poisoned = (jnp.bool_(False) if force_fallback is None
                else force_fallback)
    st = state
    outs = []
    for k in range(W):
        ev = {key: v[k] for key, v in ev_stack.items()}
        seg = {key: v[k] for key, v in seg_stack.items()}
        st, out = create_transfers_fast(
            st, ev, jnp.uint64(0), jnp.int32(0),
            force_fallback=poisoned, seg=seg)
        poisoned = out["fallback"]
        kept = {key: out[key] for key in
                ("r_status", "r_ts", "fallback", "created_count")}
        kept["fb_causes"] = out["fb_causes"]
        outs.append(kept)
    stacked = {key: (jnp.stack([o[key] for o in outs])
                     if key != "fb_causes" else
                     {c: jnp.stack([o[key][c] for o in outs])
                      for c in outs[0][key]})
               for key in outs[0]}
    return st, stacked


create_transfers_chain_unrolled_jit = jax.jit(
    _create_transfers_chain_unrolled, donate_argnums=0)


# ================================================== create_accounts (fast)

def create_accounts_fast(state, ev, timestamp, n, imported_mode=False):
    """Vectorized create_accounts (reference :3613-3689). Eligibility: no
    duplicate ids in batch, capacity suffices; imported flags require the
    imported_mode tier (native rules, reference :3648-3667) — chains +
    imported still fall back (rollback rewinds the maxima chain)."""
    from .hash_table import ht_lookup, ht_plan, ht_write

    acc = state["accounts"]
    A_dump = acc["u32"].shape[0] - 1
    N = ev["id_lo"].shape[0]
    idxs = jnp.arange(N, dtype=jnp.int32)
    valid = ev["valid"]
    nn = n.astype(jnp.uint64)
    ts_event = timestamp - nn + idxs.astype(jnp.uint64) + jnp.uint64(1)

    flags = ev["flags"]
    linked = _flag(flags, _A_LINKED) & valid
    imported = _flag(flags, _A_IMPORTED)

    e_found, e_row = ht_lookup(state["acct_ht"], ev["id_hi"], ev["id_lo"])
    e_rowc = jnp.where(e_found, e_row, A_dump)

    if imported_mode:
        e1 = jnp.any(valid & imported) & jnp.any(linked)
    else:
        e1 = jnp.any(valid & imported)
    tag = valid & ~((ev["id_hi"] == 0) & (ev["id_lo"] == 0))
    e2 = _dup_keys(ev["id_hi"], ev["id_lo"], tag)
    fallback_pre = e1 | e2

    # ONE meta gather, widened to its u64 words after the gather (the
    # 32-bit fields unpack from the tail words, ev_layout.AC_P32).
    g64 = widen(acc["u32"][e_rowc])
    AU = AC_U64_IDX
    g_ul = g64[:, _AC_UL_COL]
    g_cf = g64[:, _AC_CF_COL]
    g_flags = (g_cf >> jnp.uint64(32)).astype(jnp.uint32)
    exists_checks = [
        ((flags & 0xFFFF) != (g_flags & 0xFFFF),
         _AS["exists_with_different_flags"]),
        (~u128.eq(ev["ud128_hi"], ev["ud128_lo"],
                  g64[:, AU["ud128_hi"]], g64[:, AU["ud128_lo"]]),
         _AS["exists_with_different_user_data_128"]),
        (ev["ud64"] != g64[:, AU["ud64"]],
         _AS["exists_with_different_user_data_64"]),
        (ev["ud32"] != (g_ul & _M32).astype(jnp.uint32),
         _AS["exists_with_different_user_data_32"]),
        (ev["ledger"] != (g_ul >> jnp.uint64(32)).astype(jnp.uint32),
         _AS["exists_with_different_ledger"]),
        (ev["code"] != (g_cf & _M32).astype(jnp.uint32),
         _AS["exists_with_different_code"]),
    ]
    exists_status = _first_failure(exists_checks, created=_AS["exists"])
    exists_ts = g64[:, AU["ts"]]

    checks = [
        (ev["reserved"] != 0, _AS["reserved_field"]),
        ((flags & _AF_PADDING) != 0, _AS["reserved_flag"]),
        (u128.is_zero(ev["id_hi"], ev["id_lo"]), _AS["id_must_not_be_zero"]),
        (u128.is_max(ev["id_hi"], ev["id_lo"]), _AS["id_must_not_be_int_max"]),
        (e_found, jnp.uint32(0)),  # replaced by exists_status below
        (_flag(flags, _A_DR_LIMIT) & _flag(flags, _A_CR_LIMIT),
         _AS["flags_are_mutually_exclusive"]),
        (~u128.is_zero(ev["dp_hi"], ev["dp_lo"]), _AS["debits_pending_must_be_zero"]),
        (~u128.is_zero(ev["dpos_hi"], ev["dpos_lo"]), _AS["debits_posted_must_be_zero"]),
        (~u128.is_zero(ev["cp_hi"], ev["cp_lo"]), _AS["credits_pending_must_be_zero"]),
        (~u128.is_zero(ev["cpos_hi"], ev["cpos_lo"]), _AS["credits_posted_must_be_zero"]),
        (ev["ledger"] == 0, _AS["ledger_must_not_be_zero"]),
        (ev["code"] == 0, _AS["code_must_not_be_zero"]),
    ]
    if imported_mode:
        # Regress vs state (reference :3648-3667): the accounts groove's
        # key_max plus collision with any existing TRANSFER timestamp
        # (sorted-column membership; the in-batch component is the
        # maxima chain below). The transfers ts column is read
        # PRE-SORTED — rows are stored in applied-timestamp order
        # (round-7 op cut; see imported_batch_ctx) — so the former
        # full-table jnp.sort (t_cap rows, the widest sort in any
        # lowering) is gone.
        # method='sort', not the while-lowering default (see
        # imported_batch_ctx).
        xfr = state["transfers"]
        xfer_ts_sorted = jnp.where(
            jnp.arange(xfr["u32"].shape[0], dtype=jnp.int32)
            < xfr["count"],
            xf_col(xfr, "ts"), jnp.uint64(0xFFFFFFFFFFFFFFFF))
        pos = jnp.minimum(
            jnp.searchsorted(xfer_ts_sorted, ev["ts"], method="sort"),
            xfer_ts_sorted.shape[0] - 1)
        coll = (xfer_ts_sorted[pos] == ev["ts"]) & (ev["ts"] != 0)
        regress = imported & (
            (ev["ts"] <= state["acct_key_max"]) | coll)
        checks.append(
            (regress, _AS["imported_event_timestamp_must_not_regress"]))
    inner = _first_failure(checks)
    inner = jnp.where(inner == 0, exists_status, inner)
    ts_inner = jnp.where(inner == _AS["exists"], exists_ts, ts_event)
    if imported_mode:
        ts_inner = jnp.where((inner == _CREATED) & imported,
                             ev["ts"], ts_inner)

    status = inner
    status = jnp.where(~imported & (ev["ts"] != 0), _AS["timestamp_must_be_zero"], status)
    if imported_mode:
        # Wrapper rules (reference execute_create :3052-3063): batch
        # homogeneity vs the FIRST event's flag, timestamp range,
        # must-not-advance vs the batch commit timestamp.
        batch_imported = imported[0]
        ts_valid = (ev["ts"] >= 1) & (ev["ts"] <= _U63)
        status = jnp.where(imported & ts_valid & (ev["ts"] >= timestamp),
                           _AS["imported_event_timestamp_must_not_advance"],
                           status)
        status = jnp.where(imported & ~ts_valid,
                           _AS["imported_event_timestamp_out_of_range"],
                           status)
        status = jnp.where(
            imported != batch_imported,
            jnp.where(imported, _AS["imported_event_not_expected"],
                      _AS["imported_event_expected"]), status)
    else:
        status = jnp.where(imported, _AS["imported_event_not_expected"],
                           status)
    ts_actual = jnp.where(status == inner, ts_inner, ts_event)

    if imported_mode:
        # In-batch regress: left-to-right maxima chain over the
        # otherwise-valid sequence (see create_transfers_fast's
        # imported_mode docstring; for accounts NO check follows the
        # regress position, so only base-ok events need the override).
        actual_vec = jnp.where(imported, ev["ts"], ts_event)
        base_ok = valid & (status == _CREATED)
        cand = jnp.where(base_ok, actual_vec, jnp.uint64(0))
        run_incl = _cummax(cand)
        run_excl = jnp.maximum(
            state["acct_key_max"],
            jnp.concatenate([state["acct_key_max"][None],
                             run_incl[:-1]]))
        override = imported & base_ok & (ev["ts"] <= run_excl)
        status = jnp.where(
            override, _AS["imported_event_timestamp_must_not_regress"],
            status)
        ts_actual = jnp.where(override, ts_event, ts_actual)

    l_prev = jnp.concatenate([jnp.zeros(1, dtype=jnp.bool_), linked[:-1]])
    in_chain = linked | l_prev
    start = linked & ~l_prev
    chain_id = _cumsum(start.astype(jnp.int32))
    chain_open_evt = linked & (idxs == (n - 1))
    status = jnp.where(chain_open_evt, _AS["linked_event_chain_open"], status)
    fail = in_chain & valid & (status != _CREATED)
    fail_pos = jnp.where(fail, idxs, _INF)
    seg_first = jax.ops.segment_min(fail_pos, chain_id, num_segments=N + 1)
    my_first = seg_first[chain_id]
    # The open-chain terminator keeps chain_open even when an earlier member
    # failed (chain_open is applied after chain_broken sequentially).
    not_the_failure = (in_chain & (my_first != _INF) & (idxs != my_first)
                       & ~chain_open_evt)
    status = jnp.where(not_the_failure, _AS["linked_event_failed"], status)
    ts_actual = jnp.where(not_the_failure, ts_event, ts_actual)

    status = jnp.where(valid, status, jnp.uint32(0))
    created = valid & (status == _CREATED)

    row_off = (_cumsum(created.astype(jnp.int32))
               - created.astype(jnp.int32))
    n_created = jnp.sum(created, dtype=jnp.int32)
    e7 = (acc["count"] + n_created) > jnp.int32(A_dump)
    new_rows = acc["count"] + row_off
    ht_pos, ins_ok = ht_plan(
        state["acct_ht"], ev["id_hi"], ev["id_lo"], created)
    fallback = fallback_pre | e7 | ~ins_ok
    ok = ~fallback
    ap = created & ok
    arow = jnp.where(ap, new_rows, A_dump)

    z64 = jnp.uint64(0)
    # Packed row insert: ONE meta scatter of u32 rows (ev_layout) + the
    # balance-zero scatter;
    # masked lanes write uniform zero rows to the dump slot (scatter
    # determinism). Stored timestamp: the ACTUAL one (imported created
    # accounts keep their user timestamp; == ts_event otherwise).
    ts_store = ts_actual if imported_mode else ts_event
    named_vals = {"id_hi": ev["id_hi"], "id_lo": ev["id_lo"],
                  "ud128_hi": ev["ud128_hi"], "ud128_lo": ev["ud128_lo"],
                  "ud64": ev["ud64"], "ts": ts_store,
                  "ud32": ev["ud32"], "ledger": ev["ledger"],
                  "code": ev["code"], "flags": flags}
    new_acc = dict(acc)
    new_acc["u32"] = acc["u32"].at[arow].set(
        jnp.where(ap[:, None], ac_rows32(named_vals), jnp.uint32(0)))
    new_acc["bal"] = acc["bal"].at[arow].set(
        jnp.zeros((N, acc["bal"].shape[1]), dtype=jnp.uint32))
    new_acc["count"] = acc["count"] + jnp.where(ok, n_created, 0)

    new_ht = ht_write(
        state["acct_ht"], ht_pos, ev["id_hi"], ev["id_lo"], new_rows, ap)

    last_ts = jnp.max(jnp.where(created, ts_event, jnp.uint64(0)))
    last_actual = jnp.max(jnp.where(
        created, ts_actual if imported_mode else ts_event,
        jnp.uint64(0)))
    key_max = jnp.where(created.any() & ok,
                        jnp.maximum(state["acct_key_max"], last_actual),
                        state["acct_key_max"])
    commit_ts = jnp.where(created.any() & ok, last_ts, state["commit_ts"])

    new_state = dict(
        state,
        accounts=new_acc,
        acct_ht=new_ht,
        acct_key_max=key_max,
        commit_ts=commit_ts,
    )
    out = dict(
        r_status=jnp.where(ok, status, jnp.zeros_like(status)),
        r_ts=jnp.where(ok, jnp.where(valid, ts_actual, z64),
                       jnp.zeros_like(ts_actual)),
        fallback=fallback,
        created_count=jnp.where(ok, n_created, 0),
    )
    return new_state, out


create_accounts_fast_jit = jax.jit(create_accounts_fast, donate_argnums=0)
create_accounts_imported_jit = _tier_jit(
    "create_accounts_imported", create_accounts_fast, imported_mode=True)
