"""Verified state epochs: a cheap digest of the ledger state pytree.

TigerBeetle's doctrine is that determinism turns faults into repairable
events: corrupted blocks are *detected* by checksums and healed from a
known-good source (docs/ARCHITECTURE.md fault model; reference
src/vsr/checksum.zig + the grid scrubber). The device ledger had no
analog — a bit flipped in an HBM-resident account balance would serve
wrong answers forever. This module is the detection half of the serving
robustness layer (tigerbeetle_tpu/serving.py is the recovery half):

  - `device_state_digest(state)` — ONE tiny jitted reduction over the
    ledger state pytree (a few fused element-wise ops + a sum per
    component; its own jit entry, never part of any serving lowering —
    the op-budget gate and every kernel tier are untouched). Returns a
    dict of named u64 component digests.
  - `oracle_state_digest(sm, a_cap)` — the SAME fold computed on host
    from an oracle state (the last-verified-epoch replay target),
    packed through the ledger's own canonical row packers
    (`_pack_account_rows` / `_pack_transfer_rows` — the exact code
    `from_host` rebuilds a device from). If device and oracle disagree
    on any digested bit, the digests differ.
  - `combine(comps)` — one u64 over the component dict (host-side,
    order-independent of dict ordering).

What is digested (and what deliberately is not):

  covered   accounts matrix (all columns), the balance-limb matrix,
            transfers matrix (each widened to its u64 words: the fold
            is over the u64 view of the u32 stores, ev_layout.widen),
            and the scalar vector (row counts,
            key maxima, commit_ts) — exactly the fields the VOPR/fuzz
            differentials pin as path-canonical (identical whether a
            row was written by the fast kernel, a mirror push, or a
            from_host rebuild).
  excluded  the transfer `expires` column and the dr_row/cr_row cache
            column (not canonical across write paths: the mirror push
            zeroes expires on release, the fast kernel leaves it), the
            hash tables (probe-order-dependent layout; a corrupt bucket
            surfaces as a lookup/result divergence instead), the event
            ring (recycled per window in serving mode; rows beyond the
            consumed cursor are scratch), and pulse_next (maintained
            with equivalent but not bit-pinned logic on both sides).

The fold is sum-of-mixed-rows: per row, a column-Horner fold is mixed
(splitmix64 finalizer) with the row index and a per-component salt,
rows at/after `count` are zeroed, and the rows are summed (wrapping
u64). Addition keeps the fold shape-independent: a host pack holding
only the live rows digests identically to the full-capacity device
matrix with masked tails.
"""

from __future__ import annotations

import numpy as np

from .ev_layout import (AC_NCOLS, XF_NCOLS, XF_P32_POS, XF_U64_IDX, narrow,
                        widen)

_U64_MASK = (1 << 64) - 1
_PHI = 0x9E3779B97F4A7C15  # odd golden-ratio constant (also the Horner base)
_MIX1 = 0xBF58476D1CE4E5B9
_MIX2 = 0x94D049BB133111EB

# Per-column digest masks (None = all columns fully covered). The
# transfers store excludes the two non-canonical columns; see module doc.
AC_COL_MASKS = None


def _xf_col_masks() -> tuple:
    masks = [_U64_MASK] * XF_NCOLS
    masks[XF_U64_IDX["expires"]] = 0
    # (dr_row, cr_row) pair-pack into one u64 column — drop the whole word.
    masks[XF_P32_POS["dr_row"][0]] = 0
    return tuple(masks)


XF_COL_MASKS = _xf_col_masks()


def _mix64(x, xp):
    """splitmix64 finalizer over a u64 array (numpy or jax.numpy)."""
    u = xp.uint64
    x = x ^ (x >> u(30))
    x = x * u(_MIX1)
    x = x ^ (x >> u(27))
    x = x * u(_MIX2)
    x = x ^ (x >> u(31))
    return x


def _mix_int(x: int) -> int:
    x &= _U64_MASK
    x ^= x >> 30
    x = (x * _MIX1) & _U64_MASK
    x ^= x >> 27
    x = (x * _MIX2) & _U64_MASK
    x ^= x >> 31
    return x


def _matrix_digest(m, count, col_masks, salt: int, xp):
    """Sum over rows < count of mix(column-Horner(row) ^ row-index ^ salt).

    `m` is a (rows, cols) u64 matrix; `count` the live-row count (host
    int or traced scalar). Identical results for numpy and jax.numpy —
    both wrap u64 arithmetic — and independent of the matrix's
    capacity beyond `count` (masked to zero before the sum)."""
    rows = m.shape[0]
    u = xp.uint64
    acc = xp.zeros(rows, dtype=xp.uint64)
    for j in range(m.shape[1]):
        mask = _U64_MASK if col_masks is None else int(col_masks[j])
        if mask == 0:
            continue
        col = m[:, j]
        if mask != _U64_MASK:
            col = col & u(mask)
        acc = acc * u(_PHI) + col
    iota = xp.arange(rows, dtype=xp.uint64)
    rowd = _mix64(acc ^ (iota * u(_PHI)) ^ u(salt & _U64_MASK), xp)
    live = iota < xp.asarray(count).astype(xp.uint64)
    return xp.sum(xp.where(live, rowd, u(0)))


# Component salts: fixed, so digests are comparable across processes.
_SALT = {"accounts_u64": 0xA1, "accounts_bal": 0xB2,
         "transfers_u64": 0xC3, "scalars": 0xD4}


def _digest_components(state: dict, xp) -> dict:
    """The shared fold over a ledger state pytree (device jnp arrays or
    a host numpy pack from `pack_oracle_state`)."""
    acc = state["accounts"]
    xfr = state["transfers"]
    comps = {
        "accounts_u64": _matrix_digest(
            widen(acc["u32"]), acc["count"], AC_COL_MASKS,
            _SALT["accounts_u64"], xp),
        "accounts_bal": _matrix_digest(
            widen(acc["bal"]), acc["count"], None,
            _SALT["accounts_bal"], xp),
        "transfers_u64": _matrix_digest(
            widen(xfr["u32"]), xfr["count"], XF_COL_MASKS,
            _SALT["transfers_u64"], xp),
    }
    scalars = xp.stack([
        xp.asarray(state["acct_key_max"]).astype(xp.uint64),
        xp.asarray(state["xfer_key_max"]).astype(xp.uint64),
        xp.asarray(state["commit_ts"]).astype(xp.uint64),
        xp.asarray(acc["count"]).astype(xp.uint64),
        xp.asarray(xfr["count"]).astype(xp.uint64),
    ])
    comps["scalars"] = _matrix_digest(
        scalars[None, :], 1, None, _SALT["scalars"], xp)
    return comps


_digest_jit = None


def device_state_digest(state: dict) -> dict:
    """Digest the DEVICE ledger state: one jitted reduction (read-only —
    the state is NOT donated), resolved to host ints."""
    global _digest_jit
    import jax

    if _digest_jit is None:
        import jax.numpy as jnp

        _digest_jit = jax.jit(lambda s: _digest_components(s, jnp))
    out = jax.device_get(_digest_jit(state))
    return {k: int(v) for k, v in out.items()}


def pack_oracle_state(sm, a_cap: int) -> dict:
    """Pack an oracle state's digested components through the ledger's
    canonical host packers (the `from_host` rebuild path), as numpy.
    Only the live rows are materialized — the fold is capacity-blind."""
    from ..types import TransferPendingStatus
    from .ledger import _pack_account_rows, _pack_transfer_rows

    # Applied-timestamp order — the canonical row order (from_host and
    # _push_dirty pack device rows the same way; dict order equals it
    # on every live path, the sort pins restored states too).
    accounts = sorted(sm.accounts.values(), key=lambda a: a.timestamp)
    if accounts:
        a_u64, a_bal = _pack_account_rows(accounts)
    else:
        a_u64 = np.zeros((0, AC_NCOLS), dtype=np.uint64)
        a_bal = np.zeros((0, 16), dtype=np.uint64)
    acct_row = {a.id: r for r, a in enumerate(accounts)}
    # Commit (timestamp) order — device transfer rows append in commit
    # order, and from_host packs the same way.
    transfers = [sm.transfers[tid]
                 for tid in sm.transfer_by_timestamp.values()]
    if transfers:
        x_u64 = _pack_transfer_rows(
            transfers,
            lambda o: int(sm.pending_status.get(
                o.timestamp, TransferPendingStatus.none)),
            lambda aid, dump: acct_row.get(aid, dump),
            a_cap)
    else:
        x_u64 = np.zeros((0, XF_NCOLS), dtype=np.uint64)
    return dict(
        accounts=dict(u32=narrow(a_u64), bal=narrow(a_bal),
                      count=np.int32(len(accounts))),
        transfers=dict(u32=narrow(x_u64), count=np.int32(len(transfers))),
        acct_key_max=np.uint64(sm.accounts_key_max or 0),
        xfer_key_max=np.uint64(sm.transfers_key_max or 0),
        commit_ts=np.uint64(sm.commit_timestamp),
    )


def oracle_state_digest(sm, a_cap: int) -> dict:
    """The host-side expected digest of an oracle state (numpy fold over
    the canonical pack) — bit-comparable with `device_state_digest`."""
    comps = _digest_components(pack_oracle_state(sm, a_cap), np)
    return {k: int(v) for k, v in comps.items()}


def combine(comps: dict) -> int:
    """One u64 digest over the component dict (key-sorted, so dict
    ordering never matters)."""
    d = 0
    for k in sorted(comps):
        d = _mix_int(d ^ (int(comps[k]) & _U64_MASK))
    return d


def diverging_components(got: dict, want: dict) -> list[str]:
    """Component names where two digest dicts disagree (fault
    attribution for the recovery log)."""
    return sorted(k for k in set(got) | set(want)
                  if got.get(k) != want.get(k))


# ------------------------------------------------- partitioned states
# (parallel/partitioned.py: every store sharded by id hash over the
# mesh axis). The fold extends by shard-then-sum: each shard digests
# its LOCAL rows with LOCAL row indices — exactly the indices the
# per-shard oracle pack assigns under the same shard-then-sort order —
# and the per-component digests wrap-sum across shards. Addition keeps
# the combination order-free, so device (vmapped) and host (looped)
# agree bit-for-bit.

_pdigest_jit = None


def _stacked_digest_view(stacked: dict) -> dict:
    """The digested subset of a stacked partitioned pytree (drops the
    excluded stores so the vmapped fold never touches them)."""
    return dict(
        accounts=stacked["accounts"], transfers=stacked["transfers"],
        acct_key_max=stacked["acct_key_max"],
        xfer_key_max=stacked["xfer_key_max"],
        commit_ts=stacked["commit_ts"])


def partitioned_state_digest(stacked: dict) -> dict:
    """Digest a device-sharded (stacked) partitioned state: per-shard
    folds wrap-summed per component. Read-only, its own jit entry."""
    global _pdigest_jit
    import jax

    if _pdigest_jit is None:
        import jax.numpy as jnp

        def fold(view):
            comps = jax.vmap(lambda s: _digest_components(s, jnp))(view)
            return {k: jnp.sum(v) for k, v in comps.items()}

        _pdigest_jit = jax.jit(fold)
    out = jax.device_get(_pdigest_jit(_stacked_digest_view(stacked)))
    return {k: int(v) for k, v in out.items()}


def pack_oracle_state_partitioned(sm, a_cap: int, n_shards: int,
                                  overlay: tuple = ()) -> list:
    """Per-shard canonical packs of an oracle state: objects assigned by
    the SAME ownership hash the kernels use (shard_utils.shard_of_id),
    then packed in the canonical order within each shard (accounts by
    applied timestamp, transfers in commit order) — the shard-then-sort
    contract partitioned_from_oracle pins on device. With an `overlay`
    (elastic shards mid-/post-migration) assignment follows the READ
    owner — comparable with a device state whose migrated ranges have
    flipped AND retired (a stale pre-retire source copy, or a
    partially-copied target, is exactly the divergence the epoch verify
    should flag)."""
    from types import SimpleNamespace

    from ..parallel.shard_utils import owner_read_int

    assert a_cap % n_shards == 0, (a_cap, n_shards)

    def shard_of(id128):
        return owner_read_int(id128, n_shards, overlay)

    packs = []
    for s in range(n_shards):
        view = SimpleNamespace(
            accounts={aid: a for aid, a in sm.accounts.items()
                      if shard_of(aid) == s},
            transfers=sm.transfers,
            transfer_by_timestamp={
                ts: tid for ts, tid in sm.transfer_by_timestamp.items()
                if shard_of(tid) == s},
            pending_status=sm.pending_status,
            accounts_key_max=sm.accounts_key_max,
            transfers_key_max=sm.transfers_key_max,
            commit_timestamp=sm.commit_timestamp,
        )
        packs.append(pack_oracle_state(view, a_cap // n_shards))
    return packs


def partitioned_oracle_digest(sm, a_cap: int, n_shards: int,
                              overlay: tuple = ()) -> dict:
    """Host-side expected digest of an oracle state under the
    partitioned layout — bit-comparable with partitioned_state_digest
    over a stepped device state at the same (a_cap, n_shards) and
    (retired) overlay."""
    total: dict = {}
    for pack in pack_oracle_state_partitioned(sm, a_cap, n_shards,
                                              overlay):
        comps = _digest_components(pack, np)
        for k, v in comps.items():
            total[k] = (total.get(k, 0) + int(v)) & _U64_MASK
    return total


# ------------------------------------------------------- range digests
# (ISSUE 19, elastic shards). The migration flip needs a witness that
# ONE hash range is bit-identical on source and target even though the
# range's rows sit at different LOCAL row indices in the two stores —
# so the range fold is position-independent: instead of mixing the
# storage row index, each row mixes its OWN 64-bit ownership hash
# (shard_utils.mix_id over the row's id limbs). Rows outside [lo, hi]
# (inclusive — the overlay-entry convention) are zeroed before the
# wrap-sum, so capacity, row order, and out-of-range neighbours all
# cancel. Same exclusions as the epoch digest (expires, row caches,
# tables, ring); additionally the row CONTENT hash is paired with an
# in-range row COUNT per store, so "same digest, different cardinality"
# is impossible to miss.

_RSALT = {"accounts_u64": 0x5A1, "accounts_bal": 0x5B2,
          "transfers_u64": 0x5C3}


def _range_matrix_digest(m, count, col_masks, salt: int, h, member,
                         xp):
    """Position-independent row fold over rows < count selected by the
    `member` mask (a (rows,) bool vector — the migration-membership
    predicate over the precomputed ownership-hash vector `h`). Returns
    (digest, n_rows) as u64 scalars."""
    rows = m.shape[0]
    u = xp.uint64
    acc = xp.zeros(rows, dtype=xp.uint64)
    for j in range(m.shape[1]):
        mask = _U64_MASK if col_masks is None else int(col_masks[j])
        if mask == 0:
            continue
        col = m[:, j]
        if mask != _U64_MASK:
            col = col & u(mask)
        acc = acc * u(_PHI) + col
    rowd = _mix64(acc ^ (h * u(_PHI)) ^ u(salt & _U64_MASK), xp)
    iota = xp.arange(rows, dtype=xp.uint64)
    live = (iota < xp.asarray(count).astype(xp.uint64)) & member
    dig = xp.sum(xp.where(live, rowd, u(0)))
    n = xp.sum(live.astype(xp.uint64))
    return dig, n


def _range_digest_components(state: dict, lo, hi, src, n_shards: int,
                             xp) -> dict:
    """The range fold over one ledger-state pack (device jnp pytree or
    a host numpy pack). Membership is the overlay-entry predicate —
    `h in [lo, hi] AND base_owner(h) == src` — NOT the bare range: the
    flip compares the source and TARGET shards, and the target's own
    base rows whose hashes happen to fall inside [lo, hi] must not
    contaminate its fold. No scalars component — counters and key
    maxima are whole-shard facts, not range facts."""
    from ..parallel.shard_utils import mix_id

    u = xp.uint64
    acc = state["accounts"]
    xfr = state["transfers"]

    def member(h):
        return ((h >= xp.asarray(lo).astype(xp.uint64))
                & (h <= xp.asarray(hi).astype(xp.uint64))
                & ((h & u(n_shards - 1))
                   == xp.asarray(src).astype(xp.uint64)))

    a_u64 = widen(acc["u32"])
    a_h = mix_id(a_u64[:, 0], a_u64[:, 1])
    x_u64 = widen(xfr["u32"])
    x_h = mix_id(x_u64[:, 0], x_u64[:, 1])
    a_m, x_m = member(a_h), member(x_h)
    a_dig, a_n = _range_matrix_digest(
        a_u64, acc["count"], AC_COL_MASKS,
        _RSALT["accounts_u64"], a_h, a_m, xp)
    b_dig, _ = _range_matrix_digest(
        widen(acc["bal"]), acc["count"], None, _RSALT["accounts_bal"],
        a_h, a_m, xp)
    x_dig, x_n = _range_matrix_digest(
        x_u64, xfr["count"], XF_COL_MASKS,
        _RSALT["transfers_u64"], x_h, x_m, xp)
    return {"accounts_u64": a_dig, "accounts_bal": b_dig,
            "transfers_u64": x_dig, "accounts_rows": a_n,
            "transfers_rows": x_n}


_rdigest_jit = None


def partitioned_range_digest(stacked: dict, lo: int, hi: int,
                             src: int) -> list:
    """PER-SHARD range digests of a device-sharded (stacked)
    partitioned state: a list of component dicts, one per shard, NOT
    summed — the flip compares the source shard's entry against the
    target shard's (and the host oracle's) at the same epoch. `src` is
    the migrating range's BASE owner (membership predicate, see
    `_range_digest_components`). `lo`/`hi`/`src` are traced scalars:
    one lowering serves every migration on a given mesh size."""
    global _rdigest_jit
    import jax

    if _rdigest_jit is None:
        import jax.numpy as jnp

        def fold(view, lo_, hi_, src_):
            n = next(iter(
                view["accounts"].values())).shape[0]
            return jax.vmap(
                lambda s: _range_digest_components(s, lo_, hi_, src_,
                                                   n, jnp)
            )(view)

        _rdigest_jit = jax.jit(fold)
    out = jax.device_get(_rdigest_jit(
        _stacked_digest_view(stacked),
        np.uint64(lo & _U64_MASK), np.uint64(hi & _U64_MASK),
        np.uint64(src)))
    n_shards = len(next(iter(out.values())))
    return [{k: int(v[s]) for k, v in out.items()}
            for s in range(n_shards)]


def oracle_range_digest(sm, a_cap: int, lo: int, hi: int, src: int,
                        n_shards: int) -> dict:
    """Host-side expected range digest over the canonical oracle pack
    (whole state — the fold is position-independent, so it equals the
    membership sum across any shard placement of the same rows)."""
    pack = pack_oracle_state(sm, a_cap)
    comps = _range_digest_components(
        pack, np.uint64(lo & _U64_MASK), np.uint64(hi & _U64_MASK),
        np.uint64(src), n_shards, np)
    return {k: int(v) for k, v in comps.items()}


def sum_range_components(comps: list) -> dict:
    """Wrap-sum a list of per-shard range-digest dicts (e.g. source +
    target during double-write equals the oracle's whole-range fold)."""
    total: dict = {}
    for c in comps:
        for k, v in c.items():
            total[k] = (total.get(k, 0) + int(v)) & _U64_MASK
    return total
