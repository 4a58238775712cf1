"""Packed store layouts shared by the kernel and the ledger.

Every store (transfers, the event ring, accounts and their balance
limbs) is ONE u32 matrix of shape (rows, 2 * NCOLS): columns 2c / 2c+1
hold the low / high half of logical u64 column c, and a 32-bit field is
a plain u32 column (the low or high member of a tail pair). On a
little-endian host `np.asarray(store).view(np.uint64)` is the (rows,
NCOLS) packed u64 matrix the mirror, the delta fetch, the state epochs
and the durable format are written against — `widen` / `narrow` are
that view, and the whole change of representation lives in this module.

Why u32, and why one matrix (what a v5e showed, PR 32 — PERF.md §6):
  - a u64 array at a jit boundary is split into u32 halves on entry
    and recombined on exit: two passes over the WHOLE array and a
    temporary its size, whatever the program does to it. TPUs have no
    64-bit lanes, so the dtype is the cost. A u32 store that a program
    only row-gathers and row-scatters is updated in place;
  - an element scatter into a 2-D store, and a reshape of a 2-D store
    to 1-D, relayout the whole operand. So a partial-row update is a
    scatter of whole rows (the transfer pstat flip rewrites the row it
    already gathered), and element scatters go to 1-D arrays only
    (ops/hash_table.py);
  - the halves interleave in one matrix, not two, so a row append
    stays ONE scatter and a row-set gather ONE gather (of a row twice
    as wide: the same bytes), widened to u64 AFTER the gather on
    batch-sized arrays — the jaxpr's heavy-op census
    (perf/opbudget.py) is what it was.

Accounts keep two matrices (meta, and the (rows, 2 * 16) balance-limb
matrix whose u64 view holds four u128 fields x four u32-normalized
limbs), so an account append or gather is two ops.

Logical column -> (matrix column, half) maps; *_col()/*_named() give
named access and hide the layout. Signed 32-bit fields are stored as
their uint32 bit pattern and sign-restored on read.

Reference data model: the account_events groove row
(src/state_machine.zig:104-220), the 128-byte Account
(src/tigerbeetle.zig:10-43) and Transfer (src/tigerbeetle.zig:85-116).
"""

from __future__ import annotations

import sys

import numpy as np

assert sys.byteorder == "little", \
    "the host reads the u32 stores through a u64 view (widen / narrow)"



def _p32_maps(u64_names, p32_pairs):
    """(field -> (column, half)) for the packed 32-bit tail columns."""
    pos = {}
    for j, pair in enumerate(p32_pairs):
        for h, name in enumerate(pair):
            pos[name] = (len(u64_names) + j, h)
    return pos


def pack32(lo, hi=None):
    """Pack one or two 32-bit columns into a u64 word column (batch
    lanes that ride a stacked u64 gather, and the host row packers).
    Works on numpy and jax arrays; signed inputs go through uint32 so
    the high half is never sign-smeared."""
    w = lo.astype(np.uint32).astype(np.uint64)
    if hi is not None:
        w = w | (hi.astype(np.uint32).astype(np.uint64) << np.uint64(32))
    return w


def widen(m32):
    """(..., 2C) u32 interleaved halves -> (..., C) u64. A free view on
    a host array; on the device an elementwise combine, to be applied
    to batch-sized gathers and never to a whole store inside a serving
    program."""
    if isinstance(m32, np.ndarray):
        return np.ascontiguousarray(m32).view(np.uint64)
    from jax import lax

    # lax strided slices: jnp's `[..., 0::2]` traces to a gather.
    axis = m32.ndim - 1
    lo = lax.slice_in_dim(m32, 0, None, stride=2, axis=axis)
    hi = lax.slice_in_dim(m32, 1, None, stride=2, axis=axis)
    return lo.astype(np.uint64) | (hi.astype(np.uint64) << np.uint64(32))


def narrow(m64):
    """(..., C) u64 -> (..., 2C) u32 interleaved halves (inverse of
    `widen`)."""
    if isinstance(m64, np.ndarray):
        return np.ascontiguousarray(m64).view(np.uint32)
    import jax.numpy as jnp

    halves = jnp.stack([m64.astype(np.uint32),
                        (m64 >> np.uint64(32)).astype(np.uint32)], axis=-1)
    return halves.reshape(m64.shape[:-1] + (2 * m64.shape[-1],))


def col64(m32, col):
    """Logical u64 column `col` of an interleaved u32 matrix."""
    if isinstance(m32, np.ndarray):
        return widen(m32)[:, col]
    return (m32[:, 2 * col].astype(np.uint64)
            | (m32[:, 2 * col + 1].astype(np.uint64) << np.uint64(32)))


def _col32(m32, name, pos, signed):
    col, half = pos[name]
    v = m32[:, 2 * col + half]
    return v.astype(np.int32) if name in signed else v


def _named32(m32, u64_idx, pos, signed) -> dict:
    out = {n: col64(m32, i) for n, i in u64_idx.items()}
    for n in pos:
        out[n] = _col32(m32, n, pos, signed)
    return out


def with_col32(rows32, col32: int, vals):
    """`rows32` with its u32 column `col32` replaced by `vals`: how a
    partial-row update is made ready for a scatter of whole rows."""
    import jax.numpy as jnp

    return jnp.where(jnp.arange(rows32.shape[1]) == col32,
                     vals.astype(np.uint32)[:, None], rows32)


def _rows32(vals: dict, u64_names, p32_pairs):
    """Named batch columns -> the (N, 2C) u32 row matrix a store takes:
    each u64 field as its two halves, each 32-bit field as its own
    column (an unpaired tail member's partner is zero)."""
    import jax.numpy as jnp

    cols = []
    for n in u64_names:
        v = vals[n]
        cols += [v.astype(np.uint32), (v >> np.uint64(32)).astype(np.uint32)]
    for pr in p32_pairs:
        lo = vals[pr[0]].astype(np.uint32)
        cols += [lo, vals[pr[1]].astype(np.uint32) if len(pr) > 1
                 else jnp.zeros_like(lo)]
    return jnp.stack(cols, axis=1)


# ------------------------------------------------- account_events ring
EV_U64 = ("ts", "amt_hi", "amt_lo", "areq_hi", "areq_lo") + tuple(
    f"{side}_{f}_{half}"
    for side in ("dr", "cr")
    for f in ("dp", "dpos", "cp", "cpos")
    for half in ("hi", "lo"))
EV_I32 = ("pstat", "p_row", "dr_row", "cr_row")
EV_U32 = ("tflags", "dr_flags", "cr_flags")
# Packed 32-bit tail: append order defines the matrix columns.
EV_P32 = (("pstat", "p_row"), ("dr_row", "cr_row"),
          ("tflags", "dr_flags"), ("cr_flags",))
EV_U64_IDX = {n: i for i, n in enumerate(EV_U64)}
EV_P32_POS = _p32_maps(EV_U64, EV_P32)
EV_NCOLS = len(EV_U64) + len(EV_P32)
_EV_SIGNED = frozenset(EV_I32)


def ev_col(evr: dict, name: str):
    """Named column view of a packed events ring (device or numpy)."""
    if name in EV_U64_IDX:
        return col64(evr["u32"], EV_U64_IDX[name])
    return _col32(evr["u32"], name, EV_P32_POS, _EV_SIGNED)


def ev_cap(evr: dict) -> int:
    return evr["u32"].shape[0] - 1


def ev_named(rows: dict) -> dict:
    """Packed event rows ({'u32'} matrix) -> named column dict (works on
    device arrays, numpy, or row-sliced views)."""
    return _named32(rows["u32"], EV_U64_IDX, EV_P32_POS, _EV_SIGNED)


def ev_rows32(vals: dict):
    """Named event columns -> (N, 2 * EV_NCOLS) u32 ring rows."""
    return _rows32(vals, EV_U64, EV_P32)


# Packed account balance layout: the u64 view of acc["bal"] is (rows, 16)
# — four u128 fields x four u32-normalized limbs. Logical column =
# BAL_FIELDS index * 4 + limb.
BAL_FIELDS = ("dp", "dpos", "cp", "cpos")
BAL_IDX = {f: i * 4 for i, f in enumerate(BAL_FIELDS)}


def bal_col(field: str, limb: int) -> int:
    return BAL_IDX[field] + limb


# ------------------------------------------------------- accounts store
AC_U64 = ("id_hi", "id_lo", "ud128_hi", "ud128_lo", "ud64", "ts")
AC_U32 = ("ud32", "ledger", "code", "flags")
AC_P32 = (("ud32", "ledger"), ("code", "flags"))
AC_U64_IDX = {n: i for i, n in enumerate(AC_U64)}
AC_P32_POS = _p32_maps(AC_U64, AC_P32)
AC_NCOLS = len(AC_U64) + len(AC_P32)
_AC_SIGNED = frozenset()


AC_FLAGS_COL32 = 2 * AC_P32_POS["flags"][0] + AC_P32_POS["flags"][1]


def ac_col(acc: dict, name: str):
    """Named column view of a packed accounts store (device or numpy)."""
    if name in AC_U64_IDX:
        return col64(acc["u32"], AC_U64_IDX[name])
    return _col32(acc["u32"], name, AC_P32_POS, _AC_SIGNED)


def ac_named(rows: dict) -> dict:
    """Packed account rows ({'u32'[, 'bal']} matrices) -> named column
    dict (works on device arrays, numpy, or row-sliced views). The
    balance limb matrix rides under 'bal', widened to its (n, 16) u64
    view, when present."""
    out = _named32(rows["u32"], AC_U64_IDX, AC_P32_POS, _AC_SIGNED)
    if "bal" in rows:
        out["bal"] = widen(rows["bal"])
    return out


def ac_rows32(vals: dict):
    """Named account columns -> (N, 2 * AC_NCOLS) u32 store rows."""
    return _rows32(vals, AC_U64, AC_P32)


# ------------------------------------------------------ transfers store
XF_U64 = ("id_hi", "id_lo", "dr_hi", "dr_lo", "cr_hi", "cr_lo",
          "amt_hi", "amt_lo", "pid_hi", "pid_lo", "ud128_hi", "ud128_lo",
          "ud64", "ts", "expires")
XF_U32 = ("ud32", "timeout", "ledger", "code", "flags")
XF_I32 = ("pstat", "dr_row", "cr_row")
# pstat keeps a tail pair to itself (the durable row format; the
# post/void flip rewrites the whole row it gathered).
XF_P32 = (("ud32", "timeout"), ("ledger", "code"), ("dr_row", "cr_row"),
          ("flags",), ("pstat",))
XF_U64_IDX = {n: i for i, n in enumerate(XF_U64)}
XF_P32_POS = _p32_maps(XF_U64, XF_P32)
XF_NCOLS = len(XF_U64) + len(XF_P32)
_XF_SIGNED = frozenset(XF_I32)
# pstat's own u32 column in a store row.
XF_PSTAT_COL32 = 2 * XF_P32_POS["pstat"][0] + XF_P32_POS["pstat"][1]


def xf_col(xfr: dict, name: str):
    """Named column view of a packed transfers store (device or numpy)."""
    if name in XF_U64_IDX:
        return col64(xfr["u32"], XF_U64_IDX[name])
    return _col32(xfr["u32"], name, XF_P32_POS, _XF_SIGNED)


def xf_named(rows: dict) -> dict:
    """Packed transfer rows ({'u32'} matrix) -> named column dict (works
    on device arrays, numpy, or row-sliced views)."""
    return _named32(rows["u32"], XF_U64_IDX, XF_P32_POS, _XF_SIGNED)


def xf_rows32(vals: dict):
    """Named transfer columns -> (N, 2 * XF_NCOLS) u32 store rows."""
    return _rows32(vals, XF_U64, XF_P32)
