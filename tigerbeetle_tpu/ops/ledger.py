"""DeviceLedger: the device-resident account/transfer state store.

The TPU-native re-design of the reference's groove object caches
(src/lsm/groove.zig:885 get, :1770 insert): accounts and transfers live in
HBM as struct-of-arrays rows; id -> row lookups run through the device hash
table (ops/hash_table.py); batch validation runs the vectorized fast kernels
(ops/fast_kernels.py) with zero per-event host work.

Exactness contract: eligible batches (see fast_kernels eligibility E1-E7)
are processed entirely on device with results bit-identical to the oracle;
ineligible batches fall back to the host sequential kernel
(ops/create_kernels.py) via a full state sync — slow but exact. The ledger
therefore always matches the oracle, batch for batch.

History: account_events (CDC/balance history) rows are materialized ON
DEVICE by the fast path — exact post-application balance snapshots via a
sort + segmented limb prefix sum in the kernel — and kept in a device ring
(state["events"]); the mirror regime pushes host-generated rows (hard
batches, expiries) into the same ring.
"""

from __future__ import annotations

import time as _time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..constants import BATCH_MAX, NS_PER_S, TIMESTAMP_MIN
from ..trace import Event, NullTracer
from ..types import (
    Account,
    AccountFlags,
    CreateAccountResult,
    CreateAccountStatus,
    CreateTransferResult,
    CreateTransferStatus,
    Transfer,
    TransferPendingStatus,
)

# Transient statuses poison the transfer id (reference:
# src/tigerbeetle.zig:320-399); the write-through delta uses them to
# mirror the device's orphan inserts on the host.
_TRANSIENT_CODES = frozenset(
    int(s) for s in CreateTransferStatus if s.transient())

# Enum.__call__ per event is a measurable serving-path cost at 8190
# events/batch: precomputed code->member maps instead.
_CTS_BY_CODE = {int(m): m for m in CreateTransferStatus}
_CAS_BY_CODE = {int(m): m for m in CreateAccountStatus}
_TRANSIENT_ARR = np.fromiter(_TRANSIENT_CODES, dtype=np.uint32)
from . import u128
from .hash_table import ORPHAN_VAL, ht_init

N_PAD = 8192
assert N_PAD >= BATCH_MAX

# The column slots of a queued chunk that created nothing (orphan ids
# only, or an empty prepare the window commit still counts).
_NO_COLS = (None, None, None)

# Padded-shape buckets for the transfer kernels: a batch compiles and runs
# at the smallest bucket that fits instead of always paying BATCH_MAX-row
# kernel work (jit keeps one cached executable per bucket actually used).
PAD_BUCKETS = (1024, 2048, 4096, N_PAD)


def _pad_bucket(n: int) -> int:
    for b in PAD_BUCKETS:
        if n <= b:
            return b
    raise AssertionError(f"batch of {n} exceeds BATCH_MAX padding")

from .ev_layout import (  # noqa: F401 — re-exported ring layout
    AC_NCOLS,
    AC_P32_POS,
    AC_U32,
    AC_U64,
    AC_U64_IDX,
    BAL_FIELDS,
    BAL_IDX,
    ac_named,
    EV_NCOLS,
    EV_P32_POS,
    EV_U64,
    EV_U64_IDX,
    XF_NCOLS,
    XF_P32_POS,
    XF_PSTAT_COL32,
    XF_U64,
    XF_U64_IDX,
    bal_col,
    ev_cap,
    ev_col,
    ev_named,
    narrow,
    pack32,
    widen,
    xf_col,
    xf_named,
)



def _set32(mat: np.ndarray, pos: dict, name: str, vals) -> None:
    """Write a 32-bit logical column into its packed u64 half (host
    builder counterpart of ev_layout's *_col readers)."""
    col, half = pos[name]
    v = np.asarray(vals).astype(np.uint32).astype(np.uint64)
    mat[:, col] |= (v << np.uint64(32)) if half else v


def _pack_transfer_rows(objs, pstat_of, acct_row_of, a_dump):
    """Transfer objects -> one packed u64 row matrix (shared by the full
    rebuild and the incremental dirty push, so the two paths cannot
    drift)."""
    n = len(objs)
    u64m = np.zeros((n, XF_NCOLS), dtype=np.uint64)
    w32 = {name: np.zeros(n, dtype=np.int64) for name in XF_P32_POS}
    U = XF_U64_IDX
    for i, o in enumerate(objs):
        u64m[i, U["id_hi"]], u64m[i, U["id_lo"]] = _split(o.id)
        (u64m[i, U["dr_hi"]],
         u64m[i, U["dr_lo"]]) = _split(o.debit_account_id)
        (u64m[i, U["cr_hi"]],
         u64m[i, U["cr_lo"]]) = _split(o.credit_account_id)
        u64m[i, U["amt_hi"]], u64m[i, U["amt_lo"]] = _split(o.amount)
        u64m[i, U["pid_hi"]], u64m[i, U["pid_lo"]] = _split(o.pending_id)
        (u64m[i, U["ud128_hi"]],
         u64m[i, U["ud128_lo"]]) = _split(o.user_data_128)
        u64m[i, U["ud64"]] = o.user_data_64
        u64m[i, U["ts"]] = o.timestamp
        u64m[i, U["expires"]] = (
            o.timestamp + o.timeout * NS_PER_S if o.timeout else 0)
        w32["ud32"][i] = o.user_data_32
        w32["timeout"][i] = o.timeout
        w32["ledger"][i] = o.ledger
        w32["code"][i] = o.code
        w32["flags"][i] = o.flags
        w32["pstat"][i] = pstat_of(o)
        w32["dr_row"][i] = acct_row_of(o.debit_account_id, a_dump)
        w32["cr_row"][i] = acct_row_of(o.credit_account_id, a_dump)
    for name, vals in w32.items():
        _set32(u64m, XF_P32_POS, name, vals)
    return u64m


def _pack_account_rows(objs):
    """Account objects -> (packed u64 row matrix, balance-limb matrix)
    (shared by the full rebuild, the dirty push, and the epoch digest's
    expected pack, so the three paths cannot drift)."""
    n = len(objs)
    u64m = np.zeros((n, AC_NCOLS), dtype=np.uint64)
    bal = np.zeros((n, 16), dtype=np.uint64)
    aw32 = {name: np.zeros(n, dtype=np.int64) for name in AC_P32_POS}
    AU = AC_U64_IDX
    for i, o in enumerate(objs):
        u64m[i, AU["id_hi"]], u64m[i, AU["id_lo"]] = _split(o.id)
        for f, val in (("dp", o.debits_pending), ("dpos", o.debits_posted),
                       ("cp", o.credits_pending),
                       ("cpos", o.credits_posted)):
            for j, lim in enumerate(_limbs4(val)):
                bal[i, bal_col(f, j)] = lim
        (u64m[i, AU["ud128_hi"]],
         u64m[i, AU["ud128_lo"]]) = _split(o.user_data_128)
        u64m[i, AU["ud64"]] = o.user_data_64
        u64m[i, AU["ts"]] = o.timestamp
        aw32["ud32"][i] = o.user_data_32
        aw32["ledger"][i] = o.ledger
        aw32["code"][i] = o.code
        aw32["flags"][i] = o.flags
    for name, vals in aw32.items():
        _set32(u64m, AC_P32_POS, name, vals)
    return u64m, bal


def _pack_event_rows(records, acct_row: dict, xfer_row: dict,
                     a_dump: int) -> dict:
    """Host AccountEventRecords -> the packed ring row matrix (shared by
    the replicated rebuild/push and the partitioned per-shard rebuild,
    so the two paths cannot drift). Row maps may be SHARD-LOCAL under
    the partitioned layout: a remote account resolves to the dump row
    and a remote pending transfer to -1 (row pointers are non-canonical
    scope — the digest excludes them and consumers re-derive from ids)."""
    n = len(records)
    u64 = np.zeros((n, EV_NCOLS), dtype=np.uint64)
    w32 = {name: np.zeros(n, dtype=np.int64) for name in EV_P32_POS}
    U = EV_U64_IDX
    for i, rec in enumerate(records):
        u64[i, U["ts"]] = rec.timestamp
        u64[i, U["amt_hi"]], u64[i, U["amt_lo"]] = _split(rec.amount)
        u64[i, U["areq_hi"]], u64[i, U["areq_lo"]] = _split(
            rec.amount_requested)
        w32["tflags"][i] = (0xFFFFFFFF if rec.transfer_flags is None
                            else rec.transfer_flags)
        w32["pstat"][i] = int(rec.transfer_pending_status)
        w32["p_row"][i] = (
            xfer_row.get(rec.transfer_pending.id, -1)
            if rec.transfer_pending is not None else -1)
        for side, a in (("dr", rec.dr_account), ("cr", rec.cr_account)):
            w32[f"{side}_row"][i] = acct_row.get(a.id, a_dump)
            w32[f"{side}_flags"][i] = a.flags
            for f, val in (("dp", a.debits_pending),
                           ("dpos", a.debits_posted),
                           ("cp", a.credits_pending),
                           ("cpos", a.credits_posted)):
                (u64[i, U[f"{side}_{f}_hi"]],
                 u64[i, U[f"{side}_{f}_lo"]]) = _split(val)
    for name, vals in w32.items():
        _set32(u64, EV_P32_POS, name, vals)
    return {"u32": narrow(u64)}


class MirrorDivergence(AssertionError):
    """VERIFY spot-check failure: a device-resident row disagrees with
    the host mirror. Subclasses AssertionError (existing fail-loudly
    consumers keep working); the serving supervisor catches it
    specifically and routes to bounded replay recovery."""


def _scatter_cols(table, rows, cols):
    """Jitted fused row-scatter: one dispatch per push instead of one per
    column (the mirror regime's hot edge)."""
    out = dict(table)
    for k, v in cols.items():
        out[k] = out[k].at[rows].set(v)
    return out


_scatter_cols_jit = None


def scatter_cols(table, rows, cols):
    global _scatter_cols_jit
    if _scatter_cols_jit is None:
        import jax

        _scatter_cols_jit = jax.jit(_scatter_cols, donate_argnums=0)
    return _scatter_cols_jit(table, rows, cols)


def _split(x: int):
    return np.uint64(x >> 64), np.uint64(x & 0xFFFFFFFFFFFFFFFF)


def _limbs4(value: int):
    return [np.uint64((value >> (32 * j)) & 0xFFFFFFFF) for j in range(4)]


def _balance_int(acc, field, row) -> int:
    bal_row = acc["bal"][row]
    return sum(int(bal_row[bal_col(field, j)]) << (32 * j)
               for j in range(4))


def init_state(a_cap: int = 1 << 17, t_cap: int = 1 << 21,
               orphan_cap: int | None = None,
               e_cap: int | None = None) -> dict:
    """Fresh device ledger state pytree (host numpy; moved to device lazily
    by the first jitted call)."""
    import jax.numpy as jnp

    if e_cap is None:
        e_cap = t_cap  # one history row per created transfer (+ expiries)

    def rows_accounts():
        # Two u32 matrices of interleaved halves (see ev_layout): row
        # appends/gathers are two ops (meta + balances).
        return dict(
            u32=jnp.zeros((a_cap + 1, 2 * AC_NCOLS), jnp.uint32),
            # Packed balances: the u64 view is (rows, 16) — see
            # ev_layout.BAL_FIELDS.
            bal=jnp.zeros((a_cap + 1, 2 * 16), jnp.uint32),
            count=jnp.int32(0),
        )

    def rows_transfers():
        # One u32 matrix of interleaved halves (see ev_layout): row
        # appends and row-set gathers are ONE op each, in place.
        return dict(
            u32=jnp.zeros((t_cap + 1, 2 * XF_NCOLS), jnp.uint32),
            count=jnp.int32(0),
        )

    def rows_events():
        # The account_events history ring (reference: the account_events
        # groove, src/state_machine.zig:104-220): per created transfer,
        # POST-application u128 balance snapshots of both touched accounts,
        # computed exactly in-kernel via segmented prefix sums. One
        # u32 matrix of interleaved halves (see ev_layout) so an append
        # is ONE row scatter.
        u64 = np.zeros((e_cap + 1, EV_NCOLS), dtype=np.uint64)
        _set32(u64, EV_P32_POS, "p_row",
               np.full(e_cap + 1, -1, dtype=np.int64))
        _set32(u64, EV_P32_POS, "tflags",
               np.full(e_cap + 1, 0xFFFFFFFF, dtype=np.int64))
        return dict(
            u32=jnp.asarray(narrow(u64)),
            count=jnp.int32(0),
        )

    if orphan_cap is None:
        # Orphaned (transient-failure) ids are never evicted; keep the table
        # load low enough that bucket overflow stays improbable even for
        # failure-heavy workloads.
        orphan_cap = max(1 << 16, t_cap)
    # Orphans live INLINE in the transfer table (val = ORPHAN_VAL; the id
    # sets are disjoint forever), so one probe serves exists +
    # already-failed and one plan serves both insert kinds. Size for both
    # populations at <= 50% load.
    xfer_cap = 1 << (2 * t_cap + 2 * orphan_cap - 1).bit_length()
    return dict(
        accounts=rows_accounts(),
        transfers=rows_transfers(),
        events=rows_events(),
        acct_ht=ht_init(2 * a_cap),
        xfer_ht=ht_init(xfer_cap),
        acct_key_max=np.uint64(0),
        xfer_key_max=np.uint64(0),
        pulse_next=np.uint64(1),
        commit_ts=np.uint64(0),
    )


def _delta_gather_body(state, t_start, e_start, size_t, size_e):
    """Shared device-side delta gather: fixed-size slices of the
    appended transfer/event rows + derived gathers. Start indices may be
    host ints (sync fetch) or device scalars (pipelined windows)."""
    import jax.numpy as jnp
    from jax import lax

    xfr = state["transfers"]
    acc = state["accounts"]
    evr = state["events"]
    t = {k: lax.dynamic_slice_in_dim(v, t_start, size_t)
         for k, v in xfr.items() if k != "count"}
    e = {k: lax.dynamic_slice_in_dim(v, e_start, size_e)
         for k, v in evr.items() if k != "count"}
    dr_row = ev_col(e, "dr_row")
    cr_row = ev_col(e, "cr_row")
    p_rows = jnp.maximum(ev_col(e, "p_row"), 0)
    # Touched-account ids: ONE row gather over the concatenated row
    # set, the two leading id columns taken from the gathered rows (the
    # column positions are static layout facts, asserted so a reorder
    # cannot silently take the wrong pair).
    assert (AC_U64_IDX["id_hi"], AC_U64_IDX["id_lo"]) == (0, 1)
    ids2 = widen(
        acc["u32"][jnp.concatenate([dr_row, cr_row])][:, :4])
    n_e = dr_row.shape[0]
    return dict(
        t=t, e=e,
        dr_id_hi=ids2[:n_e, 0], dr_id_lo=ids2[:n_e, 1],
        cr_id_hi=ids2[n_e:, 0], cr_id_lo=ids2[n_e:, 1],
        # The pending rows' timestamps: gather the ROWS and take the
        # column from them (a whole-column slice first would be a pass
        # over the store every fetch).
        p_ts=xf_col({"u32": xfr["u32"][p_rows]}, "ts"),
    )


def _xfer_delta_gather(state, t_start, e_start, size_t, size_e):
    return _delta_gather_body(state, t_start, e_start, size_t, size_e)


_DER_KEYS = ("dr_id_hi", "dr_id_lo", "cr_id_hi", "cr_id_lo", "p_ts")


class _DeltaFetchHandle:
    """One in-flight device-side delta gather. Construction starts an
    async device->host copy where the backend supports it; `slice_cols`
    blocks (device_get, memoized) and returns exact-size host copies so
    the padded bucket buffer is never pinned by long-lived chunks."""

    __slots__ = ("_dev", "_host", "t0", "_t_off", "_e_off")

    def __init__(self, dev_out, t0, t_off, e_off, eager_copy=True):
        self._dev = dev_out
        self._host = None
        self.t0 = t0
        self._t_off = t_off
        self._e_off = e_off
        # eager_copy=False (pipelined serving): do NOT start the host
        # copy now — the transfer would contend with the next in-flight
        # window's operand transfers. The bytes move at drain/flush
        # instead, wholly off the commit boundary.
        if eager_copy:
            try:
                import jax

                for leaf in jax.tree_util.tree_leaves(dev_out):
                    leaf.copy_to_host_async()
            except Exception:
                pass  # backend without async copy: resolve() pays the wait

    def start_copy(self) -> None:
        """Begin the device->host transfer without blocking (idempotent;
        no-op once resolved). The drain calls this for EVERY queued
        handle up front so the transfers stream while the host
        registers earlier chunks."""
        if self._host is None and self._dev is not None:
            try:
                import jax

                for leaf in jax.tree_util.tree_leaves(self._dev):
                    leaf.copy_to_host_async()
            except Exception:
                pass

    def _resolve(self):
        host = self._host
        if host is None:
            import jax

            host = self._host = jax.device_get(self._dev)
            self._dev = None
        return host

    def slice_cols(self, which: str, rel: int, n: int) -> dict:
        out = self._resolve()
        if which == "t":
            o = self._t_off + rel
            return xf_named({k: v[o:o + n].copy()
                             for k, v in out["t"].items()})
        o = self._e_off + rel
        if which == "e":
            return ev_named({k: v[o:o + n].copy()
                             for k, v in out["e"].items()})
        assert which == "der"
        return {k: out[k][o:o + n].copy() for k in _DER_KEYS}


class _ColsView:
    """Lazily-loaded named-column mapping. Subclasses implement _load();
    this base supplies the ONE mapping surface the drain, the lazy
    mirror, and the durable column flusher consume — add new consumer
    methods here so every window type (device-fetched and
    host-synthesized) gets them together."""

    __slots__ = ("_d",)

    def _load(self) -> dict:
        raise NotImplementedError

    def load(self) -> dict:
        d = self._d
        if d is None:
            d = self._d = self._load()
        return d

    @property
    def loaded(self) -> bool:
        return self._d is not None

    def __getitem__(self, key):
        return self.load()[key]

    def __contains__(self, key):
        return key in self.load()

    def keys(self):
        return self.load().keys()

    def values(self):
        return self.load().values()

    def items(self):
        return self.load().items()

    def __iter__(self):
        return iter(self.load())

    def __len__(self):
        return len(self.load())


class _LazyCols(_ColsView):
    """Columns over a _DeltaFetchHandle slice (device-fetched)."""

    __slots__ = ("_handle", "_which", "_rel", "_n")

    def __init__(self, handle, which, rel, n):
        self._handle = handle
        self._which = which
        self._rel = rel
        self._n = n
        self._d = None

    def _load(self) -> dict:
        d = self._handle.slice_cols(self._which, self._rel, self._n)
        self._handle = None
        return d


def _ev_delta_gather_window(state, created, size_e):
    """Half-width window delta gather: ONLY the event-ring slice (the
    per-event balance snapshots — genuinely device-computed). For a
    pv-free serving window the transfer rows and touched-account ids
    are a pure function of the window's INPUT events + statuses + host-
    assigned timestamps, so they are re-synthesized on host
    (_synth_t_cols/_synth_der_cols) instead of crossing the link —
    roughly half the drain bytes of the full gather. Start is computed
    ON DEVICE (count - created) so pipelined callers never sync; the
    slice body is shared with the host-start variant."""
    import jax.numpy as jnp

    evr = state["events"]
    e_len = ev_cap(evr) + 1
    e_start = jnp.clip(evr["count"] - created, 0, e_len - size_e)
    return _ev_delta_gather_host(state, e_start, size_e)


_ev_delta_gather_window_jit_cache = None


def _ev_delta_gather_window_jit(state, created, size_e):
    global _ev_delta_gather_window_jit_cache
    if _ev_delta_gather_window_jit_cache is None:
        import jax

        _ev_delta_gather_window_jit_cache = jax.jit(
            _ev_delta_gather_window, static_argnums=(2,))
    return _ev_delta_gather_window_jit_cache(state, created, size_e)


def _ev_delta_gather_host(state, e_start, size_e):
    """Host-start variant of the event-only gather (the sync capture
    path knows its slice start as a host int)."""
    from jax import lax

    evr = state["events"]
    e = {k: lax.dynamic_slice_in_dim(v, e_start, size_e)
         for k, v in evr.items() if k != "count"}
    return dict(e=e)


_ev_delta_gather_host_jit_cache = None


def _ev_delta_gather_host_jit(state, e_start, size_e):
    global _ev_delta_gather_host_jit_cache
    if _ev_delta_gather_host_jit_cache is None:
        import jax

        _ev_delta_gather_host_jit_cache = jax.jit(
            _ev_delta_gather_host, static_argnums=(2,))
    return _ev_delta_gather_host_jit_cache(state, e_start, size_e)


_F_PENDING_HOST = None
_F_PV_HOST = None


def _pending_flag() -> int:
    global _F_PENDING_HOST
    if _F_PENDING_HOST is None:
        from ..types import TransferFlags

        _F_PENDING_HOST = int(TransferFlags.pending)
    return _F_PENDING_HOST


def _F_POST_VOID_HOST() -> int:
    global _F_PV_HOST
    if _F_PV_HOST is None:
        from ..types import TransferFlags

        _F_PV_HOST = int(TransferFlags.post_pending_transfer
                         | TransferFlags.void_pending_transfer)
    return _F_PV_HOST


_F_IMP_HOST = None


def _F_IMPORTED_HOST() -> int:
    global _F_IMP_HOST
    if _F_IMP_HOST is None:
        from ..types import TransferFlags

        _F_IMP_HOST = int(TransferFlags.imported)
    return _F_IMP_HOST


def _has_imported(evs) -> bool:
    bit = np.uint32(_F_IMPORTED_HOST())
    return any((np.asarray(e["flags"]) & bit).any() for e in evs)


_F_BAL_HOST_BITS = None


def _F_BALANCING_HOST() -> int:
    global _F_BAL_HOST_BITS
    if _F_BAL_HOST_BITS is None:
        from ..types import TransferFlags

        _F_BAL_HOST_BITS = int(TransferFlags.balancing_debit
                               | TransferFlags.balancing_credit)
    return _F_BAL_HOST_BITS


def _has_balancing(evs) -> bool:
    bit = np.uint32(_F_BALANCING_HOST())
    return any((np.asarray(e["flags"]) & bit).any() for e in evs)


_F_A_IMP_HOST = None


def _F_A_IMPORTED_HOST() -> int:
    global _F_A_IMP_HOST
    if _F_A_IMP_HOST is None:
        from ..types import AccountFlags

        _F_A_IMP_HOST = int(AccountFlags.imported)
    return _F_A_IMP_HOST


def _synth_t_cols(ev: dict, st_np, ts_b: int) -> dict:
    """Reconstruct the created transfer rows' xf_named columns from the
    batch INPUT (pv-free batches only: amounts are literal, nothing
    inherits from a pending). Must agree bit-for-bit with the device
    row writer (fast_kernels application stage; expires formula
    fast_kernels.py `ap_pending & timeout != 0` -> f_ts + timeout_ns)."""
    from ..constants import NS_PER_S
    from ..types import CreateTransferStatus, TransferPendingStatus

    created_code = np.uint32(int(CreateTransferStatus.created))
    n_b = len(st_np)
    idx = np.nonzero(np.asarray(st_np) == created_code)[0]

    def col(name):
        return np.asarray(ev[name])[idx]

    ts_event = (np.uint64(ts_b) - np.uint64(n_b)
                + idx.astype(np.uint64) + np.uint64(1))
    flags = col("flags")
    pending = (flags & np.uint32(_pending_flag())) != 0
    timeout = col("timeout")
    expires = np.where(
        pending & (timeout != 0),
        ts_event + timeout.astype(np.uint64) * np.uint64(NS_PER_S),
        np.uint64(0))
    cols = {n: col(n) for n in
            ("id_hi", "id_lo", "dr_hi", "dr_lo", "cr_hi", "cr_lo",
             "amt_hi", "amt_lo", "pid_hi", "pid_lo", "ud128_hi",
             "ud128_lo", "ud64", "ud32", "timeout", "ledger", "code",
             "flags")}
    cols["ts"] = ts_event
    cols["expires"] = expires
    cols["pstat"] = np.where(
        pending, np.int32(int(TransferPendingStatus.pending)),
        np.int32(int(TransferPendingStatus.none)))
    zrow = np.zeros(len(idx), np.int32)  # device-internal row indices
    cols["dr_row"] = zrow
    cols["cr_row"] = zrow
    return cols


def _synth_der_cols(ev: dict, st_np) -> dict:
    """Derived columns for a pv-free batch: the touched-account ids ARE
    the input's debit/credit ids; p_ts is unused (no posts/voids)."""
    from ..types import CreateTransferStatus

    created_code = np.uint32(int(CreateTransferStatus.created))
    idx = np.nonzero(np.asarray(st_np) == created_code)[0]
    return {
        "dr_id_hi": np.asarray(ev["dr_hi"])[idx],
        "dr_id_lo": np.asarray(ev["dr_lo"])[idx],
        "cr_id_hi": np.asarray(ev["cr_hi"])[idx],
        "cr_id_lo": np.asarray(ev["cr_lo"])[idx],
        "p_ts": np.zeros(len(idx), np.uint64),
    }


class _SynthCols(_ColsView):
    """Host-synthesized named columns — same surface as _LazyCols with
    no device buffer behind it (see _ColsView)."""

    __slots__ = ("_builder", "_args")

    def __init__(self, builder, *args):
        self._builder = builder
        self._args = args
        self._d = None

    def _load(self) -> dict:
        d = self._builder(*self._args)
        self._builder = self._args = None
        return d


def _xfer_delta_gather_window(state, created, size_t, size_e):
    """Window-pipeline variant of the delta gather: slice starts are
    computed ON DEVICE from the post-window counts (count - created), so
    a pipelined caller can issue this gather without ever syncing on the
    window's results. The start formula mirrors _delta_fetch_start's
    host clamps exactly; the resolver recomputes the same offsets from
    host counters at resolve time."""
    import jax.numpy as jnp

    xfr = state["transfers"]
    evr = state["events"]
    t_len = xfr["u32"].shape[0]
    e_len = ev_cap(evr) + 1
    t_start = jnp.clip(xfr["count"] - created, 0, t_len - size_t)
    e_start = jnp.clip(evr["count"] - created, 0, e_len - size_e)
    return _delta_gather_body(state, t_start, e_start, size_t, size_e)


_xfer_delta_gather_window_jit_cache = None


def _xfer_delta_gather_window_jit(state, created, size_t, size_e):
    global _xfer_delta_gather_window_jit_cache
    if _xfer_delta_gather_window_jit_cache is None:
        import jax

        _xfer_delta_gather_window_jit_cache = jax.jit(
            _xfer_delta_gather_window, static_argnums=(2, 3))
    return _xfer_delta_gather_window_jit_cache(state, created,
                                               size_t, size_e)


def _acct_delta_gather(state, a_start, size):
    from jax import lax

    acc = state["accounts"]
    return {k: lax.dynamic_slice_in_dim(v, a_start, size)
            for k, v in acc.items() if k != "count"}


_xfer_delta_gather_jit_cache = None
_acct_delta_gather_jit_cache = None


def _xfer_delta_gather_jit(state, t_start, e_start, size_t, size_e):
    global _xfer_delta_gather_jit_cache
    if _xfer_delta_gather_jit_cache is None:
        import jax

        _xfer_delta_gather_jit_cache = jax.jit(
            _xfer_delta_gather, static_argnums=(3, 4))
    return _xfer_delta_gather_jit_cache(state, t_start, e_start,
                                        size_t, size_e)


def _acct_delta_gather_jit(state, a_start, size):
    global _acct_delta_gather_jit_cache
    if _acct_delta_gather_jit_cache is None:
        import jax

        _acct_delta_gather_jit_cache = jax.jit(
            _acct_delta_gather, static_argnums=2)
    return _acct_delta_gather_jit_cache(state, a_start, size)


def pad_transfer_events(ev: dict, n_pad: int = N_PAD) -> dict:
    """Pad a transfers_to_arrays SoA dict to the kernel's static shape."""
    n = len(ev["id_lo"])
    assert n <= n_pad
    out = {}
    for k, v in ev.items():
        arr = np.zeros(n_pad, dtype=v.dtype)
        arr[:n] = v
        out[k] = arr
    valid = np.zeros(n_pad, dtype=bool)
    valid[:n] = True
    out["valid"] = valid
    return out


def pad_account_events(ev: dict, n_pad: int = N_PAD) -> dict:
    return pad_transfer_events(ev, n_pad)


def stack_superbatch(evs: list[dict], timestamps: list[int],
                     n_pad: int = N_PAD):
    """Concatenate K prepares into one kernel superbatch (host side).

    Each ev is an UNPADDED transfers_to_arrays SoA dict; sub-batch b is
    padded to n_pad and assigned commit timestamps
    `timestamps[b] - n_b + i + 1` (reference execute_create :3031 —
    per-prepare timestamp bases must be monotone across the window, which
    the replica's prepare timestamping guarantees). Returns (ev_super,
    seg) ready for create_transfers_super_jit: one dispatch executes the
    whole window, so the fixed dispatch cost is paid once per window."""
    assert len(evs) == len(timestamps) and evs
    padded = [pad_transfer_events(e, n_pad) for e in evs]
    ev_super = {k: np.concatenate([p[k] for p in padded])
                for k in padded[0]}
    K = len(padded)
    local = np.arange(n_pad, dtype=np.int64)
    ts_parts, term_parts = [], []
    for e, ts in zip(evs, timestamps):
        n_b = len(e["id_lo"])
        ts_parts.append((np.uint64(ts) - np.uint64(n_b)
                         + local.astype(np.uint64) + np.uint64(1)))
        term_parts.append(local == n_b - 1)
    seg_start = np.zeros(K * n_pad, dtype=bool)
    seg_start[::n_pad] = True
    seg = dict(ts_event=np.concatenate(ts_parts),
               seg_start=seg_start,
               chain_term=np.concatenate(term_parts))
    return ev_super, seg


def stack_chain_window(evs: list[dict], timestamps: list[int],
                       n_pad: int = N_PAD):
    """K prepares -> (K, n_pad)-stacked inputs for the scan-form chain
    kernel (create_transfers_chain_jit): scan element k is prepare k
    padded to n_pad with single-prepare seg lanes. Unlike
    stack_superbatch (one flat kernel over the whole window, whose op
    mass and eligibility are window-wide), the chain executes one
    kernel BODY per prepare with the donated state threaded through the
    scan carry — cross-prepare effects (ids created earlier in the
    window, pendings posted later) resolve through the evolving state
    instead of window-wide proofs, and K is arbitrary (no power-of-two
    constraint)."""
    assert len(evs) == len(timestamps) and evs
    padded = [pad_transfer_events(e, n_pad) for e in evs]
    ev_stack = {k: np.stack([p[k] for p in padded]) for k in padded[0]}
    local = np.arange(n_pad, dtype=np.int64)
    ts_rows, term_rows = [], []
    for e, ts in zip(evs, timestamps):
        n_b = len(e["id_lo"])
        ts_rows.append(np.uint64(ts) - np.uint64(n_b)
                       + local.astype(np.uint64) + np.uint64(1))
        term_rows.append(local == n_b - 1)
    seg_start = np.zeros((len(evs), n_pad), dtype=bool)
    seg_start[:, 0] = True
    seg_stack = dict(ts_event=np.stack(ts_rows), seg_start=seg_start,
                     chain_term=np.stack(term_rows))
    return ev_stack, seg_stack


class WindowTicket:
    """One pipelined commit window in flight: the kernel + delta gather
    are dispatched, nothing is synced. Resolution (in submission order)
    recovers exactly the synchronous path's results, capture chunks, and
    counters — or, on a fallback anywhere in the pipeline, replays the
    poisoned suffix synchronously (chained force_fallback guarantees
    poisoned windows left the device state untouched)."""

    __slots__ = ("evs", "tss", "ns", "n_pad", "out", "gather_dev",
                 "size", "deep", "all_or_nothing", "e_only", "results",
                 "route", "poison", "harvested")

    def __init__(self, evs, tss, ns, n_pad, out, gather_dev, size, deep,
                 all_or_nothing, e_only=False, route="super",
                 poison=None):
        self.evs = evs
        self.tss = tss
        self.ns = ns
        self.n_pad = n_pad
        self.out = out
        self.gather_dev = gather_dev
        self.size = size
        self.deep = deep
        self.all_or_nothing = all_or_nothing
        # Half-width capture: only the event-ring slice was gathered;
        # transfer/der columns synthesize on host from the inputs.
        self.e_only = e_only
        # Dispatch route ("chain" = the default scan-form whole-window
        # route, per-prepare outputs; "super*" = one flat superbatch
        # kernel, window-wide outputs) and the device scalar the NEXT
        # in-flight window chains as force_fallback (for a chain ticket
        # that is the LAST iteration's fallback — poisoning composes
        # transitively, so it equals "any iteration fell back").
        self.route = route
        self.poison = poison
        self.results = None  # set at resolve
        self.harvested = False

    def start_harvest(self) -> None:
        """Start non-blocking d2h copies of the kernel's ticket outputs
        (statuses, timestamps, fallback lanes, cause flags) so
        resolve_windows()' device_get finds the bytes already on host
        instead of paying a synchronous round-trip per window.
        Idempotent; fired when the NEXT window is submitted (this
        ticket's kernel is ordered before it on device, so the copy
        drains behind the in-flight dispatch) and again defensively at
        resolve. The delta-gather buffers are deliberately NOT
        harvested here: their d2h tonnage would contend with the next
        kernel's operand transfers (see _DeltaFetchHandle
        eager_copy=False) — they stay lazy until the mirror drain."""
        if self.harvested:
            return
        self.harvested = True
        import jax

        for leaf in jax.tree.leaves(self.out):
            start = getattr(leaf, "copy_to_host_async", None)
            if start is not None:
                start()


def _evs_pend_refs(evs: list[dict]) -> bool:
    """Host-side pre-route: does any pid in the window match any id in
    it? (numpy key-merge over the UNPADDED prepares; u128 keys as
    (hi, lo) rows). True routes the window to the deep superbatch tier —
    its dependency fixpoint resolves in-window pending references the
    plain chain body cannot."""
    pid_hi = np.concatenate([np.asarray(e["pid_hi"]) for e in evs])
    pid_lo = np.concatenate([np.asarray(e["pid_lo"]) for e in evs])
    nz = (pid_hi != 0) | (pid_lo != 0)
    if not nz.any():
        return False
    ids = np.stack(
        [np.concatenate([np.asarray(e["id_hi"]) for e in evs]),
         np.concatenate([np.asarray(e["id_lo"]) for e in evs])], axis=1)
    pids = np.stack([pid_hi[nz], pid_lo[nz]], axis=1)
    cat = np.concatenate([np.unique(ids, axis=0), np.unique(pids, axis=0)])
    _, counts = np.unique(cat, axis=0, return_counts=True)
    return bool((counts > 1).any())


_F_CLOSE_HOST_BITS = None


def _F_CLOSING_HOST() -> int:
    global _F_CLOSE_HOST_BITS
    if _F_CLOSE_HOST_BITS is None:
        from ..types import TransferFlags

        _F_CLOSE_HOST_BITS = int(TransferFlags.closing_debit
                                 | TransferFlags.closing_credit)
    return _F_CLOSE_HOST_BITS


def _has_closing(evs) -> bool:
    bit = np.uint32(_F_CLOSING_HOST())
    return any((np.asarray(e["flags"]) & bit).any() for e in evs)


def _request_kind(ev: dict) -> str:
    """A create_transfers request's kind, from its flags column: `mixed`
    where it holds pendings and posts or voids, else `pending`,
    `post_void` or `single` (neither)."""
    flags = np.asarray(ev["flags"])
    pending = bool((flags & np.uint32(_pending_flag())).any())
    post_void = bool((flags & np.uint32(_F_POST_VOID_HOST())).any())
    if pending and post_void:
        return "mixed"
    return "pending" if pending else "post_void" if post_void else "single"


def default_recovery_stats() -> dict:
    """The zero-valued recovery-counter record every ledger carries (the
    serving supervisor swaps in its live dict; see fallback_stats)."""
    return {"retries": 0, "backoff_s": 0.0, "replayed_windows": 0,
            "epochs_verified": 0, "checksum_mismatches": 0,
            "recoveries": {}}


class DeviceLedger:
    """Stateful wrapper: owns the device pytree + fallback orchestration."""

    # After this many consecutive batches in the host-mirror regime, drop
    # the mirror and probe the device fast path again (hysteresis).
    MIRROR_PROBE_INTERVAL = 8
    # After an 8->32-round balancing escalation, dispatch the deep
    # balancing tier directly for this many batches before re-probing
    # the shallow one.
    DEEP_PROBE_INTERVAL = 8

    def __init__(self, a_cap: int = 1 << 17, t_cap: int = 1 << 21,
                 write_through=None):
        self.a_cap = a_cap
        self.t_cap = t_cap
        self.state = init_state(a_cap, t_cap)
        self._events_pushed = 0  # device event-ring cursor
        # Absolute count of mirror events already materialized on device
        # (diverges from the ring cursor when the ring recycles or the
        # mirror prunes its flushed prefix).
        self._events_seen_abs = 0
        # Replica serving mode (set via StateMachine.attach_durable):
        # consumed event-ring rows are recycled after every batch — the
        # ring is delta-transport, not history (the forest keeps history).
        self.recycle_events = False
        self.fallbacks = 0
        self.fast_batches = 0
        self.fixpoint_batches = 0
        self.deep_fixpoint_batches = 0
        self.window_fallbacks = 0
        # On-device tier redispatches (plain->fixpoint, shallow->deep,
        # imported->imported-fixpoint): resolved WITHOUT the host.
        self.escalations = 0
        # Per-cause host-fallback counters (kernel fb_causes flags,
        # accumulated at every final-fallback decision): the measured
        # "why did we leave the device" record surfaced through
        # fallback_stats() and `start`'s shutdown record.
        self.fallback_causes: dict = {}
        # Per-batch create requests by kind (`_request_kind`: accounts,
        # single, pending, post_void, mixed), each with who answered
        # them under `by_tier`: the jitted entry of the dispatch that
        # judged it (`create_transfers_fast`,
        # `create_transfers_fixpoint`, `create_transfers_balancing`,
        # `create_accounts_fast`, ...: the `tier` of its last
        # execute_dispatch span), or the host mirror by its route
        # (`host_mirror`, `host_fallback`: execute_host_judge's
        # `route`); the requests whose proof-gated dispatch fell back
        # `limit_only` and were re-dispatched to a fixpoint tier
        # (`escalated`); and the converging limit tier's dispatches and
        # rounds (its `fix_rounds`, fetched with its flags). `judged`
        # and `fixpoint_rounds` sum it over kinds (`start`'s shutdown
        # record prints all three).
        self.judged_kinds: dict = {}
        self._last_tier: str | None = None
        self._fixpoint_rounds_max = 0
        # Dispatch-route observability: per-route window counts
        # ("chain" is the default scan-form whole-window route) and the
        # per-cause counts of prepares that fell OUT of the chain route
        # (its per-prepare fallback granularity). Surfaced through
        # fallback_stats()["routes"]; the serving supervisor mirrors
        # last_window_route into the trace catalog (dispatch_route).
        self.window_routes: dict = {}
        self.chain_batch_fallbacks: dict = {}
        self.last_window_route: str | None = None
        self.last_window_tier: str | None = None
        # Monotone per-batch op sequence: every captured write-through
        # chunk carries the op number it belongs to, so a VERIFY spot
        # divergence can name which batch produced the bad rows.
        self._op_seq = 0
        # Recovery counters (serving.py's ServingSupervisor replaces
        # this dict with its live one when it adopts the ledger): zeros
        # here so fallback_stats() always carries the recovery record —
        # "no recoveries" is a measured number in every bench run.
        self.recovery_stats: dict = default_recovery_stats()
        self._bal_deep_first = 0
        # Adaptive kernel routing: after a batch resolves breaches via the
        # limit fixpoint, later batches dispatch the fixpoint kernel first
        # (skipping the headroom-proof attempt that would fail anyway)
        # until a breach-free batch cools the workload back down.
        self._fixpoint_first = False
        # Deferred write-through: fast batches queue their device deltas
        # as columnar chunks; drain_mirror materializes them into the host
        # mirror's object stores at the next mirror read.
        self._mirror_chunks: list = []
        # Drained transfer columns retained for the durable flusher's
        # vectorized path (attach_durable turns this on; the flusher pops
        # them every commit, so retention is bounded by one bar).
        self.retain_flush_columns = False
        self._flush_columns: list = []
        # The flusher's watermark (attach_durable: a reader of
        # DurableState.events_persisted): the event index up to which the
        # column path has put rows into the trees. The drain registers a
        # chunk under it clean. With no flusher nothing is durable.
        self.events_persisted = lambda: 0
        # Unloaded lazy fetch columns (device buffers still alive); capped
        # so a long drain-free run cannot accumulate unbounded HBM.
        self._pending_cols: list = []
        # Pipelined commit windows in flight (submit_window), resolved in
        # order by resolve_windows().
        self._tickets: list = []
        # Host<->device overlap (double-buffered window staging): a
        # single-slot stage holds the NEXT window's operands, packed and
        # pytree-device_put by a one-worker background stager while the
        # current window's dispatch is in flight. submit_window consumes
        # a matching staged entry instead of packing inline; a stage
        # miss (route flipped between stage and submit, a different
        # window, or no stage call) packs inline — staging is purely an
        # optimization, the packed bytes are identical either way.
        # overlap_staging=False forces the synchronous regime (the
        # overlap gate leg's negative injection).
        self.overlap_staging = True
        self._staged = None
        self._stager = None
        # Cumulative staging accounting (fallback_stats()["staging"]):
        # stall_ms is host-staging time the DISPATCH PATH actually
        # waited on (inline packs + residual waits on a not-yet-done
        # staged pack); work_ms is the total pack+transfer work
        # wherever it ran. host_stall_fraction = stall_ms / work_ms:
        # 1.0 under forced-sync staging, ~0 with the pack fully hidden
        # behind device execution.
        self.staging_stats = {"windows": 0, "staged": 0, "misses": 0,
                              "stall_ms": 0.0, "work_ms": 0.0}
        # Observability hook: the replica's state machine (and the
        # ServingSupervisor) install their tracer here — the spans under
        # commit_execute, window_stage and the host-stall gauge;
        # standalone ledgers keep the null tracer. trace_op is the op
        # the state machine is executing (the spans' `op` tag).
        self.tracer = NullTracer()
        self.trace_op = 0
        # Partitioned-mesh attach (attach_partitioned): when set, commit
        # windows dispatch through the PartitionedRouter's fused
        # shard_map+scan route against the sharded state instead of the
        # single-chip pytree.
        self._part_router = None
        self._part_state = None
        # Device transfer-row count INCLUDING queued chunks (len(_xfer_row)
        # lags it until the next drain).
        self._xfer_rows_dev = 0
        # Host-mirror fallback regime (see _fallback_transfers): a live
        # oracle mirror of the device state, reused across consecutive
        # hard batches so each one costs an oracle apply + a dirty-delta
        # push instead of a full state sync in both directions.
        self.mirror = write_through
        self._mirror_batches = 0
        self._probe_pending = False
        # Write-through mode (the database serving path, reference analog:
        # groove object cache + write-through at commit,
        # src/lsm/groove.zig:885,1770): `write_through` is a host oracle
        # kept in PERMANENT lockstep — fast batches apply a bounded
        # device->host delta to it (_apply_fast_delta_*), hard batches run
        # on it directly and push dirty objects back down. The mirror is
        # never dropped; queries and durability read it while the device
        # remains the execution engine.
        self._wt = write_through is not None
        if self._wt:
            self._enable_dev_tracking(write_through)
            self._hard_regime = False
            self._acct_row: dict[int, int] = {}
            self._xfer_row: dict[int, int] = {}
            if (write_through.accounts or write_through.transfers
                    or write_through.account_events):
                # Attaching a restored state (restart / state sync):
                # rebuild the device tables from it.
                self.from_host(write_through)

    # ------------------------------------------------------------- fast path

    def create_accounts(self, accounts: list[Account], timestamp: int):
        from .batch import accounts_to_arrays
        from .fast_kernels import create_accounts_fast_jit

        self.resolve_windows()  # pipeline ordering
        kind = "accounts"
        if self._mirror_route():
            return self._host_judge(
                "mirror", lambda sm: sm.create_accounts(accounts, timestamp),
                kind)
        with self.tracer.span(Event.execute_stage, op=self.trace_op):
            ev = pad_account_events(accounts_to_arrays(accounts))
        n = len(accounts)
        tier = create_accounts_fast_jit
        if (np.asarray(ev["flags"])
                & np.uint32(_F_A_IMPORTED_HOST())).any():
            from .fast_kernels import create_accounts_imported_jit

            tier = create_accounts_imported_jit
        # On fallback the adopted state is the old one (all selects
        # masked); it was donated, so adopt it before syncing down.
        out, fallback = self._dispatch(tier, ev, timestamp, n, "fallback",
                                       kind=kind)
        if fallback:
            return self._host_judge(
                "fallback",
                lambda sm: sm.create_accounts(accounts, timestamp), kind)
        self.fast_batches += 1
        self._count_judged(self._last_tier, kind)
        self._probe_succeeded()
        st = np.asarray(out["r_status"][:n])
        ts = np.asarray(out["r_ts"][:n])
        if self._wt:
            self._apply_fast_delta_accounts(st)
        ts_l = ts.tolist()
        st_l = st.tolist()
        return [
            CreateAccountResult(timestamp=ts_l[i],
                                status=_CAS_BY_CODE[st_l[i]])
            for i in range(n)
        ]

    def create_transfers(self, transfers: list[Transfer], timestamp: int):
        from .batch import transfers_to_arrays

        ev = transfers_to_arrays(transfers)
        return self.create_transfers_arrays(ev, timestamp, transfers=transfers)

    def create_transfers_soa(self, ev: dict, timestamp: int):
        """The zero-object serving entry: SoA events in, (status u32,
        timestamp u64) arrays out — no per-event Python on the happy path
        (reference: commit is the cheap part, src/state_machine.zig:2564)."""
        out = self.create_transfers_arrays(ev, timestamp, raw=True)
        if isinstance(out, tuple):
            return out
        # The host mirror answered with result objects: flatten.
        with self.tracer.span(Event.execute_encode, op=self.trace_op):
            st = np.fromiter((int(r.status) for r in out), dtype=np.uint32,
                             count=len(out))
            ts = np.fromiter((r.timestamp for r in out), dtype=np.uint64,
                             count=len(out))
        return st, ts

    def _window_plan(self, evs, timestamps):
        """Route-select one candidate pipelined window WITHOUT touching
        device state: the shared eligibility/route logic behind
        stage_window and submit_window, so a staged pack is provably
        the same bytes submit_window would have packed inline. Returns
        (route, n_pad) or None (ineligible — the caller's synchronous
        path takes the window)."""
        ns = [len(e["id_lo"]) for e in evs]
        if self._part_router is not None:
            r = self._part_router
            if (len(evs) < 2 or _has_imported(evs)
                    or any(r.route(e) != "plain" for e in evs)):
                return None
            return "partitioned_chain", _pad_bucket(max(ns))
        if not (len(evs) > 1 and not self._mirror_route()):
            return None
        if _has_imported(evs):
            # Imported windows stay on the synchronous path (the
            # pipelined kernels are not imported-aware; the sync window
            # routes to the imported super tier).
            return None
        if self._wt:
            # Capacity pre-check BEFORE any device mutation: the
            # window's created rows must fit one delta-gather bucket
            # (the sync path splits into groups instead; a pipelined
            # caller just takes that path).
            t_len = int(self.state["transfers"]["u32"].shape[0])
            e_len = ev_cap(self.state["events"]) + 1
            if sum(ns) > min(32 * N_PAD, t_len, e_len):
                return None
        balancing = _has_balancing(evs)
        deep = (not balancing
                and (self._fixpoint_first or _has_closing(evs)
                     or _evs_pend_refs(evs)))
        route = ("super_balancing" if balancing
                 else "super_deep" if deep else "chain")
        return route, _pad_bucket(max(ns))

    def stage_window(self, evs: list[dict],
                     timestamps: list[int]) -> bool:
        """Double-buffered host staging: pack window k+1's stacked
        operands (stack_chain_window / stack_superbatch /
        stack_partitioned_window by route) and start their single
        pytree device transfer on the background stager thread, while
        window k's dispatch is in flight and window k-1 resolves. The
        next submit_window of the SAME window (same prepare dicts, same
        timestamps) consumes the staged operands instead of packing
        inline; anything else — the route flipped under it (breach
        hysteresis), a different window, forced-sync mode — discards
        the stage and packs inline, bit-identically. Never reads or
        writes ledger/device state past route selection, and the
        dispatch itself still happens on submit_window's thread in
        submit order — poison chaining, per-prepare fallback, and the
        clean-prefix commit contract are untouched. Returns True when
        a stage was enqueued."""
        if not self.overlap_staging:
            return False
        plan = self._window_plan(evs, timestamps)
        if plan is None:
            self._staged = None
            return False
        route, n_pad = plan
        if self._stager is None:
            self._stager = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="tb-window-stager")
        fut = self._stager.submit(self._pack_window, route, list(evs),
                                  list(timestamps), n_pad)
        # Strong refs to the prepare dicts keep their identity stable:
        # the stage can only ever be consumed by exactly this window.
        self._staged = (list(evs), [int(t) for t in timestamps],
                        route, n_pad, fut)
        return True

    def _pack_window(self, route, evs, timestamps, n_pad):
        """Stager-thread body: pure host pack + ONE pytree device
        transfer. No ledger state is read or written here (thread
        safety by construction); jax.device_put is thread-safe and the
        transfer overlaps the in-flight dispatch. Returns
        (device payload, pack wall ns)."""
        import jax

        t0 = _time.perf_counter_ns()
        if route == "partitioned_chain":
            payload = self._part_router.stage_operands(
                evs, timestamps, n_pad)
        elif route == "chain":
            payload = jax.device_put(
                stack_chain_window(evs, timestamps, n_pad))
        else:
            payload = jax.device_put(
                stack_superbatch(evs, timestamps, n_pad))
        return payload, _time.perf_counter_ns() - t0

    def _consume_staged(self, evs, timestamps, route, n_pad):
        """Take the staged operands when they are EXACTLY this window
        on this route (prepare-dict identity + timestamps + pad
        bucket); returns the device payload or None (the caller packs
        inline). A hit charges only the residual wait on the stager to
        stall_ms — the pack work itself ran overlapped — and emits the
        `overlapped` window_stage span with that wait as its cost."""
        staged, self._staged = self._staged, None
        if staged is None:
            return None
        s_evs, s_tss, s_route, s_n_pad, fut = staged
        if not (s_route == route and s_n_pad == n_pad
                and len(s_evs) == len(evs)
                and all(a is b for a, b in zip(s_evs, evs))
                and s_tss == [int(t) for t in timestamps]):
            self.staging_stats["misses"] += 1
            fut.cancel()
            return None
        t0 = _time.perf_counter_ns()
        payload, pack_ns = fut.result()
        wait_ns = _time.perf_counter_ns() - t0
        st = self.staging_stats
        st["staged"] += 1
        st["stall_ms"] += wait_ns / 1e6
        st["work_ms"] += max(pack_ns, wait_ns) / 1e6
        self.tracer.record_span(Event.window_stage, t0, wait_ns,
                                mode="overlapped", route=route)
        return payload

    def _staging_note_inline(self, route, t0_ns) -> None:
        """Account one inline (synchronous) pack+transfer: the whole
        cost is a host stall the device pipeline waited on."""
        dur_ns = _time.perf_counter_ns() - t0_ns
        st = self.staging_stats
        st["stall_ms"] += dur_ns / 1e6
        st["work_ms"] += dur_ns / 1e6
        self.tracer.record_span(Event.window_stage, t0_ns, dur_ns,
                                mode="inline", route=route)

    def _staging_gauge(self) -> None:
        st = self.staging_stats
        st["windows"] += 1
        if st["work_ms"]:
            self.tracer.gauge(Event.host_stall_fraction,
                              round(st["stall_ms"] / st["work_ms"], 6))

    def staged_matches(self, evs: list[dict],
                       timestamps: list[int]) -> bool:
        """True when the currently staged pack is EXACTLY this window
        (prepare-dict identity + timestamps, the same test
        _consume_staged applies). The admission plane's stage-ahead
        path asks this before the supervisor would re-stage a window
        the plane already put on the stager — re-staging would replace
        the in-flight pack and turn the overlap into a synchronous
        wait."""
        staged = self._staged
        if staged is None:
            return False
        s_evs, s_tss = staged[0], staged[1]
        return (len(s_evs) == len(evs)
                and all(a is b for a, b in zip(s_evs, evs))
                and s_tss == [int(t) for t in timestamps])

    def staging_summary(self) -> dict:
        """The fallback_stats()["staging"] record: windows through the
        pipelined submit path, how many consumed a staged pack, and the
        measured host-stall split the overlap gate leg reads."""
        st = self.staging_stats
        frac = (st["stall_ms"] / st["work_ms"]) if st["work_ms"] else None
        return {
            "overlap": bool(self.overlap_staging),
            "windows": st["windows"],
            "staged": st["staged"],
            "misses": st["misses"],
            "stall_ms": round(st["stall_ms"], 3),
            "work_ms": round(st["work_ms"], 3),
            "host_stall_fraction": (round(frac, 4)
                                    if frac is not None else None),
        }

    def shutdown_staging(self) -> None:
        """Drop any staged-but-undispatched window and stop the stager
        thread. The supervisor's quarantine path calls this before
        discarding the ledger, so a staged window that never dispatched
        is provably never committed (its device payload dies with the
        stage) and no worker outlives the quarantine."""
        self._staged = None
        if self._stager is not None:
            self._stager.shutdown(wait=True, cancel_futures=True)
            self._stager = None

    def submit_window(self, evs: list[dict], timestamps: list[int]):
        """Pipelined commit window: dispatch the window kernel AND its
        delta gather with ZERO host synchronization, chaining the
        previous in-flight window's fallback scalar as force_fallback —
        a fallback anywhere poisons every later in-flight window on
        device, so commit order survives without waiting (the scan
        driver's poisoning pattern, generalized to serving windows; the
        reference's analog is the 8-deep prepare pipeline,
        src/config.zig:155). Returns a WindowTicket, or None when the
        window is not eligible (caller resolves + takes the sync path).
        Results, write-through capture, and counters materialize at
        resolve_windows(). Pipelined windows are the SERVING path only:
        all-or-nothing replica windows stay on the synchronous
        create_transfers_window (their per-prepare flush attribution
        cannot survive a mid-pipeline redo).

        Dispatch routing (see ARCHITECTURE.md "Dispatch modes"): the
        DEFAULT route is the scan-form whole-window CHAIN kernel — one
        create_transfers_chain_jit dispatch whose body executes each
        prepare against the state evolved by the previous ones (op
        count ~constant in window depth; per-prepare fallback
        granularity). Windows carrying flags the plain chain body
        cannot serve natively pre-route to their specialized flat
        superbatch tier: balancing -> super_balancing, closing /
        in-window pending refs / the breach-hysteresis regime ->
        super_deep; imported windows return None (the sync path's
        super_imported tier takes them)."""
        import jax

        from .fast_kernels import (create_transfers_chain_jit,
                                   create_transfers_chain_ring_jit,
                                   create_transfers_super_deep_jit,
                                   create_transfers_super_deep_ring_jit)

        if self._part_router is not None:
            return self._submit_window_partitioned(evs, timestamps)
        plan = self._window_plan(evs, timestamps)
        if plan is None:
            self._staged = None
            return None
        route, n_pad = plan
        ns = [len(e["id_lo"]) for e in evs]
        prev_fb = self._tickets[-1].poison if self._tickets else None
        if self._tickets:
            # Async harvest of window k-1: its small ticket outputs
            # start their non-blocking d2h copy now, draining behind
            # the dispatch below; resolve_windows() finds them on host.
            self._tickets[-1].start_harvest()
        # Serving mode: the ring-reset kernel variants consume the event
        # ring from offset 0 per window, so the pipeline never needs a
        # host recycle barrier.
        ring = self._wt and self.recycle_events
        deep = route == "super_deep"
        if route == "super_balancing":
            from .fast_kernels import (
                create_transfers_super_balancing_jit,
                create_transfers_super_balancing_ring_jit,
            )

            jitfn = (create_transfers_super_balancing_ring_jit if ring
                     else create_transfers_super_balancing_jit)
        elif deep:
            jitfn = (create_transfers_super_deep_ring_jit if ring
                     else create_transfers_super_deep_jit)
        else:
            jitfn = (create_transfers_chain_ring_jit if ring
                     else create_transfers_chain_jit)
        payload = self._consume_staged(evs, timestamps, route, n_pad)
        if payload is None:
            t0 = _time.perf_counter_ns()
            if route == "chain":
                packed = stack_chain_window(evs, timestamps, n_pad)
            else:
                packed = stack_superbatch(evs, timestamps, n_pad)
            payload = jax.device_put(packed)
            self._staging_note_inline(route, t0)
        ev_d, seg_d = payload
        self._staging_gauge()
        new_state, out = jitfn(self.state, ev_d, seg_d, prev_fb)
        self.state = new_state
        self._count_route(route)
        # Poison scalar for the NEXT in-flight window: the chain's last
        # iteration's fallback (transitive poisoning makes it "any
        # iteration fell back"); the flat tiers' window scalar.
        poison = (out["fallback"][-1] if route == "chain"
                  else out["fallback"])
        gather = None
        size_te = (0, 0)
        e_only = False
        if self._wt:
            # Delta gather with DEVICE-computed slice starts: ordered
            # after the kernel on device, resolved at drain/flush.
            t_len = int(self.state["transfers"]["u32"].shape[0])
            e_len = ev_cap(self.state["events"]) + 1
            total_cap = sum(ns)
            for size in (N_PAD, 8 * N_PAD, 32 * N_PAD):
                if total_cap <= size:
                    break
            size_te = (min(size, t_len), min(size, e_len))
            # Pv-free windows fetch HALF the delta (event snapshots
            # only): the transfer/der columns are host-reconstructible
            # from the inputs — the drain moves ~half the bytes.
            excl = np.uint32(_F_POST_VOID_HOST() | _F_IMPORTED_HOST()
                             | _F_BALANCING_HOST())
            e_only = all(
                not (np.asarray(ev["flags"]) & excl).any()
                for ev in evs)
            # Committed-row count for the device-computed slice start:
            # the chain's per-iteration counts sum ON DEVICE (poisoned
            # iterations contribute 0, so a partial window's gather
            # covers exactly the committed prefix).
            created = (out["created_count"].sum() if route == "chain"
                       else out["created_count"])
            if e_only:
                gather = _ev_delta_gather_window_jit(
                    self.state, created, size_te[1])
            else:
                gather = _xfer_delta_gather_window_jit(
                    self.state, created, *size_te)
        ticket = WindowTicket(evs, timestamps, ns, n_pad, out, gather,
                              size_te, deep, False, e_only=e_only,
                              route=route, poison=poison)
        self._tickets.append(ticket)
        return ticket

    def attach_partitioned(self, router, state) -> None:
        """Serve commit windows from the partitioned mesh: every window
        submitted through submit_window (and every synchronous/redo
        window inside resolve_windows) dispatches through `router`
        (parallel/partitioned.PartitionedRouter) against the sharded
        `state` pytree — the fused shard_map+scan chain route by
        default, the per-batch ladder for flagged windows and replays.

        Attach-mode contract: the partitioned state IS the ledger
        (read it back via `partitioned_state`); the single-chip pytree
        stays at its attach-time snapshot and per-batch entry points
        (create_transfers) keep addressing it. Write-through capture is
        single-chip scope, so attaching a mirrored ledger is refused."""
        assert not self._wt, "attach_partitioned: write-through is " \
            "single-chip scope"
        assert not self._tickets, "attach_partitioned: windows in flight"
        self._part_router = router
        self._part_state = state
        # Let router.resync tear down THIS ledger's staging before it
        # rebuilds sharded state (a pack staged under the old ownership
        # map must never be consumed by identity after a resync).
        router._staging_host = self

    @property
    def partitioned_state(self):
        """The sharded state pytree commits land on in attach mode."""
        return self._part_state

    def _submit_window_partitioned(self, evs, timestamps):
        """submit_window in attach mode: the fused partitioned chain —
        ONE shard_map+lax.scan dispatch for the whole window, zero host
        synchronization, the previous in-flight window's poison scalar
        chained as force_fallback (identical pipelining contract to the
        single-chip chain route). Windows the plain chain body cannot
        serve (depth 1, imported, or any flag-routed prepare) return
        None; in attach mode the caller's synchronous path lands on
        _partitioned_window_sync, which runs the per-batch partitioned
        ladder."""
        r = self._part_router
        plan = self._window_plan(evs, timestamps)
        if plan is None:
            self._staged = None
            return None
        route, n_pad = plan
        ns = [len(e["id_lo"]) for e in evs]
        prev_fb = self._tickets[-1].poison if self._tickets else None
        if self._tickets:
            self._tickets[-1].start_harvest()
        staged = self._consume_staged(evs, timestamps, route, n_pad)
        if staged is None:
            t0 = _time.perf_counter_ns()
            staged = r.stage_operands(evs, timestamps, n_pad)
            self._staging_note_inline(route, t0)
        self._staging_gauge()
        new_state, out = r.chain_dispatch(
            evs=evs, timestamps=timestamps, n_pad=n_pad,
            state=self._part_state, force_fallback=prev_fb,
            staged=staged)
        self._part_state = new_state
        # The router counts the window (stats()["routes"], merged into
        # fallback_stats); the ledger records the latency class.
        self.last_window_route = "partitioned_chain"
        self.last_window_tier = "scan"
        ticket = WindowTicket(evs, timestamps, ns, n_pad, out, None,
                              (0, 0), False, False,
                              route="partitioned_chain",
                              poison=out["fallback"][-1])
        self._tickets.append(ticket)
        return ticket

    def _partitioned_window_sync(self, evs, tss):
        """The synchronous window path in attach mode (sync commits and
        resolve-time redo replays): PartitionedRouter.step_window —
        fused chain when eligible, else the per-batch ladder with
        on-device tier escalation. Returns the per-prepare
        (status, ts) results like create_transfers_window."""
        r = self._part_router
        self._part_state, results = r.step_window(
            self._part_state, evs, tss)
        self.last_window_route = ("partitioned_chain"
                                  if len(evs) >= 2 and all(
                                      r.route(e) == "plain" for e in evs)
                                  else "partitioned_per_batch")
        self.last_window_tier = ("scan" if self.last_window_route
                                 == "partitioned_chain" else "fallback")
        return results

    def resolve_windows(self, count: int | None = None) -> None:
        """Resolve in-flight pipelined windows in submission order —
        all of them, or just the oldest `count` (the pipelined driver
        resolves one window per submission to keep the overlap).
        Success recovers exactly the synchronous path's results and
        write-through chunks.

        Fallback handling is route-dependent. A flat super-tier window
        falls back WHOLE (state untouched): it and EVERY later in-flight
        window (poisoned on device by the chained force_fallback) replay
        through the synchronous window path in order, which escalates
        tiers or goes per-batch exactly as if the pipeline had never
        formed. A CHAIN-route window falls back PER PREPARE: the clean
        prefix committed on device and its results/capture stand; only
        the first ineligible prepare and the poisoned suffix replay —
        plus every later in-flight window, as above. Redo therefore
        always consumes the whole pipeline, even past `count`."""
        if not self._tickets:
            return
        import jax

        if count is None:
            tickets, self._tickets = self._tickets, []
        else:
            tickets = self._tickets[:count]
            del self._tickets[:count]
        # Defensive harvest: tickets younger than the last submit never
        # had a successor to fire their async d2h copy — start it now
        # so the device_gets below overlap across the batch.
        for tk in tickets:
            tk.start_harvest()
        # Attach mode replays through the partitioned ladder (the
        # single-chip pytree is not the ledger there).
        win = (self._partitioned_window_sync
               if self._part_router is not None
               else self.create_transfers_window)
        redo = False
        i = 0
        while i < len(tickets):
            tk = tickets[i]
            i += 1
            if redo:
                tk.results = ("redo", win(tk.evs, tk.tss))
                continue
            if tk.route in ("chain", "partitioned_chain"):
                k, results = self._resolve_chain_prefix(tk)
                if k == len(tk.evs):
                    tk.results = ("ok", results)
                    continue
                # Per-prepare fallback: prepares [0, k) committed on
                # device; prepare k and the poisoned suffix (state
                # untouched) replay through the synchronous window
                # path. Everything still in flight is poisoned too:
                # pull it into this redo sequence so order is
                # preserved (the sync path's own resolve guard must
                # find nothing).
                redo = True
                tickets.extend(self._tickets)
                self._tickets = []
                results.extend(win(tk.evs[k:], tk.tss[k:]))
                tk.results = ("redo", results)
                continue
            if bool(jax.device_get(tk.out["fallback"])):
                redo = True
                self._note_fb(tk.out)
                tickets.extend(self._tickets)
                self._tickets = []
                tk.results = ("redo", win(tk.evs, tk.tss))
                continue
            n_pad = tk.n_pad
            st_all = np.asarray(tk.out["r_status"])
            ts_all = np.asarray(tk.out["r_ts"])
            results = []
            st_slices = []
            for b, n_b in enumerate(tk.ns):
                st = st_all[b * n_pad:b * n_pad + n_b]
                results.append((st, ts_all[b * n_pad:b * n_pad + n_b]))
                st_slices.append(st)
            if self._wt:
                self._register_window_capture(tk, st_slices)
            if tk.deep:
                self.deep_fixpoint_batches += len(tk.evs)
            self.fast_batches += len(tk.evs)
            self._probe_succeeded()
            tk.results = ("ok", results)
        self._maybe_recycle_ring()

    def _resolve_chain_prefix(self, tk) -> tuple:
        """Resolve one chain-route ticket's clean prefix. Returns
        (k, results): k is the first fallen-back prepare index (== the
        window depth when the whole window is clean). Prepares [0, k)
        committed on device inside the one scan dispatch — their
        results and write-through capture are registered here; cause
        counters for the per-prepare fallback at k are accumulated.
        The suffix replay is the CALLER's job (pipeline order: later
        in-flight tickets must join the redo sequence first)."""
        import jax

        fb = np.asarray(jax.device_get(tk.out["fallback"]))
        W = len(tk.evs)
        k = int(np.argmax(fb)) if fb.any() else W
        if tk.route == "partitioned_chain":
            # The router owns the partitioned counters (batches,
            # events_owned, cross-shard traffic, per-cause prepares).
            self._part_router.absorb_chain_prefix(tk.out, k, W)
        st_all = np.asarray(tk.out["r_status"])
        ts_all = np.asarray(tk.out["r_ts"])
        results = []
        st_slices = []
        for b in range(k):
            st = st_all[b, :tk.ns[b]]
            results.append((st, ts_all[b, :tk.ns[b]]))
            st_slices.append(st)
        if self._wt:
            # Registers the prefix chunks; in ring mode this also
            # rewinds the host ring cursor to 0 — matching the device's
            # once-per-chain-dispatch ring reset even when k == 0.
            self._register_window_capture(tk, st_slices)
        if k:
            self.fast_batches += k
            self._probe_succeeded()
        if k < W:
            self.window_fallbacks += 1
            if tk.route != "partitioned_chain":
                # Partitioned causes were absorbed at the router above
                # (merged back through fallback_stats()["routes"]).
                self._note_chain_fb(tk.out, k)
        return k, results

    def _register_window_capture(self, tk, st_slices) -> None:
        """Resolve-time write-through capture for one pipelined window:
        identical chunk semantics to _capture_window_delta, but the
        delta gather was already issued at submit (device-start variant)
        — offsets are recomputed here from the host counters, matching
        the device's start formula exactly."""
        per = [self._batch_delta_stats(ev, st)
               for ev, st in zip(tk.evs, st_slices)]
        total = sum(n for n, _ in per)
        handle = None
        ring = self._wt and self.recycle_events
        if ring:
            # Ring-reset windows consumed the ring from offset 0.
            self._events_pushed = 0
        if total:
            t0 = self._xfer_rows_dev
            e0 = self._events_pushed
            size_t, size_e = tk.size
            t_len = int(self.state["transfers"]["u32"].shape[0])
            e_len = ev_cap(self.state["events"]) + 1
            t_start = max(0, min(t0, t_len - size_t))
            e_start = max(0, min(e0, e_len - size_e))
            handle = _DeltaFetchHandle(tk.gather_dev, t0,
                                       t0 - t_start, e0 - e_start,
                                       eager_copy=False)
        off = 0
        for b, (n_new, orphan_ids) in enumerate(per):
            op_no = self._op_seq
            self._op_seq += 1
            if n_new:
                if tk.e_only:
                    # Host-reconstructed transfer/der columns (the
                    # window carried no post/void — rows are a pure
                    # function of inputs + statuses + timestamps).
                    tc = _SynthCols(_synth_t_cols, tk.evs[b],
                                    st_slices[b], tk.tss[b])
                    derc = _SynthCols(_synth_der_cols, tk.evs[b],
                                      st_slices[b])
                else:
                    tc = _LazyCols(handle, "t", off, n_new)
                    derc = _LazyCols(handle, "der", off, n_new)
                ec = _LazyCols(handle, "e", off, n_new)
                self._track_pending_cols(tc, ec, derc)
                self._queue_chunk((tc, ec, derc), handle.t0 + off, n_new,
                                  orphan_ids, op_no)
                off += n_new
            else:
                self._queue_chunk(_NO_COLS, 0, 0, orphan_ids, op_no,
                                  keep_empty=tk.all_or_nothing)
        self._clear_dirty_dev()

    def create_transfers_window(self, evs: list[dict],
                                timestamps: list[int],
                                all_or_nothing: bool = False):
        """K prepares in ONE device dispatch (commit-window aggregation;
        the group-commit analog of the reference's 8-deep prepare
        pipeline, src/config.zig:155). Returns a list of
        (status u32[n_b], ts u64[n_b]) pairs, one per prepare.

        The DEFAULT dispatch route is the scan-form whole-window CHAIN
        kernel (one create_transfers_chain_jit dispatch; op count
        ~constant in window depth — see ARCHITECTURE.md "Dispatch
        modes"): each prepare executes against the state evolved by the
        previous ones, so cross-prepare ids/duplicates resolve through
        the state and an INELIGIBLE prepare falls back PER PREPARE —
        the clean prefix stays committed, the ineligible prepare
        replays per-batch (exact semantics incl. fixpoint escalation
        and the host-mirror path), and the poisoned remainder
        re-windows. Windows the plain chain body cannot serve natively
        pre-route to their flat superbatch tier (imported / balancing /
        closing / in-window pending refs / breach hysteresis), which
        falls back WHOLE-window with state untouched:
        - all_or_nothing=False: the window executes per-prepare through
          create_transfers_soa right here (exact sequential semantics,
          including fixpoint redispatch and the host-mirror path);
        - all_or_nothing=True (the replica commit loop): ALWAYS the
          flat superbatch route (a chain's partial commit could not be
          undone), and on fallback return None with nothing applied —
          the caller re-commits op by op through its normal path, so
          flush cadence and physical determinism are exactly those of
          a replica that never formed the window. In this mode every
          sub-batch queues exactly one flush chunk (empty ones
          included) so the caller can attribute chunks to prepares."""
        import jax

        from .fast_kernels import (create_transfers_chain_jit,
                                   create_transfers_super_deep_jit,
                                   create_transfers_super_jit)

        self.resolve_windows()  # pipeline ordering
        assert len(evs) == len(timestamps) and evs
        if self._part_router is not None:
            # Attach mode: the partitioned state IS the ledger — the
            # synchronous window path dispatches through the router
            # (fused chain when eligible, else the per-batch ladder),
            # exactly like resolve-time redo replays. The single-chip
            # pytree stays at its attach-time snapshot.
            assert not all_or_nothing, \
                "attach mode: the replica commit loop is single-chip scope"
            return self._partitioned_window_sync(evs, timestamps)
        ns = [len(e["id_lo"]) for e in evs]
        eligible = len(evs) > 1 and not self._mirror_route()
        if eligible:
            n_pad = _pad_bucket(max(ns))
            # Flag pre-route (cheap host scans) + one numpy key-merge:
            # tiers the plain chain body cannot serve natively go to
            # their specialized flat superbatch kernel; the
            # breach-hysteresis regime (the shallow/chain dispatch is a
            # known waste while limit cascades run deep) goes deep too.
            imported = _has_imported(evs)
            balancing = not imported and _has_balancing(evs)
            deep_first = (not imported and not balancing
                          and (self._fixpoint_first
                               or _has_closing(evs)
                               or _evs_pend_refs(evs)))
            chain_route = (not all_or_nothing and not imported
                           and not balancing and not deep_first)
            if chain_route:
                # One pytree put for the whole stacked window (a single
                # host round-trip instead of one per leaf).
                ev_c, seg_c = jax.device_put(
                    stack_chain_window(evs, timestamps, n_pad))
                new_state, out = create_transfers_chain_jit(
                    self.state, ev_c, seg_c)
                self.state = new_state
                self._count_route("chain")
                fb = np.asarray(jax.device_get(out["fallback"]))
                W = len(evs)
                k = int(np.argmax(fb)) if fb.any() else W
                st_all = np.asarray(out["r_status"])
                ts_all = np.asarray(out["r_ts"])
                results = [(st_all[b, :ns[b]], ts_all[b, :ns[b]])
                           for b in range(k)]
                if self._wt and k:
                    self._capture_window_delta(
                        evs[:k], [st for st, _ in results],
                        timestamps=timestamps[:k])
                if k:
                    self.fast_batches += k
                    self._probe_succeeded()
                if k == W:
                    return results
                # Per-prepare fallback: prepare k is ineligible for the
                # plain chain body. Count the cause, replay it
                # per-batch (exact path incl. escalation and the
                # mirror regime), then RE-WINDOW the poisoned
                # remainder — each recursion consumes at least one
                # prepare, so the ladder terminates; it never re-chains
                # the same ineligible prepare at its head twice.
                self.window_fallbacks += 1
                self._note_chain_fb(out, k)
                results.append(
                    self.create_transfers_soa(evs[k], timestamps[k]))
                if k + 1 < W:
                    results.extend(self.create_transfers_window(
                        evs[k + 1:], timestamps[k + 1:]))
                return results
            ev_s, seg = jax.device_put(
                stack_superbatch(evs, timestamps, n_pad))
            if imported:
                from .fast_kernels import (
                    create_transfers_super_imported_jit,
                )

                self._count_route("super_imported")
                new_state, out = create_transfers_super_imported_jit(
                    self.state, ev_s, seg)
                self.state = new_state
            elif balancing:
                # Balancing windows run natively at the deep-window
                # budget (their NORMAL tier — not counted as deep
                # escalations); an unconverged window falls back below
                # to the per-batch balancing ladder (exact semantics).
                from .fast_kernels import (
                    create_transfers_super_balancing_jit,
                )

                self._count_route("super_balancing")
                new_state, out = create_transfers_super_balancing_jit(
                    self.state, ev_s, seg)
                self.state = new_state
            elif deep_first:
                self._count_route("super_deep")
                new_state, out = create_transfers_super_deep_jit(
                    self.state, ev_s, seg)
                self.state = new_state
                self.deep_fixpoint_batches += len(evs)
            else:
                # all_or_nothing replica windows: the flat plain tier
                # (whole-window semantics the commit loop requires).
                self._count_route("super")
                new_state, out = create_transfers_super_jit(
                    self.state, ev_s, seg)
                self.state = new_state
                fb0, lo0 = (bool(x) for x in jax.device_get(
                    (out["fallback"], out["limit_only"])))
                if fb0 and lo0:
                    # Limits and/or in-window pendings were the ONLY
                    # obstacle: resolve on the deep superbatch tier
                    # (state was donated but unchanged on fallback).
                    new_state, out = create_transfers_super_deep_jit(
                        self.state, ev_s, seg)
                    self.state = new_state
                    self.deep_fixpoint_batches += len(evs)
            if not bool(jax.device_get(out["fallback"])):
                self.fast_batches += len(evs)
                self._probe_succeeded()
                st_all = np.asarray(out["r_status"])
                ts_all = np.asarray(out["r_ts"])
                results = []
                for b, n_b in enumerate(ns):
                    results.append(
                        (st_all[b * n_pad:b * n_pad + n_b],
                         ts_all[b * n_pad:b * n_pad + n_b]))
                if self._wt:
                    self._capture_window_delta(
                        evs, [st for st, _ in results],
                        timestamps=timestamps,
                        exact_chunks=all_or_nothing)
                return results
            self.window_fallbacks += 1
            self._note_fb(out)
        if all_or_nothing:
            return None
        self._count_route("per_batch")
        return [self.create_transfers_soa(ev, ts)
                for ev, ts in zip(evs, timestamps)]

    def _escalate_fixpoint(self, evp, timestamp, n, kind, balancing=False):
        """The 8-round balancing or imported fixpoint reported a cascade
        deeper than its budget (and no other obstacle): resolve it on
        device with that tier's 32-round variant before considering the
        host path. Returns (fallback, out) from the deep run; balancing
        enters its deep-first regime (the shallow dispatch is a known
        waste while cascades stay deep), imported has none (imported
        windows are rare enough that re-probing costs nothing). The
        plain limit tier converges in one dispatch and never comes
        here."""
        from .fast_kernels import (
            create_transfers_balancing_deep_jit,
            create_transfers_imported_fixpoint_deep_jit,
        )

        deep = (create_transfers_balancing_deep_jit if balancing
                else create_transfers_imported_fixpoint_deep_jit)
        deep_out, fallback = self._dispatch(deep, evp, timestamp, n,
                                            "fallback", kind=kind)
        self.deep_fixpoint_batches += 1
        self.escalations += 1
        if balancing:
            self._bal_deep_first = self.DEEP_PROBE_INTERVAL
        return fallback, deep_out

    def _dispatch(self, tier_jit, evp, timestamp, n, *flags, kind):
        """One create kernel dispatch of `tier_jit` for a request of
        `kind`: launch it, adopt the state it returns (the old one was
        donated), and block on the scalar `flags` of its output.
        Returns (out, *flag bools). One execute_dispatch span per call
        — launch, device time and the sync — so an escalation shows as
        a second span. The converging limit tier's `fix_rounds` comes
        back in the same fetch: the span's `rounds` tag and the kind's
        `fixpoint_rounds`."""
        import jax

        from .fast_kernels import create_transfers_fixpoint_jit

        self._last_tier = tier_jit.__name__
        converging = tier_jit is create_transfers_fixpoint_jit
        fetch = flags + (("fix_rounds",) if converging else ())
        with self.tracer.span(Event.execute_dispatch, op=self.trace_op,
                              tier=self._last_tier) as dispatched:
            self.state, out = tier_jit(
                self.state, evp, np.uint64(timestamp), np.int32(n))
            got = jax.device_get(tuple(out[f] for f in fetch))
            if converging:
                *got, rounds = got
                rounds = int(rounds)
                dispatched.tags["rounds"] = rounds
                counts = self._kind_counts(kind)
                counts["fixpoint_dispatches"] += 1
                counts["fixpoint_rounds"] += rounds
                self._fixpoint_rounds_max = max(self._fixpoint_rounds_max,
                                                rounds)
        return (out, *(bool(x) for x in got))

    def _escalate_limit_only(self, tier_jit, evp, timestamp, n, kind):
        """The proof-gated dispatch of a request of `kind` fell back
        `limit_only`: dispatch it again to the fixpoint tier `tier_jit`,
        under one execute_escalate span. Returns (out, fallback)."""
        self.escalations += 1
        self._kind_counts(kind)["escalated"] += 1
        with self.tracer.span(Event.execute_escalate, op=self.trace_op):
            return self._dispatch(tier_jit, evp, timestamp, n, "fallback",
                                  kind=kind)

    def warm_kernels(self, n_pad: int = N_PAD,
                     balancing: bool = True) -> None:
        """Compile every transfer-kernel variant (fast / fixpoint /
        imported, plus the balancing tiers unless balancing=False)
        at the given padded shape with an all-invalid batch — no state
        change, no events created. Drivers call this once so a mid-run
        escalation never pays a compile inside a timed region; the
        bench passes balancing=False (its workloads carry no balancing
        flags, and each tier is minutes of compile at large caps)."""
        import jax

        from .batch import transfers_to_arrays
        from .fast_kernels import (
            create_transfers_balancing_deep_jit,
            create_transfers_balancing_jit,
            create_transfers_fast_jit,
            create_transfers_fixpoint_jit,
            create_transfers_imported_jit,
        )

        evp = jax.device_put(
            pad_transfer_events(transfers_to_arrays([]), n_pad))
        variants = [create_transfers_fast_jit,
                    create_transfers_fixpoint_jit,
                    create_transfers_imported_jit]
        if balancing:
            variants += [create_transfers_balancing_jit,
                         create_transfers_balancing_deep_jit]
        for f in variants:
            self.state, out = f(self.state, evp, np.uint64(1), np.int32(0))
            assert not bool(out["fallback"])

    def create_transfers_arrays(self, ev: dict, timestamp: int,
                                transfers=None, raw=False):
        """ev: unpadded SoA dict (the zero-host-cost entry point)."""
        self.resolve_windows()  # pipeline ordering
        from .fast_kernels import (
            create_transfers_fast_jit,
            create_transfers_fixpoint_jit,
        )

        def judge(sm):
            objs = _transfers_from_arrays(ev) if transfers is None \
                else transfers
            return sm.create_transfers(objs, timestamp)

        kind = _request_kind(ev)
        if self._mirror_route():
            return self._host_judge("mirror", judge, kind)
        n = len(ev["id_lo"])
        span, at = self.tracer.span, self.trace_op
        # Small batches compile + run at the smallest padded shape that
        # fits (jit caches one executable per bucket): a 1k-event batch
        # costs 1k-row kernel work, not BATCH_MAX-row work.
        with span(Event.execute_stage, op=at):
            evp = pad_transfer_events(ev, n_pad=_pad_bucket(n))
        if _has_imported([ev]):
            # Imported batches run their own tier (native imported rules
            # + the in-batch maxima chain). Closing flags, voids of
            # closing pendings and potential limit breaches escalate to
            # the imported FIXPOINT tier (uniform closing eligibility);
            # chains and collisions go straight to exact.
            from .fast_kernels import (
                create_transfers_imported_fixpoint_jit,
                create_transfers_imported_jit,
            )

            out, fallback, limit_only = self._dispatch(
                create_transfers_imported_jit, evp, timestamp, n,
                "fallback", "limit_only", kind=kind)
            if fallback and limit_only:
                # Resolvable on device (state was donated but unchanged
                # on fallback — evp is intact).
                out, fallback = self._escalate_limit_only(
                    create_transfers_imported_fixpoint_jit, evp,
                    timestamp, n, kind)
                if fallback and bool(out["fix_unconverged"]):
                    fallback, out = self._escalate_fixpoint(
                        evp, timestamp, n, kind)
                if not fallback:
                    self.fixpoint_batches += 1
        elif _has_balancing([ev]):
            # Balancing clamps are order-dependent through the prefix
            # balances: route straight to the balancing fixpoint tier
            # (the plain kernel would hard-fall-back). Same
            # shallow->deep ladder + deep-first hysteresis as the limit
            # tiers.
            from .fast_kernels import (
                create_transfers_balancing_deep_jit,
                create_transfers_balancing_jit,
            )

            if self._bal_deep_first > 0:
                self._bal_deep_first -= 1
                out, fallback = self._dispatch(
                    create_transfers_balancing_deep_jit, evp, timestamp,
                    n, "fallback", kind=kind)
                self.deep_fixpoint_batches += 1
            else:
                out, fallback = self._dispatch(
                    create_transfers_balancing_jit, evp, timestamp, n,
                    "fallback", kind=kind)
                if fallback and bool(out["fix_unconverged"]):
                    fallback, out = self._escalate_fixpoint(
                        evp, timestamp, n, kind, balancing=True)
            if not fallback:
                self.fixpoint_batches += 1
        elif self._fixpoint_first:
            # The workload has been breaching balance limits: skip the
            # doomed headroom-proof dispatch and go straight to the
            # converging fixpoint kernel; drop back once a batch reports
            # no breach.
            out, fallback, limit_hit = self._dispatch(
                create_transfers_fixpoint_jit, evp, timestamp, n,
                "fallback", "limit_hit", kind=kind)
            if not fallback:
                self.fixpoint_batches += 1
                if not limit_hit:
                    self._fixpoint_first = False
        else:
            out, fallback, limit_only = self._dispatch(
                create_transfers_fast_jit, evp, timestamp, n,
                "fallback", "limit_only", kind=kind)
            if fallback and limit_only:
                # The only obstacle was the balance-limit headroom proof,
                # a collision, a closing flag or a void of a closing
                # pending: all resolve natively on the fixpoint variant
                # (only the state was donated — evp is intact).
                out, fallback = self._escalate_limit_only(
                    create_transfers_fixpoint_jit, evp, timestamp, n, kind)
                if not fallback:
                    self.fixpoint_batches += 1
                    self._fixpoint_first = True
        if fallback:
            self._note_fb(out)
            return self._host_judge("fallback", judge, kind)
        self.fast_batches += 1
        self._count_judged(self._last_tier, kind)
        self._probe_succeeded()
        with span(Event.execute_encode, op=at):
            st = np.asarray(out["r_status"][:n])
            ts = np.asarray(out["r_ts"][:n])
        if self._wt:
            with span(Event.execute_delta_fetch, op=at):
                self._capture_fast_delta_transfers(ev, st)
        if raw:
            return st, ts
        ts_l = ts.tolist()
        st_l = st.tolist()
        return [
            CreateTransferResult(timestamp=ts_l[i],
                                 status=_CTS_BY_CODE[st_l[i]])
            for i in range(n)
        ]

    # ------------------------------------------------------------- lookups

    def _gather_rows(self, table_key: str, store_key: str, ids: list[int]):
        """Device-side id->row lookup + row gather: only the queried rows
        cross to the host, never the full table."""
        import jax.numpy as jnp

        from .hash_table import ht_lookup

        hi = np.array([i >> 64 for i in ids], dtype=np.uint64)
        lo = np.array([i & (1 << 64) - 1 for i in ids], dtype=np.uint64)
        found, rows = ht_lookup(self.state[table_key], jnp.asarray(hi),
                                jnp.asarray(lo))
        # Orphan sentinels (negative vals in the transfer table) are not
        # live objects — a lookup must miss them.
        found = found & (rows >= 0)
        rows = jnp.maximum(rows, 0)
        store = self.state[store_key]
        gathered = {k: np.asarray(store[k][rows]) for k in store
                    if k != "count"}
        if store_key == "transfers":
            gathered = xf_named(gathered)
        elif store_key == "accounts":
            gathered = ac_named(gathered)
        return np.asarray(found), gathered

    def lookup_accounts(self, ids: list[int]) -> list[Account]:
        found, acc = self._gather_rows("acct_ht", "accounts", ids)
        out = []
        for i, aid in enumerate(ids):
            if not found[i]:
                continue
            out.append(Account(
                id=aid,
                debits_pending=_balance_int(acc, "dp", i),
                debits_posted=_balance_int(acc, "dpos", i),
                credits_pending=_balance_int(acc, "cp", i),
                credits_posted=_balance_int(acc, "cpos", i),
                user_data_128=u128.to_int(acc["ud128_hi"][i], acc["ud128_lo"][i]),
                user_data_64=int(acc["ud64"][i]),
                user_data_32=int(acc["ud32"][i]),
                ledger=int(acc["ledger"][i]),
                code=int(acc["code"][i]),
                flags=int(acc["flags"][i]),
                timestamp=int(acc["ts"][i]),
            ))
        return out

    def lookup_transfers(self, ids: list[int]) -> list[Transfer]:
        found, xfr = self._gather_rows("xfer_ht", "transfers", ids)
        return [
            _transfer_from_row(xfr, i, ids[i])
            for i in range(len(ids)) if found[i]
        ]

    # --------------------------------------------------------- host fallback

    def to_host(self):
        """Reconstruct an oracle-compatible host state from device arrays.
        Also records id -> device row maps so the mirror regime can push
        incremental deltas back without a full rebuild."""
        self.resolve_windows()  # pipeline ordering
        from ..oracle.state_machine import StateMachineOracle

        if self._wt:
            self.drain_mirror()
        self._acct_row: dict[int, int] = {}
        self._xfer_row: dict[int, int] = {}
        sm = StateMachineOracle()
        a_rows = {k: np.asarray(v)
                  for k, v in self.state["accounts"].items()}
        n_a = int(a_rows["count"])
        acc = ac_named(a_rows)
        for r in range(n_a):
            a = Account(
                id=u128.to_int(acc["id_hi"][r], acc["id_lo"][r]),
                debits_pending=_balance_int(acc, "dp", r),
                debits_posted=_balance_int(acc, "dpos", r),
                credits_pending=_balance_int(acc, "cp", r),
                credits_posted=_balance_int(acc, "cpos", r),
                user_data_128=u128.to_int(acc["ud128_hi"][r], acc["ud128_lo"][r]),
                user_data_64=int(acc["ud64"][r]),
                user_data_32=int(acc["ud32"][r]),
                ledger=int(acc["ledger"][r]),
                code=int(acc["code"][r]),
                flags=int(acc["flags"][r]),
                timestamp=int(acc["ts"][r]),
            )
            sm.accounts[a.id] = a
            sm.account_by_timestamp[a.timestamp] = a.id
            self._acct_row[a.id] = r

        t_rows = {k: np.asarray(v)
                  for k, v in self.state["transfers"].items()}
        n_t = int(t_rows["count"])
        xfr = xf_named(t_rows)
        for r in range(n_t):
            t = _transfer_from_row(xfr, r, None)
            sm.transfers[t.id] = t
            sm.transfer_by_timestamp[t.timestamp] = t.id
            self._xfer_row[t.id] = r
            pstat = int(xfr["pstat"][r])
            if pstat != 0:
                sm.pending_status[t.timestamp] = TransferPendingStatus(pstat)
                if (pstat == int(TransferPendingStatus.pending)
                        and t.timeout != 0):
                    sm.expiry[t.timestamp] = t.timestamp + t.timeout * NS_PER_S

        from .hash_table import ht_live_items

        o_hi, o_lo, o_val = ht_live_items(self.state["xfer_ht"])
        orphan = o_val < 0
        for hi_k, lo_k in zip(o_hi[orphan].tolist(),
                              o_lo[orphan].tolist()):
            sm.orphaned.add(u128.to_int(hi_k, lo_k))

        sm.accounts_key_max = int(self.state["acct_key_max"]) or None
        sm.transfers_key_max = int(self.state["xfer_key_max"]) or None
        sm.pulse_next_timestamp = int(self.state["pulse_next"])
        sm.commit_timestamp = int(self.state["commit_ts"])
        if self._wt and self.recycle_events:
            # The ring is recycled per batch in serving mode: the
            # write-through mirror (kept exact batch-for-batch) is the
            # authoritative host copy of the unpruned tail.
            sm.account_events = list(self.mirror.account_events)
            sm.events_base = self.mirror.events_base
        else:
            sm.account_events = self._events_to_host(acc, xfr)
            self._events_pushed = len(sm.account_events)
            self._events_seen_abs = sm.events_base + len(sm.account_events)
        self._xfer_rows_dev = len(self._xfer_row)
        return sm

    def _events_to_host(self, acc, xfr) -> list:
        """Reconstruct AccountEventRecords from the device history ring
        (reference: the account_events groove rows)."""
        from ..oracle.state_machine import AccountEventRecord

        n_e = int(self.state["events"]["count"])
        # Slice on device FIRST: only the live rows cross to the host, not
        # the full-capacity matrices; then expand to named columns.
        evr = ev_named({k: np.asarray(v[:n_e])
                        for k, v in self.state["events"].items()
                        if k != "count"})
        out = []

        def side_account(side: str, r: int) -> Account:
            row = int(evr[f"{side}_row"][r])
            return Account(
                id=u128.to_int(acc["id_hi"][row], acc["id_lo"][row]),
                debits_pending=u128.to_int(
                    evr[f"{side}_dp_hi"][r], evr[f"{side}_dp_lo"][r]),
                debits_posted=u128.to_int(
                    evr[f"{side}_dpos_hi"][r], evr[f"{side}_dpos_lo"][r]),
                credits_pending=u128.to_int(
                    evr[f"{side}_cp_hi"][r], evr[f"{side}_cp_lo"][r]),
                credits_posted=u128.to_int(
                    evr[f"{side}_cpos_hi"][r], evr[f"{side}_cpos_lo"][r]),
                user_data_128=u128.to_int(
                    acc["ud128_hi"][row], acc["ud128_lo"][row]),
                user_data_64=int(acc["ud64"][row]),
                user_data_32=int(acc["ud32"][row]),
                ledger=int(acc["ledger"][row]),
                code=int(acc["code"][row]),
                flags=int(evr[f"{side}_flags"][r]),
                timestamp=int(acc["ts"][row]),
            )

        for r in range(n_e):
            tflags = int(evr["tflags"][r])
            p_row = int(evr["p_row"][r])
            out.append(AccountEventRecord(
                timestamp=int(evr["ts"][r]),
                dr_account=side_account("dr", r),
                cr_account=side_account("cr", r),
                transfer_flags=None if tflags == 0xFFFFFFFF else tflags,
                transfer_pending_status=TransferPendingStatus(
                    int(evr["pstat"][r])),
                transfer_pending=(
                    _transfer_from_row(xfr, p_row, None) if p_row >= 0
                    else None),
                amount_requested=u128.to_int(
                    evr["areq_hi"][r], evr["areq_lo"][r]),
                amount=u128.to_int(evr["amt_hi"][r], evr["amt_lo"][r]),
            ))
        return out

    def from_host(self, sm) -> None:
        """Rebuild the device state from a host oracle state."""
        self.resolve_windows()  # pipeline ordering
        import jax.numpy as jnp

        from .hash_table import ht_insert

        # Queued fast-batch deltas drain into the old mirror first: when
        # `sm` IS that mirror they are preserved; when `sm` replaces it
        # wholesale they are then discarded with it.
        if self.mirror is not None:
            self.drain_mirror()
        self._mirror_chunks = []
        self.state = init_state(self.a_cap, self.t_cap)
        # Row maps must mirror the PACKING order below: BOTH stores pack
        # in applied-timestamp order — the canonical row order (the
        # state-epoch digest row-indexes against it, and the imported
        # tiers' searchsorted-only collision probes read the ts columns
        # as pre-sorted operands). For transfers that is
        # transfer_by_timestamp (commit) order — under the lazy mirror
        # a point read moves a key out of dict insertion position, so
        # enumerate(sm.transfers) could disagree with the packed rows
        # and scatter later pending flips onto the wrong device rows.
        # For accounts dict order IS creation==timestamp order on every
        # live path; the explicit sort makes restored states safe too.
        acct_objs = sorted(sm.accounts.values(),
                           key=lambda a: a.timestamp)
        self._acct_row = {a.id: r for r, a in enumerate(acct_objs)}
        self._xfer_row = {t: r for r, t in
                          enumerate(sm.transfer_by_timestamp.values())}
        self._xfer_rows_dev = len(self._xfer_row)
        st = self.state

        def batch_insert(table, keys_vals):
            for lo_i in range(0, len(keys_vals), N_PAD):
                chunk = keys_vals[lo_i:lo_i + N_PAD]
                hi = np.array([k >> 64 for k, _ in chunk], dtype=np.uint64)
                lo = np.array([k & (1 << 64) - 1 for k, _ in chunk], dtype=np.uint64)
                vals = np.array([v for _, v in chunk], dtype=np.int32)
                table, ok = ht_insert(
                    table, jnp.asarray(hi), jnp.asarray(lo),
                    jnp.asarray(vals), jnp.ones(len(chunk), dtype=bool))
                assert bool(ok), "hash rebuild overflow: raise capacities"
            return table

        accounts = acct_objs
        assert len(accounts) <= self.a_cap and len(sm.transfers) <= self.t_cap
        acc = {k: np.asarray(v).copy() if hasattr(v, "shape") else v
               for k, v in st["accounts"].items()}
        n_a_rows = len(accounts)
        a_u64, a_bal = _pack_account_rows(accounts)
        acc["u32"][:n_a_rows] = narrow(a_u64)
        acc["bal"][:n_a_rows] = narrow(a_bal)
        acc["count"] = np.int32(len(accounts))
        st["accounts"] = {k: jnp.asarray(v) for k, v in acc.items()}

        acct_row = {a.id: r for r, a in enumerate(accounts)}
        st["acct_ht"] = batch_insert(
            st["acct_ht"], [(a.id, r) for r, a in enumerate(accounts)])

        # Commit (timestamp) order, NOT dict order: under the lazy mirror
        # a point read reorders dict insertion positions, and device row
        # assignment must stay deterministic across replicas.
        transfers = [sm.transfers[tid]
                     for tid in sm.transfer_by_timestamp.values()]
        xfr = {k: np.asarray(v).copy() if hasattr(v, "shape") else v
               for k, v in st["transfers"].items()}
        u64m = _pack_transfer_rows(
            transfers,
            lambda o: int(sm.pending_status.get(
                o.timestamp, TransferPendingStatus.none)),
            lambda aid, dump: acct_row.get(aid, dump),
            self.a_cap)
        n_t = len(transfers)
        xfr["u32"][:n_t] = narrow(u64m)
        xfr["count"] = np.int32(len(transfers))
        st["transfers"] = {k: jnp.asarray(v) for k, v in xfr.items()}
        st["xfer_ht"] = batch_insert(
            st["xfer_ht"],
            [(t.id, r) for r, t in enumerate(transfers)]
            + [(oid, ORPHAN_VAL) for oid in sorted(sm.orphaned)])

        st["acct_key_max"] = np.uint64(sm.accounts_key_max or 0)
        st["xfer_key_max"] = np.uint64(sm.transfers_key_max or 0)
        st["pulse_next"] = np.uint64(sm.pulse_next_timestamp)
        st["commit_ts"] = np.uint64(sm.commit_timestamp)
        # Rebuild the history ring from the host records.
        evr = {k: (np.asarray(v).copy() if hasattr(v, "shape") else v)
               for k, v in st["events"].items()}
        cols = self._event_cols(sm.account_events)
        n_e = len(sm.account_events)
        e_cap = ev_cap(evr)
        assert n_e <= e_cap, "e_cap exceeded: raise capacities"
        for k, v in cols.items():
            evr[k][:n_e] = v
        evr["count"] = np.int32(n_e)
        st["events"] = {k: (jnp.asarray(v) if hasattr(v, "shape")
                            else jnp.int32(v)) for k, v in evr.items()}
        self._events_pushed = n_e
        self._events_seen_abs = sm.events_base + n_e
        # Everything is now device-resident: drop any push-pending marks
        # the host state carried in (e.g. from a durable-restore rebuild).
        for c in (sm.accounts, sm.transfers, sm.pending_status,
                  sm.expiry, sm.orphaned):
            c.dirty_dev.clear()

    # The fallback regime (reference analog: the "hard path" of
    # execute_create — order-dependent batches: balance limits, imported
    # timestamps, balancing clamps). First hard batch pays one full
    # device->host sync to build a live oracle mirror; while the regime
    # holds, every batch (hard or easy) runs on the mirror — the exact
    # sequential semantics — and only the DIRTY objects are scattered back
    # to the device. After MIRROR_PROBE_INTERVAL batches the mirror is
    # dropped to probe the vectorized path again.

    def _mirror_route(self) -> bool:
        """True if this batch should run on the host mirror."""
        if self._wt:
            # Write-through: the mirror always exists; the hard-regime
            # flag (not mirror presence) carries the hysteresis.
            if not self._hard_regime:
                return False
        elif self.mirror is None:
            return False
        self._mirror_batches += 1
        if self._mirror_batches > self.MIRROR_PROBE_INTERVAL:
            # Probe the device fast path — but KEEP the mirror until the
            # probe succeeds: if the batch falls back again, the (still
            # valid: pushes kept the device in sync and a failed kernel
            # leaves state untouched) mirror is reused, avoiding a full
            # to_host rebuild every probe under sustained-hard workloads.
            self._probe_pending = True
            return False
        return True

    def _probe_succeeded(self) -> None:
        """The fast path took a batch: any held mirror is now stale (the
        kernel mutated device state) — drop it. In write-through mode the
        mirror is permanent (the fast path delta-applies to it); only the
        hard-regime flag resets."""
        if self._wt:
            self._hard_regime = False
        elif self.mirror is not None:
            self.mirror = None
        self._probe_pending = False
        self._mirror_batches = 0

    def _enter_mirror(self):
        self.mirror = self.to_host()
        self._enable_dev_tracking(self.mirror)
        self._mirror_batches = 1
        # Everything in the mirror is already on device.
        for container in (self.mirror.accounts, self.mirror.transfers,
                          self.mirror.pending_status, self.mirror.expiry,
                          self.mirror.orphaned):
            container.dirty.clear()
            container.dirty_dev.clear()
        return self.mirror

    def _event_cols(self, records: list) -> dict:
        """Host AccountEventRecords -> the packed ring row matrix
        (push/from_host)."""
        return _pack_event_rows(records, self._acct_row, self._xfer_row,
                                self.a_cap)



    @staticmethod
    def _enable_dev_tracking(sm) -> None:
        """Turn on the device-push dirty channel for a mirror's containers
        (off by default: on the oracle/kernel engines nothing consumes —
        or clears — it), and swap the transfers container for the lazy
        columnar one (ops/lazy_mirror.py) — the write-through delta
        registers created rows there without building objects."""
        from .lazy_mirror import LazyEventList, LazyTransferDict

        sm.transfers = LazyTransferDict.adopt(sm.transfers)
        sm.account_events = LazyEventList.adopt(sm.account_events)
        for c in (sm.accounts, sm.transfers, sm.pending_status,
                  sm.expiry, sm.orphaned):
            c.track_dev = True
            c.dirty_dev.clear()

    def _maybe_recycle_ring(self) -> None:
        """Serving mode: every ring row has been consumed (delta-applied
        to the mirror or sourced from it), so rewind the cursor — the
        ring stays a bounded per-batch transport and the e8 capacity
        fallback can never trip from accumulated history (memory-bounds
        doctrine; the forest's events tree holds the history)."""
        if not (self._wt and self.recycle_events):
            return
        if self._tickets:
            # Outstanding pipelined windows still append at the current
            # ring offsets; recycling happens when the pipeline drains.
            return
        if self._events_pushed == 0:
            return
        import jax.numpy as jnp

        self.state["events"]["count"] = jnp.int32(0)
        self._events_pushed = 0

    def _clear_dirty_dev(self) -> None:
        """Everything the fast delta just applied to the mirror came FROM
        the device, so it must not be re-pushed by the next _push_dirty
        (re-inserting orphan ids would duplicate hash-table entries).
        The durable channel (.dirty) is left untouched for the flusher."""
        sm = self.mirror
        for c in (sm.accounts, sm.transfers, sm.pending_status,
                  sm.expiry, sm.orphaned):
            c.dirty_dev.clear()

    # ------------------------------------------------- write-through deltas

    def _delta_fetch_start(self, n_new: int) -> "_DeltaFetchHandle":
        """Issue one bounded device-side delta gather WITHOUT blocking on
        the device->host transfer: the n_new appended transfer rows +
        event-ring rows, plus derived gathers (touched account ids,
        pending-transfer timestamps). Fixed slice sizes (256 / N_PAD /
        8*N_PAD) keep the compile count at three — point batches, one
        prepare, a full commit window.

        The returned handle starts an async host copy where the backend
        supports it and resolves (device_get + exact-size slice copies)
        on first column access — which happens at drain/flush time, NOT
        on the serving commit path. On chip the transfer is the dominant
        serving cost beyond the kernel (~25 MB per 8-prepare window), so
        deferring it moves that cost off the commit boundary and overlaps
        the DMA with subsequent dispatches (reference doctrine: commit is
        the cheap part, src/state_machine.zig:2564; prefetch/IO overlaps
        execution, src/lsm/groove.zig:1339)."""
        t0 = self._xfer_rows_dev
        e0 = self._events_pushed
        t_len = int(self.state["transfers"]["u32"].shape[0])
        e_len = ev_cap(self.state["events"]) + 1
        # Buckets: point batches, one prepare, a full commit window.
        for size in (256, N_PAD, 8 * N_PAD):
            if n_new <= size:
                break
        size_t = min(size, t_len)
        size_e = min(size, e_len)
        assert n_new <= size_t and n_new <= size_e
        t_start = max(0, min(t0, t_len - size_t))
        e_start = max(0, min(e0, e_len - size_e))
        out = _xfer_delta_gather_jit(
            self.state, np.int32(t_start), np.int32(e_start), size_t, size_e)
        return _DeltaFetchHandle(out, t0, t0 - t_start, e0 - e_start)

    def _track_pending_cols(self, *cols) -> None:
        """Memory-bounds doctrine: at most ~32 unresolved delta fetches
        may hold device buffers; beyond that the oldest are loaded (their
        async copies have long completed), releasing the device side."""
        self._pending_cols = [cs for cs in self._pending_cols
                              if not cs[0].loaded]
        self._pending_cols.append(cols)
        while len(self._pending_cols) > 32:
            for c in self._pending_cols.pop(0):
                c.load()

    def _capture_window_delta(self, evs: list, st_slices: list,
                              timestamps: list = None,
                              exact_chunks: bool = False) -> None:
        """Window-level write-through capture: ONE bounded device fetch
        for a whole commit window's effects (the window kernel appends
        all created rows contiguously in commit order), split into
        per-prepare chunks so the drain and the durable flush keep their
        per-prepare watermark semantics. Replaces W per-body fetches —
        each a full device round-trip — with one (the dominant serving
        cost on chip once the kernel itself is windowed).

        timestamps: per-batch commit timestamps. When given AND the
        window carries no post/void, the fetch is HALF-WIDTH (event
        ring only) and the transfer/der columns synthesize on host —
        same contract as the pipelined e_only capture.

        exact_chunks: queue one flush chunk per sub-batch even when it
        is empty — the replica commit loop attributes chunks to
        prepares positionally (its per-op flush cadence is what keeps
        physical checkpoints byte-identical across replicas)."""
        per = [self._batch_delta_stats(ev, st_np)
               for ev, st_np in zip(evs, st_slices)]
        # Half-width synthesis requires: no post/void (amounts/fields
        # inherit from pendings on device), no imported events (their
        # stored timestamps are the USER's, not the ts_event formula),
        # and no balancing (stored amounts are the device's clamp, not
        # the input's nominal amount).
        excl_bits = np.uint32(_F_POST_VOID_HOST() | _F_IMPORTED_HOST()
                              | _F_BALANCING_HOST())
        e_only = timestamps is not None and all(
            not (np.asarray(ev["flags"]) & excl_bits).any() for ev in evs)

        def fetch_start(total):
            if e_only:
                return self._ev_delta_fetch_start(total)
            return self._delta_fetch_start(total)

        def flush_group(group):
            total = sum(n for n, _, _, _ in group)
            handle = fetch_start(total) if total else None
            off = 0
            for n_new, orphan_ids, ev_b, pack in group:
                op_no = self._op_seq
                self._op_seq += 1
                if n_new:
                    # Lazy column views: the fetch resolves (exact-size
                    # copies, full buffer released) on first access —
                    # at drain/flush, off the commit path.
                    if e_only:
                        st_b, ts_b = pack
                        tc = _SynthCols(_synth_t_cols, ev_b, st_b, ts_b)
                        derc = _SynthCols(_synth_der_cols, ev_b, st_b)
                    else:
                        tc = _LazyCols(handle, "t", off, n_new)
                        derc = _LazyCols(handle, "der", off, n_new)
                    ec = _LazyCols(handle, "e", off, n_new)
                    self._track_pending_cols(tc, ec, derc)
                    self._queue_chunk((tc, ec, derc), handle.t0 + off,
                                      n_new, orphan_ids, op_no)
                    off += n_new
                else:
                    self._queue_chunk(_NO_COLS, 0, 0, orphan_ids, op_no,
                                      keep_empty=exact_chunks)

        # One fetch per <= 8*N_PAD created rows (the fetch's largest
        # static bucket); a serving window of 8 prepares fits in one.
        group: list = []
        group_new = 0
        for b, (n_new, orphan_ids) in enumerate(per):
            if group and group_new + n_new > 8 * N_PAD:
                flush_group(group)
                group, group_new = [], 0
            pack = ((st_slices[b], timestamps[b])
                    if timestamps is not None else None)
            group.append((n_new, orphan_ids, evs[b], pack))
            group_new += n_new
        if group:
            flush_group(group)
        self._clear_dirty_dev()
        self._maybe_recycle_ring()

    def _ev_delta_fetch_start(self, n_new: int) -> "_DeltaFetchHandle":
        """Half-width sync fetch: event-ring slice only (see
        _ev_delta_gather_window)."""
        e0 = self._events_pushed
        e_len = ev_cap(self.state["events"]) + 1
        for size in (256, N_PAD, 8 * N_PAD):
            if n_new <= size:
                break
        size_e = min(size, e_len)
        assert n_new <= size_e
        e_start = max(0, min(e0, e_len - size_e))
        out = _ev_delta_gather_host_jit(self.state, np.int32(e_start),
                                        size_e)
        return _DeltaFetchHandle(out, self._xfer_rows_dev, 0,
                                 e0 - e_start)

    @staticmethod
    def _batch_delta_stats(ev: dict, st_np):
        """(created count, orphan ids) of one batch's statuses — the
        shared per-prepare summary both capture paths queue from."""
        created_code = np.uint32(int(CreateTransferStatus.created))
        orph_mask = np.isin(st_np, _TRANSIENT_ARR)
        orphan_ids = ([
            (int(ev["id_hi"][i]) << 64) | int(ev["id_lo"][i])
            for i in np.nonzero(orph_mask)[0]
        ] if orph_mask.any() else [])
        return int((st_np == created_code).sum()), orphan_ids

    def _capture_fast_delta_transfers(self, ev: dict, st_np) -> None:
        """Write-through, deferred: fetch the batch's bounded device delta
        and queue it as a columnar chunk. Materialization into the host
        mirror's object stores happens lazily at the next mirror READ
        (drain_mirror) — the serving commit path itself stays object-free
        (the same lazy discipline as StateMachine._refresh_indexes;
        reference: commit is the cheap part, src/state_machine.zig:2564)."""
        n_new, orphan_ids = self._batch_delta_stats(ev, st_np)
        op_no = self._op_seq
        self._op_seq += 1
        if n_new == 0:
            self._queue_chunk(_NO_COLS, 0, 0, orphan_ids, op_no)
            self._clear_dirty_dev()
            return
        handle = self._delta_fetch_start(n_new)
        t = _LazyCols(handle, "t", 0, n_new)
        e = _LazyCols(handle, "e", 0, n_new)
        der = _LazyCols(handle, "der", 0, n_new)
        self._track_pending_cols(t, e, der)
        self._queue_chunk((t, e, der), handle.t0, n_new, orphan_ids, op_no)
        self._clear_dirty_dev()
        self._maybe_recycle_ring()

    def _queue_chunk(self, cols, t0: int, n_new: int, orphan_ids: list,
                     op_no: int, keep_empty: bool = False) -> None:
        """Queue one prepare's captured delta twice: for the mirror
        drain, and (attach_durable) for the durable flusher's vectorized
        path — retained at CAPTURE, so flushing does not require
        materializing the mirror first. Both carry abs_start, the
        chunk's absolute event index: the flusher's double-flush
        watermark on one side, what the drain holds against that
        watermark on the other. Orphan ids ride along so the orphaned
        tree stays in lockstep without a drain. keep_empty queues a
        flush chunk even for a prepare with nothing to flush (the
        replica's window commit attributes chunks positionally)."""
        abs_start = self._events_seen_abs
        if n_new or orphan_ids:
            self._mirror_chunks.append(
                (*cols, t0, n_new, orphan_ids, op_no, abs_start))
        if self.retain_flush_columns and (n_new or orphan_ids
                                          or keep_empty):
            self._flush_columns.append(
                (*cols, n_new, abs_start, orphan_ids))
        self._xfer_rows_dev += n_new
        self._events_pushed += n_new
        self._events_seen_abs += n_new

    def drain_mirror(self) -> None:
        """Materialize every queued fast-batch delta into the host mirror.
        Called before ANY mirror read (queries, lookups via the state
        machine, durability flush, hard-batch fallback, to_host); no-op
        when nothing is queued, so it is safe to call liberally."""
        self.resolve_windows()  # pipeline ordering
        if not self._mirror_chunks:
            return
        chunks, self._mirror_chunks = self._mirror_chunks, []
        # Stream ALL pending device->host transfers up front: each
        # chunk's registration then overlaps the next chunk's bytes in
        # flight instead of ping-ponging transfer/compute per chunk.
        # Check every column view (e_only chunks synthesize t/der on
        # host — their DEVICE bytes live behind the event-ring ec).
        for cols in chunks:
            for c in cols[:3]:
                if cols[4] and isinstance(c, _LazyCols) and \
                        not c.loaded and c._handle is not None:
                    c._handle.start_copy()
                    break
        persisted = self.events_persisted()
        orphaned = self.mirror.orphaned
        for t, e, der, t0, n_new, orphan_ids, _op, abs_start in chunks:
            # A chunk whose events all lie under the flusher's watermark
            # went into the trees through its flush-columns twin (rows,
            # account finals, pending/expiry effects, orphan ids): the
            # mirror takes it in clean, so no flush puts those bytes a
            # second time. A chunk that created nothing has no event
            # range to hold against the watermark and stays dirty, as
            # does every chunk the column path has not reached.
            durable = bool(n_new) and abs_start + n_new <= persisted
            if durable:
                set.update(orphaned, orphan_ids)
            else:
                for oid in orphan_ids:
                    orphaned.add(oid)
            if n_new:
                self._materialize_delta_transfers(t, e, der, t0, n_new,
                                                  durable)
        self._clear_dirty_dev()
        from .. import constants

        if constants.VERIFY:
            # Extra-check mode: spot-audit device rows against the just-
            # drained mirror (the write-through contract, fuzz_tests.zig
            # :11-16 doctrine). Sampling is configurable via
            # TB_VERIFY_SPOT_RATE: default audits 2 rows of the newest
            # chunk; >=1.0 audits EVERY row of EVERY chunk (chaos runs
            # crank it to 100% so "auditor-clean" is exhaustive).
            import os as _os

            try:
                rate = float(
                    _os.environ.get("TB_VERIFY_SPOT_RATE", "") or 0.0)
            except ValueError:
                rate = 0.0
            checked = 0
            for t, e, der, t0, n_new, _, op_no, _abs in reversed(chunks):
                if not n_new:
                    continue
                k = n_new if rate >= 1.0 else min(2, n_new)
                xfer_ids = [u128.to_int(t["id_hi"][i], t["id_lo"][i])
                            for i in range(k)]
                # Plus a STABLE anchor — the oldest transfer — so
                # drift on rows the batch never touched (stale
                # pending flips, bad pushes) is caught too.
                if checked == 0 and self.mirror.transfers:
                    xfer_ids.append(next(iter(self.mirror.transfers)))
                self._verify_mirror_spot(
                    [u128.to_int(der["dr_id_hi"][i], der["dr_id_lo"][i])
                     for i in range(k)],
                    xfer_ids,
                    ctx=f"op {op_no}, device rows {t0}..{t0 + n_new}")
                checked += 1
                if rate < 1.0:
                    break

    def _verify_mirror_spot(self, acct_ids: list, xfer_ids: list,
                            ctx: str = "") -> None:
        """VERIFY check: device-resident rows and the host mirror must
        agree object-for-object after a drain. A divergence raises
        MirrorDivergence naming the op/prepare that produced the chunk
        and every differing field — triageable straight from the log."""
        import dataclasses as _dc

        sm = self.mirror
        where = f" at {ctx}" if ctx else ""

        def diff(got, want) -> str:
            if got is None:
                return "object missing on device"
            if want is None:
                return "object missing in mirror"
            return "differing fields: " + ", ".join(
                f"{f.name}(device={getattr(got, f.name)!r}, "
                f"mirror={getattr(want, f.name)!r})"
                for f in _dc.fields(got)
                if getattr(got, f.name) != getattr(want, f.name))

        got_a = {a.id: a for a in self.lookup_accounts(acct_ids)}
        for aid in acct_ids:
            got, want = got_a.get(aid), sm.accounts.get(aid)
            if got != want:
                raise MirrorDivergence(
                    f"verify: device/mirror divergence on account "
                    f"{aid}{where}: {diff(got, want)}")
        got_t = {t.id: t for t in self.lookup_transfers(xfer_ids)}
        for tid in xfer_ids:
            got, want = got_t.get(tid), sm.transfers.get(tid)
            if got != want:
                raise MirrorDivergence(
                    f"verify: device/mirror divergence on transfer "
                    f"{tid}{where}: {diff(got, want)}")

    def take_flush_columns(self, count: int = None) -> list:
        """Pop the drained chunks' transfer columns (numpy) for the
        durable flusher's vectorized index-key path. count=None pops
        everything; the replica's window commit pops exactly one
        prepare's worth (exact_chunks mode) so each op's flush carries
        only that op's effects — per-op flush cadence is what keeps
        physical checkpoints byte-identical across replicas."""
        if count is None:
            cols, self._flush_columns = self._flush_columns, []
            return cols
        # A short pop would attribute the WRONG chunks to later ops and
        # surface only as a distant cross-replica byte divergence — fail
        # here instead (same tripwire style as durable.py's in-order
        # chunk assert).
        assert len(self._flush_columns) >= count, \
            (len(self._flush_columns), count)
        cols = self._flush_columns[:count]
        self._flush_columns = self._flush_columns[count:]
        return cols

    def _materialize_delta_transfers(self, t, e, der, t0, n_new: int,
                                     durable: bool = False) -> None:
        """Register one captured chunk with the host mirror COLUMNARLY
        (ops/lazy_mirror.py): created transfers become lazy rows in the
        LazyTransferDict (keys + (chunk, row) refs, no objects), account
        write-back is one vectorized last-writer pass (one new Account
        per touched account, not two __dict__ copies per event), and
        account_events grow by lazy per-row proxies. Pending-status
        flips (the only order-dependent scalar work) run as a small loop
        over just the flip subset. Values any reader can observe are
        identical to the old eager per-event drain (the oracle success
        path, oracle/state_machine.py _create_transfer :417) —
        tests/test_lazy_mirror.py pins this differentially.

        durable: the column flush has already put this chunk into the
        trees (drain_mirror), so nothing it registers enters a store's
        durable channel (.dirty)."""
        from .lazy_mirror import (DeltaChunk, LazyTransferDict,
                                  apply_account_finals)

        sm = self.mirror
        n = n_new

        ids = [(h << 64) | l
               for h, l in zip(t["id_hi"].tolist(), t["id_lo"].tolist())]
        ts_list = e["ts"].tolist()
        chunk = DeltaChunk(t, e, der, sm, ids)

        transfers = sm.transfers
        assert isinstance(transfers, LazyTransferDict), \
            "device write-through mirror must hold a LazyTransferDict"
        transfers.register(ids, chunk, dirty=not durable)
        sm.transfer_by_timestamp.update(zip(ts_list, ids))
        self._xfer_row.update(zip(ids, range(t0, t0 + n)))
        last_ts = ts_list[-1]
        if sm.transfers_key_max is None or last_ts > sm.transfers_key_max:
            sm.transfers_key_max = last_ts
        sm.commit_timestamp = last_ts

        changed_accounts = apply_account_finals(sm, e, der)
        if not durable:
            sm.accounts.dirty.update(changed_accounts)

        # Pending-status flips: adds (pending creates) and releases
        # (post/void) interleave with order-dependent pulse bookkeeping,
        # so this subset stays a scalar loop — but ONLY this subset.
        pstat_np = np.asarray(e["pstat"])
        flips = np.nonzero(pstat_np != 0)[0]
        if flips.size:
            P = TransferPendingStatus
            pend_code = int(P.pending)
            pstat_l = pstat_np[flips].tolist()
            ts_l = np.asarray(e["ts"])[flips].tolist()
            pts_l = np.asarray(der["p_ts"])[flips].tolist()
            timeout_l = np.asarray(t["timeout"])[flips].tolist()
            pending_raw = sm.pending_status
            pset = dict.__setitem__
            expiry = sm.expiry
            # dict's own methods pass the DirtyDict's channels by.
            edict = dict if durable else type(expiry)
            expiry_set, expiry_pop = edict.__setitem__, edict.pop
            touched_pending: list = []
            for j in range(len(pstat_l)):
                pstat = pstat_l[j]
                if pstat == pend_code:
                    ts = ts_l[j]
                    pset(pending_raw, ts, P.pending)
                    touched_pending.append(ts)
                    timeout = timeout_l[j]
                    if timeout:
                        expires_at = ts + timeout * NS_PER_S
                        expiry_set(expiry, ts, expires_at)
                        if expires_at < sm.pulse_next_timestamp:
                            sm.pulse_next_timestamp = expires_at
                else:  # posted / voided release
                    pts = pts_l[j]
                    pset(pending_raw, pts, P(pstat))
                    touched_pending.append(pts)
                    # expiry[pts] holds exactly pts + p.timeout*NS_PER_S,
                    # and is present iff the pending transfer had a
                    # timeout and has not been released/expired — so the
                    # pop replaces reading p_obj.timeout (no object
                    # materialization on the flip path).
                    ea = expiry_pop(expiry, pts, None)
                    if ea is not None and sm.pulse_next_timestamp == ea:
                        sm.pulse_next_timestamp = TIMESTAMP_MIN
            if not durable:
                pending_raw.dirty.update(touched_pending)

        sm.account_events.extend_lazy(chunk, n)

    def _apply_fast_delta_accounts(self, st_np) -> None:
        """Write-through: apply one fast account batch to the host mirror
        (oracle _create_account :326 success path). Queued transfer chunks
        drain first so mirror commit_timestamp stays monotonic."""
        self.drain_mirror()
        sm = self.mirror
        created_code = int(CreateAccountStatus.created)
        n_new = int((st_np == np.uint32(created_code)).sum())
        if n_new == 0:
            return
        import jax

        a0 = len(self._acct_row)
        a_len = int(self.state["accounts"]["u32"].shape[0])
        size = min(256 if n_new <= 256 else N_PAD, a_len)
        assert n_new <= size
        a_start = max(0, min(a0, a_len - size))
        a_rows = jax.device_get(
            _acct_delta_gather_jit(self.state, np.int32(a_start), size))
        off = a0 - a_start
        a_rows = {k: v[off:off + n_new] for k, v in a_rows.items()}
        a = {k: v.tolist() for k, v in ac_named(a_rows).items()}
        for k in range(n_new):
            aid = (a["id_hi"][k] << 64) | a["id_lo"][k]
            acct = Account(
                id=aid,
                debits_pending=_balance_int(a, "dp", k),
                debits_posted=_balance_int(a, "dpos", k),
                credits_pending=_balance_int(a, "cp", k),
                credits_posted=_balance_int(a, "cpos", k),
                user_data_128=(a["ud128_hi"][k] << 64)
                | a["ud128_lo"][k],
                user_data_64=a["ud64"][k],
                user_data_32=a["ud32"][k],
                ledger=a["ledger"][k],
                code=a["code"][k],
                flags=a["flags"][k],
                timestamp=a["ts"][k],
            )
            sm.accounts[aid] = acct
            sm.account_by_timestamp[acct.timestamp] = aid
            self._acct_row[aid] = a0 + k
            if (sm.accounts_key_max is None
                    or acct.timestamp > sm.accounts_key_max):
                sm.accounts_key_max = acct.timestamp
            sm.commit_timestamp = acct.timestamp
        self._clear_dirty_dev()

    def _count_route(self, route: str) -> None:
        """One window dispatched via `route` (see fallback_stats). The
        tier collapses routes into the three latency classes the SLO
        objectives partition on: scan (the chain whole-window scan),
        fallback (per-batch), flat (any unrolled super route)."""
        self.window_routes[route] = self.window_routes.get(route, 0) + 1
        self.last_window_route = route
        self.last_window_tier = (
            "scan" if route in ("chain", "partitioned_chain") else
            "fallback" if route in ("per_batch", "partitioned_per_batch")
            else "flat")

    def _note_chain_fb(self, out, k: int) -> None:
        """Accumulate the chain route's per-prepare fallback causes at
        iteration k (the first fallen-back prepare; later iterations
        only carry 'forced' — the transitive poison)."""
        import jax

        for cause, v in jax.device_get(out["fb_causes"]).items():
            if bool(np.asarray(v)[k]):
                self.fallback_causes[cause] = (
                    self.fallback_causes.get(cause, 0) + 1)
                self.chain_batch_fallbacks[cause] = (
                    self.chain_batch_fallbacks.get(cause, 0) + 1)

    def _note_fb(self, out) -> None:
        """Accumulate one kernel dispatch's per-cause fallback flags
        (out["fb_causes"]) into the host counters. Called at every FINAL
        fallback decision — escalations resolved on a deeper device tier
        never reach here."""
        causes = out.get("fb_causes") if hasattr(out, "get") else None
        if causes is None:
            return
        import jax

        for k, v in jax.device_get(causes).items():
            if bool(v):
                self.fallback_causes[k] = self.fallback_causes.get(k, 0) + 1

    def _merged_routes(self) -> dict:
        """The fallback_stats()["routes"] record: the ledger's own route
        counters plus — in partitioned attach mode — the router's
        (partitioned_chain / partitioned_per_batch windows and the
        per-cause prepares that fell out of a fused window)."""
        windows = dict(self.window_routes)
        cbf = dict(self.chain_batch_fallbacks)
        if self._part_router is not None:
            rr = self._part_router.stats()["routes"]
            for k, v in rr["windows"].items():
                windows[k] = windows.get(k, 0) + v
            for k, v in rr["chain_batch_fallbacks"].items():
                cbf[k] = cbf.get(k, 0) + v
        return {"windows": windows, "chain_batch_fallbacks": cbf}

    def store_stats(self) -> dict:
        """The stores' capacities and the rows they hold now (`start`'s
        shutdown record): two scalars fetched from the device state."""
        return {"a_cap": self.a_cap, "t_cap": self.t_cap,
                "account_rows": int(self.state["accounts"]["count"]),
                "transfer_rows": int(self.state["transfers"]["count"])}

    def fallback_stats(self) -> dict:
        """Host-visible routing/fallback counters (`start`'s shutdown
        record): 'zero host fallbacks' is a measured invariant.

        Which counter counts what (they overlap, so their sum counts
        nothing): `fast_batches` is the count of create REQUESTS
        (accounts and transfers) the device judged, whatever tier
        answered — one per batch that did not fall back, one per prepare
        of a window; `fixpoint_batches` is how many of the per-batch
        ones a fixpoint tier answered, the converging limit tier or a
        balancing / imported tier, shallow or deep;
        `deep_fixpoint_batches` counts dispatches of a deep tier (per
        prepare for a deep window) and `escalations` the extra
        dispatches a batch cost beyond its first. A batch that climbs
        imported -> imported fixpoint -> its deep tier reads fast 1,
        fixpoint 1, deep 1, escalations 2: one request, three
        dispatches; one that climbs plain -> converging reads fast 1,
        fixpoint 1, escalations 1. `host_fallbacks` is the count of
        requests the host answered instead."""
        return {
            "host_fallbacks": self.fallbacks,
            "window_fallbacks": self.window_fallbacks,
            "fast_batches": self.fast_batches,
            "fixpoint_batches": self.fixpoint_batches,
            "deep_fixpoint_batches": self.deep_fixpoint_batches,
            "escalations": self.escalations,
            "causes": dict(self.fallback_causes),
            # Dispatch-route record: windows per route (chain = the
            # default scan-form whole-window dispatch; partitioned_chain
            # = its fused sibling on the partitioned mesh) + the
            # per-cause prepares that fell out of a chain window
            # (per-prepare fallback granularity — the prefix stayed
            # committed). In attach mode the PartitionedRouter owns the
            # partitioned counters; they merge in here.
            "routes": self._merged_routes(),
            # Host-staging overlap record (pipelined submit_window):
            # how much of the host's window pack+transfer work the
            # dispatch path actually waited on. host_stall_fraction is
            # the overlap gate leg's measured quantity — 1.0 means
            # fully synchronous staging, ~0 means the pack was hidden
            # behind in-flight device execution.
            "staging": self.staging_summary(),
            # Device telemetry (None unless a PartitionedRouter is
            # attached with telemetry on): the decoded-on-host
            # aggregates of the fixed-layout u32 block the fused route
            # harvests with its outputs — exchange-occupancy histogram,
            # fixpoint-round distribution, decoded poison causes,
            # flight-recorder activity.
            "device_telemetry": (
                self._part_router.stats().get("telemetry")
                if self._part_router is not None else None),
            # Chaos/recovery counters (zeros unless a ServingSupervisor
            # owns this ledger): retries, backoff time, replayed
            # windows, verified checksum epochs, recoveries by cause.
            "recovery": {
                k: (dict(v) if isinstance(v, dict) else v)
                for k, v in self.recovery_stats.items()},
        }

    def _count_judged(self, who: str, kind: str) -> None:
        counts = self._kind_counts(kind)
        counts["requests"] += 1
        counts["by_tier"][who] = counts["by_tier"].get(who, 0) + 1

    def _kind_counts(self, kind: str) -> dict:
        """`kind`'s entry of `judged_kinds`."""
        counts = self.judged_kinds.get(kind)
        if counts is None:
            counts = self.judged_kinds[kind] = {
                "requests": 0, "by_tier": {}, "escalated": 0,
                "fixpoint_dispatches": 0, "fixpoint_rounds": 0}
        return counts

    @property
    def judged(self) -> dict:
        """The per-batch create requests by who answered them, summed
        over `judged_kinds`. Unlike the tier counters they do not
        overlap; they sum to the per-batch requests."""
        out: dict = {}
        for counts in self.judged_kinds.values():
            for who, n in counts["by_tier"].items():
                out[who] = out.get(who, 0) + n
        return out

    @property
    def fixpoint_rounds(self) -> dict:
        """The converging limit tier's dispatches and their rounds,
        summed over `judged_kinds`, and the most in one."""
        kinds = self.judged_kinds.values()
        return {"dispatches": sum(c["fixpoint_dispatches"] for c in kinds),
                "rounds": sum(c["fixpoint_rounds"] for c in kinds),
                "rounds_max": self._fixpoint_rounds_max}

    def _host_judge(self, route: str, judge, kind: str):
        """One create request of `kind` answered by the host mirror:
        `route` is "mirror" (the hard regime's route, nothing
        dispatched) or "fallback" (the device's tiers fell back, which
        enters or holds the regime). `judge(sm)` runs the request on
        the mirror. The
        pure-Python oracle IS the exact sequential semantics — in the
        mirror regime it beats the device sequential kernel because the
        per-batch prefetch/compile cost disappears."""
        span, at = self.tracer.span, self.trace_op
        self.fallbacks += 1
        self._count_judged("host_" + route, kind)
        with span(Event.execute_mirror_drain, op=at):
            self.drain_mirror()
        if route == "fallback":
            if self._probe_pending:
                self._probe_pending = False
                self._mirror_batches = 1  # probe failed: regime continues
            if self._wt and not self._hard_regime:
                self._hard_regime = True
                self._mirror_batches = 1
        sm = self.mirror if self.mirror is not None else self._enter_mirror()
        with span(Event.execute_host_judge, op=at, route=route):
            results = judge(sm)
        self._push_dirty()
        return results

    def _push_dirty(self) -> None:
        """Scatter the mirror's dirty objects into the device state (the
        incremental inverse of from_host). All scatter shapes are padded to
        power-of-two buckets (padding targets the dump row, which is
        scratch by design) so XLA compiles a handful of programs, not one
        per batch size. One execute_mirror_push span, `rows` the objects
        pushed."""
        with self.tracer.span(Event.execute_mirror_push,
                              op=self.trace_op) as pushed:
            pushed.tags["rows"] = self._push_dirty_rows()

    def _push_dirty_rows(self) -> int:
        """_push_dirty's work; returns the objects it pushed."""
        import jax.numpy as jnp

        from ..oracle.state_machine import StateMachineOracle
        from .batch import next_pow2
        from .hash_table import ht_insert_jit as ht_insert

        sm: StateMachineOracle = self.mirror
        st = self.state
        acc = st["accounts"]
        xfr = st["transfers"]

        # Bucket floor 1024: at most four distinct scatter shapes ever
        # compile (1k/2k/4k/8k); the wasted lanes land on the dump row.
        def bucket(n: int) -> int:
            return max(1024, next_pow2(max(1, n)))

        def pad(arr: np.ndarray, fill) -> np.ndarray:
            n = bucket(len(arr))
            if len(arr) == n:
                return arr
            out = np.full((n, *arr.shape[1:]), fill, dtype=arr.dtype)
            out[:len(arr)] = arr
            return out

        def pad_mask(n: int) -> "jnp.ndarray":
            mask = np.zeros(bucket(n), dtype=bool)
            mask[:n] = True
            return jnp.asarray(mask)

        # ---- accounts: updates + inserts
        dirty_accounts = sorted(a for a in sm.accounts.dirty_dev
                                if a in sm.accounts)
        sm.accounts.dirty_dev.clear()
        if dirty_accounts:
            # New rows append in APPLIED-TIMESTAMP order — the canonical
            # row order (from_host / pack_oracle_state pack the same
            # way), and the invariant the imported tiers' searchsorted-
            # only collision probe reads the ts column under (the
            # per-dispatch full-table sort is gone — round-7 op cut).
            new_ids = sorted(
                (a for a in dirty_accounts if a not in self._acct_row),
                key=lambda a: sm.accounts[a].timestamp)
            next_row = int(acc["count"])
            assert next_row + len(new_ids) <= self.a_cap, "a_cap exceeded"
            for aid in new_ids:
                self._acct_row[aid] = next_row
                next_row += 1
            rows = pad(np.array([self._acct_row[a] for a in dirty_accounts],
                           dtype=np.int32), self.a_cap)
            objs = [sm.accounts[a] for a in dirty_accounts]
            u64m, bal = _pack_account_rows(objs)
            cols = {"bal": narrow(bal), "u32": narrow(u64m)}
            count = jnp.int32(next_row)
            acc = st["accounts"] = scatter_cols(
                {k: v for k, v in acc.items() if k != "count"},
                jnp.asarray(rows),
                {k: jnp.asarray(pad(v, 0)) for k, v in cols.items()})
            acc["count"] = count
            if new_ids:
                st["acct_ht"], ok = ht_insert(
                    st["acct_ht"],
                    jnp.asarray(pad(np.array([a >> 64 for a in new_ids],
                                             dtype=np.uint64), 0)),
                    jnp.asarray(pad(np.array(
                        [a & (1 << 64) - 1 for a in new_ids],
                        dtype=np.uint64), 0)),
                    jnp.asarray(pad(np.array(
                        [self._acct_row[a] for a in new_ids],
                        dtype=np.int32), 0)),
                    pad_mask(len(new_ids)))
                assert bool(ok), "acct hash overflow: raise capacities"

        # ---- transfers: inserts (immutable rows)
        dirty_transfers = sorted(t for t in sm.transfers.dirty_dev
                                 if t in sm.transfers)
        sm.transfers.dirty_dev.clear()
        # Commit-timestamp order (NOT id order): device rows must stay
        # in the canonical applied-timestamp order — the order the
        # state-epoch digest row-indexes against pack_oracle_state and
        # the imported tiers' searchsorted-only probes rely on.
        new_tids = sorted(
            (t for t in dirty_transfers if t not in self._xfer_row),
            key=lambda t: sm.transfers[t].timestamp)
        if new_tids:
            next_row = int(xfr["count"])
            assert next_row + len(new_tids) <= self.t_cap, "t_cap exceeded"
            rows = []
            for tid in new_tids:
                self._xfer_row[tid] = next_row
                rows.append(next_row)
                next_row += 1
            rows = np.array(rows, dtype=np.int32)
            rows_padded = pad(rows, self.t_cap)
            objs = [sm.transfers[t] for t in new_tids]
            u64m = _pack_transfer_rows(
                objs,
                lambda o: int(sm.pending_status.get(o.timestamp, 0)),
                lambda aid, dump: self._acct_row.get(aid, dump),
                self.a_cap)
            cols = {"u32": narrow(u64m)}
            count = jnp.int32(next_row)
            xfr = st["transfers"] = scatter_cols(
                {k: v for k, v in xfr.items() if k != "count"},
                jnp.asarray(rows_padded),
                {k: jnp.asarray(pad(v, 0)) for k, v in cols.items()})
            xfr["count"] = count
            st["xfer_ht"], ok = ht_insert(
                st["xfer_ht"],
                jnp.asarray(pad(u64m[:, XF_U64_IDX["id_hi"]].copy(), 0)),
                jnp.asarray(pad(u64m[:, XF_U64_IDX["id_lo"]].copy(), 0)),
                jnp.asarray(rows_padded),
                pad_mask(len(new_tids)))
            assert bool(ok), "xfer hash overflow: raise capacities"

        # ---- pending status flips + expiry changes on EXISTING rows
        dirty_pending = sorted(sm.pending_status.dirty_dev)
        sm.pending_status.dirty_dev.clear()
        flip = [(self._xfer_row[sm.transfer_by_timestamp[ts]],
                 int(sm.pending_status[ts]))
                for ts in dirty_pending
                if sm.transfer_by_timestamp.get(ts) in self._xfer_row]
        if flip:
            rows = pad(np.array([r for r, _ in flip], dtype=np.int32),
                       self.t_cap)
            vals = pad(np.array([v for _, v in flip], dtype=np.int32), 0)
            xfr["u32"] = xfr["u32"].at[rows, XF_PSTAT_COL32].set(
                jnp.asarray(vals.astype(np.uint32)))
        dirty_expiry = sorted(sm.expiry.dirty_dev)
        sm.expiry.dirty_dev.clear()
        exp = [(self._xfer_row[sm.transfer_by_timestamp[ts]],
                sm.expiry.get(ts, 0))
               for ts in dirty_expiry
               if sm.transfer_by_timestamp.get(ts) in self._xfer_row]
        if exp:
            rows = pad(np.array([r for r, _ in exp], dtype=np.int32),
                       self.t_cap)
            vals = pad(np.array([v for _, v in exp], dtype=np.uint64), 0)
            c = 2 * XF_U64_IDX["expires"]
            xfr["u32"] = xfr["u32"].at[
                rows[:, None], np.array([c, c + 1])].set(
                jnp.asarray(narrow(vals[:, None])))

        # ---- orphaned ids (inline in the transfer table, val sentinel)
        dirty_orphans = sorted(sm.orphaned.dirty_dev)
        sm.orphaned.dirty_dev.clear()
        if dirty_orphans:
            st["xfer_ht"], ok = ht_insert(
                st["xfer_ht"],
                jnp.asarray(pad(np.array([o >> 64 for o in dirty_orphans],
                                         dtype=np.uint64), 0)),
                jnp.asarray(pad(np.array(
                    [o & (1 << 64) - 1 for o in dirty_orphans],
                    dtype=np.uint64), 0)),
                jnp.full(bucket(len(dirty_orphans)), ORPHAN_VAL,
                         dtype=np.int32),
                pad_mask(len(dirty_orphans)))
            assert bool(ok), "orphan hash overflow: raise capacities"

        # ---- account_events: append the mirror's new history rows
        new_events = sm.account_events[self._events_seen_abs
                                       - sm.events_base:]
        if new_events:
            evr = st["events"]
            e_cap = ev_cap(evr)
            next_row = int(evr["count"])
            assert next_row + len(new_events) <= e_cap, "e_cap exceeded"
            rows = pad(np.arange(next_row, next_row + len(new_events),
                                 dtype=np.int32), e_cap)
            cols = self._event_cols(new_events)
            count = jnp.int32(next_row + len(new_events))
            st["events"] = scatter_cols(
                {k: v for k, v in evr.items() if k != "count"},
                jnp.asarray(rows),
                {k: jnp.asarray(pad(v, 0)) for k, v in cols.items()})
            st["events"]["count"] = count
            self._events_pushed += len(new_events)
        self._events_seen_abs += len(new_events)
        self._maybe_recycle_ring()

        # ---- scalars
        st["acct_key_max"] = np.uint64(sm.accounts_key_max or 0)
        st["xfer_key_max"] = np.uint64(sm.transfers_key_max or 0)
        st["pulse_next"] = np.uint64(sm.pulse_next_timestamp)
        st["commit_ts"] = np.uint64(sm.commit_timestamp)
        # Chunks are always drained before a push, so the row map is the
        # authoritative device row count again.
        self._xfer_rows_dev = len(self._xfer_row)
        return (len(dirty_accounts) + len(new_tids) + len(flip) + len(exp)
                + len(dirty_orphans) + len(new_events))

    # ------------------------------------------------------------- pulse

    def pulse_needed(self, timestamp: int) -> bool:
        return int(self.state["pulse_next"]) <= timestamp

    def expire_pending_transfers(self, timestamp: int) -> int:
        """Expiry runs on the exact host path (rare, pulse-driven),
        through the mirror regime like any other hard batch."""
        self.resolve_windows()  # pipeline ordering
        self.drain_mirror()
        sm = self.mirror if self.mirror is not None else self._enter_mirror()
        n = sm.expire_pending_transfers(timestamp)
        self._push_dirty()
        return n


def _transfer_from_row(xfr, r: int, tid) -> Transfer:
    return Transfer(
        id=(u128.to_int(xfr["id_hi"][r], xfr["id_lo"][r])
            if tid is None else tid),
        debit_account_id=u128.to_int(xfr["dr_hi"][r], xfr["dr_lo"][r]),
        credit_account_id=u128.to_int(xfr["cr_hi"][r], xfr["cr_lo"][r]),
        amount=u128.to_int(xfr["amt_hi"][r], xfr["amt_lo"][r]),
        pending_id=u128.to_int(xfr["pid_hi"][r], xfr["pid_lo"][r]),
        user_data_128=u128.to_int(xfr["ud128_hi"][r], xfr["ud128_lo"][r]),
        user_data_64=int(xfr["ud64"][r]),
        user_data_32=int(xfr["ud32"][r]),
        timeout=int(xfr["timeout"][r]),
        ledger=int(xfr["ledger"][r]),
        code=int(xfr["code"][r]),
        flags=int(xfr["flags"][r]),
        timestamp=int(xfr["ts"][r]),
    )


def _transfers_from_arrays(ev: dict) -> list[Transfer]:
    n = len(ev["id_lo"])
    return [
        Transfer(
            id=u128.to_int(ev["id_hi"][i], ev["id_lo"][i]),
            debit_account_id=u128.to_int(ev["dr_hi"][i], ev["dr_lo"][i]),
            credit_account_id=u128.to_int(ev["cr_hi"][i], ev["cr_lo"][i]),
            amount=u128.to_int(ev["amt_hi"][i], ev["amt_lo"][i]),
            pending_id=u128.to_int(ev["pid_hi"][i], ev["pid_lo"][i]),
            user_data_128=u128.to_int(ev["ud128_hi"][i], ev["ud128_lo"][i]),
            user_data_64=int(ev["ud64"][i]),
            user_data_32=int(ev["ud32"][i]),
            timeout=int(ev["timeout"][i]),
            ledger=int(ev["ledger"][i]),
            code=int(ev["code"][i]),
            flags=int(ev["flags"][i]),
            timestamp=int(ev["ts"][i]),
        )
        for i in range(n)
    ]
