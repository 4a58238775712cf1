"""Whole-program window chain: kernel differentials + the ROUTE tests.

Part 1 (slow tier): the chain kernel executes W commit windows inside
ONE compiled program (scan or unrolled form, ops/fast_kernels.py
_create_transfers_chain*); its statuses, timestamps, created counts,
and final ledger state must equal W sequential superbatch dispatches,
and a mid-chain fallback must poison every later window on device
(state untouched) exactly like the host pipeline's chained
force_fallback.

Part 2 (quick tier): the chain as the DEFAULT serving dispatch route —
submit_window/resolve_windows and the sync window path route eligible
windows through one chain dispatch; composition with per-prepare
(ineligible-window) fallback, pipelined force_fallback poisoning, and
chaos (bit-flip mid-window -> bounded replay from the last verified
epoch) — all bit-exact vs sequential dispatch / the oracle.
"""

import numpy as np
import pytest

import jax

from tigerbeetle_tpu.ops import fast_kernels as fk
from tigerbeetle_tpu.ops.batch import transfers_soa, transfers_to_arrays
from tigerbeetle_tpu.ops.ledger import DeviceLedger, stack_superbatch
from tigerbeetle_tpu.types import Account, Transfer, TransferFlags

N = 256
STACK = 2
W = 3

# The raw-kernel differentials are jit-heavy (see pytest.ini) —
# excluded from the quick gate; run via scripts/gate.py --tier slow.
# The route tests further down are quick-tier.
slow = pytest.mark.slow


def _mk_windows(seed=5, poison_window=None):
    rng = np.random.default_rng(seed)
    nid = 10 ** 6
    ts = 10 ** 12
    windows = []
    for w in range(W):
        evs, tss = [], []
        for _ in range(STACK):
            dr = rng.integers(1, 33, N, dtype=np.uint64)
            cr = rng.integers(1, 33, N, dtype=np.uint64)
            clash = dr == cr
            cr[clash] = dr[clash] % 32 + 1
            flags = np.zeros(N, dtype=np.uint32)
            if poison_window == w:
                # balancing_credit (1<<5) is a hard E1 fallback.
                flags[3] = np.uint32(int(TransferFlags.balancing_credit))
            ev = transfers_soa(np.arange(nid, nid + N), dr, cr,
                               rng.integers(1, 1000, N), flags=flags)
            nid += N
            evs.append(ev)
            tss.append(ts)
            ts += N + 10
        ev_s, seg = stack_superbatch(evs, tss)
        windows.append((ev_s, seg))
    return windows


def test_transfers_soa_equals_transfers_to_arrays():
    """The column builder makes the dict the object path makes."""
    ids, dr, cr = [7, 8, 1 << 40], [1, 2, 3], [2, 3, 1]
    amount, flags = [5, 1 << 33, 9], [0, int(TransferFlags.pending), 0]
    got = transfers_soa(ids, dr, cr, amount, flags=flags)
    want = transfers_to_arrays([
        Transfer(id=i, debit_account_id=d, credit_account_id=c, amount=a,
                 ledger=1, code=1, flags=f)
        for i, d, c, a, f in zip(ids, dr, cr, amount, flags)])
    assert sorted(got) == sorted(want)
    for key, column in want.items():
        assert got[key].dtype == column.dtype, key
        assert (got[key] == column).all(), key
    plain = transfers_soa(ids, dr, cr, amount)
    assert not plain["flags"].any() and plain["flags"].dtype == np.uint32


def _fresh_state():
    led = DeviceLedger(a_cap=1 << 10, t_cap=1 << 13)
    led.create_accounts(
        [Account(id=i, ledger=1, code=1) for i in range(1, 33)], 1000)
    return led.state


def _stack_windows(windows):
    ev_stack = {k: np.stack([np.asarray(w[0][k]) for w in windows])
                for k in windows[0][0]}
    seg_stack = {k: np.stack([np.asarray(w[1][k]) for w in windows])
                 for k in windows[0][1]}
    return ev_stack, seg_stack


def _sequential(windows):
    state = _fresh_state()
    poisoned = None
    outs = []
    for ev_s, seg in windows:
        state, out = fk.create_transfers_super_jit(
            state, {k: jax.device_put(v) for k, v in ev_s.items()},
            {k: jax.device_put(v) for k, v in seg.items()}, poisoned)
        poisoned = out["fallback"]
        outs.append({k: np.asarray(out[k]) for k in
                     ("r_status", "r_ts", "fallback", "created_count")})
    return state, outs


@slow
@pytest.mark.parametrize("form", ["scan", "unrolled"])
@pytest.mark.parametrize("poison_window", [None, 1])
def test_chain_matches_sequential(form, poison_window):
    windows = _mk_windows(poison_window=poison_window)
    want_state, want = _sequential(windows)

    ev_stack, seg_stack = _stack_windows(windows)
    chain = (fk.create_transfers_chain_jit if form == "scan"
             else fk.create_transfers_chain_unrolled_jit)
    got_state, outs = chain(_fresh_state(), ev_stack, seg_stack)

    for w in range(W):
        for key in ("r_status", "r_ts", "created_count", "fallback"):
            np.testing.assert_array_equal(
                np.asarray(outs[key])[w], want[w][key],
                err_msg=f"window {w} {key} ({form})")
    if poison_window is not None:
        fbs = np.asarray(outs["fallback"])
        assert not fbs[0] and fbs[1] and fbs[2]  # suffix poisoned
    # Final ledger state identical (the poisoned windows left it alone).
    for table, mat in (("transfers", "u32"), ("accounts", "u32"),
                       ("accounts", "bal")):
        np.testing.assert_array_equal(
            np.asarray(got_state[table][mat]),
            np.asarray(want_state[table][mat]),
            err_msg=f"{table}.{mat} diverged ({form})")
    np.testing.assert_array_equal(
        np.asarray(got_state["transfers"]["count"]),
        np.asarray(want_state["transfers"]["count"]))


# ===================================================== route tests (quick)
# The chain as the DEFAULT dispatch route. Small shapes (k=3 prepares of
# 48-64 events, 1024-row pad bucket) keep these inside the quick tier.

U128MAX = (1 << 128) - 1
PEND = int(TransferFlags.pending)
POST = int(TransferFlags.post_pending_transfer)


def _mk_serving(recycle=True):
    from tigerbeetle_tpu.oracle import StateMachineOracle

    led = DeviceLedger(a_cap=1 << 10, t_cap=1 << 13,
                       write_through=StateMachineOracle())
    led.create_accounts(
        [Account(id=i, ledger=1, code=1) for i in range(1, 65)], 120)
    led.recycle_events = recycle
    led.retain_flush_columns = recycle
    return led


def _route_windows(rng, n_windows, k=3, n=48, base=10 ** 6,
                   poison=None):
    out, nid, ts = [], base, 10 ** 12
    for w in range(n_windows):
        evs, tss = [], []
        for b in range(k):
            batch = []
            for _ in range(n):
                dr = int(rng.integers(1, 65))
                batch.append(Transfer(
                    id=nid, debit_account_id=dr,
                    credit_account_id=dr % 64 + 1,
                    amount=int(rng.integers(1, 100)), ledger=1, code=1))
                nid += 1
            if poison is not None and (w, b) == poison:
                # duplicate id within ONE prepare: hard E2 — the chain
                # route must isolate it to this prepare.
                batch[-1] = Transfer(
                    id=batch[0].id, debit_account_id=1,
                    credit_account_id=2, amount=1, ledger=1, code=1)
            ts += n + 10
            evs.append(batch)
            tss.append(ts)
        out.append((evs, tss))
    return out


def _drive_pipelined(led, windows):
    """Depth-2 pipelined submit/resolve; returns per-window result
    lists in order (the serving driver's shape)."""
    from tigerbeetle_tpu.ops.batch import transfers_to_arrays

    pending, results = [], []
    for evs, tss in windows:
        arrays = [transfers_to_arrays(b) for b in evs]
        tk = led.submit_window(arrays, tss)
        if tk is None:
            led.resolve_windows()
            while pending:
                results.append(pending.pop(0).results[1])
            results.append(led.create_transfers_window(arrays, tss))
            continue
        pending.append(tk)
        if len(pending) > 1:
            led.resolve_windows(count=1)
            while pending and pending[0].results is not None:
                results.append(pending.pop(0).results[1])
    led.resolve_windows()
    for tk in pending:
        results.append(tk.results[1])
    return results


def _drive_sync(led, windows):
    from tigerbeetle_tpu.ops.batch import transfers_to_arrays

    return [led.create_transfers_window(
        [transfers_to_arrays(b) for b in evs], tss)
        for evs, tss in windows]


def _assert_results_equal(res_a, res_b):
    assert len(res_a) == len(res_b)
    for wa, wb in zip(res_a, res_b):
        assert len(wa) == len(wb)
        for (st_a, ts_a), (st_b, ts_b) in zip(wa, wb):
            np.testing.assert_array_equal(np.asarray(st_a),
                                          np.asarray(st_b))
            np.testing.assert_array_equal(np.asarray(ts_a),
                                          np.asarray(ts_b))


def _oracle_with_accounts():
    from tigerbeetle_tpu.oracle import StateMachineOracle

    orc = StateMachineOracle()
    orc.create_accounts(
        [Account(id=i, ledger=1, code=1) for i in range(1, 65)], 120)
    return orc


def test_chain_route_default_bit_exact():
    """Eligible windows take the chain route BY DEFAULT — pipelined and
    sync — with zero host fallbacks, bit-exact vs the oracle."""
    rng = np.random.default_rng(11)
    windows = _route_windows(rng, 3)
    led_p, led_s = _mk_serving(), _mk_serving()
    orc = _oracle_with_accounts()

    res_p = _drive_pipelined(led_p, windows)
    res_s = _drive_sync(led_s, windows)
    for evs, tss in windows:
        for b, tb in zip(evs, tss):
            orc.create_transfers(b, tb)
    _assert_results_equal(res_p, res_s)
    for led in (led_p, led_s):
        stats = led.fallback_stats()
        assert stats["routes"]["windows"] == {"chain": 3}, stats
        assert stats["host_fallbacks"] == 0, stats
        assert stats["window_fallbacks"] == 0, stats
        host = led.to_host()
        assert host.accounts == orc.accounts
        assert host.transfers == orc.transfers
        assert host.pending_status == orc.pending_status
    # Write-through capture parity on the clean run: the serving-mode
    # flush columns of both drivers agree chunk for chunk (per-prepare
    # watermarks survive the chain route).
    led_p.drain_mirror()
    led_s.drain_mirror()
    cols_p = led_p.take_flush_columns()
    cols_s = led_s.take_flush_columns()
    assert [c[3] for c in cols_p] == [c[3] for c in cols_s]
    for cp, cs in zip(cols_p, cols_s):
        if cp[3]:
            for key in ("id_hi", "id_lo", "ts", "flags"):
                np.testing.assert_array_equal(
                    np.asarray(cp[0][key]), np.asarray(cs[0][key]))


def test_chain_route_cross_prepare_pend_refs_go_deep():
    """A window with cross-prepare pending references pre-routes to the
    deep superbatch tier (the chain's plain body cannot resolve
    in-window defs) — still zero host fallbacks, oracle-exact."""
    rng = np.random.default_rng(13)
    nid, ts = 5 * 10 ** 6, 10 ** 12
    pends = [Transfer(id=nid + i, debit_account_id=1 + i % 64,
                      credit_account_id=(1 + i) % 64 + 1, amount=10,
                      ledger=1, code=1, flags=PEND, timeout=1000)
             for i in range(48)]
    posts = [Transfer(id=nid + 100 + i, pending_id=nid + i,
                      amount=U128MAX, flags=POST)
             for i in range(48)]
    windows = [([pends, posts], [ts + 58, ts + 116])]
    led = _mk_serving()
    orc = _oracle_with_accounts()
    res = _drive_pipelined(led, windows)
    want = [[(r.timestamp, int(r.status))
             for r in orc.create_transfers(b, tb)]
            for b, tb in zip(*windows[0])]
    got = [[(int(t), int(s)) for s, t in zip(st.tolist(), tl.tolist())]
           for st, tl in res[0]]
    assert got == want
    stats = led.fallback_stats()
    assert stats["routes"]["windows"] == {"super_deep": 1}, stats
    assert stats["host_fallbacks"] == 0, stats


def test_chain_route_per_batch_fallback_and_poisoning():
    """Chain x pipelined force_fallback poisoning: an ineligible prepare
    mid-window falls back PER PREPARE (clean prefix committed), the
    poisoned suffix and the next in-flight window replay — results,
    mirror state, and flush columns bit-exact vs the sync path and the
    oracle."""
    rng = np.random.default_rng(17)
    windows = _route_windows(rng, 4, base=2 * 10 ** 6, poison=(1, 1))
    led_p, led_s = _mk_serving(), _mk_serving()
    orc = _oracle_with_accounts()

    res_p = _drive_pipelined(led_p, windows)
    res_s = _drive_sync(led_s, windows)
    for evs, tss in windows:
        for b, tb in zip(evs, tss):
            orc.create_transfers(b, tb)
    _assert_results_equal(res_p, res_s)
    for led in (led_p, led_s):
        stats = led.fallback_stats()
        assert stats["routes"]["chain_batch_fallbacks"].get(
            "e2_collision", 0) >= 1, stats
        host = led.to_host()
        assert host.accounts == orc.accounts
        assert host.transfers == orc.transfers
        assert set(host.orphaned) == set(orc.orphaned)
    # (Flush-column chunk parity is asserted on the CLEAN run above:
    # after a host fallback the mirror-regime hysteresis may probe the
    # fast path one batch apart between the two drivers — both exact,
    # but chunk boundaries legitimately differ.)


def test_chain_route_chaos_bitflip_bounded_replay():
    """Chain x chaos: a bit flipped in device HBM mid-run is caught by
    the next epoch's state digest; the supervisor replays AT MOST the
    windows since the last verified epoch and resumes — with the chain
    route serving the windows before and after recovery."""
    import jax.numpy as jnp

    from tigerbeetle_tpu.serving import ServingSupervisor
    from tigerbeetle_tpu.trace import Event, Tracer

    tracer = Tracer(pid=0)
    sup = ServingSupervisor(a_cap=1 << 10, t_cap=1 << 13,
                            epoch_interval=2, seed=7, tracer=tracer)
    sup.create_accounts(
        [Account(id=i, ledger=1, code=1) for i in range(1, 65)], 120)
    rng = np.random.default_rng(23)
    windows = _route_windows(rng, 5, base=3 * 10 ** 6)
    for w, (evs, tss) in enumerate(windows):
        if w == 2:
            # Flip one bit in a live account balance limb on device
            # (HBM corruption model): the epoch check after window 3
            # must catch it via the state digest.
            bal = np.asarray(sup.led.state["accounts"]["bal"]).copy()
            bal[1, 4] ^= np.uint64(1 << 17)
            sup.led.state["accounts"]["bal"] = jnp.asarray(bal)
        sup.create_transfers_window(evs, tss)
    sup.verify_epoch()
    assert sup.counters["recoveries"].get("state_digest", 0) >= 1, \
        sup.counters
    # Bounded replay: never more windows than one epoch interval.
    assert sup.counters["replayed_windows"] <= 2 * sup.epoch_interval
    # The route was the chain before and after recovery (the rebuilt
    # ledger serves through the same default), and the supervisor
    # tagged it into the trace catalog.
    assert sup.led.fallback_stats()["routes"]["windows"].get(
        "chain", 0) >= 1
    assert Event.dispatch_route.name in tracer.emitted
    # Post-recovery ground truth: the full history equals a pure oracle
    # replay of every submitted window.
    orc = _oracle_with_accounts()
    want = []
    for evs, tss in windows:
        want.append([[(r.timestamp, int(r.status))
                      for r in orc.create_transfers(b, tb)]
                     for b, tb in zip(evs, tss)])
    assert sup.history[1:] == want


def test_chain_route_counters_reach_bench_record():
    """The route record rides fallback_stats() -> bench diagnostics."""
    rng = np.random.default_rng(31)
    led = _mk_serving()
    _drive_sync(led, _route_windows(rng, 2, base=4 * 10 ** 6))
    stats = led.fallback_stats()
    assert stats["routes"]["windows"] == {"chain": 2}
    assert stats["routes"]["chain_batch_fallbacks"] == {}
