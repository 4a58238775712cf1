"""Benchmark harness: workload generation + measurement.

The package-level core of the repo's bench.py driver (reference:
src/tigerbeetle/benchmark_load.zig — "load accepted ... tx/s"): builds
Zipfian/uniform workloads as SoA arrays, runs them through the device
ledger's scan path, and measures accepted transfers / wall time. The five
configs mirror BASELINE.md.
"""

from __future__ import annotations

import time

import numpy as np

from .constants import BATCH_MAX, U128_MAX
from .types import (
    Account,
    AccountFlags,
    Transfer,
    TransferFlags,
)

BASELINE_TPS = 1_000_000  # reference design claim, single core
TARGET_TPS = 10_000_000  # driver target, single v5e chip
N = BATCH_MAX


def _soa(ids, dr, cr, amount, flags=None, pid=None, timeout=None):
    n = len(ids)
    z = np.zeros(n, dtype=np.uint64)
    z32 = np.zeros(n, dtype=np.uint32)
    return dict(
        id_hi=z.copy(), id_lo=np.asarray(ids, dtype=np.uint64),
        dr_hi=z.copy(), dr_lo=np.asarray(dr, dtype=np.uint64),
        cr_hi=z.copy(), cr_lo=np.asarray(cr, dtype=np.uint64),
        amt_hi=z.copy(), amt_lo=np.asarray(amount, dtype=np.uint64),
        pid_hi=z.copy(),
        pid_lo=z.copy() if pid is None else np.asarray(pid, dtype=np.uint64),
        ud128_hi=z.copy(), ud128_lo=z.copy(), ud64=z.copy(),
        ud32=z32.copy(),
        timeout=z32.copy() if timeout is None else np.asarray(timeout, dtype=np.uint32),
        ledger=np.ones(n, dtype=np.uint32),
        code=np.ones(n, dtype=np.uint32),
        flags=z32.copy() if flags is None else np.asarray(flags, dtype=np.uint32),
        ts=z.copy(),
    )


# Per-config routing/fallback diagnostics, recorded by each config as
# it finishes and emitted by bench.py into the run record — "no host
# fallbacks" is a measured invariant of every bench config, not an
# assumption. Keyed "configN" -> DeviceLedger.fallback_stats().
CONFIG_DIAGNOSTICS: dict = {}

# Per-config dispatch-route record: which kernel route each config's
# windows took ("chain" = the scan-form whole-window dispatch, the
# default) and the window depths used — emitted into bench.py's ##diag
# record and the final metric JSON, so a silent route degradation (the
# old power-of-two stack selection degraded odd batch counts to
# stack 1) is visible in every run record.
CONFIG_ROUTES: dict = {}


def _record_diag(key, led) -> None:
    try:
        CONFIG_DIAGNOSTICS[key] = led.fallback_stats()
        routes = led.fallback_stats().get("routes")
        if routes and routes.get("windows"):
            CONFIG_ROUTES.setdefault(key, {}).update(
                windows=routes["windows"])
    except Exception:  # diagnostics must never fail a bench run
        pass


def _record_choice(key, **chosen) -> None:
    """What a backend-dependent default resolved to (window depth, batch
    width): the run record must show which branch a chip run took."""
    CONFIG_ROUTES.setdefault(key, {}).update(chosen)


def _record_route(key, route, depths) -> None:
    CONFIG_ROUTES.setdefault(key, {}).update(
        route=route, window_depths=sorted(set(depths)))


def _make_ledger(account_count, a_cap=1 << 15, t_cap=1 << 21):
    from .ops.ledger import DeviceLedger

    led = DeviceLedger(a_cap=a_cap, t_cap=t_cap)
    accounts = [Account(id=i, ledger=1, code=1)
                for i in range(1, account_count + 1)]
    for lo in range(0, account_count, BATCH_MAX):
        chunk = accounts[lo:lo + BATCH_MAX]
        led.create_accounts(chunk, timestamp=lo + len(chunk))
    assert led.fallbacks == 0
    return led


# Warmup dispatches one small fixed set of batches so the single compiled
# program (one batch shape) serves all configs and batch counts — compile
# cost is paid once, not per config.
B_CHUNK = 8

# Prepares executed per kernel dispatch in the scan configs (commit-window
# aggregation): dispatch cost has a fixed term, so stacking amortizes it
# (by how much on a local chip is not measured yet). On CPU the kernel is
# compute-bound (no dispatch overhead to amortize, and the window-sized
# sorts cost more than K batch-sized ones), so stacking is TPU-only.
SUPERBATCH_MAX = 32


def _superbatch_default(n_batches):
    """Window depth per dispatch. The chain route (scan-form whole-
    window dispatch) accepts ARBITRARY depths — the old selection only
    admitted power-of-two stacks <= 32 dividing the batch count, which
    silently degraded odd-count windows to stack 1. At most two program
    shapes compile per run (the full depth + one tail)."""
    import jax

    if jax.default_backend() != "tpu":
        return 1
    return min(SUPERBATCH_MAX, n_batches)


def _run_scan(led, evs, ts0, stack=None, diag_key=None):
    """Dispatch batches back-to-back with no mid-run host sync; returns
    (accepted, elapsed). Host-side padding is staged before the clock.

    stack=1: one straight-line (control-flow-free) program per batch;
    the poison flag threads through dispatches as a DEVICE value, so a
    mid-run fallback masks every later batch without waiting on any
    per-batch result.

    stack=K (the serving route): K prepares per dispatch via the
    SCAN-FORM CHAIN kernel — ONE compiled program whose body executes
    each prepare against the state evolved by the previous ones
    (create_transfers_chain_jit, the same route DeviceLedger's
    submit_window takes). Program op count is ~constant in K, the
    poison scalar rides the scan carry between prepares AND between
    dispatches, and K is arbitrary (a tail window of a different depth
    compiles one extra shape). The chosen route + depths land in
    CONFIG_ROUTES -> bench.py's ##diag record."""
    import jax

    from .ops.fast_kernels import (
        _accum_jit,
        _accum_sum_jit,
        create_transfers_chain_jit,
        create_transfers_fast_jit,
    )
    from .ops.ledger import pad_transfer_events, stack_chain_window

    stack = stack or _superbatch_default(len(evs))
    if diag_key:
        _record_choice(diag_key, backend=jax.default_backend(),
                       stack=stack)
    tss = [int(ts0) + i * (N + 10) for i in range(len(evs))]
    poisoned = jax.device_put(np.bool_(False))
    accepted_dev = jax.device_put(np.int64(0))
    if stack > 1:
        groups = []
        depths = []
        for lo in range(0, len(evs), stack):
            ev_c, seg_c = stack_chain_window(
                evs[lo:lo + stack], tss[lo:lo + stack])
            depths.append(len(evs[lo:lo + stack]))
            groups.append((
                {k: jax.device_put(v) for k, v in ev_c.items()},
                {k: jax.device_put(v) for k, v in seg_c.items()}))
        if diag_key is not None:
            _record_route(diag_key, "chain", depths)
        t0 = time.perf_counter()
        for ev_c, seg_c in groups:
            led.state, outs = create_transfers_chain_jit(
                led.state, ev_c, seg_c, poisoned)
            poisoned = outs["fallback"][-1]
            accepted_dev = _accum_sum_jit(accepted_dev,
                                          outs["created_count"])
        accepted, bad = jax.device_get((accepted_dev, poisoned))
        elapsed = time.perf_counter() - t0
        assert not bool(bad), "unexpected fallback"
        return int(accepted), elapsed

    padded = [{k: jax.device_put(v) for k, v in
               pad_transfer_events(e).items()} for e in evs]
    if diag_key is not None:
        _record_route(diag_key, "per_batch", [1])
    n_arr = np.int32(N)
    t0 = time.perf_counter()
    for ev, ts in zip(padded, tss):
        led.state, outs = create_transfers_fast_jit(
            led.state, ev, np.uint64(ts), n_arr, force_fallback=poisoned)
        poisoned = outs["fallback"]
        accepted_dev = _accum_jit(accepted_dev, outs["created_count"])
    accepted, bad = jax.device_get((accepted_dev, poisoned))
    elapsed = time.perf_counter() - t0
    assert not bool(bad), "unexpected fallback"
    return int(accepted), elapsed


def _warm_and_run(led, mk, batches, diag_key=None):
    """Warm up the exact program shape the timed run will use (compile
    is paid once, outside the clock), then measure."""
    stack = _superbatch_default(batches)
    warm = stack if stack > 1 else B_CHUNK
    _run_scan(led, [mk(b) for b in range(-warm, 0)],
              np.uint64(10**11), stack=stack)
    # Warm the tail-window shape too (arbitrary depths compile a second
    # program), still outside the clock.
    tail = batches % stack
    if stack > 1 and tail:
        _run_scan(led, [mk(b) for b in range(-warm - tail, -warm)],
                  np.uint64(10**11 + 10**9), stack=tail)
    out = _run_scan(led, [mk(b) for b in range(batches)],
                    np.uint64(10**12), stack=stack, diag_key=diag_key)
    if diag_key is not None:
        _record_diag(diag_key, led)
    return out


def bench_config1(batches):
    """2 hot accounts, one ledger."""
    led = _make_ledger(2)
    rng = np.random.default_rng(1)

    def mk(b):
        base = 10**7 + b * N
        ids = np.arange(base, base + N)
        dr = np.full(N, 1)
        cr = np.full(N, 2)
        return _soa(ids, dr, cr, rng.integers(1, 1000, N))

    return _warm_and_run(led, mk, batches, diag_key="config1")


def bench_config2(batches, account_count=10_000):
    """Uniform random transfers over 10K accounts (fuzz shape)."""
    led = _make_ledger(account_count)
    rng = np.random.default_rng(2)

    def mk(b):
        base = 10**7 + b * N
        ids = np.arange(base, base + N)
        dr = rng.integers(1, account_count + 1, N, dtype=np.uint64)
        cr = rng.integers(1, account_count + 1, N, dtype=np.uint64)
        clash = dr == cr
        cr[clash] = dr[clash] % account_count + 1
        return _soa(ids, dr, cr, rng.integers(1, 10**6, N))

    return _warm_and_run(led, mk, batches, diag_key="config2")


def bench_config_zipfian(batches, account_count=10_000, theta=0.99):
    """Zipfian hot accounts — the reference benchmark's default workload
    shape (src/tigerbeetle/benchmark_load.zig:66-77 account_count_hot)."""
    from .utils import ZipfianGenerator

    led = _make_ledger(account_count)
    zipf = ZipfianGenerator(account_count, theta=theta, seed=7)
    rng = np.random.default_rng(7)

    def mk(b):
        base = 10**7 + b * N
        ids = np.arange(base, base + N)
        dr = zipf.draw(N).astype(np.uint64) + 1
        cr = zipf.draw(N).astype(np.uint64) + 1
        clash = dr == cr
        cr[clash] = dr[clash] % account_count + 1
        return _soa(ids, dr, cr, rng.integers(1, 1000, N))

    return _warm_and_run(led, mk, batches)


def bench_config3(batches, account_count=1000):
    """Linked chains: all-or-nothing pairs, ~25% of chains failing."""
    led = _make_ledger(account_count)
    rng = np.random.default_rng(3)
    linked = int(TransferFlags.linked)

    def mk(b):
        base = 10**7 + b * N
        ids = np.arange(base, base + N)
        dr = rng.integers(1, account_count + 1, N, dtype=np.uint64)
        cr = rng.integers(1, account_count + 1, N, dtype=np.uint64)
        clash = dr == cr
        cr[clash] = dr[clash] % account_count + 1
        flags = np.zeros(N, dtype=np.uint32)
        flags[0::2] = linked  # pairs: even=head, odd=terminator
        # poison ~25% of chains: terminator debits a missing account
        bad = rng.random(N // 2) < 0.25
        dr[1::2][bad] = account_count + 10**6
        return _soa(ids, dr, cr, rng.integers(1, 1000, N), flags=flags)

    return _warm_and_run(led, mk, batches, diag_key="config3")


def bench_config4(batches=2, n=None, account_count=64):
    """Two-phase under balance limits — the hard-semantics config: breach
    batches run the on-device limit fixpoint (ops/fast_kernels.py
    LIMIT_FIXPOINT_ROUNDS); only cascades deeper than the round budget
    would fall back to the exact host path.

    Batch size is platform-tuned (the workload — pending + post/void
    under limits — doesn't pin it): on TPU the fixpoint's ~220-op cost
    is nearly row-count-independent, so full protocol-max batches
    amortize it 8x; on CPU the kernel is compute-bound and 1024-row
    buckets win."""
    import jax

    from .ops.ledger import DeviceLedger

    if n is None:
        n = N if jax.default_backend() == "tpu" else 1024

    from .ops.ledger import _pad_bucket

    # Room for (batches + warmup) * 2 * n transfers plus orphan entries
    # (~half of pend events breach): next power of two with 2x headroom.
    need = (batches + 1) * 2 * n * 2
    t_cap = 1 << max(14, (need - 1).bit_length())
    led = DeviceLedger(a_cap=1 << 12, t_cap=t_cap)
    # Compile all kernel tiers now (incl. the deep-fixpoint escalation)
    # so a mid-run cascade never pays a compile inside the clock.
    # No balancing tiers: the bench workloads carry no balancing flags.
    led.warm_kernels(_pad_bucket(n), balancing=False)
    limit = int(AccountFlags.debits_must_not_exceed_credits)
    accounts = [Account(id=i, ledger=1, code=1,
                        flags=limit if i % 2 == 0 else 0)
                for i in range(1, account_count + 1)]
    led.create_accounts(accounts, timestamp=account_count)
    rng = np.random.default_rng(4)
    pend = int(TransferFlags.pending)
    post = int(TransferFlags.post_pending_transfer)
    void = int(TransferFlags.void_pending_transfer)

    from .types import CreateTransferStatus

    created_code = np.uint32(int(CreateTransferStatus.created))
    # Commit-window aggregation (TPU): the deep superbatch tier resolves
    # in-window pending references (pend batch i, post/void batch i+1)
    # natively, so the alternating two-phase workload windows just like
    # config2's scans — W stacked prepares per dispatch amortizes the
    # fixed dispatch cost. On CPU the
    # kernel is compute-bound and windowing only adds sort width.
    # One compiled window shape only: W_PAIRS must divide `batches` (a
    # tail window of a different K would compile inside the timed region).
    W_PAIRS = 1
    if jax.default_backend() == "tpu":
        for w in (4, 3, 2):
            if batches % w == 0:
                W_PAIRS = w
                break
    _record_choice("config4", backend=jax.default_backend(),
                   batch_width=n, window_pairs=W_PAIRS)
    accepted = 0
    ts = 10**12
    next_id = 10**7

    def mk_pair_batches(ts_base):
        nonlocal next_id
        out = []
        pend_base = next_id
        next_id += n
        dr = rng.integers(1, account_count + 1, n, dtype=np.uint64)
        cr = rng.integers(1, account_count + 1, n, dtype=np.uint64)
        clash = dr == cr
        cr[clash] = dr[clash] % account_count + 1
        ev = _soa(np.arange(pend_base, pend_base + n), dr, cr,
                  rng.integers(1, 100, n),
                  flags=np.full(n, pend, dtype=np.uint32))
        out.append((ev, ts_base + n + 10))
        even = np.arange(n) % 2 == 0
        rev = _soa(np.arange(next_id, next_id + n),
                   np.zeros(n, dtype=np.uint64),
                   np.zeros(n, dtype=np.uint64),
                   np.where(even, np.uint64(U128_MAX & ((1 << 64) - 1)),
                            np.uint64(0)),
                   flags=np.where(even, post, void).astype(np.uint32),
                   pid=np.arange(pend_base, pend_base + n))
        rev["amt_hi"] = np.where(even, np.uint64(U128_MAX >> 64),
                                 np.uint64(0))
        rev["ledger"] = np.zeros(n, dtype=np.uint32)  # inherit from pending
        rev["code"] = np.zeros(n, dtype=np.uint32)
        next_id += n
        out.append((rev, ts_base + 2 * (n + 10)))
        return out

    def ticket_created(tk):
        _, res = tk.results
        return sum(int((np.asarray(st) == created_code).sum())
                   for st, _ in res)

    # Depth-2 pipelined windows (TPU): submit window k+1 before
    # resolving k — the upload + dispatch overlap k's execution. Two
    # warmup windows compile both kernel variants (unchained + chained
    # force_fallback) before the clock starts.
    t0 = None
    pending: list = []
    warmup_left = 2 if W_PAIRS > 1 else 1
    b = 0
    while b < batches or warmup_left:
        if warmup_left == 0 and t0 is None:
            led.resolve_windows()
            pending.clear()  # warmup events don't count
            accepted = 0
            t0 = time.perf_counter()
        pairs = W_PAIRS if warmup_left else min(W_PAIRS, batches - b)
        window = []
        for _ in range(pairs):
            window.extend(mk_pair_batches(ts))
            ts += 2 * (n + 10)
        if W_PAIRS > 1:
            tk = led.submit_window(
                [ev for ev, _ in window], [t for _, t in window])
            assert tk is not None, "config4 window unexpectedly ineligible"
            pending.append(tk)
            if len(pending) > 1:
                led.resolve_windows(count=1)
                accepted += ticket_created(pending.pop(0))
        else:
            for ev, ts_b in window:
                st, _ = led.create_transfers_soa(ev, ts_b)
                accepted += int((np.asarray(st) == created_code).sum())
        if warmup_left:
            warmup_left -= 1
        else:
            b += pairs
    led.resolve_windows()
    for tk in pending:
        accepted += ticket_created(tk)
    elapsed = time.perf_counter() - t0
    _record_diag("config4", led)
    return accepted, elapsed


def bench_config6_serving(batches=24, account_count=10_000):
    """The database serving path (VERDICT r1 #2): the same boundary a
    replica commits through — StateMachine(engine='device').commit() with
    multi-batch wire bodies — so the benched engine IS the served engine.
    Covers body decode, the vectorized device kernel, the write-through
    host mirror, and result encode (reference: execute path
    src/state_machine.zig:2564 + benchmark_load.zig)."""
    from . import multi_batch
    from .state_machine import StateMachine
    from .types import Operation

    sm = StateMachine(engine="device", a_cap=1 << 15, t_cap=1 << 19)
    rng = np.random.default_rng(6)
    ts = 1000
    accounts = [Account(id=i, ledger=1, code=1)
                for i in range(1, account_count + 1)]
    for lo in range(0, account_count, N):
        chunk = accounts[lo:lo + N]
        ts += len(chunk) + 10
        sm.create_accounts(chunk, ts)

    # One trailer element (128 B) rides in the 1 MiB body, so a single
    # multi-batch holds N-1 events (reference: batch_max derivation,
    # src/state_machine.zig:336-380).
    nb = N - 1

    def mk_body(base):
        dr = rng.integers(1, account_count + 1, nb, dtype=np.uint64)
        cr = rng.integers(1, account_count + 1, nb, dtype=np.uint64)
        clash = dr == cr
        cr[clash] = dr[clash] % account_count + 1
        amt = rng.integers(1, 10**6, nb)
        payload = b"".join(
            Transfer(id=int(base + i), debit_account_id=int(dr[i]),
                     credit_account_id=int(cr[i]), amount=int(amt[i]),
                     ledger=1, code=1).pack()
            for i in range(nb))
        return multi_batch.encode([payload], 128)

    next_id = 10**7
    bodies = []
    for _ in range(batches + 1):
        bodies.append(mk_body(next_id))
        next_id += nb

    # Serving commits aggregate a window of committed prepares per device
    # dispatch when a backlog exists (commit_window; the reference's
    # pipeline admits 8 prepares in flight, src/config.zig:155). Latency
    # is recorded per WINDOW (submit -> resolve wall) into a log2
    # histogram — the window is the unit that completes; smearing its
    # latency as latency/W per prepare fabricated W identical samples
    # and flattened the true distribution (see PERF.md).
    import jax

    W = 1
    if jax.default_backend() == "tpu":
        for w in (8, 4, 2):
            if batches % w == 0:
                W = w
                break
    _record_choice("config6", backend=jax.default_backend(),
                   window_depth=W)
    ts += nb + 10
    sm.commit(Operation.create_transfers, bodies[0], ts)  # warmup compile
    if W > 1:
        # Warm BOTH pipelined window shapes: the first in-flight window
        # compiles the unchained kernel variant, the second compiles the
        # fallback-chained one (force_fallback scalar) + the device-start
        # delta gather.
        for _ in range(2):
            wts = []
            for _ in range(W):
                ts += nb + 10
                wts.append(ts)
            rec = sm.submit_commit_window(
                Operation.create_transfers,
                [mk_body(next_id + i * nb) for i in range(W)], wts)
            assert rec is not None
            next_id += W * nb
        sm.resolve_commit_windows()
    from .trace.histogram import Histogram

    n_before = len(sm.state.transfers)
    hist = Histogram()  # per-window latency, milliseconds
    t0 = time.perf_counter()
    if W > 1:
        # Depth-2 pipelined serving: submit window k+1 before resolving
        # window k — upload + dispatch overlap the previous window's
        # execution (the reference pipelines 8 prepares the same way,
        # src/config.zig:155). One histogram sample per window.
        def note_done(done_recs):
            now = time.perf_counter()
            for done in done_recs:
                hist.record((now - done["_tb"]) * 1000)

        wins = []
        for lo in range(1, len(bodies), W):
            window = bodies[lo:lo + W]
            wts = []
            for _ in window:
                ts += nb + 10
                wts.append(ts)
            wins.append((window, wts))
        for i, (window, wts) in enumerate(wins):
            tb = time.perf_counter()
            rec = sm.submit_commit_window(
                Operation.create_transfers, window, wts)
            if rec is None:
                note_done(sm.resolve_commit_windows())
                sm.commit_window(Operation.create_transfers, window, wts)
                hist.record((time.perf_counter() - tb) * 1000)
                continue
            rec["_tb"] = tb
            # Stage window k+1's operand pack NOW, so it runs on the
            # staging worker while this iteration's blocking resolve
            # waits on window k's device execution (double-buffered
            # host↔device overlap; the submit below consumes the pack).
            if i + 1 < len(wins):
                sm.stage_commit_window(
                    Operation.create_transfers, wins[i + 1][0],
                    wins[i + 1][1])
            if len(sm._pending_windows) > 1:
                note_done(sm.resolve_commit_windows(count=1))
        note_done(sm.resolve_commit_windows())
    else:
        for body in bodies[1:]:
            ts += nb + 10
            tb = time.perf_counter()
            sm.commit(Operation.create_transfers, body, ts)
            hist.record((time.perf_counter() - tb) * 1000)
    elapsed = time.perf_counter() - t0
    # The commit path defers mirror materialization (columnar chunks,
    # drained lazily at read boundaries). Time the drain separately and
    # report it — nothing hidden: config6 tps is the commit boundary,
    # drain_ms is the deferred host-object cost a query/durability reader
    # would pay once, amortized over the whole run.
    td = time.perf_counter()
    sm.led.drain_mirror()
    drain_ms = (time.perf_counter() - td) * 1000
    assert sm.led.fallbacks == 0, "serving bench unexpectedly fell back"
    _record_diag("config6", sm.led)
    accepted = len(sm.state.transfers) - n_before
    # True per-window latency percentiles out of the histogram (~1%
    # relative error; p100 is the exact max the histogram carries).
    # The serialized histogram rides in the record so the SLO engine
    # and the gate's bench-regression leg can re-derive any quantile
    # (the reference reports p100, benchmark_load.zig:587).
    latency = None
    if hist.count:
        latency = {
            "p50_ms": round(hist.quantile(0.50), 3),
            "p95_ms": round(hist.quantile(0.95), 3),
            "p99_ms": round(hist.quantile(0.99), 3),
            "p999_ms": round(hist.quantile(0.999), 3),
            "p100_ms": round(hist.max, 3),
            "windows": hist.count,
            "drain_ms_total": round(drain_ms, 1),
            "sustained_tps": round(
                accepted / (elapsed + drain_ms / 1000), 1),
            "histogram": hist.to_dict(),
        }
    return accepted, elapsed, latency


def parity_config5(n_batches=6, batch=256):
    """Differential check: DeviceLedger vs sequential oracle, mixed workload."""
    from .oracle import StateMachineOracle
    from .ops.ledger import DeviceLedger

    led = DeviceLedger(a_cap=1 << 12, t_cap=1 << 14)
    sm = StateMachineOracle()
    rng = np.random.default_rng(5)
    accts = [Account(id=i, ledger=1, code=1) for i in range(1, 101)]
    for eng in (led, sm):
        eng.create_accounts(accts, 100)
    ts = 10**12
    next_id = 10**6
    pend = int(TransferFlags.pending)
    post = int(TransferFlags.post_pending_transfer)
    for b in range(n_batches):
        events = []
        for i in range(batch):
            roll = rng.random()
            tid = next_id
            next_id += 1
            if roll < 0.7:
                events.append(Transfer(
                    id=tid, debit_account_id=int(rng.integers(0, 110)),
                    credit_account_id=int(rng.integers(1, 110)),
                    amount=int(rng.integers(0, 1000)), ledger=1,
                    code=int(rng.integers(0, 2))))
            elif roll < 0.85:
                events.append(Transfer(
                    id=tid, debit_account_id=int(rng.integers(1, 101)),
                    credit_account_id=1 + int(rng.integers(1, 100)),
                    amount=int(rng.integers(1, 100)), ledger=1, code=1,
                    flags=pend))
            else:
                events.append(Transfer(
                    id=tid, pending_id=int(rng.integers(10**6, next_id)),
                    amount=U128_MAX, flags=post))
        for e in events:
            # Post/void events legitimately carry zero account ids (sentinel
            # = inherit from the pending transfer); only fix regular events.
            if (e.flags & post) == 0 and e.debit_account_id == e.credit_account_id:
                e.credit_account_id = e.debit_account_id % 100 + 1
        ts += batch + 10
        got = led.create_transfers(events, ts)
        want = sm.create_transfers(events, ts)
        if [(r.timestamp, r.status) for r in got] != [
                (r.timestamp, r.status) for r in want]:
            return False
    host = led.to_host()
    return (host.accounts == sm.accounts and host.transfers == sm.transfers
            and host.pending_status == sm.pending_status
            and host.orphaned == sm.orphaned
            and host.account_events == sm.account_events)



def bench_admission(rounds=24, sessions=100_000, reqs_per_round=96,
                    seed=83):
    """Sessionized-Zipfian admission bench (ISSUE 18): the admission
    plane in front of a real ServingSupervisor under an offered load
    ~2x the pump's window capacity, sessions drawn Zipfian-hot from a
    `sessions`-deep population on a deterministic virtual clock.

    The success metric of the serving path under overload is NOT raw
    tps — it is SUSTAINED admitted tps plus per-class admitted
    queue-wait p99 while lower classes shed explicitly. This returns
    the ##admission record bench.py streams and devhub renders:
    per-class admitted/shed-by-reason counts, the shed line reached,
    queue/credit occupancy, conservation, and both virtual-sustained
    and wall events/s."""
    from .admission import AdmissionClass, AdmissionPlane, VirtualClock
    from .serving import ServingSupervisor
    from .trace import Tracer

    n_accounts = 128
    txns_per_req = 4
    tick_s = 0.020
    classes = (
        AdmissionClass("critical", 0, slo_ms=100.0, deadline_ms=400.0),
        AdmissionClass("standard", 1, slo_ms=200.0, deadline_ms=600.0),
        AdmissionClass("batch", 2, slo_ms=300.0, deadline_ms=300.0),
    )
    tracer = Tracer(pid=0)
    clock = VirtualClock()
    sup = ServingSupervisor(a_cap=1 << 10, t_cap=1 << 15,
                            epoch_interval=16, sleep=lambda s: None,
                            seed=seed, tracer=tracer)
    plane = AdmissionPlane(
        sup, classes=classes, prepare_max=64, window_prepares=2,
        max_windows_per_pump=2, session_credits=4, max_queue=4096,
        burn_window_ticks=4, burn_budget=0.25, cool_ticks=4,
        clock=clock, seed=seed, head_rate=0.05)
    plane.open_accounts([Account(id=i, ledger=1, code=1)
                         for i in range(1, n_accounts + 1)],
                        n_accounts + 10)

    from .utils.zipfian import ZipfianGenerator

    zipf = ZipfianGenerator(sessions, theta=1.1, seed=seed)
    rng = np.random.default_rng(seed)
    next_id = 10 ** 6
    t0 = time.perf_counter()
    for _round in range(rounds):
        for s in zipf.draw(reqs_per_round).tolist():
            sid = int(s) + 1
            m = sid % 10
            cls = ("critical" if m == 0
                   else "standard" if m <= 3 else "batch")
            evs = []
            for _ in range(txns_per_req):
                dr = int(rng.integers(1, n_accounts + 1))
                evs.append(Transfer(
                    id=next_id, debit_account_id=dr,
                    credit_account_id=dr % n_accounts + 1,
                    amount=int(rng.integers(1, 100)), ledger=1, code=1))
                next_id += 1
            plane.submit(sid, evs, cls=cls)
        plane.pump()
        clock.advance(tick_s)
    plane.drain()
    wall_s = time.perf_counter() - t0
    sup.led.shutdown_staging()
    st = plane.stats()
    st["session_population"] = sessions
    st["rounds"] = rounds
    st["offered_events_per_round"] = reqs_per_round * txns_per_req
    st["sustained_admitted_eps_virtual"] = round(
        st["events_admitted"] / (rounds * tick_s), 1)
    st["admitted_eps_wall"] = round(
        st["events_admitted"] / max(wall_s, 1e-9), 1)
    st["wall_s"] = round(wall_s, 3)
    return st
