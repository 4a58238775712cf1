"""Benchmark driver: create_transfers validated transfers/sec on TPU.

One process, on the device JAX gives it (reference:
src/tigerbeetle/benchmark_driver.zig). Runs the bench configs and the
side probes, streams per-config progress as `##bench {...}` lines, and
prints ONE JSON record at the end: {"metric", "value", "unit",
"vs_baseline", "device": {platform, kind, count}, ...}.

A number under a device metric's name comes from a chip run or not at
all: without a TPU the driver prints no record and exits non-zero,
unless the CPU was asked for BY NAME (BENCH_PLATFORM=cpu — a labelled
proxy whose metric carries a `_cpu_proxy` suffix). A side probe that
fails is recorded in the run AND makes the exit code non-zero.

Env knobs:
  BENCH_PLATFORM=cpu       run on the CPU backend, by name
  BENCH_QUICK=1            small CI run
  BENCH_CONFIGS="1,2,3"    config subset
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))


def trace_overhead_probe(quick: bool) -> dict:
    """Tracing-cost guard: the SAME in-process replica commit loop run
    three ways — NullTracer default, recording tracers, and the full
    causal-tracing posture (recording tracers plus a traced client
    stamping trace contexts at sampling 1.0) — so the record carries
    all three wall clocks every run and a tracing-cost regression is
    visible in the devhub history like any throughput regression. The
    recording run's per-commit-stage aggregates double as the devhub
    "commit pipeline" panel's data; the causal run's assembled request
    trees feed the per-request waterfall panel and the
    `ctx_overhead_ratio` acceptance (<= 1.15x of NullTracer).

    Methodology of the guarded ratio: requests carry a 16-transfer
    batch (small against the system's real window sizes, so the
    traced-path share is still overstated, but not the degenerate
    1-transfer request); only the request loop is timed (cluster
    construction is not the traced path and its storage init wobbles
    by milliseconds run to run); null/traced samples interleave,
    min-of-3 each. The legacy `overhead_ratio` series keeps its
    whole-run single-sample shape."""
    from tigerbeetle_tpu import constants, multi_batch
    from tigerbeetle_tpu.state_machine import StateMachine
    from tigerbeetle_tpu.testing.cluster import Cluster
    from tigerbeetle_tpu.trace import Tracer
    from tigerbeetle_tpu.types import Account, Operation, Transfer

    n_ops = 16 if quick else 48
    batch = 16  # transfers per request
    was_verify = constants.VERIFY

    def run(tracer_factory, ops=None, client_tracer=None):
        # Oracle engine: a pure-Python commit pipeline, so the runs
        # differ ONLY by the tracer (no jit warmup to launder the
        # comparison) and the tracer's share of the wall clock is at its
        # honest maximum. Returns (whole-run seconds, request-loop
        # seconds, cluster).
        t0 = time.perf_counter()
        cluster = Cluster(seed=17, replica_count=1,
                          tracer_factory=tracer_factory,
                          state_machine_factory=lambda: StateMachine(
                              engine="oracle"))
        client = cluster.client(5, tracer=client_tracer)

        def drive(op, body):
            client.request(op, body)
            assert cluster.run(4000, until=lambda: client.idle), \
                cluster.debug_status()

        drive(Operation.create_accounts, multi_batch.encode(
            [b"".join(Account(id=i, ledger=1, code=1).pack()
                      for i in (1, 2))], 128))
        t1 = time.perf_counter()
        for k in range(n_ops if ops is None else ops):
            body = b"".join(
                Transfer(id=900 + k * batch + j, debit_account_id=1,
                         credit_account_id=2, amount=1 + k,
                         ledger=1, code=1).pack() for j in range(batch))
            drive(Operation.create_transfers,
                  multi_batch.encode([body], 128))
        t2 = time.perf_counter()
        return t2 - t0, t2 - t1, cluster

    try:
        run(None, ops=2)  # untimed warmup: imports, first-touch caches
        tracers = {}

        def mk(i):
            tracers[i] = Tracer(pid=i)
            return tracers[i]

        recording_s, _, _ = run(mk)
        # Causal posture: fresh recording tracers AND a traced client,
        # head sampling 1.0 — every request mints, stamps and records
        # its causal tree end to end (the most expensive honest case).
        null_s = None
        null_loop_s = None
        traced_s = None
        traced_loop_s = None
        ctx_tracers: dict = {}
        client_tracer = None
        for _ in range(3):
            n_run, n_loop, _ = run(None)
            null_s = n_run if null_s is None else min(null_s, n_run)
            null_loop_s = (n_loop if null_loop_s is None
                           else min(null_loop_s, n_loop))
            ctx_tracers = {}

            def mkc(i, _t=ctx_tracers):
                _t[i] = Tracer(pid=i)
                return _t[i]

            client_tracer = Tracer(pid=99)
            t_run, t_loop, _ = run(mkc, client_tracer=client_tracer)
            traced_s = t_run if traced_s is None else min(traced_s, t_run)
            traced_loop_s = (t_loop if traced_loop_s is None
                             else min(traced_loop_s, t_loop))
    finally:
        constants.set_verify(was_verify)  # Cluster turns it on globally
    stages = {k: v for k, v in tracers[0].aggregates.snapshot().items()
              if k.startswith("commit_")}
    spans = sum(s["count"] for s in stages.values())
    # Critical-path attribution over the recording run's merged trace:
    # which stage owns the slowest-decile windows (devhub "p99 critical
    # path" panel; trace/merge.py critical_path).
    from tigerbeetle_tpu.trace import (assemble_traces, critical_path,
                                       merge_traces)

    merged = merge_traces([tracers[i].chrome_dict()
                           for i in sorted(tracers)])
    cp = critical_path(merged, quantile=0.9)
    # Per-request waterfall: the causal run's assembled span trees,
    # slowest first (devhub "per-request waterfall" panel).
    asm = assemble_traces(merge_traces(
        [ctx_tracers[i].chrome_dict() for i in sorted(ctx_tracers)]
        + [client_tracer.chrome_dict()]))
    waterfall = [
        {"trace_id": t["trace_id"],
         "total_us": t["critical_path"]["total_us"],
         "stages": t["critical_path"]["stages"],
         "owner": t["critical_path"]["owner"],
         "keep_reason": t["keep_reason"]}
        for t in sorted(asm["traces"],
                        key=lambda t: -t["critical_path"]["total_us"])
        if t["kept"]][:12]
    return {
        "ops": n_ops + 1,
        "batch": batch,
        "null_s": round(null_s, 4),
        "recording_s": round(recording_s, 4),
        "overhead_ratio": round(recording_s / null_s, 4) if null_s else None,
        "traced_s": round(traced_s, 4),
        "null_loop_s": round(null_loop_s, 4),
        "traced_loop_s": round(traced_loop_s, 4),
        "ctx_overhead_ratio": (round(traced_loop_s / null_loop_s, 4)
                               if null_loop_s else None),
        "spans_recorded": spans,
        "commit_stages": stages,
        "critical_path": cp,
        "requests_assembled": {"total": asm["total"],
                               "complete": asm["complete"],
                               "orphan_spans": asm["orphan_spans"]},
        "request_waterfall": waterfall,
    }


def profile_probe_bench(quick: bool) -> dict:
    """Performance-observatory record (ISSUE 20): a small seeded
    serving workload run with the sampled dispatch profiler at
    sampling 1/1, so the ##profile line carries a NON-EMPTY
    dispatch_device_time histogram for every route the run drives
    (chain + per-batch here; the partitioned tiers ride the shard
    probe's mesh when >= 8 devices exist), the static FLOPs/HBM-bytes
    cost model per tier from the lowered HLO, the achieved-vs-roofline
    fraction per tier, and the memory watermark vs the committed
    membudget. Everything is assembled by trace.profile_probe over the
    run's tracer — the probe adds no dispatches of its own beyond the
    workload."""
    import numpy as np

    from tigerbeetle_tpu.serving import ServingSupervisor
    from tigerbeetle_tpu.trace import (AlertEngine, DispatchProfiler,
                                       MemWatch, Tracer, profile_probe)
    from tigerbeetle_tpu.types import Account, Transfer

    tracer = Tracer()
    prof = DispatchProfiler(tracer=tracer, sample_every=1)
    mw = MemWatch(tracer=tracer)
    eng = AlertEngine(tracer=tracer, tick_every=1)
    sup = ServingSupervisor(a_cap=1 << 9, t_cap=1 << 11,
                            epoch_interval=4, tracer=tracer,
                            profiler=prof, memwatch=mw,
                            alert_engine=eng)
    sup.create_accounts([Account(id=i, ledger=1, code=1)
                         for i in range(1, 9)], 10 ** 9)
    rng = np.random.default_rng(20)
    ts, tid = 2 * 10 ** 9, 1
    n_windows = 4 if quick else 8

    def mk_batch(n):
        nonlocal tid
        out = []
        for _ in range(n):
            dr, cr = (int(x) for x in
                      rng.choice(np.arange(1, 9), 2, replace=False))
            out.append(Transfer(id=tid, debit_account_id=dr,
                                credit_account_id=cr, amount=1,
                                ledger=1, code=1))
            tid += 1
        return out

    for _ in range(n_windows):
        # W=2 prepares -> the chain (whole-window scan) route.
        sup.create_transfers_window([mk_batch(64), mk_batch(64)],
                                    [ts, ts + 10 ** 6])
        ts += 10 ** 7
    for _ in range(max(2, n_windows // 2)):
        # Single small prepare -> the per-batch tier.
        sup.create_transfers_window([mk_batch(8)], [ts])
        ts += 10 ** 7
    sup.verify_epoch()  # final memwatch observation at the quiesce
    rec = profile_probe(tracer=tracer, profiler=prof)
    rec["memwatch"] = mw.stats()
    rec["alerts"] = eng.stats()
    rec["windows"] = sup.windows_total
    return rec


def shard_balance_probe(quick: bool) -> dict:
    """Partitioned-route balance diagnostics: mixed uniform commit
    windows through PartitionedRouter.step_window on whatever mesh
    exists — the FUSED chain dispatch (one shard_map+scan per window,
    the serving default) — reporting events routed per shard,
    cross-shard fraction, exchange overflow count, per-device resident
    bytes, the windows-by-route counters, and the warm per-window
    dispatch latency percentiles. The ##shard line of the run record
    (devhub "shard balance" panel).

    Round 10: the shard counters (events per shard, cross-shard
    transfers/fraction, exchange overflows) decode from the DEVICE
    telemetry block the fused dispatch harvests with its outputs — the
    router absorbs the block, no host-side recomputation — and the
    record additively gains the `telemetry` sub-dict (occupancy
    histogram and friends) the SLO engine's exchange-headroom burn
    objective evaluates per run. Schema otherwise unchanged."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from tigerbeetle_tpu.oracle import StateMachineOracle
    from tigerbeetle_tpu.ops.batch import transfers_to_arrays
    from tigerbeetle_tpu.parallel.partitioned import (
        PartitionedRouter,
        partitioned_state_bytes,
        replicated_state_bytes,
    )
    from tigerbeetle_tpu.types import Account, Transfer

    mesh = Mesh(np.array(jax.devices()), ("batch",))
    router = PartitionedRouter(mesh, a_cap=1 << 9, t_cap=1 << 11)
    oracle = StateMachineOracle()
    oracle.create_accounts([Account(id=i, ledger=1, code=1)
                            for i in range(1, 33)], 10 ** 9)
    state = router.from_oracle(oracle)
    rng = np.random.default_rng(11)
    ts, tid = 2 * 10 ** 9, 1
    n_windows = 2 if quick else 4
    lat_ms = []

    def mk_window():
        nonlocal ts, tid
        window, tss = [], []
        for _ in range(2):  # W=2 prepares per fused dispatch
            evs = []
            for _ in range(256):
                dr, cr = (int(x) for x in
                          rng.choice(np.arange(1, 33), 2,
                                     replace=False))
                evs.append(Transfer(id=tid, debit_account_id=dr,
                                    credit_account_id=cr, amount=1,
                                    ledger=1, code=1))
                tid += 1
            window.append(transfers_to_arrays(evs))
            tss.append(ts)
            ts += 10 ** 6
        return window, tss

    for wi in range(n_windows):
        window, tss = mk_window()
        t0 = time.perf_counter()
        state, results = router.step_window(state, window, tss, 1024)
        if wi > 0:  # window 0 pays the one-time compile; not latency
            lat_ms.append((time.perf_counter() - t0) * 1000.0)
        assert len(results) == len(window)
        assert router.host_fallbacks == 0, router.stats()

    # Live-migration probe (ISSUE 19): split half of shard 0's hash
    # space to shard 1 UNDER the same traffic — the record the devhub
    # elastic-shards row and the migration-duration trend read. The
    # whole five-stage protocol runs (snapshot/copy/double-write/
    # flip/retire); windows_live counts commit windows that landed
    # while the migration was in flight.
    migration = None
    if router.n_shards >= 2:
        from tigerbeetle_tpu.parallel.resharding import (
            ReshardController,
            ReshardPlan,
        )
        # Fresh state for the migration leg: the balance sweep above
        # deliberately fills the per-shard transfer tables near
        # capacity, and a split doubles the target's load — migrate on
        # a re-seeded state (same caps/mesh, so the compiled lowerings
        # are reused) with smaller windows (same 1024 pad bucket).
        orc_m = StateMachineOracle()
        orc_m.create_accounts([Account(id=i, ledger=1, code=1)
                               for i in range(1, 33)], 10 ** 9)
        state_m = router.from_oracle(orc_m)
        ctl = ReshardController(router, chunk_rows=256,
                                min_double_write_windows=2)
        mig_fallbacks0 = router.host_fallbacks

        def mk_small_window():
            nonlocal ts, tid
            window, tss = [], []
            for _ in range(2):
                evs = []
                for _ in range(64):
                    dr, cr = (int(x) for x in
                              rng.choice(np.arange(1, 33), 2,
                                         replace=False))
                    evs.append(Transfer(id=tid, debit_account_id=dr,
                                        credit_account_id=cr, amount=1,
                                        ledger=1, code=1))
                    tid += 1
                window.append(transfers_to_arrays(evs))
                tss.append(ts)
                ts += 10 ** 6
            return window, tss

        window, tss = mk_small_window()  # warm rows to migrate
        state_m, _ = router.step_window(state_m, window, tss, 1024)
        state_m = ctl.begin(state_m, ReshardPlan(
            lo=0, hi=(1 << 63) - 1, src=0, dst=1, kind="split"))
        guard = 0
        while ctl.stage != "done":
            window, tss = mk_small_window()
            state_m = ctl.on_window(state_m, window)
            state_m, _ = router.step_window(state_m, window, tss, 1024)
            guard += 1
            assert guard < 64, (ctl.stage, ctl.aborts)
        assert not ctl.aborts, ctl.aborts
        assert router.host_fallbacks == mig_fallbacks0, router.stats()
        m = ctl.migrations[-1]
        migration = {
            "kind": m["kind"], "src": m["src"], "dst": m["dst"],
            "rows_copied": m["rows_copied"],
            "double_write_windows": m["double_write_windows"],
            "duration_s": m["duration_s"],
            "windows_live": guard,
        }

    # Degenerate single-hot-account probe (Zipfian s -> inf): every
    # event touches ONE account, so no hash range smaller than the
    # whole shard isolates the load — the detector must answer
    # `unsplittable` (naming the hash) and must NOT thrash (cooldown:
    # the immediate re-propose returns None). The remedy documented in
    # ARCHITECTURE.md is AT2 lane parallelism, not placement.
    # One shard has no placement to judge: the detector proposes
    # nothing there, and the record says so.
    hot_range = None
    if router.n_shards >= 2:
        from tigerbeetle_tpu.parallel.resharding import HotRangeDetector
        det = HotRangeDetector(n_shards=router.n_shards)
        hot = [Transfer(id=10 ** 7 + i, debit_account_id=7,
                        credit_account_id=7, amount=1, ledger=1, code=1)
               for i in range(256)]
        for _ in range(2):
            det.observe_window([transfers_to_arrays(hot)])
        verdict = det.propose()
        assert verdict and verdict["verdict"] == "unsplittable", verdict
        assert det.propose() is None, "detector thrashed past cooldown"
        hot_range = {k: verdict[k] for k in
                     ("verdict", "shard", "fraction", "note")}

    s = router.stats()
    lat_ms.sort()

    def _pct(p):
        return round(lat_ms[min(len(lat_ms) - 1,
                                int(p * len(lat_ms)))], 3)
    try:
        # Route record for the ##diag/dispatch_routes panel: the probe
        # is the run's partitioned leg, so its windows-by-route counters
        # (partitioned_chain = the fused default) ride the same record
        # as the per-config chain routes.
        from tigerbeetle_tpu.benchmark import CONFIG_ROUTES
        CONFIG_ROUTES["shard_probe"] = dict(s["routes"])
    except Exception:
        pass
    return {
        "n_shards": router.n_shards,
        # Per-WINDOW wall latency of the fused dispatch (one
        # shard_map+scan per W=2 window; warm — window 0 carries the
        # one-time compile and is excluded).
        "window_latency": {
            "p50_ms": _pct(0.50), "p99_ms": _pct(0.99),
            "p100_ms": round(lat_ms[-1], 3),
            "windows_timed": len(lat_ms),
            "events_per_window": 512,
        },
        # Decoded from the harvested device telemetry block (the
        # router's absorb path), not recomputed host-side.
        "events_per_shard": s["events_owned"],
        "cross_shard_transfers": s["cross_shard_transfers"],
        "cross_shard_fraction": s["cross_shard_fraction"],
        "exchange_overflows": s["exchange_overflows"],
        "routes": s["routes"],
        # Device telemetry aggregates incl. the exchange-occupancy
        # histogram dict trace/slo.py evaluate_bench_record reads for
        # the exchange_occupancy_p99_pct objective.
        "telemetry": s["telemetry"],
        "state_bytes_per_device": partitioned_state_bytes(state),
        "state_bytes_replicated_equiv": replicated_state_bytes(
            router.a_cap * router.n_shards,
            router.t_cap * router.n_shards),
        # Elastic-shards probe: one live split migration's record
        # (None on a 1-shard mesh) + the degenerate single-hot-account
        # detector verdict — the devhub shard panel's migration row.
        "migration": migration,
        "hot_range": hot_range,
    }


def run_configs(device: dict) -> list:
    """Execute the configs and side probes on `device` (what JAX gave
    this process); returns the names of the side probes that failed."""
    t0 = time.time()
    failed: list = []
    from tigerbeetle_tpu.benchmark import (
        BASELINE_TPS,
        CONFIG_DIAGNOSTICS,
        CONFIG_ROUTES,
        TARGET_TPS,
        bench_config1,
        bench_config2,
        bench_config3,
        bench_config4,
        bench_config6_serving,
        parity_config5,
    )

    quick = os.environ.get("BENCH_QUICK") == "1"
    subset = os.environ.get("BENCH_CONFIGS")
    run = {t.strip() for t in (subset or "1,2,3,4,5,6").split(",")}
    unknown = run - {"1", "2", "3", "4", "5", "6"}
    assert not unknown, f"BENCH_CONFIGS has unknown tokens: {sorted(unknown)}"
    # Full-mode counts are multiples of SUPERBATCH_MAX=32 so the scan
    # configs run whole commit windows (one compiled program shape).
    b1 = 8 if quick else 32
    b2 = 8 if quick else 128  # 128 * 8190 ~ 1M transfers
    b3 = 8 if quick else 32

    def emit(key, val):
        print(f"##bench {json.dumps({key: val})}", flush=True)

    def emit_diag(key):
        # Per-cause fallback counts (DeviceLedger.fallback_stats): every
        # config's "no host fallbacks" claim is a measured number in the
        # run record, streamed as it lands so a mid-run wedge keeps it.
        # Cumulative (the parent's partial.update replaces the whole
        # key): a wedge after config N keeps configs 1..N.
        if CONFIG_DIAGNOSTICS.get(key) is not None:
            emit("fallback_diagnostics", dict(CONFIG_DIAGNOSTICS))

    def tps(a, e):
        return None if a is None else round(a / e if e > 0 else 0.0, 1)

    acc1 = el1 = acc2 = el2 = acc3 = el3 = acc4 = el4 = parity = None
    if "1" in run:
        acc1, el1 = bench_config1(b1)
        emit("config1_2hot_tps", tps(acc1, el1))
        emit_diag("config1")
    if "2" in run:
        acc2, el2 = bench_config2(b2)
        emit("config2_10k_tps", tps(acc2, el2))
        emit_diag("config2")
    if "3" in run:
        acc3, el3 = bench_config3(b3)
        emit("config3_chains_tps", tps(acc3, el3))
        emit_diag("config3")
    if "4" in run:
        acc4, el4 = bench_config4(batches=2 if quick else 6)
        emit("config4_twophase_limits_tps", tps(acc4, el4))
        emit_diag("config4")
    if "5" in run:
        parity = parity_config5(n_batches=3 if quick else 6)
        emit("config5_oracle_parity", parity)
    acc6 = el6 = None
    serving_latency = None
    if "6" in run:
        acc6, el6, serving_latency = bench_config6_serving(
            batches=4 if quick else 24)
        emit("config6_serving_tps", tps(acc6, el6))
        emit_diag("config6")
        if serving_latency:
            emit("serving_batch_latency", serving_latency)

    # Chaos/recovery counters (retries, backoff time, replayed windows,
    # checksum epochs verified, recoveries by cause) per config — zeros
    # in a healthy run, and MEASURED zeros: the ledger always carries
    # the record (DeviceLedger.fallback_stats()["recovery"]), so a
    # bench that ever exercises the serving supervisor reports its
    # recoveries in the same record as its fallbacks.
    recovery = {cfg: d.get("recovery")
                for cfg, d in CONFIG_DIAGNOSTICS.items()
                if isinstance(d, dict) and d.get("recovery") is not None}
    if recovery:
        emit("recovery_diagnostics", recovery)

    # Host-staging record (ISSUE 16): per-config double-buffered window
    # staging accounting — total host staging work (work_ms), the part
    # the dispatch path actually waited on (stall_ms), windows staged
    # ahead vs packed inline, and the headline host_stall_fraction
    # (stall/work; 1.0 = fully synchronous staging, ~0 = the pack is
    # hidden behind in-flight device execution). The overlap gate leg
    # asserts a ceiling on the same number from a live seeded run.
    host_staging = {cfg: d.get("staging")
                    for cfg, d in CONFIG_DIAGNOSTICS.items()
                    if isinstance(d, dict) and d.get("staging") is not None}
    if host_staging:
        emit("host_staging", host_staging)

    # Op-budget summary (light tier subset, pure tracing — no device
    # execution): the per-run record of the kernels' heavy-op footprint
    # on its own ##opbudget line; devhub renders it next to the
    # fallback-diagnostics table. The full table incl. deep/sharded
    # tiers plus the gate ceilings live in perf/opbudget.py +
    # perf/opbudget_r06.json.
    # Tracing-cost record (##trace): NullTracer vs recording tracer on
    # one replica commit loop, plus the recorded per-commit-stage
    # aggregates (the devhub commit-pipeline panel renders them).
    trace_probe = None
    try:
        trace_probe = trace_overhead_probe(quick)
    except Exception as e:  # recorded, and the run exits non-zero
        trace_probe = {"error": str(e)[:200]}
        failed.append("trace")
    print("##trace " + json.dumps({"trace": trace_probe}), flush=True)

    # Shard-balance record (##shard): partitioned-route diagnostics —
    # events per shard, cross-shard fraction, exchange overflows — so a
    # skewed ownership hash or an overflow-prone exchange capacity is
    # visible in the devhub history like any throughput regression.
    shard = None
    try:
        shard = shard_balance_probe(quick)
    except Exception as e:  # recorded, and the run exits non-zero
        shard = {"error": str(e)[:200]}
        failed.append("shard_balance")
    print("##shard " + json.dumps({"shard_balance": shard}), flush=True)

    # Admission record (##admission): the ISSUE 18 ingress plane under
    # a sessionized Zipfian overload on a virtual clock — sustained
    # admitted events/s plus per-class admitted-wait p99 while lower
    # classes shed explicitly (the overload gate leg asserts the same
    # contract live; this keeps the measured numbers in the run record
    # so a shed-behavior regression is visible in the devhub history).
    admission = None
    try:
        from tigerbeetle_tpu.benchmark import bench_admission

        admission = bench_admission(rounds=8 if quick else 24)
    except Exception as e:  # recorded, and the run exits non-zero
        admission = {"error": str(e)[:200]}
        failed.append("admission")
    print("##admission " + json.dumps({"admission": admission}),
          flush=True)

    # Performance-observatory record (##profile): sampled
    # dispatch_device_time histograms per route, the static
    # FLOPs/HBM-bytes cost model per tier, achieved-vs-roofline
    # fractions, and the memory watermark vs the committed membudget
    # (trace/profiler.py + trace/memwatch.py; ISSUE 20).
    profile = None
    try:
        profile = profile_probe_bench(quick)
    except Exception as e:  # recorded, and the run exits non-zero
        profile = {"error": str(e)[:200]}
        failed.append("profile")
    print("##profile " + json.dumps({"profile": profile}), flush=True)

    # Dispatch-route record: which kernel route each config's windows
    # took ("chain" = the scan-form whole-window dispatch, the default
    # serving route; "partitioned_chain" = the fused sharded-state
    # window route the shard probe takes) + the window depths used — a
    # silent route degradation is as visible as a throughput
    # regression. Emitted after the shard probe so its partitioned
    # route counters ride the same record.
    if CONFIG_ROUTES:
        emit("dispatch_routes", dict(CONFIG_ROUTES))

    opbudget = None
    try:
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "tb_opbudget", os.path.join(REPO, "perf", "opbudget.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        opbudget = mod.summary_line()
    except Exception as e:  # recorded, and the run exits non-zero
        opbudget = {"error": str(e)[:200]}
        failed.append("opbudget")
    print("##opbudget " + json.dumps(opbudget), flush=True)

    value = None if acc2 is None else (acc2 / el2 if el2 > 0 else 0.0)
    proxy = "" if device["platform"] == "tpu" else "_cpu_proxy"
    out = {
        "metric": "create_transfers_validated_per_sec" + proxy,
        # The device this record was measured on, as JAX reports it.
        "device": device,
        "elapsed_s": round(time.time() - t0, 1),
        "failed_probes": failed,
        "value": None if value is None else round(value, 1),
        "unit": "transfers/s",
        "vs_baseline": None if value is None else round(value / BASELINE_TPS, 4),
        "vs_target_10m": None if value is None else round(value / TARGET_TPS, 4),
        "config1_2hot_tps": tps(acc1, el1),
        "config2_10k_tps": tps(acc2, el2),
        "config3_chains_tps": tps(acc3, el3),
        "config4_twophase_limits_tps": tps(acc4, el4),
        "config5_oracle_parity": parity,
        "config6_serving_tps": tps(acc6, el6),
        # Mean 8190-event batch latency at config2 rate. (True per-batch
        # syncs would serialize the pipelined dispatch, so the mean is
        # reported under an honest name; REAL percentiles come from the
        # serving config below, whose commits are synchronous.)
        "batch_latency_mean_ms": (
            None if not acc2 else round(8190 / (acc2 / el2) * 1000, 3)),
        # Per-batch serving-commit latency percentiles (reference reports
        # p100 — benchmark_load.zig:587).
        "serving_batch_latency": serving_latency,
        # Per-config routing/fallback counters (per-cause): the measured
        # "zero host fallbacks" record behind every number above.
        "fallback_diagnostics": dict(CONFIG_DIAGNOSTICS),
        # Dispatch route + window depth per config (chain = the default
        # whole-window scan route).
        "dispatch_routes": dict(CONFIG_ROUTES),
        # Chaos/recovery counters next to the fallback record (zeros in
        # a healthy run — and recorded, not assumed).
        "recovery_diagnostics": recovery,
        # Double-buffered window-staging accounting per config: host
        # staging work vs the stall the dispatch path paid, and the
        # host_stall_fraction the overlap gate leg ceilings.
        "host_staging": host_staging,
        # Heavy-op census of the kernels this run dispatched (see the
        # ##opbudget line / perf/opbudget.py).
        "opbudget": opbudget,
        # Tracing-cost guard + commit-stage shares (##trace line).
        "trace": trace_probe,
        # Partitioned-route shard balance (##shard line): events per
        # shard, cross-shard fraction, exchange overflow count.
        "shard_balance": shard,
        # Admission-plane record (##admission line): per-class
        # admitted/shed counts, shed line, occupancy, sustained tps.
        "admission": admission,
        # Performance-observatory record (##profile line): per-route
        # sampled dispatch timing, static cost model, roofline
        # fractions, memory watermark.
        "profile": profile,
        "engine": "device_ledger_scan",
    }
    # Bottleneck analysis (VERDICT r1 #3): where the serving gap lives.
    # config2 is the pure on-device scan; config6 is the replica commit
    # boundary (wire decode + kernel + write-through mirror + encode) —
    # their ratio isolates the HOST share of the serving path.
    if acc2 and acc6 and el2 > 0 and el6 > 0:
        scan_tps = acc2 / el2
        serve_tps = acc6 / el6
        out["bottleneck"] = {
            "device_scan_tps": round(scan_tps, 1),
            "serving_tps": round(serve_tps, 1),
            "host_share_of_serving": round(
                max(0.0, 1.0 - serve_tps / scan_tps), 4),
            "note": ("serving cost beyond the device scan is host-side: "
                     "wire codecs + the write-through mirror apply"),
        }
    print(json.dumps(out), flush=True)
    return failed


def main() -> int:
    on_cpu_by_name = os.environ.get("BENCH_PLATFORM") == "cpu"
    import jax

    if on_cpu_by_name:
        jax.config.update("jax_platforms", "cpu")
    from tigerbeetle_tpu import compile_cache

    compile_cache.enable()
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    if device["platform"] != "tpu" and not on_cpu_by_name:
        print(f"bench: JAX found {device}, not a TPU — no record is "
              "printed. BENCH_PLATFORM=cpu runs the labelled CPU proxy.",
              file=sys.stderr)
        return 2
    failed = run_configs(device)
    if failed:
        print(f"bench: side probes failed: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
