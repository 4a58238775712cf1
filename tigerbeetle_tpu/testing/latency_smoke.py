"""Bench-regression leg: live serving-window p99 vs committed baseline.

scripts/gate.py's bench-regression leg. Two checks:

1. **Live vs baseline** — a small seeded serving run (the supervisor's
   real `create_transfers_window` path, device engine on whatever
   platform the gate runs) measures per-window submit→resolve latency
   into a log2 histogram and compares its p99 against the committed
   `perf/latency_baseline.json` (written by `--write-baseline` on a
   healthy tree). RED when live p99 exceeds
   ``baseline_p99 * TOLERANCE + SLACK_MS`` — the tolerance absorbs
   machine-to-machine CPU noise; an injected 2x per-window slowdown
   (the knob below) sails past it.
2. **Committed trajectory** — the `BENCH_r*.json` records' pinned
   `serving_batch_latency.p99_ms` series must not have regressed: the
   latest value may not exceed ``TRAJECTORY_TOLERANCE`` times the best
   prior value. This audits what is COMMITTED, independent of the
   current machine.

Fault injection for the gate's own negative test: set
``TB_TPU_LATENCY_INJECT_MS`` to sleep that many milliseconds inside
every window dispatch — the leg must then go RED (and does; see
tests/test_metrics.py).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import time

from ..serving import RetryPolicy, ServingSupervisor
from ..trace import Tracer
from ..trace.histogram import Histogram
from ..types import Account, Transfer

BASELINE_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "perf",
    "latency_baseline.json")
BENCH_GLOB = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..",
    "BENCH_r*.json")
# Live p99 may drift this much over the committed baseline before the
# leg reds: generous because gate machines differ, but an injected 2x
# slowdown (every window + sleep) still lands far beyond it.
TOLERANCE = 1.75
SLACK_MS = 5.0
# The committed BENCH trajectory's pinned p99 series: latest vs best
# prior. Cross-run machines differ more than same-gate runs do.
TRAJECTORY_TOLERANCE = 2.0
# The series starts EMPTY: PR 23 deleted every committed BENCH record
# (all were CPU runs or re-reports of one file taken over a retired
# remote link); the next benchmark PR commits the first of the new one.

WARMUP_WINDOWS = 2
MEASURE_WINDOWS = 12
BATCHES_PER_WINDOW = 2
EVENTS_PER_BATCH = 64
N_ACCOUNTS = 32


def measure(windows: int = MEASURE_WINDOWS,
            warmup: int = WARMUP_WINDOWS,
            tracer=None) -> Histogram:
    """Run the seeded serving workload; per-window latency (ms) into a
    histogram. Honors TB_TPU_LATENCY_INJECT_MS (the injection knob)."""
    inject_ms = float(os.environ.get("TB_TPU_LATENCY_INJECT_MS", "0"))
    tracer = tracer if tracer is not None else Tracer(pid=0)
    # epoch_interval past the run length: epoch verification (quiesce +
    # full oracle replay) costs an order of magnitude more than a
    # window and would own p99, drowning the regression signal in one
    # structurally-slow sample.
    sup = ServingSupervisor(
        a_cap=1 << 9, t_cap=1 << 12,
        epoch_interval=2 * (warmup + windows) + 1,
        retry=RetryPolicy(max_retries=2, base_delay_s=1e-3,
                          max_delay_s=4e-3, deadline_s=30.0),
        seed=1234, tracer=tracer)
    if inject_ms > 0:
        sup.fault_hook = lambda idx, what: time.sleep(inject_ms / 1000.0)
    ts = 1_000
    sup.create_accounts([Account(id=i, ledger=1, code=1)
                         for i in range(1, N_ACCOUNTS + 1)], ts)
    next_id = 1_000_000
    hist = Histogram()
    for w in range(warmup + windows):
        batches = []
        for _ in range(BATCHES_PER_WINDOW):
            batch = []
            for k in range(EVENTS_PER_BATCH):
                dr = (next_id + k) % N_ACCOUNTS + 1
                cr = dr % N_ACCOUNTS + 1
                batch.append(Transfer(
                    id=next_id + k, debit_account_id=dr,
                    credit_account_id=cr, amount=1 + k % 7,
                    ledger=1, code=1))
            next_id += EVENTS_PER_BATCH
            batches.append(batch)
        stamps = []
        for b in batches:
            ts += len(b) + 10
            stamps.append(ts)
        t0 = time.perf_counter()
        sup.create_transfers_window(batches, stamps)
        if w >= warmup:
            hist.record((time.perf_counter() - t0) * 1000.0)
    return hist


def check_trajectory(bench_glob: str | None = None) -> int:
    """Audit the committed BENCH_r*.json pinned p99 series. Returns
    failure count; records without the series are reported, never
    silently skipped. Schema-stable across record generations: the
    audit keys ONLY on `parsed.serving_batch_latency.p99_ms`, so
    pre-observatory records (no `profile` sub-dict — every round
    before ISSUE 20) audit identically to new ones (`bench_glob` lets
    tests prove that on synthetic old records)."""
    paths = sorted(glob.glob(bench_glob or BENCH_GLOB))
    series = []
    for path in paths:
        with open(path) as f:
            parsed = json.load(f).get("parsed") or {}
        lat = parsed.get("serving_batch_latency") or {}
        p99 = lat.get("p99_ms")
        if p99 is None:
            print(f"[bench-reg] {os.path.basename(path)}: no pinned "
                  f"serving p99 (skipped)", flush=True)
            continue
        series.append((os.path.basename(path), float(p99)))
    if len(series) < 2:
        print(f"[bench-reg] trajectory: {len(series)} pinned record(s), "
              f"nothing to compare", flush=True)
        return 0
    latest_name, latest = series[-1]
    best_prior = min(v for _, v in series[:-1])
    ratio = latest / best_prior if best_prior else float("inf")
    ok = ratio <= TRAJECTORY_TOLERANCE
    print(f"[bench-reg] trajectory {latest_name}: p99 {latest:.1f}ms vs "
          f"best prior {best_prior:.1f}ms (x{ratio:.2f}, limit "
          f"x{TRAJECTORY_TOLERANCE}) -> {'ok' if ok else 'RED'}",
          flush=True)
    return 0 if ok else 1


def regression_main(argv=None) -> int:
    """Gate entry: measure live, compare against the committed
    baseline, audit the BENCH trajectory. `--write-baseline`
    (re)generates perf/latency_baseline.json from a healthy tree
    instead of comparing."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--write-baseline", action="store_true")
    ap.add_argument("--windows", type=int, default=MEASURE_WINDOWS)
    args = ap.parse_args(argv)
    hist = measure(windows=args.windows)
    summary = hist.summary()
    print(f"[bench-reg] live: {hist.count} windows, "
          f"p50 {summary['p50']:.1f}ms p99 {summary['p99']:.1f}ms",
          flush=True)
    if args.write_baseline:
        with open(BASELINE_PATH, "w") as f:
            json.dump({
                "p50_ms": round(summary["p50"], 3),
                "p99_ms": round(summary["p99"], 3),
                "windows": hist.count,
                "workload": {
                    "measure_windows": args.windows,
                    "warmup_windows": WARMUP_WINDOWS,
                    "batches_per_window": BATCHES_PER_WINDOW,
                    "events_per_batch": EVENTS_PER_BATCH,
                },
                "histogram": hist.to_dict(),
            }, f, indent=1)
            f.write("\n")
        print(f"[bench-reg] baseline written: {BASELINE_PATH}",
              flush=True)
        return 0
    failures = check_trajectory()
    try:
        with open(BASELINE_PATH) as f:
            base = json.load(f)
    except OSError:
        print(f"[bench-reg] RED: no committed baseline at "
              f"{BASELINE_PATH} (run --write-baseline on a healthy "
              f"tree)", flush=True)
        return failures + 1
    limit = base["p99_ms"] * TOLERANCE + SLACK_MS
    ok = summary["p99"] <= limit
    print(f"[bench-reg] p99 {summary['p99']:.1f}ms vs baseline "
          f"{base['p99_ms']:.1f}ms (limit {limit:.1f}ms = "
          f"x{TOLERANCE} + {SLACK_MS}ms) -> {'ok' if ok else 'RED'}",
          flush=True)
    return failures + (0 if ok else 1)


if __name__ == "__main__":  # pragma: no cover - gate entry
    import sys

    sys.exit(regression_main())
