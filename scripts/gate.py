#!/usr/bin/env python
"""Pre-snapshot gate: the quick test tier + the 8-device SPMD dryrun.

Run before banking a snapshot:

    python scripts/gate.py            # quick tier + dryrun_multichip(8)
    python scripts/gate.py --no-mesh  # quick tier only
    python scripts/gate.py --tier slow   # one of: quick, slow, soak, all

Tiers (markers documented in pytest.ini):

  quick  (default) every test not marked slow/soak — the jit-light
         correctness surface; finishes well inside the tier-1 budget.
  slow   the jit-heavy parity/differential tiers (kernel parity, the
         fixpoint/balancing/imported/sharded differential suites, VOPR
         scenario sweeps): each file compiles many XLA programs.
  soak   long randomized soaks; run when touching the matching
         subsystem, not per snapshot.

The gate also runs the fixed CHAOS seed set (testing/chaos.py
gate_main: seeded device-fault injection against the serving
supervisor — zero-silent-corruption asserted per seed; skip with
--no-chaos), the REBUILD smoke (3-replica in-process cluster, zero one
data file under load, recover-from-cluster, state-epoch digest match,
plus one fixed seed each of the message_bus and storage_faults
fuzzers; skip with --no-rebuild), the CHAIN-ROUTE leg (testing/chain_smoke.py: the
default whole-window scan dispatch through the real
submit_window/resolve_windows route — chain taken by default,
per-prepare fallback parity vs the sync path and the oracle, zero
host fallbacks on plain windows, committed chain budgets present;
skip with --no-chain), the PARTITIONED-CHAIN leg
(testing/partitioned_chain_smoke.py + parallel/multihost.py: the fused
sharded-state window route — one shard_map+scan dispatch per window —
differential vs the per-batch ladder and the oracle on an 8-device
virtual mesh, then the 2-process jax.distributed local leg, skipped
gracefully where multi-process init is unavailable; skip with
--no-partitioned-chain), the OVERLAP leg
(testing/overlap_smoke.py: double-buffered window staging proven live —
a seeded pipelined serving run's host_stall_fraction strictly under the
committed STALL_CEILING with every eligible window staged ahead, the
forced-sync negative measuring exactly 1.0 and failing the predicate,
and bit-exact history parity overlapped vs sync on the chain and fused
partitioned-chain routes; skip with --no-overlap), the RESHARD leg
(testing/reshard_smoke.py: crash-safe live resharding — a seeded
split+migrate+merge_back completes under live traffic on mesh-2 and
mesh-8 with the src==dst range-digest witness at every flip, zero
aborts/host fallbacks and bit-exact history vs a never-resharded
oracle, plus the corrupted-copy negative that must abort PRE-FLIP
with a flight artifact; skip with --no-reshard), the TELEMETRY leg
(testing/telemetry_smoke.py: the device-telemetry plane of the fused
route — harvested per-prepare block decoded bit-exact vs a host
recomputation on 1/2/8-device meshes, telemetry-lane census vs the
committed budget, a negative over-budget-pack red, and the measured
telemetry-on vs -off dispatch overhead ratio under the budget's
overhead_ratio_max; skip with --no-telemetry), the TRACE-CATALOG coverage leg
(testing/trace_coverage.py: the smokes re-run under recording tracers;
red when any event in tigerbeetle_tpu/trace/event.py is never emitted
or an off-catalog name is emitted, or an emitted span/histogram event
never fed a non-empty histogram; skip with --no-trace-cov), the
METRICS leg (testing/trace_coverage.py metrics_main: perf/slo.json
must load with every objective on-catalog — a dead SLO is a RED — and
a live /metrics endpoint over a seeded serving run must serve
Prometheus-parseable text with per-route window histograms and SLO
series; skip with --no-metrics), the STATIC leg
(testing/static_smoke.py: jaxhound 2.0's four whole-stack passes over
the full serving-entry registry on an 8-device virtual mesh — device
determinism, host-determinism AST lint, retrace/recompile audit vs the
committed perf/tracebudget_r*.json, sharding-spec verification of the
partitioned lowerings — plus one negative injected-violation proof per
pass, each of which must RED; skip with --no-static), the CAUSALITY leg
(testing/causality_smoke.py: causal request tracing end to end on a
REAL 3-replica vortex at sampling 1.0 — one complete orphan-free span
tree per client request, the commit causally attributed inside it,
per-pid clock-skew correction from matched bus send/recv pairs, plus
two negative proofs (dropped trace-context header, dropped root span)
that must each RED; skip with --no-causality), the PROFILE leg
(testing/observatory_smoke.py: the performance observatory — per-route
dispatch_device_time histograms non-empty with finite
achieved-vs-roofline fractions, the live memory watermark green vs the
committed perf/membudget_r*.json with the injected-leak negative RED,
a seeded latency burn firing the page-severity alert (runbook anchor,
alert:<rule> tail retention, frozen flight artifact) with the
alert-disabled and dead-rule negatives, and the measured observatory
overhead ratio under the membudget's profiler ceiling; skip with
--no-profile), and the
op-budget check + jaxhound serving-path lints
(`perf/opbudget.py --check --lint`): a kernel change that raises any
tier's heavy-op count or operand bytes past its committed budget
(perf/opbudget_r09.json — incl. the chain and partitioned-chain
routes' whole-program and scan-BODY censuses), bakes a >4 KiB closure
constant into a serving
entry, drops state-buffer donation, or introduces a while loop beyond
an entry's allowance into a serving lowering is a RED. See
ARCHITECTURE.md "Op-budget workflow" for reading a failure /
intentionally raising a budget.

Exit status is nonzero on ANY red (test failure, collection error,
timeout, dryrun assertion, budget excess, lint), so
`python scripts/gate.py && snapshot` cannot bank a broken tree.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TIER_EXPR = {
    "quick": "not slow and not soak",
    "slow": "slow",
    "soak": "soak",
    "all": "",
}


def run_tests(tier: str, timeout: int) -> int:
    expr = TIER_EXPR[tier]
    cmd = [
        sys.executable, "-m", "pytest", "tests/", "-q",
        "--continue-on-collection-errors",
        "-p", "no:cacheprovider", "-p", "no:randomly",
    ]
    if expr:
        cmd += ["-m", expr]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    print(f"[gate] {tier} tier: {' '.join(cmd)}", flush=True)
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=REPO, env=env, timeout=timeout)
        rc = p.returncode
    except subprocess.TimeoutExpired:
        print(f"[gate] RED: {tier} tier timed out after {timeout}s",
              flush=True)
        return 124
    print(f"[gate] {tier} tier rc={rc} in {time.time() - t0:.0f}s",
          flush=True)
    return rc


def run_opbudget(timeout: int = 900) -> int:
    """Op-budget check + jaxhound serving-path lints (see module doc)."""
    cmd = [sys.executable, os.path.join(REPO, "perf", "opbudget.py"),
           "--check", "--lint"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    print(f"[gate] opbudget: {' '.join(cmd)}", flush=True)
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=REPO, env=env, timeout=timeout)
        rc = p.returncode
    except subprocess.TimeoutExpired:
        print(f"[gate] RED: opbudget timed out after {timeout}s",
              flush=True)
        return 124
    print(f"[gate] opbudget rc={rc} in {time.time() - t0:.0f}s",
          flush=True)
    return rc


def run_chaos(timeout: int = 900) -> int:
    """Fixed chaos seed set (CPU engine, small workloads): the serving
    recovery path — verified epochs, bounded replay, retry/backoff,
    shard-loss reroute — can never silently rot. One subprocess so the
    seeds share jit caches; see testing/chaos.py gate_main/GATE_SEEDS.
    Any undetected corruption or parity break is a RED."""
    cmd = [sys.executable, "-c",
           "import sys; from tigerbeetle_tpu.testing import chaos; "
           "sys.exit(chaos.gate_main())"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    print("[gate] chaos: fixed seed set (testing/chaos.py)", flush=True)
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=REPO, env=env, timeout=timeout)
        rc = p.returncode
    except subprocess.TimeoutExpired:
        print(f"[gate] RED: chaos timed out after {timeout}s", flush=True)
        return 124
    print(f"[gate] chaos rc={rc} in {time.time() - t0:.0f}s", flush=True)
    return rc


def run_rebuild(timeout: int = 600) -> int:
    """Rebuild-from-cluster smoke: 3-replica in-process cluster, traffic
    past a WAL wrap, zero one replica's data file, rebuild it from its
    peers, state-epoch digest match (testing/cluster.py rebuild_smoke) —
    plus one fixed seed of each rebuild-adjacent fuzzer (message_bus,
    storage_faults). Skip with --no-rebuild."""
    cmd = [sys.executable, "-c",
           "from tigerbeetle_tpu.testing.cluster import rebuild_smoke; "
           "from tigerbeetle_tpu.testing import fuzz; "
           "rebuild_smoke(); "
           "fuzz.run('message_bus', 1); "
           "fuzz.run('storage_faults', 1, iterations=2); "
           "print('[gate] rebuild ok')"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    print("[gate] rebuild: zero-one-data-file smoke + new fuzzer seeds",
          flush=True)
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=REPO, env=env, timeout=timeout)
        rc = p.returncode
    except subprocess.TimeoutExpired:
        print(f"[gate] RED: rebuild timed out after {timeout}s", flush=True)
        return 124
    print(f"[gate] rebuild rc={rc} in {time.time() - t0:.0f}s", flush=True)
    return rc


def run_chain(timeout: int = 600) -> int:
    """Chain-route leg: quick differential of the default whole-window
    scan dispatch through the REAL submit_window/resolve_windows route —
    chain taken by default, per-prepare fallback parity vs the sync
    path and the oracle, zero host fallbacks on plain windows, and the
    committed chain budgets present (testing/chain_smoke.py; the
    r07 budget values themselves are enforced by the opbudget leg).
    Skip with --no-chain."""
    cmd = [sys.executable, "-c",
           "from tigerbeetle_tpu.testing import chain_smoke; "
           "chain_smoke.chain_smoke()"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    print("[gate] chain: whole-window scan-route differential "
          "(testing/chain_smoke.py)", flush=True)
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=REPO, env=env, timeout=timeout)
        rc = p.returncode
    except subprocess.TimeoutExpired:
        print(f"[gate] RED: chain timed out after {timeout}s", flush=True)
        return 124
    print(f"[gate] chain rc={rc} in {time.time() - t0:.0f}s", flush=True)
    return rc


def run_partitioned_chain(timeout: int = 900) -> int:
    """Partitioned-chain leg: quick differential of the FUSED
    partitioned window route (ONE shard_map+scan dispatch per window
    over account-range-sharded state) on an 8-device virtual CPU mesh —
    chain taken by default, per-prepare limit-cascade fallback with
    on-device escalation, parity vs the per-batch ladder and the
    oracle, digest equality, zero host fallbacks, committed r09 fused
    budgets present (testing/partitioned_chain_smoke.py) — then the
    2-process ``jax.distributed`` local leg (parallel/multihost.py):
    the same route over a coordinator-connected 2-process global mesh,
    skipped gracefully where the multi-process runtime is unavailable.
    Skip with --no-partitioned-chain."""
    cmd = [sys.executable, "-c",
           "from tigerbeetle_tpu.testing import partitioned_chain_smoke"
           " as s; s.partitioned_chain_smoke(); "
           "from tigerbeetle_tpu.parallel import multihost; "
           "print('[gate] multihost 2-process: '"
           " + multihost.two_process_smoke())"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    print("[gate] partitioned-chain: fused sharded window route "
          "differential + 2-process multihost leg", flush=True)
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=REPO, env=env, timeout=timeout)
        rc = p.returncode
    except subprocess.TimeoutExpired:
        print(f"[gate] RED: partitioned-chain timed out after "
              f"{timeout}s", flush=True)
        return 124
    print(f"[gate] partitioned-chain rc={rc} in "
          f"{time.time() - t0:.0f}s", flush=True)
    return rc


def run_overlap(timeout: int = 900) -> int:
    """Overlap leg: host↔device double-buffered window staging proven
    LIVE (testing/overlap_smoke.py, 8-device virtual mesh for the
    partitioned arm) — a seeded pipelined serving run must measure a
    host_stall_fraction strictly under the committed STALL_CEILING with
    every eligible window staged ahead, the forced-sync negative
    (DeviceLedger.overlap_staging=False) must measure exactly 1.0 and
    FAIL the ceiling predicate, and the overlapped history must be
    bit-exact vs the sync arm's (poisoned window included) on both the
    chain and fused partitioned-chain routes. Skip with
    --no-overlap."""
    cmd = [sys.executable, "-c",
           "from tigerbeetle_tpu.testing import overlap_smoke as s; "
           "s.overlap_smoke()"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    print("[gate] overlap: double-buffered staging stall ceiling + "
          "forced-sync negative (testing/overlap_smoke.py)", flush=True)
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=REPO, env=env, timeout=timeout)
        rc = p.returncode
    except subprocess.TimeoutExpired:
        print(f"[gate] RED: overlap timed out after {timeout}s",
              flush=True)
        return 124
    print(f"[gate] overlap rc={rc} in {time.time() - t0:.0f}s",
          flush=True)
    return rc


def run_reshard(timeout: int = 900) -> int:
    """Reshard leg: crash-safe live resharding proven LIVE
    (testing/reshard_smoke.py, 8-device virtual mesh) — a seeded
    split + migrate + merge_back completes under live traffic on a
    mesh-2 AND a mesh-8 sub-mesh with the src==dst range-digest
    witness at every flip, zero aborts, zero host fallbacks, and the
    history bit-exact vs a never-resharded oracle; the negative arm
    (an injected copy corruption) must abort PRE-FLIP with a
    FLIGHT_*_reshard_* artifact — a flip that goes through despite
    the corruption is a RED. Skip with --no-reshard."""
    cmd = [sys.executable, "-c",
           "from tigerbeetle_tpu.testing import reshard_smoke as s; "
           "s.reshard_smoke()"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    print("[gate] reshard: live split+migrate+merge_back with digest "
          "witness + corrupted-copy negative "
          "(testing/reshard_smoke.py)", flush=True)
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=REPO, env=env, timeout=timeout)
        rc = p.returncode
    except subprocess.TimeoutExpired:
        print(f"[gate] RED: reshard timed out after {timeout}s",
              flush=True)
        return 124
    print(f"[gate] reshard rc={rc} in {time.time() - t0:.0f}s",
          flush=True)
    return rc


def run_overload(timeout: int = 900) -> int:
    """Overload leg: the admission plane's SLO-driven load shedding
    proven LIVE (testing/overload_smoke.py) — a seeded 100k-session
    Zipfian overload at ~2x window capacity must keep every class's
    ADMITTED queue-wait p99 within its committed per-class budget while
    at least one class sheds, every rejection a typed ShedResult with a
    tail-kept trace (submitted == admitted + shed, zero silent drops),
    the admitted history bit-exact vs an oracle replay of only the
    admitted requests, and the shed-line-disabled negative must
    collapse past the largest budget and FAIL the gate predicate. Skip
    with --no-overload."""
    cmd = [sys.executable, "-c",
           "from tigerbeetle_tpu.testing import overload_smoke as s; "
           "s.overload_smoke()"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    print("[gate] overload: 100k-session Zipfian admission shedding + "
          "no-shed negative (testing/overload_smoke.py)", flush=True)
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=REPO, env=env, timeout=timeout)
        rc = p.returncode
    except subprocess.TimeoutExpired:
        print(f"[gate] RED: overload timed out after {timeout}s",
              flush=True)
        return 124
    print(f"[gate] overload rc={rc} in {time.time() - t0:.0f}s",
          flush=True)
    return rc


def run_telemetry(timeout: int = 900) -> int:
    """Telemetry leg: the round-10 device-telemetry plane on the fused
    partitioned-chain route (testing/telemetry_smoke.py, 8-device
    virtual mesh) — the harvested per-prepare block decoded bit-exact
    vs a host recomputation on 1/2/8-device meshes, the telemetry-lane
    census vs the committed budget's `telemetry` section, a negative
    proof that a grown pack reds perf/opbudget.check_telemetry, and
    the measured telemetry-on vs telemetry-off dispatch overhead ratio
    under the budget's `overhead_ratio_max`. Skip with
    --no-telemetry."""
    cmd = [sys.executable, "-c",
           "from tigerbeetle_tpu.testing import telemetry_smoke as s; "
           "s.telemetry_smoke()"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    print("[gate] telemetry: device block oracle + lane census + "
          "overhead ratio (testing/telemetry_smoke.py)", flush=True)
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=REPO, env=env, timeout=timeout)
        rc = p.returncode
    except subprocess.TimeoutExpired:
        print(f"[gate] RED: telemetry timed out after {timeout}s",
              flush=True)
        return 124
    print(f"[gate] telemetry rc={rc} in {time.time() - t0:.0f}s",
          flush=True)
    return rc


def run_trace_coverage(timeout: int = 900) -> int:
    """Trace-catalog coverage leg: the vopr/chaos/rebuild-style smokes
    (plus deterministic scenarios for rare events) run under recording
    tracers; RED if any catalog event (tigerbeetle_tpu/trace/event.py)
    is never emitted, or any emitted name is off-catalog (the recording
    tracer hard-errors on those). Skip with --no-trace-cov."""
    cmd = [sys.executable, "-c",
           "import sys; "
           "from tigerbeetle_tpu.testing import trace_coverage; "
           "sys.exit(trace_coverage.coverage_main())"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # The reshard scenario drives a 2-shard migration; the virtual
    # mesh makes the leg's shard scenarios real multi-device.
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    print("[gate] trace-cov: catalog coverage "
          "(testing/trace_coverage.py)", flush=True)
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=REPO, env=env, timeout=timeout)
        rc = p.returncode
    except subprocess.TimeoutExpired:
        print(f"[gate] RED: trace-cov timed out after {timeout}s",
              flush=True)
        return 124
    print(f"[gate] trace-cov rc={rc} in {time.time() - t0:.0f}s",
          flush=True)
    return rc


def run_metrics(timeout: int = 600) -> int:
    """Metrics leg: perf/slo.json must load with every referenced event
    on-catalog (a dead SLO — an objective nothing can feed — is a RED),
    and a live /metrics HTTP endpoint over a real seeded serving run
    must serve Prometheus-parseable text carrying the per-route window
    histograms and the SLO series (testing/trace_coverage.py
    metrics_main). Skip with --no-metrics."""
    cmd = [sys.executable, "-c",
           "import sys; "
           "from tigerbeetle_tpu.testing import trace_coverage; "
           "sys.exit(trace_coverage.metrics_main())"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    print("[gate] metrics: SLO catalog check + /metrics exposition "
          "smoke", flush=True)
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=REPO, env=env, timeout=timeout)
        rc = p.returncode
    except subprocess.TimeoutExpired:
        print(f"[gate] RED: metrics timed out after {timeout}s",
              flush=True)
        return 124
    print(f"[gate] metrics rc={rc} in {time.time() - t0:.0f}s",
          flush=True)
    return rc


def run_causality(timeout: int = 900) -> int:
    """Causality leg: causal request tracing acceptance over a REAL
    3-replica vortex cluster at sampling 1.0 — every client request
    must assemble into exactly one complete orphan-free span tree
    rooted at client_request with the commit causally attributed
    inside it, after per-pid clock-skew correction; two negative
    proofs (dropped trace-context header, dropped root span) must
    each trip the checker (testing/causality_smoke.py). Skip with
    --no-causality."""
    cmd = [sys.executable, "-c",
           "import sys; "
           "from tigerbeetle_tpu.testing import causality_smoke; "
           "sys.exit(causality_smoke.causality_main())"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    print("[gate] causality: causal trace assembly over a real vortex "
          "(testing/causality_smoke.py)", flush=True)
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=REPO, env=env, timeout=timeout)
        rc = p.returncode
    except subprocess.TimeoutExpired:
        print(f"[gate] RED: causality timed out after {timeout}s",
              flush=True)
        return 124
    print(f"[gate] causality rc={rc} in {time.time() - t0:.0f}s",
          flush=True)
    return rc


def run_profile(timeout: int = 900) -> int:
    """Profile leg: the performance observatory proven live WITH its
    negatives (testing/observatory_smoke.py) — sampled per-dispatch
    histograms + static-cost-model roofline fractions per tier, the
    memory watermark audited green vs the committed
    perf/membudget_r*.json and the injected-leak arm RED, the seeded
    latency burn firing the page alert (typed, runbook-anchored,
    trace-tail-keeping, flight-freezing) with the alert-disabled and
    dead-rule arms, and the observatory overhead ratio under the
    membudget's profiler ceiling. Skip with --no-profile."""
    cmd = [sys.executable, "-c",
           "from tigerbeetle_tpu.testing import observatory_smoke as s; "
           "s.observatory_smoke()"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    print("[gate] profile: dispatch roofline + memwatch budget + "
          "burn-rate alerts (testing/observatory_smoke.py)", flush=True)
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=REPO, env=env, timeout=timeout)
        rc = p.returncode
    except subprocess.TimeoutExpired:
        print(f"[gate] RED: profile timed out after {timeout}s",
              flush=True)
        return 124
    print(f"[gate] profile rc={rc} in {time.time() - t0:.0f}s",
          flush=True)
    return rc


def run_static(timeout: int = 900) -> int:
    """Static leg: jaxhound 2.0's four whole-stack passes (device
    determinism, host-determinism AST lint, retrace/recompile audit vs
    the committed perf/tracebudget_r*.json head, sharding-spec
    verification) over the FULL serving-entry registry on an 8-device
    virtual mesh, plus a negative injected-violation proof per pass
    (testing/static_smoke.py). Skip with --no-static."""
    cmd = [sys.executable, "-c",
           "from tigerbeetle_tpu.testing import static_smoke as s; "
           "s.static_smoke()"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    print("[gate] static: jaxhound passes + negative proofs "
          "(testing/static_smoke.py)", flush=True)
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=REPO, env=env, timeout=timeout)
        rc = p.returncode
    except subprocess.TimeoutExpired:
        print(f"[gate] RED: static timed out after {timeout}s",
              flush=True)
        return 124
    print(f"[gate] static rc={rc} in {time.time() - t0:.0f}s",
          flush=True)
    return rc


def run_mesh(n_devices: int) -> int:
    # dryrun_multichip handles its own harness-proofing (re-execs into a
    # pinned virtual-CPU-mesh subprocess when needed).
    print(f"[gate] dryrun_multichip({n_devices})", flush=True)
    t0 = time.time()
    p = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; "
         f"g.dryrun_multichip({n_devices}); print('[gate] mesh ok')"],
        cwd=REPO)
    print(f"[gate] mesh rc={p.returncode} in {time.time() - t0:.0f}s",
          flush=True)
    return p.returncode


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tier", default="quick", choices=sorted(TIER_EXPR))
    ap.add_argument("--no-mesh", action="store_true",
                    help="skip the 8-device SPMD dryrun")
    ap.add_argument("--no-opbudget", action="store_true",
                    help="skip the op-budget check + jaxhound lints")
    ap.add_argument("--no-chaos", action="store_true",
                    help="skip the fixed chaos seed set (serving "
                         "recovery path)")
    ap.add_argument("--no-rebuild", action="store_true",
                    help="skip the rebuild-from-cluster smoke + new "
                         "fuzzer seeds")
    ap.add_argument("--no-trace-cov", action="store_true",
                    help="skip the trace-catalog coverage leg (dead/"
                         "off-catalog metric detection)")
    ap.add_argument("--no-chain", action="store_true",
                    help="skip the chain-route leg (whole-window scan "
                         "dispatch differential)")
    ap.add_argument("--no-partitioned-chain", action="store_true",
                    help="skip the partitioned-chain leg (fused "
                         "sharded window route differential + "
                         "2-process multihost leg)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="skip the overlap leg (double-buffered window "
                         "staging stall ceiling + forced-sync negative)")
    ap.add_argument("--no-reshard", action="store_true",
                    help="skip the live-resharding leg (seeded "
                         "split+migrate+merge_back under traffic + "
                         "corrupted-copy negative, "
                         "testing/reshard_smoke.py)")
    ap.add_argument("--no-overload", action="store_true",
                    help="skip the overload leg (admission-plane "
                         "Zipfian shed/SLO proof + no-shed negative)")
    ap.add_argument("--no-telemetry", action="store_true",
                    help="skip the telemetry leg (device block oracle "
                         "+ lane census + overhead ratio)")
    ap.add_argument("--no-metrics", action="store_true",
                    help="skip the metrics leg (SLO catalog check + "
                         "/metrics exposition smoke)")
    ap.add_argument("--no-causality", action="store_true",
                    help="skip the causality leg (causal request "
                         "tracing acceptance over a real vortex "
                         "cluster + negative proofs)")
    ap.add_argument("--no-profile", action="store_true",
                    help="skip the profile leg (dispatch roofline + "
                         "memwatch budget + burn-rate alert negatives)")
    ap.add_argument("--no-static", action="store_true",
                    help="skip the static leg (jaxhound determinism/"
                         "retrace/sharding passes + negative proofs)")
    ap.add_argument("--mesh-devices", type=int, default=8)
    ap.add_argument("--timeout", type=int, default=840,
                    help="test-tier wall clock budget (s)")
    args = ap.parse_args()

    reds = []
    rc = run_tests(args.tier, args.timeout)
    if rc != 0:
        reds.append(f"{args.tier} tier rc={rc}")
    if not args.no_opbudget:
        rc = run_opbudget()
        if rc != 0:
            reds.append(f"opbudget rc={rc}")
    if not args.no_chaos:
        rc = run_chaos()
        if rc != 0:
            reds.append(f"chaos rc={rc}")
    if not args.no_rebuild:
        rc = run_rebuild()
        if rc != 0:
            reds.append(f"rebuild rc={rc}")
    if not args.no_chain:
        rc = run_chain()
        if rc != 0:
            reds.append(f"chain rc={rc}")
    if not args.no_partitioned_chain:
        rc = run_partitioned_chain()
        if rc != 0:
            reds.append(f"partitioned-chain rc={rc}")
    if not args.no_overlap:
        rc = run_overlap()
        if rc != 0:
            reds.append(f"overlap rc={rc}")
    if not args.no_reshard:
        rc = run_reshard()
        if rc != 0:
            reds.append(f"reshard rc={rc}")
    if not args.no_overload:
        rc = run_overload()
        if rc != 0:
            reds.append(f"overload rc={rc}")
    if not args.no_telemetry:
        rc = run_telemetry()
        if rc != 0:
            reds.append(f"telemetry rc={rc}")
    if not args.no_trace_cov:
        rc = run_trace_coverage()
        if rc != 0:
            reds.append(f"trace-cov rc={rc}")
    if not args.no_metrics:
        rc = run_metrics()
        if rc != 0:
            reds.append(f"metrics rc={rc}")
    if not args.no_causality:
        rc = run_causality()
        if rc != 0:
            reds.append(f"causality rc={rc}")
    if not args.no_profile:
        rc = run_profile()
        if rc != 0:
            reds.append(f"profile rc={rc}")
    if not args.no_static:
        rc = run_static()
        if rc != 0:
            reds.append(f"static rc={rc}")
    if not args.no_mesh:
        rc = run_mesh(args.mesh_devices)
        if rc != 0:
            reds.append(f"dryrun_multichip({args.mesh_devices}) rc={rc}")
    if reds:
        print(f"[gate] RED: {'; '.join(reds)}", flush=True)
        return 1
    print("[gate] GREEN", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
