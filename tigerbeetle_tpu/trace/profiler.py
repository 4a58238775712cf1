"""Sampled per-dispatch profiling + the static roofline cost model.

The trace plane (spans, device telemetry, request trees) says how long
a commit window took; this module says where the DEVICE time goes and
how far each dispatch tier sits from what the hardware could do
(ISSUE 20, the attribution side of the 302k -> 10M tps campaign):

- ``DispatchProfiler`` wraps the serving dispatch thunks (chain /
  partitioned-chain / per-batch) with deterministic 1-in-N sampling.
  A sampled dispatch is timed wall-to-ready — ``block_until_ready`` on
  the dispatch result, so the timer covers real device execution, not
  just async enqueue — and lands in the ``dispatch_device_time``
  catalog histogram partitioned by route and shape tier. Unsampled
  dispatches pay one integer increment (the gate's profile leg,
  testing/observatory_smoke.py, holds the whole plane under the 1.05
  overhead ceiling in perf/membudget_r*.json).
- Where the backend supports programmatic capture, ``capture_once``
  wraps one sampled dispatch in a ``jax.profiler`` trace (a real XLA
  profile artifact under ``capture_dir``); elsewhere the deterministic
  timer fallback is the whole story and the capture records why.
- ``static_cost_model`` derives FLOPs + HBM bytes per serving entry
  from the lowered HLO via the jaxhound registry (compiled
  ``cost_analysis``), and ``roofline_fractions`` divides each tier's
  achievable time (max of compute-limit and bandwidth-limit against
  nominal platform peaks) by its MEASURED sampled dispatch time — the
  achieved-vs-roofline fraction every bench record now carries.

Nothing here runs device code of its own: the profiler observes the
real serving routes in situ (reference: src/trace.zig's discipline —
profiling is a property of the serving path, not a separate harness).
"""

from __future__ import annotations

import time
from typing import Callable, Optional

from .event import Event

# Published peaks, keyed by `device_kind` as JAX reports it:
# (FLOP/s, HBM bytes/s). These are headline device numbers, not
# measured ceilings — the roofline fraction is an attribution signal
# (which tier is furthest from achievable), not a benchmark claim.
# "TPU v5 lite": 197 TFLOP/s bf16, 819 GB/s HBM (Google Cloud
# documentation, "TPU v5e"). A TPU kind that is not in the table is an
# error, never a default. The "cpu" row is a deliberately round
# envelope for the gate's CPU smoke (testing/observatory_smoke.py);
# nothing a chip run prints reads it.
NOMINAL_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
    "cpu": (100e9, 50e9),
}


def peaks_key(device) -> str:
    """The NOMINAL_PEAKS key for a JAX device: its `device_kind` on a
    TPU (unknown kind -> KeyError), its platform name otherwise."""
    if device.platform == "tpu":
        if device.device_kind not in NOMINAL_PEAKS:
            raise KeyError(
                f"no published peaks for TPU kind {device.device_kind!r}: "
                "add a row to trace/profiler.py NOMINAL_PEAKS, with its "
                "source")
        return device.device_kind
    return device.platform


# Representative registry entry per dispatch tier (jaxhound.registry
# names): the cost model lowers these, not all 19 entries — one per
# route keeps the bench probe seconds, not minutes.
TIER_ENTRIES = {
    "flat": "create_transfers_fast_jit",
    "chain": "create_transfers_chain_jit",
    "partitioned_chain": "partitioned_chain_step",
}

# The serving ledger's route names for each registry route: the live
# dispatch labels windows "per_batch" where the registry's flat tier
# serves them (same jit entries, different vocabulary layer).
ROUTE_ALIASES = {
    "flat": ("flat", "per_batch"),
    "chain": ("chain",),
    "partitioned_chain": ("partitioned_chain",),
}


class DispatchProfiler:
    """Deterministic 1-in-N dispatch sampler feeding the
    ``dispatch_device_time`` histogram.

    ``time(thunk, route=..., tier=...)`` replaces a bare ``thunk()``
    at the dispatch site. Sampling is a modular counter (no RNG — the
    serving path stays deterministic-replay clean); a sampled call is
    timed through ``jax.block_until_ready`` on its result. The result
    is returned either way, so the call site is oblivious."""

    def __init__(self, tracer=None, sample_every: int = 8,
                 capture_dir: Optional[str] = None):
        from .tracer import NullTracer

        if sample_every < 1:
            raise ValueError(f"sample_every must be >= 1, "
                             f"got {sample_every}")
        self.tracer = tracer if tracer is not None else NullTracer()
        self.sample_every = sample_every
        self.capture_dir = capture_dir
        self.dispatches = 0
        self.samples = 0
        self.last_us: Optional[float] = None
        # One-shot programmatic capture state: armed by capture_once(),
        # consumed by the next sampled dispatch.
        self._capture_armed = False
        self.capture_result: Optional[dict] = None

    def capture_once(self, capture_dir: Optional[str] = None) -> None:
        """Arm a one-shot ``jax.profiler`` trace around the next
        sampled dispatch. The artifact (or the reason the backend
        refused) lands in ``capture_result``."""
        if capture_dir is not None:
            self.capture_dir = capture_dir
        self._capture_armed = True

    def time(self, thunk: Callable[[], object], *, route, tier):
        """Run one dispatch, sampled 1-in-N. Returns the thunk's
        result unchanged. `route`/`tier` may be strings or zero-arg
        callables — callables resolve AFTER the thunk runs, because the
        serving ledger only knows which route a window took once it has
        dispatched it (the same late-tagging the window_commit span
        does)."""
        self.dispatches += 1
        if (self.dispatches - 1) % self.sample_every:
            return thunk()
        import jax

        capture = self._capture_armed
        if capture:
            self._capture_armed = False
            self._start_capture()
        t0 = time.perf_counter_ns()
        try:
            out = thunk()
            jax.block_until_ready(out)
        finally:
            if capture:
                self._stop_capture()
        dt_us = (time.perf_counter_ns() - t0) / 1e3
        self.samples += 1
        self.last_us = dt_us
        self.tracer.observe(Event.dispatch_device_time, dt_us,
                            route=str(route() if callable(route)
                                      else route),
                            tier=str(tier() if callable(tier)
                                     else tier))
        return out

    def _start_capture(self) -> None:
        import jax

        if self.capture_dir is None:
            self.capture_result = {"ok": False,
                                   "reason": "no capture_dir set"}
            return
        try:
            jax.profiler.start_trace(self.capture_dir)
            self.capture_result = {"ok": True, "dir": self.capture_dir}
        except Exception as e:  # backend/platform-dependent support
            self.capture_result = {
                "ok": False,
                "reason": f"{type(e).__name__}: {e} "
                          f"(deterministic timer fallback in effect)"}

    def _stop_capture(self) -> None:
        if not (self.capture_result and self.capture_result.get("ok")):
            return
        import jax

        try:
            jax.profiler.stop_trace()
        except Exception as e:
            self.capture_result = {"ok": False,
                                   "reason": f"stop_trace: "
                                             f"{type(e).__name__}: {e}"}

    def stats(self) -> dict:
        return {
            "dispatches": self.dispatches,
            "samples": self.samples,
            "sample_every": self.sample_every,
            "last_us": self.last_us,
            "capture": self.capture_result,
        }


# ------------------------------------------------------ static cost model


def static_cost_model(include_partitioned: Optional[bool] = None,
                      depth: int = 4) -> dict:
    """FLOPs + HBM bytes per dispatch tier from the lowered HLO.

    Lowers one representative jaxhound registry entry per route at the
    representative window depth, runs the compiled artifact's
    ``cost_analysis`` (jaxhound.analyze_lowered — failures are recorded
    as ``stats_unavailable`` strings, never swallowed as zero cost),
    and attaches the nominal-peak roofline seconds per platform. The
    result is deterministic for a given jax version + device count, so
    bench records can diff it across rounds."""
    import jax

    from ..jaxhound import analyze_lowered
    from ..jaxhound.registry import entries

    platform = peaks_key(jax.devices()[0])
    reg = entries(include_partitioned=include_partitioned)
    model: dict = {"platform": platform, "depth": depth, "tiers": {}}
    for tier, entry_name in TIER_ENTRIES.items():
        entry = reg.get(entry_name)
        if entry is None:  # partitioned tier absent on small meshes
            continue
        try:
            analysis = analyze_lowered(entry.lower(depth=depth))
        except Exception as e:
            model["tiers"][tier] = {
                "entry": entry_name,
                "unavailable": f"{type(e).__name__}: {e}"}
            continue
        stats = analysis.get("stats", {})
        row = {
            "entry": entry_name,
            "route": entry.route,
            "instructions": analysis.get("instructions"),
            "flops": stats.get("flops"),
            "hbm_bytes": stats.get("bytes accessed"),
            "optimal_seconds": stats.get("optimal_seconds"),
        }
        if analysis.get("stats_unavailable"):
            row["stats_unavailable"] = analysis["stats_unavailable"]
        rs = roofline_seconds(row["flops"], row["hbm_bytes"], platform)
        if rs is not None:
            row["roofline_seconds"] = rs
        model["tiers"][tier] = row
    return model


def roofline_seconds(flops, hbm_bytes, platform: str) -> Optional[float]:
    """Achievable seconds for one dispatch under the nominal peaks:
    max of the compute limit and the bandwidth limit (classic roofline
    — whichever wall binds). None when the cost analysis gave nothing
    (never fabricate a 0-second roofline)."""
    peaks = NOMINAL_PEAKS.get(platform)
    if peaks is None or not flops and not hbm_bytes:
        return None
    peak_flops, peak_bw = peaks
    return max((flops or 0.0) / peak_flops,
               (hbm_bytes or 0.0) / peak_bw)


def measured_dispatch_us(tracer) -> dict:
    """Per-series sampled dispatch summaries from a recording tracer:
    series key -> {route, tier, count, p50_us, p99_us, max_us}. Series
    keys follow the tracer's hist_tags projection
    (``dispatch_device_time|route:...,tier:...``)."""
    out: dict = {}
    series = getattr(tracer, "histogram_series", None)
    if not series:
        return out
    for key, (name, tags) in series.items():
        if name != Event.dispatch_device_time.name:
            continue
        h = tracer.histograms[key]
        s = h.summary()
        out[key] = {
            "route": tags.get("route"),
            "tier": tags.get("tier"),
            "count": s.get("count"),
            "p50_us": h.quantile(0.5),
            "p99_us": h.quantile(0.99),
            "max_us": s.get("max"),
        }
    return out


def roofline_fractions(cost_model: dict, measured: dict) -> dict:
    """Achieved-vs-roofline fraction per tier: roofline seconds over
    the measured sampled-dispatch p50 (1.0 = at the nominal wall;
    0.01 = two orders of magnitude of attribution left to claim).
    ``measured`` is ``measured_dispatch_us``'s output; routes are
    matched tier->route via the cost model rows."""
    out: dict = {}
    for tier, row in cost_model.get("tiers", {}).items():
        rs = row.get("roofline_seconds")
        if rs is None:
            continue
        route = row.get("route")
        accepted = ROUTE_ALIASES.get(route, (route,))
        p50s = [m["p50_us"] for m in measured.values()
                if m.get("route") in accepted and m.get("count")]
        if not p50s:
            continue
        measured_s = min(p50s) / 1e6  # best tier sample: the fastest
        if measured_s <= 0:
            continue
        out[tier] = {
            "route": route,
            "roofline_seconds": rs,
            "measured_p50_s": measured_s,
            "fraction": rs / measured_s,
        }
    return out


def profile_probe(tracer=None, profiler: Optional[DispatchProfiler] = None,
                  include_partitioned: Optional[bool] = None,
                  depth: int = 4) -> dict:
    """The observatory's profile record: static cost model + measured
    sampled-dispatch histograms + achieved-vs-roofline fractions per
    tier + profiler/sampling counters. Pure assembly over state the
    run already produced — the probe itself dispatches nothing."""
    cost_model = static_cost_model(
        include_partitioned=include_partitioned, depth=depth)
    measured = measured_dispatch_us(tracer) if tracer is not None else {}
    out = {
        "cost_model": cost_model,
        "dispatch_device_time": measured,
        "roofline": roofline_fractions(cost_model, measured),
    }
    if profiler is not None:
        out["sampler"] = profiler.stats()
    return out
