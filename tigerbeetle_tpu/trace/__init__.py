"""Tracing and metrics subsystem: typed catalog, spans, StatsD, merge.

reference: src/trace.zig + src/trace/event.zig + src/trace/statsd.zig.
Layout mirrors the reference:

- `event.py`  — the typed event catalog (every legal span/counter/gauge,
  fixed tag schemas, per-event concurrency lanes). Free-form names are a
  hard error under the recording tracer; the gate's coverage leg fails
  on catalog events the smokes never emit.
- `tracer.py` — NullTracer (production default, zero overhead) and the
  recording Tracer (bounded ring with self-describing eviction,
  wall-clock-anchored timestamps, per-event timing aggregates), and
  `install_gc_spans` (one `host_gc` span per collector pause).
- `statsd.py` — DogStatsD UDP emission + interval-flushed aggregates
  (gauges reset after emit, like the reference) with histogram-derived
  p50/p95/p99/p999 `|ms` timing lines per series.
- `histogram.py` — log2-bucketed, losslessly mergeable latency
  histograms (~1% relative error), fed by every span at close.
- `merge.py`  — cluster-wide trace merge (pid=replica, common timeline),
  exact offline span quantiles, p99 critical-path attribution, and
  causal assembly: per-request span trees from propagated trace
  contexts, with clock-skew correction from matched bus span pairs.
- `context.py` — the compact trace-context block (trace_id u128,
  parent_span_id u64, sampled flag) carried in the VSR header's
  reserved region, plus deterministic minting and head sampling.
- `slo.py`    — objectives from perf/slo.json, evaluation against live
  histograms, and run-granular burn-rate accounting.
- `flight_recorder.py` — bounded per-replica ring of per-window device
  telemetry + route decisions + epoch digests, dumped as a JSON
  artifact on quarantine/recovery/retry-exhaustion, with lossless
  cross-replica merge via the shared histogram layout.
- `profiler.py` — the performance observatory's dispatch side: sampled
  block-until-ready dispatch timing (`dispatch_device_time`), optional
  programmatic jax.profiler capture, and the static FLOPs/HBM-bytes
  cost model + achieved-vs-roofline fractions per dispatch tier.
- `memwatch.py` — device-memory watermark plane: the deterministic
  static-allocation ledger (bytes per component from shapes) audited
  against the committed perf/membudget_r*.json, plus per-device
  allocator stats where the backend exposes them.
- `alerts.py`  — SRE-style multi-window multi-burn-rate alert engine
  over the SLO objectives, in commit-window-tick time: typed alerts
  with runbook anchors, `alert:<rule>` tail retention, and page-
  severity flight-recorder freezes.

The tracer is injected at construction into the replica (which hands it
on to its durable state, its state machine and the device ledger),
journal, grid scrubber, message bus, serving supervisor, and sharded
router; `trace/span_tree.py` lays the commit stages' child spans over
their parents; see
docs/operating/monitoring.md for the operator-facing catalog.
"""

from .alerts import Alert, AlertEngine, AlertRule, load_alert_rules
from .context import (TraceContext, fmt_span_id, fmt_trace_id,
                      head_sampled, mint_context, mint_trace_id)
from .event import CATALOG, TID_BASE, Event, EventKind, EventSpec, lookup
from .flight_recorder import FlightRecorder, merge_flight_records
from .histogram import Histogram
from .memwatch import (MemWatch, check_budget, device_memory_stats,
                       load_budget, measure_ledger, pytree_bytes,
                       static_ledger)
from .merge import (CRITICAL_PATH_STAGES, assemble_traces, causal_edges,
                    critical_path, estimate_clock_offsets,
                    merge_trace_files, merge_traces, span_quantile)
from .profiler import (DispatchProfiler, measured_dispatch_us,
                       profile_probe, roofline_fractions,
                       roofline_seconds, static_cost_model)
from .slo import Objective, burn_rates, evaluate, load_objectives
from .statsd import StatsD, TimingAggregates
from .tracer import NullTracer, Tracer, install_gc_spans

__all__ = [
    "CATALOG", "TID_BASE", "Event", "EventKind", "EventSpec", "lookup",
    "TraceContext", "fmt_span_id", "fmt_trace_id", "head_sampled",
    "mint_context", "mint_trace_id",
    "FlightRecorder", "merge_flight_records",
    "Histogram", "CRITICAL_PATH_STAGES", "critical_path",
    "assemble_traces", "causal_edges", "estimate_clock_offsets",
    "merge_trace_files", "merge_traces", "span_quantile",
    "Objective", "burn_rates", "evaluate", "load_objectives",
    "StatsD", "TimingAggregates",
    "NullTracer", "Tracer", "install_gc_spans",
    "Alert", "AlertEngine", "AlertRule", "load_alert_rules",
    "MemWatch", "check_budget", "device_memory_stats", "load_budget",
    "measure_ledger", "pytree_bytes", "static_ledger",
    "DispatchProfiler", "measured_dispatch_us", "profile_probe",
    "roofline_fractions", "roofline_seconds", "static_cost_model",
]
