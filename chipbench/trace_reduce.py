"""From the profiler's `.xplane.pb` and the program's chrome trace of
host spans to the numbers the per-layer metrics read.

Device planes are the planes named `/device:TPU:<n>`; on each, the line
`XLA Ops` holds one event per executed HLO op and `XLA Modules` one per
executed program. Event times are nanoseconds from the profiler's
start; the `chipbench_anchor` annotation, entered by the launcher at a
recorded wall-clock instant, turns them into wall-clock times, which is
the clock of the program's spans (`Tracer` stamps `ts` in wall
microseconds).
"""

from __future__ import annotations

import functools
import glob
import json
import os
import re

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
ANCHOR = "chipbench_anchor"
# The XLA modules that are create_transfers dispatches: those whose name
# starts with this. Only the plain tier carries the name; every other
# tier (limit fixpoint, deep, ...) is
# `jax.jit(functools.partial(create_transfers_fast, ...))`, which XLA
# names `jit__unknown` (ops/fast_kernels.py:2364-2410 at PR 26), as it
# does any other unnamed entry, so those are not counted: no cell sends
# what they serve, and a cell that does needs the program to name them.
KERNEL_MODULES = ("jit_create_transfers",)
# A device gap shorter than this lies between two ops of one dispatch;
# such gaps are summed under one name and not looked up span by span.
SHORT_GAP_NS = 100_000.0
BETWEEN_OPS = "between ops of a dispatch"
# Host spans a device gap is attributed to, most specific first.
# The program's spans that occur once per committed op, each tagged
# with its `op`: a mean over them is a mean per op.
PER_OP_STAGES = ("journal_write", "commit_execute", "commit_compact")
GAP_SPANS = ("commit_checkpoint", "commit_compact", "journal_write",
             "commit_execute", "commit_prefetch", "bus_recv", "bus_send")


def find_xplane(profile_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _events(line) -> tuple[np.ndarray, np.ndarray, list[str]]:
    ev = list(line.events)
    start = np.fromiter((e.start_ns for e in ev), np.float64, len(ev))
    dur = np.fromiter((e.duration_ns for e in ev), np.float64, len(ev))
    return start, dur, [e.name for e in ev]


def union_seconds(start: np.ndarray, dur: np.ndarray) -> tuple[float, list]:
    """Length of the union of [start, start+dur) in seconds, and the
    merged intervals (ns)."""
    if not len(start):
        return 0.0, []
    order = np.argsort(start)
    merged = []
    lo, hi = start[order[0]], start[order[0]] + dur[order[0]]
    for i in order[1:]:
        s, e = start[i], start[i] + dur[i]
        if s > hi:
            merged.append((lo, hi))
            lo, hi = s, e
        else:
            hi = max(hi, e)
    merged.append((lo, hi))
    return sum(h - l for l, h in merged) / 1e9, merged


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


@functools.lru_cache(maxsize=None)
def short_op(hlo: str) -> str:
    """`%while.6 while` from the HLO text the trace names an op by."""
    name, _, rest = hlo.partition(" = ")
    m = _OPCODE.search(" " + rest)
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    op = target.group(1) if target else (m.group(1) if m else "")
    return f"{name} {op}".strip()[:100]


def reduce_xplane(path: str) -> dict:
    """Per device: op and module events; plus the anchor's trace time."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"anchor_ns": None, "devices": []}
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith(DEVICE_PREFIX):
            if OPS_LINE not in lines and MODULES_LINE not in lines:
                continue
            dev = {"name": plane.name}
            for key, name in (("ops", OPS_LINE), ("modules", MODULES_LINE)):
                dev[key] = (_events(lines[name]) if name in lines else
                            (np.zeros(0), np.zeros(0), []))
            out["devices"].append(dev)
        elif out["anchor_ns"] is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == ANCHOR:
                        out["anchor_ns"] = e.start_ns
                        break
                if out["anchor_ns"] is not None:
                    break
    return out


def device_summary(xp: dict, module_patterns: tuple) -> dict:
    """busy seconds (union of op intervals, averaged over devices), the
    merged busy intervals of the first device, and per-dispatch seconds
    of the modules whose name starts with one of `module_patterns`: the union of
    the op intervals inside each such module event. `op_seconds` sums
    each op's own events (a `while` counts its body again)."""
    busy, merged0, dispatches, op_totals, module_counts = [], [], [], {}, {}
    for d, dev in enumerate(xp["devices"]):
        o_start, o_dur, o_name = dev["ops"]
        m_start, m_dur, m_name = dev["modules"]
        src = (o_start, o_dur) if len(o_start) else (m_start, m_dur)
        seconds, merged = union_seconds(*src)
        busy.append(seconds)
        if d == 0:
            merged0 = merged
        order = np.argsort(m_start)
        ms, me = m_start[order], (m_start + m_dur)[order]
        names = [m_name[i] for i in order]
        if len(o_start) and len(ms):
            owner = np.searchsorted(ms, o_start, side="right") - 1
            inside = (owner >= 0) & (o_start < me[np.clip(owner, 0, None)])
        else:
            owner = inside = np.zeros(0, dtype=int)
        for j, name in enumerate(names):
            short = name.split("(")[0]
            module_counts[short] = module_counts.get(short, 0) + 1
            if not name.startswith(module_patterns):
                continue
            if len(o_start):
                # The union, not the sum: a `while` op's event spans its
                # body's ops, which have events of their own.
                mine = inside & (owner == j)
                dispatches.append(union_seconds(o_start[mine], o_dur[mine])[0])
            else:
                dispatches.append(float(me[j] - ms[j]) / 1e9)
        for i in range(len(o_start)):
            mod = names[owner[i]] if inside[i] else "(no module)"
            key = f"{mod.split('(')[0]}/{short_op(o_name[i])}"
            op_totals[key] = op_totals.get(key, 0.0) + o_dur[i] / 1e9
        if not len(o_start):
            for j, name in enumerate(names):
                key = name.split("(")[0]
                op_totals[key] = op_totals.get(key, 0.0) + (me[j] - ms[j]) / 1e9
    n = max(len(xp["devices"]), 1)
    return {"busy_s": sum(busy) / n, "busy_intervals_ns": merged0,
            "dispatch_seconds": dispatches, "module_counts": module_counts,
            "op_seconds": {k: v / n for k, v in op_totals.items()}}


def load_spans(chrome_path: str, read_operations=()) -> dict:
    """The program's completed spans: name -> (start_s, dur_s) arrays on
    the wall clock, and the trace's own count of dropped events. Beside
    them, for the stages that run once per committed op, each span's
    `op` tag, and the ops that are reads: those whose `commit_execute`
    names one of `read_operations` (the program's operation numbers)."""
    with open(chrome_path) as f:
        doc = json.load(f)
    by_name: dict[str, list] = {}
    read_ops = set()
    for e in doc["traceEvents"]:
        if e.get("ph") == "X":
            args = e.get("args", {})
            by_name.setdefault(e["name"], []).append(
                (e["ts"] / 1e6, e["dur"] / 1e6, args.get("op", -1)))
            if e["name"] == "commit_execute" and \
                    args.get("operation") in read_operations:
                read_ops.add(args["op"])
    spans = {k: (np.array([s for s, _, _ in v]), np.array([d for _, d, _ in v]))
             for k, v in by_name.items()}
    return {"spans": spans,
            "op": {k: np.array([op for _, _, op in by_name[k]])
                   for k in PER_OP_STAGES if k in by_name},
            "read_ops": np.array(sorted(read_ops), dtype=np.int64),
            "dropped_events": doc["metadata"]["dropped_events"]}


def stage_spans(spans: dict, name: str, ops: str = "writes"):
    """(start_s, dur_s) of the spans of `name`. Of a stage that runs
    once per committed op (PER_OP_STAGES), `ops` says which ops' spans:
    "writes", every op that is no read, so that a mean per op means in
    a cell whose mix states reads what it means in one that does not;
    "reads"; or "all"."""
    start, dur = spans["spans"][name]
    if ops == "all" or name not in PER_OP_STAGES:
        return start, dur
    # a context made by hand carries no tags: no op of it is a read
    is_read = (np.isin(spans["op"][name], spans["read_ops"])
               if "read_ops" in spans else np.zeros(len(start), bool))
    keep = is_read if ops == "reads" else ~is_read
    return start[keep], dur[keep]


def window_durations(context: dict, name: str, ops: str = "writes"):
    """Durations (s) of the program's spans of `name` that start inside
    the measured window (`ops` as in `stage_spans`), or None where there
    is nothing sound to read: no span trace, no such span, or a ring
    that dropped events."""
    spans = context["spans"]
    if spans is None or spans["dropped_events"] != 0:
        return None
    if name not in spans["spans"]:
        return None
    w = context["window"]
    start, dur = stage_spans(spans, name, ops)
    inside = dur[(start >= w["wall_t0"]) & (start < w["wall_t1"])]
    return inside if len(inside) else None


def idle_gaps(merged_ns: list, trace_t0_ns: float, trace_t1_ns: float,
              anchor_ns: float, anchor_wall_s: float, spans: dict) -> dict:
    """Device-idle seconds inside the traced window, by the host span that
    covers most of each gap ("none" where none does); the gaps under
    SHORT_GAP_NS together under BETWEEN_OPS."""
    edges = [trace_t0_ns] + [x for iv in merged_ns for x in iv] + [trace_t1_ns]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    totals: dict[str, float] = {}
    short = sum(hi - lo for lo, hi in gaps if hi - lo < SHORT_GAP_NS)
    if short:
        totals[BETWEEN_OPS] = short / 1e9
    for lo, hi in gaps:
        if hi - lo < SHORT_GAP_NS:
            continue
        w0 = anchor_wall_s + (lo - anchor_ns) / 1e9
        w1 = anchor_wall_s + (hi - anchor_ns) / 1e9
        best, best_cover = "none", 0.0
        for name in GAP_SPANS:
            if name not in spans:
                continue
            start, dur = spans[name]
            cover = np.clip(np.minimum(start + dur, w1)
                            - np.maximum(start, w0), 0, None).sum()
            if cover > best_cover + 1e-9 and cover >= 0.5 * (w1 - w0):
                best, best_cover = name, cover
                break  # most specific span that covers half the gap
        totals[best] = totals.get(best, 0.0) + (w1 - w0)
    return {k: v for k, v in totals.items() if v > 0}


def top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
