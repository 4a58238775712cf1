"""The program's child spans laid over their parents, for the readers
of the per-phase metrics.

`trace_reduce.load_spans` keys the program's spans by name and keeps
only (start, duration), so a child is matched to its parent by
containment in time: it belongs to the parent occurrence it starts
inside. `DurableState.flush` runs under `commit_compact` and again
under a checkpoint, so `flush_columns` and `flush_objects` appear under
both; a reader that names `commit_compact` as the parent counts only
the per-op pass. Every function returns None where there is nothing
sound to read (no span trace, a ring that dropped events, a program
that has no such span), and the metric is then left out of the line.
"""

from __future__ import annotations

import numpy as np

from chipbench.trace_reduce import stage_spans


def _sound(context: dict, *names: str):
    spans = context["spans"]
    if spans is None or spans["dropped_events"] != 0:
        return None
    by_name = spans["spans"]
    if any(n not in by_name for n in names):
        return None
    return by_name


def children_in_window(context: dict, child: str, parent: str,
                       ops: str = "writes"):
    """Durations (s) of the `child` spans that start inside a `parent`
    that starts inside the measured window, and the number of those
    parents; None where there is nothing sound to read or no such
    child. Of a parent that runs once per committed op, `ops` says
    which ops' (`trace_reduce.stage_spans`): the writes' for what a
    write alone does (a read executes and flushes nothing of theirs),
    "all" for what every op owes, as its compaction beat."""
    by_name = _sound(context, child, parent)
    if by_name is None:
        return None
    w = context["window"]
    p_start, p_dur = stage_spans(context["spans"], parent, ops)
    keep = (p_start >= w["wall_t0"]) & (p_start < w["wall_t1"])
    if not keep.any():
        return None
    order = np.argsort(p_start[keep])
    p_lo = p_start[keep][order]
    p_hi = (p_start + p_dur)[keep][order]
    c_start, c_dur = by_name[child]
    owner = np.searchsorted(p_lo, c_start, side="right") - 1
    inside = (owner >= 0) & (c_start < p_hi[np.clip(owner, 0, None)])
    if not inside.any():
        return None
    return c_dur[inside], len(p_lo)


def child_ms_per_parent(context: dict, child: str, parent: str,
                        ops: str = "writes"):
    """Milliseconds of `child` spans per occurrence of `parent`: the
    summed duration of the children that start inside a parent that
    starts inside the measured window, over the number of those parents
    (a parent with no such child counts as zero); `ops` as in
    `children_in_window`."""
    found = children_in_window(context, child, parent, ops)
    if found is None:
        return None
    return 1e3 * float(found[0].sum()) / found[1]


def seconds_in_window(context: dict, name: str):
    """Seconds of the window that lie inside spans of `name` (each span
    clipped to the window), or None."""
    by_name = _sound(context, name)
    if by_name is None:
        return None
    w = context["window"]
    start, dur = by_name[name]
    return float(np.clip(np.minimum(start + dur, w["wall_t1"])
                         - np.maximum(start, w["wall_t0"]), 0, None).sum())
