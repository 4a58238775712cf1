"""Milliseconds of the program's `commit_execute` span over the window's
lookups (the ops whose span names a read operation), mean per lookup:
the state machine answering a read, ids from bytes to the reply's rows.
It has no child span yet. Nothing where the window held no read."""

from chipbench.trace_reduce import window_durations


def read(context: dict):
    dur = window_durations(context, "commit_execute", ops="reads")
    return None if dur is None else 1e3 * float(dur.mean())
