"""Milliseconds of the program's `commit_execute` span inside the window:
state-machine execution (the device dispatch and its host work), mean per
prepare that is no read (`trace_reduce.stage_spans`; a read's: `lookup_execute_ms`)."""

from chipbench.trace_reduce import window_durations


def read(context: dict):
    dur = window_durations(context, "commit_execute")
    return None if dur is None else 1e3 * float(dur.mean())
