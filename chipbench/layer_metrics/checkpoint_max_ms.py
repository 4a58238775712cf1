"""Milliseconds of the longest `commit_checkpoint` span inside the
window: what the requests in flight at a checkpoint wait for."""

from chipbench.trace_reduce import window_durations


def read(context: dict):
    dur = window_durations(context, "commit_checkpoint")
    return None if dur is None else 1e3 * float(dur.max())
