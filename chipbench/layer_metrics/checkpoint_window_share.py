"""Percent of the window's seconds spent inside `commit_checkpoint`
spans: what the durable flush takes from the rate."""

from chipbench.trace_reduce import window_durations


def read(context: dict):
    dur = window_durations(context, "commit_checkpoint")
    if dur is None:
        return None
    return 100.0 * float(dur.sum()) / context["window"]["seconds"]
