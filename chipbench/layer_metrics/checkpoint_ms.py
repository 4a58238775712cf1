"""Milliseconds of the program's `commit_checkpoint` span inside the window:
forest checkpoint + superblock flip, mean per checkpoint."""

from chipbench.trace_reduce import window_durations


def read(context: dict):
    dur = window_durations(context, "commit_checkpoint")
    return None if dur is None else 1e3 * float(dur.mean())
