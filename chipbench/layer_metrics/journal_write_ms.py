"""Milliseconds of the program's `journal_write` span inside the window:
WAL prepare+header write, mean per prepare."""

from chipbench.trace_reduce import window_durations


def read(context: dict):
    dur = window_durations(context, "journal_write")
    return None if dur is None else 1e3 * float(dur.mean())
