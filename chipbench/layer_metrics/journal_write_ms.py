"""Milliseconds of the program's `journal_write` span inside the window:
WAL prepare+header write, mean per prepare that is no read
(`trace_reduce.stage_spans`: a read's body is a few ids)."""

from chipbench.trace_reduce import window_durations


def read(context: dict):
    dur = window_durations(context, "journal_write")
    return None if dur is None else 1e3 * float(dur.mean())
