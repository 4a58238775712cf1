"""Milliseconds of the program's `commit_compact` span inside the window:
durable flush of the committed op + one compaction beat, mean per prepare
that is no read (`trace_reduce.stage_spans`: a read has nothing to flush;
the beat it owes all the same is in `compact_beat_ms`)."""

from chipbench.trace_reduce import window_durations


def read(context: dict):
    dur = window_durations(context, "commit_compact")
    return None if dur is None else 1e3 * float(dur.mean())
