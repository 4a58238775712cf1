"""Milliseconds per committed op of the serving thread's busy time
that lie under no commit stage: `loop_busy` seconds of the window less
the seconds inside `commit_execute`, `commit_compact` and
`commit_checkpoint` spans, over the `commit_execute` spans that start in
the window: those of every op, reads too, since the serving thread owes
this work to a prepare of any operation. What is left is the protocol's
own work: frame checksum, on_request, journal submit, reply build and
send."""

from chipbench.span_children import seconds_in_window
from chipbench.trace_reduce import window_durations

STAGES = ("commit_execute", "commit_compact", "commit_checkpoint")


def read(context: dict):
    busy = seconds_in_window(context, "loop_busy")
    ops = window_durations(context, "commit_execute", ops="all")
    if busy is None or ops is None:
        return None
    staged = sum(seconds_in_window(context, s) or 0.0 for s in STAGES)
    return 1e3 * (busy - staged) / len(ops)
