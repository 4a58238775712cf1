"""Milliseconds of the program's `compact_beat` spans per op: one
compaction beat over every tree of the forest. Summed over the spans
that start inside a `commit_compact` span of the window, over the number
of those parents: every op's, a read's too, since each op owes a beat
whatever it carries."""

from chipbench.span_children import child_ms_per_parent


def read(context: dict):
    return child_ms_per_parent(context, "compact_beat", "commit_compact",
                               ops="all")
