"""Milliseconds of the program's `flush_objects` spans per op: the durable
flush's object loops over the mirror's dirty stores. Summed over the
spans that start inside a `commit_compact` span of the window, over the
number of those parents."""

from chipbench.span_children import child_ms_per_parent


def read(context: dict):
    return child_ms_per_parent(context, "flush_objects", "commit_compact")
