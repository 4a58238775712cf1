"""Milliseconds of the program's `flush_columns` spans per op: the
vectorized durable flush of the op's device delta columns (waits for the
delta's bytes first). Summed over the spans that start inside a
`commit_compact` span of the window, over the number of those parents."""

from chipbench.span_children import child_ms_per_parent


def read(context: dict):
    return child_ms_per_parent(context, "flush_columns", "commit_compact")
