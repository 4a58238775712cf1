"""Milliseconds of the program's `execute_encode` spans per op:
status/timestamp arrays to host and the reply's wire encode. Summed over
the spans that start inside a `commit_execute` span of the window, over
the number of those parents."""

from chipbench.span_children import child_ms_per_parent


def read(context: dict):
    return child_ms_per_parent(context, "execute_encode", "commit_execute")
