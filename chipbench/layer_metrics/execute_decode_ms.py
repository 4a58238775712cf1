"""Milliseconds of the program's `execute_decode` spans per op: wire
validation, multi-batch decode and bytes -> SoA columns. Summed over the
spans that start inside a `commit_execute` span of the window, over the
number of those parents."""

from chipbench.span_children import child_ms_per_parent


def read(context: dict):
    return child_ms_per_parent(context, "execute_decode", "commit_execute")
