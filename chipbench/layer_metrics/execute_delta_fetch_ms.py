"""Milliseconds of the program's `execute_delta_fetch` spans per op: the
write-through delta gather and the device -> host copy it starts. Summed
over the spans that start inside a `commit_execute` span of the window,
over the number of those parents."""

from chipbench.span_children import child_ms_per_parent


def read(context: dict):
    return child_ms_per_parent(context, "execute_delta_fetch", "commit_execute")
