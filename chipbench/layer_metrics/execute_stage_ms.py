"""Milliseconds of the program's `execute_stage` spans per op: padding the
event columns to the kernel's bucket. Summed over the spans that start
inside a `commit_execute` span of the window, over the number of those
parents."""

from chipbench.span_children import child_ms_per_parent


def read(context: dict):
    return child_ms_per_parent(context, "execute_stage", "commit_execute")
