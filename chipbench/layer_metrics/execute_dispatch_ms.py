"""Milliseconds of the program's `execute_dispatch` spans per op: the
kernel dispatch, jit call until its fallback flags are on the host
(launch + device + sync; an escalation's second dispatch adds to its
op). Summed over the spans that start inside a `commit_execute` span of
the window, over the number of those parents."""

from chipbench.span_children import child_ms_per_parent


def read(context: dict):
    return child_ms_per_parent(context, "execute_dispatch", "commit_execute")
