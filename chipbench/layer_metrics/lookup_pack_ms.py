"""Milliseconds of the program's `lookup_pack` spans per lookup: of a
served lookup, the rows found, in request order, packed into the reply.
Summed over the spans that start inside the `commit_execute` of a lookup
of the window, over the number of those lookups: a part of
`lookup_execute_ms`. Nothing where the program has no such span (a
parent of the PR that added it) or the window held no read."""

from chipbench.span_children import child_ms_per_parent


def read(context: dict):
    return child_ms_per_parent(context, "lookup_pack", "commit_execute",
                               ops="reads")
