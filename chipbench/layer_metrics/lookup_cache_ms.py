"""Milliseconds of the program's `lookup_cache` spans per lookup: of a
served lookup, the loop over the ids against the object cache, hits kept
and misses listed. Summed over the spans that start inside the
`commit_execute` of a lookup of the window, over the number of those
lookups: a part of `lookup_execute_ms`. Nothing where the program has no
such span (a parent of the PR that added it) or the window held no
read."""

from chipbench.span_children import child_ms_per_parent


def read(context: dict):
    return child_ms_per_parent(context, "lookup_cache", "commit_execute",
                               ops="reads")
