"""Milliseconds of the longest `compact_beat` span that starts inside a
`commit_compact` span of the window: the one beat a bar that freezes
and sorts the memtable, which `compact_beat_ms`, a mean over the ops,
hides. The request in flight waits for it, a read as a write: every
op's `commit_compact` counts."""

from chipbench.span_children import children_in_window


def read(context: dict):
    found = children_in_window(context, "compact_beat", "commit_compact",
                               ops="all")
    return None if found is None else 1e3 * float(found[0].max())
