"""Milliseconds of the program's `memtable_fold` spans per op: the fold
of the column runs of `xfer_by_ts` and `transfers` into their memtables'
dicts, a row at a time, which the first read by key of a request of
posts or voids forces. It lies inside `flush_two_phase`. Summed over
the spans that start inside a `commit_compact` span of the window, over
the number of those parents (an op that folds nothing counts as zero).
Nothing where the program has no such span or opened none."""

from chipbench.span_children import child_ms_per_parent


def read(context: dict):
    return child_ms_per_parent(context, "memtable_fold", "commit_compact")
