"""Milliseconds of the program's `flush_two_phase` spans per op: the
column flush's loop over the rows that set a pending status or read
their pending transfer (a put into the `pending` tree a pending; two
tree reads, a 128-byte copy and a put a post or void), the fold of the
trees it reads included (`memtable_fold_ms`). It lies inside
`flush_columns`, so it is a part of `flush_columns_ms`, not beside it.
Summed over the spans that start inside a `commit_compact` span of the
window, over the number of those parents: a request of pendings and a
request of posts count alike. Nothing where the program has no such
span (a parent of the PR that added it) or opened none (a window with
no two-phase row)."""

from chipbench.span_children import child_ms_per_parent


def read(context: dict):
    return child_ms_per_parent(context, "flush_two_phase", "commit_compact")
