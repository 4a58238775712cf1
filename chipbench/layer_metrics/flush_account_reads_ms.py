"""Milliseconds of the program's `flush_account_reads` spans per op: the
column flush's reads of the previous row of each distinct account of the
op, one `Tree.get` a key, from the memtable's dict or, where the row was
last written before the last freeze, from the level tables. It lies
inside `flush_columns`, so it is a part of `flush_columns_ms`, not
beside it. Summed over the spans that start inside a `commit_compact`
span of the window's writes, over the number of those parents. Nothing
where the program has no such span (a parent of the PR that added
it)."""

from chipbench.span_children import child_ms_per_parent


def read(context: dict):
    return child_ms_per_parent(context, "flush_account_reads",
                               "commit_compact")
