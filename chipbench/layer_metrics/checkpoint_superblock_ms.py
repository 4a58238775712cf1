"""Milliseconds of the program's `checkpoint_superblock` spans per
checkpoint: snapshot write, superblock store and the event-tail prune.
Summed over the spans that start inside a `commit_checkpoint` span of
the window, over the number of those parents."""

from chipbench.span_children import child_ms_per_parent


def read(context: dict):
    return child_ms_per_parent(context, "checkpoint_superblock", "commit_checkpoint")
