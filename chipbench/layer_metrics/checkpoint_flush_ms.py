"""Milliseconds of the program's `checkpoint_flush` spans per checkpoint:
the durable flush inside DurableState.checkpoint. Summed over the spans
that start inside a `commit_checkpoint` span of the window, over the
number of those parents."""

from chipbench.span_children import child_ms_per_parent


def read(context: dict):
    return child_ms_per_parent(context, "checkpoint_flush", "commit_checkpoint")
