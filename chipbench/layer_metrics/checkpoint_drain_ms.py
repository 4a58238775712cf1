"""Milliseconds of the program's `checkpoint_mirror_drain` spans per
checkpoint: the session pack and the state read that drains the deferred
device mirror into host objects. Summed over the spans that start inside
a `commit_checkpoint` span of the window, over the number of those
parents."""

from chipbench.span_children import child_ms_per_parent


def read(context: dict):
    return child_ms_per_parent(context, "checkpoint_mirror_drain", "commit_checkpoint")
