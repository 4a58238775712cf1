"""Percent of the window inside the program's `host_gc` spans: pauses
of Python's cyclic collector, one span per collection. A program that
records no `host_gc` span in its whole run has no such hook and the
metric is left out; one that has the hook and collected nothing inside
the window reads 0."""

from chipbench.span_children import seconds_in_window


def read(context: dict):
    paused = seconds_in_window(context, "host_gc")
    if paused is None:
        return None
    return 100.0 * paused / context["window"]["seconds"]
