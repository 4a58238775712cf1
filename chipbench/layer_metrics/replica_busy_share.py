"""Percent of the window inside the program's `loop_busy` spans: the
one serving thread's utilisation. A busy turn runs from the moment the
bus's wait ended to the next poll call, and is recorded when it lasted
1 ms or more; the rest of the window the thread sat in its select."""

from chipbench.span_children import seconds_in_window


def read(context: dict):
    busy = seconds_in_window(context, "loop_busy")
    if busy is None:
        return None
    return 100.0 * busy / context["window"]["seconds"]
