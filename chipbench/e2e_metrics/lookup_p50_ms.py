"""Median of the client-side milliseconds, send to decoded reply, over
the window's answered lookups (a mix's `reads`): the per-operation
figure beside the request percentiles, which read every request, these
too. Nothing where the window held no lookup. No higher percentile of
its own: two dozen samples carry none, and the tail is `request_p98_ms`."""

from chipbench.window import percentile_ms


def read(context: dict):
    seconds = context["window"]["lookup_seconds"]
    return percentile_ms(seconds, 50) if seconds else None
