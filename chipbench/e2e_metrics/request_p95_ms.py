"""95th percentile of the client-side milliseconds, send to decoded reply,
over all answered requests of the window, of any operation."""

from chipbench.window import percentile_ms


def read(context: dict):
    return percentile_ms(context["window"]["request_seconds"], 95)
