"""Seconds `start` spent warming its kernels (compiling, or loading
from the compile cache), from its own `kernels warm in` line."""

import re


def read(context: dict):
    for line in context["server_lines"]:
        m = re.match(r"^kernels warm in ([0-9.]+)s", line)
        if m:
            return float(m.group(1))
    return None
