"""Zipfian item indices by the exact inverse CDF.

Copied from tigerbeetle_tpu/utils/zipfian.py (PR 26): item i (0-based)
has probability proportional to 1/(i+1)^theta (upstream:
src/stdx/zipfian.zig, the benchmark's hot-account shape; upstream uses
the YCSB approximation of the same distribution).
"""

from __future__ import annotations

import numpy as np


def zipfian_cdf(n: int, theta: float) -> np.ndarray:
    weights = 1.0 / np.power(np.arange(1, n + 1, dtype=np.float64), theta)
    return np.cumsum(weights / weights.sum())


def draw(cdf: np.ndarray, rng: np.random.Generator, count: int) -> np.ndarray:
    """`count` item indices in [0, n), item 0 the most likely."""
    idx = np.searchsorted(cdf, rng.random(count), side="left")
    return np.minimum(idx, len(cdf) - 1).astype(np.int64)
