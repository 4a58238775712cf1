"""Convergence depth at BENCH scale (n=8190, W_PAIRS=4 windows) for the
folded fixpoint — decides whether LIMIT_FIXPOINT_ROUNDS_DEEP can drop."""
import functools
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import importlib

import perf.fixpoint_rounds_probe as P

P.N = 8190
P.W_PAIRS = 4
P.WINDOWS = 6
P.T_CAP = 1 << 19

if __name__ == "__main__":
    for rounds in (24,):
        unconv, fb = P.run(rounds)
        print(f"BENCHSCALE rounds={rounds:2d} "
              f"unconverged={sum(unconv)}/{len(unconv)} {unconv}",
              flush=True)
        if not any(unconv):
            break
