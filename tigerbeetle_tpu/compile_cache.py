"""The one persistent XLA compile cache. Two places use it: `start`
(`main.py` `cmd_start`) turns it on with `enable()`, and the warm-up
(`ops/warmup.py` `warmup_kernels`) precompiles its warm set in parallel
where JAX's cache directory is set. `chip_smoke.py` reports what
`start` prints.

Serving kernels at production caps take tens of seconds each to compile
for a TPU, so a server that recompiled them at every boot would spend
minutes before `listening`. Where JAX_COMPILATION_CACHE_DIR is set JAX
reads it itself and this module sets nothing; otherwise the cache lives
at one fixed, git-ignored path inside the checkout (the path is part of
the cache key: a directory named after a pid, a time or a temporary
name would never hit).
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_DIR = os.path.join(_CHECKOUT, "scratch", "jax_cache")


def enable() -> str | None:
    """Turn the persistent cache on for this process; returns its path,
    or None on the CPU backend (it compiles in seconds, and its cache
    loader prints a page of machine-feature warnings on every hit)
    unless the environment asked for a cache by name."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
