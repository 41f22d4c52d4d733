"""Where JAX's persistent compilation cache lives.

One rule for every entry point (chip_smoke.py, benchmark/run.py, the
measurement tools, tests/conftest.py): when ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing here sets a directory; otherwise
the cache is one fixed git-ignored directory inside the checkout. The
directory is part of the cache key, so it must not move between runs.
"""
from __future__ import annotations

import os

__all__ = ["CHECKOUT_CACHE_DIR", "configure_compile_cache"]

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache(min_compile_time_secs: float = 2.0) -> str:
    """Place the persistent compile cache; returns the directory in use.
    ``min_compile_time_secs`` is the smallest compile worth an entry."""
    import jax
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        placed = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", placed)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_time_secs))
    return placed
