"""Persistent XLA compile cache, placeable from outside.

Entry points that time device work (``chip_smoke.py``, ``bench.py``, the
``tools/`` mains) call :func:`enable_compile_cache` before first backend use,
so a second run of the same program on the same machine compiles warm.

The directory is part of the cache key, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` decides when set (JAX reads it itself — nothing
is configured here, and no other directory is ever set in code); otherwise the
cache lives at ``<checkout>/.jax_cache``, a fixed path derived from this file.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use.
    Idempotent. Call before the first jitted dispatch."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # thresholds at zero: the small programs (feed touch, top-k, probes) are
    # most of a cold start's compile COUNT and are worth keeping too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
