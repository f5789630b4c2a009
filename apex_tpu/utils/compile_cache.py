"""Persistent XLA compilation cache at a path that never moves.

The cache key includes the directory, so a path built from a temporary
name, a pid or the time never hits. Entry points (``chip_smoke.py``,
``bench.py``, the ``examples/`` mains) call :func:`enable_compile_cache`
once before their first compile.

No reference-file citation: NVIDIA Apex has no compile step to cache.
"""

from __future__ import annotations

import os

#: the checkout root, found from this file (``<checkout>/apex_tpu/utils/``)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Returns the cache directory in force. With
    ``JAX_COMPILATION_CACHE_DIR`` set JAX reads it itself and nothing is
    configured here; otherwise the cache is ``<checkout>/.jax_cache`` and
    every program is kept, however quick its compile, so a second run of
    the same command adds no entries."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
