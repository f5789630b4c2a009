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


def keep_scope_names_in_cache_key() -> None:
    """The step names its phases in op metadata (``jax.named_scope``:
    ``optimizer_update``, ``layers``, ...) and device traces are cut by
    those names. JAX leaves metadata out of the persistent cache's key by
    default, so a cache warmed by another build of the same arithmetic
    hands back an executable that carries that build's names. Keep the
    metadata in the key, with the checkout's own path cut from the source
    files so that a second checkout of the same tree still hits. Called
    when ``apex_tpu`` is imported: any compile may land in a cache the
    machine set up (``JAX_COMPILATION_CACHE_DIR``)."""
    import re

    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if not jax.config.jax_hlo_source_file_canonicalization_regex:
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          re.escape(_CHECKOUT + os.sep))


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
