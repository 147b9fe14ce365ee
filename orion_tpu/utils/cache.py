"""Persistent XLA compilation cache (shared by the CLIs and the benchmark).

The 1.3B train step takes over a minute to compile; caching it on disk
makes every later invocation start in seconds. The directory is part of
the cache key, so it is fixed: ``JAX_COMPILATION_CACHE_DIR`` where the
environment sets it (jax reads the variable itself, nothing is set in
code), otherwise ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def enable_compile_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(_CHECKOUT, ".jax_cache")
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 5.0)


__all__ = ["enable_compile_cache"]
