"""JAX's persistent compilation cache for the entry-point scripts."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path inside the checkout: the directory is part of every cache
# key, so a path that moved between processes would never hit.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (JAX reads the
    variable itself, and no other directory is set here); otherwise the
    cache is ``<checkout>/.jax_cache``. Every program is cached, however
    quick its compile, so a second process compiles nothing it has seen.
    Library code and the tests never call this: only ``__main__`` blocks."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
