"""Persistent XLA compile cache for the program's entry points.

The store compiles dozens of small flush, compaction and read programs, so a
cold process spends seconds in the compiler before its first answer. Entry
points (``chip_smoke.py`` and the benchmark mains) call
``enable_compile_cache()`` once; library code and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed path inside the checkout: the cache key includes nothing that moves
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, places the cache and JAX reads
    it itself; otherwise the cache lives at ``<repo>/.jax_cache``. Every
    program is cached, however fast it compiled."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir
