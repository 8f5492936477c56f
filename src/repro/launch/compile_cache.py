"""JAX's persistent compilation cache, kept at one fixed place.

Every entry point (``chip_smoke.py``, ``python -m repro.launch.serve``,
the benchmark mains) calls :func:`enable_compile_cache` before its first
compile, so a second run of the same programs on the same machine loads
them instead of compiling again.
"""
from __future__ import annotations

import os
import pathlib

#: Default cache directory: ``.jax_cache/`` at the root of the checkout.
#: The path is part of the cache key, so it must not move between runs.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it at import and
    this sets nothing.  Otherwise the cache goes to :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
