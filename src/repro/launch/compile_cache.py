"""JAX's persistent compilation cache for the repo's entry points.

``enable_compile_cache()`` is called from each ``main()`` (the chip smoke,
`repro.launch.serve`, the benchmark runner), never at import. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
else is configured here. Otherwise the cache lives in a fixed directory
inside the checkout: the path is part of the cache key, so it must not
depend on a temp name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
