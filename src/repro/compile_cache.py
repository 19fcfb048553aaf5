"""JAX's persistent compilation cache, as the program's entry points use it.

A cold run on a TPU compiles every scan program; the persistent cache lets
the next process load them instead.  `enable_compile_cache` is called once
by each entry point (`chip_smoke.py`, `benchmarks/run.py`,
`repro.launch.serve`, `examples/*`) before anything compiles.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed directory inside the checkout (listed in .gitignore).
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here.  Otherwise the cache lives in
    ``<repo>/.jax_cache``.  Every program is cached, however quickly it
    compiled: a cold run compiles hundreds of them.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
