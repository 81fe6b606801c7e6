"""Where JAX keeps its persistent compilation cache.

Every entry point that compiles the serving path (``repro.launch.serve``,
``benchmarks/serving_bench.py``, ``chip_smoke.py``) calls
:func:`enable_compile_cache` once before its first compile.  The cache
key includes the directory, so the default is a fixed path inside the
checkout — never one made from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <repo>/.jax_cache (gitignored)
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
    nothing is set here; otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
