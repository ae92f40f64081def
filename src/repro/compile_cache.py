"""JAX's persistent compilation cache for the program's entry points.

``chip_smoke.py``, ``python -m repro.server.launch``,
``benchmarks/run.py`` and ``benchmarks/load_bench.py`` call
:func:`enable_compile_cache` before they compile anything, so a second
run in the same checkout loads its programs instead of compiling them.
The tests do not call it.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["CACHE_DIR", "enable_compile_cache"]

# fixed, inside the checkout and git-ignored: the directory is part of
# what a cache entry is found by, so a path built from a temporary
# name, a pid or the time would never hit
CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing; otherwise the cache lives in :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
