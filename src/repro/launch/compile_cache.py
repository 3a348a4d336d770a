"""Where the entry points keep JAX's persistent compilation cache.

``chip_smoke.py``, ``python -m repro.launch.train`` and
``benchmarks/run.py`` call :func:`enable_compile_cache` before their first
compile, so processes that compile the same programs share the work.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed directory: the cache never hits if its path moves between runs.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and no
    directory is set here. Otherwise the cache goes to ``.jax_cache/`` at
    the repository root.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
