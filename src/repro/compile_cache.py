"""Persistent compilation cache for the program's entry points.

Entry points (``chip_smoke.py``, ``python -m repro.api.cli``,
``benchmarks/run.py`` and the benchmark ``__main__``s) call
:func:`enable` once before their first compile. Importing the package
never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout's own cache directory (git-ignored). Fixed on purpose:
#: the path is part of what a cache entry is found under, so a directory
#: named after a temp dir, a pid or the time would never hit again.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives in
    :data:`CHECKOUT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
