"""Process-level settings the program's entry points apply at start-up.

Nothing here runs on import: the scripts under ``benchmarks/`` and
``examples/`` and ``chip_smoke.py`` call :func:`use_compile_cache` before
their first compile, and the library itself never changes JAX's settings.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: A fixed, gitignored directory inside the checkout.  The cache directory is
#: part of what JAX keys its entries by, so it must not move between runs.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here.  Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
