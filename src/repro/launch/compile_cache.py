"""Where the persistent XLA compilation cache lives.

Called once by each entry point (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve``, ``benchmarks/run.py``) before its first compile.
Nothing calls it on import or in tests.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed directory inside the checkout (listed in .gitignore), so that every
# run from the same checkout finds the programs the last one compiled.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    directory is set here; otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
