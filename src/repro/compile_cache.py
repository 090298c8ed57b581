"""Persistent XLA compilation cache for the program's entry points.

``chip_smoke.py``, ``benchmarks/run.py`` and the examples call
:func:`enable_compile_cache` first thing, so a second run with the same
shapes loads compiled programs instead of compiling them again.  Importing
the library never turns the cache on, and the tests never call this.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# Fixed path inside the checkout (listed in .gitignore): compiled programs
# are looked up only in the directory they were written to, so a path that
# varies from run to run would never hit.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no other path.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
