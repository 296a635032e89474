"""JAX's persistent compilation cache at a fixed place.

The cache directory is part of what a later process looks up, so it must
not move between runs: ``$JAX_COMPILATION_CACHE_DIR`` when the environment
sets it (JAX reads that variable itself), else ``.jax_cache/`` at the root
of this checkout. Entry points call ``enable_compile_cache()`` before they
compile anything.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its fixed directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
