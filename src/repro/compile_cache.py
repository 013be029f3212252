"""JAX's persistent compilation cache for this checkout's entry points.

A compiled program is keyed by, among other things, the cache directory,
so the directory has to be the same from one run to the next for a later
run to find what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, inside the checkout, git-ignored (.gitignore: .jax_cache/)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it as
    ``jax_compilation_cache_dir`` and nothing is set here. Otherwise the
    cache goes to ``CHECKOUT_CACHE_DIR``. Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
