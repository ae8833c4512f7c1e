"""Where jax keeps its persistent compilation cache for the launchers.

The cache key includes the cache path, so the directory must not move
between runs: a caller that sets ``JAX_COMPILATION_CACHE_DIR`` chooses it
(jax reads the variable itself), otherwise it is the fixed ``.jax_cache``
directory at the root of this checkout. Tests never call this, so they run
with the cache off.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
