"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` once, before they compile.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
module sets nothing.  Otherwise the cache lives at a fixed ``.jax_cache``
directory in the checkout: the path is part of the cache key, so a name
that moves between runs (a temp dir, a pid, a time) would never hit.
Library code and tests never call it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
