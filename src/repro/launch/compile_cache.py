"""JAX's persistent compilation cache for the entry points of this checkout.

Entry points (``launch.serve``'s ``main``, ``chip_smoke.py``) call
:func:`enable_compile_cache` before they compile anything; importing a
module never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

#: The checkout's own cache directory (git-ignored). Its path is part of
#: each entry's key, so it is fixed: a run finds what an earlier run in the
#: same checkout compiled.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it
    itself and no other directory is set here. Otherwise the cache is
    :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
