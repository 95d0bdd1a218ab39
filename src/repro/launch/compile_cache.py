"""JAX's persistent compilation cache for the program's entry points.

Entry points call ``enable_compile_cache()`` from their ``main``; nothing
turns the cache on at import.  Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX already keeps its cache there and this module changes nothing.
Otherwise the cache lives at the fixed ``<checkout>/.jax_cache``
(git-ignored): the directory is part of each entry's key, so it is never
derived from a temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
