"""JAX's persistent compilation cache for the repo's entry points."""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: a fixed path, because the path is part of what a
# cache entry is found under (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and no
    other path is set here; otherwise the cache lives in the checkout's
    ``.jax_cache``.  Every compile is kept, however quick: a cold start
    pays for dozens of kernel and step compiles.  Called at the start of
    each entry point's ``main``, never at import."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
