"""Where the entry points keep JAX's persistent compilation cache.

Called by ``main`` functions only, never when a library module is
imported: a library that moved the cache would decide for every program
that imports it.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache: src/repro/launch/ -> three levels up. A fixed
# path, so every run from this checkout finds what the last one compiled.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own
    and nothing is set here; otherwise the cache goes to ``DEFAULT_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
