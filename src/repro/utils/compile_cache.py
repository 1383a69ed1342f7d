"""Where JAX's persistent compilation cache lives.

Entry points call :func:`enable_compile_cache` before their first compile;
importing the library never touches the cache.  The cache directory is part
of every entry's key, so it is a fixed path: a directory built from a temp
name, a pid or a time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache — this file is <checkout>/src/repro/utils/.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at a fixed directory and
    return it.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads
    it itself and nothing else is set here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
