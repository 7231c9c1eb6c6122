"""Where the entry points keep JAX's persistent compile cache.

A cold YOLO serve compiles several large executables; the persistent
cache lets the next run of an entry point load them instead. The cache
key includes the directory, so the directory must not move between runs.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# One fixed directory inside the checkout (git-ignored).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here; otherwise the cache goes to
    :data:`DEFAULT_DIR`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
