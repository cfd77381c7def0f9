"""Where JAX keeps its persistent compilation cache.

Called from the launchers' ``main`` functions, never at import time, so
importing any module of this package leaves JAX's configuration alone."""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed default: a cache entry is found again only under the same path
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads
    it itself. Otherwise the cache goes to ``.jax_cache`` at the repo
    root."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
