"""Where JAX keeps its persistent compilation cache.

A cold compile of the serving programs takes minutes on the chip; the cache
turns the next process's compiles into reads. Its directory is part of the
cache's identity, so it must not move between runs: a fixed path inside the
checkout, or the one the deployment names.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["configure_compile_cache", "DEFAULT_DIR"]

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn the cache on for this process and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone; otherwise the cache lives in ``<repo>/.jax_cache``.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
