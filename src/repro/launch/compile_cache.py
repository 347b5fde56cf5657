"""JAX's persistent compilation cache, kept at a fixed path.

The cache key includes the directory, so a path that moves between runs
never hits: ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads
it itself), and otherwise the cache lives at ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
