"""Where JAX keeps its persistent compilation cache.

A cold process compiles every program it runs; the persistent cache lets
the next process on the same machine load them instead. The cache is
keyed by path, so it lives at a fixed place: ``JAX_COMPILATION_CACHE_DIR``
when that is set (JAX reads the variable itself), otherwise
``<repo root>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compilation. Sets nothing when
    ``JAX_COMPILATION_CACHE_DIR`` is set.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
