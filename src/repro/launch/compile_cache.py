"""JAX's persistent compilation cache, at one fixed place.

Compiling the paper-width train step takes tens of seconds, and each new
process pays it again unless the compiled program is on disk. The cache
key includes the directory, so the directory must not move between runs:
no temporary, per-process or dated path.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <repo>/.jax_cache: this file is <repo>/src/repro/launch/compile_cache.py
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed. Otherwise the cache goes to ``<repo>/.jax_cache``.
    Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
