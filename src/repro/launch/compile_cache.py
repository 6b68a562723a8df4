"""JAX's persistent compilation cache for every entry point.

A served step is several whole-model compiles (each prefill bucket, the
decode step, one megastep per K), so each process that starts cold pays
them again unless the cache persists. `JAX_COMPILATION_CACHE_DIR`, when
set, is where the cache lives — JAX reads it itself, and nothing here
overrides it. Otherwise the cache goes to one fixed directory inside the
checkout (`<repo>/.jax_cache`, git-ignored): the path is part of the
cache key, so it must not move between runs.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
