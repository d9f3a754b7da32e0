"""JAX's persistent compilation cache, placed from outside the code.

A cold start compiles every serving program, which at published widths
takes minutes. ``use_compile_cache()`` keeps compiled programs on disk so
that a later process finds them:

* when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
  nothing is set here;
* otherwise the cache lives at ``<repo>/.jax_cache``, a fixed path (never
  derived from a temporary name, a pid or the time, so every run from
  this checkout looks in the same place).

Call it at the top of an entry point, before the first compile.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
