"""Persistent compilation cache for the repo's entry points.

``chip_smoke.py``, the ``benchmarks/`` mains and the ``examples/`` scripts
call :func:`use_compile_cache` once at start-up, so a second run on the
same machine finds its compiled programs again. The library itself sets no
cache when it is imported.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
other directory is set. Otherwise the cache is one fixed directory inside
the checkout, ``<repo>/.jax_cache/`` (git-ignored): a fixed path, never one
built from a temp name, a pid or the time, because the path is part of what
makes a cached entry found again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the fallback cache directory: ``.jax_cache`` at the checkout root
#: (this file is ``src/repro/compile_cache.py``)
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
