"""Where the entry points keep JAX's persistent compilation cache.

A process that compiles a program JAX has compiled before, with the same
inputs, reads it back from this cache instead of compiling again.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it.  Otherwise the entry points (``chip_smoke.py``,
``examples/*.py``, ``benchmarks/*``) call :func:`use_compile_cache` before
their first compile, which points the cache at ``<repo>/.jax_cache``: a
fixed path, because a directory that moves is never found again.  Importing
``repro`` sets no cache, so tests write none.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Enable the persistent cache for this process; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
