"""Persistent XLA compilation cache for the entry points.

A chip run otherwise compiles every program cold.  The entry points
(``chip_smoke.py``, :mod:`repro.launch.train`, :mod:`repro.launch.serve`)
call :func:`enable_compile_cache` first thing in ``main``; importing this
module sets nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache: a fixed path, so every run of a checkout finds the
# programs the previous one compiled (listed in .gitignore)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to ``REPO_CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
