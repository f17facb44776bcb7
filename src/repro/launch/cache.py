"""Persistent XLA compile cache for the command-line entry points.

Rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here.  Otherwise the cache lives at one fixed path inside
the checkout, ``<repo>/.jax_cache`` (git-ignored).  The path is part of the
cache key, so it is never temporary, per-process or time-stamped: a second
run from the same checkout finds the first run's compiled programs.

Entry points (``chip_smoke.py``, ``benchmarks/``, ``examples/``) call
:func:`enable_compile_cache` before their first compile.  Tests never do.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
