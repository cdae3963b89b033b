"""JAX's persistent compilation cache for the entry points.

Each entry point (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve``, ``benchmarks/run.py``) calls
:func:`enable_compile_cache` before its first compile, so processes that
share a checkout share compiled programs.

* Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
  helper sets no other directory.
* Otherwise the cache lives in ``<checkout>/.jax_cache`` (git-ignored).  The
  path is fixed: it is part of the cache key, so a directory built from a
  temp name, a PID or the time would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory JAX will use."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
