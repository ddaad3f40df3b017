"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points (``chip_smoke.py``, the benchmarks, the examples) call
:func:`enable_compile_cache` before their first compile.  Importing
:mod:`repro` never does, so the tests compile without a cache.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; this module
  sets nothing else.
- Otherwise: ``<checkout>/.jax_cache``.  The path is part of the cache
  key, so it is derived from this file and never from a temporary name,
  a pid or the time (``.gitignore`` lists it).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
