"""JAX's persistent compilation cache, set up once per entry point.

Every entry point (``chip_smoke.py``, ``python -m repro.launch.allocd``,
``python -m benchmarks.run`` and the ``benchmarks.bench_*`` mains) calls
``enable_compile_cache()`` before it compiles anything, so a second run of
the same program on the same machine reads its executables back instead of
compiling them again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the cache lives at ``<checkout>/.jax_cache``:
a fixed path, because the directory is part of what a later run must find.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
