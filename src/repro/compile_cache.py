"""One persistent XLA compile cache for every entry point.

Entry points (``chip_smoke.py``, ``benchmarks/cluster_bench.py`` and the
examples) call :func:`enable_compile_cache` once, before their first
compile.  When ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it on its own
and nothing is configured here.  Otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``): the directory is part
of what a cached entry is found by, so it never depends on a temporary
name, a process id or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
