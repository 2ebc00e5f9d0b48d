"""JAX's persistent compilation cache for the repository's entry points.

A cold process compiles every program it runs; at published widths the
24-layer prefill and decode programs alone take tens of seconds. The entry
points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/*.py``) call
:func:`enable_compile_cache` first thing, so a second run in the same
checkout reads its executables back instead. Library code and tests never
call it.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache", "CACHE_DIR"]

#: The cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset: one fixed
#: directory at the repository root (the directory is part of every
#: entry's identity, so it must not move between runs).
CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads
    the variable itself and no other directory is set here. Otherwise the
    cache lives in :data:`CACHE_DIR`. Every compile is cached, however
    short — the Pallas kernels compile in well under a second each, and
    there are many of them."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
