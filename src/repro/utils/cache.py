"""Persistent XLA compilation cache.

Repeated figure runs recompile the same executables from scratch on every
process start; pointing jax at an on-disk cache makes the second and later
runs skip compilation entirely.  Enabled from ``benchmarks/common.py``,
every ``repro.launch`` entry point and ``chip_smoke.py``; the scan
engine's bucketing policy (DESIGN.md §8) keeps the cached executable set
small.

Where the cache lives is decided outside the program when
``JAX_COMPILATION_CACHE_DIR`` is set: jax reads that variable itself and
this module sets no directory.  Otherwise the cache sits at one fixed
absolute path inside the checkout, derived from this file's location —
never from the working directory — since the directory is part of the
cache key and a path that moves never hits.
"""
from __future__ import annotations

import os

import jax

DEFAULT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "experiments", ".jax_cache"))


def enable_compilation_cache() -> str:
    """Turn jax's persistent compilation cache on; returns its directory.

    The thresholds are dropped to zero so even the small CPU-scale
    executables are cached.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
