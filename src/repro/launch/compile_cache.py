"""JAX's persistent compilation cache, kept at one fixed place.

Entry points that compile (``chip_smoke.py``, ``launch/serve.py``,
``launch/calibrate.py``, ``launch/tune.py``) call
:func:`enable_compile_cache` before their first compile, so a second run
loads executables instead of compiling them again.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

#: ``<checkout>/.jax_cache``.  Fixed, never temporary or per-process:
#: the directory is part of every cache entry's key, so a cache that
#: moves never hits.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache lives at :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
