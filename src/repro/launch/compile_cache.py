"""Persistent XLA compile cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``launch/serve.py``) call :func:`enable_compile_cache` before their
first compile; importing the library never touches the cache.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here.  Otherwise the cache lives at one fixed path inside the
checkout (``<checkout>/.jax_cache``, gitignored): the path is part of
the cache key, so a temporary or per-run directory would never hit.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
