"""JAX's persistent compilation cache for the command-line entry points.

Called by the CLIs (``launch/serve_bcpnn.py``, ``launch/train_dp.py``)
and ``chip_smoke.py`` before their first compile, never at import: tests
and library callers keep JAX's own default.  Processes that share the
directory reuse each other's compiled programs; the path is part of the
cache key, so it is fixed, never temporary.
"""
from __future__ import annotations

import os
import pathlib

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

# src/repro/launch/compile_cache.py -> the checkout root, three levels up
DEFAULT_DIR = str(
    pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Point the cache at its directory and return it:
    ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself, so
    nothing is set here), else ``.jax_cache`` at the checkout root."""
    import jax

    if os.environ.get(ENV_DIR):
        return os.environ[ENV_DIR]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
