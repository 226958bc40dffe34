"""JAX's persistent compilation cache, for the entry points.

Entry points (``graph_service``, ``chip_smoke.py``, the benchmark runners)
call ``enable_compile_cache()`` before their first compile; importing the
package sets nothing.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and nothing else is set.  Otherwise the cache lives in a
fixed directory of the checkout, so a later run of the same code finds
its entries (the path is part of a cache key's context).
"""
from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
