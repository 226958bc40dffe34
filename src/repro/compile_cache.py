"""JAX's persistent compilation cache, for the entry points, and the
count of programs this process built.

Entry points (``graph_service``, ``chip_smoke.py``, the benchmark runners)
call ``enable_compile_cache()`` before their first compile; importing the
package sets nothing.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and nothing else is set.  Otherwise the cache lives in a
fixed directory of the checkout, so a later run of the same code finds
its entries (the path is part of a cache key's context).

``count_programs_built()`` (called when the store is imported) counts in
``runtime_programs_built_total`` every program this process compiled or
loaded from the persistent cache.  JAX raises its backend-compile event
once for each, whether the program was compiled or loaded, so the count
does not depend on how warm the cache was.
"""
from __future__ import annotations

import os

import jax

from . import obs

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


# JAX's event for each program built, compiled or loaded from the cache.
BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
_programs_built = None


def count_programs_built() -> None:
    """Count every program built from now on in
    ``runtime_programs_built_total``; once per process, later calls do
    nothing.  The counter exists (at 0) from the first call."""
    global _programs_built
    if _programs_built is not None:
        return
    _programs_built = obs.counter("runtime_programs_built_total")

    def on_duration(event, duration, **kw):
        if event == BUILD_EVENT:
            _programs_built.inc()

    jax.monitoring.register_event_duration_secs_listener(on_duration)
