"""Segment reduction over sorted CSR edges (jnp; XLA on every backend).

The analytics hot loop (DESIGN.md §5): per edge e (sorted by source),
   message = wt[e] * x[dst[e]]       (one XLA gather)
   y[seg_id[e]] += message           (one sorted XLA segment reduction)

There is no Pallas kernel here.  Mosaic has no 1-D dynamic gather, so
``x[dst]`` has to run in XLA anyway, and a tiled kernel that reduces the
gathered messages only per tile still leaves an edge-sized sorted segment
reduction to XLA.  XLA's ``segment_sum``/``segment_min`` with
``indices_are_sorted=True`` is the one implementation; ``ref.py`` keeps an
independent scatter form for the tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_INF = 3.0e38  # "unreachable" in BFS / SSSP / CC


@functools.partial(jax.jit, static_argnames=("n_out",))
def gather_segsum(dst: jnp.ndarray, seg_id: jnp.ndarray, wt: jnp.ndarray,
                  x: jnp.ndarray, *, n_out: int) -> jnp.ndarray:
    """y[s] = Σ_{e: seg_id[e]==s} wt[e] * x[dst[e]].

    seg_id must be non-decreasing (CSR order); ids >= n_out are dropped.
    """
    vals = wt.astype(jnp.float32) * x.astype(jnp.float32)[dst]
    return jax.ops.segment_sum(vals, seg_id, num_segments=n_out,
                               indices_are_sorted=True)


@functools.partial(jax.jit, static_argnames=("n_out",))
def gather_segmin(dst: jnp.ndarray, seg_id: jnp.ndarray, wt: jnp.ndarray,
                  x: jnp.ndarray, *, n_out: int) -> jnp.ndarray:
    """y[s] = min_{e: seg_id[e]==s} (wt[e] + x[dst[e]]); absent -> 3e38.

    The relaxation primitive of BFS / SSSP / CC.
    """
    vals = wt.astype(jnp.float32) + x.astype(jnp.float32)[dst]
    y = jax.ops.segment_min(vals, seg_id, num_segments=n_out,
                            indices_are_sorted=True)
    return jnp.minimum(y, _INF)
