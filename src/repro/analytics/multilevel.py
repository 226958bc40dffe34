"""Merge-free analytics directly over the multi-level CSR (beyond-paper).

Linear aggregations (PageRank messages, degree, weighted scans) distribute
over the level structure: every visible record contributes ±f(edge), with
tombstones entering negatively, so

    Σ_runs Σ_records ±f  ==  Σ_live-edges f

— no per-vertex merge, no global sort.  Each run is already CSR-sorted, so
each term is one gather-segsum sweep.  Exactness requires alternating
insert/delete histories per key (asserted in property tests; the compaction
GC maintains it for the steady state).

Min-style algorithms (BFS/SSSP/CC) are NOT linear; they use the exact
materialized view instead (analytics/view.py).
"""
from __future__ import annotations

from typing import List

import jax.numpy as jnp

from ..kernels import ops
from .view import RunView


def multilevel_spmv(views: List[RunView], x: jnp.ndarray, *,
                    n_out: int) -> jnp.ndarray:
    """y[u] = Σ_{live (u,v)} x[v], computed run-by-run with ± weights."""
    y = jnp.zeros((n_out,), jnp.float32)
    for rv in views:
        y = y + ops.gather_segsum(rv.dst, rv.src, rv.wt, x, n_out=n_out)
    return y


def multilevel_degree(views: List[RunView], *, n_out: int) -> jnp.ndarray:
    ones = jnp.ones((n_out,), jnp.float32)
    return multilevel_spmv(views, ones, n_out=n_out)


def multilevel_pagerank(views: List[RunView], *, n_out: int, iters: int = 20,
                        d: float = 0.85) -> jnp.ndarray:
    """PageRank without ever materializing a merged CSR."""
    deg = multilevel_degree(views, n_out=n_out)
    x = jnp.full((n_out,), 1.0 / n_out, jnp.float32)
    for _ in range(iters):
        contrib = x / jnp.maximum(deg, 1.0)
        y = multilevel_spmv(views, contrib, n_out=n_out)
        dangling = jnp.sum(jnp.where(deg == 0, x, 0.0))
        x = (1.0 - d) / n_out + d * (y + dangling / n_out)
    return x
