"""Graph algorithms over a CSRView (paper §5.3: SSSP, BFS, CC, SCAN + PR).

All algorithms are whole-graph vectorized sweeps with the gather-segsum /
gather-segmin segment reductions as the inner loop, wrapped in
lax.while_loop with convergence tests — pure JAX end to end.

Direction convention: the stored edge u->v is traversed from u (pull over the
stored direction).  Benchmarks ingest graphs undirected (both directions),
matching the paper's treatment of the analytics workloads.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from ..kernels import ops
from .view import CSRView, source_ids

_INF = jnp.float32(3.0e38)


def _edge_wt_zero(view: CSRView) -> jnp.ndarray:
    return jnp.zeros_like(view.prop)


@functools.partial(jax.jit, static_argnames=("n", "iters"))
def _pagerank_impl(voff, dst, *, n: int, iters: int, d: float):
    seg = source_ids(voff, dst.shape[0], n)
    deg = (voff[1:] - voff[:-1]).astype(jnp.float32)
    wt = jnp.ones_like(dst, jnp.float32)

    def body(_, x):
        contrib = x / jnp.maximum(deg, 1.0)
        y = ops.gather_segsum(dst, seg, wt, contrib, n_out=n)
        # Dangling mass is redistributed uniformly.
        dangling = jnp.sum(jnp.where(deg == 0, x, 0.0))
        return (1.0 - d) / n + d * (y + dangling / n)

    x0 = jnp.full((n,), 1.0 / n, jnp.float32)
    return jax.lax.fori_loop(0, iters, body, x0)


def pagerank(view: CSRView, iters: int = 20, d: float = 0.85) -> jnp.ndarray:
    return _pagerank_impl(view.voff, view.dst, n=view.n_vertices,
                          iters=iters, d=d)


@functools.partial(jax.jit, static_argnames=("n",))
def _bfs_impl(voff, dst, src_v, *, n: int):
    seg = source_ids(voff, dst.shape[0], n)
    dist0 = jnp.full((n,), _INF).at[src_v].set(0.0)
    zero_w = jnp.zeros_like(dst, jnp.float32)

    def cond(state):
        dist, changed, it = state
        return changed & (it < n)

    def body(state):
        dist, _, it = state
        relax = ops.gather_segmin(dst, seg, zero_w + 1.0, dist, n_out=n)
        new = jnp.minimum(dist, relax)
        return new, jnp.any(new < dist), it + 1

    dist, _, _ = jax.lax.while_loop(cond, body, (dist0, jnp.bool_(True),
                                                 jnp.int32(0)))
    return dist


def bfs(view: CSRView, source: int) -> jnp.ndarray:
    """Hop distances from source (INF = unreachable)."""
    return _bfs_impl(view.voff, view.dst, jnp.asarray(source, jnp.int32),
                     n=view.n_vertices)


@functools.partial(jax.jit, static_argnames=("n",))
def _sssp_impl(voff, dst, wts, src_v, *, n: int):
    seg = source_ids(voff, dst.shape[0], n)
    dist0 = jnp.full((n,), _INF).at[src_v].set(0.0)

    def cond(state):
        dist, changed, it = state
        return changed & (it < n)

    def body(state):
        dist, _, it = state
        relax = ops.gather_segmin(dst, seg, wts, dist, n_out=n)
        new = jnp.minimum(dist, relax)
        return new, jnp.any(new < dist), it + 1

    dist, _, _ = jax.lax.while_loop(cond, body, (dist0, jnp.bool_(True),
                                                 jnp.int32(0)))
    return dist


def sssp(view: CSRView, source: int) -> jnp.ndarray:
    """Bellman-Ford shortest paths using edge properties as weights.

    Note the relaxation direction: dist[u] <- min over u's stored edges
    (u, v) of w + dist[v], i.e. paths follow stored edges from u; for the
    usual source-rooted semantics ingest graphs undirected (benchmarks do).
    """
    return _sssp_impl(view.voff, view.dst, jnp.maximum(view.prop, 0.0),
                      jnp.asarray(source, jnp.int32), n=view.n_vertices)


@functools.partial(jax.jit, static_argnames=("n",))
def _cc_impl(voff, dst, *, n: int):
    seg = source_ids(voff, dst.shape[0], n)
    label0 = jnp.arange(n, dtype=jnp.float32)
    zero_w = jnp.zeros_like(dst, jnp.float32)

    def cond(state):
        lab, changed, it = state
        return changed & (it < n)

    def body(state):
        lab, _, it = state
        nbr_min = ops.gather_segmin(dst, seg, zero_w, lab, n_out=n)
        new = jnp.minimum(lab, nbr_min)
        return new, jnp.any(new < lab), it + 1

    lab, _, _ = jax.lax.while_loop(cond, body, (label0, jnp.bool_(True),
                                                jnp.int32(0)))
    return lab.astype(jnp.int32)


def cc(view: CSRView) -> jnp.ndarray:
    """Connected components by min-label propagation (undirected ingestion)."""
    return _cc_impl(view.voff, view.dst, n=view.n_vertices)


@functools.partial(jax.jit, static_argnames=("n",))
def _scan_impl(voff, dst, prop, *, n: int):
    seg = source_ids(voff, dst.shape[0], n)
    ones = jnp.ones((n,), jnp.float32)
    deg = ops.gather_segsum(dst, seg, jnp.ones_like(prop), ones, n_out=n)
    wsum = ops.gather_segsum(dst, seg, prop, ones, n_out=n)
    return deg, wsum


def scan_stats(view: CSRView) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SCAN: traverse every vertex's one-hop neighbours (paper's SCAN is the
    substrate of PR/PHP/GNN); returns (degree, Σ edge property) per vertex."""
    return _scan_impl(view.voff, view.dst, view.prop, n=view.n_vertices)
