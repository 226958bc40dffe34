"""The plain reference and the comparisons that decide ``correct``.

``Reference`` (live edge set, PageRank, BFS) is a copy of
``chip_smoke.py``'s ``Reference`` and ``_slices``; the comparisons are ``chip_smoke.py``'s
``check_neighbors``/``check_csr``/``check_bfs``/``check_pagerank``,
rewritten to count every wrong answer in one vectorized pass instead of
stopping at the first.  Nothing here imports the program under test.

``pagerank_lowp``, at the end, is the precision control that the
PageRank comparison has to fail: the reference computed in bfloat16 in
the program's place (``control.py`` runs it).
"""
from __future__ import annotations

from typing import NamedTuple, Sequence

import numpy as np

BFS_INF = np.float32(3.0e38)  # the analytics kernels' "unreachable"


class Reference:
    """Plain numpy model of the store's semantics over one update stream:
    per (src, dst) the newest update wins and a delete hides the edge.
    ``ops`` is the sequence of ``(delete, src, dst)`` batches in apply
    order.  Holds the live edge set as a CSR (``voff``, ``dst``)."""

    def __init__(self, ops, n_vertices: int):
        n = n_vertices
        keys = np.concatenate([np.asarray(s, np.int64) * n + d
                               for _, s, d in ops])
        dead = np.concatenate([np.full(len(s), is_del) for is_del, s, _ in
                               ops])
        # First occurrence in the reversed stream = newest update per key.
        uniq, first = np.unique(keys[::-1], return_index=True)
        live = uniq[~dead[::-1][first]]
        self.n = n
        self.n_records = len(keys)
        self.src = live // n
        self.dst = live % n
        self.voff = np.searchsorted(self.src, np.arange(n + 1))
        self.deg = np.diff(self.voff)

    def neighbors(self, v: int) -> np.ndarray:
        return self.dst[self.voff[v]:self.voff[v + 1]]

    def pagerank(self, iters: int, d: float) -> np.ndarray:
        """analytics.pagerank's formula in float64: pull over stored
        edges, dangling mass spread uniformly."""
        n, deg = self.n, self.deg.astype(np.float64)
        x = np.full(n, 1.0 / n)
        for _ in range(iters):
            contrib = x / np.maximum(deg, 1.0)
            y = np.bincount(self.src, weights=contrib[self.dst], minlength=n)
            dangling = x[deg == 0].sum()
            x = (1.0 - d) / n + d * (y + dangling / n)
        return x

    def bfs(self, source: int) -> np.ndarray:
        """Hop levels from ``source`` (BFS_INF where unreachable)."""
        level = np.full(self.n, BFS_INF, np.float32)
        level[source] = 0.0
        frontier = np.array([source], np.int64)
        hop = 0
        while len(frontier):
            hop += 1
            nbrs = self.dst[_slices(self.voff, frontier)]
            nxt = np.unique(nbrs[level[nbrs] == BFS_INF])
            level[nxt] = hop
            frontier = nxt
        return level


def _slices(voff: np.ndarray, vs: np.ndarray) -> np.ndarray:
    """Concatenated CSR positions voff[v]:voff[v+1] for every v in vs."""
    starts, lens = voff[vs], voff[vs + 1] - voff[vs]
    total = int(lens.sum())
    if total == 0:
        return np.empty(0, np.int64)
    base = np.repeat(starts - np.cumsum(lens) + lens, lens)
    return base + np.arange(total)


# ------------------------------------------------------------ comparisons
class Check(NamedTuple):
    """One number compared: its value, its limit, and the first wrong
    answer it found (empty when none)."""

    name: str
    value: float
    limit: float
    detail: str = ""

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def adjacency_wrong(ref: Reference, vs, got: Sequence) -> tuple:
    """(mask over queries: the answer is not the vertex's live neighbor
    list in ascending dst order, description of the first such answer).
    ``got[i]`` answers ``vs[i]``."""
    vs = np.asarray(vs, np.int64)
    if len(got) != len(vs):
        return (np.ones(len(vs), bool),
                f"{len(got)} answers for {len(vs)} queries")
    glen = np.fromiter((len(g) for g in got), np.int64, len(got))
    wlen = ref.deg[vs]
    bad = glen != wlen
    same = np.nonzero(~bad)[0]
    if len(same):
        g = [got[i] for i in same]
        gcat = np.concatenate(g).astype(np.int64)
        wcat = ref.dst[_slices(ref.voff, vs[same])]
        owner = np.repeat(np.arange(len(same)), glen[same])
        wrong = np.bincount(owner, weights=gcat != wcat,
                            minlength=len(same)) > 0
        bad[same[wrong]] = True
    idx = np.nonzero(bad)[0]
    if not len(idx):
        return bad, ""
    i = int(idx[0])
    v = int(vs[i])
    return bad, (f"vertex {v}: got {len(got[i])} neighbors, want "
                 f"{int(wlen[i])} (first got "
                 f"{np.asarray(got[i])[:5].tolist()}, want "
                 f"{ref.neighbors(v)[:5].tolist()})")


def csr_wrong(ref: Reference, voff, dst) -> tuple:
    """``adjacency_wrong`` over every vertex of the CSR (voff, dst)."""
    voff = np.asarray(voff, np.int64)
    dst = np.asarray(dst, np.int64)
    if len(voff) != ref.n + 1 or voff[-1] != len(dst):
        return (np.ones(ref.n, bool),
                f"CSR has {len(voff) - 1} vertices / {len(dst)} edges, "
                f"want {ref.n} / {len(ref.dst)}")
    return adjacency_wrong(ref, np.arange(ref.n), np.split(dst, voff[1:-1]))


def bfs_mismatches(want: np.ndarray, got) -> tuple:
    got = np.asarray(got, np.float32)
    bad = np.nonzero(got != want)[0]
    if not len(bad):
        return 0, ""
    v = int(bad[0])
    return len(bad), f"vertex {v}: got {got[v]!r}, want {want[v]!r}"


def max_rel_err(want: np.ndarray, got) -> tuple:
    """Largest |got - want| / want over vertices (PageRank is positive);
    inf where ``got`` is not finite or has the wrong length."""
    got = np.asarray(got, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf"), f"shape {got.shape} or non-finite values"
    rel = np.abs(got - want) / want
    v = int(np.argmax(rel))
    return float(rel[v]), f"vertex {v}: got {got[v]!r}, want {want[v]!r}"


# --------------------------------------------------------------- controls
def pagerank_lowp(ref: Reference, iters: int, d: float, dtype) -> np.ndarray:
    """The reference's PageRank computed with JAX in ``dtype`` on JAX's
    default device: the precision control for a float32 PageRank."""
    import jax
    import jax.numpy as jnp

    n = ref.n
    src = jnp.asarray(ref.src, jnp.int32)
    dst = jnp.asarray(ref.dst, jnp.int32)
    deg = jnp.asarray(ref.deg, dtype)

    @jax.jit
    def run(src, dst, deg):
        def body(_, x):
            contrib = x / jnp.maximum(deg, jnp.asarray(1, dtype))
            y = jax.ops.segment_sum(contrib[dst], src, num_segments=n,
                                    indices_are_sorted=True)
            dangling = jnp.sum(jnp.where(deg == 0, x, 0)).astype(dtype)
            return ((1 - d) / n + d * (y + dangling / n)).astype(dtype)
        return jax.lax.fori_loop(0, iters, body,
                                 jnp.full((n,), 1.0 / n, dtype))

    return np.asarray(run(src, dst, deg).astype(jnp.float32), np.float64)
