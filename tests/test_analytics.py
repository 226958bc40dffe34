"""Analytics over snapshots: PR/BFS/SSSP/CC/SCAN vs python references."""
import collections
import heapq

import numpy as np
import pytest

from repro.analytics import (bfs, cc, materialize_csr, multilevel_pagerank,
                             multilevel_views, pagerank, scan_stats, sssp)
from repro.core import LSMGraph
from repro.data.graphgen import powerlaw_edges
from conftest import small_store_cfg


V = 300


@pytest.fixture(scope="module")
def graph():
    rng = np.random.default_rng(3)
    g = LSMGraph(small_store_cfg(vmax=V))
    u, w = powerlaw_edges(V, 2500, seed=3)
    # canonicalize undirected pairs (no self-loops, no (a,b)+(b,a) dups —
    # the multilevel ± fast path requires alternating per-key histories)
    keep = u < w
    u, w = u[keep], w[keep]
    key = u.astype(np.int64) * V + w
    _, first = np.unique(key, return_index=True)
    u, w = u[np.sort(first)], w[np.sort(first)]
    wt = rng.uniform(0.1, 1.0, len(u)).astype(np.float32)
    g.insert_edges(np.r_[u, w], np.r_[w, u], prop=np.r_[wt, wt])
    # alternating deletes (multilevel ± precondition)
    k = 300
    g.delete_edges(np.r_[u[:k], w[:k]], np.r_[w[:k], u[:k]])
    live = {}
    for i in range(len(u)):
        a, b_, c = int(u[i]), int(w[i]), float(wt[i])
        live[(a, b_)] = c
        live[(b_, a)] = c
    for i in range(k):
        live.pop((int(u[i]), int(w[i])), None)
        live.pop((int(w[i]), int(u[i])), None)
    snap = g.snapshot()
    view = materialize_csr(snap, V)
    adj = collections.defaultdict(list)
    for (a, b_), c in live.items():
        adj[a].append((b_, c))
    yield g, snap, view, live, adj
    snap.release()


def test_materialize_exact(graph):
    _, _, view, live, _ = graph
    assert view.n_edges == len(live)


def test_pagerank_stochastic(graph):
    _, _, view, _, _ = graph
    pr = np.asarray(pagerank(view, iters=30))
    assert abs(pr.sum() - 1.0) < 1e-3
    assert (pr >= 0).all()


def test_pagerank_multilevel_matches_merged(graph):
    _, snap, view, _, _ = graph
    pr1 = np.asarray(pagerank(view, iters=10))
    pr2 = np.asarray(multilevel_pagerank(multilevel_views(snap),
                                         n_out=V, iters=10))
    assert np.abs(pr1 - pr2).max() < 1e-5


def test_bfs_vs_reference(graph):
    _, _, view, _, adj = graph
    src = next(iter(adj))
    dist = np.asarray(bfs(view, src))
    ref = {src: 0}
    dq = collections.deque([src])
    while dq:
        x = dq.popleft()
        for y, _ in adj[x]:
            if y not in ref:
                ref[y] = ref[x] + 1
                dq.append(y)
    for v, d in ref.items():
        assert int(dist[v]) == d
    for v in range(V):
        if v not in ref:
            assert dist[v] > 1e30


def test_sssp_vs_dijkstra(graph):
    _, _, view, _, adj = graph
    src = next(iter(adj))
    d_jax = np.asarray(sssp(view, src))
    ref = {src: 0.0}
    pq = [(0.0, src)]
    while pq:
        dx, x = heapq.heappop(pq)
        if dx > ref.get(x, 9e18) + 1e-12:
            continue
        for y, c in adj[x]:
            nd = dx + c
            if nd < ref.get(y, 9e18) - 1e-9:
                ref[y] = nd
                heapq.heappush(pq, (nd, y))
    for v, dv in ref.items():
        assert abs(float(d_jax[v]) - dv) < 1e-3, v


def test_cc_matches_bfs_partition(graph):
    _, _, view, _, adj = graph
    labels = np.asarray(cc(view))
    # two vertices in the same component must share a label
    src = next(iter(adj))
    comp = set()
    dq = collections.deque([src])
    seen = {src}
    while dq:
        x = dq.popleft()
        comp.add(x)
        for y, _ in adj[x]:
            if y not in seen:
                seen.add(y)
                dq.append(y)
    assert len({int(labels[v]) for v in comp}) == 1


def test_scan_stats(graph):
    _, _, view, live, _ = graph
    deg, wsum = scan_stats(view)
    assert int(np.asarray(deg).sum()) == len(live)
    total_w = sum(live.values())
    assert abs(float(np.asarray(wsum).sum()) - total_w) / total_w < 1e-3


def test_analytics_use_pallas_consistent(graph):
    """PageRank's segment-sum inner loop agrees with the same iteration
    over the scatter oracle of kernels/ref.py."""
    import jax.numpy as jnp
    from repro.kernels import ref
    _, _, view, _, _ = graph
    pr_k = np.asarray(pagerank(view, iters=5))
    deg = jnp.diff(view.voff).astype(jnp.float32)
    wt = jnp.ones(view.dst.shape, jnp.float32)
    x = jnp.full((V,), 1.0 / V, jnp.float32)
    for _ in range(5):
        y = ref.gather_segsum_ref(view.dst, view.seg_ids(), wt,
                                  x / jnp.maximum(deg, 1.0), V)
        dangling = jnp.sum(jnp.where(deg == 0, x, 0.0))
        x = 0.15 / V + 0.85 * (y + dangling / V)
    assert np.abs(pr_k - np.asarray(x)).max() < 1e-4
