"""Closed-loop analytics jobs over a loaded graph, as LDBC Graphalytics
measures processing: the graph is loaded once, then one client runs one
job after another on it, each ``pagerank`` and then ``bfs`` from the
vertex of highest degree, blocked on its result.

Set-up is the load: ``preload_share`` of the stream of the mix's fixed
graph, its vertex labels permuted by the seed (``graphgen.loaded_graph``:
every seed does the same work), goes through the write path, so the
store holds flushed L0 runs and a MemGraph that is not empty; one pinned
snapshot is materialized as the CSR view
(``materialize_csr``) that every job reads, and one job outside the
window compiles every program a job runs.

The comparison checks the view's CSR, and the last job's PageRank and
BFS levels, against the reference over the loaded stream.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import graphgen, reference, storeutil
from chipbench.bench import WindowResult


class State:
    def __init__(self, ops, view, source):
        self.ops = ops
        self.view = view
        self.source = source
        self.last = None          # the last job's (pagerank, bfs)


def _job(b, st: State):
    from repro.analytics import bfs, pagerank
    t = b.traffic
    with b.span("pagerank"):
        pr = pagerank(st.view, iters=t["pagerank_iters"], d=t["damping"])
        pr.block_until_ready()
    with b.span("bfs"):
        lv = bfs(st.view, st.source)
        lv.block_until_ready()
    return pr, lv


def setup(b) -> State:
    from repro.analytics import materialize_csr
    ops = graphgen.loaded_graph(b.config["graph"], b.traffic, b.seed)
    store = storeutil.open_cell_store(b)
    for op in ops:
        storeutil.apply(store, op)
    snap = store.snapshot()
    try:
        view = materialize_csr(snap, storeutil.n_vertices(b.config))
        view.voff.block_until_ready()
        view.dst.block_until_ready()
    finally:
        snap.release()
    source = int(np.argmax(np.diff(np.asarray(view.voff))))
    b.log(f"loaded {graphgen.n_records(ops)} records; level edges "
          f"{store.level_sizes()}, MemGraph {store.n_edges_cached()}; view "
          f"{view.n_edges} edges; BFS source {source}")
    st = State(ops, view, source)
    _job(b, st)
    return st


def window(b, st: State, t0: float, deadline: float) -> WindowResult:
    jobs = 0
    while time.perf_counter() < deadline:
        st.last = None            # free the previous job's arrays first
        st.last = _job(b, st)
        jobs += 1
    end = time.perf_counter()
    b.info["pagerank"] = {"edges": st.view.n_edges,
                          "vertices": st.view.n_vertices,
                          "iters": b.traffic["pagerank_iters"]}
    b.log(f"analytics: {jobs} jobs in {end - t0!r} s")
    return WindowResult(attempted=jobs, end=end,
                        metrics={"analytics_job_s": (end - t0) / jobs},
                        samples={"jobs": jobs})


def release(b, st: State) -> None:
    pr, lv = st.last
    st.last = (np.asarray(pr), np.asarray(lv))
    st.view = (np.asarray(st.view.voff), np.asarray(st.view.dst))


def check(b, st: State):
    t = b.traffic
    voff, dst = st.view
    pr, lv = st.last
    ref = reference.Reference(st.ops, storeutil.n_vertices(b.config))
    csr_bad, d1 = reference.csr_wrong(ref, voff, dst)
    n_bfs, d2 = reference.bfs_mismatches(ref.bfs(st.source), lv)
    err, d3 = reference.max_rel_err(
        ref.pagerank(t["pagerank_iters"], t["damping"]), pr)
    checks = [
        reference.Check("view_wrong_vertices", int(csr_bad.sum()), 0, d1),
        reference.Check("bfs_wrong_levels", n_bfs, 0, d2),
        reference.Check("pagerank_max_rel_err", err,
                        t["limits"]["pagerank_max_rel_err"], d3)]
    return checks, int(not all(c.ok for c in checks))


def close(b, st: State) -> None:
    st.view = st.last = None
