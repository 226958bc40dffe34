#!/usr/bin/env python
"""Chip smoke: the LSMGraph store's main path once, on a TPU, at a real size.

    python chip_smoke.py              # one chip: durable store, reads, analytics
    python chip_smoke.py --chips 4    # four range shards, shard i on chip i
    python chip_smoke.py --scale 19   # a cut: R-MAT scale 19 (never below 18)

One process drives every phase through the store's own entry points
(``open_store`` + ``ConcurrentLSMGraph``, the phases of
``launch/graph_service.py``):

  generate  Graph500 R-MAT graph (a, b, c = 0.57, 0.19, 0.19; edge
            factor 16; scale 20 cut to 18 by default, see SCALE) from
            ``--seed``, and the paper's 20:1
            insert/delete stream over it, ingested undirected;
  ingest    into a durable store (WAL group commit, ``wal_sync="batch"``)
            under ``chip_smoke_data/`` in the checkout;
  reads     a pinned snapshot answers ``neighbors_batch`` point reads, a
            quarter of them for vertices with no edges (the presence
            filters' case), and a 2-hop expansion from a few vertices;
  analytics 10 PageRank iterations and a BFS over the snapshot's CSR view;
  reopen    close, recover from disk, compare the recovered edge set with
            the pre-close snapshot.

Every answer is checked against a plain numpy model built from the same
update stream (newest update per (src, dst) wins, a delete hides the
edge): point reads, 2-hop and BFS levels exactly, PageRank within 1e-4
(max abs).  Phase seconds are printed as smoke timings, not metrics.

The script exits non-zero without a result line when JAX finds no TPU,
when a check fails or a phase raises.  Its last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
DATA_DIR = os.path.join(ROOT, "chip_smoke_data")

FULL_SCALE = 20       # Graph500 R-MAT scale: 2^20 vertices
MIN_SCALE = 18        # a cut for time never goes below this
# The scale run by default, cut one step at a time from FULL_SCALE: on one
# v5e chip a scale-20 run had not finished its ingest after 1,154 s, and a
# scale-19 run after 866 s; scale 18 passes in 323 s with a cold compile
# cache.
SCALE = 18
EDGE_FACTOR = 16      # Graph500: edges = 16 x vertices
QUERIES = 4096        # point reads in the pinned-snapshot batch
ABSENT_SHARE = 0.25   # share of point reads for vertices with no edges
TWO_HOP_SEEDS = 4
PR_ITERS = 10
PR_DAMPING = 0.85
PR_MAX_ABS = 1e-4     # PageRank tolerance (max abs) against float64 numpy
BFS_INF = np.float32(3.0e38)  # the analytics kernels' "unreachable"


def _import_repro():
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"chip_smoke: no repro package under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


# ----------------------------------------------------------------- reference
class Reference:
    """Plain numpy model of the store's semantics over one update stream:
    per (src, dst) the newest update wins and a delete hides the edge.
    ``ops`` is the sequence of ``(delete, src, dst)`` batches in apply
    order.  Holds the live edge set as a CSR (``voff``, ``dst``)."""

    def __init__(self, ops, n_vertices: int):
        n = n_vertices
        keys = np.concatenate([s.astype(np.int64) * n + d
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

    def pagerank(self, iters: int, d: float = PR_DAMPING) -> np.ndarray:
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


def make_stream(scale: int, seed: int):
    """The R-MAT graph and the store's undirected 20:1 update stream over
    it, as ``(delete, src, dst)`` batches in apply order."""
    from repro.data.graphgen import rmat_edges, update_stream
    src, dst = rmat_edges(scale, EDGE_FACTOR << scale, seed=seed)
    ops = [(op == "delete", np.r_[s, d], np.r_[d, s])
           for op, s, d in update_stream(src, dst)]
    return src, dst, ops


def pick_queries(ref: Reference, n_queries: int, seed: int) -> np.ndarray:
    """Point-read vertices: present ones, plus ABSENT_SHARE with no edges."""
    rng = np.random.default_rng(seed + 1)
    present = np.nonzero(ref.deg > 0)[0]
    absent = np.nonzero(ref.deg == 0)[0]
    n_abs = min(len(absent), int(n_queries * ABSENT_SHARE))
    qs = np.concatenate([rng.choice(present, n_queries - n_abs),
                         rng.choice(absent, n_abs, replace=False)])
    return rng.permutation(qs).astype(np.int64)


# -------------------------------------------------------------------- checks
def check_neighbors(ref: Reference, vs, got) -> str:
    """'' when every ``got[i]`` equals vertex ``vs[i]``'s live neighbor
    list (ascending dst), else the first mismatch."""
    if len(got) != len(vs):
        return f"{len(got)} answers for {len(vs)} queries"
    for v, g in zip(np.asarray(vs).tolist(), got):
        want = ref.neighbors(v)
        if len(g) != len(want) or not np.array_equal(
                np.asarray(g, np.int64), want):
            return (f"vertex {v}: got {len(g)} neighbors, want {len(want)}"
                    f" (first got {np.asarray(g)[:5].tolist()}, want "
                    f"{want[:5].tolist()})")
    return ""


def check_pagerank(want: np.ndarray, got: np.ndarray) -> str:
    err = float(np.max(np.abs(np.asarray(got, np.float64) - want)))
    if not np.all(np.isfinite(got)) or err > PR_MAX_ABS:
        return f"max abs error {err!r} > {PR_MAX_ABS}"
    return ""


def check_bfs(want: np.ndarray, got: np.ndarray) -> str:
    got = np.asarray(got, np.float32)
    bad = np.nonzero(got != want)[0]
    if len(bad):
        v = int(bad[0])
        return (f"{len(bad)} vertices differ; vertex {v}: got {got[v]!r}, "
                f"want {want[v]!r}")
    return ""


def check_csr(ref_voff, ref_dst, voff, dst) -> str:
    voff, dst = np.asarray(voff, np.int64), np.asarray(dst, np.int64)
    if len(dst) != len(ref_dst):
        return f"{len(dst)} edges, want {len(ref_dst)}"
    if not np.array_equal(voff, ref_voff):
        v = int(np.nonzero(voff != ref_voff)[0][0])
        return f"offsets differ first at vertex {v}"
    if not np.array_equal(dst, ref_dst):
        return f"dst differs first at edge {int(np.nonzero(dst != ref_dst)[0][0])}"
    return ""


# -------------------------------------------------------------------- phases
class Smoke:
    """Phase timer, check ledger and printer of one smoke run."""

    def __init__(self, log=print):
        self.log = log
        self.failures = []

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        yield
        self.log(f"phase {name}: {time.perf_counter() - t0:.3f} s (smoke "
                 "timing, not a metric)")

    def progress(self, every_s: float = 30.0):
        """A callback for ``_ingest_stream``: logs the records sent and
        their rate every ``every_s`` seconds of a long ingest."""
        t0 = last = time.perf_counter()

        def report(n: int) -> None:
            nonlocal last
            now = time.perf_counter()
            if now - last >= every_s:
                last = now
                self.log(f"ingest: {n} records sent in {now - t0:.1f} s "
                         f"({n / (now - t0):.0f}/s, smoke timing)")
        return report

    def check(self, name: str, err: str) -> None:
        self.log(f"check {name}: {'FAIL ' + err if err else 'PASS'}")
        if err:
            self.failures.append(name)

    def store_seconds(self, where: str) -> None:
        """Count and total of every ``*_seconds`` histogram the store's
        spans have filled (apply, flush, compaction, WAL, resolve):
        where a phase's wall time went, as smoke timings."""
        from repro import obs
        totals = {}
        for m in obs.REGISTRY.collect():
            if getattr(m, "kind", "") == "histogram" and \
                    m.name.endswith("_seconds"):
                snap = m.snapshot()
                if snap["count"]:
                    c, s = totals.get(m.name, (0, 0.0))
                    totals[m.name] = (c + snap["count"], s + snap["sum"])
        self.log(f"store seconds {where} (smoke timing): " + ", ".join(
            f"{k}={s:.3f}/{c}" for k, (c, s) in sorted(totals.items())))

    def memory(self, where: str, devices=None) -> None:
        import jax
        for dev in devices or jax.devices()[:1]:
            stats = dev.memory_stats()
            if not stats:
                self.log(f"device memory {where} ({dev}): not reported by "
                         "this backend")
                continue
            self.log(f"device memory {where} ({dev}): bytes_in_use="
                     f"{stats.get('bytes_in_use')} peak_bytes_in_use="
                     f"{stats.get('peak_bytes_in_use')}")


def _prepare(smoke: Smoke, scale: int, seed: int, n_queries: int):
    with smoke.phase("generate"):
        src, dst, ops = make_stream(scale, seed)
        ref = Reference(ops, 1 << scale)
        qs = pick_queries(ref, n_queries, seed)
    smoke.log(f"sizes: vertices={1 << scale} rmat_edges={len(src)} "
              f"stream_records={ref.n_records} live_edges={len(ref.dst)} "
              f"queries={len(qs)} absent_queries="
              f"{int(np.sum(ref.deg[qs] == 0))}")
    return src, dst, ref, qs


def _reads(smoke: Smoke, snap, ref: Reference, qs, seed: int) -> None:
    with smoke.phase("point_reads"):
        got = snap.neighbors_batch(qs)
    smoke.check("point_reads", check_neighbors(ref, qs, got))
    rng = np.random.default_rng(seed + 2)
    seeds = rng.choice(np.nonzero(ref.deg > 0)[0], TWO_HOP_SEEDS,
                       replace=False).astype(np.int64)
    with smoke.phase("two_hop"):
        hop1 = snap.neighbors_batch(seeds)
        frontier = np.unique(np.concatenate(hop1)).astype(np.int64)
        hop2 = snap.neighbors_batch(frontier)
    smoke.log(f"two_hop: seeds={seeds.tolist()} frontier={len(frontier)} "
              f"reach={sum(len(h) for h in hop2)}")
    smoke.check("two_hop", check_neighbors(ref, seeds, hop1)
                or check_neighbors(ref, frontier, hop2))


def run_single(scale: int = FULL_SCALE, seed: int = 0, *,
               workdir: str = DATA_DIR,
               mem_bytes=None, n_queries: int = QUERIES, log=print) -> list:
    """The one-chip phases on JAX's default device.  Returns the names of
    the failed checks (empty = all passed)."""
    from repro.analytics import bfs, materialize_csr, pagerank
    from repro.core import StoreConfig
    from repro.core.concurrent import ConcurrentLSMGraph
    from repro.core.types import MEMGRAPH_BYTES
    from repro.launch.graph_service import _ingest_stream
    from repro.storage import open_store

    smoke = Smoke(log)
    n = 1 << scale
    src, dst, ref, qs = _prepare(smoke, scale, seed, n_queries)
    cfg = StoreConfig.for_graph(n, mem_bytes or MEMGRAPH_BYTES)
    shutil.rmtree(workdir, ignore_errors=True)
    g = ConcurrentLSMGraph(store=open_store(workdir, cfg, wal_sync="batch"))
    store2 = None
    try:
        with smoke.phase("ingest"):
            n_ops, _, _ = _ingest_stream(g, src, dst, g.flush,
                                         progress=smoke.progress())
        smoke.log(f"ingested {n_ops} records; level edges="
                  f"{g.store.level_sizes()} mem_edges={cfg.mem_edges}")
        smoke.store_seconds("after ingest")
        smoke.memory("after ingest")
        snap = g.snapshot()
        _reads(smoke, snap, ref, qs, seed)
        with smoke.phase("analytics_view"):
            view = materialize_csr(snap, n)
            pre_voff, pre_dst = np.asarray(view.voff), np.asarray(view.dst)
        smoke.check("snapshot_edges", check_csr(ref.voff, ref.dst,
                                                pre_voff, pre_dst))
        with smoke.phase("pagerank"):
            pr = np.asarray(pagerank(view, iters=PR_ITERS, d=PR_DAMPING))
        smoke.check("pagerank", check_pagerank(ref.pagerank(PR_ITERS), pr))
        source = int(np.argmax(ref.deg))
        with smoke.phase("bfs"):
            lv = np.asarray(bfs(view, source))
        smoke.log(f"bfs: source={source} reached="
                  f"{int(np.sum(lv < BFS_INF))} depth={int(lv[lv < BFS_INF].max())}")
        smoke.check("bfs", check_bfs(ref.bfs(source), lv))
        smoke.memory("after analytics")
        del view
        snap.release()
        with smoke.phase("close"):
            g.close()
        g = None
        with smoke.phase("reopen"):
            store2 = open_store(workdir)
        with store2.snapshot() as snap2:
            with smoke.phase("reopen_view"):
                view2 = materialize_csr(snap2, n)
            smoke.check("reopen_edges", check_csr(
                pre_voff, pre_dst, view2.voff, view2.dst))
            with smoke.phase("reopen_point_reads"):
                got = snap2.neighbors_batch(qs)
            smoke.check("reopen_point_reads", check_neighbors(ref, qs, got))
    finally:
        if g is not None:
            g.close()
        if store2 is not None:
            store2.close()
    shutil.rmtree(workdir, ignore_errors=True)
    return smoke.failures


def _device_sets(tree) -> list:
    import jax
    return [a.devices() for a in jax.tree_util.tree_leaves(tree)
            if isinstance(a, jax.Array)]


def run_sharded(devices, scale: int = FULL_SCALE, seed: int = 0, *,
                mem_bytes=None, n_queries: int = QUERIES, log=print) -> list:
    """The sharded path: one range shard per device in ``devices``, shard
    i's device state on ``devices[i]``, every shard driven from this
    process.  Same graph, reads and reference as ``run_single``."""
    from repro.core import StoreConfig
    from repro.core.types import MEMGRAPH_BYTES
    from repro.launch.graph_service import _ingest_stream
    from repro.shard import CompactionScheduler, ShardedGraphStore

    smoke = Smoke(log)
    n = 1 << scale
    src, dst, ref, qs = _prepare(smoke, scale, seed, n_queries)
    cfg = StoreConfig.for_graph(n, mem_bytes or MEMGRAPH_BYTES)
    g = ShardedGraphStore(cfg, len(devices), devices=devices)
    try:
        sched = CompactionScheduler(g).start()
        with smoke.phase("ingest"):
            try:
                n_ops, _, _ = _ingest_stream(g, src, dst, flush=g.flush_all,
                                             progress=smoke.progress())
            finally:
                sched.stop()
        smoke.log(f"ingested {n_ops} records; per-shard level edges="
                  f"{g.level_sizes()}")
        smoke.store_seconds("after ingest")
        smoke.memory("after ingest", devices)
        with g.snapshot() as snap:
            _reads(smoke, snap, ref, qs, seed)
            misplaced = []
            for s, (shard_snap, dev) in enumerate(zip(snap.snaps, devices)):
                state = shard_snap.state
                runs = [rf.arrays for lvl in state.levels for rf in lvl]
                bb = shard_snap._get_backbone()
                spine = (bb.src, bb.dst, bb.ts, bb.rid, bb.marker, bb.prop,
                         bb.fwords, bb.fmasks)
                sets = _device_sets((runs, spine, state.mem, state.index))
                if not runs or any(ds != {dev} for ds in sets):
                    misplaced.append(f"shard {s}: {len(runs)} runs, devices "
                                     f"{sorted({str(d) for ds in sets for d in ds})}")
                log(f"shard {s}: {len(runs)} runs, {len(sets)} arrays on "
                    f"{dev}")
            smoke.check("shard_placement", "; ".join(misplaced))
    finally:
        g.close()
    return smoke.failures


# ---------------------------------------------------------------------- main
def _compile_counter():
    """Counts backend compiles and persistent-cache hits in this process."""
    import jax
    counts = {"compiles": 0, "cache_hits": 0}

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            counts["compiles"] += 1

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            counts["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the single-store path; 4: only the sharded "
                         "path, one range shard per chip")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=int, default=SCALE,
                    choices=range(MIN_SCALE, FULL_SCALE + 1),
                    metavar=f"{{{MIN_SCALE}..{FULL_SCALE}}}",
                    help="R-MAT scale (default %(default)s); below the full "
                         "scale only where a chip call's time limit forces "
                         "the cut")
    args = ap.parse_args(argv)

    _import_repro()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    counts = _compile_counter()
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}; compile cache {cache_dir}")
    if args.scale < FULL_SCALE:
        print(f"reduced: R-MAT scale {FULL_SCALE} -> {args.scale} (one chip "
              "call must finish inside its time limit)")
    t0 = time.perf_counter()
    if args.chips == 1:
        failures = run_single(args.scale, args.seed)
    else:
        failures = run_sharded(devices[:args.chips], args.scale, args.seed)
    print(f"total: {time.perf_counter() - t0:.3f} s (smoke timing, not a "
          f"metric); compiles={counts['compiles']} "
          f"persistent_cache_hits={counts['cache_hits']}")
    if failures:
        print(f"chip_smoke: FAILED checks: {failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
