"""Pipelined read path: prefetch, chunk padding, tournament merge.

Oracle rule: whatever the pipeline overlaps (cold segment loads, per-chunk
resolves, per-run merge streams), `neighbors_batch` stays element-wise equal
to the per-vertex reference `neighbors_scalar` — including with every
segment evicted cold mid-batch, under a concurrent compaction, and for any
source count the device tournament covers.
"""
import threading
import time

import numpy as np
import pytest

from conftest import small_store_cfg

from repro.core import LSMGraph
from repro.core.store import prefetch_pool


def _assert_batch_equals_scalar(snap, vs):
    batch = snap.neighbors_batch(vs)
    assert len(batch) == len(vs)
    for v, got in zip(vs, batch):
        ref = snap.neighbors_scalar(int(v))
        np.testing.assert_array_equal(got, ref, err_msg=f"vertex {v}")


def _durable_multi_run_store(root, n_runs=4, seed=0, v=500, per_run=900):
    """A durable store with ``n_runs`` L0 runs (each evictable) + tombstones."""
    from repro.storage import open_store
    rng = np.random.default_rng(seed)
    g = open_store(str(root), small_store_cfg(l0_run_limit=n_runs + 64),
                   wal_sync="off")
    for i in range(n_runs):
        src = rng.integers(0, v, per_run).astype(np.int32)
        dst = rng.integers(0, v, per_run).astype(np.int32)
        g.insert_edges(src, dst, prop=rng.random(per_run).astype(np.float32))
        if i == n_runs // 2:
            di = rng.choice(per_run, per_run // 8, replace=False)
            g.delete_edges(src[di], dst[di])
        g.flush_memgraph()
    assert len(g.levels[0]) == n_runs and int(g.mem.ne) == 0
    return g


def _evict_all(g) -> int:
    n = 0
    for lvl in g.levels:
        for rf in lvl:
            n += bool(rf.evict())
    return n


# ------------------------------------------------------------------ prefetch
def test_cold_evicted_batch_equals_scalar(tmp_path):
    """Every segment evicted: the batched resolve reloads them through the
    background prefetcher and still matches the scalar oracle."""
    g = _durable_multi_run_store(tmp_path, n_runs=4)
    try:
        snap = g.snapshot()
        assert _evict_all(g) == 4
        _assert_batch_equals_scalar(snap, np.arange(0, 520))
        snap.release()
    finally:
        g.close()


def test_prefetch_range_loads_in_background(tmp_path):
    """_prefetch_range alone (no foreground read) re-materializes cold
    overlapping runs via the shared pool."""
    g = _durable_multi_run_store(tmp_path, n_runs=3)
    try:
        snap = g.snapshot()
        assert _evict_all(g) == 3
        scheduled = snap._prefetch_range(0, g.cfg.vmax)
        assert scheduled == 3
        deadline = time.time() + 30
        runs = list(g.levels[0])
        while (any(rf.arrays is None for rf in runs)
               and time.time() < deadline):
            time.sleep(0.01)
        assert all(rf.arrays is not None for rf in runs)
        # idempotent: nothing cold left to schedule
        assert snap._prefetch_range(0, g.cfg.vmax) == 0
        snap.release()
    finally:
        g.close()


def test_prefetch_failure_surfaces_on_foreground_load(tmp_path):
    """A background load failure leaves the run cold; the foreground
    ensure_loaded retries and raises the real error."""
    g = _durable_multi_run_store(tmp_path, n_runs=2)
    try:
        rf = g.levels[0][0]
        assert rf.evict()
        real_loader = rf.loader

        def boom():
            raise IOError("injected cold-load failure")

        rf.loader = boom
        assert rf.prefetch(prefetch_pool())
        time.sleep(0.1)          # let the background attempt run + fail
        assert rf.arrays is None
        with pytest.raises(IOError):
            rf.ensure_loaded()
        rf.loader = real_loader
        rf.ensure_loaded()       # recovery path still works
    finally:
        g.close()


def test_chunked_resolve_under_concurrent_compaction(tmp_path):
    """A pinned snapshot resolving in chunks answers identically while
    compact_l0 rewrites the levels (and unlinks replaced files) underneath
    it — the pin + re-materialize contract, now with prefetch in flight."""
    g = _durable_multi_run_store(tmp_path, n_runs=4, seed=3)
    try:
        snap = g.snapshot()
        vs = np.arange(0, 500)
        ref = snap.neighbors_batch(vs)
        snap._BATCH_CHUNK = 64           # force many chunks (+ trailing pad)
        started = threading.Event()

        def compactor():
            started.set()
            g.compact_l0()

        t = threading.Thread(target=compactor)
        t.start()
        started.wait()
        try:
            for _ in range(3):
                _evict_all(g)            # re-chill whatever reloaded
                got = snap.neighbors_batch(vs)
                for a, b in zip(ref, got):
                    np.testing.assert_array_equal(a, b)
        finally:
            t.join(timeout=120)
        assert not t.is_alive()
        _assert_batch_equals_scalar(snap, np.arange(0, 500, 7))
        snap.release()
    finally:
        g.close()


# ------------------------------------------------------------ chunk padding
def test_trailing_chunk_padded_to_chunk_cap():
    """Every chunk of a chunked resolve runs at the same padded width (one
    jit cache entry), including the trailing partial chunk."""
    rng = np.random.default_rng(11)
    g = LSMGraph(small_store_cfg(l0_run_limit=100))
    g.insert_edges(rng.integers(0, 400, 3000), rng.integers(0, 400, 3000))
    g.flush_memgraph()
    g.insert_edges(rng.integers(0, 400, 200), rng.integers(0, 400, 200))
    snap = g.snapshot()
    snap._BATCH_CHUNK = 64
    seen_pads = []
    real = snap._resolve_batch

    def spy(u, pad_to=None):
        seen_pads.append(pad_to)
        return real(u, pad_to=pad_to)

    snap._resolve_batch = spy
    vs = np.arange(0, 330)               # 330 uniques -> 6 chunks, tail of 10
    one_shot = LSMGraph.snapshot(g).neighbors_batch(vs)
    got = snap.neighbors_batch(vs)
    assert len(seen_pads) == 6
    assert set(seen_pads) == {64}        # uniform pad incl. the 10-wide tail
    for a, b in zip(one_shot, got):
        np.testing.assert_array_equal(a, b)
    snap.release()


# --------------------------------------------------------- tournament merge
def _rand_sorted_stream(rng, n, cap, key_lo=0, key_hi=40):
    i32max = np.iinfo(np.int32).max
    k1 = rng.integers(key_lo, key_hi, n).astype(np.int32)
    k2 = rng.integers(key_lo, key_hi, n).astype(np.int32)
    k3 = rng.integers(0, 1 << 20, n).astype(np.int32)
    order = np.lexsort((k3, k2, k1))
    cols = [k1[order], k2[order], k3[order],
            (rng.random(n) < 0.25),
            rng.standard_normal(n).astype(np.float32)]
    out = []
    for j, c in enumerate(cols):
        p = np.full(cap, i32max if j < 3 else 0, c.dtype)
        p[:n] = c
        out.append(p)
    return tuple(out), n


def _check_tournament(streams, ns):
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    merged = kops.tournament_merge(
        [tuple(jnp.asarray(c) for c in s) for s in streams])
    total = sum(ns)
    cat = [np.concatenate([s[i][:n] for s, n in zip(streams, ns)])
           for i in range(5)]
    order = np.lexsort((cat[2], cat[1], cat[0]))   # stable — the oracle
    for i in range(5):
        np.testing.assert_array_equal(
            np.asarray(merged[i])[:total], cat[i][order],
            err_msg=f"col {i}")


@pytest.mark.parametrize("k", list(range(3, 9)))
def test_tournament_merge_matches_host_lexsort(k):
    """k = 3..8 pre-sorted sources: the log-k tournament is byte-identical
    to a stable host lexsort of the concatenation."""
    rng = np.random.default_rng(100 + k)
    streams, ns = [], []
    for _ in range(k):
        n = int(rng.integers(1, 300))
        cap = max(n, int(rng.choice([256, 384, 512])))
        s, nn = _rand_sorted_stream(rng, n, cap)
        streams.append(s)
        ns.append(nn)
    _check_tournament(streams, ns)


def test_tournament_merge_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(st.data())
    def inner(data):
        k = data.draw(st.integers(min_value=1, max_value=8))
        seed = data.draw(st.integers(min_value=0, max_value=2 ** 31 - 1))
        rng = np.random.default_rng(seed)
        streams, ns = [], []
        for _ in range(k):
            n = data.draw(st.integers(min_value=0, max_value=64))
            cap = max(64, n)
            s, nn = _rand_sorted_stream(rng, n, cap, key_hi=6)  # many ties
            streams.append(s)
            ns.append(nn)
        _check_tournament(streams, ns)

    inner()


def _deep_store(n_runs, seed=7, v=400):
    rng = np.random.default_rng(seed)
    g = LSMGraph(small_store_cfg(l0_run_limit=n_runs + 64))
    for _ in range(n_runs):
        g.insert_edges(rng.integers(0, v, 400), rng.integers(0, v, 400))
        g.flush_memgraph()
    assert len(g.levels[0]) == n_runs
    return g


@pytest.mark.parametrize("k", [3, 5, 8])
def test_collect_sorted_no_host_lexsort_k_sources(k):
    """Deep snapshots (k <= 8 visible pre-sorted sources) materialize with
    ZERO host lexsorts — the tournament covers them; and the view still
    matches the scalar oracle."""
    from repro.analytics import materialize_csr, view as view_mod
    g = _deep_store(k)
    snap = g.snapshot()
    assert len([r for r in snap.all_run_records() if len(r[0])]) == k
    before = dict(view_mod.MERGE_STATS)
    view = materialize_csr(snap, 400)
    assert view_mod.MERGE_STATS["host_lexsort"] == before["host_lexsort"]
    assert view_mod.MERGE_STATS["kernel_merge"] == before["kernel_merge"] + 1
    voff, vdst = np.asarray(view.voff), np.asarray(view.dst)
    for v in range(400):
        np.testing.assert_array_equal(
            np.sort(vdst[voff[v]:voff[v + 1]]), snap.neighbors_scalar(v),
            err_msg=f"vertex {v}")
    snap.release()


def test_resolve_batch_deep_snapshot_tournament_equals_scalar():
    """Deep snapshots (8 and 9 visible sources, MemGraph populated): the
    tournament-merged read spine matches the scalar oracle."""
    rng = np.random.default_rng(17)
    g = _deep_store(8, seed=17)
    g.insert_edges(rng.integers(0, 400, 300), rng.integers(0, 400, 300))
    snap = g.snapshot()   # 9 sources
    _assert_batch_equals_scalar(snap, np.arange(0, 410, 3))
    snap.release()
    g.flush_memgraph()
    g2 = _deep_store(7, seed=18)
    g2.insert_edges(rng.integers(0, 400, 300), rng.integers(0, 400, 300))
    snap2 = g2.snapshot()  # 8 sources
    _assert_batch_equals_scalar(snap2, np.arange(0, 410, 3))
    snap2.release()


def test_legacy_lexsort_path_equals_backbone(monkeypatch):
    """LSMG_READ_TOURNAMENT_K=0 escape hatch: the per-resolve concat+lexsort
    path answers identically to the read-spine path."""
    from repro.core import store as store_mod
    g = _deep_store(4, seed=19)
    rng = np.random.default_rng(19)
    g.insert_edges(rng.integers(0, 400, 200), rng.integers(0, 400, 200))
    vs = np.arange(0, 410, 2)
    snap = g.snapshot()
    spine = snap.neighbors_batch(vs)
    snap.release()
    monkeypatch.setattr(store_mod, "_READ_TOURNAMENT_MAX_K", 0)
    snap2 = g.snapshot()
    legacy = snap2.neighbors_batch(vs)
    _assert_batch_equals_scalar(snap2, vs[:40])
    snap2.release()
    for a, b in zip(spine, legacy):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------- multilevel parity
def test_multilevel_views_skips_runs_invisible_at_tau():
    """Empty-at-τ sources contribute no RunView (no dead kernel dispatch),
    and the ± aggregation still matches live degrees."""
    from repro.analytics import multilevel_views
    from repro.analytics.multilevel import multilevel_degree
    # Distinct (src, dst) pairs: the ± telescoping precondition (alternating
    # per-key history) — duplicates would double-count live membership.
    rng = np.random.default_rng(23)
    v = 400
    pairs = rng.choice(v * v, 1200, replace=False)
    g = LSMGraph(small_store_cfg(l0_run_limit=100))
    for i in range(3):
        p = pairs[i * 400:(i + 1) * 400]
        g.insert_edges((p // v).astype(np.int32), (p % v).astype(np.int32))
        g.flush_memgraph()
    assert len(g.levels[0]) == 3 and int(g.mem.ne) == 0
    snap = g.snapshot()          # MemGraph empty: 3 sources, none skipped
    views = multilevel_views(snap)
    assert len(views) == 3       # the empty MemGraph tier emitted no view
    deg = np.asarray(multilevel_degree(views, n_out=400))
    want = snap.degrees_batch(np.arange(400))
    np.testing.assert_array_equal(deg.astype(np.int64), want)
    snap.release()


# ------------------------------------------------------------------ sharded
def test_sharded_cold_reads_equal_oracle(tmp_path):
    """Routed sharded reads with every shard's segments evicted cold equal
    a single-store oracle (prefetch fans out across shards)."""
    from repro.shard import open_sharded_store
    rng = np.random.default_rng(29)
    cfg = small_store_cfg(l0_run_limit=100)
    src = rng.integers(0, cfg.vmax, 4000).astype(np.int64)
    dst = rng.integers(0, cfg.vmax, 4000).astype(np.int64)
    oracle = LSMGraph(cfg)
    oracle.insert_edges(src, dst)
    oracle.flush_memgraph()
    g = open_sharded_store(str(tmp_path / "shards"), cfg, n_shards=4,
                           wal_sync="off")
    try:
        g.insert_edges(src, dst)
        g.flush_all()
        for shard in g.shards:
            _evict_all(shard)
        qs = rng.integers(0, cfg.vmax, 600).astype(np.int64)
        with oracle.snapshot() as osnap, g.snapshot() as ssnap:
            ref = osnap.neighbors_batch(qs)
            got = ssnap.neighbors_batch(qs)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)
    finally:
        g.close()
