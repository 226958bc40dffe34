"""Batched read subsystem: scalar equivalence, regressions, error paths.

`neighbors_batch` must be byte-identical to the per-vertex reference
(`neighbors_scalar`) across every tier combination a snapshot can pin:
MemGraph-only, MemGraph + L0, deep L1+ after compaction cascades, with
tombstones, across flush/compaction boundaries, and under the no-index
ablation.
"""
import numpy as np
import pytest

from repro.core import LSMGraph
from repro.core.concurrent import ConcurrentLSMGraph
from conftest import small_store_cfg


def _assert_batch_equals_scalar(snap, vs):
    batch = snap.neighbors_batch(vs)
    assert len(batch) == len(vs)
    for v, got in zip(vs, batch):
        ref = snap.neighbors_scalar(int(v))
        np.testing.assert_array_equal(got, ref, err_msg=f"vertex {v}")
        assert got.dtype == ref.dtype


def _multi_tier_store(seed=0):
    """MemGraph + L0 + L1 all populated, with tombstones."""
    rng = np.random.default_rng(seed)
    # big run limit: flushes never auto-compact; compaction driven explicitly
    g = LSMGraph(small_store_cfg(l0_run_limit=100))
    src = rng.integers(0, 500, 6000).astype(np.int32)
    dst = rng.integers(0, 500, 6000).astype(np.int32)
    g.insert_edges(src, dst, prop=np.arange(6000, dtype=np.float32))
    di = rng.choice(6000, 400, replace=False)
    g.delete_edges(src[di], dst[di])
    g.flush_memgraph()
    g.compact_l0()                           # whole L0 -> L1
    g.insert_edges(rng.integers(0, 500, 700), rng.integers(0, 500, 700))
    g.flush_memgraph()                       # a fresh L0 run stays put
    g.insert_edges(rng.integers(0, 500, 150), rng.integers(0, 500, 150))
    assert int(g.mem.ne) > 0 and len(g.levels[0]) > 0
    assert sum(r.ne for r in g.levels[1]) > 0
    return g


def test_batched_equals_scalar_multi_tier():
    g = _multi_tier_store()
    snap = g.snapshot()
    # includes absent ids (500..519) and every present id
    _assert_batch_equals_scalar(snap, np.arange(0, 520))
    snap.release()


def test_batched_equals_scalar_memgraph_only():
    g = LSMGraph(small_store_cfg())
    g.insert_edges([1, 1, 2, 9], [5, 6, 7, 9])
    g.delete_edges([1], [5])
    snap = g.snapshot()
    assert g.level_sizes() == [0] * g.cfg.n_levels  # nothing flushed
    _assert_batch_equals_scalar(snap, np.arange(0, 12))
    snap.release()


def test_batched_props_equal_scalar():
    g = _multi_tier_store(seed=1)
    snap = g.snapshot()
    for v in range(0, 500, 37):
        bd, bp = snap.neighbors_batch([v], return_props=True)[0]
        sd, sp = snap.neighbors_scalar(v, return_props=True)
        np.testing.assert_array_equal(bd, sd)
        np.testing.assert_array_equal(bp, sp)
    snap.release()


def test_batched_duplicate_and_unsorted_queries():
    g = _multi_tier_store(seed=2)
    snap = g.snapshot()
    vs = np.array([44, 3, 44, 499, 0, 3, 44])
    _assert_batch_equals_scalar(snap, vs)
    snap.release()


def test_batched_empty_query():
    g = LSMGraph(small_store_cfg())
    snap = g.snapshot()
    assert snap.neighbors_batch(np.empty(0, np.int64)) == []
    snap.release()


def test_batched_stable_across_compaction_boundary():
    """A pinned snapshot answers identically before and after a compaction
    rewrites the levels underneath it — batched and scalar alike."""
    g = _multi_tier_store(seed=3)
    snap = g.snapshot()
    pre = snap.neighbors_batch(np.arange(0, 500))
    g.compact_l0()
    g.compact_partial(1)
    post = snap.neighbors_batch(np.arange(0, 500))
    for a, b in zip(pre, post):
        np.testing.assert_array_equal(a, b)
    _assert_batch_equals_scalar(snap, np.arange(0, 500))
    snap.release()


def test_batched_no_index_ablation():
    g = _multi_tier_store(seed=4)
    snap = g.snapshot()
    try:
        object.__setattr__(snap.cfg, "use_multilevel_index", False)
        _assert_batch_equals_scalar(snap, np.arange(0, 500, 3))
    finally:
        object.__setattr__(snap.cfg, "use_multilevel_index", True)
    snap.release()


def test_neighbors_wrapper_matches_scalar():
    """neighbors() routes through neighbors_batch (which takes the scalar
    fast path for a 1-vertex batch) — results must be identical."""
    g = _multi_tier_store(seed=5)
    snap = g.snapshot()
    for v in (0, 7, 250, 499, 1000):
        np.testing.assert_array_equal(snap.neighbors(v),
                                      snap.neighbors_scalar(v))
    snap.release()


def test_batched_chunked_resolve_equals_unchunked():
    """Query vectors above _BATCH_CHUNK stream through bounded-size device
    resolves; the stitched result must equal the one-shot resolve."""
    g = _multi_tier_store(seed=10)
    snap = g.snapshot()
    vs = np.arange(0, 520)
    one_shot = snap.neighbors_batch(vs)
    snap._BATCH_CHUNK = 64  # force ~8 chunks (instance override)
    chunked = snap.neighbors_batch(vs)
    for a, b in zip(one_shot, chunked):
        np.testing.assert_array_equal(a, b)
    snap.release()


def test_degrees_batch_matches_neighbors():
    g = _multi_tier_store(seed=6)
    snap = g.snapshot()
    vs = np.arange(0, 100)
    deg = snap.degrees_batch(vs)
    assert deg.tolist() == [len(snap.neighbors_scalar(int(v))) for v in vs]
    snap.release()


# --------------------------------------------------------------- regressions
def test_vertices_includes_dst_only_vertex():
    """Seed bug: a vertex appearing exclusively as a destination was
    invisible to vertices()/edge_set()."""
    g = LSMGraph(small_store_cfg())
    g.insert_edges([3], [7])  # single directed edge: 7 is dst-only
    snap = g.snapshot()
    assert snap.vertices().tolist() == [3, 7]
    assert snap.edge_set() == {(3, 7)}
    snap.release()


def test_vertices_includes_dst_only_after_flush():
    g = LSMGraph(small_store_cfg())
    g.insert_edges([3], [7])
    g.flush_memgraph()
    snap = g.snapshot()
    assert snap.vertices().tolist() == [3, 7]
    snap.release()


def test_materialize_csr_matches_batched_adjacency():
    """The (possibly kernel-merged) materialized view equals per-vertex
    adjacency from the batched read path."""
    from repro.analytics import materialize_csr
    g = _multi_tier_store(seed=7)
    snap = g.snapshot()
    view = materialize_csr(snap, 500)
    voff = np.asarray(view.voff)
    vdst = np.asarray(view.dst)
    for v, nbrs in zip(range(500), snap.neighbors_batch(np.arange(500))):
        got = np.sort(vdst[voff[v]:voff[v + 1]])
        np.testing.assert_array_equal(got, nbrs, err_msg=f"vertex {v}")
    snap.release()


def test_materialize_two_source_kernel_merge_path():
    """Exactly two visible sorted sources (one L0 run + one L1 segment,
    MemGraph empty) takes the Pallas merge-path kernel branch in
    view._collect_sorted; the result must still match scalar adjacency."""
    from repro.analytics import materialize_csr
    rng = np.random.default_rng(8)
    g = LSMGraph(small_store_cfg(l0_run_limit=100))
    g.insert_edges(rng.integers(0, 300, 900), rng.integers(0, 300, 900))
    g.flush_memgraph()
    g.compact_l0()                           # -> one L1 segment
    g.insert_edges(rng.integers(0, 300, 200), rng.integers(0, 300, 200))
    g.flush_memgraph()                       # -> one L0 run, MemGraph empty
    snap = g.snapshot()
    assert len([r for r in snap.all_run_records() if len(r[0])]) == 2
    view = materialize_csr(snap, 300)
    voff, vdst = np.asarray(view.voff), np.asarray(view.dst)
    for v in range(300):
        np.testing.assert_array_equal(
            np.sort(vdst[voff[v]:voff[v + 1]]), snap.neighbors_scalar(v),
            err_msg=f"vertex {v}")
    snap.release()


def test_run_lookup_batch_matches_scalar_run_lookup():
    import jax.numpy as jnp
    from repro.core import csr as csr_mod
    rng = np.random.default_rng(9)
    src = np.sort(rng.integers(0, 100, 500)).astype(np.int32)
    run = csr_mod.build_run_arrays(
        jnp.asarray(src), jnp.asarray(rng.integers(0, 100, 500), jnp.int32),
        jnp.asarray(np.arange(500), jnp.int32),
        jnp.zeros(500, bool), jnp.zeros(500, jnp.float32),
        jnp.asarray(500, jnp.int32), vcap=256)
    qs = jnp.asarray(np.arange(-0, 110), jnp.int32)
    f_b, s_b, e_b = (np.asarray(x) for x in csr_mod.run_lookup_batch(
        run, qs))
    for i, v in enumerate(np.asarray(qs)):
        f, s, e = csr_mod.run_lookup(run, jnp.asarray(v, jnp.int32))
        assert (bool(f), int(s), int(e)) == (bool(f_b[i]), int(s_b[i]),
                                             int(e_b[i])), v


def test_concurrent_writer_error_surfaces_on_next_call():
    """A background writer failure must surface as RuntimeError on the next
    insert_edges/flush, not vanish into the thread."""
    g = ConcurrentLSMGraph(small_store_cfg())
    g.insert_edges([1], [2])
    g.flush()

    def boom(*a, **k):
        raise ValueError("injected writer failure")

    g.store._apply_no_flush = boom
    g.insert_edges([3], [4])           # queued; writer thread hits boom
    with pytest.raises(RuntimeError):
        g.flush()
    with pytest.raises(RuntimeError):
        g.insert_edges([5], [6])
