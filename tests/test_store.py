"""LSMGraph store end-to-end: reads after flush/compaction cascades."""
import numpy as np
import pytest

from repro.core import LSMGraph
from conftest import small_store_cfg


@pytest.fixture(scope="module")
def loaded():
    rng = np.random.default_rng(7)
    g = LSMGraph(small_store_cfg())
    ref = {}
    n = 8000
    src = rng.integers(0, 800, n).astype(np.int32)
    dst = rng.integers(0, 800, n).astype(np.int32)
    g.insert_edges(src, dst, prop=np.arange(n, dtype=np.float32))
    for i, (s, d) in enumerate(zip(src, dst)):
        ref.setdefault(int(s), {})[int(d)] = float(i)
    di = rng.choice(n, 500, replace=False)
    g.delete_edges(src[di], dst[di])
    for i in di:
        ref[int(src[i])].pop(int(dst[i]), None)
    return g, ref


def test_neighbors_exact(loaded):
    g, ref = loaded
    snap = g.snapshot()
    for v in list(ref)[:150]:
        got = set(int(x) for x in snap.neighbors(v))
        assert got == set(ref[v]), v
    snap.release()


def test_multilevel_structure(loaded):
    g, _ = loaded
    sizes = g.level_sizes()
    assert sum(sizes) > 0
    assert len(g.levels[0]) < g.cfg.l0_run_limit  # compactions ran


def test_props_latest_version_wins(loaded):
    g, ref = loaded
    snap = g.snapshot()
    v = next(iter(ref))
    dsts, props = snap.neighbors(v, return_props=True)
    for d, p in zip(dsts, props):
        assert ref[v][int(d)] == float(p)
    snap.release()


def test_query_edge(loaded):
    g, ref = loaded
    v = next(iter(ref))
    d = next(iter(ref[v]))
    assert g.query_edge(v, d)
    assert not g.query_edge(v, 4095)


def test_reinsert_after_delete(loaded):
    g, ref = loaded
    v, d = 4000, 4001  # fresh ids
    g.insert_edges([v], [d])
    g.delete_edges([v], [d])
    g.insert_edges([v], [d])
    snap = g.snapshot()
    assert int(d) in set(int(x) for x in snap.neighbors(v))
    snap.release()


def test_index_ablation_same_answers(loaded):
    """Fig 16: with and without the multi-level index, answers agree."""
    g, ref = loaded
    snap = g.snapshot()
    import dataclasses
    try:
        for v in list(ref)[:40]:
            with_idx = set(int(x) for x in snap.neighbors(v))
            object.__setattr__(snap.cfg, "use_multilevel_index", False)
            without = set(int(x) for x in snap.neighbors(v))
            object.__setattr__(snap.cfg, "use_multilevel_index", True)
            assert with_idx == without == set(ref[v])
    finally:
        object.__setattr__(snap.cfg, "use_multilevel_index", True)
        snap.release()


def test_min_readable_fid_filters_l0(loaded):
    """Paper §4.3: after L0 compaction, vertices in range only read L0 files
    with fid >= max compacted fid + 1."""
    g, _ = loaded
    import numpy as np
    min_fid = np.asarray(g.index.l0_min_fid)
    assert (min_fid > 0).any()  # compactions bumped the readable floor


def test_compaction_keeps_source_entries_outside_source_range():
    """An output segment may reach past the compacted source range (it
    absorbed a wider target segment); source-level entries of vertices
    outside the source range belong to segments that were not compacted
    and must survive."""
    import jax.numpy as jnp
    from repro.core import index as mlindex
    from repro.core.types import INVALID_VID
    idx = mlindex.empty_index(64, 3)
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    # Two L1 segments: fid 7 holds vertices 10..19, fid 8 holds 30..39.
    for fid, lo in ((7, 10), (8, 30)):
        idx = mlindex.note_compaction(
            idx, level=1, new_vkeys=i32(np.arange(lo, lo + 10)),
            new_voff=i32(np.arange(11) * 2), new_nv=i32(10),
            new_fid=i32(fid), range_lo=i32(lo), range_hi=i32(lo + 10),
            src_lo=i32(0), src_hi=i32(0), l0_min_fid_update=i32(-1))
    # fid 7 moves to L2, merged with an L2 segment spanning 0..49.
    idx = mlindex.note_compaction(
        idx, level=2, new_vkeys=i32(np.arange(0, 50)),
        new_voff=i32(np.arange(51)), new_nv=i32(50), new_fid=i32(9),
        range_lo=i32(0), range_hi=i32(50), src_lo=i32(10), src_hi=i32(20),
        l0_min_fid_update=i32(-1))
    l1 = np.asarray(idx.lvl_fid[:, 0])
    assert (l1[10:20] == INVALID_VID).all()
    assert (l1[30:40] == 8).all()
    assert (np.asarray(idx.lvl_fid[:50, 1]) == 9).all()


def _stream_model(ops, n):
    """Newest update per (src, dst) wins; a delete hides the edge."""
    keys = np.concatenate([s.astype(np.int64) * n + d for _, s, d in ops])
    dead = np.concatenate([np.full(len(s), x) for x, s, _ in ops])
    uniq, first = np.unique(keys[::-1], return_index=True)
    live = uniq[~dead[::-1][first]]
    return live // n, live % n


@pytest.mark.parametrize("mode", ["concurrent", "reopen"])
def test_rmat_update_stream_reads_match_model(mode, tmp_path):
    """A duplicate-heavy R-MAT insert/delete stream through a tiny MemGraph
    (many flushes, L0->L1->L2 compactions): every vertex's neighbor list
    equals the newest-wins model, also with the active MemGraph holding
    the last batch and after recovery from disk."""
    from repro.core import StoreConfig
    from repro.core.concurrent import ConcurrentLSMGraph
    from repro.data.graphgen import rmat_edges, update_stream
    from repro.storage import open_store
    scale = 8
    n = 1 << scale
    cfg = StoreConfig.for_graph(n, mem_bytes=150 * 20)
    src, dst = rmat_edges(scale, 16 << scale, seed=0)
    if mode == "concurrent":
        g = ConcurrentLSMGraph(store=open_store(str(tmp_path), cfg))
    else:
        g = open_store(str(tmp_path), cfg)
    ops = []
    for op, s, d in update_stream(src, dst):
        s2, d2 = np.r_[s, d], np.r_[d, s]
        ops.append((op == "delete", s2, d2))
        (g.delete_edges if op == "delete" else g.insert_edges)(s2, d2)
    if mode == "concurrent":
        g.flush()
    if mode == "reopen":
        g.close()
        g = open_store(str(tmp_path))
    lsrc, ldst = _stream_model(ops, n)
    voff = np.searchsorted(lsrc, np.arange(n + 1))
    with g.snapshot() as snap:
        got = snap.neighbors_batch(np.arange(n, dtype=np.int64))
    bad = [v for v in range(n) if not np.array_equal(
        np.asarray(got[v], np.int64), ldst[voff[v]:voff[v + 1]])]
    g.close()
    assert bad == []
