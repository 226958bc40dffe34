"""Property-based tests (hypothesis) on system invariants."""
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="hypothesis not installed (see requirements-dev.txt); property "
           "tests are skipped rather than breaking suite collection")
from hypothesis import HealthCheck, given, settings, strategies as st

from repro.core import LSMGraph
from repro.core.index import CompactIndex
from conftest import small_store_cfg

_sets = settings(max_examples=20, deadline=None,
                 suppress_health_check=list(HealthCheck))


@st.composite
def op_sequences(draw):
    n_ops = draw(st.integers(3, 12))
    ops = []
    live = set()
    for _ in range(n_ops):
        kind = draw(st.sampled_from(["insert", "insert", "insert", "delete"]))
        k = draw(st.integers(1, 60))
        src = draw(st.lists(st.integers(0, 40), min_size=k, max_size=k))
        dst = draw(st.lists(st.integers(0, 40), min_size=k, max_size=k))
        if kind == "delete":
            if not live:
                continue
            pool = list(live)
            idx = draw(st.lists(st.integers(0, len(pool) - 1),
                                min_size=1, max_size=min(8, len(pool))))
            pairs = [pool[i] for i in idx]
            ops.append(("delete", pairs))
            live -= set(pairs)
        else:
            pairs = list(zip(src, dst))
            ops.append(("insert", pairs))
            live |= set(pairs)
    return ops


@given(op_sequences())
@_sets
def test_store_matches_dict_model(ops):
    """The store == a dict adjacency model under any insert/delete sequence."""
    g = LSMGraph(small_store_cfg(vmax=64, mem_edges=64, batch_cap=32,
                                 n_segments=256, hash_slots=512,
                                 ovf_cap=512, seg_target_edges=128))
    model = {}
    for kind, pairs in ops:
        src = np.array([p[0] for p in pairs], np.int32)
        dst = np.array([p[1] for p in pairs], np.int32)
        if kind == "insert":
            g.insert_edges(src, dst)
            for p in pairs:
                model.setdefault(p[0], set()).add(p[1])
        else:
            g.delete_edges(src, dst)
            for p in pairs:
                model.get(p[0], set()).discard(p[1])
    snap = g.snapshot()
    for v in range(41):
        got = set(int(x) for x in snap.neighbors(v))
        assert got == model.get(v, set()), (v, got, model.get(v, set()))
    snap.release()


@given(op_sequences())
@_sets
def test_multilevel_spmv_equals_materialized(ops):
    """± tombstone annihilation == exact merge for alternating histories."""
    from repro.analytics import (materialize_csr, multilevel_degree,
                                 multilevel_views)
    g = LSMGraph(small_store_cfg(vmax=64, mem_edges=64, batch_cap=32,
                                 n_segments=256, hash_slots=512,
                                 ovf_cap=512, seg_target_edges=128))
    seen = set()
    for kind, pairs in ops:
        if kind == "insert":
            # no dup live inserts (within a batch or across batches)
            pairs = [p for p in dict.fromkeys(pairs) if p not in seen]
            seen |= set(pairs)
        else:
            # no double-deletes: histories must alternate ins/del
            pairs = [p for p in dict.fromkeys(pairs) if p in seen]
            seen -= set(pairs)
        if not pairs:
            continue
        src = np.array([p[0] for p in pairs], np.int32)
        dst = np.array([p[1] for p in pairs], np.int32)
        (g.insert_edges if kind == "insert" else g.delete_edges)(src, dst)
    snap = g.snapshot()
    view = materialize_csr(snap, 64)
    deg_exact = np.asarray(view.degrees).astype(np.float32)
    deg_fast = np.asarray(multilevel_degree(
        multilevel_views(snap), n_out=64))
    np.testing.assert_allclose(deg_fast, deg_exact, atol=1e-4)
    snap.release()


@given(st.lists(st.tuples(st.integers(0, 500), st.integers(1, 4),
                          st.integers(0, 1 << 20), st.integers(0, 4096)),
                min_size=1, max_size=60))
@_sets
def test_compact_index_matches_dense_semantics(entries):
    """The 2-slot + page-set compact index returns exactly what was set."""
    ci = CompactIndex(vmax=512, interval=64)
    model = {}
    for (v, lvl, fid, off) in entries:
        ci.set_position(v, lvl, fid, off)
        model[(v, lvl)] = (fid, off)
    for (v, lvl), want in model.items():
        got = ci.get_positions(v)
        assert got.get(lvl) == want


@given(st.integers(0, 100), st.integers(0, 100))
@_sets
def test_merge_perm_sizes(na, nb):
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    rng = np.random.default_rng(na * 101 + nb)
    cap = 128

    def mk(n):
        k1 = np.sort(rng.integers(0, 10, n)).astype(np.int32)
        k2 = rng.integers(0, 10, n).astype(np.int32)
        k3 = rng.integers(0, 100, n).astype(np.int32)
        o = np.lexsort((k3, k2, k1))
        out = []
        for k in (k1[o], k2[o], k3[o]):
            p = np.full(cap, np.iinfo(np.int32).max, np.int32)
            p[:n] = k
            out.append(jnp.asarray(p))
        return tuple(out)

    idx = jnp.arange(cap, dtype=jnp.int32)
    merged = kops.merge_streams(mk(na) + (idx,), mk(nb) + (idx + cap,))
    perm = np.asarray(merged[3])
    valid = perm[:na + nb]
    assert len(set(valid.tolist())) == na + nb  # a permutation
    assert ((valid < cap) | (valid >= cap)).all()
