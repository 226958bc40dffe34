"""CSR run construction, lookup, merge + version-retention GC."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import csr
from repro.core.types import INVALID_VID


def _mk(src, dst, ts=None, marker=None, prop=None, cap=64, vcap=32):
    n = len(src)
    ts = np.arange(n) if ts is None else np.asarray(ts)
    marker = np.zeros(n, bool) if marker is None else np.asarray(marker)
    prop = np.ones(n, np.float32) if prop is None else np.asarray(prop)

    def pad(a, fill=0):
        out = np.full(cap, fill, np.asarray(a).dtype)
        out[:n] = a
        return jnp.asarray(out)

    return csr.build_run_arrays(
        pad(np.asarray(src, np.int32)), pad(np.asarray(dst, np.int32)),
        pad(ts.astype(np.int32)), pad(marker),
        pad(prop.astype(np.float32)), jnp.asarray(n, jnp.int32), vcap=vcap)


def test_build_sorts_and_offsets():
    run = _mk([5, 1, 5, 3], [9, 2, 1, 7])
    assert int(run.nv) == 3 and int(run.ne) == 4
    vk = np.asarray(run.vkeys)[:3].tolist()
    assert vk == [1, 3, 5]
    # vertex 5's edges sorted by dst
    f, s, e = csr.run_lookup(run, jnp.asarray(5))
    assert bool(f) and np.asarray(run.dst)[int(s):int(e)].tolist() == [1, 9]


def test_lookup_missing():
    run = _mk([1, 2], [3, 4])
    f, s, e = csr.run_lookup(run, jnp.asarray(7))
    assert not bool(f)


def test_expand_src_inverse():
    run = _mk([4, 4, 2, 9], [1, 2, 3, 4])
    src = np.asarray(csr._expand_src(run))[:4].tolist()
    assert src == [2, 4, 4, 9]


def test_merge_vertex_aware_order():
    """Paper Example 1: merged edges grouped by src, sorted by dst."""
    a = _mk([0, 1], [1, 3], ts=[0, 1])
    b = _mk([0, 2], [4, 0], ts=[2, 3])
    m = csr.merge_runs([a, b], tau_min=100, vcap=16)
    assert int(m.ne) == 4
    src = np.asarray(csr._expand_src(m))[:4].tolist()
    dst = np.asarray(m.dst)[:4].tolist()
    assert src == [0, 0, 1, 2] and dst == [1, 4, 3, 0]


def test_merge_gc_pair_annihilation():
    # insert (1,2)@0 then tombstone (1,2)@5: with tau_min>=5 the PAIR
    # annihilates at any level where nothing below holds the key live
    # (csr._gc_keep_mask) — here the level below holds only other keys.
    a = _mk([1], [2], ts=[0])
    b = _mk([1], [2], ts=[5], marker=[True])
    below = _mk([1, 3], [1, 2], ts=[-2, -1])
    m_mid = csr.merge_runs([a, b], tau_min=10, vcap=16, deeper=[below])
    assert int(m_mid.ne) == 0
    m_bot = csr.merge_runs([a, b], tau_min=10, vcap=16)
    assert int(m_bot.ne) == 0


def test_merge_gc_double_insert_keeps_tombstone():
    # ins@-1 lives below; this merge holds [ins@1, del@5].  The del must
    # survive above the bottom to shadow the deeper live generation, and
    # goes once nothing below holds the key live.
    a = _mk([1], [2], ts=[1])
    b = _mk([1], [2], ts=[5], marker=[True])
    below = _mk([1], [2], ts=[-1])
    m_mid = csr.merge_runs([a, b], tau_min=10, vcap=16, deeper=[below])
    assert int(m_mid.ne) == 1 and bool(np.asarray(m_mid.marker)[0])
    m_bot = csr.merge_runs([a, b], tau_min=10, vcap=16)
    assert int(m_bot.ne) == 0


def test_merge_gc_orphan_tombstone_survives_mid_level():
    # A tombstone whose insert lives DEEPER (not in this merge) must survive
    # above the bottom level to shadow it.
    b = _mk([1], [2], ts=[5], marker=[True])
    below = _mk([1, 1], [1, 2], ts=[0, 1])
    m_mid = csr.merge_runs([b], tau_min=10, vcap=16, deeper=[below])
    assert int(m_mid.ne) == 1 and bool(np.asarray(m_mid.marker)[0])


def test_merge_gc_respects_live_snapshot():
    a = _mk([1], [2], ts=[0])
    b = _mk([1], [2], ts=[5], marker=[True])
    # A reader pinned at tau=3 must still see the original insert.
    m = csr.merge_runs([a, b], tau_min=3, vcap=16)
    assert int(m.ne) == 2


@pytest.mark.parametrize("below, kept", [
    ([([1], [2], [0], [False])], True),          # live insert below
    ([([1], [2], [0], [False]),
      ([1, 1], [2, 2], [1, 2], [False, True])], False),  # newest below: del
    ([([1], [3], [0], [False])], False),         # other key below
    ([], False),                                 # bottom
])
def test_merge_gc_tombstone_vs_newest_below(below, kept):
    """[del@5, ins@6, del@7] merged above runs that may hold the key: the
    dead newest tombstone stays exactly when the key's newest version
    below is an insert (dropping it would resurrect the edge)."""
    m = csr.merge_runs([_mk([1, 1, 1], [2, 2, 2], ts=[5, 6, 7],
                            marker=[True, False, True])],
                       tau_min=10, vcap=16,
                       deeper=[_mk(s, d, ts=t, marker=mk)
                               for s, d, t, mk in below])
    assert int(m.ne) == int(kept)
    if kept:
        assert bool(np.asarray(m.marker)[0])


def test_newest_in_run_bisects_within_vertex():
    run = _mk([1, 1, 1, 2, 2, 4], [3, 5, 5, 3, 9, 5],
              ts=[0, 1, 2, 3, 4, 5], marker=[False, False, True, False,
                                              False, False])
    src = jnp.asarray([1, 1, 1, 2, 2, 3, 4, 4, INVALID_VID], jnp.int32)
    dst = jnp.asarray([3, 5, 4, 9, 5, 5, 5, 6, 5], jnp.int32)
    found, ts, marker = csr._newest_in_run(run, src, dst)
    found = np.asarray(found)
    assert found.tolist() == [True, True, False, True, False, False, True,
                              False, False]
    assert np.asarray(ts)[found].tolist() == [0, 2, 4, 5]
    assert np.asarray(marker)[found].tolist() == [False, True, False, False]


def test_slice_vertex_range():
    run = _mk([1, 2, 3, 4], [9, 8, 7, 6])
    sub = csr.run_slice_vertex_range(run, 2, 4, vcap=8)
    assert int(sub.ne) == 2
    assert np.asarray(sub.vkeys)[:2].tolist() == [2, 3]


def test_repad_and_quantize():
    run = _mk([1, 2], [3, 4])
    small = csr.repad_run(run, 8, 8)
    assert small.vkeys.shape[0] == 8 and small.dst.shape[0] == 8
    f, s, e = csr.run_lookup(small, jnp.asarray(2))
    assert bool(f)
    assert csr.quantize_cap(1000) == 1024
