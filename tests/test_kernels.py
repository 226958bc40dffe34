"""Device kernels vs independent jnp oracles: shape/dtype sweeps (the
Pallas attention kernel in interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("e,v", [(64, 8), (512, 64), (1000, 300),
                                 (513, 7), (2048, 2048)])
def test_gather_segsum_sweep(e, v, rng):
    seg = np.sort(rng.integers(0, v, e)).astype(np.int32)
    dst = rng.integers(0, v, e).astype(np.int32)
    wt = rng.choice([1.0, -1.0, 0.0, 2.5], e).astype(np.float32)
    x = rng.normal(size=v).astype(np.float32)
    y1 = ops.gather_segsum(jnp.asarray(dst), jnp.asarray(seg),
                           jnp.asarray(wt), jnp.asarray(x), n_out=v)
    y2 = ref.gather_segsum_ref(jnp.asarray(dst), jnp.asarray(seg),
                               jnp.asarray(wt), jnp.asarray(x), v)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("e,v", [(100, 20), (777, 100)])
def test_gather_segmin_sweep(e, v, rng):
    seg = np.sort(rng.integers(0, v, e)).astype(np.int32)
    dst = rng.integers(0, v, e).astype(np.int32)
    wt = rng.uniform(0, 2, e).astype(np.float32)
    x = rng.normal(size=v).astype(np.float32)
    y1 = ops.gather_segmin(jnp.asarray(dst), jnp.asarray(seg),
                           jnp.asarray(wt), jnp.asarray(x), n_out=v)
    y2 = ref.gather_segmin_ref(jnp.asarray(dst), jnp.asarray(seg),
                               jnp.asarray(wt), jnp.asarray(x), v)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-5, atol=1e-5)


def _sorted_keys(rng, n, cap, kmax=40):
    """n lexsorted (k1, k2, k3) keys in a cap-slot stream whose pad slots
    carry INT32_MAX keys (they sort to the merged tail)."""
    k1 = rng.integers(0, kmax, n).astype(np.int32)
    k2 = rng.integers(0, kmax, n).astype(np.int32)
    k3 = rng.integers(0, 10000, n).astype(np.int32)
    o = np.lexsort((k3, k2, k1))
    out = []
    for k in (k1[o], k2[o], k3[o]):
        p = np.full(cap, np.iinfo(np.int32).max, np.int32)
        p[:n] = k
        out.append(jnp.asarray(p))
    return tuple(out)


@pytest.mark.parametrize("na,nb,cap", [(0, 5, 64), (100, 200, 256),
                                       (256, 256, 256), (777, 333, 1024)])
def test_merge_perm_sweep(na, nb, cap, rng):
    """merge_streams carries each record's index into concat(A, B) as
    payload; the merged payload is the reference merge permutation."""
    a = _sorted_keys(rng, na, cap)
    b = _sorted_keys(rng, nb, cap)
    ia = jnp.arange(cap, dtype=jnp.int32)
    merged = ops.merge_streams(a + (ia,), b + (ia + cap,))
    p1 = np.asarray(merged[3])
    p2 = ref.merge_perm_ref(a, b, na, nb)
    assert np.array_equal(p1[:na + nb], p2[:na + nb])


@pytest.mark.parametrize("b,hq,hkv,s,d,dt", [
    (1, 4, 2, 256, 64, np.float32),
    (2, 2, 2, 128, 128, np.float32),
    (1, 8, 1, 128, 64, np.float32),
])
def test_flash_attention_sweep(b, hq, hkv, s, d, dt, rng):
    q = jnp.asarray(rng.normal(size=(b, hq, s, d)).astype(dt))
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)).astype(dt))
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)).astype(dt))
    o1 = ops.attention(q, k, v, use_pallas=True)
    o2 = ref.mha_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-3, atol=2e-3)


def test_flash_attention_noncausal(rng):
    q = jnp.asarray(rng.normal(size=(1, 2, 128, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 64)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 64)).astype(np.float32))
    o1 = ops.attention(q, k, v, causal=False, use_pallas=True)
    o2 = ref.mha_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-3, atol=2e-3)


def test_segsum_tombstone_annihilation(rng):
    """wt=-1 rows cancel wt=+1 rows of the same (seg, dst) — the multilevel
    analytics fast path's core identity."""
    seg = np.array([0, 0, 1, 1], np.int32)
    dst = np.array([5, 5, 6, 7], np.int32)
    wt = np.array([1.0, -1.0, 1.0, 1.0], np.float32)
    x = rng.normal(size=10).astype(np.float32)
    y = np.asarray(ops.gather_segsum(
        jnp.asarray(dst), jnp.asarray(seg), jnp.asarray(wt),
        jnp.asarray(x), n_out=2))
    assert abs(y[0]) < 1e-6
    assert abs(y[1] - (x[6] + x[7])) < 1e-5
