"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def gather_segsum_ref(dst: jnp.ndarray, seg_id: jnp.ndarray, wt: jnp.ndarray,
                      x: jnp.ndarray, n_out: int) -> jnp.ndarray:
    """y[s] = sum over edges e with seg_id[e]==s of wt[e] * x[dst[e]].

    The CSR message-aggregation inner loop (PageRank / degree / weighted
    scans).  wt folds validity masks AND tombstone annihilation (wt = -1).
    """
    vals = wt * x[jnp.clip(dst, 0, x.shape[0] - 1)]
    return jnp.zeros((n_out,), x.dtype).at[
        jnp.clip(seg_id, 0, n_out - 1)].add(jnp.where(seg_id < n_out, vals, 0))


def gather_segmin_ref(dst: jnp.ndarray, seg_id: jnp.ndarray, wt: jnp.ndarray,
                      x: jnp.ndarray, n_out: int) -> jnp.ndarray:
    """y[s] = min over edges e with seg_id[e]==s of (wt[e] + x[dst[e]])."""
    inf = jnp.float32(3.0e38)
    vals = wt + x[jnp.clip(dst, 0, x.shape[0] - 1)]
    return jnp.full((n_out,), inf, x.dtype).at[
        jnp.clip(seg_id, 0, n_out - 1)].min(
        jnp.where(seg_id < n_out, vals, inf))


def merge_perm_ref(a_keys, b_keys, na: int, nb: int) -> np.ndarray:
    """Permutation merging two (k1,k2,k3)-lexicographically-sorted key sets.

    Returns perm int32[len] with values indexing concat(A, B); A wins ties
    (stability).  Padded tail (beyond na+nb) points at INVALID (= total)."""
    a1, a2, a3 = (np.asarray(k)[:na] for k in a_keys)
    b1, b2, b3 = (np.asarray(k)[:nb] for k in b_keys)
    cap = len(np.asarray(a_keys[0])) + len(np.asarray(b_keys[0]))
    keys = list(zip(a1.tolist(), a2.tolist(), a3.tolist(), [0] * na,
                    range(na))) + \
        list(zip(b1.tolist(), b2.tolist(), b3.tolist(), [1] * nb,
                 [len(np.asarray(a_keys[0])) + j for j in range(nb)]))
    keys.sort(key=lambda t: (t[0], t[1], t[2], t[3]))
    perm = np.full(cap, cap, np.int32)
    for out_i, t in enumerate(keys):
        perm[out_i] = t[4]
    return perm


def mha_ref(q, k, v, *, causal: bool = True, scale: float | None = None):
    """Reference attention: q [B,Hq,S,D], k/v [B,Hkv,S,D] (GQA broadcast)."""
    bq, hq, sq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        skv = k.shape[2]
        mask = jnp.tril(jnp.ones((sq, skv), bool), k=skv - sq)
        logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)
