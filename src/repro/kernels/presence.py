"""Vectorized vertex-presence test over run filters (jnp, on device).

One call answers "which of these B query vertices might each of these R
runs contain?" as a dense bool[R, B] hit matrix — the batched read
path's pre-gate: rows of the per-(run, query) visibility matrix are
ANDed with this before spine rank + index gather + annihilation, so
filtered-out pairs never cost device work and fully-rejected cold runs
are never loaded.

Inputs are the packed presence words of every visible run stacked into
one uint32[R, W] matrix (rows padded to the widest filter; a padded row
of all-ones bits = "always maybe", used for runs without a filter) plus
a per-run uint32 position mask (= mbits - 1, power-of-two table sizes).
The hash is the same splitmix32 double-hash the host-side builder uses
(``core.filters``) — formula-identical by contract, so a key inserted at
build time can never miss at query time.

The probe is FILTER_K gathers of filter words at hashed, data-dependent
positions, and the filter rows need not fit VMEM, so there is no Pallas
kernel: Mosaic does not lower an in-kernel vector gather ("Only 2D
gather is supported").  XLA's gather is the one implementation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.filters import FILTER_K, FILTER_SALT


def _mix(x: jnp.ndarray) -> jnp.ndarray:
    """uint32 avalanche — MUST mirror ``core.filters._mix32``."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _hash_pair(q: jnp.ndarray):
    h1 = _mix(q)
    h2 = _mix(q ^ jnp.uint32(FILTER_SALT)) | jnp.uint32(1)
    return h1, h2


@jax.jit
def presence_matrix(words: jnp.ndarray, masks: jnp.ndarray,
                    queries: jnp.ndarray) -> jnp.ndarray:
    """bool[R, B] from uint32[R, W] words, uint32[R] masks (mbits - 1 per
    run) and int32[B] queries: the AND of FILTER_K bit probes."""
    h1, h2 = _hash_pair(queries.astype(jnp.uint32))
    h1, h2, mask = h1[None, :], h2[None, :], masks[:, None]
    hit = None
    for i in range(FILTER_K):
        pos = (h1 + jnp.uint32(i) * h2) & mask
        w = (pos >> 5).astype(jnp.int32)
        bits = jnp.take_along_axis(words, w, axis=1)
        h = ((bits >> (pos & jnp.uint32(31))) & jnp.uint32(1)) != 0
        hit = h if hit is None else (hit & h)
    return hit
