"""Device-side sorted merges (read spine + analytics collect).

``merge_streams`` merges two whole (src, dst, ts)-sorted record streams,
payload included, as a cross-rank merge: A[i]'s output position is i plus
its lexicographic rank in B (a vectorized binary search), and the payload
is applied by gathers only.  ``tournament_merge`` folds k pre-sorted
sources with a log-k tournament of such pairwise passes, so k sources
merge on device with no host lexsort — the deep-snapshot read path and the
analytics collect both ride it.

Keys are (k1, k2, k3) = (src, dst, ts) compared lexicographically — no 64-bit
packing needed (TPUs have no native int64).

There is no Pallas merge-path kernel: its per-tile windows start at
data-dependent element offsets and were read with an in-kernel vector
gather, which Mosaic does not lower ("Only 2D gather is supported"), and
it held both whole inputs in VMEM.  This jnp merge is the one
implementation on every backend.
"""
from __future__ import annotations

import threading
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import obs

class MergeStats:
    """Merge-path counters as a view over the metric registry.

    Each key is backed by a monotonic ``merge_<key>_total`` registry
    counter (thread-safe: bumped from reader threads, the compactor, and
    the spine splicer concurrently), so the exporter and the legacy
    mapping read surface (`stats["k"]`, `dict(stats)`) see one set of
    numbers.  ``reset()`` keeps its test-facing zero-the-view semantics by
    remembering per-key base offsets — the registry counters themselves
    stay monotonic.  Writers must go through ``bump``."""

    _KEYS = ("kernel_merge", "host_lexsort", "spine_build", "spine_splice",
             "spine_reuse")

    def __init__(self, registry=None) -> None:
        self._mu = threading.Lock()
        self._registry = registry if registry is not None else obs.REGISTRY
        self._counters = {k: self._registry.counter(f"merge_{k}_total")
                          for k in self._KEYS}
        self._base: Dict[str, int] = {k: 0 for k in self._KEYS}

    def _counter(self, key: str):
        c = self._counters.get(key)
        if c is None:
            with self._mu:
                c = self._counters.get(key)
                if c is None:
                    c = self._registry.counter(f"merge_{key}_total")
                    self._counters[key] = c
                    self._base[key] = 0
        return c

    def bump(self, key: str, n: int = 1) -> None:
        self._counter(key).inc(n)

    def snapshot_stats(self) -> Dict[str, int]:
        """Point-in-time copy of every counter (the test-facing accessor)."""
        with self._mu:
            return {k: c.value - self._base[k]
                    for k, c in self._counters.items()}

    def reset(self) -> None:
        with self._mu:
            for k, c in self._counters.items():
                self._base[k] = c.value

    # Mapping-compatible read surface: dict(stats) and stats["key"] work.
    def __getitem__(self, key: str) -> int:
        with self._mu:
            return self._counters[key].value - self._base[key]

    def keys(self):
        with self._mu:
            return list(self._counters.keys())

    def __iter__(self):
        return iter(self.keys())

    def __len__(self) -> int:
        with self._mu:
            return len(self._counters)


MERGE_STATS = MergeStats()


def snapshot_stats() -> Dict[str, int]:
    """Module-level accessor for the shared merge counters."""
    return MERGE_STATS.snapshot_stats()


def _lex_less(a1, a2, a3, b1, b2, b3, *, strict: bool):
    lt = (a1 < b1) | ((a1 == b1) & ((a2 < b2) | ((a2 == b2) & (a3 < b3))))
    if strict:
        return lt
    eq = (a1 == b1) & (a2 == b2) & (a3 == b3)
    return lt | eq


def lex_searchsorted(keys_a, q1, q2, q3, n_keys, *, side: str):
    """Vectorized lexicographic binary search of (q1,q2,q3) tuples into the
    3-component sorted key set keys_a (jnp; used for merge-path splits)."""
    k1, k2, k3 = keys_a
    n = k1.shape[0]
    lo = jnp.zeros(q1.shape, jnp.int32)
    hi = jnp.broadcast_to(jnp.asarray(n_keys, jnp.int32), q1.shape)
    steps = max(1, n.bit_length() + 1)

    def body(_, state):
        lo, hi = state
        open_ = lo < hi  # converged lanes must not move (fixed-step loop)
        mid = (lo + hi) // 2
        m = jnp.clip(mid, 0, n - 1)
        a1, a2, a3 = k1[m], k2[m], k3[m]
        if side == "left":
            go_right = _lex_less(a1, a2, a3, q1, q2, q3, strict=True)
        else:
            go_right = _lex_less(a1, a2, a3, q1, q2, q3, strict=False)
        go_right = go_right & open_
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right | ~open_, hi, mid)
        return lo, hi

    # Static step count: unroll at trace time — an XLA while loop pays
    # per-iteration dispatch overhead that dwarfs the O(n) body on CPU.
    state = (lo, hi)
    for i in range(steps):
        state = body(i, state)
    return state[0]


@jax.jit
def merge_streams(a_cols: Tuple[jnp.ndarray, ...],
                  b_cols: Tuple[jnp.ndarray, ...]):
    """Merge two sorted record streams into one, payload included.

    ``a_cols``/``b_cols``: tuples whose first three columns are the int32
    lexicographic sort keys; remaining columns are payload of any dtype.
    Every slot participates (capacity == validity): pad records must carry
    key columns that sort to the tail (e.g. all INT32_MAX).  Returns the
    merged column tuple of length len(a) + len(b).
    """
    # Gather-only payload application (XLA CPU scatters lower to a serial
    # loop; gathers vectorize).  pos_a is strictly increasing, so for every
    # output slot o the count of A-elements among outputs [0, o] is
    # ca = searchsorted(pos_a, o, right); slot o holds A[ca-1] iff that
    # element's position IS o, else B[o - ca].
    a1, a2, a3 = a_cols[:3]
    b1, b2, b3 = b_cols[:3]
    na, nb = a1.shape[0], b1.shape[0]
    ra = lex_searchsorted((b1, b2, b3), a1, a2, a3, nb, side="left")
    pos_a = jnp.arange(na, dtype=jnp.int32) + ra
    o = jnp.arange(na + nb, dtype=jnp.int32)
    ca = jnp.searchsorted(pos_a, o, side="right").astype(jnp.int32)
    ia = jnp.clip(ca - 1, 0, na - 1)
    from_a = (ca > 0) & (pos_a[ia] == o)
    ib = jnp.clip(o - ca, 0, nb - 1)
    return tuple(jnp.where(from_a, cca[ia], ccb[ib])
                 for cca, ccb in zip(a_cols, b_cols))


def tournament_merge(streams: Sequence[Tuple[jnp.ndarray, ...]]):
    """log-k tournament of pairwise merges over k sorted streams.

    Adjacent streams pair per round; an odd straggler advances unmerged.
    Pairing is order-preserving and each pairwise pass is stable (A's ties
    first), so the tournament as a whole is stable: records with equal keys
    come out in stream order, byte-identical to a stable lexsort of the
    concatenation.  Host-level loop — each round's merges are independent
    device dispatches.
    """
    streams = list(streams)
    if not streams:
        raise ValueError("tournament_merge needs at least one stream")
    while len(streams) > 1:
        nxt = [merge_streams(streams[i], streams[i + 1])
               for i in range(0, len(streams) - 1, 2)]
        if len(streams) % 2:
            nxt.append(streams[-1])
        streams = nxt
    return streams[0]
