"""Analytics views over a pinned LSMGraph snapshot.

Two read strategies (DESIGN.md §5):

  * `materialize_csr` — exact merged live CSR at τ.  One sort over the
    snapshot's visible records; every iteration of every algorithm then runs
    at CSR speed.  This is the TPU analogue of the paper's observation that
    CSR layout is what makes analytics fast — and the cost is one compaction-
    sized sort, amortized over the (tens of) iterations an algorithm runs.

  * `multilevel_views` — zero-merge per-run CSR views, consumed by
    multilevel.py with the ± tombstone-annihilation trick (linear
    aggregations) — the beyond-paper fast path.
"""
from __future__ import annotations

from typing import List, NamedTuple, Tuple

import jax.numpy as jnp
import numpy as np

from ..core.store import Snapshot
from ..core.types import BYTES_PER_EDGE, BYTES_PER_PROP


class CSRView(NamedTuple):
    """Dense live CSR over vertex-id space [0, n_vertices)."""

    voff: jnp.ndarray   # int32[V+1]
    dst: jnp.ndarray    # int32[E]
    prop: jnp.ndarray   # float32[E]
    n_vertices: int
    n_edges: int

    @property
    def degrees(self) -> jnp.ndarray:
        return self.voff[1:] - self.voff[:-1]

    def seg_ids(self) -> jnp.ndarray:
        """Per-edge source id (inverse CSR), sorted by construction."""
        e = jnp.arange(self.dst.shape[0], dtype=jnp.int32)
        j = jnp.searchsorted(self.voff[1:], e, side="right").astype(jnp.int32)
        return jnp.minimum(j, self.n_vertices - 1)


# Max sources merged on device by _collect_sorted's tournament; deeper
# snapshots fall back to one host lexsort.  MERGE_STATS counts which branch
# ran (tests assert zero host lexsorts for any k <= TOURNAMENT_MAX_SOURCES).
# The counters live with the merge kernels (kernels/merge.py) and are
# thread-safe — views run on reader threads concurrently with the spine
# splicer and the compactor; this module-level name is a shared alias.
TOURNAMENT_MAX_SOURCES = 8
from ..kernels.merge import MERGE_STATS  # noqa: E402  (shared thread-safe counters)


def _merge_sources_tournament(sources):
    """Merge k (src, dst, ts)-sorted record tuples with the log-k pairwise
    merge tournament (kernels/merge.py): device merges instead of a host
    lexsort over the concatenation.  Sources pad to quantized capacities
    with all-MAX keys (they sort to the merged tail and are sliced off)."""
    from ..core.csr import quantize_cap
    from ..kernels import ops as kops
    i32max = np.iinfo(np.int32).max
    streams = []
    for rec in sources:
        n = len(rec[0])
        cap = quantize_cap(n)
        cols = []
        for j, col in enumerate(rec):
            fill = i32max if j < 3 else 0
            p = np.full(cap, fill, col.dtype)
            p[:n] = col
            cols.append(jnp.asarray(p))
        streams.append(tuple(cols))
    merged = kops.tournament_merge(streams)
    total = sum(len(rec[0]) for rec in sources)
    return tuple(np.asarray(c)[:total] for c in merged)


def _collect_sorted(snapshot: Snapshot):
    """All visible records, (src, dst, ts)-lexsorted.

    CSR runs arrive pre-sorted (fid is not None); MemGraph tiers arrive in
    arrival order and are sorted individually.  Any 2..TOURNAMENT_MAX_SOURCES
    pre-sorted sources (deep snapshots included) merge on-device via the
    log-k tournament of pairwise merge-path passes; beyond that one host
    lexsort remains (the TPU path would be a device sort, csr._sort_records).
    Sources with no record visible at τ are skipped up front — they can
    only add dead weight to the merge."""
    sources = []
    for (src, dst, ts, marker, prop, fid) in snapshot.all_run_records():
        if len(src) == 0 or not (ts <= snapshot.tau).any():
            continue
        rec = (np.asarray(src, np.int32), np.asarray(dst, np.int32),
               np.asarray(ts, np.int32), np.asarray(marker, bool),
               np.asarray(prop, np.float32))
        if fid is None:  # MemGraph tier: arrival order — sort this source
            order = np.lexsort((rec[2], rec[1], rec[0]))
            rec = tuple(c[order] for c in rec)
        sources.append(rec)
    if not sources:
        z = np.zeros(0, np.int64)
        return z, z, z, np.zeros(0, bool), np.zeros(0, np.float32)
    if len(sources) == 1:
        src, dst, ts, marker, prop = sources[0]
    elif len(sources) <= TOURNAMENT_MAX_SOURCES:
        MERGE_STATS.bump("kernel_merge")
        src, dst, ts, marker, prop = _merge_sources_tournament(sources)
    else:
        MERGE_STATS.bump("host_lexsort")
        cat = tuple(np.concatenate([s[i] for s in sources])
                    for i in range(5))
        order = np.lexsort((cat[2], cat[1], cat[0]))
        src, dst, ts, marker, prop = (c[order] for c in cat)
    return (src.astype(np.int64), dst.astype(np.int64), ts.astype(np.int64),
            marker, prop)


def materialize_csr(snapshot: Snapshot, n_vertices: int) -> CSRView:
    """Exact live adjacency at snapshot.tau as one dense CSR."""
    src, dst, ts, marker, prop = _collect_sorted(snapshot)
    vis = ts <= snapshot.tau  # order-preserving filter on sorted records
    src, dst, ts, marker, prop = (a[vis] for a in (src, dst, ts, marker,
                                                   prop))
    last = np.ones(len(src), bool)
    if len(src):
        last[:-1] = (src[:-1] != src[1:]) | (dst[:-1] != dst[1:])
    live = last & ~marker
    src, dst, prop = src[live], dst[live], prop[live]
    voff = np.searchsorted(src, np.arange(n_vertices + 1)).astype(np.int32)
    snapshot._store.io.analytics_read += len(src) * (
        BYTES_PER_EDGE + BYTES_PER_PROP)
    return CSRView(voff=jnp.asarray(voff), dst=jnp.asarray(dst, jnp.int32),
                   prop=jnp.asarray(prop), n_vertices=n_vertices,
                   n_edges=int(len(src)))


class RunView(NamedTuple):
    """One visible run as (seg-sorted) raw edges with ± annihilation weights."""

    src: jnp.ndarray
    dst: jnp.ndarray
    wt: jnp.ndarray   # +prop/+1 insert, -prop/-1 tombstone, 0 invisible


def multilevel_views(snapshot: Snapshot, *, weighted: bool = False
                     ) -> List[RunView]:
    """Per-run views for merge-free linear aggregation (DESIGN.md §5).

    Precondition (asserted by property tests): per (src, dst) key the record
    history alternates insert/delete, so Σ(±) telescopes to live membership.
    """
    out: List[RunView] = []
    for (src, dst, ts, marker, prop, fid) in snapshot.all_run_records():
        vis = ts <= snapshot.tau
        n_vis = int(vis.sum())
        if n_vis == 0:
            # Same empty-tier skip the batched resolve has: a run with no
            # record visible at τ contributes only zero weights, so every
            # downstream per-run aggregation kernel would dispatch dead.
            continue
        base = prop if weighted else np.ones(len(src), np.float32)
        wt = np.where(marker, -base, base) * vis
        # CSR runs (fid set) arrive src-sorted — only MemGraph tiers need
        # the host sort for the segment kernel's rank compression.
        if fid is None:
            order = np.argsort(src, kind="stable")
            src, dst, wt = src[order], dst[order], wt[order]
        out.append(RunView(src=jnp.asarray(src, jnp.int32),
                           dst=jnp.asarray(dst, jnp.int32),
                           wt=jnp.asarray(wt, jnp.float32)))
        snapshot._store.io.analytics_read += n_vis * BYTES_PER_EDGE
    return out
