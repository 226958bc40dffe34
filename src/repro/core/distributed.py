"""Distributed LSMGraph: vertex-sharded storage + collective analytics.

Scale-out story (DESIGN.md §6):
  * vertices are RANGE-partitioned over the `data` mesh axis; every shard owns
    an independent LSMGraph (its runs never overlap other shards');
  * update ingestion routes edge batches to their owner shard with a bucketed
    `all_to_all` (padded, ragged-safe) — the same dispatch shape MoE expert
    parallelism uses (models/moe.py), so the collective schedule is shared;
  * analytics iterate locally (segment kernels over the shard's CSR) and
    exchange the dense iterate with `all_gather` per sweep; the optimized
    variant overlaps the gather with local compute (§Perf iteration);
  * the `pod` axis replicates the graph service for throughput/fault domains;
    cross-pod traffic is only the O(V) iterate, not edges.

Everything here is pure jit/shard_map code usable under any mesh — including
the 512-device dry-run mesh (launch/dryrun.py lowers `pagerank_step` and
`route_updates` for both production meshes).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..analytics.view import CSRView
from ..kernels import ref as kref


class ShardedCSR(NamedTuple):
    """Stacked per-shard CSR (leading axis = shards)."""

    dst: jnp.ndarray      # int32[S, Emax]
    seg: jnp.ndarray      # int32[S, Emax]  — GLOBAL source vertex id
    wt: jnp.ndarray       # float32[S, Emax] (0 = pad)
    deg: jnp.ndarray      # float32[S, Vl]  — local out-degrees
    v_start: jnp.ndarray  # int32[S]
    n_vertices: int
    n_shards: int

    @property
    def v_local(self) -> int:
        return self.deg.shape[1]


def partition_csr(view: CSRView, n_shards: int) -> ShardedCSR:
    """Range-partition a CSRView into stacked shard-local arrays (host)."""
    v = view.n_vertices
    vl = (v + n_shards - 1) // n_shards
    voff = np.asarray(view.voff)
    dst = np.asarray(view.dst)
    prop = np.asarray(view.prop)
    seg = np.asarray(view.seg_ids())
    emax = 1
    pieces = []
    for s in range(n_shards):
        lo_v, hi_v = s * vl, min((s + 1) * vl, v)
        lo_e, hi_e = int(voff[lo_v]), int(voff[hi_v])
        pieces.append((lo_v, dst[lo_e:hi_e], seg[lo_e:hi_e],
                       prop[lo_e:hi_e],
                       (voff[lo_v + 1:hi_v + 1] - voff[lo_v:hi_v])))
        emax = max(emax, hi_e - lo_e)
    S = n_shards
    out_dst = np.zeros((S, emax), np.int32)
    out_seg = np.zeros((S, emax), np.int32)
    out_wt = np.zeros((S, emax), np.float32)
    out_deg = np.zeros((S, vl), np.float32)
    v_start = np.zeros((S,), np.int32)
    for s, (lo_v, d, g, p, degs) in enumerate(pieces):
        n = len(d)
        out_dst[s, :n] = d
        out_seg[s, :n] = g
        out_wt[s, :n] = 1.0
        out_deg[s, :len(degs)] = degs
        v_start[s] = lo_v
    return ShardedCSR(dst=jnp.asarray(out_dst), seg=jnp.asarray(out_seg),
                      wt=jnp.asarray(out_wt), deg=jnp.asarray(out_deg),
                      v_start=jnp.asarray(v_start), n_vertices=v,
                      n_shards=n_shards)


def _local_segsum(dst, seg, wt, x_full, v_start, vl):
    """Shard-local CSR reduce: y_local[u - v_start] over local edges."""
    vals = wt * jnp.take(x_full, dst, axis=0, mode="clip")
    lseg = jnp.clip(seg - v_start, 0, vl - 1)
    return jnp.zeros((vl,), jnp.float32).at[lseg].add(
        jnp.where(wt != 0.0, vals, 0.0))


def pagerank_step(shard: ShardedCSR, x_local: jnp.ndarray, *,
                  axis: str = "data", damping: float = 0.85,
                  exchange: str = "fp32") -> jnp.ndarray:
    """One PR sweep per shard — call via shard_map (in/out P(axis)).

    `exchange` compresses the dense-iterate all-gather (the service's only
    cross-shard traffic — §Perf hillclimb C):
      fp32 — baseline; bf16 — 2x fewer bytes; int8 — 4x, shared pmax scale
      (quantization error bounded by |c|_max/127 per sweep; measured
      accuracy in tests/test_distributed.py).
    """
    vl = x_local.shape[0]
    deg_local = shard.deg
    contrib_local = x_local / jnp.maximum(deg_local[0], 1.0)
    if exchange == "bf16":
        contrib_full = jax.lax.all_gather(
            contrib_local.astype(jnp.bfloat16), axis,
            tiled=True).astype(jnp.float32)
    elif exchange == "int8":
        amax = jax.lax.pmax(jnp.max(jnp.abs(contrib_local)), axis)
        scale = jnp.maximum(amax, 1e-30) / 127.0
        q = jnp.clip(jnp.round(contrib_local / scale), -127, 127
                     ).astype(jnp.int8)
        contrib_full = jax.lax.all_gather(
            q, axis, tiled=True).astype(jnp.float32) * scale
    else:
        contrib_full = jax.lax.all_gather(contrib_local, axis, tiled=True)
    y = _local_segsum(shard.dst[0], shard.seg[0], shard.wt[0], contrib_full,
                      shard.v_start[0], vl)
    dang_local = jnp.sum(jnp.where(deg_local[0] == 0, x_local, 0.0))
    dangling = jax.lax.psum(dang_local, axis)
    n = shard.n_vertices
    return (1.0 - damping) / n + damping * (y + dangling / n)


def make_distributed_pagerank(mesh: Mesh, shard: ShardedCSR, *,
                              axis: str = "data", iters: int = 20,
                              damping: float = 0.85,
                              exchange: str = "fp32"):
    """Returns a jit'd distributed PageRank over the given mesh.

    The shard arrays are passed sharded on `axis`; replicated on every other
    mesh axis (the pod axis replicates the service).
    """
    spec_sharded = P(axis)
    n = shard.n_vertices

    def _one(dst, seg, wt, deg, v_start, x_local):
        sh = ShardedCSR(dst=dst, seg=seg, wt=wt, deg=deg, v_start=v_start,
                        n_vertices=n, n_shards=shard.n_shards)

        def body(_, x):
            return pagerank_step(sh, x, axis=axis, damping=damping,
                                 exchange=exchange)

        return jax.lax.fori_loop(0, iters, body, x_local)

    mapped = jax.shard_map(
        _one, mesh=mesh,
        in_specs=(spec_sharded,) * 5 + (spec_sharded,),
        out_specs=spec_sharded,
        check_vma=False,
    )

    def run():
        x0 = jnp.full((shard.n_shards * shard.v_local,), 1.0 / n, jnp.float32)
        return mapped(shard.dst, shard.seg, shard.wt, shard.deg,
                      shard.v_start, x0)

    return jax.jit(run)


# ---------------------------------------------------------------------------
# Update routing: the distributed ingest path.
# ---------------------------------------------------------------------------

def _bucket_exchange(src, channels, fills, n_valid, *, v_local: int,
                     n_shards: int, bucket_cap: int, axis: str):
    """Shared bucketed-``all_to_all`` core: owner = src // v_local (range
    partition), stable bucket layout (sort by owner, rank within bucket),
    one exchange per channel.  Returns (routed_src, routed_channels, valid,
    dropped) — every output padded to ``n_shards * bucket_cap``."""
    bc = src.shape[0]
    pos = jnp.arange(bc, dtype=jnp.int32)
    valid = pos < n_valid
    owner = jnp.where(valid, src // v_local, n_shards)
    # Stable bucket layout: sort by owner, then rank within bucket.
    order = jnp.lexsort((pos, owner))
    owner_s = owner[order]
    first = jnp.searchsorted(owner_s, owner_s, side="left")
    rank = jnp.arange(bc, dtype=jnp.int32) - first.astype(jnp.int32)
    slot = jnp.where((owner_s < n_shards) & (rank < bucket_cap),
                     owner_s * bucket_cap + rank, n_shards * bucket_cap)
    dropped = jnp.sum((rank >= bucket_cap) & (owner_s < n_shards))

    def scatter(x, fill):
        buf = jnp.full((n_shards * bucket_cap,), fill, x.dtype)
        return buf.at[slot].set(x[order], mode="drop")

    # all_to_all: dimension 0 split into n_shards chunks, exchanged.
    def a2a(x):
        x = x.reshape(n_shards, bucket_cap)
        return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                  tiled=False).reshape(-1)

    b_src = scatter(src, -1)
    routed = tuple(a2a(scatter(x, f)) for x, f in zip(channels, fills))
    b_valid = (b_src >= 0).astype(jnp.int32)
    return a2a(b_src), routed, a2a(b_valid), dropped


def route_updates_local(src, dst, prop, n_valid, *, v_local: int,
                        n_shards: int, bucket_cap: int, axis: str = "data"):
    """Inside shard_map: route this shard's pending updates to owner shards.

    Returns (src, dst, prop, valid) of received updates, padded to
    n_shards * bucket_cap.  Owner = src // v_local (range partition).
    """
    r_src, (r_dst, r_prop), r_valid, dropped = _bucket_exchange(
        src, (dst, prop), (-1, 0.0), n_valid, v_local=v_local,
        n_shards=n_shards, bucket_cap=bucket_cap, axis=axis)
    return r_src, r_dst, r_prop, r_valid, dropped[None].astype(jnp.int32)


def route_edge_batches_local(src, dst, prop, marker, n_valid, *,
                             v_local: int, n_shards: int, bucket_cap: int,
                             axis: str = "data"):
    """Route full ``EdgeBatch`` payloads (insert AND tombstone records) to
    owner shards — the sharded graph service's write dispatch.  Identical
    bucket/`all_to_all` shape to ``route_updates_local`` plus a marker
    channel (int32 0/1: tombstones must reach the same owner shard as the
    inserts they annihilate).  Returns (src, dst, prop, marker, valid,
    dropped)."""
    r_src, (r_dst, r_prop, r_marker), r_valid, dropped = _bucket_exchange(
        src, (dst, prop, marker.astype(jnp.int32)), (-1, 0.0, 0), n_valid,
        v_local=v_local, n_shards=n_shards, bucket_cap=bucket_cap, axis=axis)
    return (r_src, r_dst, r_prop, r_marker, r_valid,
            dropped[None].astype(jnp.int32))


def make_route_updates(mesh: Mesh, *, v_local: int, n_shards: int,
                       batch_cap: int, bucket_cap: int, axis: str = "data"):
    """jit'd distributed update router over `mesh` (dry-run lowerable)."""

    def _route(src, dst, prop, n_valid):
        # 1-D inputs arrive shard-local already; n_valid is (1,) per shard.
        return route_updates_local(
            src, dst, prop, n_valid[0], v_local=v_local,
            n_shards=n_shards, bucket_cap=bucket_cap, axis=axis)

    mapped = jax.shard_map(
        _route, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
        check_vma=False,
    )
    return jax.jit(mapped)


def make_route_edge_batches(mesh: Mesh, *, v_local: int, n_shards: int,
                            bucket_cap: int, axis: str = "data"):
    """jit'd distributed ``EdgeBatch`` router over ``mesh`` (the sharded
    service's write tier; marker channel included)."""

    def _route(src, dst, prop, marker, n_valid):
        return route_edge_batches_local(
            src, dst, prop, marker, n_valid[0], v_local=v_local,
            n_shards=n_shards, bucket_cap=bucket_cap, axis=axis)

    mapped = jax.shard_map(
        _route, mesh=mesh,
        in_specs=(P(axis),) * 5,
        out_specs=(P(axis),) * 6,
        check_vma=False,
    )
    return jax.jit(mapped)
