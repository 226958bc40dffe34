"""CSR run construction, lookup and merge (paper §2.2, §4.2.1).

Every function here is pure and jit-able over fixed-capacity arrays.  A run is
always sorted by (src, dst, ts); invalid slots carry src == INVALID_VID so they
sort to the tail.  The k-way compaction merge is realized as concat + sort.

Record sorts run on the host (``lexsort_order``): a multi-key device sort
takes minutes to compile for a TPU at store sizes and recompiles for every
capacity bucket, while moving the keys across takes milliseconds.  Every
other step (gathers, GC, CSR offsets) stays on the device, sort-free.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .types import INVALID_VID, CSRRunArrays


def lexsort_order(*keys: jnp.ndarray) -> jnp.ndarray:
    """Stable permutation sorting records by ``keys`` (most significant
    first), computed by numpy and returned on the keys' device.

    Compiling ``jnp.lexsort`` over three int32 keys for one TPU v5e chip
    (on an x86 host CPU) takes 228 s at 4M records and 127 s at 64K, once
    per capacity bucket — longer than a whole scale-18 smoke run's device
    work."""
    order = np.lexsort([np.asarray(k) for k in keys[::-1]]).astype(np.int32)
    return jax.device_put(order, keys[0].sharding)


def _lexsort_edges(src, dst, ts) -> jnp.ndarray:
    """Order: src asc, then dst asc, then ts asc. Returns permutation."""
    return lexsort_order(src, dst, ts)


@functools.partial(jax.jit, static_argnames=("vcap",))
def build_sorted_run_arrays(src_s, dst_s, ts_s, marker_s, prop_s, n, *,
                            vcap: int) -> CSRRunArrays:
    """CSR run from records already (src, dst, ts)-sorted with the n valid
    ones first.  No sort: the vertex keys are the change points of src."""
    ecap = src_s.shape[0]
    pos = jnp.arange(ecap, dtype=jnp.int32)
    valid = pos < n
    src_s = jnp.where(valid, src_s, INVALID_VID)
    first = valid & ((pos == 0) | (src_s != jnp.roll(src_s, 1)))
    starts = jnp.nonzero(first, size=vcap, fill_value=ecap)[0]
    starts = starts.astype(jnp.int32)
    has = starts < ecap
    vkeys = jnp.where(has, src_s[jnp.minimum(starts, ecap - 1)], INVALID_VID)
    # Pad keys point at position n: empty slices, no masking needed.
    voff = jnp.where(has, starts, n)
    n = n.astype(jnp.int32)
    return CSRRunArrays(
        vkeys=vkeys.astype(jnp.int32),
        voff=jnp.concatenate([voff, n[None]]).astype(jnp.int32),
        dst=jnp.where(valid, dst_s, 0), ts=jnp.where(valid, ts_s, 0),
        marker=jnp.where(valid, marker_s, False),
        prop=jnp.where(valid, prop_s, 0.0),
        nv=jnp.sum(has).astype(jnp.int32), ne=n,
    )


@jax.jit
def _mask_invalid(src, n):
    return jnp.where(jnp.arange(src.shape[0]) < n, src, INVALID_VID)


@functools.partial(jax.jit, static_argnames=("vcap",))
def _gather_build(src, dst, ts, marker, prop, n, order, *, vcap: int):
    return build_sorted_run_arrays(src[order], dst[order], ts[order],
                                   marker[order], prop[order], n, vcap=vcap)


def build_run_arrays(
    src: jnp.ndarray,
    dst: jnp.ndarray,
    ts: jnp.ndarray,
    marker: jnp.ndarray,
    prop: jnp.ndarray,
    n: jnp.ndarray,
    *,
    vcap: int,
) -> CSRRunArrays:
    """Sort raw edges into a CSR run. Entries at positions >= n are ignored."""
    n = jnp.asarray(n, jnp.int32)
    src = _mask_invalid(src, n)
    return _gather_build(src, dst, ts, marker, prop, n,
                         _lexsort_edges(src, dst, ts), vcap=vcap)


@jax.jit
def run_lookup(run: CSRRunArrays, v: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(found, start, end) of vertex v's edge slice. O(log nv) memory I/O —
    the multi-level index path (index.py) replaces this with O(1)."""
    i = jnp.searchsorted(run.vkeys, v).astype(jnp.int32)
    i_c = jnp.minimum(i, run.vcap - 1)
    found = run.vkeys[i_c] == v
    start = run.voff[i_c]
    end = run.voff[i_c + 1]
    return found, jnp.where(found, start, 0), jnp.where(found, end, 0)


@jax.jit
def run_lookup_batch(run: CSRRunArrays, vs: jnp.ndarray):
    """Vectorized `run_lookup`: (found, start, end) for a whole int32 query
    vector in one jit'd binary-search pass.  Pad slots (INVALID_VID) report
    not-found."""
    i = jnp.searchsorted(run.vkeys, vs).astype(jnp.int32)
    i_c = jnp.minimum(i, run.vcap - 1)
    found = (run.vkeys[i_c] == vs) & (vs != INVALID_VID)
    start = run.voff[i_c]
    end = run.voff[i_c + 1]
    return found, jnp.where(found, start, 0), jnp.where(found, end, 0)


@jax.jit
def map_run_to_queries(run: CSRRunArrays, vs: jnp.ndarray) -> jnp.ndarray:
    """Inverse of run_lookup_batch: per EDGE record, the position of its
    source vertex in the sorted query vector vs — or B for records of
    non-queried vertices / pad slots.

    One O(ecap) pass per run replaces per-vertex slice gathers, so the
    batched read path needs no per-vertex degree cap: ragged adjacency is
    carried as (qid, record) pairs and resolved by one segmented sort.
    """
    B = vs.shape[0]
    src = _expand_src(run)
    j = jnp.searchsorted(vs, src).astype(jnp.int32)
    j_c = jnp.minimum(j, B - 1)
    hit = (vs[j_c] == src) & (src != INVALID_VID)
    return jnp.where(hit, j_c, B)


@functools.partial(jax.jit, static_argnames=("cap",))
def run_gather(run: CSRRunArrays, start: jnp.ndarray, end: jnp.ndarray, *, cap: int):
    """Gather up to `cap` edge records from [start, end)."""
    idx = start + jnp.arange(cap, dtype=jnp.int32)
    m = idx < end
    idx_c = jnp.minimum(idx, run.ecap - 1)
    return (
        jnp.where(m, run.dst[idx_c], INVALID_VID),
        jnp.where(m, run.ts[idx_c], 0),
        jnp.where(m, run.marker[idx_c], False),
        jnp.where(m, run.prop[idx_c], 0.0),
        m,
    )


def _gc_keep_mask(src: jnp.ndarray, dst: jnp.ndarray, ts: jnp.ndarray,
                  marker: jnp.ndarray, valid: jnp.ndarray,
                  tau_min: jnp.ndarray,
                  live_below: jnp.ndarray) -> jnp.ndarray:
    """Version-retention GC over (src,dst,ts)-sorted records (DESIGN.md §4).

    1. Drop a record iff a newer record of the same (src,dst) exists with
       ts <= tau_min (superseded before any live snapshot could see it).
    2. A newest-of-key tombstone with ts <= tau_min (dead to every live
       snapshot) drops unless ``live_below`` says the key's newest version
       in the levels BELOW the merge output is an insert — then the
       tombstone must stay to shadow it.  Where the key is dead or absent
       below, nothing can be re-exposed, and the tombstone goes together
       with the versions it superseded (for alternating insert/delete
       histories the dropped records cancel, which keeps the multilevel ±
       analytics invariant Σ± per key == live count).  At the bottom level
       ``live_below`` is all False.
    """
    nxt_same = (
        valid
        & jnp.roll(valid, -1)
        & (src == jnp.roll(src, -1))
        & (dst == jnp.roll(dst, -1))
    )
    nxt_same = nxt_same.at[-1].set(False)
    nxt_ts = jnp.roll(ts, -1)
    superseded = nxt_same & (nxt_ts <= tau_min)
    dead_tomb = marker & ~nxt_same & (ts <= tau_min) & ~live_below
    return valid & ~superseded & ~dead_tomb


def _sort_records(src, dst, ts, marker, prop, valid):
    """(src, dst, ts)-sort a merge input; invalid slots sort last."""
    src = jnp.where(valid, src, INVALID_VID)
    return _gather(_lexsort_edges(src, dst, ts),
                   src, dst, ts, marker, prop, valid)


@jax.jit
def _gather(order, *cols):
    return tuple(c[order] for c in cols)


@jax.jit
def _newest_in_run(run: CSRRunArrays, src: jnp.ndarray, dst: jnp.ndarray):
    """Per (src, dst) key: whether ``run`` holds it, and the ts and marker
    of its newest record there.  A vertex's records in a merged run are
    (dst, ts)-sorted, so the newest is the last one with dst <= key: one
    vectorized upper-bound bisection inside each vertex's slice."""
    found_v, start, end = run_lookup_batch(run, src)
    last_i = run.ecap - 1

    def step(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        open_ = lo < hi
        le = run.dst[jnp.minimum(mid, last_i)] <= dst
        return (jnp.where(open_ & le, mid + 1, lo),
                jnp.where(open_ & ~le, mid, hi))

    n_steps = max(1, int(run.ecap).bit_length() + 1)
    lo, _ = jax.lax.fori_loop(0, n_steps, step, (start, end))
    last = jnp.clip(lo - 1, 0, last_i)
    found = found_v & (lo > start) & (run.dst[last] == dst)
    return found, run.ts[last], run.marker[last]


def _live_below(deeper: Sequence[CSRRunArrays], src, dst) -> jnp.ndarray:
    """Per (src, dst) key: its newest version across ``deeper`` is an
    insert."""
    best_ts = jnp.full(src.shape, jnp.iinfo(jnp.int32).min, jnp.int32)
    live = jnp.zeros(src.shape, bool)
    for run in deeper:
        found, ts, marker = _newest_in_run(run, src, dst)
        newer = found & (ts > best_ts)
        best_ts = jnp.where(newer, ts, best_ts)
        live = jnp.where(newer, ~marker, live)
    return live


@functools.partial(jax.jit, static_argnames=("vcap",))
def _gc_build(src, dst, ts, marker, prop, valid, tau_min, live_below, *,
              vcap: int) -> CSRRunArrays:
    keep = _gc_keep_mask(src, dst, ts, marker, valid, tau_min, live_below)
    return _compact_sorted(keep, src, dst, ts, marker, prop, vcap=vcap)


def _compact_sorted(keep, src, dst, ts, marker, prop, *, vcap: int):
    """The kept records of a sorted stream, moved in order to a dense
    prefix (no sort), as a CSR run."""
    n = jnp.sum(keep).astype(jnp.int32)
    idx = jnp.nonzero(keep, size=keep.shape[0], fill_value=0)[0]
    return build_sorted_run_arrays(src[idx], dst[idx], ts[idx], marker[idx],
                                   prop[idx], n, vcap=vcap)


def merge_runs(
    runs: Sequence[CSRRunArrays],
    tau_min: int,
    *,
    vcap: int,
    deeper: Sequence[CSRRunArrays] = (),
) -> CSRRunArrays:
    """Vertex-aware compaction merge of k runs into one (paper Example 1).

    The result keeps every version still visible to a snapshot >= tau_min and
    annihilates superseded versions / dead tombstones.  ``deeper`` are the
    runs below the output level whose vertex ranges overlap it (none at the
    bottom): a dead tombstone survives only where one of them still holds
    a live version of its key.
    """
    src = jnp.concatenate([_expand_src(r) for r in runs])
    dst = jnp.concatenate([r.dst for r in runs])
    ts = jnp.concatenate([r.ts for r in runs])
    marker = jnp.concatenate([r.marker for r in runs])
    prop = jnp.concatenate([r.prop for r in runs])
    valid = jnp.concatenate(
        [jnp.arange(r.ecap, dtype=jnp.int32) < r.ne for r in runs]
    )
    src, dst, ts, marker, prop, valid = _sort_records(
        src, dst, ts, marker, prop, valid)
    live_below = _live_below(deeper, src, dst)
    return _gc_build(src, dst, ts, marker, prop, valid,
                     jnp.asarray(tau_min, jnp.int32), live_below, vcap=vcap)


@jax.jit
def _expand_src(run: CSRRunArrays) -> jnp.ndarray:
    """Recover the per-edge src array from (vkeys, voff): src[e] = vkeys[j]
    for voff[j] <= e < voff[j+1].  One searchsorted — the inverse of CSR."""
    e = jnp.arange(run.ecap, dtype=jnp.int32)
    j = jnp.searchsorted(run.voff[1:], e, side="right").astype(jnp.int32)
    j = jnp.minimum(j, run.vcap - 1)
    s = run.vkeys[j]
    return jnp.where(e < run.ne, s, INVALID_VID)


@functools.partial(jax.jit, static_argnames=("vcap",))
def run_slice_vertex_range(run: CSRRunArrays, lo: int, hi: int,
                           *, vcap: int) -> CSRRunArrays:
    """Extract the sub-run covering vertices in [lo, hi).  Used by partial
    (per-segment) compaction to pull only the overlapping vertex range."""
    src = _expand_src(run)
    inside = (src >= lo) & (src < hi)
    return _compact_sorted(inside, src, run.dst, run.ts, run.marker,
                           run.prop, vcap=vcap)


def empty_run(vcap: int, ecap: int) -> CSRRunArrays:
    return CSRRunArrays(
        vkeys=jnp.full((vcap,), INVALID_VID, jnp.int32),
        voff=jnp.zeros((vcap + 1,), jnp.int32),
        dst=jnp.zeros((ecap,), jnp.int32),
        ts=jnp.zeros((ecap,), jnp.int32),
        marker=jnp.zeros((ecap,), bool),
        prop=jnp.zeros((ecap,), jnp.float32),
        nv=jnp.asarray(0, jnp.int32),
        ne=jnp.asarray(0, jnp.int32),
    )


def repad_run(run: CSRRunArrays, vcap: int, ecap: int) -> CSRRunArrays:
    """Copy a run into (possibly smaller-capacity) fresh padding.  Host-level
    utility to keep capacities in quantized buckets across compactions."""
    def fit1(x, cap, fill):
        if x.shape[0] == cap:
            return x
        if x.shape[0] > cap:
            return x[:cap]
        return jnp.concatenate(
            [x, jnp.full((cap - x.shape[0],), fill, x.dtype)])
    return CSRRunArrays(
        vkeys=fit1(run.vkeys, vcap, INVALID_VID),
        voff=fit1(run.voff, vcap + 1, run.voff[-1]),
        dst=fit1(run.dst, ecap, 0),
        ts=fit1(run.ts, ecap, 0),
        marker=fit1(run.marker, ecap, False),
        prop=fit1(run.prop, ecap, 0.0),
        nv=run.nv, ne=run.ne,
    )


def quantize_cap(n: int, minimum: int = 256, half_steps: bool = False) -> int:
    """Round up to a power-of-two bucket — bounds recompilation count.

    ``half_steps`` also allows 1.5x-power-of-two buckets (overshoot capped
    at +50 % instead of +100 %, for ~1 extra compile per size decade) —
    used where the padded length feeds work linear in it, e.g. the batched
    read path's annihilation lexsort."""
    c = minimum
    while c < n:
        if half_steps and (c * 3) // 2 >= n:
            return (c * 3) // 2
        c <<= 1
    return c
