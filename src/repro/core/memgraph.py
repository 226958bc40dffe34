"""MemGraph — the graph-aware write cache (paper §4.1).

Structure: an open-addressing hashmap (vertex id -> row) over a pool of
fixed-size segments (one segment per low-degree vertex; ~95 % of vertices per
paper Table 2) plus an overflow tier for edges beyond the segment size.  The
overflow tier is the TPU adaptation of the paper's skip list: append now, sort
on flush/scan (DESIGN.md §2.1) — same ordered-scan API, TPU-native cost.

The batched insert is fully vectorized, including hashmap find-or-insert with
collision resolution by iterated scatter-min claim rounds.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .csr import lexsort_order
from .types import INVALID_VID, EdgeBatch, MemGraphState, StoreConfig

_HASH_MULT = np.uint32(2654435761)
_MAX_PROBE_ROUNDS = 64


def empty_memgraph(cfg: StoreConfig) -> MemGraphState:
    ns, g, h, oc = cfg.n_segments, cfg.seg_size, cfg.hash_slots, cfg.ovf_cap
    return MemGraphState(
        htab_key=jnp.full((h,), INVALID_VID, jnp.int32),
        htab_row=jnp.zeros((h,), jnp.int32),
        seg_owner=jnp.full((ns,), INVALID_VID, jnp.int32),
        seg_len=jnp.zeros((ns,), jnp.int32),
        seg_dst=jnp.zeros((ns, g), jnp.int32),
        seg_ts=jnp.zeros((ns, g), jnp.int32),
        seg_marker=jnp.zeros((ns, g), bool),
        seg_prop=jnp.zeros((ns, g), jnp.float32),
        ovf_src=jnp.zeros((oc,), jnp.int32),
        ovf_dst=jnp.zeros((oc,), jnp.int32),
        ovf_ts=jnp.zeros((oc,), jnp.int32),
        ovf_marker=jnp.zeros((oc,), bool),
        ovf_prop=jnp.zeros((oc,), jnp.float32),
        n_rows=jnp.asarray(0, jnp.int32),
        ovf_n=jnp.asarray(0, jnp.int32),
        ne=jnp.asarray(0, jnp.int32),
    )


def _hash(v: jnp.ndarray, hcap: int) -> jnp.ndarray:
    return (v.astype(jnp.uint32) * _HASH_MULT).astype(jnp.uint32) % np.uint32(hcap)


class _ProbeState(NamedTuple):
    htab_key: jnp.ndarray
    htab_row: jnp.ndarray
    n_rows: jnp.ndarray
    probe: jnp.ndarray
    row: jnp.ndarray
    is_new: jnp.ndarray
    resolved: jnp.ndarray


def _find_or_insert_rows(
    htab_key: jnp.ndarray,
    htab_row: jnp.ndarray,
    n_rows: jnp.ndarray,
    ukeys: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Vectorized open-addressing find-or-insert for a batch of *unique* keys.

    Collision rule per round: every unresolved key whose current probe slot is
    empty proposes to claim it; the minimum unique-index wins (scatter-min);
    losers advance their probe.  Terminates in <= _MAX_PROBE_ROUNDS rounds for
    load factors < ~0.75 (asserted by the `ok` flag + tests).
    """
    U = ukeys.shape[0]
    hcap = htab_key.shape[0]
    base = _hash(ukeys, hcap).astype(jnp.int32)
    uidx = jnp.arange(U, dtype=jnp.int32)
    init = _ProbeState(
        htab_key=htab_key, htab_row=htab_row, n_rows=n_rows,
        probe=jnp.zeros((U,), jnp.int32),
        row=jnp.full((U,), -1, jnp.int32),
        is_new=jnp.zeros((U,), bool),
        resolved=ukeys == INVALID_VID,
    )

    def cond(state: _ProbeState):
        return ~jnp.all(state.resolved)

    def body(state: _ProbeState) -> _ProbeState:
        pos = (base + state.probe) % hcap
        k = state.htab_key[pos]
        hit = ~state.resolved & (k == ukeys)
        row = jnp.where(hit, state.htab_row[pos], state.row)
        resolved = state.resolved | hit
        empty = ~resolved & (k == INVALID_VID)
        # Claim round: scatter-min of unique-index into per-slot owner array.
        owner = jnp.full((hcap,), U, jnp.int32)
        owner = owner.at[jnp.where(empty, pos, hcap)].min(uidx, mode="drop")
        win = empty & (owner[pos] == uidx)
        new_rank = jnp.cumsum(win.astype(jnp.int32)) - 1
        new_row = state.n_rows + new_rank
        row = jnp.where(win, new_row, row)
        safe_pos = jnp.where(win, pos, hcap)
        htab_key = state.htab_key.at[safe_pos].set(ukeys, mode="drop")
        htab_row = state.htab_row.at[safe_pos].set(new_row, mode="drop")
        resolved = resolved | win
        # Unresolved keys saw either a foreign key or lost a claim: advance.
        probe = jnp.where(resolved, state.probe, state.probe + 1)
        return _ProbeState(
            htab_key=htab_key, htab_row=htab_row,
            n_rows=state.n_rows + jnp.sum(win, dtype=jnp.int32),
            probe=probe, row=row, is_new=state.is_new | win,
            resolved=resolved,
        )

    # Bounded while: fori over max rounds with masked body (all-resolved is a
    # no-op round), keeping the loop reverse-mode-free and trivially bounded.
    def fori_body(_, state):
        return jax.lax.cond(cond(state), body, lambda s: s, state)

    final = jax.lax.fori_loop(0, _MAX_PROBE_ROUNDS, fori_body, init)
    ok = jnp.all(final.resolved)
    return (final.htab_key, final.htab_row, final.n_rows, final.row,
            final.is_new, ok)


@jax.jit
def lookup_rows(mg: MemGraphState, keys: jnp.ndarray) -> jnp.ndarray:
    """Pure lookup: row per key, -1 if absent. O(1) expected probes."""
    hcap = mg.hcap
    base = _hash(keys, hcap).astype(jnp.int32)

    def fori_body(r, state):
        row, resolved = state
        pos = (base + r) % hcap
        k = mg.htab_key[pos]
        hit = ~resolved & (k == keys)
        row = jnp.where(hit, mg.htab_row[pos], row)
        resolved = resolved | hit | (k == INVALID_VID)
        return row, resolved

    row = jnp.full(keys.shape, -1, jnp.int32)
    resolved = keys == INVALID_VID
    row, _ = jax.lax.fori_loop(0, _MAX_PROBE_ROUNDS, fori_body, (row, resolved))
    return row


@functools.partial(jax.jit, static_argnames=("mode",))
def insert_batch(
    mg: MemGraphState, batch: EdgeBatch, *, mode: str = "memgraph"
) -> Tuple[MemGraphState, jnp.ndarray]:
    """Insert a batch of edge updates.  Returns (new_state, ok_flag).

    mode: "memgraph" (paper design), "array_only" / "skiplist_only"
    (Fig. 15 ablation variants).
    """
    bc = batch.src.shape[0]
    g = mg.segsize
    pos = jnp.arange(bc, dtype=jnp.int32)
    valid = pos < batch.n
    srcv = jnp.where(valid, batch.src, INVALID_VID)

    if mode == "skiplist_only":
        # Everything goes to the overflow ("skip list") tier.
        opos = mg.ovf_n + jnp.cumsum(valid.astype(jnp.int32)) - 1
        safe = jnp.where(valid, opos, mg.ovf_cap)
        new = mg._replace(
            ovf_src=mg.ovf_src.at[safe].set(batch.src, mode="drop"),
            ovf_dst=mg.ovf_dst.at[safe].set(batch.dst, mode="drop"),
            ovf_ts=mg.ovf_ts.at[safe].set(batch.ts, mode="drop"),
            ovf_marker=mg.ovf_marker.at[safe].set(batch.marker, mode="drop"),
            ovf_prop=mg.ovf_prop.at[safe].set(batch.prop, mode="drop"),
            ovf_n=mg.ovf_n + batch.n,
            ne=mg.ne + batch.n,
        )
        ok = (mg.ovf_n + batch.n) <= mg.ovf_cap
        return new, ok

    ukeys, inv = jnp.unique(
        srcv, size=bc, fill_value=INVALID_VID, return_inverse=True)
    htab_key, htab_row, n_rows, urow, is_new, hash_ok = _find_or_insert_rows(
        mg.htab_key, mg.htab_row, mg.n_rows, ukeys.astype(jnp.int32))
    seg_owner = mg.seg_owner.at[
        jnp.where(is_new, urow, mg.nseg)].set(ukeys, mode="drop")

    row_e = jnp.where(valid, urow[inv], -1)

    # Arrival-order rank of each edge within its row (stable by position).
    row_key = jnp.where(valid, row_e, jnp.iinfo(jnp.int32).max)
    order = jnp.lexsort((pos, row_key))
    row_sorted = row_key[order]
    first_idx = jnp.searchsorted(row_sorted, row_sorted, side="left")
    rank_sorted = jnp.arange(bc, dtype=jnp.int32) - first_idx.astype(jnp.int32)
    rank = jnp.zeros((bc,), jnp.int32).at[order].set(rank_sorted)

    base_len = jnp.where(valid, mg.seg_len[jnp.clip(row_e, 0, mg.nseg - 1)], 0)
    slot = base_len + rank
    in_seg = valid & (slot < g)
    if mode == "array_only":
        # Paper ablation: adjacency arrays only.  Structurally the spill still
        # lands in the shared pool, but the movement cost of growing a compact
        # array (copy d_v edges) is charged by the store's byte accounting.
        pass

    flat = jnp.where(in_seg, row_e * g + slot, mg.nseg * g)
    seg_dst = mg.seg_dst.reshape(-1).at[flat].set(batch.dst, mode="drop")
    seg_ts = mg.seg_ts.reshape(-1).at[flat].set(batch.ts, mode="drop")
    seg_marker = mg.seg_marker.reshape(-1).at[flat].set(batch.marker, mode="drop")
    seg_prop = mg.seg_prop.reshape(-1).at[flat].set(batch.prop, mode="drop")

    is_ovf = valid & ~in_seg
    ovf_rank = jnp.cumsum(is_ovf.astype(jnp.int32)) - 1
    opos = jnp.where(is_ovf, mg.ovf_n + ovf_rank, mg.ovf_cap)
    ovf_src = mg.ovf_src.at[opos].set(batch.src, mode="drop")
    ovf_dst = mg.ovf_dst.at[opos].set(batch.dst, mode="drop")
    ovf_ts = mg.ovf_ts.at[opos].set(batch.ts, mode="drop")
    ovf_marker = mg.ovf_marker.at[opos].set(batch.marker, mode="drop")
    ovf_prop = mg.ovf_prop.at[opos].set(batch.prop, mode="drop")
    n_ovf = jnp.sum(is_ovf, dtype=jnp.int32)

    seg_len = mg.seg_len.at[jnp.where(valid, row_e, mg.nseg)].add(
        1, mode="drop")

    new = MemGraphState(
        htab_key=htab_key, htab_row=htab_row,
        seg_owner=seg_owner, seg_len=seg_len,
        seg_dst=seg_dst.reshape(mg.seg_dst.shape),
        seg_ts=seg_ts.reshape(mg.seg_ts.shape),
        seg_marker=seg_marker.reshape(mg.seg_marker.shape),
        seg_prop=seg_prop.reshape(mg.seg_prop.shape),
        ovf_src=ovf_src, ovf_dst=ovf_dst, ovf_ts=ovf_ts,
        ovf_marker=ovf_marker, ovf_prop=ovf_prop,
        n_rows=n_rows, ovf_n=mg.ovf_n + n_ovf, ne=mg.ne + batch.n,
    )
    ok = (
        hash_ok
        & (n_rows <= mg.nseg)
        & ((mg.ovf_n + n_ovf) <= mg.ovf_cap)
    )
    return new, ok


@jax.jit
def flush_arrays(mg: MemGraphState):
    """Flatten MemGraph into raw (src, dst, ts, marker, prop, n) edge arrays
    of static length NS*G + Oc, ready for csr.build_run_arrays."""
    ns, g = mg.nseg, mg.segsize
    owner = jnp.repeat(mg.seg_owner, g)
    slot = jnp.tile(jnp.arange(g, dtype=jnp.int32), ns)
    stored = jnp.minimum(jnp.repeat(mg.seg_len, g), g)
    seg_valid = (owner != INVALID_VID) & (slot < stored)
    ovf_valid = jnp.arange(mg.ovf_cap, dtype=jnp.int32) < mg.ovf_n

    src = jnp.concatenate([jnp.where(seg_valid, owner, INVALID_VID),
                           jnp.where(ovf_valid, mg.ovf_src, INVALID_VID)])
    dst = jnp.concatenate([mg.seg_dst.reshape(-1), mg.ovf_dst])
    ts = jnp.concatenate([mg.seg_ts.reshape(-1), mg.ovf_ts])
    marker = jnp.concatenate([mg.seg_marker.reshape(-1), mg.ovf_marker])
    prop = jnp.concatenate([mg.seg_prop.reshape(-1), mg.ovf_prop])
    nvalid = jnp.sum(seg_valid, dtype=jnp.int32) + mg.ovf_n
    # Compact valid entries to a dense prefix, in order (no sort).
    order = jnp.nonzero(src != INVALID_VID, size=src.shape[0],
                        fill_value=src.shape[0] - 1)[0]
    pad = jnp.arange(src.shape[0]) >= nvalid
    return (jnp.where(pad, INVALID_VID, src[order]), dst[order], ts[order],
            marker[order], prop[order], nvalid)


@functools.partial(jax.jit, static_argnames=("cap",))
def scan_vertex(mg: MemGraphState, v: jnp.ndarray, *, cap: int):
    """All cached edge records of vertex v (fixed-size output).

    Segment tier: direct G-slot read.  Overflow tier: masked scan — the cost
    the paper's Fig 15 'skip list only' ablation measures.
    """
    row = lookup_rows(mg, v[None])[0]
    g = mg.segsize
    row_c = jnp.clip(row, 0, mg.nseg - 1)
    stored = jnp.where(row >= 0, jnp.minimum(mg.seg_len[row_c], g), 0)
    sidx = jnp.arange(cap, dtype=jnp.int32)
    seg_m = sidx < stored
    sslot = jnp.minimum(sidx, g - 1)
    dst = jnp.where(seg_m, mg.seg_dst[row_c, sslot], INVALID_VID)
    ts = jnp.where(seg_m, mg.seg_ts[row_c, sslot], 0)
    marker = jnp.where(seg_m, mg.seg_marker[row_c, sslot], False)
    prop = jnp.where(seg_m, mg.seg_prop[row_c, sslot], 0.0)

    ovf_m = (mg.ovf_src == v) & (jnp.arange(mg.ovf_cap) < mg.ovf_n)
    oidx = jnp.nonzero(ovf_m, size=cap, fill_value=mg.ovf_cap)[0]
    o_ok = oidx < mg.ovf_cap
    oidx_c = jnp.minimum(oidx, mg.ovf_cap - 1)
    n_seg = jnp.sum(seg_m, dtype=jnp.int32)
    # Append overflow records after the segment records.
    tgt = jnp.where(o_ok, n_seg + jnp.arange(cap, dtype=jnp.int32), cap)
    dst = dst.at[tgt].set(mg.ovf_dst[oidx_c], mode="drop")
    ts = ts.at[tgt].set(mg.ovf_ts[oidx_c], mode="drop")
    marker = marker.at[tgt].set(mg.ovf_marker[oidx_c], mode="drop")
    prop = prop.at[tgt].set(mg.ovf_prop[oidx_c], mode="drop")
    mask = jnp.arange(cap) < (n_seg + jnp.sum(o_ok, dtype=jnp.int32))
    return dst, ts, marker, prop, mask


@jax.jit
def scan_vertices_batch(mg: MemGraphState, vs: jnp.ndarray):
    """Batched `scan_vertex`: cached records of a whole query vector at once.

    vs: int32[B], SORTED ascending, padded with INVALID_VID.  Returns flat
    (qid, dst, ts, marker, prop) arrays of static length B*G + Oc, where
    qid[i] is the position of record i's vertex in vs, or B for slots that
    carry no queried record.  One hashmap probe batch + one gather for the
    segment tier, one searchsorted pass over the overflow tier — constant
    jit'd ops regardless of B (vs. one scan_vertex dispatch per vertex).
    """
    B = vs.shape[0]
    g = mg.segsize
    rows = lookup_rows(mg, vs)
    row_c = jnp.clip(rows, 0, mg.nseg - 1)
    stored = jnp.where(rows >= 0, jnp.minimum(mg.seg_len[row_c], g), 0)
    seg_valid = jnp.arange(g, dtype=jnp.int32)[None, :] < stored[:, None]
    qid_seg = jnp.where(
        seg_valid, jnp.arange(B, dtype=jnp.int32)[:, None], B)
    # Overflow tier: map every overflow record to its query slot (if any) by
    # binary search into the sorted query vector — the inverse direction of
    # scan_vertex's per-vertex nonzero scan, and cap-free.
    oi = jnp.searchsorted(vs, mg.ovf_src).astype(jnp.int32)
    oi_c = jnp.minimum(oi, B - 1)
    ohit = ((vs[oi_c] == mg.ovf_src)
            & (mg.ovf_src != INVALID_VID)
            & (jnp.arange(mg.ovf_cap, dtype=jnp.int32) < mg.ovf_n))
    qid = jnp.concatenate([qid_seg.reshape(-1),
                           jnp.where(ohit, oi_c, B)])
    dst = jnp.concatenate([mg.seg_dst[row_c].reshape(-1), mg.ovf_dst])
    ts = jnp.concatenate([mg.seg_ts[row_c].reshape(-1), mg.ovf_ts])
    marker = jnp.concatenate([mg.seg_marker[row_c].reshape(-1),
                              mg.ovf_marker])
    prop = jnp.concatenate([mg.seg_prop[row_c].reshape(-1), mg.ovf_prop])
    return qid, dst, ts, marker, prop


def backbone_stream(mg: MemGraphState):
    """One MemGraph tier as a read-spine stream (rid = -1: always visible).

    The sealed-tier handoff: a tier frozen by the flush rotate enters the
    shared per-state read spine through this function, flattened and sorted
    into (src, dst, ts) order once.  Arrival-ordered, so this stream (alone)
    pays a per-tier record sort (``csr.lexsort_order``); invalid slots
    already carry src == INVALID_VID and sort to the tail."""
    src, dst, ts, marker, prop, _n = flush_arrays(mg)
    return _stream_in_order(lexsort_order(src, dst, ts),
                            src, dst, ts, marker, prop)


@jax.jit
def _stream_in_order(order, src, dst, ts, marker, prop):
    rid = jnp.full(src.shape, -1, jnp.int32)
    return (src[order], dst[order], ts[order], rid,
            marker[order], prop[order])


def memgraph_should_flush(mg: MemGraphState, cfg: StoreConfig) -> bool:
    """Host-side flush trigger (paper: MemGraph reaches capacity)."""
    return bool(
        int(mg.ne) >= cfg.mem_edges
        or int(mg.n_rows) >= cfg.n_segments - cfg.batch_cap
        or int(mg.ovf_n) >= cfg.ovf_cap - cfg.batch_cap
        or int(mg.n_rows) >= int(0.7 * cfg.hash_slots)
    )
