"""Multi-level index (paper §4.2.2) + vertex-grained min-readable-fid (§4.3).

Dense variant (default on TPU): int32[V, L] file-id and offset arrays — one
gather per vertex per level, the paper's "O(1) memory I/O" read path.  The
paper's 2-slot + 4 KB page-set compressed variant is implemented in
`CompactIndex` (host-side) for the space benchmark and fidelity tests.

Functional-update note (DESIGN.md §4): readers pin an immutable index-array
reference at snapshot time, so the paper's vertex-grained read-write locks are
replaced by structural immutability; the same mid-compaction visibility rules
(Example 3) hold and are unit-tested.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .types import INVALID_VID, BYTES_PER_INDEX_ENTRY


class IndexState(NamedTuple):
    """Dense multi-level index.

    Column c of lvl_fid/lvl_off corresponds to level c+1 (L0 has no per-vertex
    offsets — its runs are probed via min/first fid, exactly the paper).
    """

    l0_first_fid: jnp.ndarray   # int32[V] — first L0 file containing v
    l0_min_fid: jnp.ndarray     # int32[V] — minimum *readable* L0 fid (§4.3)
    lvl_fid: jnp.ndarray        # int32[V, L] — INVALID_VID = absent
    lvl_off: jnp.ndarray        # int32[V, L]


def empty_index(vmax: int, n_levels: int) -> IndexState:
    return IndexState(
        l0_first_fid=jnp.full((vmax,), INVALID_VID, jnp.int32),
        l0_min_fid=jnp.zeros((vmax,), jnp.int32),
        lvl_fid=jnp.full((vmax, n_levels), INVALID_VID, jnp.int32),
        lvl_off=jnp.zeros((vmax, n_levels), jnp.int32),
    )


@jax.jit
def note_l0_flush(idx: IndexState, vkeys: jnp.ndarray, nv: jnp.ndarray,
                  fid: jnp.ndarray) -> IndexState:
    """After a MemGraph flush lands at L0 with file `fid`: record the first
    L0 file per contained vertex (filters invalid random reads, Fig 8)."""
    vmax = idx.l0_first_fid.shape[0]
    valid = jnp.arange(vkeys.shape[0]) < nv
    safe = jnp.where(valid, vkeys, vmax)
    return idx._replace(
        l0_first_fid=idx.l0_first_fid.at[safe].min(fid, mode="drop"))


@functools.partial(jax.jit, static_argnames=("level",))
def note_compaction(
    idx: IndexState,
    *,
    level: int,                 # target level (>= 1)
    new_vkeys: jnp.ndarray,     # int32[Vc] vertices in the merged output
    new_voff: jnp.ndarray,      # int32[Vc+1]
    new_nv: jnp.ndarray,
    new_fid: jnp.ndarray,
    range_lo: jnp.ndarray,      # target vertex range [lo, hi) the output covers
    range_hi: jnp.ndarray,
    src_lo: jnp.ndarray,        # compacted source vertex range [lo, hi)
    src_hi: jnp.ndarray,        # (empty: no source level to clear)
    l0_min_fid_update: jnp.ndarray,  # max L0 fid involved + 1; -1 = not an L0 compaction
) -> IndexState:
    """Index maintenance after compaction into `level` (paper §4.2.2/§4.3).

    1. Vertices in the SOURCE range lose their source-level entries:
       - L0 source: min-readable-fid := max involved fid + 1 and first-fid
         cleared (whole-L0 compactions, paper rule);
       - L_{level-1} source: its column cleared.
       An output segment may reach past the source range (it absorbed a
       wider overlapping target segment); source-level segments there were
       not compacted and keep their entries.
    2. Vertices in the merged output gain (fid, offset) at `level`.
    3. Vertices in the target range but absent from the output (fully
       annihilated) are cleared at `level` too — handled by clearing the
       whole range first.
    """
    vmax = idx.l0_first_fid.shape[0]
    allv = jnp.arange(vmax, dtype=jnp.int32)
    in_range = (allv >= range_lo) & (allv < range_hi)
    in_src = (allv >= src_lo) & (allv < src_hi)

    l0_min = idx.l0_min_fid
    l0_first = idx.l0_first_fid
    is_l0 = l0_min_fid_update >= 0
    l0_min = jnp.where(is_l0 & in_src,
                       jnp.maximum(l0_min, l0_min_fid_update), l0_min)
    l0_first = jnp.where(is_l0 & in_src, INVALID_VID, l0_first)

    lvl_fid, lvl_off = idx.lvl_fid, idx.lvl_off
    if level >= 2:
        src_col = level - 2
        lvl_fid = lvl_fid.at[:, src_col].set(
            jnp.where(in_src, INVALID_VID, lvl_fid[:, src_col]))
    tgt_col = level - 1
    # Clear the full range at the target, then write the surviving vertices.
    lvl_fid = lvl_fid.at[:, tgt_col].set(
        jnp.where(in_range, INVALID_VID, lvl_fid[:, tgt_col]))
    valid = jnp.arange(new_vkeys.shape[0]) < new_nv
    safe = jnp.where(valid, new_vkeys, vmax)
    lvl_fid = lvl_fid.at[safe, tgt_col].set(new_fid, mode="drop")
    lvl_off = lvl_off.at[safe, tgt_col].set(new_voff[:-1], mode="drop")
    return IndexState(l0_first_fid=l0_first, l0_min_fid=l0_min,
                      lvl_fid=lvl_fid, lvl_off=lvl_off)


@jax.jit
def lookup(idx: IndexState, v: jnp.ndarray):
    """Positions of vertex v's edges on every level: O(1) memory I/O each —
    the multi-level-index read path (vs. per-run binary search)."""
    return (idx.l0_first_fid[v], idx.l0_min_fid[v],
            idx.lvl_fid[v], idx.lvl_off[v])


@jax.jit
def lookup_batch(idx: IndexState, vs: jnp.ndarray):
    """Multi-level index positions for a whole query vector in 4 gathers:
    (l0_first[B], l0_min[B], lvl_fid[B, L], lvl_off[B, L]).  This is the
    batched read path's one-shot index resolution — per-vertex `lookup`
    dispatches collapse into a single jit'd gather set.  Pad queries
    (INVALID_VID) clip to the LAST row and return that row's (arbitrary)
    data; callers MUST mask pad slots out by qid, never rely on them."""
    v_c = jnp.minimum(vs, idx.l0_first_fid.shape[0] - 1)
    return (idx.l0_first_fid[v_c], idx.l0_min_fid[v_c],
            idx.lvl_fid[v_c], idx.lvl_off[v_c])


def index_nbytes_dense(vmax: int, n_levels: int) -> int:
    return vmax * (2 + 2 * n_levels) * BYTES_PER_INDEX_ENTRY


# ---------------------------------------------------------------------------
# Compact 2-slot + page-set variant (paper Fig. 8) — host-side reference.
# ---------------------------------------------------------------------------

_PAGE_BYTES = 4096
_ENTRY_BYTES = 12  # (level:2, fid:4, off:4) padded


class CompactIndex:
    """The paper's compressed index: per-vertex array rows hold the L0 first
    fid + up to two inline (level, fid, off) positions; extra positions spill
    into 4 KB pages allocated per contiguous vertex interval (split-in-half on
    overflow, merge-on-shrink)."""

    def __init__(self, vmax: int, interval: int = 1024):
        self.vmax = vmax
        self.interval = interval
        self.l0_first = np.full(vmax, INVALID_VID, np.int64)
        self.l0_min = np.zeros(vmax, np.int64)
        self.slots: List[Dict[int, Tuple[int, int]]] = [dict() for _ in range(vmax)]
        # page directory: vertex -> page id; pages: id -> dict v -> {lvl: (fid, off)}
        self._pages: Dict[int, Dict[int, Dict[int, Tuple[int, int]]]] = {}
        self._page_of: Dict[int, int] = {}
        self._next_page = 0

    # -- write path ---------------------------------------------------------
    def set_position(self, v: int, level: int, fid: int, off: int) -> None:
        row = self.slots[v]
        if level in row or len(row) < 2:
            row[level] = (fid, off)
            return
        # Spill the largest-level inline entry to the page set (bottom levels
        # hold 99 % of edges — keep hot low levels inline, paper intuition).
        pid = self._page_for(v)
        spill_lvl = max(row)
        if level < spill_lvl:
            self._pages[pid].setdefault(v, {})[spill_lvl] = row.pop(spill_lvl)
            row[level] = (fid, off)
        else:
            self._pages[pid].setdefault(v, {})[level] = (fid, off)
        self._maybe_split(pid)

    def clear_position(self, v: int, level: int) -> None:
        self.slots[v].pop(level, None)
        pid = self._page_of.get(v // self.interval)
        if pid is not None:
            entry = self._pages[pid].get(v)
            if entry:
                entry.pop(level, None)

    # -- read path ----------------------------------------------------------
    def get_positions(self, v: int) -> Dict[int, Tuple[int, int]]:
        out = dict(self.slots[v])
        pid = self._page_of.get(v // self.interval)
        if pid is not None:
            out.update(self._pages[pid].get(v, {}))
        return out

    # -- pages ---------------------------------------------------------------
    def _page_for(self, v: int) -> int:
        key = v // self.interval
        if key not in self._page_of:
            self._page_of[key] = self._next_page
            self._pages[self._next_page] = {}
            self._next_page += 1
        return self._page_of[v // self.interval]

    def _maybe_split(self, pid: int) -> None:
        # 4 KB page capacity in entries; split vertex intervals on overflow
        # (paper splits the one interval in half; we halve the global interval
        # and rehash — an upper bound on page count, same asymptotics).
        n_entries = sum(len(m) for m in self._pages[pid].values())
        if n_entries * _ENTRY_BYTES <= _PAGE_BYTES or self.interval <= 1:
            return
        self.interval //= 2
        old_pages = self._pages
        self._pages, self._page_of, self._next_page = {}, {}, 0
        for page in old_pages.values():
            for v, entry in page.items():
                npid = self._page_for(v)
                self._pages[npid][v] = entry

    def nbytes(self) -> int:
        inline = self.vmax * (8 + 2 * _ENTRY_BYTES + 8)
        return inline + len(self._pages) * _PAGE_BYTES
