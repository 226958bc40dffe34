"""LSMGraph store facade (paper §3.2 workflow, §4.2 multi-level CSR).

Host-side orchestration over jit'd array ops:

  write path:   insert/delete batches -> MemGraph (double-buffered) ->
                flush to an L0 CSR run -> whole-L0 compaction into L1 ->
                partial (per-segment-file) compaction L_i -> L_{i+1}
  read path:    Snapshot pins (version, index arrays, run refs, τ);
                neighbors() merges MemGraph + L0 runs (>= min readable fid)
                + one CSR segment per L1+ level via the multi-level index,
                with timestamp masking and tombstone annihilation.

Every level holds an ordered list of CSR segment *files* with disjoint vertex
ranges (L0: overlapping, ordered by fid) — the paper's segmentation — so
partial compaction replaces only overlapping segment files.

Concurrency: ALL mutable store state lives in one immutable, atomically-
published ``StoreState`` (epoch publication — see the "Concurrency model"
doc in ``repro.core.__init__``).  Writers build the next state off to the
side and install it with a single reference swap under a short host-only
commit lock; ``snapshot()`` is a lock-free read of the current state, and
every snapshot at the same sealed epoch shares one ``_ReadBackbone`` via
the state's ``_SpineHandle``.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import csr, filters, index as mlindex, memgraph as mg_mod
from .. import obs
from ..kernels import ops as kops
from ..kernels.merge import MERGE_STATS as _MERGE_STATS
from .types import (BYTES_PER_EDGE, BYTES_PER_PROP, INVALID_VID, EdgeBatch,
                    IOCounters, MemGraphState, RunFile, StoreConfig, Version)
from .versions import VersionChain


def _np(x) -> np.ndarray:
    return np.asarray(x)


# 0 disables the tournament-merged read backbone entirely (every resolve
# then takes the legacy concat-then-lexsort path) — an escape hatch, not a
# tuning knob.
_READ_TOURNAMENT_MAX_K = int(os.environ.get("LSMG_READ_TOURNAMENT_K", "8"))


def _read_filters_enabled() -> bool:
    """Per-run presence-filter gating on the read path.  Read PER RESOLVE
    (not cached at import) so the filters-on/off equivalence tests and the
    depth-sweep bench can flip ``LSMG_READ_FILTERS`` mid-process.  Filters
    only ever REMOVE provably-absent (run, query) pairs, so the results
    are byte-identical either way — 0 is an ablation lever, not a
    correctness escape hatch."""
    return os.environ.get("LSMG_READ_FILTERS", "1") not in (
        "0", "false", "False")

# Shared background pool for cold-segment loads: prefetch submissions from
# the read path overlap disk I/O with device dispatch.  Process-wide and
# created lazily, so import stays cheap and pure in-memory stores never
# spawn threads.  Deliberately NARROW by default: a segment load is partly
# CPU work (CRC, array conversion), so on small hosts extra loader threads
# fight the XLA compute pool instead of overlapping it — one background
# loader + the foreground thread already forms the two-stage pipeline.
_PREFETCH_WORKERS = int(os.environ.get(
    "LSMG_PREFETCH_WORKERS",
    str(max(1, min(4, (os.cpu_count() or 2) - 1)))))
_PREFETCH_POOL: Optional[ThreadPoolExecutor] = None
_PREFETCH_POOL_LOCK = threading.Lock()

# Default per-process store ordinal for metric labels: each LSMGraph gets a
# bounded-cardinality ``store="s<N>"`` label unless the caller names it.
_STORE_ORDINAL = itertools.count()


def prefetch_pool() -> ThreadPoolExecutor:
    global _PREFETCH_POOL
    if _PREFETCH_POOL is None:
        with _PREFETCH_POOL_LOCK:
            if _PREFETCH_POOL is None:
                _PREFETCH_POOL = ThreadPoolExecutor(
                    max_workers=_PREFETCH_WORKERS,
                    thread_name_prefix="lsm-prefetch")
    return _PREFETCH_POOL


@dataclasses.dataclass(frozen=True, eq=False)
class StoreState:
    """One immutable, atomically-published store state.

    The epoch-publication recipe: a commit builds every field off to the
    side and installs the next ``StoreState`` with a single reference swap
    (atomic under the GIL), so a reader that grabs ``store._state`` holds a
    complete, internally-consistent view forever — no locks on the read
    path.  ``runs_by_fid`` is a plain dict but is NEVER mutated after
    publication (commits build a fresh dict).  ``spine`` is the state's
    shared, lazily-built read backbone: per-batch writes reuse the previous
    handle (the active MemGraph is resolved outside the spine), while
    sealed-membership changes — flush rotate/commit, compaction commit,
    health change, recovery install — publish a fresh one."""

    epoch: int
    tau: int
    mem: MemGraphState
    mem_id: int
    mem_full: Optional[MemGraphState]
    mem_full_id: Optional[int]
    levels: Tuple[Tuple[RunFile, ...], ...]
    index: object                     # mlindex arrays (immutable jnp)
    runs_by_fid: Dict[int, RunFile]   # frozen-by-convention after publish
    version: Version
    degraded: tuple                   # DegradedRange tuple at publish time
    spine: "_SpineHandle"


@dataclasses.dataclass(frozen=True, eq=False)
class _RunSpine:
    """The merged SEALED-RUN portion of a read spine: every L0/L1+ run's
    records tournament-merged into one (src, dst, ts)-ordered stream, with
    ``rid`` = the record's position in ``runs``.  Cached store-wide
    (`_SpineCache`) so consecutive sealed epochs splice instead of
    re-merging the world.  ``cols`` are fitted to the half-step quantized
    capacity; valid records form a sorted ``total``-length prefix (pads
    carry src == INVALID_VID and sort to the tail)."""

    fids: frozenset
    runs: Tuple[Tuple[RunFile, int], ...]   # rid order; col < 0 means L0
    cols: tuple                             # (src,dst,ts,rid,marker,prop)
    total: int


def _fit_spine_cols(cols, total: int):
    """Pad or trim merged spine columns to the half-step quantized capacity
    (valid records are a sorted prefix, so trimming only drops pads)."""
    cap = csr.quantize_cap(total, half_steps=True)
    n = int(cols[0].shape[0])
    if n < cap:
        return _pad_backbone(*cols, pad=cap - n)
    if n > cap:
        return tuple(c[:cap] for c in cols)
    return tuple(cols)


def _spine_run_streams(runs, rid_base: int = 0):
    """Per-run backbone streams (prefetching cold segments first)."""
    pool = None
    for rf, _col in runs:
        if rf.arrays is None:
            pool = pool or prefetch_pool()
            rf.prefetch(pool)
    return [_run_backbone_stream(rf.ensure_loaded(),
                                 jnp.asarray(rid_base + i, jnp.int32))
            for i, (rf, _col) in enumerate(runs)]


def _build_run_spine(runs) -> _RunSpine:
    """From-scratch merge of a sealed run set (the cold-cache path)."""
    runs = tuple(runs)
    if not runs:
        z = jnp.zeros((0,), jnp.int32)
        cols = (z, z, z, z, jnp.zeros((0,), bool),
                jnp.zeros((0,), jnp.float32))
        return _RunSpine(frozenset(), (), cols, 0)
    total = sum(rf.ne for rf, _col in runs)
    cols = kops.tournament_merge(_spine_run_streams(runs))
    _MERGE_STATS.bump("spine_build")
    return _RunSpine(frozenset(rf.fid for rf, _col in runs), runs,
                     _fit_spine_cols(cols, total), total)


@functools.partial(jax.jit, static_argnames=("out_cap",))
def _filter_remap_spine(src, dst, ts, rid, marker, prop, rid_map,
                        out_cap: int):
    """Compress a spine's retained records (rid_map[rid] >= 0) into a dense
    sorted prefix with remapped rids — the kept side of a splice.  The
    gather preserves order, so the result is still (src, dst, ts)-sorted."""
    n = src.shape[0]
    rid_c = jnp.clip(rid, 0, rid_map.shape[0] - 1)
    new_rid = jnp.where(rid >= 0, rid_map[rid_c], -1)
    keep = (src != INVALID_VID) & (new_rid >= 0)
    idx = jnp.nonzero(keep, size=out_cap, fill_value=n)[0]
    idx_c = jnp.minimum(idx, n - 1)
    ok = idx < n
    return (jnp.where(ok, src[idx_c], INVALID_VID),
            jnp.where(ok, dst[idx_c], 0),
            jnp.where(ok, ts[idx_c], 0),
            jnp.where(ok, new_rid[idx_c], -1),
            jnp.where(ok, marker[idx_c], False),
            jnp.where(ok, prop[idx_c], 0.0))


def _splice_run_spine(base: _RunSpine, runs) -> _RunSpine:
    """Incremental spine invalidation: splice a changed run set into an
    existing merged spine.  Runs surviving from ``base`` keep their
    already-merged relative order (one jit'd compress + rid remap); only
    the ADDED runs' streams enter a fresh tournament against that retained
    stream — re-merge the delta, never the world.  Because every record
    carries a globally-unique ts, the merged (src, dst, ts) order is
    independent of merge-tree shape: a spliced spine's valid prefix is
    byte-identical to a from-scratch build's (rid numbering aside)."""
    runs = tuple(runs)
    new_fids = {rf.fid for rf, _col in runs}
    kept = [(rf, col) for (rf, col) in base.runs if rf.fid in new_fids]
    kept_fids = {rf.fid for rf, _col in kept}
    added = [(rf, col) for (rf, col) in runs if rf.fid not in kept_fids]
    pos = {rf.fid: i for i, (rf, _col) in enumerate(base.runs)}
    rid_map = np.full(max(len(base.runs), 1), -1, np.int32)
    for new_i, (rf, _col) in enumerate(kept):
        rid_map[pos[rf.fid]] = new_i
    retained_total = sum(rf.ne for rf, _col in kept)
    out_cap = csr.quantize_cap(max(retained_total, 1))
    retained = _filter_remap_spine(*base.cols, jnp.asarray(rid_map),
                                   out_cap=out_cap)
    streams = [retained] + _spine_run_streams(added, rid_base=len(kept))
    cols = kops.tournament_merge(streams)
    total = retained_total + sum(rf.ne for rf, _col in added)
    _MERGE_STATS.bump("spine_splice")
    return _RunSpine(frozenset(new_fids), tuple(kept + added),
                     _fit_spine_cols(cols, total), total)


class _SpineCache:
    """Store-level cache of recently merged run spines, keyed by fid set.

    ``get`` serves three cases: identical fid set -> reuse outright;
    overlapping set -> splice the delta; disjoint/cold -> from-scratch
    build.  Generation-aware, TWO slots (newest first): a snapshot pinned
    just before a flush/compaction commit still resolves against the
    PREVIOUS sealed epoch — with one slot, the new epoch's spine evicts
    it, and the old snapshot's next resolve forces a full splice/rebuild
    (and then evicts the new epoch right back: cache ping-pong).  Keeping
    one generation of history lets both epochs' snapshots hit.  The
    splice base is the cached spine with the LARGEST fid overlap (ties ->
    newest).  Guarded by its own mutex — never a store writer lock, so a
    reader building here can only wait on a peer reader."""

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._slots: List[_RunSpine] = []   # newest-first, len <= 2

    def get(self, runs) -> _RunSpine:
        runs = tuple(runs)
        fids = frozenset(rf.fid for rf, _col in runs)
        with self._mu:
            for cached in self._slots:
                if cached.fids == fids:
                    _MERGE_STATS.bump("spine_reuse")
                    return cached
            base: Optional[_RunSpine] = None
            best = 0
            if fids:
                for cached in self._slots:
                    overlap = len(cached.fids & fids)
                    if overlap > best:
                        best, base = overlap, cached
            if base is not None:
                spine = _splice_run_spine(base, runs)
            else:
                spine = _build_run_spine(runs)
            if fids or not self._slots:
                self._slots = ([spine] + self._slots)[:2]
            return spine


class _SpineHandle:
    """Lazily-built read backbone shared by EVERY snapshot at one sealed
    epoch.  Built at most once under a handle-local build latch that no
    writer ever takes — a reader blocking here waits only on a peer
    reader's in-flight build, never on a writer-held store lock — and
    assigned only after full construction, so the old per-Snapshot
    double-checked-locking race (a half-warm backbone becoming visible)
    disappears structurally."""

    __slots__ = ("_mu", "_bb")

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._bb: Optional["_ReadBackbone"] = None

    def ready(self) -> bool:
        return self._bb is not None

    def get(self, state: StoreState, store: "LSMGraph") -> "_ReadBackbone":
        bb = self._bb
        if bb is None:
            with self._mu:
                bb = self._bb
                if bb is None:
                    bb = _build_state_backbone(state, store)
                    self._bb = bb
        return bb


def _build_state_backbone(state: StoreState, store: "LSMGraph"):
    """Merge the state's SEALED tiers (L0/L1+ runs via the store's spine
    cache, plus the rotated-out full MemGraph) into the shared read spine.
    The ACTIVE MemGraph is deliberately absent: it is resolved per query
    batch (`_mem_resolve`) and, by the ts tier-dominance invariant (every
    active record is strictly newer than every sealed record), its visible
    (src, dst) pairs simply suppress the sealed winners — so per-batch
    writes never invalidate this spine.  Runs quarantined at publish time
    (``state.degraded``) are excluded; overlapping queries raise typed
    errors via the snapshot's degraded check instead."""
    bad = {r.fid for r in state.degraded}
    runs: List[Tuple[RunFile, int]] = []
    for rf in state.levels[0]:
        if rf.nv > 0 and rf.fid not in bad:
            runs.append((rf, -1))
    for col, lvl in enumerate(state.levels[1:]):
        for rf in lvl:
            if rf.nv > 0 and rf.fid not in bad:
                runs.append((rf, col))
    spine = store._spine_cache.get(runs)
    cols, total = spine.cols, spine.total
    mem_full = state.mem_full
    if mem_full is not None and int(mem_full.ne) != 0:
        # The sealed-tier handoff: the frozen full MemGraph rides the spine
        # (rid = -1, always visible) until its flush commit retires it.
        total = total + int(mem_full.ne)
        mem_stream = mg_mod.backbone_stream(mem_full)
        if spine.total == 0:
            # Rotate-published state with no runs yet (or all quarantined):
            # the mem stream IS the spine — merging against the zero-length
            # run columns would dispatch an empty-operand gather.
            cols = _fit_spine_cols(mem_stream, total)
        else:
            cols = kops.tournament_merge([mem_stream, tuple(cols)])
            cols = _fit_spine_cols(cols, total)
    src, d, t, rid, m, p = cols
    fwords, fmasks = store._place(_stack_presence(spine.runs))
    return _ReadBackbone(src, d, t, rid, m, p, _np(d), _np(p),
                         list(spine.runs), fwords, fmasks)


def _stack_presence(runs):
    """Stack the per-run presence filters into one device-resident
    (uint32[R, W] words, uint32[R] masks) pair for the vectorized batched
    membership test.  Rows are padded to the widest filter; a run WITHOUT
    a filter (pre-v2 segment) gets an all-ones row — every probe hits, so
    it degrades to "always maybe" exactly like the scalar path's
    ``presence is None`` case.  W stays a power of two (max over
    power-of-two word counts), so the all-ones mask W*32-1 is valid."""
    filts = [rf.presence for rf, _col in runs]
    if not filts or all(f is None for f in filts):
        return None, None
    width = max(f.words.shape[0] for f in filts if f is not None)
    mat = np.empty((len(filts), width), np.uint32)
    masks = np.empty(len(filts), np.uint32)
    for i, f in enumerate(filts):
        if f is None:
            mat[i] = np.uint32(0xFFFFFFFF)
            masks[i] = width * 32 - 1
        else:
            nw = f.words.shape[0]
            mat[i, :nw] = f.words
            mat[i, nw:] = 0   # masked off: positions never exceed mbits-1
            masks[i] = f.mbits - 1
    return jnp.asarray(mat), jnp.asarray(masks)


class LSMGraph:
    """Dynamic graph store: LSM-tree level structure over CSR runs.

    Lock roster (see the core package doc for the full protocol):

    * ``_lock`` — the COMMIT lock: short, host-only read-modify-write of
      ``self._state`` (plus WAL append / ts assignment).  Never held
      across device work; never taken by readers.
    * ``_write_lock`` — serializes MemGraph writers (apply chunks + the
      flush rotate); device-side inserts happen under it, outside
      ``_lock``.
    * ``_flush_lock`` — serializes flush pipelines and level/index
      mutation (compaction commits take it too).
    * ``_compact_lock`` — serializes whole compactions.
    * ``_fid_lock`` — fid allocation (flush and resegment race otherwise).

    Order: ``_compact_lock`` > ``_flush_lock`` > ``_write_lock`` >
    ``_lock`` (> ``versions._lock``); any prefix may be skipped, never
    reordered."""

    def __init__(self, cfg: StoreConfig, durability=None,
                 obs_label: Optional[str] = None, device=None):
        cfg.validate()
        self.cfg = cfg
        # Device that holds this store's arrays (None = JAX's default).
        # The roots of the device state (MemGraph tiers, index, stacked
        # filters) are committed to it; every run, spine and index array
        # is computed from them and so stays on it.
        self.device = device
        # Optional durability engine (repro.storage.DurableStorage): WAL /
        # segment-file / manifest hooks.  None = in-memory store (seed mode).
        self.durability = durability
        self._lock = threading.RLock()
        self._write_lock = threading.RLock()   # serializes MemGraph writers
        self._flush_lock = threading.RLock()   # serializes flush pipelines
        self._compact_lock = threading.RLock()  # serializes compactions
        self._fid_lock = threading.Lock()
        self.versions = VersionChain()
        # Observability: one label per store instance; instruments are
        # resolved once here so hot paths touch cached references only.
        self.obs_label = obs_label or f"s{next(_STORE_ORDINAL)}"
        self.io = IOCounters().bind(store=self.obs_label)
        self._obs_apply = obs.histogram("store_apply_seconds",
                                        store=self.obs_label)
        self._obs_resolve = obs.histogram("read_resolve_seconds",
                                          store=self.obs_label)
        self._obs_publish = obs.counter("store_state_publish_total",
                                        store=self.obs_label)
        # NOTE: the L0-depth / runs-per-level GAUGES are deliberately not
        # cached — empty levels get their series removed at commit time
        # (see _obs_update_level_gauges), and a cached reference would keep
        # writing to an orphaned instrument the exporters no longer see.
        # Amplification-ledger feeders (obs.amplification): logical ingest
        # volume and read-path work, all plain counters on the hot path.
        self._obs_ingest_bytes = obs.counter("store_logical_ingest_bytes",
                                             store=self.obs_label)
        self._obs_edges_ins = obs.counter("store_edges_inserted_total",
                                          store=self.obs_label)
        self._obs_edges_del = obs.counter("store_edges_deleted_total",
                                          store=self.obs_label)
        self._obs_read_queries = obs.counter("read_queries_total",
                                             store=self.obs_label)
        self._obs_read_probes = obs.counter("read_runs_probed_total",
                                            store=self.obs_label)
        self._obs_read_returned = obs.counter("read_returned_bytes",
                                              store=self.obs_label)
        # Presence-filter telemetry (tentpole of PR 10): checked = (run,
        # query) pairs tested, skipped = pairs the filter proved absent,
        # false_positive = filter said "maybe" but the gather found
        # nothing (scalar path only — the one place a miss is observable).
        self._obs_filter_checked = obs.counter("read_filter_checked_total",
                                               store=self.obs_label)
        self._obs_filter_skipped = obs.counter("read_filter_skipped_total",
                                               store=self.obs_label)
        self._obs_filter_fp = obs.counter(
            "read_filter_false_positive_total", store=self.obs_label)
        self.on_flush_needed = None  # callback for the concurrent wrapper
        self._ts = 0
        self._next_fid = 0
        self._next_mem_id = 1
        self._spine_cache = _SpineCache()
        version = self.versions.publish((0,), (), 0)
        self._state = StoreState(
            epoch=0, tau=0, mem=self._empty_memgraph(), mem_id=0,
            mem_full=None, mem_full_id=None,
            levels=tuple(() for _ in range(cfg.n_levels)),
            index=self._place(mlindex.empty_index(cfg.vmax, cfg.n_levels)),
            runs_by_fid={}, version=version, degraded=(),
            spine=_SpineHandle())
        if durability is not None:
            durability.attach(self)

    # ------------------------------------------------------------------ util
    def _place(self, tree):
        """Commit a pytree of fresh arrays to the store's device."""
        return tree if self.device is None else jax.device_put(
            tree, self.device)

    def _empty_memgraph(self) -> MemGraphState:
        return self._place(mg_mod.empty_memgraph(self.cfg))

    @property
    def state(self) -> StoreState:
        """The current published state — one atomic reference read."""
        return self._state

    # Read-only views of the published state: legacy call sites (tests,
    # benchmarks, the storage engine) keep reading `store.levels` etc.;
    # all mutation goes through state publication.
    @property
    def mem(self) -> MemGraphState:
        return self._state.mem

    @property
    def mem_id(self) -> int:
        return self._state.mem_id

    @property
    def mem_full(self) -> Optional[MemGraphState]:
        return self._state.mem_full

    @property
    def mem_full_id(self) -> Optional[int]:
        return self._state.mem_full_id

    @property
    def levels(self) -> Tuple[Tuple[RunFile, ...], ...]:
        return self._state.levels

    @property
    def index(self):
        return self._state.index

    @property
    def runs_by_fid(self) -> Dict[int, RunFile]:
        return self._state.runs_by_fid

    def _swap_state(self, **fields) -> StoreState:
        """Install the next StoreState (epoch + caller-precomputed fields).
        Caller holds ``_lock``; every expensive value is computed before
        entering it — this is a host-only read-modify-write."""
        cur = self._state
        nxt = dataclasses.replace(cur, epoch=cur.epoch + 1, **fields)
        self._state = nxt
        self._obs_publish.inc()  # host-only: safe under the commit lock
        return nxt

    def _obs_update_level_gauges(self,
                                 levels: Tuple[Tuple[RunFile, ...], ...]
                                 ) -> None:
        """Refresh the L0-depth / runs-per-level gauges after a membership
        commit (flush, compaction, recovery, empty-run drop).  Off the
        commit lock: callers pass the levels tuple they just published.

        A level that just emptied gets its series REMOVED, not set to 0:
        a full compaction that drains L0 (or annihilates a whole level)
        would otherwise leave the dead series in every export forever.
        Cold path (one commit per flush/compaction), so gauges are
        get-or-created here instead of cached at construction."""
        reg = obs.REGISTRY
        if levels[0]:
            obs.gauge("store_l0_depth", store=self.obs_label).set(
                len(levels[0]))
        else:
            reg.remove("store_l0_depth", store=self.obs_label)
        for i, lvl in enumerate(levels):
            if lvl:
                obs.gauge("store_level_runs", store=self.obs_label,
                          level=str(i)).set(len(lvl))
            else:
                reg.remove("store_level_runs", store=self.obs_label,
                           level=str(i))

    def note_health_change(self) -> None:
        """Republish after a quarantine or heal: the next state carries the
        live degraded set and a FRESH spine handle, so spines built from
        here on exclude (or re-include) the affected segments.  Called by
        the storage engine off the serving path."""
        deg = self.degraded_ranges()
        with self._lock:
            self._swap_state(degraded=deg, spine=_SpineHandle())

    def drop_read_spine(self) -> None:
        """Forget every cached merged read view: reset the splice cache and
        publish a fresh (empty) spine handle.  The next snapshot read
        rebuilds from run arrays, paying the lazy disk loads again.  Pairs
        with the storage engine's segment eviction — without this, the
        state-owned spine would keep serving merged copies of evicted
        bytes and the chaos harness's cold-read lever would read warm."""
        self._spine_cache = _SpineCache()
        with self._lock:
            self._swap_state(spine=_SpineHandle())

    def _new_fid(self) -> int:
        with self._fid_lock:
            f = self._next_fid
            self._next_fid += 1
            return f

    @property
    def tau(self) -> int:
        return self._state.tau

    def n_edges_cached(self) -> int:
        return int(self._state.mem.ne)

    # ----------------------------------------------------------------- write
    def insert_edges(self, src, dst, prop=None) -> Optional[int]:
        """Insert a batch.  Durable stores return the WAL commit seq of the
        last appended record (awaitable via ``ack``); in-memory: None."""
        return self._apply(src, dst, prop, delete=False)

    def delete_edges(self, src, dst) -> Optional[int]:
        """Deletion = tombstone record (annihilates at read & compaction).
        Returns the WAL commit seq like ``insert_edges``."""
        return self._apply(src, dst, None, delete=True)

    def _apply_no_flush(self, src, dst, prop, *, delete: bool) -> Optional[int]:
        """Ingest without the inline flush trigger — the concurrent wrapper's
        background compactor owns flush/compaction."""
        return self._apply(src, dst, prop, delete=delete, allow_flush=False)

    def _apply(self, src, dst, prop, *, delete: bool,
               allow_flush: bool = True) -> Optional[int]:
        src = np.asarray(src, np.int32).ravel()
        dst = np.asarray(dst, np.int32).ravel()
        if prop is None:
            prop = np.zeros_like(src, dtype=np.float32)
        else:
            prop = np.asarray(prop, np.float32).ravel()
        bc = self.cfg.batch_cap
        commit_seq: Optional[int] = None
        for off in range(0, len(src), bc):
            s, d, p = src[off:off + bc], dst[off:off + bc], prop[off:off + bc]
            n = len(s)
            if not allow_flush:
                # Backstop for the concurrent wrapper: if the background
                # compactor lags and the cache hits hard capacity, wait.
                deadline = time.time() + 60.0
                while self._mem_hard_full() and time.time() < deadline:
                    if self.on_flush_needed is not None:
                        self.on_flush_needed()
                    time.sleep(0.001)
                if self._mem_hard_full():
                    raise RuntimeError(
                        "background flush did not relieve a hard-full "
                        "MemGraph within 60 s")
            marker = np.full(n, delete, bool)
            t_chunk = time.perf_counter()
            with self._write_lock:
                st = self._state
                with self._lock:
                    ts = np.arange(self._ts, self._ts + n, dtype=np.int32)
                    self._ts += n
                    if self.durability is not None:
                        # WAL-before-MemGraph: the batch is logged before it
                        # can become readable; fsync group-commits off-path.
                        commit_seq = self.durability.on_apply(
                            s, d, ts, marker, p)
                # Device-side insert OUTSIDE the commit lock: the functional
                # MemGraph update builds the next tier off to the side
                # (_write_lock keeps it single-writer) and only the
                # reference swap below re-enters _lock.
                new_mem, ok = self._insert_batch(st.mem, s, d, ts, marker, p)
                if not ok:
                    if self.durability is not None:
                        # Keep WAL == acknowledged state: replay must not
                        # resurrect a batch whose insert raised.
                        self.durability.on_apply_abort(int(ts[0]) if n else -1)
                    raise RuntimeError(
                        "MemGraph capacity/hash overflow — raise mem caps")
                if self.cfg.memcache_mode == "array_only":
                    # Charge the compact-array growth movement the ablation
                    # emulates: spilled edges imply copying the vertex's edges.
                    self.io.flush_write += n  # nominal movement charge
                with self._lock:
                    # tau advances ONLY with a mem publish — every other
                    # commit keeps the tau of the content it carries.
                    self._swap_state(mem=new_mem, tau=self._ts)
            self._obs_apply.observe(time.perf_counter() - t_chunk)
            # Amplification-ledger denominator: logical bytes the caller
            # handed us (20 B/edge record), counted once per accepted chunk.
            self._obs_ingest_bytes.inc(n * (BYTES_PER_EDGE + BYTES_PER_PROP))
            (self._obs_edges_del if delete else self._obs_edges_ins).inc(n)
            if allow_flush and mg_mod.memgraph_should_flush(
                    self._state.mem, self.cfg):
                self.flush_memgraph()
        return commit_seq

    def _insert_batch(self, mem: MemGraphState, s, d, t, m, p):
        """Pad one <= batch_cap chunk into an EdgeBatch and insert it into
        the given MemGraph tier, returning ``(new_mem, ok)``.  Functional:
        the caller publishes the returned tier.  Runs under ``_write_lock``
        (single writer), never under the commit lock.  Shared by the live
        write path (store-assigned ts) and WAL replay (original ts)."""
        bc = self.cfg.batch_cap
        batch = EdgeBatch(
            src=jnp.asarray(_pad(s, bc)),
            dst=jnp.asarray(_pad(d, bc)),
            ts=jnp.asarray(_pad(t, bc)),
            prop=jnp.asarray(_pad(p, bc)),
            marker=jnp.asarray(_pad(m, bc)),
            n=jnp.asarray(len(s), jnp.int32),
        )
        new_mem, ok = mg_mod.insert_batch(
            mem, batch, mode=self.cfg.memcache_mode)
        return new_mem, bool(ok)

    def _ingest_replay(self, src, dst, ts, marker, prop) -> None:
        """Recovery-only ingest: re-insert WAL records with their ORIGINAL
        timestamps (no WAL re-append — the records are already on disk).
        Flushes triggered here follow the normal durable path, advancing the
        WAL floor as they land."""
        src = np.asarray(src, np.int32).ravel()
        dst = np.asarray(dst, np.int32).ravel()
        ts = np.asarray(ts, np.int32).ravel()
        marker = np.asarray(marker, bool).ravel()
        prop = np.asarray(prop, np.float32).ravel()
        bc = self.cfg.batch_cap
        for off in range(0, len(src), bc):
            s, d = src[off:off + bc], dst[off:off + bc]
            t, m, p = ts[off:off + bc], marker[off:off + bc], prop[off:off + bc]
            with self._write_lock:
                st = self._state
                with self._lock:
                    self._ts = max(self._ts, int(t[-1]) + 1)
                new_mem, ok = self._insert_batch(st.mem, s, d, t, m, p)
                if not ok:
                    raise RuntimeError(
                        "MemGraph overflow during WAL replay — raise mem caps")
                with self._lock:
                    self._swap_state(mem=new_mem, tau=self._ts)
            n, nd = len(s), int(np.count_nonzero(m))
            self._obs_ingest_bytes.inc(n * (BYTES_PER_EDGE + BYTES_PER_PROP))
            self._obs_edges_del.inc(nd)
            self._obs_edges_ins.inc(n - nd)
            if mg_mod.memgraph_should_flush(self._state.mem, self.cfg):
                self.flush_memgraph()

    def _mem_hard_full(self) -> bool:
        mem = self._state.mem
        return (
            int(mem.ovf_n) >= self.cfg.ovf_cap - self.cfg.batch_cap
            or int(mem.n_rows) >= self.cfg.n_segments - self.cfg.batch_cap
            or int(mem.n_rows) >= int(0.72 * self.cfg.hash_slots)
        )

    # ----------------------------------------------------------------- flush
    def flush_memgraph(self) -> Optional[RunFile]:
        """MemGraph -> L0 CSR run, written directly without compaction
        (paper: 'directly written to L0'); then maybe L0 compaction.

        The sort/build runs outside every lock writers or readers contend
        on: the full MemGraph is double-buffered and immutable while the
        fresh one takes writes (paper §5.1: 'two MemGraphs alternate').
        The rotate and the commit are each ONE published state swap; both
        seal membership, so both install fresh spine handles."""
        with self._flush_lock:
            if int(self._state.mem.ne) == 0:
                return None
            with obs.REGISTRY.span("store_flush", store=self.obs_label):
                fresh = self._empty_memgraph()  # device, pre-lock
                deg = self.degraded_ranges()
                with self._write_lock:
                    # _write_lock excludes in-flight appliers: self._ts is
                    # exactly the published tau and no WAL record
                    # interleaves between the rotate swap and
                    # on_flush_rotate below.
                    with self._lock:
                        st = self._state
                        if int(st.mem.ne) == 0:
                            return None
                        mem_id = self._next_mem_id
                        self._next_mem_id += 1
                        wal_floor = self._ts  # every record below this ts
                        # is in mem_full or already-flushed runs
                        version = self.versions.publish(
                            (mem_id, st.mem_id),
                            tuple(r.fid for r in st.levels[0]), self._ts)
                        # Rotate double buffer: full MemGraph stays readable.
                        self._swap_state(
                            mem=fresh, mem_id=mem_id, mem_full=st.mem,
                            mem_full_id=st.mem_id, version=version,
                            degraded=deg, spine=_SpineHandle())
                        mem_full = st.mem
                    if self.durability is not None:
                        self.durability.on_flush_rotate(wal_floor)
                obs.REGISTRY.trace_instant("store_flush_rotate",
                                           store=self.obs_label)
                src, dst, ts, marker, prop, n = mg_mod.flush_arrays(mem_full)
                cap = csr.quantize_cap(int(n))
                run = csr.build_run_arrays(src, dst, ts, marker, prop, n,
                                           vcap=cap)
                run = csr.repad_run(run, cap, cap)
                rf = self._wrap(run, level=0)
                # Index update off-lock: _flush_lock (held) is the only
                # serializer of index mutation; apply publishes never touch
                # it.
                new_index = mlindex.note_l0_flush(
                    self._state.index, run.vkeys, run.nv,
                    jnp.asarray(rf.fid, jnp.int32))
                self.io.flush_write += rf.nbytes
                self.io.index_write += int(run.nv) * 8
                # Per-level write-amp numerator (logical movement; durable
                # stores also get the physical mirror in _write_segment).
                obs.counter("store_level_write_bytes", store=self.obs_label,
                            level="0").inc(rf.nbytes)
                new_runs = dict(self._state.runs_by_fid)
                new_runs[rf.fid] = rf
                deg = self.degraded_ranges()
                with self._lock:
                    st = self._state
                    new_levels = (st.levels[0] + (rf,),) + st.levels[1:]
                    version = self.versions.publish(
                        (st.mem_id,),
                        tuple(r.fid for r in new_levels[0]), st.tau)
                    # Flush done: retire the full MemGraph from the state.
                    self._swap_state(
                        levels=new_levels, index=new_index,
                        runs_by_fid=new_runs, mem_full=None,
                        mem_full_id=None, version=version,
                        degraded=deg, spine=_SpineHandle())
                    need_compact = (len(new_levels[0])
                                    >= self.cfg.l0_run_limit)
                self._obs_update_level_gauges(new_levels)
                obs.REGISTRY.trace_instant("store_flush_commit",
                                           store=self.obs_label,
                                           fid=str(rf.fid))
                if self.durability is not None:
                    # Segment write + manifest flush-edit + WAL prune.  On
                    # crash before the manifest edit lands the WAL tail
                    # replays mem_full.
                    self.durability.on_flush_commit(rf, wal_floor=wal_floor)
        if need_compact:
            self.compact_l0()
        return rf

    def _wrap(self, run: csr.CSRRunArrays, level: int) -> RunFile:
        """Materialize a RunFile (fid allocation under its own lock — flush
        and resegment may race).  Registration in ``runs_by_fid`` happens at
        COMMIT time, inside the membership swap that makes the run visible."""
        nv, ne = int(run.nv), int(run.ne)
        if nv > 0:
            vk = _np(run.vkeys[:nv])
            min_v, max_v = int(vk[0]), int(vk[-1])
            presence = filters.from_vkeys(vk)
        else:
            min_v, max_v = 0, -1
            presence = filters.from_vkeys(np.empty(0, np.int64))
        return RunFile(fid=self._new_fid(), level=level, arrays=run,
                       min_vid=min_v, max_vid=max_v, created_ts=self._ts,
                       nv=nv, ne=ne, io=self.io, presence=presence)

    # ------------------------------------------------------------ compaction
    def compact_l0(self) -> None:
        """Whole-L0 compaction (paper: all overlapping L0 CSRs merge in one
        compaction to avoid re-compacting identical ranges).

        The expensive merge runs OUTSIDE the store lock over immutable pinned
        runs; only source selection and the metadata swap lock — so readers
        snapshot freely during compaction (paper §4.3, Fig 18).
        """
        with self._compact_lock:
            # Source selection is a lock-free read of one published state:
            # membership only changes under _flush_lock, which the commit
            # below re-checks by removing selected fids (never "all of L0").
            st = self._state
            l0 = [r for r in st.levels[0] if r.nv > 0]
            l0_all = list(st.levels[0])
            if not l0:
                if l0_all:
                    self._drop_empty_l0(l0_all)
                return
            lo = min(r.min_vid for r in l0)
            hi = max(r.max_vid for r in l0) + 1
            overlap = [r for r in st.levels[1]
                       if r.nv > 0 and r.min_vid < hi and r.max_vid >= lo]
            self._merge_into(sources=l0, overlap=overlap, target_level=1,
                             range_lo=lo, range_hi=hi,
                             l0_max_fid=max(r.fid for r in l0),
                             also_remove=l0_all)
            self._maybe_cascade(1)

    def _drop_empty_l0(self, empties: List[RunFile]) -> None:
        """Publish L0 minus zero-vertex runs (defensive; no record moves)."""
        drop = {r.fid for r in empties}
        with self._flush_lock:
            new_runs = {f: r for f, r in self._state.runs_by_fid.items()
                        if f not in drop}
            with self._lock:
                st = self._state
                new_levels = (tuple(r for r in st.levels[0]
                                    if r.fid not in drop),) + st.levels[1:]
                version = self.versions.publish(
                    (st.mem_id,) + ((st.mem_full_id,)
                                    if st.mem_full_id is not None else ()),
                    tuple(r.fid for r in new_levels[0]), st.tau)
                self._swap_state(levels=new_levels, runs_by_fid=new_runs,
                                 version=version, spine=_SpineHandle())
            self._obs_update_level_gauges(new_levels)

    def compact_partial(self, level: int) -> None:
        """Partial compaction: move ONE segment file of `level` down (paper
        §4.2.1) — only overlapping target segments participate."""
        with self._compact_lock:
            st = self._state
            segs = st.levels[level]
            if not segs:
                return
            src_seg = max(segs, key=lambda r: r.ne)
            lo, hi = src_seg.min_vid, src_seg.max_vid + 1
            overlap = [r for r in st.levels[level + 1]
                       if r.nv > 0 and r.min_vid < hi and r.max_vid >= lo]
            self._merge_into(sources=[src_seg], overlap=overlap,
                             target_level=level + 1, range_lo=lo, range_hi=hi,
                             l0_max_fid=None, also_remove=[src_seg])
            self._maybe_cascade(level + 1)

    def _merge_into(self, *, sources: List[RunFile], overlap: List[RunFile],
                    target_level: int, range_lo: int, range_hi: int,
                    l0_max_fid: Optional[int],
                    also_remove: List[RunFile]) -> None:
        with obs.REGISTRY.span("store_compaction", store=self.obs_label,
                               level=str(target_level)):
            self._merge_into_timed(
                sources=sources, overlap=overlap, target_level=target_level,
                range_lo=range_lo, range_hi=range_hi, l0_max_fid=l0_max_fid,
                also_remove=also_remove)

    def _merge_into_timed(self, *, sources: List[RunFile],
                          overlap: List[RunFile], target_level: int,
                          range_lo: int, range_hi: int,
                          l0_max_fid: Optional[int],
                          also_remove: List[RunFile]) -> None:
        # ---- compute phase: no lock, immutable inputs ----
        all_runs = [r.ensure_loaded() for r in sources + overlap]
        tot_e = sum(r.ne for r in sources + overlap)
        self.io.compaction_read += sum(
            r.nbytes for r in sources + overlap)
        tau_min = self.versions.min_live_tau(self._ts)
        vcap = csr.quantize_cap(max(tot_e, 1))
        merged = csr.merge_runs(all_runs, tau_min, vcap=vcap,
                                deeper=self._runs_below(
                                    target_level, sources + overlap))
        new_segs = self._resegment(merged, target_level)
        self.io.compaction_write += sum(r.nbytes for r in new_segs)
        obs.counter("store_level_write_bytes", store=self.obs_label,
                    level=str(target_level)).inc(
            sum(r.nbytes for r in new_segs))
        if self.durability is not None:
            # Write the merge outputs while no lock is held; they stay
            # invisible (orphans) until the manifest edit below lands.
            self.durability.on_compact_segments(new_segs)
        # ---- commit phase: publish, not mutate-under-lock ----
        # _flush_lock orders this commit (and its manifest 'compact' edit +
        # old-file unlinks) against a concurrent flush pipeline: a compacted
        # L0 run's manifest 'flush' ADD must land before this edit REMOVES
        # it, or a crash could recover a manifest naming an unlinked file /
        # resurrecting merged records.  Lock order is _compact -> _flush ->
        # _write -> _lock everywhere (flush_memgraph releases _flush_lock
        # before it calls compact_l0), so this cannot deadlock.  The new
        # membership/index are computed under _flush_lock alone (it is the
        # only serializer of level/index change); only the reference swap
        # enters the commit lock.
        with self._flush_lock:
            self._commit_merge(sources=sources, overlap=overlap,
                               new_segs=new_segs,
                               merged_nv=int(merged.nv),
                               target_level=target_level,
                               range_lo=range_lo, range_hi=range_hi,
                               l0_max_fid=l0_max_fid,
                               also_remove=also_remove)
            if self.durability is not None:
                # One fsync'd manifest record makes the swap crash-atomic;
                # the replaced files are deleted only after it lands.
                removed = {r.fid: r for r in also_remove + overlap}
                self.durability.on_compact_commit(
                    [removed[f] for f in sorted(removed)], new_segs,
                    target_level)

    def _runs_below(self, level: int,
                    inputs: List[RunFile]) -> List[csr.CSRRunArrays]:
        """Runs in the levels below ``level`` whose vertex ranges overlap the
        merge inputs': the only places an older version of a merged key
        can live (levels are disjoint in vertex range within a level, and
        every shallower record is newer)."""
        lo = min(r.min_vid for r in inputs)
        hi = max(r.max_vid for r in inputs)
        return [r.ensure_loaded()
                for lvl in self._state.levels[level + 1:] for r in lvl
                if r.nv > 0 and r.min_vid <= hi and r.max_vid >= lo]

    def _commit_merge(self, *, sources, overlap, new_segs, merged_nv,
                      target_level, range_lo, range_hi, l0_max_fid,
                      also_remove) -> None:
        """Build the post-compaction membership + index functionally (caller
        holds ``_flush_lock`` — level/index fields cannot change under us;
        concurrent apply publishes only touch mem/tau), then install it with
        one commit-lock swap."""
        st = self._state
        # Remove compacted source files from their level (runs flushed to L0
        # during an in-flight compaction survive untouched).
        src_level = target_level - 1
        removed_fids = {r.fid for r in also_remove}
        new_levels = list(st.levels)
        new_levels[src_level] = tuple(
            r for r in st.levels[src_level] if r.fid not in removed_fids)
        # Replace overlapping target segments; keep disjoint ones untouched.
        overlap_fids = {r.fid for r in overlap}
        keep = [r for r in st.levels[target_level]
                if r.fid not in overlap_fids]
        new_levels[target_level] = tuple(sorted(
            keep + new_segs, key=lambda r: r.min_vid))
        new_levels = tuple(new_levels)
        # Index + vertex-grained version-control updates (paper §4.3): the new
        # (fid, offset) per vertex, the cleared source level, and — for L0
        # compactions — the min readable L0 fid = max involved fid + 1.
        index = st.index
        l0_update = jnp.asarray(
            l0_max_fid + 1 if l0_max_fid is not None else -1, jnp.int32)
        src_lo = jnp.asarray(range_lo, jnp.int32)
        src_hi = jnp.asarray(range_hi, jnp.int32)

        def note(index, vkeys, voff, nv, fid, lo, hi):
            return mlindex.note_compaction(
                index, level=target_level, new_vkeys=vkeys, new_voff=voff,
                new_nv=nv, new_fid=jnp.asarray(fid, jnp.int32),
                range_lo=jnp.asarray(lo, jnp.int32),
                range_hi=jnp.asarray(hi, jnp.int32),
                src_lo=src_lo, src_hi=src_hi, l0_min_fid_update=l0_update)

        for seg in new_segs:
            a = seg.arrays
            index = note(index, a.vkeys, a.voff, a.nv, seg.fid,
                         seg.min_vid, seg.max_vid + 1)
        # Target ranges between [range_lo, range_hi) not covered by a new
        # seg were annihilated (all of it when there is no new seg): clear
        # them at the target too.
        covered = [(s.min_vid, s.max_vid + 1) for s in new_segs]
        for (glo, ghi) in _range_gaps(range_lo, range_hi, covered):
            index = note(index, jnp.full((1,), INVALID_VID, jnp.int32),
                         jnp.zeros((2,), jnp.int32), jnp.asarray(0, jnp.int32),
                         INVALID_VID, glo, ghi)
        self.io.index_write += merged_nv * 8
        new_runs = dict(st.runs_by_fid)
        for r in sources + overlap:
            new_runs.pop(r.fid, None)
        for seg in new_segs:
            new_runs[seg.fid] = seg
        deg = self.degraded_ranges()
        with self._lock:
            cur = self._state  # re-read: mem/tau may have advanced
            version = self.versions.publish(
                (cur.mem_id,) + ((cur.mem_full_id,)
                                 if cur.mem_full_id is not None else ()),
                tuple(r.fid for r in new_levels[0]), cur.tau)
            self._swap_state(levels=new_levels, index=index,
                             runs_by_fid=new_runs, version=version,
                             degraded=deg, spine=_SpineHandle())
        self._obs_update_level_gauges(new_levels)
        obs.REGISTRY.trace_instant("store_compact_commit",
                                   store=self.obs_label,
                                   level=str(target_level),
                                   segs=str(len(new_segs)))

    def _resegment(self, merged: csr.CSRRunArrays, level: int) -> List[RunFile]:
        """Split a merged run into segment files at vertex boundaries,
        balancing sizes; a very high degree vertex gets its own segment
        (paper §4.2.1).  The merged run is already (src, dst, ts)-sorted, so
        each segment is a contiguous slice — no re-sorting."""
        ne, nv = int(merged.ne), int(merged.nv)
        if ne == 0:
            return []
        target = self.cfg.seg_target_edges
        voff = _np(merged.voff[:nv + 1])
        segs: List[RunFile] = []
        start_v = 0
        while start_v < nv:
            # Largest end_v with <= target edges (always >= 1 vertex, so a
            # high-degree vertex lands in its own segment file).
            end_v = int(np.searchsorted(voff, voff[start_v] + target,
                                        side="right")) - 1
            end_v = min(max(end_v, start_v + 1), nv)
            e_lo, e_hi = int(voff[start_v]), int(voff[end_v])
            n_v, n_e = end_v - start_v, e_hi - e_lo
            vcap, ecap = csr.quantize_cap(n_v), csr.quantize_cap(max(n_e, 1))
            sub = csr.CSRRunArrays(
                vkeys=merged.vkeys[start_v:end_v],
                voff=merged.voff[start_v:end_v + 1] - e_lo,
                dst=merged.dst[e_lo:e_hi], ts=merged.ts[e_lo:e_hi],
                marker=merged.marker[e_lo:e_hi], prop=merged.prop[e_lo:e_hi],
                nv=self._place(jnp.asarray(n_v, jnp.int32)),
                ne=self._place(jnp.asarray(n_e, jnp.int32)))
            segs.append(self._wrap(csr.repad_run(sub, vcap, ecap), level=level))
            start_v = end_v
        return segs

    def _maybe_cascade(self, level: int) -> None:
        if level >= self.cfg.n_levels - 1:
            return
        size = sum(r.ne for r in self._state.levels[level])
        if size > self.cfg.level_capacity(level):
            self.compact_partial(level)

    # ------------------------------------------------------------------ read
    def snapshot(self) -> "Snapshot":
        """Pin a consistent view — LOCK-FREE: one atomic read of the
        published state; the version-chain pin touches only the chain's own
        constant-time refcount mutex (never held across device work or a
        writer commit).  No store lock is acquired anywhere on this path —
        the lock-discipline lint (tools/lint_locks.py) enforces it."""
        st = self._state
        self.versions.pin(st.version, st.tau)
        return Snapshot(self, st)

    def query_edge(self, u: int, v: int) -> bool:
        snap = self.snapshot()
        try:
            return bool(snap.query_edges_batch([u], [v])[0])
        finally:
            snap.release()

    def query_edges_batch(self, us, vs) -> np.ndarray:
        """Batched point-membership: one snapshot, one batched resolve."""
        snap = self.snapshot()
        try:
            return snap.query_edges_batch(us, vs)
        finally:
            snap.release()

    # ------------------------------------------------------------ durability
    def sync(self) -> None:
        """Durability barrier: fsync the WAL tail (no-op when in-memory)."""
        if self.durability is not None:
            self.durability.sync()

    def ack(self, commit_seq: Optional[int]) -> None:
        """Await durability of ONE write batch: blocks until the WAL record
        with ``commit_seq`` (returned by ``insert_edges``/``delete_edges``)
        is fsynced — a per-batch ack instead of the global ``sync()``
        barrier.  No-op for in-memory stores or a ``None`` seq."""
        if commit_seq is not None and self.durability is not None:
            self.durability.sync_upto(commit_seq)

    def _install_recovered(self, levels, index, tau: int,
                           next_fid: int) -> None:
        """Publish the initial state reconstructed by ``storage.recovery``:
        one swap installs the recovered run membership, rebuilt index, and
        replayed tau — after this, the store serves reads with no trace of
        the recovery-time mutation (recovery builds its level lists
        locally, never poking published state)."""
        levels_t = tuple(tuple(lvl) for lvl in levels)
        runs = {r.fid: r for lvl in levels_t for r in lvl}
        deg = self.degraded_ranges()
        with self._flush_lock, self._write_lock:
            with self._fid_lock:
                self._next_fid = max(self._next_fid, next_fid)
            with self._lock:
                self._ts = max(self._ts, tau)
                st = self._state
                version = self.versions.publish(
                    (st.mem_id,) + ((st.mem_full_id,)
                                    if st.mem_full_id is not None else ()),
                    tuple(r.fid for r in levels_t[0]), self._ts)
                self._swap_state(levels=levels_t, index=index,
                                 runs_by_fid=runs, tau=self._ts,
                                 version=version, degraded=deg,
                                 spine=_SpineHandle())
        self._obs_update_level_gauges(levels_t)

    def degraded_ranges(self) -> tuple:
        """Vertex ranges whose on-disk data is quarantined/unreadable
        (``storage.errors.DegradedRange`` tuples).  Empty for in-memory
        stores and healthy durable stores.  Queries overlapping a degraded
        range raise a typed ``CorruptionError`` instead of returning
        silently-incomplete adjacency."""
        if self.durability is not None and \
                hasattr(self.durability, "degraded_ranges"):
            return self.durability.degraded_ranges()
        return ()

    def close(self) -> None:
        """Flush WAL buffers and release file handles.  The store stays
        usable for reads but further writes are undefined; reopen via
        ``repro.storage.open_store``."""
        if self.durability is not None:
            self.durability.close()

    # ----------------------------------------------------------------- stats
    def level_sizes(self) -> List[int]:
        return [sum(r.ne for r in lvl) for lvl in self.levels]

    def disk_bytes(self) -> int:
        """Space cost (Fig 14).  Durable mode reports ACTUAL on-disk bytes
        (WAL + segments + manifest); in-memory mode keeps the byte-accounting
        proxy over live runs + index."""
        if self.durability is not None:
            return self.durability.disk_bytes()
        run_bytes = sum(r.nbytes for lvl in self.levels for r in lvl)
        return run_bytes + mlindex.index_nbytes_dense(
            self.cfg.vmax, self.cfg.n_levels)


def slice_adjacency(offs: np.ndarray, dst: np.ndarray, prop: np.ndarray,
                    inv: np.ndarray, return_props: bool) -> list:
    """Expand a resolved (offsets, dst, prop) adjacency block into the
    per-query result list: element j is the slice for unique vertex
    ``inv[j]``.  Shared by ``Snapshot.neighbors_batch`` and the sharded
    read tier's cross-shard reassembly.  Pure-Python ints in the hot loop:
    per-element numpy scalar indexing costs more than the slicing itself
    at large batch sizes."""
    offs_l = np.asarray(offs).tolist()
    if return_props:
        return [(dst[offs_l[i]:offs_l[i + 1]], prop[offs_l[i]:offs_l[i + 1]])
                for i in inv.tolist()]
    return [dst[offs_l[i]:offs_l[i + 1]] for i in inv.tolist()]


def _pad(a: np.ndarray, n: int) -> np.ndarray:
    if len(a) == n:
        return a
    out = np.zeros(n, a.dtype)
    out[:len(a)] = a
    return out


def _range_gaps(lo: int, hi: int,
                covered: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    gaps, cur = [], lo
    for (clo, chi) in sorted(covered):
        if clo > cur:
            gaps.append((cur, clo))
        cur = max(cur, chi)
    if cur < hi:
        gaps.append((cur, hi))
    return gaps


@dataclasses.dataclass
class _ReadBackbone:
    """The snapshot's merged read spine: every pinned record, tournament-
    merged ONCE into global (src, dst, ts) order.  The merge keys are
    query-independent, so the log-k merge cost amortizes over every
    subsequent batched resolve on the snapshot (RapidStore-style query
    decoupling) — a resolve then only ranks the query vector into the
    spine and annihilates.  ``rid`` maps each record to its source run
    (-1 = MemGraph tier, always visible) for per-query index visibility."""

    src: jnp.ndarray
    dst: jnp.ndarray
    ts: jnp.ndarray
    rid: jnp.ndarray
    marker: jnp.ndarray
    prop: jnp.ndarray
    dst_np: np.ndarray          # host copies for the output gather
    prop_np: np.ndarray
    runs: List[Tuple[RunFile, int]]   # rid order; col < 0 means L0
    # Stacked presence-filter words of ``runs`` (uint32[R, W], rows padded
    # to the widest filter; all-ones row = run without a filter) + per-run
    # position masks (uint32[R] = mbits - 1).  None when no run carries a
    # filter.  Built once per sealed epoch alongside the spine, so every
    # resolve tests the whole query vector against all runs in one
    # vectorized pass (``kernels.ops.presence_matrix``).
    fwords: Optional[jnp.ndarray] = None
    fmasks: Optional[jnp.ndarray] = None


class Snapshot:
    """A pinned consistent view — one published ``StoreState``.

    Immutability makes the pin trivially consistent: the state was frozen
    at publication and commits create new arrays, never mutate pinned ones
    (DESIGN.md §4).  Construction is LOCK-FREE: every field is a read of
    the already-consistent state object.
    """

    def __init__(self, store: LSMGraph, state: StoreState):
        self._store = store
        self.state = state
        self.version = state.version
        self.tau = state.tau
        self.cfg = store.cfg
        self.index = state.index
        self.mem_states: List[MemGraphState] = [state.mem]
        if state.mem_full is not None:
            self.mem_states.append(state.mem_full)
        # Degraded ranges read LIVE at snapshot time (the engine's own
        # health mutex, not a store lock): runs whose file was quarantined
        # are excluded from the pin (their arrays are gone and
        # unreloadable); queries overlapping their vertex ranges raise a
        # typed error instead of silently missing edges.
        self.degraded = store.degraded_ranges()
        bad_fids = {r.fid for r in self.degraded}
        self.l0_runs: List[RunFile] = [
            r for r in state.levels[0] if r.fid not in bad_fids]
        self.level_runs: List[List[RunFile]] = [
            [r for r in lvl if r.fid not in bad_fids]
            for lvl in state.levels[1:]]
        # Evicted (durable, cold) segments stay cold at pin time: every read
        # path materializes lazily via ensure_loaded, and a run's file can't
        # vanish under a pin — compaction re-materializes the runs it removes
        # before unlinking their files (engine.on_compact_commit), so the
        # pinned RunFile objects keep (or can reload) their arrays.
        self.runs_by_fid = {r.fid: r
                            for lvl in ([self.l0_runs] + self.level_runs)
                            for r in lvl}
        self._released = False

    def release(self) -> None:
        if not self._released:
            self._store.versions.unpin(self.version.vid, self.tau)
            self._released = True

    def __enter__(self) -> "Snapshot":
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    # -------------------------------------------------------------- raw runs
    def all_run_records(self):
        """(src, dst, ts, marker, prop) numpy record arrays of every visible
        run incl. MemGraph tiers — the analytics fast path iterates these."""
        recs = []
        for mg in self.mem_states:
            src, dst, ts, marker, prop, n = mg_mod.flush_arrays(mg)
            n = int(n)
            recs.append((_np(src)[:n], _np(dst)[:n], _np(ts)[:n],
                         _np(marker)[:n], _np(prop)[:n], None))
        for rf in self.l0_runs:
            recs.append(_run_records(rf, min_fid_filter=True))
        for lvl in self.level_runs:
            for rf in lvl:
                recs.append(_run_records(rf, min_fid_filter=False))
        return recs

    # ------------------------------------------------------------- neighbors
    def neighbors(self, v: int, return_props: bool = False):
        """Exact adjacency of v at τ — thin wrapper over the batched read
        path (one-element batch).  `neighbors_scalar` keeps the original
        per-run host loop as the reference implementation."""
        return self.neighbors_batch(
            np.asarray([v], np.int64), return_props=return_props)[0]

    def neighbors_batch(self, vs, return_props: bool = False):
        """Adjacency of every vertex in `vs` at τ (paper read workflow,
        batched and pipelined).

        First resolve on a snapshot: cold segments prefetch on the
        background pool while MemGraph tiers lexsort individually, then a
        log-k tournament of pairwise merge-path passes folds every pinned
        source into the query-independent read spine (`_ReadBackbone`) —
        CSR runs enter in their native (src, dst, ts) order, unsorted.
        Every resolve (including the first): one vectorized rank of the
        spine into the query vector + a vectorized multi-level-index
        visibility gather (`index.lookup_batch`) + one segmented
        annihilation (newest visible wins per (src, dst), tombstone
        masking).  No per-vertex degree cap exists anywhere.  Returns a
        list parallel to `vs` of int64 dst arrays (or (dst, prop) tuples),
        byte-identical to the scalar path.
        """
        vs = np.asarray(vs, np.int64).ravel()
        if vs.size == 0:
            return []
        uniq, inv = np.unique(vs, return_inverse=True)
        self._check_degraded(uniq)
        if len(uniq) == 1:
            # Point-read fast path: a 1-vertex batch would still scan every
            # visible run's full record array; the scalar slice-gather path
            # is strictly cheaper (and identical — see the equivalence
            # tests).  Keeps query_edge / neighbors() at O(degree) cost.
            one = self.neighbors_scalar(int(uniq[0]),
                                        return_props=return_props)
            return [one] * len(vs)
        offs, dst, prop = self._resolve_batch_chunked(uniq)
        return slice_adjacency(offs, dst, prop, inv, return_props)

    def degraded_overlap(self, u) -> tuple:
        """The pinned degraded ranges that ``u``'s vertices actually touch
        (exact per-vid check, not a bounding-box one)."""
        if not self.degraded:
            return ()
        u = np.asarray(u)
        return tuple(r for r in self.degraded
                     if bool(((u >= r.lo) & (u <= r.hi)).any()))

    def _check_degraded(self, u) -> None:
        hit = self.degraded_overlap(u)
        if hit:
            # Runtime-only import: storage imports core at module load, so
            # the reverse edge must stay out of import time.
            from ..storage.errors import CorruptionError
            raise CorruptionError(
                "query touches degraded vertex range(s) "
                + ", ".join(f"[{r.lo}, {r.hi}] (fid {r.fid})" for r in hit),
                ranges=hit)

    # Bound on unique vertices per device resolve: caps the (chunk, seg_size)
    # MemGraph gather and the final sort buffer, so edge_set()-style whole-
    # graph resolves stream in bounded memory instead of one |V|-sized spike.
    _BATCH_CHUNK = 1 << 14

    def _prefetch_range(self, lo: int, hi: int,
                        queries: Optional[np.ndarray] = None) -> int:
        """Kick background loads for every cold visible run whose vertex
        range overlaps [lo, hi] — host metadata only, no device sync, so
        disk I/O overlaps whatever the caller dispatches next.  Conservative
        superset of the runs a resolve of that range will touch; their
        ``ensure_loaded`` joins the in-flight load.  When the exact query
        vector is known, each run's presence filter gates the schedule: a
        cold run that rejects EVERY query is provably untouched by the
        resolve, so its disk load is skipped outright.  Returns the number
        of loads scheduled."""
        if hi < lo:
            return 0
        use_filters = queries is not None and _read_filters_enabled()
        n = 0
        pool = None
        for rf in self.runs_by_fid.values():
            if (rf.arrays is None and rf.nv > 0
                    and rf.max_vid >= lo and rf.min_vid <= hi):
                if (use_filters and rf.presence is not None
                        and not rf.presence.might_contain(queries).any()):
                    continue
                if pool is None:
                    pool = prefetch_pool()
                n += rf.prefetch(pool)
        return n

    def _resolve_batch_chunked(self, u: np.ndarray):
        if len(u) <= self._BATCH_CHUNK:
            return self._resolve_batch(u)
        # Uniform chunk padding: the trailing partial chunk resolves at the
        # same padded query width as the full ones, so every chunk hits one
        # jit cache entry instead of compiling per distinct tail size.
        chunk_pad = csr.quantize_cap(self._BATCH_CHUNK, minimum=64)
        chunks = [u[lo:lo + self._BATCH_CHUNK]
                  for lo in range(0, len(u), self._BATCH_CHUNK)]
        offs_l, dst_l, prop_l = [np.zeros(1, np.int64)], [], []
        base = 0
        for i, cu in enumerate(chunks):
            if i + 1 < len(chunks) and not self.spine_ready():
                # Double-buffer (legacy / pre-spine): chunk i+1's cold
                # segments stream in while chunk i dispatches and
                # annihilates.  Once the backbone exists, chunks never
                # touch segment arrays again.
                nxt = chunks[i + 1]
                self._prefetch_range(
                    int(nxt[0]), int(nxt[-1]),
                    queries=nxt if _READ_TOURNAMENT_MAX_K <= 0 else None)
            offs, dst, prop = self._resolve_batch(cu, pad_to=chunk_pad)
            offs_l.append(offs[1:] + base)
            dst_l.append(dst)
            prop_l.append(prop)
            base += len(dst)
        return (np.concatenate(offs_l), np.concatenate(dst_l),
                np.concatenate(prop_l))

    def spine_ready(self) -> bool:
        """True once the shared per-state read spine exists (ANY snapshot
        at this sealed epoch may already have built it)."""
        return self.state.spine.ready()

    def _get_backbone(self) -> _ReadBackbone:
        """The state's shared read backbone (built on first use by whichever
        snapshot at this epoch gets here first — see ``_SpineHandle``)."""
        return self.state.spine.get(self.state, self._store)

    def _resolve_batch(self, u: np.ndarray, pad_to: Optional[int] = None):
        """Timed wrapper over ``_resolve_batch_impl``: every device resolve
        (one per <= _BATCH_CHUNK query chunk) lands in the owning store's
        ``read_resolve_seconds`` histogram."""
        t0 = time.perf_counter()
        out = self._resolve_batch_impl(u, pad_to)
        dt = time.perf_counter() - t0
        self._store._obs_resolve.observe(dt)
        self._store._obs_read_queries.inc(len(u))
        ring = obs.REGISTRY.trace_ring  # one check; None = tracing off
        if ring is not None:
            ring.append({"name": "read_resolve",
                         "labels": {"store": self._store.obs_label,
                                    "queries": str(len(u))},
                         "t0": t0, "dur": dt, "depth": 0,
                         "thread": threading.current_thread().name,
                         "ok": True})
        return out

    def _resolve_batch_impl(self, u: np.ndarray,
                            pad_to: Optional[int] = None):
        """Resolve a SORTED UNIQUE query vector: (offsets[B+1], dst, prop),
        with dst ascending within each query's slice (scalar-path order).

        Rides the state's SHARED sealed-tier read spine (built once per
        sealed epoch, amortized over every resolve of every snapshot at
        that epoch): one vectorized rank of the query vector into the
        spine + the per-query index-visibility gather + one segmented
        annihilation (newest visible wins per (src, dst), tombstone
        hides).  The ACTIVE MemGraph is resolved separately per batch and
        its visible (src, dst) pairs suppress the sealed winners — sound
        because every active record is strictly newer than every sealed
        one (ts tier dominance), so the combined result is byte-identical
        to annihilating one merged stream.  ``LSMG_READ_TOURNAMENT_K=0``
        falls back to the legacy per-resolve concat-then-lexsort."""
        B = len(u)
        bp = pad_to if pad_to is not None else csr.quantize_cap(B, minimum=64)
        assert bp >= B, "pad_to below query count"
        lo_q, hi_q = (int(u[0]), int(u[-1])) if B else (0, -1)
        if not self.spine_ready():
            # Pre-spine only: once the backbone holds the merged records,
            # evicted segment arrays are never read again on this snapshot
            # — reloading them would be pure wasted I/O.  Filter-gating
            # applies only on the legacy path: the spine build merges
            # every run regardless, so skipping its prefetch would just
            # move the load into the foreground.
            self._prefetch_range(
                lo_q, hi_q,
                queries=u if _READ_TOURNAMENT_MAX_K <= 0 else None)
        u_pad = np.full(bp, int(INVALID_VID), np.int64)
        u_pad[:B] = u
        u_j = jnp.asarray(u_pad, jnp.int32)
        if _READ_TOURNAMENT_MAX_K <= 0:
            return self._resolve_batch_legacy(u, u_j, bp, lo_q, hi_q)
        bb = self._get_backbone()
        mem = self.state.mem
        have_mem = int(mem.ne) != 0
        if bb.src.shape[0] == 0 and not have_mem:
            self._store._obs_read_probes.inc(0)
            return (np.zeros(B + 1, np.int64), np.empty(0, np.int64),
                    np.empty(0, np.float32))
        tau_j = jnp.asarray(self.tau, jnp.int32)
        nq_j = jnp.asarray(B, jnp.int32)
        qid = live = None
        n_run = 0
        probed = int(have_mem)
        if bb.src.shape[0]:
            # Vectorized index lookup -> per-(run, query) visibility.
            first_g, min_g, lvl_fid_g, _ = mlindex.lookup_batch(
                self.index, u_j)
            first_np, min_np = _np(first_g), _np(min_g)
            lvl_np = _np(lvl_fid_g)
            vis_rows = []
            for rf, col in bb.runs:
                if not self.cfg.use_multilevel_index:
                    # Ablation: no index — every segment file is probed
                    # (Fig 16 baseline); rank filtering still applies.
                    vis_rows.append(np.ones(bp, bool))
                elif col < 0:
                    vis_rows.append(
                        (rf.fid >= min_np)
                        & ((first_np == INVALID_VID) | (rf.fid >= first_np)))
                else:
                    vis_rows.append(lvl_np[:, col] == rf.fid)
            vis_mat = (np.stack(vis_rows) if vis_rows
                       else np.zeros((1, bp), bool))
            if vis_rows and bb.fwords is not None and _read_filters_enabled():
                # One vectorized membership test of the whole query vector
                # against every run's filter; AND it into the visibility
                # matrix so filtered-out (run, query) pairs are dropped
                # BEFORE spine rank + annihilation.  Zero false negatives
                # (hash contract with the builder), so this only removes
                # provably-dead pairs — results stay byte-identical.
                fhit = _np(kops.presence_matrix(bb.fwords, bb.fmasks, u_j))
                pre = int(np.count_nonzero(vis_mat[:, :B]))
                vis_mat &= fhit
                store = self._store
                store._obs_filter_checked.inc(pre)
                store._obs_filter_skipped.inc(
                    pre - int(np.count_nonzero(vis_mat[:, :B])))
            # Read-amp accounting: sorted sources this batch actually
            # consults — runs with at least one visible query post-filter,
            # plus the active MemGraph tier.  Batch-amortized — divide by
            # read_queries_total for the per-query figure.
            if vis_rows:
                probed += int(np.count_nonzero(vis_mat[:, :B].any(axis=1)))
            qid, live, n_run = _backbone_resolve(
                bb.src, bb.dst, bb.ts, bb.rid, bb.marker, u_j,
                jnp.asarray(vis_mat), tau_j, nq_j)
        self._store._obs_read_probes.inc(probed)
        if not have_mem:
            return self._finish_resolve(qid, bb.dst_np, bb.prop_np,
                                        live, int(n_run), B)
        mqid, mdst, mts, mmk, mpr = mg_mod.scan_vertices_batch(mem, u_j)
        mq, md, mp, mlive, pq, pd, n_present = _mem_resolve(
            mqid, mdst, mts, mmk, mpr, tau_j, nq_j)
        parts = []
        if qid is not None:
            live = _suppress_sealed(qid, bb.dst, live, pq, pd, n_present)
            sealed = _np(live)
            parts.append((_np(qid)[sealed],
                          bb.dst_np[sealed].astype(np.int64),
                          bb.prop_np[sealed].astype(np.float32)))
        ml = _np(mlive)
        parts.append((_np(mq)[ml], _np(md)[ml].astype(np.int64),
                      _np(mp)[ml].astype(np.float32)))
        return self._finish_resolve_parts(parts, int(n_run), B)

    def _resolve_batch_legacy(self, u, u_j, bp, lo_q, hi_q):
        """Per-resolve concat + one segmented lexsort (the pre-backbone
        read path, kept behind LSMG_READ_TOURNAMENT_K=0)."""
        B = len(u)
        mems = [mg for mg in self.mem_states if int(mg.ne) != 0]
        first_g, min_g, lvl_fid_g, _ = mlindex.lookup_batch(self.index, u_j)
        first_np, min_np = _np(first_g), _np(min_g)
        lvl_np = _np(lvl_fid_g)
        use_filters = _read_filters_enabled()
        store = self._store

        def filter_vis(rf, vis):
            # AND the run's presence filter into its visibility row BEFORE
            # the any() gate, so a run every query misses is skipped — and
            # never ``ensure_loaded`` — on this per-run path.  L1+ indexed
            # rows skip this: the multi-level index is exact per vertex,
            # so a filter can only re-confirm it.
            if not use_filters or rf.presence is None:
                return vis
            pre = int(np.count_nonzero(vis[:B]))
            vis = vis.copy()
            vis[:B] &= rf.presence.might_contain(u)
            store._obs_filter_checked.inc(pre)
            store._obs_filter_skipped.inc(
                pre - int(np.count_nonzero(vis[:B])))
            return vis

        runs: List[Tuple[RunFile, Optional[np.ndarray]]] = []
        for rf in self.l0_runs:
            if rf.nv == 0 or rf.max_vid < lo_q or rf.min_vid > hi_q:
                continue
            vis = ((rf.fid >= min_np)
                   & ((first_np == INVALID_VID) | (rf.fid >= first_np)))
            vis = filter_vis(rf, vis)
            if vis[:B].any():
                runs.append((rf, vis))
        if self.cfg.use_multilevel_index:
            for col, lvl in enumerate(self.level_runs):
                for rf in lvl:
                    if rf.nv == 0:
                        continue
                    vis = lvl_np[:, col] == rf.fid
                    if vis[:B].any():
                        runs.append((rf, vis))
        else:
            for lvl in self.level_runs:
                for rf in lvl:
                    if rf.nv == 0 or rf.max_vid < lo_q or rf.min_vid > hi_q:
                        continue
                    vis = filter_vis(rf, np.ones(bp, bool))
                    if not vis[:B].any():
                        continue
                    runs.append((rf, vis if use_filters
                                 and rf.presence is not None else None))
        self._store._obs_read_probes.inc(len(runs) + len(mems))
        if not mems and not runs:
            return (np.zeros(B + 1, np.int64), np.empty(0, np.int64),
                    np.empty(0, np.float32))
        all_vis = np.ones(bp, bool)
        q, d, p, live, n_run = self._merge_lexsort(
            mems, runs, u_j, all_vis, jnp.asarray(self.tau, jnp.int32),
            jnp.asarray(B, jnp.int32))
        return self._finish_resolve(q, _np(d), _np(p), live, int(n_run), B)

    def _finish_resolve(self, qid, dst_np, prop_np, live, n_run: int, B: int):
        """Shared resolve epilogue: byte accounting + live-record gather +
        per-query offsets (kept single-sourced so the legacy escape hatch
        can never diverge from the spine path)."""
        self._store.io.analytics_read += n_run * (
            BYTES_PER_EDGE + BYTES_PER_PROP)
        live = _np(live)
        ql = _np(qid)[live]
        dl = dst_np[live].astype(np.int64)
        pl = prop_np[live].astype(np.float32)
        self._store._obs_read_returned.inc(
            len(dl) * (BYTES_PER_EDGE + BYTES_PER_PROP))
        offs = np.searchsorted(ql, np.arange(B + 1))
        return offs, dl, pl

    def _finish_resolve_parts(self, parts, n_run: int, B: int):
        """Combine the sealed-spine and active-tier live records into the
        final (offsets, dst, prop).  The (qid, dst) pairs are disjoint
        across parts (suppression removed every sealed winner of a
        mem-present pair) and unique within each, so the lexsort is a
        deterministic two-way merge — byte-identical to annihilating one
        merged stream."""
        self._store.io.analytics_read += n_run * (
            BYTES_PER_EDGE + BYTES_PER_PROP)
        ql = np.concatenate([p[0] for p in parts])
        dl = np.concatenate([p[1] for p in parts]).astype(np.int64)
        pl = np.concatenate([p[2] for p in parts]).astype(np.float32)
        order = np.lexsort((dl, ql))
        ql, dl, pl = ql[order], dl[order], pl[order]
        self._store._obs_read_returned.inc(
            len(dl) * (BYTES_PER_EDGE + BYTES_PER_PROP))
        offs = np.searchsorted(ql, np.arange(B + 1))
        return offs, dl, pl

    def _merge_lexsort(self, mems, runs, u_j, all_vis, tau_j, nq_j):
        """Legacy merge: concat every source and run one segmented lexsort."""
        recs = [mg_mod.scan_vertices_batch(mg, u_j) for mg in mems]
        n_mem = sum(int(r[0].shape[0]) for r in recs)
        for rf, vis in runs:
            recs.append(_run_query_records(
                rf.ensure_loaded(), u_j,
                jnp.asarray(all_vis if vis is None else vis)))
        qid = jnp.concatenate([r[0] for r in recs])
        dstc = jnp.concatenate([r[1] for r in recs])
        tsc = jnp.concatenate([r[2] for r in recs])
        mkc = jnp.concatenate([r[3] for r in recs])
        prc = jnp.concatenate([r[4] for r in recs])
        total = int(qid.shape[0])
        # Half-step buckets: the concat feeds the lexsort, this path's
        # dominant (pad-length-linear) cost.
        cap = csr.quantize_cap(total, half_steps=True)
        if cap != total:
            pad = cap - total
            qid = jnp.concatenate(
                [qid, jnp.full((pad,), INVALID_VID, jnp.int32)])
            dstc = jnp.concatenate([dstc, jnp.zeros((pad,), jnp.int32)])
            tsc = jnp.concatenate([tsc, jnp.zeros((pad,), jnp.int32)])
            mkc = jnp.concatenate([mkc, jnp.zeros((pad,), bool)])
            prc = jnp.concatenate([prc, jnp.zeros((pad,), jnp.float32)])
        q, d, p, live, n_run = _annihilate_batch(
            qid, dstc, tsc, mkc, prc, tau_j, nq_j,
            jnp.asarray(n_mem, jnp.int32))
        return q, d, p, live, int(n_run)

    def neighbors_scalar(self, v: int, return_props: bool = False):
        """Reference per-vertex read path: MemGraph first, then L0 runs with
        fid >= max(first, min readable fid), then one (fid, offset) per L1+
        level from the multi-level index (paper read workflow).  Kept as the
        equivalence oracle and benchmark baseline for `neighbors_batch`."""
        self._check_degraded(np.asarray([v]))
        recs: List[Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]] = []
        cap = self.cfg.seg_size + self.cfg.ovf_cap  # max cacheable degree
        for mg in self.mem_states:
            if int(mg.ne) == 0:
                continue  # no records; skip the capacity-shaped scan
            d, t, m, p, mask = mg_mod.scan_vertex(
                mg, jnp.asarray(v, jnp.int32), cap=cap)
            mask = _np(mask)
            recs.append((_np(d)[mask], _np(t)[mask], _np(m)[mask],
                         _np(p)[mask]))
        first_fid, min_fid, lvl_fid, lvl_off = (
            int(self.index.l0_first_fid[v]), int(self.index.l0_min_fid[v]),
            _np(self.index.lvl_fid[v]), _np(self.index.lvl_off[v]))
        bytes_read = 0
        use_filters = _read_filters_enabled()
        store = self._store

        def filter_rejects(rf) -> bool:
            # Presence pre-gate: a rejecting filter skips the gather — and,
            # for a cold run, the whole segment reload (the filter words
            # survive eviction).  The false-positive counter calibrates the
            # bits-per-key budget against live traffic.
            if not use_filters or rf.presence is None:
                return False
            store._obs_filter_checked.inc(1)
            if not bool(rf.presence.might_contain(v)[0]):
                store._obs_filter_skipped.inc(1)
                return True
            return False

        def note_fp(rf) -> None:
            if use_filters and rf.presence is not None:
                store._obs_filter_fp.inc(1)

        for rf in self.l0_runs:
            if rf.fid < min_fid or (first_fid != INVALID_VID
                                    and rf.fid < first_fid):
                continue
            if filter_rejects(rf):
                continue
            r = _gather_vertex(rf, v)
            if r is not None:
                recs.append(r)
                bytes_read += len(r[0]) * (BYTES_PER_EDGE + BYTES_PER_PROP)
            else:
                note_fp(rf)
        if self.cfg.use_multilevel_index:
            for col in range(lvl_fid.shape[0]):
                fid = int(lvl_fid[col])
                if fid == INVALID_VID or fid not in self.runs_by_fid:
                    continue
                rf = self.runs_by_fid[fid]
                r = _gather_vertex(rf, v, known_off=int(lvl_off[col]))
                if r is not None:
                    recs.append(r)
                    bytes_read += len(r[0]) * (BYTES_PER_EDGE + BYTES_PER_PROP)
        else:
            # Ablation: no index — binary-search every segment file (the
            # RocksDB-style path the paper's Fig 16 compares against).
            for lvl in self.level_runs:
                for rf in lvl:
                    if rf.nv == 0 or not (rf.min_vid <= v <= rf.max_vid):
                        continue
                    if filter_rejects(rf):
                        continue
                    r = _gather_vertex(rf, v)
                    if r is not None:
                        recs.append(r)
                        bytes_read += len(r[0]) * (
                            BYTES_PER_EDGE + BYTES_PER_PROP)
                    else:
                        note_fp(rf)
        self._store.io.analytics_read += bytes_read
        self._store._obs_read_queries.inc(1)
        self._store._obs_read_probes.inc(len(recs))
        out = _annihilate(recs, self.tau, return_props)
        self._store._obs_read_returned.inc(
            len(out[0] if return_props else out)
            * (BYTES_PER_EDGE + BYTES_PER_PROP))
        return out

    def query_edges_batch(self, us, vs) -> np.ndarray:
        """Batched edge-membership: bool[i] = (us[i] -> vs[i]) is live at τ.

        Built on the ``neighbors_batch`` offsets (ROADMAP "batched write
        path symmetry"): one batched resolve of the unique sources, then a
        vectorized bisection per pair in the already-sorted adjacency slice
        — no per-edge snapshot round-trips."""
        us = np.asarray(us, np.int64).ravel()
        vs = np.asarray(vs, np.int64).ravel()
        if us.shape != vs.shape:
            raise ValueError("us and vs must have the same length")
        if us.size == 0:
            return np.zeros(0, bool)
        nbrs = self.neighbors_batch(us)
        out = np.zeros(len(us), bool)
        for i, (adj, v) in enumerate(zip(nbrs, vs)):
            j = int(np.searchsorted(adj, v))
            out[i] = j < len(adj) and int(adj[j]) == v
        return out

    def degree(self, v: int) -> int:
        return len(self.neighbors(v))

    def degrees_batch(self, vs) -> np.ndarray:
        """Live out-degree of every vertex in vs — one batched resolve."""
        return np.array([len(n) for n in self.neighbors_batch(vs)], np.int64)

    def edge_set(self) -> set:
        """Full live edge set at τ (verification only — O(E)); one batched
        resolve over `vertices()` instead of a per-vertex host loop."""
        vs = self.vertices()
        out = set()
        for v, nbrs in zip(vs.tolist(), self.neighbors_batch(vs)):
            out.update((v, int(d)) for d in nbrs)
        return out

    def vertices(self) -> np.ndarray:
        """Every vertex id seen at τ — as a source OR a destination (a
        vertex appearing only as dst is still a vertex of the graph)."""
        vs = set()
        for (src, dst, ts, marker, prop, _) in self.all_run_records():
            m = ts <= self.tau
            vs.update(np.unique(src[m]).tolist())
            vs.update(np.unique(dst[m]).tolist())
        return np.array(sorted(vs), np.int64)


@jax.jit
def _run_query_records(run: csr.CSRRunArrays, u: jnp.ndarray,
                       vis_q: jnp.ndarray):
    """Flat (qid, dst, ts, marker, prop) of one run restricted to queried
    vertices with per-query visibility vis_q (index / min-fid rules)."""
    B = u.shape[0]
    qid = csr.map_run_to_queries(run, u)
    ok = (qid < B) & vis_q[jnp.minimum(qid, B - 1)]
    return (jnp.where(ok, qid, B), run.dst, run.ts, run.marker, run.prop)


@jax.jit
def _annihilate_batch(qid, dst, ts, marker, prop, tau, nq, run_from):
    """Segmented annihilation: one lexsort by (qid, dst, ts) over every
    record of the batch; per (qid, dst) the newest ts <= τ wins and a
    tombstone winner hides the edge — the batch-wide generalization of
    `_annihilate`.  Also returns the count of run-sourced visible records
    (positions >= run_from) for scalar-identical byte accounting."""
    pos = jnp.arange(qid.shape[0], dtype=jnp.int32)
    n_run = jnp.sum((pos >= run_from) & (qid < nq), dtype=jnp.int32)
    dead = jnp.iinfo(jnp.int32).max
    qkey = jnp.where((qid < nq) & (ts <= tau), qid, dead)
    order = jnp.lexsort((ts, dst, qkey))
    q, d = qkey[order], dst[order]
    m, p = marker[order], prop[order]
    last = (q != jnp.roll(q, -1)) | (d != jnp.roll(d, -1))
    last = last.at[-1].set(True)
    live = last & ~m & (q < nq)
    return q, d, p, live, n_run


@jax.jit
def _run_backbone_stream(run: csr.CSRRunArrays, rid: jnp.ndarray):
    """One CSR run as a backbone stream: (src, dst, ts, rid, marker, prop),
    sorted by construction — a run is natively (src, dst, ts)-ordered and
    pad slots carry src == INVALID_VID, so NO per-stream sort happens."""
    src = csr._expand_src(run)
    return (src, run.dst, run.ts, jnp.broadcast_to(rid, src.shape),
            run.marker, run.prop)


_I32MAX = np.iinfo(np.int32).max


@jax.jit
def _mem_qkey(qid, ts, tau, nq):
    return jnp.where((qid < nq) & (ts <= tau), qid, _I32MAX)


def _mem_resolve(qid, dst, ts, marker, prop, tau, nq):
    """Annihilate the ACTIVE MemGraph tier's records per (query, dst): one
    sort by (qid, dst, ts); the newest τ-visible record of each pair
    wins (a tombstone winner hides the pair).  Also emits the sorted
    (qid, dst) pair set holding ANY visible record — the suppression probe:
    by the ts tier-dominance invariant, every such pair's OVERALL winner
    lives in this tier, so the sealed spine's winner for it is discarded
    (`_suppress_sealed`).  Pair slots beyond ``n_present`` carry all-MAX
    keys (sortedness preserved).

    The sort runs on the host over the queried records only (the scan
    returns the whole overflow tier, mostly unqueried); see
    ``csr.lexsort_order`` for why record sorts leave the device."""
    qkey = _mem_qkey(qid, ts, tau, nq)
    qk = np.asarray(qkey)
    hit = np.flatnonzero(qk != _I32MAX)
    sub = hit[np.lexsort((np.asarray(ts)[hit], np.asarray(dst)[hit],
                          qk[hit]))]
    order = np.concatenate([sub, np.flatnonzero(qk == _I32MAX)])
    return _mem_resolve_sorted(
        jax.device_put(order.astype(np.int32), qkey.sharding),
        qkey, dst, marker, prop, nq)


@jax.jit
def _mem_resolve_sorted(order, qkey, dst, marker, prop, nq):
    dead = _I32MAX
    q, d = qkey[order], dst[order]
    m, p = marker[order], prop[order]
    last = (q != jnp.roll(q, -1)) | (d != jnp.roll(d, -1))
    last = last.at[-1].set(True)
    present = last & (q < nq)
    live = present & ~m
    n = q.shape[0]
    idx = jnp.nonzero(present, size=n, fill_value=n)[0]
    idx_c = jnp.minimum(idx, n - 1)
    pq = jnp.where(idx < n, q[idx_c], dead)
    pd = jnp.where(idx < n, d[idx_c], dead)
    n_present = jnp.sum(present, dtype=jnp.int32)
    return q, d, p, live, pq, pd, n_present


@jax.jit
def _suppress_sealed(qid_s, dst_s, live_s, pq, pd, n_present):
    """Drop sealed-spine winners whose (query, dst) pair the active tier
    also holds: one lexicographic binary search of every sealed record
    into the mem-present pair set."""
    z = jnp.zeros_like(qid_s)
    pos = kops.lex_searchsorted((pq, pd, jnp.zeros_like(pq)),
                                qid_s, dst_s, z, n_present, side="left")
    pos_c = jnp.minimum(pos, pq.shape[0] - 1)
    hit = (pos < n_present) & (pq[pos_c] == qid_s) & (pd[pos_c] == dst_s)
    return live_s & ~hit


@functools.partial(jax.jit, static_argnames=("pad",))
def _pad_backbone(src, dst, ts, rid, marker, prop, pad: int):
    return (jnp.concatenate([src, jnp.full((pad,), INVALID_VID, jnp.int32)]),
            jnp.concatenate([dst, jnp.zeros((pad,), jnp.int32)]),
            jnp.concatenate([ts, jnp.zeros((pad,), jnp.int32)]),
            jnp.concatenate([rid, jnp.full((pad,), -1, jnp.int32)]),
            jnp.concatenate([marker, jnp.zeros((pad,), bool)]),
            jnp.concatenate([prop, jnp.zeros((pad,), jnp.float32)]))


@jax.jit
def _backbone_resolve(src, dst, ts, rid, marker, u, vis_mat, tau, nq):
    """Resolve one query batch against the merged spine: rank every record
    into the query vector (one searchsorted over the spine), gather its
    per-(run, query) visibility, then segmented annihilation — per
    (src, dst) group the newest ALIVE record wins (segmented max of alive
    positions; dead records stay in place) and a tombstone winner hides
    the edge.  Also returns the queried-record count (pre-τ visibility,
    scalar-parity byte accounting)."""
    B = u.shape[0]
    n = src.shape[0]
    j = jnp.searchsorted(u, src).astype(jnp.int32)
    j_c = jnp.minimum(j, B - 1)
    hit = (u[j_c] == src) & (src != INVALID_VID)
    rid_c = jnp.clip(rid, 0, vis_mat.shape[0] - 1)
    queried = hit & ((rid < 0) | vis_mat[rid_c, j_c])
    alive = queried & (ts <= tau)
    qid = jnp.where(hit, j_c, B)
    idx = jnp.arange(n, dtype=jnp.int32)
    new_grp = (src != jnp.roll(src, 1)) | (dst != jnp.roll(dst, 1))
    new_grp = new_grp.at[0].set(True)
    gid = jnp.cumsum(new_grp.astype(jnp.int32)) - 1
    winner = jax.ops.segment_max(jnp.where(alive, idx, -1), gid,
                                 num_segments=n)
    live = alive & (idx == winner[gid]) & ~marker & (qid < nq)
    n_run = jnp.sum(queried & (rid >= 0), dtype=jnp.int32)
    return qid, live, n_run


def _run_records(rf: RunFile, min_fid_filter: bool):
    a = rf.ensure_loaded()  # concurrent evict: reload, local ref stays valid
    ne = rf.ne
    src = _np(csr._expand_src(a))[:ne]
    return (src, _np(a.dst)[:ne], _np(a.ts)[:ne], _np(a.marker)[:ne],
            _np(a.prop)[:ne], rf.fid)


def _gather_vertex(rf: RunFile, v: int, known_off: Optional[int] = None):
    if rf.nv == 0:
        return None
    a = rf.ensure_loaded()  # concurrent evict: reload, local ref stays valid
    if known_off is None:
        found, start, end = csr.run_lookup(a, jnp.asarray(v, jnp.int32))
        if not bool(found):
            return None
        start, end = int(start), int(end)
    else:
        # Multi-level index gave the offset: O(1), no binary search.
        start = known_off
        vk = _np(a.vkeys)
        nv = rf.nv
        voff = _np(a.voff)
        i = int(np.searchsorted(voff[:nv + 1], start, side="right")) - 1
        end = int(voff[min(i + 1, nv)])
        if i >= nv or int(vk[i]) != v:
            return None
    if end <= start:
        return None
    sl = slice(start, end)
    return (_np(a.dst[sl]), _np(a.ts[sl]), _np(a.marker[sl]), _np(a.prop[sl]))


def _annihilate(recs, tau: int, return_props: bool):
    """Merge per-run records: newest ts <= τ wins per dst; tombstone hides."""
    if not recs:
        return (np.empty(0, np.int64), np.empty(0, np.float32)) \
            if return_props else np.empty(0, np.int64)
    dst = np.concatenate([r[0] for r in recs]).astype(np.int64)
    ts = np.concatenate([r[1] for r in recs]).astype(np.int64)
    marker = np.concatenate([r[2] for r in recs]).astype(bool)
    prop = np.concatenate([r[3] for r in recs]).astype(np.float32)
    m = ts <= tau
    dst, ts, marker, prop = dst[m], ts[m], marker[m], prop[m]
    if len(dst) == 0:
        return (np.empty(0, np.int64), np.empty(0, np.float32)) \
            if return_props else np.empty(0, np.int64)
    order = np.lexsort((ts, dst))
    dst, ts, marker, prop = dst[order], ts[order], marker[order], prop[order]
    last = np.ones(len(dst), bool)
    last[:-1] = dst[:-1] != dst[1:]
    live = last & ~marker
    if return_props:
        return dst[live], prop[live]
    return dst[live]
