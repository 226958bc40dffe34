"""Core pytree types and configuration for LSMGraph-on-TPU.

Design rules (see DESIGN.md §4):
  * every device structure is a NamedTuple of fixed-capacity arrays + scalar
    fill counts, so all update/flush/compaction paths jit cleanly;
  * host-side metadata (file ids, level numbers, byte accounting) lives in
    plain dataclass wrappers that are never traced.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Callable, NamedTuple, Optional, Tuple

import jax.numpy as jnp

from repro import obs

# Sentinel for "no vertex" — vertex ids must be < INVALID_VID.
INVALID_VID = jnp.iinfo(jnp.int32).max

# Byte accounting mirroring the paper's on-disk edge body (dst, ts, prop_off,
# marker) with 8-byte vids in the paper; we count 16 B of topology + 4 B of
# property per edge, and 8 B per index entry.  Used only by the I/O-proxy and
# space benchmarks — the in-memory arrays are int32/float32.
BYTES_PER_EDGE = 16
BYTES_PER_PROP = 4
BYTES_PER_INDEX_ENTRY = 8

# The paper's default MemGraph size (§6 setup): 64 MB of edge records.
MEMGRAPH_BYTES = 64 << 20


class EdgeBatch(NamedTuple):
    """A fixed-capacity batch of edge updates (insert or tombstone)."""

    src: jnp.ndarray      # int32[BC]
    dst: jnp.ndarray      # int32[BC]
    ts: jnp.ndarray       # int32[BC] — globally unique, monotone per edge
    prop: jnp.ndarray     # float32[BC]
    marker: jnp.ndarray   # bool[BC] — True = deletion tombstone
    n: jnp.ndarray        # int32[]  — number of valid leading entries


class CSRRunArrays(NamedTuple):
    """One immutable CSR run ("CSR file" in the paper, Fig. 6).

    vkeys is the sorted list of distinct source vertices present (padded with
    INVALID_VID); voff[i]:voff[i+1] bounds vertex vkeys[i]'s edges, which are
    sorted by (dst, ts).  Properties are a parallel array = the paper's
    separate property file.
    """

    vkeys: jnp.ndarray    # int32[Vc]
    voff: jnp.ndarray     # int32[Vc+1]
    dst: jnp.ndarray      # int32[Ec]
    ts: jnp.ndarray       # int32[Ec]
    marker: jnp.ndarray   # bool[Ec]
    prop: jnp.ndarray     # float32[Ec]
    nv: jnp.ndarray       # int32[] — valid vertices
    ne: jnp.ndarray       # int32[] — valid edges

    @property
    def vcap(self) -> int:
        return self.vkeys.shape[0]

    @property
    def ecap(self) -> int:
        return self.dst.shape[0]


@dataclasses.dataclass(eq=False)  # identity eq: arrays are not comparable
class RunFile:
    """Host wrapper: a CSR run plus the paper's file-header metadata.

    In durable mode ``path``/``loader`` point at the on-disk segment file;
    ``arrays`` may then be evicted (set to None) and is lazily reloaded via
    ``ensure_loaded`` — cold L1+ levels need not stay resident in RAM.
    """

    fid: int
    level: int
    arrays: Optional[CSRRunArrays]
    min_vid: int
    max_vid: int
    created_ts: int
    nv: int
    ne: int
    path: Optional[str] = None
    loader: Optional[Callable[[], CSRRunArrays]] = dataclasses.field(
        default=None, repr=False)
    # Device-resident vertex-presence filter (core.filters.PresenceFilter)
    # over this run's source-vertex set.  Deliberately OUTSIDE the
    # evictable ``arrays``: a cold run can reject a query — and dodge the
    # segment reload — without touching disk.  None = no filter (pre-v2
    # segment, or a run the caller chose not to filter): always "maybe".
    presence: Optional[object] = dataclasses.field(default=None, repr=False)
    # Store-level I/O counters for retry accounting (set by the owning
    # store; None for standalone RunFiles).
    io: Optional["IOCounters"] = dataclasses.field(default=None, repr=False)
    # Orders load vs evict vs the compaction-commit re-materialize+unlink:
    # without it a reader past its None-check could open an already-deleted
    # segment file.
    _load_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False)
    # Best-effort dedup of in-flight background loads; races are benign
    # (ensure_loaded serializes the actual load on _load_lock).
    _prefetching: bool = dataclasses.field(default=False, repr=False)

    @property
    def nbytes(self) -> int:
        return self.ne * (BYTES_PER_EDGE + BYTES_PER_PROP)

    # Cold-load instrumentation (slow path only: the resident fast path in
    # ``ensure_loaded`` stays untouched).  A "hit" is a load that found the
    # arrays already materialized by prefetch/a concurrent reader once under
    # the lock; a "miss" pays the actual segment load.
    _OBS_HIT = obs.counter("read_prefetch_hit_total")
    _OBS_MISS = obs.counter("read_prefetch_miss_total")
    _OBS_SCHED = obs.counter("read_prefetch_scheduled_total")
    _OBS_LOAD = obs.histogram("storage_segment_load_seconds")
    # Read-amp context for the amplification ledger: bytes materialized by
    # cold segment loads (process-wide — RunFiles outlive store labels).
    _OBS_COLD_BYTES = obs.counter("read_cold_load_bytes")

    def ensure_loaded(self, _retry_counter: str = "read_retries"
                      ) -> CSRRunArrays:
        """Materialize ``arrays`` (no-op when resident).  Returns a local
        reference, so a concurrent ``evict`` cannot null it between the
        check and the caller's use.

        Transient loader failures (duck-typed: the exception carries
        ``transient = True``, e.g. an EIO on a cold segment read) are
        retried with bounded exponential backoff + wall-clock deadline;
        each retry bumps ``io.<_retry_counter>``.  Corruption and other
        non-transient errors propagate on the first attempt.  The retry
        lives HERE — once, under the load lock — so foreground loads and
        background prefetch cannot stack retries multiplicatively."""
        a = self.arrays
        if a is not None:
            return a
        with self._load_lock:
            a = self.arrays
            if a is None:
                if self.loader is None:
                    raise RuntimeError(
                        f"RunFile fid={self.fid} has no arrays and no loader")
                self._OBS_MISS.inc()
                self._OBS_COLD_BYTES.inc(self.nbytes)
                if self.io is not None:
                    # Per-store attribution of the same bytes: the ledger's
                    # read-amp report prefers this over the process-wide
                    # class counter, which mixes every store's cold loads.
                    self.io.cold_load += self.nbytes
                t0 = time.perf_counter()
                a = self._load_with_retry(_retry_counter)
                self._OBS_LOAD.observe(time.perf_counter() - t0)
                self.arrays = a
            else:
                self._OBS_HIT.inc()
        return a

    def _load_with_retry(self, counter_attr: str) -> CSRRunArrays:
        attempts = int(os.environ.get("LSMG_IO_RETRIES", "3"))
        base = float(os.environ.get("LSMG_IO_RETRY_BASE", "0.002"))
        budget = float(os.environ.get("LSMG_IO_RETRY_DEADLINE", "2.0"))
        deadline = time.monotonic() + budget
        delay = base
        attempt = 0
        while True:
            try:
                return self.loader()
            except Exception as e:
                attempt += 1
                if (not getattr(e, "transient", False)
                        or attempt >= attempts
                        or time.monotonic() + delay > deadline):
                    raise
                if self.io is not None:
                    setattr(self.io, counter_attr,
                            getattr(self.io, counter_attr) + 1)
                time.sleep(delay)
                delay = min(delay * 2, 0.1)

    def prefetch(self, executor) -> bool:
        """Async counterpart of ``ensure_loaded``: start materializing
        ``arrays`` on ``executor`` if the run is cold.  The background load
        serializes with foreground loads/evicts on ``_load_lock``, so a
        concurrent ``ensure_loaded`` simply joins it.  Transient errors get
        the same bounded retry as foreground loads (counted separately in
        ``io.prefetch_retries``); a load that still fails leaves the run
        cold — the error then surfaces on the next foreground
        ``ensure_loaded`` instead of vanishing into the pool.
        Returns True iff a load was scheduled."""
        if self.arrays is not None or self.loader is None or self._prefetching:
            return False
        self._prefetching = True

        def _load() -> None:
            try:
                self.ensure_loaded(_retry_counter="prefetch_retries")
            except Exception:
                pass
            finally:
                self._prefetching = False

        try:
            executor.submit(_load)
        except RuntimeError:      # pool shut down: foreground load covers it
            self._prefetching = False
            return False
        self._OBS_SCHED.inc()
        return True

    def evict(self) -> bool:
        """Drop the in-RAM arrays if a disk copy exists.  Returns True if
        evicted.  A concurrently pinned snapshot will transparently reload
        through ``ensure_loaded`` on its next read."""
        with self._load_lock:
            if self.arrays is not None and self.loader is not None:
                self.arrays = None
                return True
            return False


class MemGraphState(NamedTuple):
    """MemGraph (paper §4.1): hashmap → fixed segments + overflow tier.

    Low-degree vertices (≈95 %) live in one G-slot segment each; edges past G
    go to the overflow append-log (the TPU stand-in for the paper's skip list:
    deferred ordering via sort-on-flush — see DESIGN.md §2.1).
    """

    htab_key: jnp.ndarray   # int32[H]  — INVALID_VID = empty
    htab_row: jnp.ndarray   # int32[H]
    seg_owner: jnp.ndarray  # int32[NS]
    seg_len: jnp.ndarray    # int32[NS] — true cached degree (may exceed G)
    seg_dst: jnp.ndarray    # int32[NS, G]
    seg_ts: jnp.ndarray     # int32[NS, G]
    seg_marker: jnp.ndarray  # bool[NS, G]
    seg_prop: jnp.ndarray   # float32[NS, G]
    ovf_src: jnp.ndarray    # int32[Oc]
    ovf_dst: jnp.ndarray    # int32[Oc]
    ovf_ts: jnp.ndarray     # int32[Oc]
    ovf_marker: jnp.ndarray  # bool[Oc]
    ovf_prop: jnp.ndarray   # float32[Oc]
    n_rows: jnp.ndarray     # int32[]
    ovf_n: jnp.ndarray      # int32[]
    ne: jnp.ndarray         # int32[]

    @property
    def hcap(self) -> int:
        return self.htab_key.shape[0]

    @property
    def nseg(self) -> int:
        return self.seg_owner.shape[0]

    @property
    def segsize(self) -> int:
        return self.seg_dst.shape[1]

    @property
    def ovf_cap(self) -> int:
        return self.ovf_src.shape[0]


@dataclasses.dataclass(frozen=True)
class StoreConfig:
    """LSMGraph configuration (paper defaults: 64 MB MemGraph, T=10, 5 levels,
    two alternating MemGraphs)."""

    vmax: int = 1 << 16            # vertex-id space
    # -- MemGraph --
    mem_edges: int = 1 << 14       # P: flush threshold (edges)
    seg_size: int = 8              # G: slots per low-degree segment
    n_segments: int = 1 << 13      # NS: segment pool rows
    hash_slots: int = 1 << 14      # H (power of two)
    ovf_cap: int = 1 << 14         # Oc: overflow ("skip list") capacity
    batch_cap: int = 1 << 12       # BC: max edges per vectorized insert
    # -- levels --
    n_levels: int = 5
    level_factor: int = 10         # T
    l0_run_limit: int = 4          # flushes before L0→L1 compaction
    seg_target_edges: int = 1 << 15  # segment-file split target at L1+
    # -- behaviour --
    dedup_gc: bool = True          # drop superseded versions at compaction
    use_multilevel_index: bool = True   # Fig. 16 ablation switch
    memcache_mode: str = "memgraph"     # memgraph | array_only | skiplist_only

    @classmethod
    def for_graph(cls, n_vertices: int,
                  mem_bytes: int = MEMGRAPH_BYTES) -> "StoreConfig":
        """Size a store for a graph of ``n_vertices``: the MemGraph holds
        ``mem_bytes`` of edge records (20 B each) before it flushes, and
        its row pool, hash table and overflow tier are sized so that edge
        count, not a secondary cap, triggers the flush.  Segment files at
        L1+ split at one MemGraph's worth of edges."""
        mem_edges = max(256, mem_bytes // (BYTES_PER_EDGE + BYTES_PER_PROP))
        bc = min(1 << 12, mem_edges)
        rows = min(n_vertices, mem_edges) + bc
        # Power of two keeping `rows` under the 0.7 load factor at which
        # memgraph_should_flush fires.
        hash_slots = 1 << (-(-rows * 10 // 7) - 1).bit_length()
        return cls(vmax=n_vertices, mem_edges=mem_edges, seg_size=8,
                   n_segments=rows, hash_slots=hash_slots,
                   ovf_cap=mem_edges + bc, batch_cap=bc,
                   seg_target_edges=mem_edges)

    def level_capacity(self, level: int) -> int:
        """Edge capacity of level i: P * T**i (L0 counts runs, not edges)."""
        return self.mem_edges * (self.level_factor ** max(level, 1))

    def validate(self) -> None:
        assert self.hash_slots & (self.hash_slots - 1) == 0, "H must be 2^k"
        assert self.n_segments * self.seg_size + self.ovf_cap >= self.mem_edges
        assert self.batch_cap <= self.mem_edges
        assert self.memcache_mode in ("memgraph", "array_only", "skiplist_only")


@dataclasses.dataclass
class IOCounters:
    """Bytes-moved accounting — the I/O proxy for the paper's disk-I/O plots.

    ``flush_write``/``compaction_*``/``analytics_read``/``index_write`` are
    the paper's logical-bytes proxy (counted in every mode); ``wal_write``,
    ``segment_write``, ``segment_read`` and ``manifest_write`` count
    *actual* file bytes and advance only when a durable storage engine is
    attached.

    After ``bind(registry, **labels)`` every field write is mirrored into
    registry counters (``io_<field>_bytes``, or ``_total`` for retry
    counts), so the legacy ``store.io.wal_write += n`` sites keep working
    unchanged while the exporter sees the same numbers.  ``snapshot()``
    copies are unbound (frozen-in-time values, not live series).
    """

    flush_write: int = 0
    compaction_read: int = 0
    compaction_write: int = 0
    analytics_read: int = 0
    index_write: int = 0
    wal_write: int = 0        # durable: WAL record bytes appended
    segment_write: int = 0    # durable: segment file bytes written
    segment_read: int = 0     # durable: segment file bytes (re)loaded
    manifest_write: int = 0   # durable: manifest edit-log bytes appended
    cold_load: int = 0        # durable: segment bytes materialized by
    #                           cold loads (per-store slice of the
    #                           process-wide read_cold_load_bytes)
    read_retries: int = 0     # transient-I/O retries on foreground loads
    prefetch_retries: int = 0  # transient-I/O retries in the prefetch pool

    def __setattr__(self, name: str, value) -> None:
        # Mirror field increments into bound registry counters.  During
        # __init__ / dataclasses.replace the mirror key is absent from
        # __dict__, so construction takes the plain path.
        mirror = self.__dict__.get("_mirror")
        if mirror is not None:
            c = mirror.get(name)
            if c is not None:
                d = value - self.__dict__.get(name, 0)
                if d > 0:
                    c.inc(d)
        object.__setattr__(self, name, value)

    def bind(self, registry=None, **labels) -> "IOCounters":
        """Mirror this instance's fields into per-field registry counters,
        bootstrapping any value accumulated before binding."""
        registry = registry if registry is not None else obs.REGISTRY
        mirror = {}
        for f in dataclasses.fields(self):
            unit = "total" if f.name.endswith("retries") else "bytes"
            c = registry.counter(f"io_{f.name}_{unit}", **labels)
            cur = getattr(self, f.name)
            if cur > 0:
                c.inc(cur)
            mirror[f.name] = c
        self.__dict__["_mirror"] = mirror
        return self

    def total_write(self) -> int:
        return self.flush_write + self.compaction_write + self.index_write

    def total(self) -> int:
        return self.total_write() + self.compaction_read + self.analytics_read

    def durable_write(self) -> int:
        """Actual bytes written to disk (WAL + segment files)."""
        return self.wal_write + self.segment_write

    def snapshot(self) -> "IOCounters":
        return dataclasses.replace(self)

    def delta(self, other: "IOCounters") -> "IOCounters":
        return IOCounters(
            flush_write=self.flush_write - other.flush_write,
            compaction_read=self.compaction_read - other.compaction_read,
            compaction_write=self.compaction_write - other.compaction_write,
            analytics_read=self.analytics_read - other.analytics_read,
            index_write=self.index_write - other.index_write,
            wal_write=self.wal_write - other.wal_write,
            segment_write=self.segment_write - other.segment_write,
            segment_read=self.segment_read - other.segment_read,
            manifest_write=self.manifest_write - other.manifest_write,
            cold_load=self.cold_load - other.cold_load,
            read_retries=self.read_retries - other.read_retries,
            prefetch_retries=self.prefetch_retries - other.prefetch_retries,
        )


@dataclasses.dataclass(frozen=True)
class Version:
    """A readable view (paper §4.3): MemGraph ids + L0 file ids + snapshot τ.

    L1+ visibility is carried by the multi-level index (vertex-grained), not
    by the version chain — exactly the paper's split.
    """

    vid: int
    memgraph_ids: Tuple[int, ...]
    l0_fids: Tuple[int, ...]
    tau: int


def empty_batch(batch_cap: int) -> EdgeBatch:
    z = jnp.zeros((batch_cap,), jnp.int32)
    return EdgeBatch(
        src=z, dst=z, ts=z,
        prop=jnp.zeros((batch_cap,), jnp.float32),
        marker=jnp.zeros((batch_cap,), bool),
        n=jnp.asarray(0, jnp.int32),
    )
