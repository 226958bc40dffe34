"""repro.obs — the store-wide observability layer.

One process-wide ``MetricRegistry`` (``REGISTRY``) holds every counter,
gauge, and latency histogram in the system; ``span(...)`` times scopes
into duration histograms and, while a JAX profiler session is active,
onto the profiler's trace.  ``export_json``/``export_prometheus``
snapshot the whole registry; ``Reporter`` does so periodically.  The compaction
scheduler, the adaptive LSM tuner, and the serving front end (ROADMAP)
all read from here rather than growing their own ad-hoc state.

Observability model
===================

**Naming.** ``<layer>_<what>[_<unit>]``, lower_snake_case.  The first
token is the owning layer and becomes the family in the hierarchical
JSON export.  Counters of discrete events end in ``_total``; byte
counters in ``_bytes``; duration histograms in ``_seconds`` (``span``
appends it automatically); unit-less gauges (depths, 0/1 flags) carry no
unit suffix.

**Layer ownership.**  A metric is registered and written by exactly one
layer — readers go through the exporter, never by reaching into another
layer's instruments:

* ``store_*``  — core/store.py + core/concurrent.py: apply/flush/
  compaction spans, ``store_state_publish_total``, ``store_l0_depth`` and
  ``store_level_runs`` gauges, background-thread error counts.  The
  write path's spans, per chunk of at most ``batch_cap`` records:
  ``store_apply`` (the chunk), inside it ``store_apply_wal`` (WAL record
  encode + append under the commit lock), ``store_apply_insert``
  (padding, host-to-device put, dispatch of the MemGraph insert) and
  ``store_apply_sync`` (the wait for the insert's ok flag); after it
  ``store_flush_check`` (the flush trigger's device scalar reads).
  Inside ``store_flush``: ``store_flush_build`` (flush arrays, CSR run
  build and repad, the run file's host metadata, with any compiles they
  trigger) and ``store_flush_index`` (the multi-level index update).
* ``storage_*`` — storage/wal.py + storage/engine.py: the spans
  ``storage_wal_append``, ``storage_wal_fsync``, ``storage_wal_ack``
  (the whole ``sync_upto``: how long an acking client blocks, including
  behind an in-flight group commit), ``storage_flush_persist`` (a
  flush's segment write, manifest edit and WAL prune) and
  ``storage_segment_write``; group-commit batch size, segment
  load/evict, scrubber verdicts, quarantine counts.
* ``shard_*``  — shard/store.py: per-shard fencing state, ack latency,
  degraded-range count, routed-batch fan-out.
* ``read_*``   — the read path (core/store.py resolve + core/types.py
  prefetch): the ``read_resolve`` span (one per batched resolve),
  prefetch hit/miss, and the presence-filter counters —
  ``read_filter_checked_total`` ((run, query) pairs
  tested against a run's vertex-presence filter),
  ``read_filter_skipped_total`` (pairs the filter proved absent — device
  work and, on the per-run paths, cold segment loads avoided),
  ``read_filter_false_positive_total`` (filter said "maybe", the gather
  found nothing; observable on the scalar path only).  All three carry
  ``store=``; skipped/checked is the filter's live selectivity, and
  false-positive/checked calibrates the bits-per-key budget.
* ``compaction_*`` — shard/scheduler.py: the amplification-driven
  scheduler's decision stream.  ``compaction_sched_decision_total``
  (``decision=`` ``compact`` | ``skip_hot`` | ``skip_backoff`` | ``idle``
  — a closed enum), ``compaction_sched_compactions_total`` (``shard=``),
  and the ``compaction_sched_interval_seconds`` gauge tracking the
  backoff-widened tick.  Written only by the scheduler thread.
* ``io_*``     — the ``IOCounters`` mirror (core/types.py): byte counters
  kept byte-compatible with the legacy dataclass API.
* ``merge_*``  — the ``MERGE_STATS`` view (kernels/merge.py): kernel-vs-
  host merge branch counts, spine build/splice/reuse.
* ``runtime_*`` — compile_cache.py: ``runtime_programs_built_total``,
  every program this process compiled or loaded from the persistent
  compilation cache (one per ``jax.monitoring`` backend-compile event,
  which a cache load raises too).  Installed when the store is imported.
* ``amp_*``    — derived amplification gauges (obs/amplification.py):
  written ONLY by ``AmplificationLedger.refresh_gauges`` — never by a
  hot path.

**Derived metrics (amplification).**  ``obs/amplification.py`` turns raw
counters into the paper's evaluation ratios: write amplification
(physical WAL + segment + manifest bytes ÷ ``store_logical_ingest_bytes``,
overall and per level via ``storage_level_write_bytes``; in-memory
stores use the flush/compaction/index logical proxy), read amplification
(``io_analytics_read_bytes`` touched ÷ ``read_returned_bytes``, plus
``read_runs_probed_total``/``read_queries_total`` runs-per-query), and
space amplification (``disk_bytes()`` ÷ live edge bytes).  Rules for
ratio gauges: family ``amp``, suffix ``_ratio`` (the one sanctioned
unit-less suffix — a ratio IS the unit), runs-per-query gauges carry no
suffix; values are REFRESHED from counters (``refresh_gauges``, hooked
into ``Reporter``), never incremented; an empty-denominator series is
REMOVED (``MetricRegistry.remove``), not set to 0 — "no data" must not
export as "no amplification".  The JSON report form is schema
``lsmg-amp-v1`` (``AmplificationLedger.report``).

**Dead series.**  A gauge whose subject disappears (a level emptied by a
full compaction, a ratio losing its denominator) is removed via
``MetricRegistry.remove`` at the owning commit point, so exporters stop
reporting it; stale last values never outlive their subject.

**Trace export.**  There is one trace: the JAX profiler's.  While a
profiler session is active (``jax.profiler.start_trace``, or
``graph_service --trace DIR``), every span opens a profiler TraceMe
named by the span (labels ride as its metadata) and every
``trace_instant`` (flush rotate/commit, compaction commit, WAL rotate,
quarantine, rebuild, shard fence) is a zero-length one.  They land in
the session's ``.xplane.pb`` on the ``/host:CPU`` plane, one line per
thread, nested with JAX's own dispatch, transfer and compile spans and
on the same clock as the device's operations; Perfetto or TensorBoard
opens it.  A span that triggers a compile shows the compile nested
under it.

**Label cardinality.**  Labels multiply series; every label must be
bounded by configuration, never by data.  Allowed: store ordinal
(``store="s0"``), shard index (``shard="3"``), level (``level="1"``),
small closed enums (``verdict="healed"``).  Forbidden: vertex ids, seq
numbers, file ids, timestamps — anything that grows with the workload
belongs in a histogram observation or a trace event, not a label.

**Cost.**  Instruments are cached at call sites (module- or
instance-level attributes; spans as a ``SpanSite``), so hot paths pay
one lock + one add — never a registry map lookup.  With no profiler
session a span pays two ``perf_counter`` calls, one histogram observe
and one ``TraceMe.is_enabled()`` check (about 0.1 us); a TraceMe is
built only inside a session.  This package imports nothing of JAX: it
finds the TraceMe class once JAX has loaded it.  ``tests/test_obs.py``
enforces the per-op bound and the < 2% ingest overhead budget.
"""
from .registry import (Counter, Gauge, Histogram, MetricRegistry, Span,
                       SpanSite)
from .export import SCHEMA, Reporter, export_json, export_prometheus

#: The process-wide default registry every production call site uses.
REGISTRY = MetricRegistry()

# Derived layers import lazily-resolved REGISTRY, so they must come after
# its definition.
from .amplification import (AMP_SCHEMA, AmplificationLedger,  # noqa: E402
                            shard_amplification)


def counter(name: str, **labels) -> Counter:
    return REGISTRY.counter(name, **labels)


def gauge(name: str, **labels) -> Gauge:
    return REGISTRY.gauge(name, **labels)


def histogram(name: str, **labels) -> Histogram:
    return REGISTRY.histogram(name, **labels)


def span(name: str, **labels) -> Span:
    return REGISTRY.span(name, **labels)


def span_site(name: str, **labels) -> SpanSite:
    return REGISTRY.span_site(name, **labels)


__all__ = [
    "REGISTRY", "SCHEMA", "AMP_SCHEMA", "MetricRegistry", "Counter",
    "Gauge", "Histogram", "Span", "SpanSite", "Reporter",
    "AmplificationLedger", "export_json", "export_prometheus",
    "shard_amplification", "counter", "gauge", "histogram", "span",
    "span_site",
]
