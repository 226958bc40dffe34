"""Observability layer: registry primitives under concurrency, histogram
percentile accuracy against numpy, the near-zero disabled-path cost
contract, exporter schemas, spans, and the instrumented-store integration
surfaces (IOCounters mirror, MergeStats view, per-layer metric families).
The spans on the profiler's trace are tested in test_tracing.py."""
import json
import threading
import time

import numpy as np
import pytest

from conftest import small_store_cfg
from repro import obs
from repro.obs import (SCHEMA, Reporter, export_json, export_prometheus)
from repro.obs.registry import Histogram, MetricRegistry


# ----------------------------------------------------------- registry core
def test_counter_concurrent_exact():
    reg = MetricRegistry()
    c = reg.counter("t_hits_total", worker="w")
    n_threads, per = 8, 10_000

    def work():
        for _ in range(per):
            c.inc()

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # Linearizable counting: no lost updates under contention.
    assert c.value == n_threads * per
    c.inc(42)
    assert c.value == n_threads * per + 42


def test_histogram_concurrent_observe_exact():
    reg = MetricRegistry()
    h = reg.histogram("t_latency_seconds")
    n_threads, per = 8, 5_000

    def work(seed):
        rng = np.random.default_rng(seed)
        for x in rng.uniform(1e-5, 1e-2, per):
            h.observe(float(x))

    ts = [threading.Thread(target=work, args=(i,)) for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    snap = h.snapshot()
    assert snap["count"] == n_threads * per
    assert 0 < snap["min"] <= snap["p50"] <= snap["p99"] <= snap["max"]


def test_gauge_set_inc_dec():
    reg = MetricRegistry()
    g = reg.gauge("t_depth", level="0")
    g.set(5)
    assert g.value == 5
    g.inc(2)
    g.dec()
    assert g.value == 6


def test_registry_identity_and_kind_mismatch():
    reg = MetricRegistry()
    a = reg.counter("t_x_total", shard="0")
    assert reg.counter("t_x_total", shard="0") is a
    assert reg.counter("t_x_total", shard="1") is not a
    with pytest.raises(TypeError):
        reg.gauge("t_x_total", shard="0")


# ----------------------------------------------------- histogram accuracy
def test_histogram_percentiles_vs_numpy():
    """Log-bucket estimates must land within one bucket ratio of numpy's
    exact percentiles: buckets_per_decade=20 bounds any in-range estimate
    to a factor of 10**(1/20) ~ 1.122 of the true value."""
    rng = np.random.default_rng(11)
    xs = rng.lognormal(mean=-6.0, sigma=1.2, size=50_000)
    reg = MetricRegistry()
    h = reg.histogram("t_acc_seconds")
    for x in xs:
        h.observe(float(x))
    ratio = 10.0 ** (1.0 / 20.0)
    for p in (50.0, 99.0, 99.9):
        true = float(np.percentile(xs, p))
        est = h.percentile(p)
        assert true / ratio <= est <= true * ratio, (p, true, est)
    snap = h.snapshot()
    assert snap["count"] == len(xs)
    assert snap["min"] == pytest.approx(xs.min())
    assert snap["max"] == pytest.approx(xs.max())
    assert snap["sum"] == pytest.approx(xs.sum(), rel=1e-6)


def test_histogram_empty_and_clamping():
    reg = MetricRegistry()
    h = reg.histogram("t_edge_seconds", lo=1e-3, hi=1e0)
    assert h.percentile(50) == 0.0
    assert h.snapshot()["count"] == 0
    # Out-of-range observations clamp into edge buckets but min/max stay
    # exact, and percentiles stay inside the observed envelope.
    h.observe(1e-9)
    h.observe(50.0)
    snap = h.snapshot()
    assert snap["count"] == 2
    assert snap["min"] == pytest.approx(1e-9)
    assert snap["max"] == pytest.approx(50.0)
    assert snap["min"] <= h.percentile(50) <= snap["max"]


# ------------------------------------------------------------------ spans
def test_span_observes_duration_histogram():
    reg = MetricRegistry()
    with reg.span("t_op", store="s0") as sp:
        time.sleep(0.01)
    assert sp.duration >= 0.01
    snap = reg.histogram("t_op_seconds", store="s0").snapshot()
    assert snap["count"] == 1
    assert snap["min"] >= 0.01


def test_disabled_path_overhead():
    """The no-exporter/no-profiler hot path must stay near-free: one span
    is two perf_counter calls, one locked histogram update, and exactly one
    profiler enabled check.  Bound the per-op cost so a store doing thousands of
    instrument ops per ingest chunk (each chunk ~milliseconds of apply
    work) stays well under a 2% overhead envelope."""
    reg = MetricRegistry()
    c = reg.counter("t_ov_total")
    n = 20_000

    def best_of(runs, fn):
        best = float("inf")
        for _ in range(runs):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    def counters():
        for _ in range(n):
            c.inc()

    def spans():
        for _ in range(n):
            with reg.span("t_ov"):
                pass

    per_inc = best_of(3, counters) / n
    per_span = best_of(3, spans) / n
    # Generous CI-safe bounds; typical measured costs are ~0.2us and ~2us.
    assert per_inc < 20e-6, f"counter.inc cost {per_inc*1e6:.2f}us"
    assert per_span < 60e-6, f"span cost {per_span*1e6:.2f}us"


# -------------------------------------------------------------- exporters
def _sample_registry():
    reg = MetricRegistry()
    reg.counter("store_ops_total", store="s0").inc(7)
    reg.gauge("store_l0_depth", store="s0").set(3)
    h = reg.histogram("read_resolve_seconds")
    for x in (1e-4, 2e-4, 5e-3):
        h.observe(x)
    return reg


def test_export_json_schema_roundtrip():
    reg = _sample_registry()
    doc = json.loads(json.dumps(export_json(reg)))  # must be JSON-clean
    assert doc["schema"] == SCHEMA
    assert set(doc["families"]) == {"store", "read"}
    store_fam = doc["families"]["store"]
    (ops_entry,) = store_fam["ops_total"]
    assert ops_entry["type"] == "counter"
    assert ops_entry["value"] == 7
    assert ops_entry["labels"] == {"store": "s0"}
    (depth_entry,) = store_fam["l0_depth"]
    assert depth_entry["type"] == "gauge" and depth_entry["value"] == 3
    (hist_entry,) = doc["families"]["read"]["resolve_seconds"]
    assert hist_entry["type"] == "histogram"
    assert hist_entry["count"] == 3
    for k in ("sum", "min", "max", "p50", "p99", "p999"):
        assert k in hist_entry


def test_export_prometheus_text():
    text = export_prometheus(_sample_registry())
    assert "# TYPE store_ops_total counter" in text
    assert 'store_ops_total{store="s0"} 7' in text
    assert "# TYPE store_l0_depth gauge" in text
    assert "read_resolve_seconds_count 3" in text
    assert 'read_resolve_seconds{quantile="0.99"}' in text
    # every sample line is `name[{labels}] value`
    for line in text.splitlines():
        if line and not line.startswith("#"):
            assert len(line.rsplit(" ", 1)) == 2


def test_reporter_thread_periodic_and_final():
    reg = _sample_registry()
    got = []
    rep = Reporter(reg, interval=0.05, sink=got.append).start()
    time.sleep(0.2)
    rep.stop()
    assert len(got) >= 2  # at least one periodic + the final report
    assert all(d["schema"] == SCHEMA for d in got)
    assert not rep._thread.is_alive()


# ------------------------------------------------- store integration views
def test_iocounters_mirror_durable_manifest_bytes(tmp_path):
    """A durable store's IOCounters mirror into labeled registry counters,
    including the new manifest_write funnel (the engine's 'open' record
    lands before the store exists and must still be credited)."""
    from repro.storage import open_store

    g = open_store(str(tmp_path / "db"), small_store_cfg(), wal_sync="off")
    src = np.arange(512, dtype=np.int32)
    dst = (src * 7 + 1) % 512
    g.insert_edges(src, dst)
    g.flush_memgraph()
    io = g.io
    assert io.manifest_write > 0
    assert io.wal_write > 0 and io.segment_write > 0
    label = g.obs_label
    for field in ("manifest_write", "wal_write", "segment_write"):
        c = obs.REGISTRY.counter(f"io_{field}_bytes", store=label)
        assert c.value == getattr(io, field), field
    # snapshot()-style copies (dataclasses.replace) must come back unbound:
    # mutating a copy must not double-count into the registry.
    import dataclasses
    copy = dataclasses.replace(io)
    before = obs.REGISTRY.counter("io_wal_write_bytes", store=label).value
    copy.wal_write += 999
    assert obs.REGISTRY.counter(
        "io_wal_write_bytes", store=label).value == before
    g.close()


def test_merge_stats_registry_view():
    """MERGE_STATS keeps its mapping/reset surface while the backing
    registry counters stay monotonic across reset()."""
    from repro.kernels.merge import MERGE_STATS

    MERGE_STATS.reset()
    assert MERGE_STATS["kernel_merge"] == 0
    base = obs.REGISTRY.counter("merge_kernel_merge_total").value
    MERGE_STATS.bump("kernel_merge")
    MERGE_STATS.bump("kernel_merge")
    assert MERGE_STATS["kernel_merge"] == 2
    assert dict(MERGE_STATS)["kernel_merge"] == 2
    assert obs.REGISTRY.counter(
        "merge_kernel_merge_total").value == base + 2
    MERGE_STATS.reset()
    assert MERGE_STATS["kernel_merge"] == 0
    # registry counter did NOT rewind
    assert obs.REGISTRY.counter(
        "merge_kernel_merge_total").value == base + 2


def test_store_emits_per_layer_families():
    """End-to-end: a store exercising apply/flush/read paths populates the
    store/io/merge/read families the report schema promises."""
    from repro.core import LSMGraph

    g = LSMGraph(small_store_cfg())
    label = g.obs_label
    rng = np.random.default_rng(5)
    for i in range(4):
        src = rng.integers(0, 1 << 10, 600).astype(np.int32)
        dst = rng.integers(0, 1 << 10, 600).astype(np.int32)
        g.insert_edges(src, dst)
    g.flush_memgraph()
    with g.snapshot() as snap:
        snap.neighbors_batch(np.arange(64, dtype=np.int64))
    doc = export_json(obs.REGISTRY)
    fams = doc["families"]
    for fam in ("store", "io", "merge", "read"):
        assert fam in fams, fam
    assert obs.REGISTRY.counter(
        "store_state_publish_total", store=label).value > 0
    assert obs.REGISTRY.histogram(
        "store_apply_seconds", store=label).snapshot()["count"] > 0
    assert obs.REGISTRY.histogram(
        "read_resolve_seconds", store=label).snapshot()["count"] > 0
    g.close()


def test_concurrent_background_error_surfaced():
    """Satellite 1: a background-thread failure is captured structurally
    (work item, repr, traceback), bumps the error counter, and surfaces
    through the _check raise chain — no print-and-swallow."""
    from repro.core.concurrent import ConcurrentLSMGraph

    g = ConcurrentLSMGraph(small_store_cfg())
    before = obs.REGISTRY.counter(
        "store_background_errors_total", thread="writer").value
    # Poison the writer: _apply_no_flush will explode on a bad batch shape.
    g.store._apply_no_flush = None  # type: ignore[assignment]
    g._q.put(("insert", np.array([1]), np.array([2]), None))
    for _ in range(200):
        if g._error is not None:
            break
        time.sleep(0.01)
    assert g._error is not None
    with pytest.raises(RuntimeError, match="background thread failed"):
        g._check()
    err = g.last_errors["writer"]
    assert "insert batch of 1" == err["work"]
    assert "TypeError" in err["error"] or "TypeError" in err["traceback"]
    assert obs.REGISTRY.counter(
        "store_background_errors_total", thread="writer").value == before + 1
