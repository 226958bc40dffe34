"""Amplification ledger and regression-gate tests.

Covers the derived-metrics ledger's byte-exact reconciliation against
``IOCounters`` and ``disk_bytes()``, the dead-series gauge rules, span
outcome recording, Prometheus escaping, the bench_compare regression
gate, and the read-path accounting overhead bound (same microbench
discipline as the disabled-path span check in test_obs.py).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import re
import time
from pathlib import Path

import numpy as np
import pytest

from conftest import small_store_cfg
from repro import obs
from repro.obs.amplification import (AMP_SCHEMA, LOGICAL_EDGE_BYTES,
                                     AmplificationLedger)
from repro.obs.export import export_prometheus
from repro.obs.registry import MetricRegistry


def _ingest(g, n_batches=6, batch=512, seed=0, v=1 << 10):
    rng = np.random.default_rng(seed)
    total = 0
    for _ in range(n_batches):
        src = rng.integers(0, v, batch).astype(np.int64)
        dst = rng.integers(0, v, batch).astype(np.int64)
        g.insert_edges(src, dst)
        total += batch
    return total


# ---------------------------------------------------------------- ledger
def test_logical_edge_bytes_pins_core_constants():
    """obs cannot import core (layering), so the ledger duplicates the
    record size; this pin breaks if the core layout ever changes."""
    from repro.core.types import BYTES_PER_EDGE, BYTES_PER_PROP

    assert LOGICAL_EDGE_BYTES == BYTES_PER_EDGE + BYTES_PER_PROP


def test_ledger_reconciles_durable_io_exact(tmp_path):
    """Integration (satellite 4): durable ingest + flush + compact; the
    ledger's physical-byte parts equal the IOCounters fields and the
    registry mirrors byte-for-byte, and disk accounting is consistent."""
    from repro.storage import open_store

    g = open_store(str(tmp_path / "db"), small_store_cfg(),
                   wal_sync="off")
    n = _ingest(g)
    g.flush_memgraph()
    g.compact_l0()
    led = AmplificationLedger(g)
    rep = led.report(exact_space=True)
    assert rep["schema"] == AMP_SCHEMA
    assert rep["mode"] == "physical"
    w = rep["write"]
    # Exact-byte reconciliation against the IOCounters mirror.
    assert w["physical_bytes"]["wal"] == g.io.wal_write
    assert w["physical_bytes"]["segment"] == g.io.segment_write
    assert w["physical_bytes"]["manifest"] == g.io.manifest_write
    assert w["physical_bytes"]["total"] == (
        g.io.wal_write + g.io.segment_write + g.io.manifest_write)
    assert w["logical_ingest_bytes"] == n * LOGICAL_EDGE_BYTES
    assert w["overall"] == pytest.approx(
        w["physical_bytes"]["total"] / (n * LOGICAL_EDGE_BYTES))
    # Per-level physical bytes: every segment write funnels through the
    # engine, so the level series must sum to the segment counter.
    assert sum(e["bytes"] for e in w["per_level"].values()) == \
        g.io.segment_write
    # Space side reconciles against the store's own disk accounting.
    assert rep["space"]["disk_bytes"] == g.disk_bytes()
    assert rep["space"]["estimate"] is False
    assert rep["space"]["overall"] > 0
    # dataclasses.replace copies stay unbound: no double-count.
    before = obs.REGISTRY.counter(
        "io_wal_write_bytes", store=g.obs_label).value
    copy = dataclasses.replace(g.io)
    copy.wal_write += 12345
    assert obs.REGISTRY.counter(
        "io_wal_write_bytes", store=g.obs_label).value == before
    assert led.write_amplification()["physical_bytes"]["wal"] == before
    g.close()


def test_read_amplification_counters():
    """Batched reads feed queries/probes/returned; touched >= returned and
    runs-per-query reflects the batch-amortized source count."""
    from repro.core import LSMGraph

    g = LSMGraph(small_store_cfg())
    _ingest(g, n_batches=4)
    g.flush_memgraph()
    led = AmplificationLedger(g)
    base = led.read_amplification()
    with g.snapshot() as snap:
        snap.neighbors_batch(np.arange(256, dtype=np.int64))
    r = led.read_amplification()
    assert r["queries"] - base["queries"] >= 256
    assert r["runs_probed"] > base["runs_probed"]
    assert r["bytes_returned"] > base["bytes_returned"]
    assert r["bytes_touched"] >= r["bytes_returned"]
    assert r["overall"] >= 1.0
    assert r["runs_per_query"] > 0
    g.close()


def test_space_estimate_upper_bounds_exact():
    """Duplicate inserts inflate the counter estimate but never deflate
    it below the exact live-edge measure."""
    from repro.core import LSMGraph

    g = LSMGraph(small_store_cfg())
    src = np.arange(256, dtype=np.int64) % 64
    dst = (src * 3 + 1) % 64
    g.insert_edges(src, dst)
    g.insert_edges(src, dst)  # duplicates: estimate counts them twice
    g.flush_memgraph()
    led = AmplificationLedger(g)
    est = led.live_edge_bytes()
    exact = led.live_edge_bytes(exact=True)
    assert est["estimate"] is True and exact["estimate"] is False
    assert est["bytes"] >= exact["bytes"] > 0
    g.close()


def test_empty_store_ratios_are_null_and_gauges_absent():
    """0/0 must export as 'no data' (None / removed series), never 0.0."""
    from repro.core import LSMGraph

    g = LSMGraph(small_store_cfg())
    led = AmplificationLedger(g)
    rep = led.report()
    assert rep["write"]["overall"] is None
    assert rep["read"]["overall"] is None
    led.refresh_gauges()
    assert not obs.REGISTRY.find("amp_write_ratio", store=g.obs_label)
    assert not obs.REGISTRY.find("amp_read_ratio", store=g.obs_label)
    g.close()


def test_refresh_gauges_sets_ratio_series():
    from repro.core import LSMGraph

    g = LSMGraph(small_store_cfg())
    _ingest(g, n_batches=3)
    g.flush_memgraph()
    with g.snapshot() as snap:
        snap.neighbors_batch(np.arange(64, dtype=np.int64))
    AmplificationLedger(g).refresh_gauges()
    w = obs.REGISTRY.find("amp_write_ratio", store=g.obs_label)
    assert any(i.labels.get("level") is None for i in w)   # overall
    assert any(i.labels.get("level") == "0" for i in w)    # per-level
    assert obs.REGISTRY.find("amp_read_ratio", store=g.obs_label)
    assert obs.REGISTRY.find("amp_space_ratio", store=g.obs_label)
    g.close()


def test_shard_health_report_carries_amplification():
    from repro.shard import ShardedGraphStore

    g = ShardedGraphStore(small_store_cfg(), 2)
    # Sources spread over the full vertex range so BOTH shards see edges.
    src = (np.arange(512, dtype=np.int64) * 8) % (1 << 12)
    g.insert_edges(src, (src * 7 + 1) % (1 << 12))
    g.flush_all()
    g.sharded_neighbors_batch(np.arange(64, dtype=np.int64))
    rep = g.health_report()
    assert set(rep) == {0, 1}
    for entry in rep.values():
        amp = entry["amplification"]
        assert set(amp) == {"write", "read", "space", "runs_per_query"}
        assert amp["write"] is not None and amp["write"] > 0
    g.close()


# ------------------------------------------------------- dead series rules
def test_level_gauges_removed_when_level_drains():
    """Satellite 1: a full L0 compaction drains level 0 — its depth and
    runs gauges must disappear from exports, not freeze at stale values."""
    from repro.core import LSMGraph

    # High l0_run_limit: no auto-compaction drains L0 before we look.
    g = LSMGraph(small_store_cfg(l0_run_limit=64))
    _ingest(g, n_batches=3)
    g.flush_memgraph()
    label = g.obs_label
    assert obs.REGISTRY.find("store_l0_depth", store=label)
    assert obs.REGISTRY.find("store_level_runs", store=label, level="0")
    g.compact_l0()   # drains L0 into L1
    assert not obs.REGISTRY.find("store_l0_depth", store=label)
    assert not obs.REGISTRY.find("store_level_runs", store=label,
                                 level="0")
    assert obs.REGISTRY.find("store_level_runs", store=label, level="1")
    g.close()


def test_registry_remove_and_find():
    reg = MetricRegistry()
    reg.gauge("x_depth", store="a", level="0").set(3)
    reg.gauge("x_depth", store="a", level="1").set(5)
    reg.gauge("x_depth", store="b", level="0").set(7)
    assert len(reg.find("x_depth")) == 3
    assert len(reg.find("x_depth", store="a")) == 2
    assert reg.remove("x_depth", store="a", level="0") is True
    assert reg.remove("x_depth", store="a", level="0") is False  # gone
    assert {i.value for i in reg.find("x_depth")} == {5, 7}
    # get-or-create after remove registers a FRESH zero-state instrument
    assert reg.gauge("x_depth", store="a", level="0").value == 0


# ------------------------------------------------------------ span outcome
def test_span_exception_records_outcome_and_counter():
    reg = MetricRegistry()
    sp = reg.span("store_flush", store="s0")
    with pytest.raises(ValueError):
        with sp:
            raise ValueError("boom")
    assert sp.name == "store_flush" and sp.ok is False
    assert reg.counter("store_flush_errors_total", store="s0").value == 1
    # success path: ok True, no extra error count
    with reg.span("store_flush", store="s0") as sp:
        pass
    assert sp.ok is True
    assert reg.counter("store_flush_errors_total", store="s0").value == 1
    # duration histogram observed BOTH exits
    assert reg.histogram("store_flush_seconds",
                         store="s0").snapshot()["count"] == 2


# ------------------------------------------------------ exporter hardening
def test_prometheus_escapes_hostile_labels_roundtrip():
    reg = MetricRegistry()
    hostile = 'pa\\th "quoted"\nnewline'
    reg.counter("io_err_total", path=hostile).inc(3)
    text = export_prometheus(
        reg, help_text={"io_err_total": 'errors \\ by "path"\nline2'})
    # One metric line, one TYPE line, one HELP line — no line breaks leak.
    lines = text.strip().splitlines()
    assert len(lines) == 3
    help_line, type_line, metric = lines
    assert help_line == \
        '# HELP io_err_total errors \\\\ by "path"\\nline2'
    assert type_line == "# TYPE io_err_total counter"
    m = re.match(r'io_err_total\{path="(.*)"\} 3$', metric)
    assert m, metric
    unescaped = (m.group(1).replace("\\n", "\n").replace('\\"', '"')
                 .replace("\\\\", "\\"))
    assert unescaped == hostile


# -------------------------------------------------------- regression gate
def _load_bench_compare():
    path = Path(__file__).resolve().parents[1] / "tools" / "bench_compare.py"
    spec = importlib.util.spec_from_file_location("bench_compare", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _traj(us=1000.0, amp=2.0):
    return {
        "schema": "lsmg-bench-trajectory-v1", "pr": 9,
        "suites": {"update/lsmgraph": {"us_per_call": us, "derived": ""},
                   "tiny/noise": {"us_per_call": 1.0, "derived": ""}},
        "amplification": {
            "durable": {"write": {"overall": amp},
                        "read": {"overall": 1.5},
                        "space": {"overall": None}}},
    }


def test_bench_compare_self_passes_inflation_fails():
    bc = _load_bench_compare()
    kw = dict(threshold=0.30, amp_threshold=0.25, min_us=50.0)
    same = bc.compare(_traj(), _traj(), **kw)
    assert same["regressions"] == []
    worse = bc.compare(_traj(), _traj(us=10000.0, amp=20.0), **kw)
    assert len(worse["regressions"]) == 2      # row + write-amp
    assert any("update/lsmgraph" in r for r in worse["regressions"])
    assert any("write-amp" in r for r in worse["regressions"])
    # sub-noise-floor rows never gate, None ratios never gate
    noise = bc.compare(_traj(), _traj(us=1000.0), **kw)
    assert noise["regressions"] == []


# ------------------------------------------------------- overhead budget
def test_read_accounting_overhead_bounded():
    """The resolve wrapper's additions (3 counter incs + the
    ``read_resolve`` span with no profiler session) must stay far below
    resolve cost — same discipline as the disabled-path span check."""
    from repro.core import LSMGraph

    g = LSMGraph(small_store_cfg())
    n = 20_000

    def accounting():
        q, p, r = (g._obs_read_queries, g._obs_read_probes,
                    g._obs_read_returned)
        span = g._span_resolve
        t0 = time.perf_counter()
        for _ in range(n):
            with span():
                q.inc(64)
            p.inc(5)
            r.inc(1280)
        return time.perf_counter() - t0

    per_call = min(accounting() for _ in range(3)) / n
    assert per_call < 60e-6, \
        f"read accounting costs {per_call*1e6:.2f}us per resolve"
    g.close()


# ------------------------------------------------------- reporter refresh
def test_reporter_refresh_hooks_run_and_drop_on_error():
    from repro.obs.export import Reporter

    reg = MetricRegistry()
    calls = {"ok": 0, "bad": 0}

    def ok():
        calls["ok"] += 1

    def bad():
        calls["bad"] += 1
        raise RuntimeError("refresh broke")

    docs = []
    rep = Reporter(reg, interval=999.0, sink=docs.append,
                   refresh=[ok, bad])
    rep._export()
    rep._export()
    assert calls["ok"] == 2
    assert calls["bad"] == 1          # dropped after the first failure
    rep.start()
    rep.stop()                        # final export still runs hooks
    assert calls["ok"] == 3
    assert len(docs) == 1
