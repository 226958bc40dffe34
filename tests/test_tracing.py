"""The store's spans on the JAX profiler's clock.

A ``repro.obs`` span opens a profiler TraceMe while a profiler session is
active, so it lands on the trace's ``/host:CPU`` plane, nested on its
thread's line; with no session it only records its histogram.  Also: the
write path's, flush's and WAL's spans, the count of programs built, and
``graph_service --trace DIR``'s profiler helper.  All on the CPU.
"""
from __future__ import annotations

import glob
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from conftest import small_store_cfg
from repro import obs
from repro.obs import registry as obs_registry
from repro.obs.registry import MetricRegistry

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")


def _profile(trace_dir, fn):
    """Run ``fn`` under a profiler session; the host plane's lines, each a
    list of (name, start_ns, end_ns)."""
    jax.profiler.start_trace(str(trace_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    pd = ProfileData.from_file(paths[0])
    (host,) = [p for p in pd.planes if p.name == "/host:CPU"]
    return [[(e.name, e.start_ns, e.end_ns) for e in line.events]
            for line in host.lines]


def _hist(name, **labels):
    return obs.REGISTRY.histogram(name + "_seconds", **labels)


def _ingest(g, n, seed=0, v=1 << 10):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, v, n).astype(np.int32)
    dst = rng.integers(0, v, n).astype(np.int32)
    return g.insert_edges(src, dst)


# ------------------------------------------------------- profiler session
def test_nested_spans_on_the_host_plane(tmp_path):
    reg = MetricRegistry()

    def work():
        with reg.span("t_outer", store="s0"):
            with reg.span("t_inner", store="s0"):
                jnp.ones(3).block_until_ready()

    lines = _profile(tmp_path, work)
    (line,) = [ln for ln in lines
               if {"t_outer", "t_inner"} <= {n for n, _, _ in ln}]
    (outer,) = [ev for ev in line if ev[0] == "t_outer"]
    (inner,) = [ev for ev in line if ev[0] == "t_inner"]
    assert outer[1] <= inner[1] <= inner[2] <= outer[2]
    # The histograms record as they do without a session.
    assert reg.histogram("t_outer_seconds", store="s0").count == 1
    assert reg.histogram("t_inner_seconds", store="s0").count == 1


def test_trace_instant_on_the_host_plane(tmp_path):
    reg = MetricRegistry()

    def work():
        with reg.span("t_around"):
            reg.trace_instant("t_instant", fid=3)

    lines = _profile(tmp_path, work)
    events = [ev for ln in lines for ev in ln]
    (inst,) = [ev for ev in events if ev[0] == "t_instant"]
    (around,) = [ev for ev in events if ev[0] == "t_around"]
    assert around[1] <= inst[1] <= inst[2] <= around[2]
    # An instant records no metric.
    assert [m.name for m in reg.collect()] == ["t_around_seconds"]


def test_span_without_session_records_only_its_histogram(monkeypatch):
    built = []

    class FakeTraceMe:
        @staticmethod
        def is_enabled():
            return False

        def __init__(self, *a, **kw):
            built.append(a)

    monkeypatch.setattr(obs_registry, "_TRACEME", FakeTraceMe)
    reg = MetricRegistry()
    site = reg.span_site("t_quiet", store="s0")
    for _ in range(3):
        with site() as sp:
            pass
        assert sp.ok and sp._tm is None
    reg.trace_instant("t_instant_quiet")
    assert built == []
    assert [m.name for m in reg.collect()] == ["t_quiet_seconds"]
    assert reg.histogram("t_quiet_seconds", store="s0").count == 3


def test_importing_obs_imports_no_jax():
    code = ("import sys, repro.obs\n"
            "with repro.obs.span('t_x'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules and "
            "'jaxlib' not in sys.modules, 'jax imported'\n")
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


# ------------------------------------------------------------ store spans
WRITE_SPANS = ("store_apply", "store_apply_wal", "store_apply_insert",
               "store_apply_sync", "store_flush_check")


def test_write_path_spans_per_chunk(tmp_path):
    from repro.storage import open_store

    cfg = small_store_cfg()
    g = open_store(str(tmp_path / "db"), cfg, wal_sync="off")
    label = g.obs_label
    k = 3
    _ingest(g, k * cfg.batch_cap)
    hists = {n: _hist(n, store=label) for n in WRITE_SPANS}
    for n, h in hists.items():
        assert h.count == k, n
    children = sum(hists[n].sum for n in ("store_apply_wal",
                                          "store_apply_insert",
                                          "store_apply_sync"))
    assert 0 < children <= hists["store_apply"].sum
    g.close()


def test_one_flush_records_each_flush_span_once(tmp_path):
    from repro.storage import open_store

    g = open_store(str(tmp_path / "db"), small_store_cfg(), wal_sync="off")
    label = g.obs_label
    _ingest(g, 512)
    persist = _hist("storage_flush_persist")
    seg_write = _hist("storage_segment_write")
    p0, s0 = persist.count, seg_write.count
    assert g.flush_memgraph() is not None
    for n in ("store_flush", "store_flush_build", "store_flush_index"):
        assert _hist(n, store=label).count == 1, n
    assert persist.count == p0 + 1
    assert seg_write.count == s0 + 1
    g.close()


def test_ack_records_one_wal_ack_per_call(tmp_path):
    from repro.storage import open_store

    g = open_store(str(tmp_path / "db"), small_store_cfg(), wal_sync="batch")
    ack = _hist("storage_wal_ack")
    c0 = ack.count
    seq = _ingest(g, 100)
    g.ack(seq)
    assert ack.count == c0 + 1
    g.ack(seq)           # already durable: returns at once, still timed
    assert ack.count == c0 + 2
    g.close()


def test_programs_built_counts_a_new_shape_once():
    import repro.core.store  # noqa: F401  (installs the counter)

    built = obs.REGISTRY.counter("runtime_programs_built_total")
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.ones(7919, jnp.float32)
    c0 = built.value
    f(x).block_until_ready()
    c1 = built.value
    assert c1 > c0
    f(x).block_until_ready()
    assert built.value == c1


# ------------------------------------------------------- service helper
def test_graph_service_profiled_writes_one_trace(tmp_path):
    from repro.core import LSMGraph
    from repro.launch.graph_service import profiled

    trace_dir = tmp_path / "trace"
    with profiled(str(trace_dir)):
        g = LSMGraph(small_store_cfg())
        _ingest(g, 300)
        g.close()
    paths = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    assert len(paths) == 1, paths
    pd = ProfileData.from_file(paths[0])
    names = {e.name for p in pd.planes if p.name == "/host:CPU"
             for line in p.lines for e in line.events}
    assert "store_apply" in names
    assert {"store_apply_insert", "store_apply_sync"} <= names


@pytest.mark.parametrize("trace_dir", [None, ""])
def test_graph_service_profiled_off_without_a_directory(trace_dir):
    from repro.launch.graph_service import profiled

    with profiled(trace_dir):
        assert not jax.profiler.TraceAnnotation.is_enabled()
