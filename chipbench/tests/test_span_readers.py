"""Tests of the per-layer readers of the write path's, flush's and WAL's
spans and of the programs-built counter, on hand-built run data.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest chipbench/tests
"""
from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run  # noqa: E402
from chipbench.bench import RunData  # noqa: E402

# metric -> (histogram it reads, scale to the metric's unit)
SPAN_READERS = {
    "apply_wal_ms": ("store_apply_wal_seconds", 1e3),
    "apply_dispatch_ms": ("store_apply_insert_seconds", 1e3),
    "apply_sync_ms": ("store_apply_sync_seconds", 1e3),
    "flush_check_ms": ("store_flush_check_seconds", 1e3),
    "flush_build_s": ("store_flush_build_seconds", 1.0),
    "ack_wait_ms": ("storage_wal_ack_seconds", 1e3),
}
COUNTER = "runtime_programs_built_total"


def _run_data(obs: dict) -> RunData:
    return RunData(obs=obs, spans=[], trace=None, info={}, peaks={},
                   window_s=20.0)


def _read(metric: str, obs: dict):
    mod = run._load_module(run.metric_path(metric),
                           "m_" + metric.replace(".", "_"))
    return mod.read(_run_data(obs))


@pytest.mark.parametrize("metric", sorted(SPAN_READERS))
def test_span_reader_is_the_window_mean(metric):
    hist, scale = SPAN_READERS[metric]
    got = _read(metric, {hist: (4, 0.010), "other_seconds": (1, 9.0)})
    assert got == pytest.approx(0.010 / 4 * scale)


@pytest.mark.parametrize("metric", sorted(SPAN_READERS))
def test_span_reader_is_silent_without_observations(metric):
    hist, _ = SPAN_READERS[metric]
    assert _read(metric, {hist: (0, 0.0)}) is None
    assert _read(metric, {}) is None       # a program without the span


def test_programs_built_reads_the_window_delta():
    assert _read("programs_built.ingest", {COUNTER: 6}) == 6
    assert _read("programs_built.ingest", {COUNTER: 0}) == 0
    assert _read("programs_built.ingest", {}) is None


def test_programs_built_is_zero_for_an_unchanged_counter():
    delta = run.obs_delta({COUNTER: 41}, {COUNTER: 41})
    assert _read("programs_built.ingest", delta) == 0
