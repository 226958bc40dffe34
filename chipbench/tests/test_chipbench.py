"""Tests of the chip benchmark on the CPU, at small sizes.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest chipbench/tests

They check that every name in ``BENCHMARK.json`` resolves to its files,
that each traffic mix runs end to end through the harness and its
comparison passes, that the comparison fails for the controls and for a
timed path broken in each way the cell can break, that the trace
reduction reads a recorded chip trace, and that the run refuses to
report without a TPU.
"""
from __future__ import annotations

import copy
import gzip
import json
import os
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import (control, graphgen, kernel_cost, reference,  # noqa: E402
                       run, trace_reduce)
from chipbench.fsync_ledger import FsyncLedger  # noqa: E402

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
SEED = 2 ** 31 + 12345        # larger than 32 signed bits hold
SCALE = 10
MEM_BYTES = 20 * 20000        # a 20,000-record MemGraph: flushes at scale 10


def small(cell: run.Cell) -> run.Cell:
    cfg = copy.deepcopy(cell.config)
    cfg["graph"]["scale"] = SCALE
    cfg["store"]["mem_bytes"] = MEM_BYTES
    return cell._replace(config=cfg)


def execute(name: str, tmp_path, trace: bool = False, seconds: float = 2.0,
            seed: int = SEED) -> dict:
    import jax
    cell = small(run.resolve_cell(name))
    return run.execute(cell, seed, seconds, trace, devices=jax.devices(),
                       data_dir=str(tmp_path), log=lambda msg: None)


# ------------------------------------------------------------------ names
def test_every_name_resolves_to_its_files():
    for w in SPEC["workloads"]:
        cell = run.resolve_cell(w["name"])
        assert os.path.isfile(run.driver_path(cell.traffic["driver"]))
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
    for c in SPEC["configs"]:
        path = os.path.join(ROOT, c["file"])
        assert path.startswith(os.path.join(ROOT, "chipbench") + os.sep)
        conf = json.load(open(path))
        assert set(c["reduced"]) == set(conf["reduced"])
    for m in SPEC["per_layer"]:
        mod = run._load_module(run.metric_path(m["name"]), "m")
        assert callable(mod.read)
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        for cell in m["workloads"]:
            assert cell in CELLS


def test_unknown_device_kind_is_an_error():
    assert run.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run.load_peaks("TPU v9 imaginary")


# ------------------------------------------------------------ whole runs
@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(name, tmp_path):
    out = execute(name, tmp_path)
    line = json.loads(json.dumps(out))
    assert RESULT_KEYS <= set(line) and list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    cell = run.resolve_cell(name)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_program_metrics(name, tmp_path):
    out = execute(name, tmp_path, trace=True)
    assert out["correct"] is True
    cell = run.resolve_cell(name)
    got = set(out["metrics"])
    assert got <= {m["name"] for m in cell.per_layer}
    # The CPU has no TPU plane: every device_trace metric stays silent.
    assert got == {m["name"] for m in cell.per_layer
                   if m["source"] != "device_trace"}


def test_no_tpu_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


# ------------------------------------------------------- faults, controls
def _patch_insert(monkeypatch, fn):
    from repro.core.store import LSMGraph
    orig = LSMGraph.insert_edges

    def insert(self, src, dst, prop=None):
        src, dst = fn(np.asarray(src), np.asarray(dst), self.cfg.vmax)
        return orig(self, src, dst, prop)
    monkeypatch.setattr(LSMGraph, "insert_edges", insert)


INGEST_FAULTS = {
    # the MemGraph insert returns its state unchanged
    "state_unchanged": lambda mp: mp.setattr(
        "repro.core.store.LSMGraph._insert_batch",
        lambda self, mem, *a: (mem, True)),
    "half_batch_left_out": lambda mp: _patch_insert(
        mp, lambda s, d, n: (s[:len(s) // 2], d[:len(d) // 2])),
    "record_altered": lambda mp: _patch_insert(
        mp, lambda s, d, n: (s, np.r_[(d[:1] + 1) % n, d[1:]])),
    # the ack returns before any fsync covers the request
    "ack_skips_fsync": lambda mp: mp.setattr(
        "repro.core.store.LSMGraph.ack", lambda self, seq: None),
}


def _patch_analytics(monkeypatch, name, wrap):
    import repro.analytics as an
    monkeypatch.setattr(an, name, wrap(getattr(an, name)))


def _half_view(pagerank):
    def pr(view, **kw):
        import jax.numpy as jnp
        half = view.dst.shape[0] // 2
        return pagerank(view._replace(
            voff=jnp.minimum(view.voff, half), dst=view.dst[:half],
            prop=view.prop[:half], n_edges=half), **kw)
    return pr


ANALYTICS_FAULTS = {
    # PageRank returns its starting state: the iterations never ran
    "state_unchanged": lambda mp: _patch_analytics(
        mp, "pagerank", lambda f: lambda view, iters, d: f(view, iters=0,
                                                           d=d)),
    "half_batch_left_out": lambda mp: _patch_analytics(
        mp, "pagerank", _half_view),
    "answer_altered": lambda mp: _patch_analytics(
        mp, "bfs", lambda f: lambda view, s: f(view, s).at[s].add(1.0)),
}

FAULTS = ([("durable-ingest", k) for k in INGEST_FAULTS]
          + [("inmem-analytics", k) for k in ANALYTICS_FAULTS])
FAULT_TABLE = {"durable-ingest": INGEST_FAULTS,
               "inmem-analytics": ANALYTICS_FAULTS}


@pytest.mark.parametrize("name,fault", FAULTS)
def test_broken_timed_path_is_not_correct(name, fault, tmp_path,
                                          monkeypatch):
    FAULT_TABLE[name][fault](monkeypatch)
    out = execute(name, tmp_path, seconds=1.0)
    assert out["correct"] is False, out["checks"]
    assert out["failed"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_a_limit(name, tmp_path):
    import jax
    cell = small(run.resolve_cell(name))
    readings = control.CONTROLS[cell.traffic["driver"]](
        cell, SEED, 1.0, devices=jax.devices(), data_dir=str(tmp_path),
        log=lambda msg: None)
    limits = cell.traffic.get("limits", {})
    assert any(v > limits.get(k, 0) for k, v in readings.items()), readings


# ------------------------------------------------------ yardstick pieces
def test_generator_is_the_programs():
    from repro.data import graphgen as program
    a = graphgen.rmat_edges(12, 5000, seed=7)
    b = program.rmat_edges(12, 5000, seed=7)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    ours = list(graphgen.update_stream(*a, seed=0))
    theirs = list(program.update_stream(*b))
    assert len(ours) == len(theirs)
    assert all(o[0] == t[0] and np.array_equal(o[1], t[1])
               and np.array_equal(o[2], t[2]) for o, t in zip(ours, theirs))


def test_stream_is_a_function_of_the_seed():
    g = json.load(open(os.path.join(
        BENCH, "configs", "g500-s18-durable.json")))["graph"]
    g = dict(g, scale=8)
    one = graphgen.stream_blocks(g, SEED, 60000)
    two = graphgen.stream_blocks(g, SEED, 60000)
    other = graphgen.stream_blocks(g, SEED + 1, 60000)
    assert graphgen.n_records(one) >= 60000
    assert all(np.array_equal(a.src, b.src) for a, b in zip(one, two))
    assert not np.array_equal(one[0].src, other[0].src)
    assert graphgen.stream_blocks(g, -3, 1)[0].src.size > 0


def test_loaded_graph_is_one_graph_relabelled_by_the_seed():
    g = json.load(open(os.path.join(
        BENCH, "configs", "g500-s18-inmem.json")))["graph"]
    g = dict(g, scale=8)
    t = {"graph_seed": 5, "preload_share": 1.0}
    one = graphgen.loaded_graph(g, t, SEED)
    two = graphgen.loaded_graph(g, t, SEED + 1)
    assert [op.delete for op in one] == [op.delete for op in two]
    assert not np.array_equal(one[0].src, two[0].src)
    deg = [np.sort(reference.Reference(ops, 1 << 8).deg) for ops in (one, two)]
    assert np.array_equal(*deg) and deg[0].sum() > 0


def test_reference_semantics_and_comparison():
    ops = [graphgen.Op(False, np.array([0, 0, 1]), np.array([2, 1, 0])),
           graphgen.Op(True, np.array([0]), np.array([2])),
           graphgen.Op(False, np.array([2]), np.array([0]))]
    ref = reference.Reference(ops, 3)
    assert ref.neighbors(0).tolist() == [1]
    assert ref.neighbors(2).tolist() == [0]
    vs = np.array([0, 1, 2, 0])
    good = [np.array([1]), np.array([0]), np.array([0]), np.array([1])]
    bad, _ = reference.adjacency_wrong(ref, vs, good)
    assert not bad.any()
    wrong = [np.array([1]), np.array([2]), np.array([]), np.array([1])]
    bad, detail = reference.adjacency_wrong(ref, vs, wrong)
    assert bad.tolist() == [False, True, True, False] and "vertex 1" in detail
    bad, _ = reference.csr_wrong(ref, [0, 1, 2, 3], [1, 0, 0])
    assert not bad.any()


def test_pagerank_bytes_on_a_hand_counted_csr():
    # 3 vertices, edges 0->1, 0->2, 1->2, 2->0: per iteration the edge
    # targets (4 x 4 B), the offsets (4 x 4 B), x read and written
    # (2 x 3 x 4 B): 56 B; 10 iterations 560 B.
    assert kernel_cost.pagerank_bytes(4, 3, 1) == 16 + 16 + 24
    assert kernel_cost.pagerank_bytes(4, 3, 10) == 560


def test_fsync_ledger_covers_and_cuts(tmp_path):
    root = tmp_path / "store"
    root.mkdir()
    ledger = FsyncLedger(str(root)).install()
    try:
        fd = os.open(str(root / "log"), os.O_WRONLY | os.O_CREAT, 0o644)
        os.write(fd, b"abc")
        os.fsync(fd)
        ledger.mark()
        assert ledger.uncovered() == 0
        os.write(fd, b"defg")
        assert ledger.uncovered() == 1
        image = ledger.image()
        os.fsync(fd)
        assert ledger.uncovered() == 0
        os.close(fd)
        (root / "later").write_bytes(b"x")
    finally:
        ledger.uninstall()
    assert image == {str(root / "log"): 3}
    ledger.cut(image)
    assert sorted(p.name for p in root.iterdir()) == ["log"]
    assert (root / "log").read_bytes() == b"abc"


def test_trace_reduce_on_a_toy_trace():
    host = [("bench.window", 0, 100), ("bench.write", 5, 35),
            ("PjitFunction(insert)", 8, 12), ("bench.ack", 35, 60)]
    ops = [("p:a", 10, 20), ("p:b", 15, 30), ("q:c", 40, 50),
           ("q:late", 100, 120)]
    programs = [("p", 10, 30), ("q", 40, 50), ("q", 100, 120)]
    r = trace_reduce.reduce(trace_reduce.Trace(
        {"/device:TPU:0": ops}, {"/device:TPU:0": programs}, host))
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(30e-9)
    assert r.span_device_s == pytest.approx({"write": 20e-9, "ack": 10e-9})
    assert r.span_count == {"write": 1, "ack": 1}
    assert r.breakdown["device_ops"] == [["p", pytest.approx(20e-9)],
                                         ["q", pytest.approx(10e-9)]]
    idle = dict(r.breakdown["idle_gaps"])
    assert idle == pytest.approx({"bench.window": 45e-9, "bench.ack": 15e-9,
                                  "bench.write": 8e-9,
                                  "PjitFunction(insert)": 2e-9})


RECORDED = os.path.join(BENCH, "testdata", "v5e_trace.json.gz")
RECORDED_EXPECT = os.path.join(BENCH, "testdata", "v5e_trace.expect.json")


def test_trace_reduce_on_a_recorded_chip_trace():
    """A chip trace of one analytics job (TPU v5 lite, seed 1001) reduces
    to the numbers written down when it was recorded, and its busy time
    agrees with a plain interval sweep."""
    with gzip.open(RECORDED, "rt") as f:
        raw = json.load(f)
    trace = trace_reduce.Trace(
        *({k: [tuple(e) for e in v] for k, v in raw[key].items()}
          for key in ("ops", "programs")),
        [tuple(e) for e in raw["host"]])
    r = trace_reduce.reduce(trace)
    want = json.load(open(RECORDED_EXPECT))
    lo, hi = next((a, b) for n, a, b in trace.host
                  if n == trace_reduce.WINDOW_SPAN)
    busy, end = 0, lo
    for _, a, b in sorted(trace.ops["/device:TPU:0"], key=lambda e: e[1]):
        a, b = max(a, end), min(b, hi)
        if b > a:
            busy += b - a
            end = b
    assert r.busy_s == pytest.approx(busy * 1e-9)
    assert 0 < r.busy_s < r.window_s
    assert r.window_s == pytest.approx(want["window_s"])
    assert r.busy_s == pytest.approx(want["busy_s"])
    assert r.span_device_s == pytest.approx(want["span_device_s"])
    assert r.span_count == {"snapshot": 1, "view": 1, "pagerank": 1,
                            "bfs": 1}
    assert r.breakdown["device_ops"][0][0] == want["top_program"]
    assert sum(t for _, t in r.breakdown["idle_gaps"]) <= \
        r.window_s - r.busy_s + 1e-9
