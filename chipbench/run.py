#!/usr/bin/env python3
"""The chip benchmark of the LSMGraph store: one cell, one process.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a store
configuration (``chipbench/configs/<name>.json``) under a traffic mix
(``chipbench/traffic/<name>.json``).  The mix names its driver
(``chipbench/drivers/<driver>.py``), which builds the cell's store from
``--seed``, warms up every shape the window uses, drives the store's own
entry points for ``--seconds``, and compares what the window produced
with the plain reference (``chipbench/reference.py``).  With ``--trace
1`` the window runs under the JAX profiler and each per-layer metric of
the cell is read by its own reader (``chipbench/metrics/<name>.py``).

The run fails, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` ``breakdown``, and last ``checks``: every number
compared beside its limit, which also end standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, Optional  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# JAX's persistent compilation cache: a fixed directory of the checkout,
# so that every run after a cell's first finds its programs.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# Durable stores live here, one directory per cell, removed after a run.
DATA_DIR = os.path.join(BENCH_DIR, ".data")

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.bench import Bench, Cell, RunData  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------- spec
def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _for_cell(entries: list, cell: str) -> list:
    return [m for m in entries if cell in m.get("workloads", [cell])]


def resolve_cell(workload: str, root: str = ROOT) -> Cell:
    """Find a cell's configuration, traffic mix and metrics by name."""
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(by_name)})")
    w = by_name[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = dict(_load_json(os.path.join(root, conf["file"])),
                  name=conf["name"])
    traffic = dict(_load_json(traffic_path(w["traffic"], root)),
                   name=w["traffic"])
    return Cell(w["name"], int(w["chips"]), config, traffic,
                _for_cell(spec["end_to_end"], w["name"]),
                _for_cell(spec["per_layer"], w["name"]))


def traffic_path(name: str, root: str = ROOT) -> str:
    return os.path.join(root, "chipbench", "traffic", f"{name}.json")


def driver_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "drivers", f"{name}.py")


def metric_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "metrics", f"{name}.py")


def _load_module(path: str, label: str):
    spec = importlib.util.spec_from_file_location(label, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------- helpers
def check_devices(chips: int):
    """The devices a cell runs on; raises NoChip without enough TPUs."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache(cache_dir: str = CACHE_DIR) -> None:
    """Every program goes to the persistent cache, however fast its
    compile, so that only a cell's first run in a checkout compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Backend compiles and persistent-cache loads in this process."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def read(self) -> tuple:
        return self.compiles, self.cache_hits


def obs_totals() -> Dict[str, object]:
    """The program's counters (value) and histograms ((count, sum)),
    summed over label sets, by name."""
    from repro import obs
    out: Dict[str, object] = {}
    for m in obs.REGISTRY.collect():
        kind = getattr(m, "kind", "")
        if kind == "counter":
            out[m.name] = out.get(m.name, 0) + m.value
        elif kind == "histogram":
            c, s = out.get(m.name, (0, 0.0))
            out[m.name] = (c + m.count, s + m.sum)
    return out


def obs_delta(before: dict, after: dict) -> Dict[str, object]:
    out = {}
    for k, v in after.items():
        b = before.get(k)
        if isinstance(v, tuple):
            b = b or (0, 0.0)
            out[k] = (v[0] - b[0], v[1] - b[1])
        else:
            out[k] = v - (b or 0)
    return out


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def load_peaks(kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, not a default."""
    table = _load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


def read_per_layer(cell: Cell, run: RunData) -> Dict[str, dict]:
    out = {}
    for m in cell.per_layer:
        mod = _load_module(metric_path(m["name"]),
                           "chipbench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# -------------------------------------------------------------------- run
def execute(cell: Cell, seed: int, seconds: float, trace: bool, *,
            devices, data_dir: str = DATA_DIR, log=None) -> dict:
    """Set up, measure, check: the result line's object.  ``devices``
    are the cell's devices; the caller has checked them."""
    import jax
    from chipbench import trace_reduce

    b = Bench(cell, seed, seconds, data_dir, log)
    driver = _load_module(driver_path(cell.traffic["driver"]),
                          "chipbench_driver_" + cell.traffic["driver"])
    counter = CompileCounter()
    dev = devices[0]
    kind = dev.device_kind
    state = driver.setup(b)
    try:
        before = obs_totals()
        tdir = None
        if trace:
            tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(tdir, profiler_options=opts)
        b.spans.clear()
        c0 = counter.read()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            res = driver.window(b, state, t0, t0 + seconds)
        c1 = counter.read()
        if trace:
            jax.profiler.stop_trace()
        window_s = res.end - t0
        spans = list(b.spans)
        delta = obs_delta(before, obs_totals())
        peak = memory_peak(devices)
        b.log(f"window: {window_s!r} s, {res.attempted} attempted, "
              f"compiles in window {c1[0] - c0[0]}, cache loads in window "
              f"{c1[1] - c0[1]}; samples {res.samples}")
        b.log(f"set-up: {t0 - T_START!r} s; compiles so far {c1[0]}, "
              f"persistent-cache loads so far {c1[1]}")
        reduced = None
        if trace:
            reduced = trace_reduce.reduce_dir(tdir)
            shutil.rmtree(tdir, ignore_errors=True)
        driver.release(b, state)
        checks, failed = driver.check(b, state)
    finally:
        driver.close(b, state)

    device = {"platform": dev.platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": all(c.ok for c in checks), "attempted": res.attempted,
           "failed": failed}
    if trace:
        run = RunData(delta, spans, reduced, b.info,
                      load_peaks(kind) if dev.platform == "tpu" else {},
                      window_s)
        out["metrics"] = read_per_layer(cell, run)
        if reduced.busy_s is not None:
            device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
            out["breakdown"] = reduced.breakdown
    else:
        metrics = dict(res.metrics, setup_s=t0 - T_START)
        out["metrics"] = {m["name"]: {"value": metrics[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
    out["device"] = device
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    for c in checks:
        if c.detail and not c.ok:
            b.log(f"check {c.name}: first wrong answer: {c.detail}")
    # The result line's key order: checks last.
    return {k: out[k] for k in ("correct", "attempted", "failed", "metrics",
                                "device", "breakdown", "checks") if k in out}


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = resolve_cell(args.workload)
        devices = check_devices(cell.chips)
    except (KeyError, FileNotFoundError, NoChip) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chipbench: no program under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    enable_compile_cache()
    dev = devices[0]
    print(f"device: platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    result = execute(cell, args.seed, args.seconds, bool(args.trace),
                     devices=devices)
    print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
