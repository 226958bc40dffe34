"""What the harness hands a traffic driver and a per-layer reader.

A driver (``chipbench/drivers/<name>.py``) defines ``setup(b) -> state``,
``window(b, state, t0, deadline) -> WindowResult``, ``release(b,
state)`` (free what the comparison does not need), ``check(b, state) ->
(checks, failed)`` and ``close(b, state)``.  A reader
(``chipbench/metrics/<name>.py``) defines ``read(run: RunData)``, which
returns the metric's value or None where it finds nothing to read.
"""
from __future__ import annotations

import contextlib
import os
import time
from typing import Dict, List, NamedTuple


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict        # the configuration file, with its "name"
    traffic: dict       # the traffic mix file, with its "name"
    end_to_end: list    # BENCHMARK.json entries this cell reports
    per_layer: list


class Bench:
    """What a driver sees of the run: the cell, the seed, the window
    length, a log, the benchmark's own spans and the numbers the driver
    leaves for the per-layer readers (``info``)."""

    def __init__(self, cell: Cell, seed: int, seconds: float,
                 data_dir: str, log=None):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.data_dir = os.path.join(data_dir, cell.name)
        self.log = log or (lambda msg: print(msg, flush=True))
        self.spans: List[tuple] = []
        self.info: Dict[str, object] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span around one call into a layer: on the host clock
        (``spans``) and, when tracing, in the profiler's trace as
        ``bench.<name>``."""
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        self.spans.append((name, t0, time.perf_counter()))


class WindowResult(NamedTuple):
    attempted: int
    end: float               # host clock when the window's last work ended
    metrics: Dict[str, float]
    samples: Dict[str, int]  # sample counts behind the metrics


class RunData(NamedTuple):
    """What a per-layer reader reads: program counters and histograms as
    deltas over the window, the benchmark's host spans in the window, the
    reduced profiler trace (None without one), the driver's ``info``, the
    device's peaks and the window's length on the host clock."""

    obs: Dict[str, object]
    spans: List[tuple]
    trace: object
    info: Dict[str, object]
    peaks: dict
    window_s: float
