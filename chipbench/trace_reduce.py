"""From a JAX profiler trace to the benchmark's device numbers.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
and keeps two things, in nanoseconds on the profiler's clock:

* per TPU (each ``/device:TPU:<i>`` plane), every device operation (its
  ``XLA Ops`` line) and every program execution (its ``XLA Modules``
  line, named by the jitted function);
* the host thread that opened the window span (``bench.window``): every
  span on it, the benchmark's ``bench.*`` spans and JAX's own (dispatch,
  transfers) alike.

``reduce`` turns that into the window's length, the device's busy time
(the union of its operations' intervals, averaged over the chips), the
device time inside each kind of benchmark span, and the ``breakdown``:
the programs that took most device time, and the device's idle time by
the innermost host span open while it was idle.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
NO_HOST_SPAN = "(no host span)"
TOP = 10

Event = Tuple[str, int, int]   # (name, start_ns, end_ns)


class Trace(NamedTuple):
    ops: Dict[str, List[Event]]       # device plane -> operations
    programs: Dict[str, List[Event]]  # device plane -> program executions
    host: List[Event]                 # the window thread's spans


class Reduced(NamedTuple):
    window_s: float
    busy_s: Optional[float]           # None: the trace holds no device
    span_device_s: Dict[str, float]   # bench span name -> device seconds
    span_count: Dict[str, int]
    breakdown: dict


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} .xplane.pb files under "
                                f"{trace_dir}, want 1")
    return paths[0]


def _op_name(text: str) -> str:
    """``%fusion.3 = s32[8]{0} fusion(...)`` -> ``fusion.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _program_name(text: str) -> str:
    """``jit_insert_batch(1844236338978020579)`` -> ``jit_insert_batch``."""
    return text.split("(", 1)[0]


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    programs: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            ops[plane.name] = [(_op_name(e.name), int(e.start_ns),
                                int(e.end_ns)) for e in lines[OPS_LINE].events]
            programs[plane.name] = [
                (_program_name(e.name), int(e.start_ns), int(e.end_ns))
                for e in (lines[MODULES_LINE].events
                          if MODULES_LINE in lines else [])]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                events = [(e.name, int(e.start_ns), int(e.end_ns))
                          for e in line.events]
                if any(name == WINDOW_SPAN for name, _, _ in events):
                    host = events
    return Trace(ops, programs, host)


def reduce_dir(trace_dir: str) -> Reduced:
    return reduce(load_xplane(find_xplane(trace_dir)))


# ---------------------------------------------------------------- intervals
def union(starts, ends) -> Tuple[np.ndarray, np.ndarray]:
    """Disjoint sorted intervals covering the union of [starts, ends)."""
    starts = np.asarray(starts, np.int64)
    ends = np.asarray(ends, np.int64)
    if not len(starts):
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > e[:-1]
    idx = np.nonzero(new)[0]
    last = np.r_[idx[1:] - 1, len(s) - 1]
    return s[idx], e[last]


def covered(us, ue, a, b) -> np.ndarray:
    """Length of [a, b) covered by the disjoint sorted intervals (us, ue),
    for arrays a, b."""
    a = np.asarray(a, np.int64)
    b = np.asarray(b, np.int64)
    cum = np.r_[0, np.cumsum(ue - us)]

    def upto(t):
        k = np.searchsorted(us, t, side="right")
        over = np.where(k > 0, np.maximum(ue[np.maximum(k - 1, 0)] - t, 0), 0)
        return cum[k] - over

    return np.maximum(upto(b) - upto(a), 0)


def clip(events: List[Event], lo: int, hi: int):
    names = [n for n, _, _ in events]
    s = np.clip(np.array([a for _, a, _ in events], np.int64), lo, hi)
    e = np.clip(np.array([b for _, _, b in events], np.int64), lo, hi)
    keep = e > s
    return [n for n, k in zip(names, keep) if k], s[keep], e[keep]


def innermost_segments(host: List[Event], lo: int, hi: int):
    """Cut [lo, hi) into segments, each named by the innermost host span
    open over all of it (NO_HOST_SPAN where none is).  The spans of one
    thread nest, so the innermost open span is the newest one on a stack
    from which spans are dropped once they have ended."""
    evs = sorted((ev for ev in host if ev[2] > lo and ev[1] < hi),
                 key=lambda ev: (ev[1], -ev[2]))
    bounds = sorted({lo, hi} | {t for _, a, b in evs for t in (a, b)
                                if lo < t < hi})
    segs, stack, j = [], [], 0
    for a, b in zip(bounds[:-1], bounds[1:]):
        while j < len(evs) and evs[j][1] <= a:
            stack.append(evs[j])
            j += 1
        while stack and stack[-1][2] <= a:
            stack.pop()
        segs.append((stack[-1][0] if stack else NO_HOST_SPAN, a, b))
    return segs


# ------------------------------------------------------------------ reduce
def reduce(trace: Trace) -> Reduced:
    windows = [(a, b) for n, a, b in trace.host if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW_SPAN} spans in the trace")
    lo, hi = windows[0]
    spans: Dict[str, list] = {}   # bench span name -> [(start, end)]
    for n, a, b in trace.host:
        if n.startswith(SPAN_PREFIX) and n != WINDOW_SPAN and b > lo \
                and a < hi:
            spans.setdefault(n[len(SPAN_PREFIX):], []).append(
                (max(a, lo), min(b, hi)))
    span_count = {k: len(v) for k, v in spans.items()}
    window_s = (hi - lo) * 1e-9
    if not trace.ops:
        return Reduced(window_s, None, {}, span_count,
                       {"device_ops": [], "idle_gaps": []})
    busy, span_dev, prog_time, idle = [], {}, {}, {}
    segs = innermost_segments(trace.host, lo, hi)
    seg_a = np.array([a for _, a, _ in segs], np.int64)
    seg_b = np.array([b for _, _, b in segs], np.int64)
    ndev = len(trace.ops)
    for plane, ops in trace.ops.items():
        _, s, e = clip(ops, lo, hi)
        us, ue = union(s, e)
        busy.append(int((ue - us).sum()))
        for k, ab in spans.items():
            a, b = np.array(ab, np.int64).T
            span_dev[k] = span_dev.get(k, 0.0) + float(
                covered(us, ue, a, b).sum()) * 1e-9 / ndev
        names, ps, pe = clip(trace.programs.get(plane, []), lo, hi)
        for n, d in zip(names, ((pe - ps) * 1e-9 / ndev).tolist()):
            prog_time[n] = prog_time.get(n, 0.0) + d
        # Idle time of this device inside each host segment.
        idle_s = ((seg_b - seg_a) - covered(us, ue, seg_a, seg_b)) * 1e-9
        for (n, _, _), t in zip(segs, idle_s.tolist()):
            if t > 0:
                idle[n] = idle.get(n, 0.0) + t / ndev
    top = sorted(prog_time.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return Reduced(window_s, float(np.mean(busy)) * 1e-9, span_dev,
                   span_count, {"device_ops": [list(kv) for kv in top],
                                "idle_gaps": [list(kv) for kv in gaps]})
