#!/usr/bin/env python
"""Observability smoke gate (``make obs-smoke``).

Runs ``graph_service --metrics FILE`` at tiny scale — once single-store
durable, once sharded durable — and schema-validates the per-phase metric
reports: every phase must carry a well-formed ``lsmg-metrics-v1`` export
(typed entries, complete histogram summaries) and the final phase must
cover the per-layer families the observability model promises (store /
storage / io / merge / read, plus shard + compaction in sharded mode —
the scheduler's decision enum is checked closed, and the read family
must keep exporting the presence-filter counters).  This is the
bit-rot gate for the metrics pipeline: an instrument that stops being
registered, an exporter field that disappears, or a phase hook that stops
firing all fail here before any dashboard notices.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

REPORT_SCHEMA = "lsmg-metrics-report-v1"
EXPORT_SCHEMA = "lsmg-metrics-v1"
HIST_KEYS = ("count", "sum", "min", "max", "p50", "p99", "p999")


def fail(msg: str) -> None:
    raise SystemExit(f"obs-smoke FAILED: {msg}")


def run_service(report_path: str, extra: list) -> None:
    cmd = [sys.executable, "-m", "repro.launch.graph_service",
           "--vertices", "300", "--edges", "2000", "--queries", "64",
           # ~1k-edge MemGraph: the run must flush and compact to export
           # the flush/compaction families.
           "--memgraph-mb", "0.02",
           "--metrics", report_path] + extra
    env = dict(os.environ)
    env.setdefault("PYTHONPATH", "src")
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=900)
    if r.returncode != 0:
        fail(f"{' '.join(cmd)} exited {r.returncode}\n"
             f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}")


def validate(report_path: str, want_phases: set, want_families: set,
             tag: str) -> None:
    try:
        with open(report_path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        fail(f"[{tag}] report unreadable: {e}")
    if doc.get("schema") != REPORT_SCHEMA:
        fail(f"[{tag}] bad report schema: {doc.get('schema')!r}")
    phases = doc.get("phases", {})
    missing = want_phases - set(phases)
    if missing:
        fail(f"[{tag}] missing phases: {sorted(missing)} "
             f"(got {sorted(phases)})")
    n_entries = 0
    for pname, snap in phases.items():
        if snap.get("schema") != EXPORT_SCHEMA:
            fail(f"[{tag}] phase {pname}: bad export schema")
        for fam, metrics in snap.get("families", {}).items():
            for mname, entries in metrics.items():
                for e in entries:
                    n_entries += 1
                    where = f"[{tag}] {pname}/{fam}_{mname}"
                    if not isinstance(e.get("labels"), dict):
                        fail(f"{where}: labels not a dict")
                    kind = e.get("type")
                    if kind in ("counter", "gauge"):
                        if not isinstance(e.get("value"), (int, float)):
                            fail(f"{where}: missing numeric value")
                    elif kind == "histogram":
                        for k in HIST_KEYS:
                            if not isinstance(e.get(k), (int, float)):
                                fail(f"{where}: histogram missing {k}")
                        if e["count"] > 0 and not (
                                e["min"] <= e["p50"] <= e["max"]):
                            fail(f"{where}: p50 outside [min, max]")
                    else:
                        fail(f"{where}: unknown type {kind!r}")
    # The last phase sees the whole run: every promised family must exist.
    final = phases["restart_verify"]
    fams = set(final["families"])
    missing = want_families - fams
    if missing:
        fail(f"[{tag}] final phase missing families {sorted(missing)} "
             f"(got {sorted(fams)})")

    # Semantic spot-checks on the final snapshot: a durable run must have
    # moved WAL bytes and published store states.
    def value_of(fam: str, metric: str) -> float:
        return sum(e.get("value", e.get("count", 0))
                   for e in final["families"].get(fam, {}).get(metric, []))

    if value_of("io", "wal_write_bytes") <= 0:
        fail(f"[{tag}] durable run recorded no WAL bytes")
    if value_of("io", "manifest_write_bytes") <= 0:
        fail(f"[{tag}] durable run recorded no manifest bytes")
    if value_of("store", "state_publish_total") <= 0:
        fail(f"[{tag}] no StoreState publishes recorded")
    # Presence-filter telemetry: the three read_filter_* series are
    # registered per store at construction, so a durable run that stops
    # exporting them means the read path lost its filter instrumentation.
    read_fam = final["families"].get("read", {})
    for m in ("filter_checked_total", "filter_skipped_total",
              "filter_false_positive_total"):
        if m not in read_fam:
            fail(f"[{tag}] read family missing {m}")
    if "compaction" in want_families:
        # Scheduler decision stream: the enum is CLOSED — a new decision
        # kind must be added here (and documented in repro.obs) on purpose.
        comp = final["families"].get("compaction", {})
        decisions = {e["labels"].get("decision")
                     for e in comp.get("sched_decision_total", [])}
        want = {"compact", "skip_hot", "skip_backoff", "idle"}
        if decisions != want:
            fail(f"[{tag}] compaction decision enum {sorted(decisions)} "
                 f"!= {sorted(want)}")
        if not comp.get("sched_interval_seconds"):
            fail(f"[{tag}] compaction family missing sched_interval gauge")
    print(f"obs-smoke [{tag}]: {len(phases)} phases, "
          f"{n_entries} entries validated")


def main() -> None:
    base_families = {"store", "storage", "io", "merge", "read"}
    with tempfile.TemporaryDirectory(prefix="obs_smoke_") as td:
        single = os.path.join(td, "single.json")
        run_service(single, ["--durable", os.path.join(td, "db_single")])
        validate(single,
                 want_phases={"ingest", "analytics", "queries",
                              "concurrent_reads", "restart_verify"},
                 want_families=base_families, tag="single-durable")

        sharded = os.path.join(td, "sharded.json")
        run_service(sharded, ["--durable", os.path.join(td, "db_shard"),
                              "--shards", "2", "--analytics", "2hop"])
        validate(sharded,
                 want_phases={"ingest", "analytics", "queries",
                              "restart_verify"},
                 want_families=base_families | {"shard", "compaction"},
                 tag="sharded-durable")
    print("obs-smoke OK")


if __name__ == "__main__":
    main()
