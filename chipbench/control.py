#!/usr/bin/env python3
"""The controls of the benchmark's comparisons, at a cell's own size.

    python3 chipbench/control.py --workload <cell> --seeds 11,12,13 --seconds 20

A control is what the program's place would hold one step down, and its
readings have to fail the cell's limits.  ``inmem-analytics`` states
float32: its control is the reference's PageRank in bfloat16 on the
chip.  ``durable-ingest`` states no precision but a guarantee, that an
ack returns only once a WAL fsync covers the request: its control is the
program's own path that breaks it, the cell run through the harness with
the WAL's ``wal_sync`` set to ``"off"``, so that ``ack`` returns at once
and nothing is ever fsynced.  For each seed this prints what the cell's
numbers read for the control beside the cell's limits.  The benchmark's
own runs never run it.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import graphgen, reference, storeutil  # noqa: E402


def without_fsync(cell):
    """The cell with its WAL never fsynced: acks return at once."""
    config = copy.deepcopy(cell.config)
    config["store"]["wal_sync"] = "off"
    return cell._replace(config=config)


def ingest_control(cell, seed: int, seconds: float, **kw) -> dict:
    """The harness's checks of a run whose acks skip the fsync."""
    from chipbench import run
    out = run.execute(without_fsync(cell), seed, seconds, False, **kw)
    return {k: c["value"] for k, c in out["checks"].items()}


def analytics_control(cell, seed: int, seconds: float, **kw) -> dict:
    """The reference's PageRank in bfloat16, on JAX's default device."""
    import jax.numpy as jnp
    config, traffic = cell.config, cell.traffic
    ops = graphgen.loaded_graph(config["graph"], traffic, seed)
    ref = reference.Reference(ops, storeutil.n_vertices(config))
    it, d = traffic["pagerank_iters"], traffic["damping"]
    err, _ = reference.max_rel_err(
        ref.pagerank(it, d), reference.pagerank_lowp(ref, it, d,
                                                     jnp.bfloat16))
    return {"pagerank_max_rel_err": err}


CONTROLS = {"ingest": ingest_control, "analytics": analytics_control}


def main(argv=None) -> int:
    from chipbench import run
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, three or more")
    ap.add_argument("--seconds", type=float, default=20.0,
                    help="the window of a control that runs the harness")
    args = ap.parse_args(argv)
    cell = run.resolve_cell(args.workload)
    devices = run.check_devices(cell.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    run.enable_compile_cache()
    dev = devices[0]
    print(f"device: platform={dev.platform} device_kind={dev.device_kind}")
    control = CONTROLS[cell.traffic["driver"]]
    for seed in [int(s) for s in args.seeds.split(",")]:
        readings = control(cell, seed, args.seconds, devices=devices)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": readings}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
