"""A cell's store, built from its configuration file through the
program's own entry points (``StoreConfig.for_graph``, ``open_store``,
``LSMGraph``)."""
from __future__ import annotations

import dataclasses
import shutil

import numpy as np


def store_config(config: dict):
    """The configuration's ``StoreConfig``: sized for its graph by
    ``StoreConfig.for_graph`` with its MemGraph bytes, then its
    level settings."""
    from repro.core import StoreConfig
    g, s = config["graph"], config["store"]
    cfg = StoreConfig.for_graph(1 << g["scale"], s["mem_bytes"])
    return dataclasses.replace(cfg, level_factor=s["level_factor"],
                               l0_run_limit=s["l0_run_limit"])


def open_cell_store(b):
    """A fresh store for the cell: durable under the cell's data
    directory (WAL policy from the configuration), or in memory."""
    s = b.config["store"]
    cfg = store_config(b.config)
    if not s["durable"]:
        from repro.core import LSMGraph
        return LSMGraph(cfg)
    from repro.storage import open_store
    shutil.rmtree(b.data_dir, ignore_errors=True)
    return open_store(b.data_dir, cfg, wal_sync=s["wal_sync"])


def reopen_cell_store(b):
    """Recover the cell's durable store from its directory alone."""
    from repro.storage import open_store
    return open_store(b.data_dir, wal_sync=b.config["store"]["wal_sync"])


def remove_cell_data(b) -> None:
    shutil.rmtree(b.data_dir, ignore_errors=True)


def apply(store, op):
    """One write request through the store's entry point; returns its
    WAL commit seq (None in memory)."""
    if op.delete:
        return store.delete_edges(op.src, op.dst)
    return store.insert_edges(op.src, op.dst)


def p95(values) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


def n_vertices(config: dict) -> int:
    return 1 << config["graph"]["scale"]
