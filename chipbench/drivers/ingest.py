"""Closed-loop durable ingest: one client sends the update stream's
requests in order, each through ``insert_edges``/``delete_edges`` and
then ``ack(seq)``, and sends the next once the ack has returned.

Set-up generates the stream (the graph's own blocks, then further R-MAT
samples, enough for ``headroom_records_per_s`` over the window) and
drives it through the same calls until ``warm_flushes`` flushes have
landed, which compiles and caches every program the window runs:
insert chunks, the flush and the index update.

The comparison holds each ack to the configuration's guarantee: a
completed fsync has to cover every byte the request wrote under the
store's directory when its ack returns (``chipbench/fsync_ledger.py``).
It then reads ``readback_vertices`` vertices back twice, from a fresh
snapshot and from a crash image taken when the window's last ack
returned (every file cut back to what an fsync covered; the store is
reopened from it alone), and compares them with the reference over every
acknowledged request: every source vertex of the window's last request,
and the rest drawn from the seed among the sources of the window's
requests.  One such batch is one resolve of the program's.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import graphgen, reference, storeutil
from chipbench.bench import WindowResult
from chipbench.fsync_ledger import FsyncLedger


class State:
    def __init__(self, store, ops, next_op, ledger):
        self.store = store
        self.ops = ops
        self.next = next_op        # index of the next request to send
        self.window = (next_op, next_op)
        self.ledger = ledger
        self.unsynced = []         # window requests acked before an fsync
        self.image = None          # the crash image at the last ack
        self.closed = False


def setup(b) -> State:
    t = b.traffic
    cfg = storeutil.store_config(b.config)
    need = (cfg.mem_edges * (t["warm_flushes"] + 1)
            + int(t["headroom_records_per_s"] * b.seconds))
    ops = graphgen.stream_blocks(b.config["graph"], b.seed, need)
    b.log(f"stream: {len(ops)} requests, {graphgen.n_records(ops)} "
          f"records; mem_edges={cfg.mem_edges}")
    ledger = FsyncLedger(b.data_dir).install()
    store = storeutil.open_cell_store(b)
    # A store that never flushes stops the warm-up a MemGraph later.
    i, sent = 0, 0
    while (len(store.levels[0]) < t["warm_flushes"]
           and sent < cfg.mem_edges * (t["warm_flushes"] + 1)):
        store.ack(storeutil.apply(store, ops[i]))
        sent += len(ops[i].src)
        i += 1
    b.log(f"warm-up: {i} requests, {graphgen.n_records(ops[:i])} records, "
          f"level edges {store.level_sizes()}")
    return State(store, ops, i, ledger)


def window(b, st: State, t0: float, deadline: float) -> WindowResult:
    store, ops, ledger = st.store, st.ops, st.ledger
    first = i = st.next
    lat = []
    records = 0
    while time.perf_counter() < deadline and i < len(ops):
        op = ops[i]
        ledger.mark()
        ts = time.perf_counter()
        with b.span("write"):
            seq = storeutil.apply(store, op)
        with b.span("ack"):
            store.ack(seq)
        lat.append(time.perf_counter() - ts)
        st.unsynced.append(ledger.uncovered() > 0)
        records += len(op.src)
        i += 1
    end = time.perf_counter()
    st.image = ledger.image()
    if i == len(ops):
        b.log(f"the pre-generated stream ran dry {end - t0!r} s into the "
              "window: raise headroom_records_per_s")
    st.next = i
    st.window = (first, i)
    b.log(f"ingest: {records} records acknowledged in {len(lat)} requests; "
          f"ack median {float(np.median(lat)) * 1e3!r} ms, max "
          f"{max(lat) * 1e3!r} ms; level edges {store.level_sizes()}")
    return WindowResult(
        attempted=len(lat), end=end,
        metrics={"ingest_rate": records / (end - t0),
                 "ack_p95_ms": storeutil.p95(lat) * 1e3},
        samples={"requests": len(lat), "records": records})


def release(b, st: State) -> None:
    """The comparison reads the store itself: nothing to free."""


def readback_vertices(b, st: State) -> np.ndarray:
    lo, hi = st.window
    window = st.ops[lo:hi] or st.ops[:st.next]
    last = np.unique(window[-1].src)
    rest = np.setdiff1d(np.unique(np.concatenate([op.src for op in window])),
                        last)
    k = min(len(rest), max(0, b.traffic["readback_vertices"] - len(last)))
    pick = graphgen.derived_rng(b.seed, 4).choice(rest, k, replace=False)
    return np.sort(np.r_[last, pick]).astype(np.int64)


def _read(store, vs):
    with store.snapshot() as snap:
        return snap.neighbors_batch(vs)


def _reopen_crash_image(b, st: State, ref, vs):
    """The store reopened from the crash image alone; a store that cannot
    reopen has lost every vertex read back."""
    st.ledger.uninstall()
    st.ledger.cut(st.image)
    try:
        store2 = storeutil.reopen_cell_store(b)
        try:
            return reference.adjacency_wrong(ref, vs, _read(store2, vs))
        finally:
            store2.close()
    except Exception as e:  # noqa: BLE001 - any failure to recover counts
        return np.ones(len(vs), bool), f"reopen failed: {e!r}"


def check(b, st: State):
    n = storeutil.n_vertices(b.config)
    ref = reference.Reference(st.ops[:st.next], n)
    vs = readback_vertices(b, st)
    fresh, d1 = reference.adjacency_wrong(ref, vs, _read(st.store, vs))
    st.store.close()
    st.closed = True
    crashed, d2 = _reopen_crash_image(b, st, ref, vs)
    bad = np.zeros(n, bool)
    bad[vs[fresh | crashed]] = True
    lo, hi = st.window
    failed = sum(bool(bad[op.src].any()) or unsynced
                 for op, unsynced in zip(st.ops[lo:hi], st.unsynced))
    first = next((k for k, u in enumerate(st.unsynced) if u), None)
    d0 = "" if first is None else f"window request {first}"
    return [reference.Check("acks_before_fsync", sum(st.unsynced), 0, d0),
            reference.Check("snapshot_wrong_vertices",
                            int(fresh.sum()), 0, d1),
            reference.Check("crash_wrong_vertices",
                            int(crashed.sum()), 0, d2)], failed


def close(b, st: State) -> None:
    st.ledger.uninstall()
    if not st.closed:
        st.store.close()
    storeutil.remove_cell_data(b)
