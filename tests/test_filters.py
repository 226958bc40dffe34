"""Vertex-presence filters + the amplification-driven compaction scheduler.

Covers PR 10's contracts: the blocked splitmix filter never false-negatives
(deterministic + property-based), the host and device probe formulas agree
bit-for-bit, the v2 segment filter section round-trips / CRC-checks /
rebuilds byte-identically from the WAL, v1 files stay readable as
"no filter", the read path is byte-identical with filters disabled
(``LSMG_READ_FILTERS=0``), cold runs stay cold for filter-rejected
vertices, the spine cache keeps one generation of history, and the
scheduler's rank / hot-skip / backoff policy.
"""
import glob
import os
import time

import numpy as np
import pytest

from conftest import small_store_cfg
from repro import obs
from repro.core import LSMGraph, filters
from repro.core.types import StoreConfig
from repro.kernels import ops as kops
from repro.shard.scheduler import CompactionScheduler
from repro.shard.store import ShardedGraphStore
from repro.storage import faultfs, open_store
from repro.storage import segments as seg_mod
from repro.storage.errors import CorruptionError, DurabilityLost


def _durable_cfg(**kw):
    base = dict(vmax=1 << 12, mem_edges=1 << 12, l0_run_limit=64)
    base.update(kw)
    return StoreConfig(**base)


# ------------------------------------------------------------ filter core
def test_filter_zero_false_negatives():
    vkeys = (np.arange(500, dtype=np.int64) * 7919) % (1 << 31)
    f = filters.from_vkeys(vkeys)
    assert f.might_contain(vkeys).all()


def test_filter_false_positive_rate_bounded():
    rng = np.random.default_rng(3)
    members = rng.integers(0, 1 << 30, 2000).astype(np.int64)
    f = filters.from_vkeys(members)
    absent = np.setdiff1d(
        rng.integers(1 << 30, 1 << 31, 20000).astype(np.int64), members)
    fp = f.might_contain(absent).mean()
    # 16 bits/key, k=4 gives ~0.2% theoretical; 2% is a generous ceiling
    # that still catches a broken hash (which false-positives at ~100%).
    assert fp < 0.02


def test_empty_filter_rejects_everything():
    f = filters.from_vkeys(np.empty(0, np.int64))
    assert not f.might_contain(np.arange(64, dtype=np.int64)).any()


def test_from_words_rejects_non_pow2():
    with pytest.raises(ValueError):
        filters.from_words(np.zeros(3, np.uint32), 96)


def test_host_device_probe_parity():
    """The numpy ``might_contain`` and the device ``presence_matrix``
    are the same formula by contract."""
    rng = np.random.default_rng(11)
    runs = [rng.integers(0, 1 << 28, n).astype(np.int64)
            for n in (1, 40, 700)]
    filts = [filters.from_vkeys(v) for v in runs]
    width = max(f.words.shape[0] for f in filts)
    mat = np.zeros((len(filts), width), np.uint32)
    masks = np.empty(len(filts), np.uint32)
    for i, f in enumerate(filts):
        mat[i, :f.words.shape[0]] = f.words
        masks[i] = f.mbits - 1
    queries = np.concatenate([runs[1][:20],
                              rng.integers(0, 1 << 28, 300)]).astype(np.int64)
    host = np.stack([f.might_contain(queries) for f in filts])
    dev = np.asarray(kops.presence_matrix(mat, masks, queries))
    np.testing.assert_array_equal(dev, host)


def test_filter_property_no_false_negatives():
    pytest.importorskip(
        "hypothesis",
        reason="hypothesis not installed (see requirements-dev.txt); "
               "property tests skip rather than breaking collection")
    from hypothesis import HealthCheck, given, settings, strategies as st

    @given(st.lists(st.integers(0, (1 << 31) - 1), min_size=0, max_size=400),
           st.lists(st.integers(0, (1 << 31) - 1), min_size=1, max_size=100))
    @settings(max_examples=30, deadline=None,
              suppress_health_check=list(HealthCheck))
    def inner(members, probes):
        mem = np.array(members, np.int64)
        f = filters.from_vkeys(mem)
        # Never a false negative, for ANY member set.
        if len(mem):
            assert f.might_contain(mem).all()
        # Host and device probes agree on arbitrary queries.
        q = np.array(probes, np.int64)
        dev = np.asarray(kops.presence_matrix(
            f.words[None, :], np.array([f.mbits - 1], np.uint32), q))[0]
        np.testing.assert_array_equal(dev, f.might_contain(q))

    inner()


# -------------------------------------------------------- segment format
def _one_segment(g, root):
    segs = sorted(glob.glob(os.path.join(root, "segments", "*.csr")))
    assert segs
    return segs[-1]


def test_segment_v2_filter_section_roundtrip(tmp_path):
    root = str(tmp_path / "store")
    g = open_store(root, _durable_cfg())
    src = np.arange(0, 600, 2, dtype=np.int64)  # evens only
    g.insert_edges(src, src + 1)
    g.flush_memgraph()
    seg = _one_segment(g, root)
    meta = seg_mod.read_segment_header(seg)
    assert meta["ver"] == 2
    assert seg_mod.verify_segment(seg)["ver"] == 2
    filt = seg_mod.read_segment_filter(seg)
    assert filt is not None
    # Section is the pure function of the body's vkeys: identical words to
    # an in-memory build, and identical to the resident RunFile's filter.
    rf = g._state.levels[0][0]
    want = filters.build_words(np.asarray(rf.arrays.vkeys)[:rf.nv]
                               .astype(np.int64))
    np.testing.assert_array_equal(filt.words, want)
    np.testing.assert_array_equal(rf.presence.words, want)
    # The filter actually separates: evens present, odds (mostly) absent.
    assert filt.might_contain(src).all()
    g.close()


def test_segment_v1_backward_compat(tmp_path):
    root = str(tmp_path / "store")
    g = open_store(root, _durable_cfg())
    g.insert_edges(np.arange(100, dtype=np.int64),
                   np.arange(100, dtype=np.int64) + 1)
    g.flush_memgraph()
    rf = g._state.levels[0][0]
    v1 = str(tmp_path / "legacy.csr")
    seg_mod.write_segment(v1, rf, version=1)
    assert seg_mod.read_segment_header(v1)["ver"] == 1
    assert seg_mod.verify_segment(v1)["ver"] == 1
    assert seg_mod.read_segment_filter(v1) is None   # "always maybe"
    meta, run = seg_mod.read_segment(v1)
    np.testing.assert_array_equal(np.asarray(run.vkeys)[:meta["nv"]],
                                  np.asarray(rf.arrays.vkeys)[:rf.nv])
    g.close()


def test_recovery_rehydrates_filters(tmp_path):
    root = str(tmp_path / "store")
    g = open_store(root, _durable_cfg())
    g.insert_edges(np.arange(0, 400, 2, dtype=np.int64),
                   np.arange(0, 400, 2, dtype=np.int64) + 1)
    g.flush_memgraph()
    want = np.asarray(g._state.levels[0][0].presence.words)
    g.close()
    g2 = open_store(root)
    rf = g2._state.levels[0][0]
    assert rf.presence is not None
    np.testing.assert_array_equal(np.asarray(rf.presence.words), want)
    g2.close()


def test_filter_section_corruption_scrub_rebuilds_byte_identical(tmp_path):
    """Crash-injection: rot ONLY the filter section of an evicted segment.
    The scrubber must catch it (body CRC alone would pass), quarantine,
    and rebuild from the WAL — byte-identical INCLUDING the section."""
    root = str(tmp_path / "store")
    g = open_store(root, _durable_cfg(), wal_sync="always")
    g.insert_edges(np.arange(0, 600, 2, dtype=np.int64),
                   np.arange(0, 600, 2, dtype=np.int64) + 1)
    g.flush_memgraph()
    seg = _one_segment(g, root)
    want_bytes = open(seg, "rb").read()
    meta = seg_mod.read_segment_header(seg)
    sect_off = seg_mod._HDR.size + seg_mod.body_nbytes(meta["nv"],
                                                       meta["ne"])
    assert sect_off < len(want_bytes)  # v2: a section exists
    g.durability.evict_all_segments()
    # Flip a payload bit inside the section, beyond the 16-byte header.
    faultfs.flip_bit(seg, offset=sect_off + seg_mod._FHDR.size + 1)
    with pytest.raises(CorruptionError):
        seg_mod.verify_segment(seg)
    stats = g.durability.scrub_once()
    assert stats["rebuilt"] == 1
    assert open(seg, "rb").read() == want_bytes
    assert g.degraded_ranges() == ()
    g.close()


# ------------------------------------------------------- read-path gates
def _mixed_store(durable_root=None):
    cfg = (small_store_cfg(l0_run_limit=64) if durable_root is None
           else _durable_cfg())
    g = (LSMGraph(cfg) if durable_root is None
         else open_store(durable_root, cfg))
    rng = np.random.default_rng(17)
    for _ in range(3):
        src = rng.integers(0, 1 << 10, 400).astype(np.int64)
        dst = rng.integers(0, 1 << 12, 400).astype(np.int64)
        g.insert_edges(src, dst)
        g.flush_memgraph()
    g.delete_edges(src[:50], dst[:50])
    g.insert_edges(rng.integers(0, 1 << 10, 100).astype(np.int64),
                   rng.integers(0, 1 << 12, 100).astype(np.int64))
    return g


def _read_all(g, vs):
    with g.snapshot() as snap:
        nbrs = snap.neighbors_batch(vs, return_props=True)
        scal = [snap.neighbors_scalar(int(v), return_props=True)
                for v in vs[:32]]
    return nbrs, scal


def test_filters_on_off_byte_identical(monkeypatch):
    """The filter is an OPTIMIZATION: with ``LSMG_READ_FILTERS=0`` every
    resolve path returns byte-identical adjacency."""
    g = _mixed_store()
    vs = np.arange(0, 1 << 11, 3, dtype=np.int64)  # present + absent mix
    on_b, on_s = _read_all(g, vs)
    monkeypatch.setenv("LSMG_READ_FILTERS", "0")
    off_b, off_s = _read_all(g, vs)
    for (d1, p1), (d2, p2) in zip(on_b, off_b):
        np.testing.assert_array_equal(d1, d2)
        np.testing.assert_array_equal(p1, p2)
    for (d1, p1), (d2, p2) in zip(on_s, off_s):
        np.testing.assert_array_equal(d1, d2)
        np.testing.assert_array_equal(p1, p2)


def test_filters_on_off_byte_identical_legacy_path(monkeypatch):
    from repro.core import store as store_mod
    monkeypatch.setattr(store_mod, "_READ_TOURNAMENT_MAX_K", 0)
    g = _mixed_store()
    vs = np.arange(0, 1 << 11, 5, dtype=np.int64)
    on_b, _ = _read_all(g, vs)
    monkeypatch.setenv("LSMG_READ_FILTERS", "0")
    off_b, _ = _read_all(g, vs)
    for (d1, p1), (d2, p2) in zip(on_b, off_b):
        np.testing.assert_array_equal(d1, d2)
        np.testing.assert_array_equal(p1, p2)


def test_filter_metrics_flow():
    g = _mixed_store()
    checked = obs.counter("read_filter_checked_total", store=g.obs_label)
    skipped = obs.counter("read_filter_skipped_total", store=g.obs_label)
    c0, s0 = checked.value, skipped.value
    with g.snapshot() as snap:
        # Scalar reads of vertices far outside the ingested src range:
        # every (run, query) pair should be checked and (nearly) all
        # skipped.
        for v in range(1 << 11, (1 << 11) + 32):
            snap.neighbors_scalar(v)
    assert checked.value > c0
    assert skipped.value > s0


def test_cold_runs_stay_cold_for_absent_vertices(tmp_path):
    """The headline win: after eviction, scalar reads of vertices every
    filter rejects never reload a segment — zero cold bytes."""
    root = str(tmp_path / "store")
    g = open_store(root, _durable_cfg())
    src = np.arange(0, 1 << 11, 2, dtype=np.int64)      # evens only
    g.insert_edges(src, src + 1)
    g.flush_memgraph()
    g.durability.evict_all_segments()
    cold0 = g.io.cold_load
    hits = 0
    with g.snapshot() as snap:
        for v in range(1, 81, 2):                        # absent odds
            hits += len(snap.neighbors_scalar(v))
    assert hits == 0
    assert g.io.cold_load == cold0                       # nothing loaded
    with g.snapshot() as snap:
        assert snap.neighbors_scalar(2).tolist() == [3]  # a present even
    assert g.io.cold_load > cold0                        # real load paid
    g.close()


def test_spine_cache_keeps_one_generation_of_history():
    """Two-slot cache: a snapshot pinned before a flush still resolves
    against the previous epoch without evicting the new epoch's spine."""
    g = _mixed_store()
    with g.snapshot() as old_snap:
        old_snap.neighbors_batch(np.arange(8, dtype=np.int64))
        old_fids = g._spine_cache._slots[0].fids
        g.insert_edges(np.arange(64, dtype=np.int64),
                       np.arange(64, dtype=np.int64) + 1)
        g.flush_memgraph()
        with g.snapshot() as new_snap:
            new_snap.neighbors_batch(np.arange(8, dtype=np.int64))
        slots = g._spine_cache._slots
        assert len(slots) == 2
        assert slots[1].fids == old_fids          # history retained
        assert slots[0].fids > old_fids           # new epoch newest-first


# -------------------------------------------------------------- scheduler
def _sharded_with_debt(n_runs=3):
    cfg = small_store_cfg(l0_run_limit=64)
    g = ShardedGraphStore(cfg, n_shards=2)
    # Ingest + flush only into shard 0's range: it accrues L0 debt.
    lo, hi = g.part.shard_range(0)
    for i in range(n_runs):
        src = np.arange(lo, lo + 40, dtype=np.int64)
        g.insert_edges(src % (hi - lo) + lo, src + i + 1)
        g.shards[0].flush_memgraph()
    return g


def test_scheduler_compacts_worst_shard_then_idles():
    g = _sharded_with_debt()
    sched = CompactionScheduler(g)
    assert len(g.shards[0]._state.levels[0]) >= 2
    scores = sched.shard_scores()
    assert set(scores) == {0}                     # shard 1 has no debt
    out = sched.step()
    assert out["decision"] == "compact" and out["shard"] == 0
    assert len(g.shards[0]._state.levels[0]) < 2  # debt drained
    assert sched.step()["decision"] == "idle"
    g.close()


def test_scheduler_skips_hot_shard():
    g = _sharded_with_debt()
    sched = CompactionScheduler(g)
    # A writer commits on shard 0 between ticks: its ack histogram count
    # advances, so the only eligible shard is HOT and must be skipped.
    obs.histogram("shard_ack_seconds", shard="0").observe(0.001)
    out = sched.step()
    assert out["decision"] == "skip_hot"
    assert len(g.shards[0]._state.levels[0]) >= 2  # untouched
    # Next tick the shard is quiet again: compaction proceeds.
    assert sched.step()["decision"] == "compact"
    g.close()


def test_scheduler_backs_off_on_ack_latency_jump():
    g = _sharded_with_debt(n_runs=4)
    sched = CompactionScheduler(g, min_l0=1)
    h = obs.histogram("shard_ack_seconds", shard="1")   # shard 1: not the
    h.observe(0.001)                                    # compact target
    h.observe(0.001)
    assert sched.step()["decision"] == "compact"        # baseline window
    h.observe(0.5)                                      # 500x mean jump
    base = sched.base_interval
    out = sched.step()
    assert out["decision"] == "skip_backoff"
    assert out["interval"] == pytest.approx(base * sched.backoff)
    # Calm window: interval decays back toward base and work resumes.
    h.observe(0.001)
    out = sched.step()
    assert out["decision"] in ("compact", "idle")
    assert out["interval"] == pytest.approx(base)
    g.close()


@pytest.mark.parametrize("exc,swallowed", [
    (DurabilityLost("fsync failed on a fenced shard"), True),
    (RuntimeError("bug in a compaction"), False),
])
def test_scheduler_thread_surfaces_unexpected_errors(exc, swallowed):
    """The background loop survives a typed storage failure (a shard
    fenced mid-compaction) but hands any other error to ``stop()``."""
    g = _sharded_with_debt()
    sched = CompactionScheduler(g, interval=0.001)
    ticks = []

    def failing_step():
        ticks.append(1)
        raise exc

    sched.step = failing_step
    sched.start()
    deadline = time.time() + 10.0
    while len(ticks) < 3 and sched._thread.is_alive() \
            and time.time() < deadline:
        time.sleep(0.005)
    if swallowed:
        assert len(ticks) >= 3 and sched._thread.is_alive()
        sched.stop()
    else:
        assert len(ticks) == 1
        with pytest.raises(RuntimeError, match="bug in a compaction"):
            sched.stop()
    assert sched._thread is None
    g.close()
