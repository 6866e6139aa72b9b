"""On-device emit accumulation (engine.step.EmitRing) correctness.

The runtime parks packed emits of up to HEATMAP_EMIT_FLUSH_K batches on
device and pulls them in ONE transfer, so K batches pay one pull's
round trips.  These tests pin the flush contract: forced flush before every
checkpoint commit, flush on ring-capacity and watermark pressure,
replay-equivalence after a restore mid-flush-interval, and conservation
(no event lost or double-emitted across flush/checkpoint boundaries).
"""

import time

import numpy as np
import pytest

from heatmap_tpu.config import load_config
from heatmap_tpu.sink import MemoryStore
from heatmap_tpu.stream import MemorySource, MicroBatchRuntime, SyntheticSource

T_NOW = int(time.time()) - 600


def mk_cfg(tmp_path, **over):
    over.setdefault("checkpoint_dir", str(tmp_path / "ckpt"))
    over.setdefault("batch_size", 512)
    over.setdefault("state_capacity_log2", 13)
    over.setdefault("speed_hist_bins", 8)
    over.setdefault("store", "memory")
    return load_config({}, **over)


def mk_events(n, t0=T_NOW, n_vehicles=20):
    rng = np.random.default_rng(7)
    return [{
        "provider": "mbta",
        "vehicleId": f"veh-{i % n_vehicles}",
        "lat": float(rng.uniform(42.3, 42.4)),
        "lon": float(rng.uniform(-71.1, -71.0)),
        "speedKmh": float(rng.uniform(0, 80)),
        # stay inside one window-length of event time: these tests pin
        # ring-capacity behavior, and an advancing watermark would add
        # pressure flushes of its own (covered separately below)
        "ts": t0 + (i % 60),
    } for i in range(n)]


# ------------------------------------------------------------- unit level
def test_emitring_stacked_flush_equals_per_batch_pull():
    """flush_stacked must hand back EXACTLY what per-batch
    pull_packed_stack would have, for both pull disciplines — the ring
    changes transfer granularity, never content."""
    from heatmap_tpu.engine.multi import MultiStats, stats_from_packed
    from heatmap_tpu.engine.single import SingleAggregator
    from heatmap_tpu.engine.step import (AggParams, EmitRing,
                                         pull_packed_stack)

    params = AggParams(res=8, window_s=300, emit_capacity=256)
    rng = np.random.default_rng(1)

    def batches(n):
        agg = SingleAggregator(params, capacity=1 << 10, batch_size=128,
                               hist_bins=8)
        out = []
        for k in range(n):
            lat = rng.uniform(0.73, 0.74, 128).astype(np.float32)
            lng = rng.uniform(-1.25, -1.24, 128).astype(np.float32)
            speed = rng.uniform(0, 90, 128).astype(np.float32)
            ts = np.full(128, T_NOW + k, np.int32)
            valid = np.ones(128, bool)
            out.append(agg.step_packed_ride(lat, lng, speed, ts, valid,
                                            -(2**31)))
        return out

    rng = np.random.default_rng(1)
    packs_a = batches(3)
    rng = np.random.default_rng(1)
    packs_b = batches(3)
    for prefix in (False, True):
        ring = EmitRing(4)
        for i, p in enumerate(packs_a):
            ring.append(p[None], tag=i)   # (P=1, E+1, L) block per batch
        flushed = ring.flush_stacked(prefix)
        assert [t for _, t in flushed] == [0, 1, 2]
        assert len(ring) == 0 and ring.n_flushes == 1
        for (bufs, _tag), ref in zip(flushed, packs_b):
            ref_bufs = pull_packed_stack(ref[None], prefix)
            assert len(bufs) == 1
            np.testing.assert_array_equal(bufs[0], ref_bufs[0])
            # the ridden stats decode identically through the ring
            assert (stats_from_packed(bufs[0])
                    == stats_from_packed(ref_bufs[0]))
            assert isinstance(stats_from_packed(bufs[0]), MultiStats)


def test_emitring_flush_in_transfers_of_max_depth():
    """``max_depth`` splits a deep flush into stacked transfers of at
    most that many entries (each depth is a program of its own): the
    batches come back in append order with the content of one
    transfer."""
    from heatmap_tpu.engine.step import EmitRing

    rng = np.random.default_rng(3)
    packs = [rng.integers(0, 2**32, (2, 9, 13), dtype=np.uint32)
             for _ in range(5)]
    for k, p in enumerate(packs):
        p[:, 0, 0] = k + 1       # head row: rows emitted by the batch
    for prefix in (False, True):
        whole, split = EmitRing(8), EmitRing(8)
        for i, p in enumerate(packs):
            whole.append(p, tag=i)
            split.append(p, tag=i)
        ref = whole.flush_stacked(prefix)
        got = split.flush_stacked(prefix, max_depth=2)
        assert [t for _, t in got] == [t for _, t in ref] == list(range(5))
        assert len(split) == 0 and split.n_flushes == 1
        for (bufs, _), (ref_bufs, _), p in zip(got, ref, packs):
            n = int(p[0, 0, 0])
            for b, r in zip(bufs, ref_bufs):
                np.testing.assert_array_equal(b[:n + 1], r[:n + 1])


def test_emitring_refuses_shape_change():
    """A slab/emit-capacity resize mid-interval would corrupt the stack;
    append must refuse loudly (the runtime flushes before every grow)."""
    from heatmap_tpu.engine.step import EmitRing

    ring = EmitRing(4)
    ring.append(np.zeros((1, 9, 13), np.uint32))
    with pytest.raises(ValueError, match="flush before"):
        ring.append(np.zeros((1, 17, 13), np.uint32))


def test_emitring_residency_accounting():
    """take()/flush_stacked record per-entry residency: seconds parked
    and batches-resident (appends from the entry's own, inclusive, to
    the flush — the oldest entry of a K-deep flush reads K)."""
    from heatmap_tpu.engine.step import EmitRing

    ring = EmitRing(4)
    a = np.zeros((2, 3, 4), np.uint32)
    for tag in range(3):
        ring.append(a, tag)
    entries = ring.take()
    res = ring.last_flush_residency
    assert len(entries) == len(res) == 3
    assert [b for _, b in res] == [3, 2, 1]
    assert all(s >= 0.0 for s, _ in res)
    # the lifetime append counter keeps counting across flushes
    ring.append(a, 9)
    ring.take()
    assert [b for _, b in ring.last_flush_residency] == [1]
    ring.take()
    assert ring.last_flush_residency == []


def test_emitring_capacity():
    from heatmap_tpu.engine.step import EmitRing

    ring = EmitRing(2)
    assert not ring.append(np.zeros((1, 9, 13), np.uint32))
    assert ring.append(np.zeros((1, 9, 13), np.uint32))  # full
    assert ring.full
    assert ring.flush_stacked(False)
    assert not ring.full


def test_emitring_idle_entries_do_not_trigger(tmp_path):
    """Per-mesh-shard flush independence (ISSUE 11): entries appended
    ``live=False`` (empty dispatches) park — their eviction emits and
    stats must still be pulled eventually — but never advance the flush
    trigger, so an idle shard's ring only drains at forced barriers.
    The 8x-capacity hard cap bounds the parked memory regardless."""
    from heatmap_tpu.engine.step import EmitRing

    ring = EmitRing(2)
    for i in range(15):
        assert not ring.full
        ring.append(np.zeros((1, 9, 13), np.uint32), tag=i, live=False)
    assert len(ring) == 15 and ring.live_pending == 0
    # the 8 * capacity memory backstop trips on the 16th idle entry
    assert ring.append(np.zeros((1, 9, 13), np.uint32), live=False)
    assert ring.full
    flushed = ring.flush_stacked(False)
    assert len(flushed) == 16 and not ring.full
    # one live entry among idles: the LIVE count is the trigger
    ring.append(np.zeros((1, 9, 13), np.uint32), live=False)
    assert not ring.append(np.zeros((1, 9, 13), np.uint32), live=True)
    assert ring.live_pending == 1 and not ring.full
    assert ring.append(np.zeros((1, 9, 13), np.uint32), live=True)
    assert ring.full  # 2 live == capacity; the idle one rides along
    assert len(ring.take()) == 3
    assert ring.live_pending == 0


# --------------------------------------------------------- runtime level
def test_ring_amortizes_pulls_and_conserves(tmp_path):
    """Steady state: one pull per K batches (the >= 4x round-trip
    reduction at the default interval), with every event accounted and
    sunk exactly once."""
    cfg = mk_cfg(tmp_path, emit_flush_k=4)
    store = MemoryStore()
    n = 8 * 512
    src = SyntheticSource(n_events=n, n_vehicles=50, events_per_second=2048)
    rt = MicroBatchRuntime(cfg, src, store, checkpoint_every=0)
    rt.run()
    snap = rt.metrics.snapshot()
    assert snap["events_valid"] == n
    assert sum(d["count"] for d in store._tiles.values()) == n
    # 8 batches at K=4: ring-full flushes + the close flush — strictly
    # fewer pulls than batches, and every batch accounted exactly once
    assert snap["emit_pull_batches"] == 8
    assert 0 < snap["emit_pulls"] <= 3
    assert snap["emit_pulls"] < 8 / 2


def test_flush_forced_before_checkpoint_commit(tmp_path):
    """A checkpoint must never commit offsets past batches whose emits
    are still parked on device: the capture flushes the ring first, so
    the committed watermark and the sink writes cover every batch the
    offsets cover."""
    cfg = mk_cfg(tmp_path, emit_flush_k=8)
    store = MemoryStore()
    src = SyntheticSource(n_events=4 * 512, n_vehicles=50,
                          events_per_second=2048)
    rt = MicroBatchRuntime(cfg, src, store, checkpoint_every=2)
    rt.step_once()
    assert len(rt._ring) == 1          # parked, not pulled
    rt.step_once()                     # epoch 2: checkpoint fires
    assert len(rt._ring) == 0          # flushed by the capture
    assert rt.metrics.counters["emit_pulls"] == 1
    rt._ckpt_join()
    meta = rt.ckpt.load_meta()
    assert meta is not None and meta["epoch"] == 2
    # the commit's watermark covers both flushed batches
    assert meta["max_event_ts"] == rt.max_event_ts
    rt.close()


def test_flush_on_ring_capacity_pressure(tmp_path):
    """K parked batches force a flush before the next dispatch — the
    ring can never grow past its configured capacity."""
    cfg = mk_cfg(tmp_path, emit_flush_k=2)
    store = MemoryStore()
    src = MemorySource()
    rt = MicroBatchRuntime(cfg, src, store, checkpoint_every=0)
    evs = mk_events(5 * 512, t0=T_NOW)
    for k in range(5):
        src.push(evs[k * 512:(k + 1) * 512])
        rt.step_once()
        assert len(rt._ring) <= 2
    # steps 3 and 5 hit ring-full (2 entries each); batch 5 still parked
    assert rt.metrics.counters["emit_pulls"] == 2
    assert len(rt._ring) == 1
    rt.close()
    assert rt.metrics.counters["emit_pull_batches"] == 5
    assert sum(d["count"] for d in store._tiles.values()) == 5 * 512


def test_flush_on_watermark_pressure(tmp_path):
    """When the cutoff crosses a window boundary (eviction may fire),
    parked batches flush BEFORE the dispatch so closed windows reach the
    sink promptly instead of up to K batches later."""
    cfg = mk_cfg(tmp_path, emit_flush_k=16, watermark_minutes=10)
    store = MemoryStore()
    src = MemorySource()
    rt = MicroBatchRuntime(cfg, src, store, checkpoint_every=0)
    src.push(mk_events(100, t0=T_NOW))
    rt.step_once()
    src.push(mk_events(100, t0=T_NOW + 3600))   # jump an hour ahead
    rt.step_once()                              # watermark advances here
    assert rt.metrics.counters.get("emit_pulls", 0) == 0
    src.push(mk_events(100, t0=T_NOW + 3700))
    rt.step_once()   # cutoff crossed window boundaries -> pressure flush
    assert rt.metrics.counters["emit_pulls"] == 1
    assert len(rt._ring) == 1                   # only batch 3 parked
    rt.close()
    assert sum(d["count"] for d in store._tiles.values()) == 300


def test_replay_equivalence_after_restore_mid_interval(tmp_path):
    """Crash mid-flush-interval (parked batches lost with the device),
    resume from the last commit, replay to the end: state and sink must
    equal a continuous run's exactly — no event lost or double-emitted
    across the flush/checkpoint/restore boundaries."""
    cfg = mk_cfg(tmp_path, emit_flush_k=3)
    store = MemoryStore()
    n = 8 * 512
    src = SyntheticSource(n_events=n, n_vehicles=60, events_per_second=2048)
    rt = MicroBatchRuntime(cfg, src, store, checkpoint_every=2)
    for _ in range(5):
        rt.step_once()
    rt._ckpt_join()
    # crash: abandon rt with batch 5's emits still parked in the ring
    # (no close, no exit commit); drain the writer so the sink state is
    # deterministic for the comparison below
    assert len(rt._ring) >= 1
    rt.writer.drain()

    src2 = SyntheticSource(n_events=n, n_vehicles=60,
                           events_per_second=2048)
    rt2 = MicroBatchRuntime(cfg, src2, store, checkpoint_every=2)
    assert rt2.epoch == 4              # resumed from the epoch-4 commit
    assert src2.offset() == 4 * 512    # batch 5 replays
    rt2.run()

    cfg3 = mk_cfg(tmp_path, emit_flush_k=3,
                  checkpoint_dir=str(tmp_path / "ckpt3"))
    src3 = SyntheticSource(n_events=n, n_vehicles=60,
                           events_per_second=2048)
    store3 = MemoryStore()
    rt3 = MicroBatchRuntime(cfg3, src3, store3, checkpoint_every=2)
    rt3.run()

    (res, wmin), agg2 = next(iter(rt2.aggs.items()))
    agg3 = rt3.aggs[(res, wmin)]
    for a, b in zip(agg2.state, agg3.state):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert store._tiles == store3._tiles
    assert rt2.max_event_ts == rt3.max_event_ts


def test_flush_k1_is_per_batch_pull(tmp_path):
    """emit_flush_k=1 must reproduce the pre-ring per-batch pull exactly
    (it is also what multi-host runs force)."""
    cfg = mk_cfg(tmp_path, emit_flush_k=1)
    store = MemoryStore()
    n = 3 * 512
    src = SyntheticSource(n_events=n, n_vehicles=50, events_per_second=2048)
    rt = MicroBatchRuntime(cfg, src, store, checkpoint_every=0)
    rt.run()
    snap = rt.metrics.snapshot()
    assert snap["emit_pulls"] == 3 and snap["emit_pull_batches"] == 3
    assert sum(d["count"] for d in store._tiles.values()) == n


def test_flush_k_validated():
    with pytest.raises(ValueError, match="HEATMAP_EMIT_FLUSH_K"):
        load_config({"HEATMAP_EMIT_FLUSH_K": "0"})
    with pytest.raises(ValueError, match="HEATMAP_PREFETCH_BATCHES"):
        load_config({"HEATMAP_PREFETCH_BATCHES": "-1"})
    cfg = load_config({"HEATMAP_EMIT_FLUSH_K": "4",
                       "HEATMAP_PREFETCH_BATCHES": "2"})
    assert cfg.emit_flush_k == 4 and cfg.prefetch_batches == 2
