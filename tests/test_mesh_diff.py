"""Partitioned-mesh differential (ISSUE 11): folding the same event
corpus through 1 device vs an N-device mesh in ``partitioned`` mode
(shard-per-device H3 feed partitioning, per-device emit rings,
per-shard governors) must produce BYTE-IDENTICAL merged emits —
including invalid, late, and duplicate events, and across a checkpoint
resume mid-ring.

Why this holds by construction (the PR 7 process-fleet argument, moved
intra-process):

- the feed partitioner compacts each device's owned rows to its block
  prefix IN STREAM ORDER, so every (cell, window) group's f32
  accumulation order is the single-device fold's;
- the watermark advances from the PRE-partition rows, so every
  device's cutoff sequence — late drops and evictions — is the
  single-device one;
- a device owning none of a batch's cells still dispatches (all
  invalid): per-batch slab rewrite counts match the single-device
  fold's;
- cell spaces are disjoint across devices (merge is upsert-only).

Plus the two mesh-specific acceptance properties: per-shard flush
INDEPENDENCE (an idle shard's device→host pull count stays at the
idle-flush floor while a hot shard flushes at its own cadence) and
per-shard GOVERNING (skewed shards converge to different batch buckets
with merged emits byte-identical to the ungoverned mesh).
"""

import copy
import time

import numpy as np
import pytest

from heatmap_tpu.config import load_config
from heatmap_tpu.parallel import make_mesh
from heatmap_tpu.sink import MemoryStore
from heatmap_tpu.stream import MemorySource, MicroBatchRuntime

T_NOW = int(time.time()) - 600
BATCH = 256
N_DEV = 4


def mk_stream():
    """The test_shard_diff hazard stream: wide box (all shards own
    cells), invalid rows, duplicates, hour-late rows."""
    rng = np.random.default_rng(11)

    def ev(i, t, lat=None, lon=None):
        v = i % 37
        return {
            "provider": "mbta" if v % 3 else "opensky",
            "vehicleId": f"veh-{v}",
            "lat": float(rng.uniform(42.3, 42.5)) if lat is None else lat,
            "lon": float(rng.uniform(-71.2, -71.0)) if lon is None else lon,
            "speedKmh": float(rng.uniform(0, 80)),
            "bearing": 0.0,
            "accuracyM": 5.0,
            "ts": t,
        }

    out = [ev(i, T_NOW + i % 120) for i in range(3 * BATCH)]
    out += [
        ev(1, T_NOW + 130, lat=95.0),            # lat out of range
        ev(2, T_NOW + 130, lon=-200.0),          # lon out of range
        ev(3, -5),                               # negative ts
        ev(4, T_NOW + 130, lat=float("nan")),    # non-finite lat
    ]
    dup = ev(0, T_NOW + 200, lat=42.35, lon=-71.05)
    out += [copy.deepcopy(dup) for _ in range(8)]
    out += [ev(i, T_NOW - 3600) for i in range(24)]          # late
    out += [ev(i, T_NOW + 210 + i % 30) for i in range(BATCH - 36)]
    return out


def run_one(tmp_path, events, tag, mesh=None, flush_k=3, govern=False,
            max_batches=None, checkpoint_every=0, source=None,
            store=None, **over):
    cfg = load_config(
        {}, batch_size=BATCH, state_capacity_log2=12, speed_hist_bins=8,
        store="memory", emit_flush_k=flush_k, govern=govern,
        govern_min_batch=64, checkpoint_dir=str(tmp_path / f"ckpt-{tag}"),
        **over)
    if source is None:
        source = MemorySource(copy.deepcopy(events))
        source.finish()
    store = MemoryStore() if store is None else store
    rt = MicroBatchRuntime(cfg, source, store, mesh=mesh,
                           checkpoint_every=checkpoint_every)
    rt.run(max_batches=max_batches)
    return rt, store


def assert_stores_equal(s1, sN):
    assert s1._tiles.keys() == sN._tiles.keys()
    for k in s1._tiles:
        assert s1._tiles[k] == sN._tiles[k], k
    assert s1._positions == sN._positions


def test_one_vs_mesh_byte_identical(tmp_path):
    events = mk_stream()
    rt1, s1 = run_one(tmp_path, events, "base")
    rtN, sN = run_one(tmp_path, events, "mesh", mesh=make_mesh(N_DEV))

    assert rtN._parted is not None, "auto mode must pick partitioned"
    assert rtN._mesh_mode == "partitioned"
    assert len(s1._tiles) > 100                 # a real city's worth
    assert_stores_equal(s1, sN)

    # accounting parity: the partition is disjoint, so per-shard sums
    # equal the single-device counters exactly
    c1, cN = rt1.metrics.counters, rtN.metrics.counters
    for key in ("events_valid", "events_late", "events_invalid",
                "tiles_emitted", "positions_emitted"):
        assert c1.get(key, 0) == cN.get(key, 0), key
    # the watermark tracks the FULL stream (pre-partition rows)
    assert rt1.max_event_ts == rtN.max_event_ts
    # every shard folded something on the wide box, and the ring
    # amortized: pulls <= ceil(batches/K) + 1 forced close flush per
    # shard, far below one pull per (shard, batch)
    stats = rtN.mesh_shard_stats()
    assert len(stats) == N_DEV
    assert all(m["rows"] > 0 for m in stats)
    n_batches = rtN.epoch
    for m in stats:
        assert m["emit_pulls"] <= -(-n_batches // 3) + 1, m
        assert m["emit_pull_batches"] == n_batches, m
    # zero post-warmup retraces across every per-device program
    assert rtN.runtimeinfo.compile.snapshot()["retraces_after_warmup"] \
        == 0


def test_mesh_resume_mid_ring_byte_identical(tmp_path):
    """A mesh run killed between checkpoints (ring entries parked on
    every device) resumes from its own commit and converges to the
    1-device baseline — per-entry offset snapshots keep commits
    dispatch-aligned, and the pre-commit barrier flush covers every
    accounted batch."""
    import json

    from heatmap_tpu.stream.source import JsonlReplaySource

    events = mk_stream()
    path = tmp_path / "corpus.jsonl"
    with open(path, "w") as fh:
        for e in events:
            fh.write(json.dumps(e) + "\n")
    rt1, s1 = run_one(tmp_path, events, "rbase",
                      source=JsonlReplaySource(str(path)))

    store = MemoryStore()
    mesh = make_mesh(N_DEV)
    rt_a, _ = run_one(tmp_path, events, "rmesh", mesh=mesh,
                      checkpoint_every=1, max_batches=2, store=store,
                      source=JsonlReplaySource(str(path)))
    # close() drains the prefetched entry too, so 2 stepped + ≤1 drained
    assert 2 <= rt_a.epoch < 5
    rt_b, _ = run_one(tmp_path, events, "rmesh", mesh=mesh,
                      checkpoint_every=1, store=store,
                      source=JsonlReplaySource(str(path)))
    # the resume seeked past rt_a's dispatched offsets and replayed
    # ONLY the remainder
    assert rt_b.epoch > rt_a.epoch
    assert rt_a.metrics.counters.get("events_valid", 0) \
        + rt_b.metrics.counters.get("events_valid", 0) \
        == rt1.metrics.counters.get("events_valid"), \
        "every valid row folded exactly once across the resume"
    assert_stores_equal(s1, store)


def test_mesh_mode_checkpoint_refuses_cross_mode_restore(tmp_path):
    """A partitioned-mode checkpoint must not restore into a
    shuffle-mode run (same block layout, different key ownership)."""
    import pytest

    events = mk_stream()[:BATCH]
    mesh = make_mesh(2)
    run_one(tmp_path, events, "xmode", mesh=mesh, checkpoint_every=1)
    with pytest.raises(RuntimeError, match="mesh mode"):
        run_one(tmp_path, events, "xmode", mesh=mesh,
                mesh_partitioned="0")


def test_hot_cold_flush_independence(tmp_path):
    """80/20-style geographic skew, taken to the limit: every event in
    one tight cluster, so ONE device owns the whole stream.  The hot
    shard flushes at its own K cadence; the cold shards' pull counts
    stay at the idle-flush floor (the single forced close/barrier
    flush), because their empty parked entries never advance the
    live-batch trigger."""
    rng = np.random.default_rng(7)
    # event time stays inside one window (i % 120): watermark-pressure
    # barrier flushes — which rightly drain EVERY shard when a window
    # closes — must not fire, so the floor measured here is the close()
    # barrier alone
    events = [{"provider": "p", "vehicleId": f"v{i % 5}",
               "lat": 42.3601 + float(rng.uniform(-1e-4, 1e-4)),
               "lon": -71.0589 + float(rng.uniform(-1e-4, 1e-4)),
               "speedKmh": 1.0, "ts": T_NOW + i % 120}
              for i in range(6 * BATCH)]
    rtN, _ = run_one(tmp_path, events, "hot", mesh=make_mesh(N_DEV),
                     flush_k=2)
    stats = rtN.mesh_shard_stats()
    hot = [m for m in stats if m["rows"] > 0]
    cold = [m for m in stats if m["rows"] == 0]
    assert len(hot) == 1 and len(cold) == N_DEV - 1
    # hot: one pull per K live batches (+ the final barrier flush)
    assert hot[0]["emit_pulls"] >= rtN.epoch // 2
    # cold: ONLY the idle-flush floor — forced barrier flushes (close,
    # checkpoints), never the hot shard's cadence
    for m in cold:
        assert m["emit_pulls"] <= 1, m
        assert m["emit_pull_batches"] == rtN.epoch, m


def test_governed_mesh_shards_converge_apart_results_identical(tmp_path):
    """ISSUE 11 acceptance: per-mesh-shard governors under 80/20 skew
    converge to DIFFERENT batch buckets (each shard's fill is its own)
    while merged emits stay byte-identical to the ungoverned mesh run —
    the governor re-partitions batching, never results.  Exact-
    arithmetic corpus (fixed position per vehicle, speeds on a 0.25
    grid) so byte-identity across regrouped chunk shapes is decidable;
    only the breach signal (event ages over the SLO) is scripted."""
    from heatmap_tpu.stream.shardmap import MeshPartition

    # fixed candidate positions, partitioned through the REAL partitioner
    rng = np.random.default_rng(5)
    cand = np.stack([42.30 + rng.uniform(0, 0.2, 48),
                     -71.20 + rng.uniform(0, 0.2, 48)], axis=1)
    mp = MeshPartition(2, snap_res=8)
    ids, _ = mp.partition(np.radians(cand[:, 0]).astype(np.float32),
                          np.radians(cand[:, 1]).astype(np.float32))
    heavy = [i for i in range(48) if ids[i] == 0][:12]
    light = [i for i in range(48) if ids[i] == 1][:3]
    assert len(heavy) == 12 and len(light) == 3, "probe found both sides"

    def ev(slot, k, t, lat=None, lon=None):
        return {"provider": "p", "vehicleId": f"veh-{slot}",
                "lat": float(cand[slot, 0]) if lat is None else lat,
                "lon": float(cand[slot, 1]) if lon is None else lon,
                "speedKmh": (k % 320) * 0.25, "bearing": 0.0,
                "accuracyM": 5.0, "ts": t}

    events = []
    for k in range(5 * BATCH):
        # 4-of-5 rows to device 0's cells, 1-of-5 to device 1's
        slot = heavy[k % 12] if k % 5 else light[k % 3]
        events.append(ev(slot, k, T_NOW + k % 120))
    events.append(ev(heavy[0], 1, T_NOW + 130, lat=95.0))   # invalid
    dup = ev(heavy[1], 7, T_NOW + 200)
    events += [copy.deepcopy(dup) for _ in range(8)]        # dups
    events += [ev(heavy[i % 12], i, T_NOW - 3600)           # very late
               for i in range(24)]

    def run_mesh(governed):
        cfg = load_config(
            {}, batch_size=BATCH, state_capacity_log2=12,
            speed_hist_bins=8, store="memory", emit_flush_k=1,
            govern=governed, govern_min_batch=64,
            govern_interval_s=1e-3,
            checkpoint_dir=str(tmp_path / f"gm{int(governed)}"))
        src = MemorySource(copy.deepcopy(events))
        src.finish()
        store = MemoryStore()
        rt = MicroBatchRuntime(cfg, src, store, mesh=make_mesh(2),
                               checkpoint_every=0)
        if governed:
            class _Clk:
                t = 1000.0

                def __call__(self):
                    return self.t

            clk = _Clk()
            for gov in rt._mesh_governors:
                gov.clock = clk
                gov._last_decide = clk.t
        rounds = 0
        while True:
            if governed and rounds < 4:
                # scripted breach: the interval median reads over the
                # SLO; fill/idle stay genuinely measured per shard —
                # the divergence comes from the skew, not the script
                h = rt.metrics.event_age.labels(bound="mean")
                h.observe(999.0)
                h.observe(999.0)
            if governed and 1 <= rounds <= 4:
                rt._mesh_governors[0].clock.t += 1.0
            progressed = rt.step_once()
            rounds += 1
            if not progressed and src.exhausted:
                break
        rt.close()
        return rt, store

    rt_g, store_g = run_mesh(True)
    rt_u, store_u = run_mesh(False)

    gov0, gov1 = rt_g._mesh_governors
    assert gov0.batch_rows == BATCH, gov0.snapshot()
    assert gov1.batch_rows == 64, gov1.snapshot()
    assert rt_g.runtimeinfo.compile.snapshot()["retraces_after_warmup"] \
        == 0

    assert len(store_g._tiles) > 10
    assert_stores_equal(store_u, store_g)
    assert rt_g.max_event_ts == rt_u.max_event_ts
    for key in ("events_valid", "events_late", "events_invalid"):
        assert rt_g.metrics.counters.get(key, 0) \
            == rt_u.metrics.counters.get(key, 0), key


def test_fastpath_pin_surfaces_in_telemetry(tmp_path):
    """Satellite bugfix: a pinned fast path (multi-host forcing
    emit_flush_k=1/prefetch=0) must surface as
    heatmap_fastpath_pinned{reason=} and a /healthz warning check, not
    just one INFO log line."""
    from heatmap_tpu.serve.api import healthz_payload

    rt, _ = run_one(tmp_path, mk_stream()[:8], "pin")
    assert rt._fastpath_pinned == {}
    before, _ = healthz_payload(rt)
    assert "fastpath_pinned" not in before["checks"]

    rt._note_fastpath_pinned("multihost_lockstep",
                             "emit_flush_k 8->1, prefetch_batches 1->0")
    text = rt.metrics.expose_text()
    assert 'heatmap_fastpath_pinned{reason="multihost_lockstep"} 1' \
        in text
    payload, down = healthz_payload(rt)
    chk = payload["checks"]["fastpath_pinned"]
    assert chk["ok"] and chk.get("warn")
    assert "multihost_lockstep" in chk["value"]
    # a WARNING, not a degradation: the verdict is whatever it was
    # before the pin surfaced
    assert not down and payload["status"] == before["status"]


def test_mesh_partition_stability_and_composition():
    """The mesh partition key is a pure function of the cell index —
    stable across instances — and composes with process-level sharding
    by consuming DIFFERENT hash bits (correlated moduli must not park
    every one of a process's rows on its first device)."""
    from heatmap_tpu.stream.shardmap import MeshPartition, ShardMap

    rng = np.random.default_rng(3)
    lat = np.radians(42.3 + rng.uniform(0, 0.2, 512)).astype(np.float32)
    lng = np.radians(-71.2 + rng.uniform(0, 0.2, 512)).astype(np.float32)
    a = MeshPartition(4, snap_res=8)
    b = MeshPartition(4, snap_res=8)
    ids_a, cells = a.partition(lat, lng)
    ids_b, _ = b.partition(lat, lng)
    np.testing.assert_array_equal(ids_a, ids_b)
    assert len(set(ids_a.tolist())) > 1, "wide box spreads devices"
    # reusing pre-snapped cells is the identical assignment
    ids_c, _ = a.partition(lat, lng, cells=cells)
    np.testing.assert_array_equal(ids_a, ids_c)

    # composition: rows owned by ONE process shard (outer mod 2) must
    # still spread across a 2-device mesh — the naive same-hash
    # assignment would collapse them all onto one device
    sm = ShardMap(2, 0, 8)
    owned = sm.shard_of_cells(cells) == 0
    mp = MeshPartition(2, snap_res=8, outer_shards=2)
    dev = mp.device_of_cells(cells[owned])
    assert len(set(dev.tolist())) == 2, "quotient bits decorrelate"


@pytest.mark.parametrize("partition", ["mesh", "shards"])
def test_partitioned_stream_refuses_in_program_snap(tmp_path, monkeypatch,
                                                    partition):
    """Rows are routed by their host-snapped cell, so a partitioned
    stream must group by that same cell.  Grouped by the f32 in-program
    snap, an edge point opens its (cell, window) group on a second
    owner and the store keeps one partial group: on a v5e mesh 2.65% of
    the synthetic_backfill events went missing that way (PR 21).  An
    explicit in-program snap on a partitioned stream is refused."""
    from heatmap_tpu.engine import step as engine_step

    monkeypatch.setattr(engine_step, "SNAP_IMPL", None)
    monkeypatch.setenv("HEATMAP_H3_IMPL", "xla")
    cfg = load_config(
        {}, batch_size=BATCH, state_capacity_log2=12, store="memory",
        checkpoint_dir=str(tmp_path / "ckpt"),
        **({"shards": 2, "shard_index": 0} if partition == "shards"
           else {}))
    mesh = make_mesh(N_DEV) if partition == "mesh" else None
    with pytest.raises(RuntimeError, match="partitioned"):
        MicroBatchRuntime(cfg, MemorySource([]), MemoryStore(), mesh=mesh)


def test_partitioned_resume_refuses_in_program_keyed_checkpoint(
        tmp_path, monkeypatch):
    """Before PR 21 a partitioned mesh off the CPU keyed its checkpoints
    with the in-program snap ("xla").  Resuming such a checkpoint under
    HEATMAP_H3_IMPL=auto would pin the in-program snap again and bring
    back the grouping that lost events: the resume raises instead."""
    import glob
    import json

    from heatmap_tpu.engine import step as engine_step

    # the pin writes the process-wide snap policy: restore it after
    monkeypatch.setattr(engine_step, "SNAP_IMPL", None)
    monkeypatch.setenv("HEATMAP_H3_IMPL", "auto")
    events = mk_stream()
    mesh = make_mesh(N_DEV)
    run_one(tmp_path, events, "xla-keyed", mesh=mesh, checkpoint_every=1,
            max_batches=2)
    metas = glob.glob(str(tmp_path / "ckpt-xla-keyed" / "commit-*"
                          / "meta.json"))
    assert metas
    for path in metas:
        with open(path) as fh:
            meta = json.load(fh)
        assert meta["snap_impl"] == "native"
        meta["snap_impl"] = "xla"
        with open(path, "w") as fh:
            json.dump(meta, fh)
    with pytest.raises(RuntimeError, match="partitioned"):
        run_one(tmp_path, events, "xla-keyed", mesh=mesh,
                checkpoint_every=1)
