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
import functools
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


def _event(rng, i, t, lat=None, lon=None):
    """One event of vehicle ``i % 37`` at time ``t``, placed uniformly
    over a wide box unless ``lat``/``lon`` are given."""
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


def mk_stream():
    """The test_shard_diff hazard stream: wide box (all shards own
    cells), invalid rows, duplicates, hour-late rows."""
    rng = np.random.default_rng(11)
    ev = functools.partial(_event, rng)

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


def mk_late_band():
    """On-time traffic, then a band of rows straddling the watermark
    (10 minutes behind the newest event) and an hour past it,
    interleaved with on-time rows, then on-time traffic again."""
    rng = np.random.default_rng(12)
    out = [_event(rng, i, T_NOW + i % 120) for i in range(2 * BATCH)]
    for i in range(2 * BATCH):
        if i % 3 == 0:
            t = T_NOW - 3600 + i % 300          # an hour late
        elif i % 3 == 1:
            t = T_NOW - 900 + i % 600           # across the cutoff
        else:
            t = T_NOW + 120 + i % 60            # on time
        out.append(_event(rng, i, t))
    out += [_event(rng, i, T_NOW + 200 + i % 60) for i in range(BATCH)]
    return out


def mk_invalid():
    """Two rows in five invalid, of every kind the parser drops, spread
    through every batch."""
    rng = np.random.default_rng(13)
    bad = [dict(lat=95.0), dict(lon=-200.0), dict(lat=float("nan")),
           dict(lon=float("inf")), dict(t=-5)]
    out = []
    for i in range(4 * BATCH):
        if i % 5 < 2:
            kw = dict(bad[(i // 5) % len(bad)])
            out.append(_event(rng, i, kw.pop("t", T_NOW + i % 120), **kw))
        else:
            out.append(_event(rng, i, T_NOW + i % 120))
    return out


def mk_zipf_hubs():
    """Zipf cell occupancy over 1024 hubs: the first hub takes over a
    third of all rows, so the device that owns it runs hot."""
    rng = np.random.default_rng(14)
    n, n_hubs = 4 * BATCH, 1024
    p = 1.0 / np.arange(1, n_hubs + 1) ** 1.5
    hub_lat = rng.uniform(42.3, 42.5, n_hubs)
    hub_lon = rng.uniform(-71.2, -71.0, n_hubs)
    pick = rng.choice(n_hubs, size=n, p=p / p.sum())
    return [_event(rng, i, T_NOW + i % 120,
                   lat=float(hub_lat[h] + rng.uniform(-1e-5, 1e-5)),
                   lon=float(hub_lon[h] + rng.uniform(-1e-5, 1e-5)))
            for i, h in enumerate(pick)]


def mk_turnover():
    """An hour of event time in stream order, about ten minutes per
    batch: every batch closes windows and the watermark evicts them."""
    rng = np.random.default_rng(15)
    n = 6 * BATCH
    return [_event(rng, i, T_NOW - 3600 + i * 3600 // n) for i in range(n)]


def run_one(tmp_path, events, tag, mesh=None, flush_k=3, govern=False,
            max_batches=None, checkpoint_every=0, source=None,
            store=None, env=None, **over):
    cfg = load_config(
        env or {}, batch_size=BATCH, state_capacity_log2=12, speed_hist_bins=8,
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


def _live_groups(rt) -> int:
    """Live (cell, window) groups left in every pair's slab."""
    return sum(int(np.count_nonzero(
        np.asarray(agg.snapshot().key_hi) != np.uint32(0xFFFFFFFF)))
        for agg in rt.aggs.values())


TRAFFIC = {
    "hazard": (mk_stream, None),
    "late_band": (mk_late_band, None),
    "invalid": (mk_invalid, None),
    "zipf_hubs": (mk_zipf_hubs, None),
    "turnover": (mk_turnover, None),
    "windows": (mk_stream, {"WINDOW_MINUTES": "1,5,15"}),
}


@pytest.mark.parametrize("traffic", list(TRAFFIC))
def test_one_vs_mesh_byte_identical(tmp_path, traffic):
    make, env = TRAFFIC[traffic]
    events = make()
    rt1, s1 = run_one(tmp_path, events, "base", env=env)
    rtN, sN = run_one(tmp_path, events, "mesh", mesh=make_mesh(N_DEV),
                      env=env)

    assert rtN._parted is not None and rtN._mesh_mode == "partitioned"
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
    # every shard folded something, and every shard's ring saw every
    # batch (empty blocks dispatch too)
    stats = rtN.mesh_shard_stats()
    assert len(stats) == N_DEV
    assert all(m["rows"] > 0 for m in stats)
    n_batches = rtN.epoch
    for m in stats:
        assert m["emit_pull_batches"] == n_batches, m
    # zero post-warmup retraces across every per-device program
    assert rtN.runtimeinfo.compile.snapshot()["retraces_after_warmup"] \
        == 0

    if traffic == "hazard":
        # the ring amortized: pulls <= ceil(batches/K) + 1 forced close
        # flush per shard, far below one pull per (shard, batch)
        for m in stats:
            assert m["emit_pulls"] <= -(-n_batches // 3) + 1, m
    elif traffic == "late_band":
        assert c1["events_late"] > BATCH // 2
    elif traffic == "invalid":
        assert c1["events_invalid"] == sum(
            i % 5 < 2 for i in range(len(events)))
    elif traffic == "zipf_hubs":
        # each shard folded exactly the rows the partition gives it
        from heatmap_tpu.stream.events import parse_events

        cols = parse_events(events, {}, {})
        ids, _ = rtN.meshmap.partition(cols.lat_rad, cols.lng_rad)
        want = np.bincount(ids, minlength=N_DEV).tolist()
        assert [m["rows"] for m in stats] == want
        assert max(want) > 1.5 * len(events) / N_DEV, "one shard hot"
    elif traffic == "turnover":
        windows = {k.split("|")[3] for k in s1._tiles}
        assert len(windows) >= 10
        # closed windows left both slabs alike
        assert 0 < _live_groups(rtN) == _live_groups(rt1) \
            < len(s1._tiles)
    elif traffic == "windows":
        # three pairs fused in each device's program, one grid each
        assert len(rtN.aggs) == 3
        assert len({k.split("|")[1] for k in s1._tiles}) == 3


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


@pytest.mark.parametrize("ck_mode", ["shuffle", None],
                         ids=["shuffle", "pre_mode"])
def test_mesh_mode_checkpoint_refuses_cross_mode_restore(tmp_path,
                                                         ck_mode):
    """A checkpoint of the removed ICI-shuffle mesh — marked "shuffle",
    or a multi-shard meta written before checkpoints recorded their
    mode — keyed its shard blocks by a key hash, not by H3 cell: the
    partitioned mesh refuses to resume it and says to clear the
    checkpoint directory."""
    import glob
    import json

    events = mk_stream()[:BATCH]
    mesh = make_mesh(2)
    run_one(tmp_path, events, "xmode", mesh=mesh, checkpoint_every=1)
    metas = glob.glob(str(tmp_path / "ckpt-xmode" / "commit-*"
                          / "meta.json"))
    assert metas
    for path in metas:
        with open(path) as fh:
            meta = json.load(fh)
        assert meta["mesh_mode"] == "partitioned"
        if ck_mode is None:
            del meta["mesh_mode"]
        else:
            meta["mesh_mode"] = ck_mode
        with open(path, "w") as fh:
            json.dump(meta, fh)
    with pytest.raises(RuntimeError, match="mesh mode 'shuffle'.*clear"):
        run_one(tmp_path, events, "xmode", mesh=mesh)


def test_runtime_refuses_a_multi_process_jax_runtime(tmp_path,
                                                     monkeypatch):
    """One runtime serves one JAX process: on a multi-process JAX
    runtime it refuses to start and names the fleet deployment that
    replaces it (one runtime per host, split by HEATMAP_SHARDS)."""
    import jax

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    cfg = load_config({}, batch_size=BATCH, state_capacity_log2=12,
                      store="memory", checkpoint_dir=str(tmp_path / "c"))
    with pytest.raises(RuntimeError, match="one runtime per host.*"
                                           "HEATMAP_SHARDS"):
        MicroBatchRuntime(cfg, MemorySource([]), MemoryStore(),
                          mesh=make_mesh(2))


@pytest.mark.parametrize("fold", ["mesh", "fleet"])
def test_multi_resolution_pyramid_refused_on_a_partitioned_stream(
        tmp_path, fold):
    """H3 children are not contained in their parents, so a partition
    by one resolution's cells splits cells of the pyramid's other
    resolutions over two folds, each then counting part of a group.
    Both partitioned deployments refuse a pyramid at start-up and say
    how to run it: a mesh when the runtime builds its aggregator, a
    HEATMAP_SHARDS fleet when the config loads."""
    env = {"H3_RESOLUTIONS": "7,8,9"}
    if fold == "fleet":
        env.update(HEATMAP_SHARDS="2", HEATMAP_SHARD_INDEX="0")
        with pytest.raises(ValueError, match="multi-resolution pyramid.*"
                                             "2-process HEATMAP_SHARDS"):
            load_config(env)
        return
    cfg = load_config(env, batch_size=BATCH, state_capacity_log2=12,
                      store="memory", checkpoint_dir=str(tmp_path / "c"))
    with pytest.raises(ValueError, match="multi-resolution pyramid.*"
                                         "4-device mesh.*one device"):
        MicroBatchRuntime(cfg, MemorySource([]), MemoryStore(),
                          mesh=make_mesh(N_DEV))


def test_pyramid_pipeline_folds_on_one_device_by_default(tmp_path):
    """``python -m heatmap_tpu.stream hex_pyramid`` on a host of several
    devices builds no mesh unless asked (a mesh would refuse the
    pyramid); asking for one is refused."""
    import dataclasses

    from heatmap_tpu.models.pipelines import build_runtime, get_pipeline

    p = get_pipeline("hex_pyramid")
    p = dataclasses.replace(p, config=dataclasses.replace(
        p.config, store="memory", checkpoint_dir=str(tmp_path / "c")))
    rt, _ = build_runtime(p, source=MemorySource([]))
    assert rt._mesh_mode is None and rt._parted is None
    rt.close()
    with pytest.raises(ValueError, match="multi-resolution pyramid"):
        build_runtime(p, source=MemorySource([]), n_devices=2)


@pytest.mark.parametrize("var", ["HEATMAP_COORDINATOR",
                                 "HEATMAP_NUM_PROCESSES",
                                 "HEATMAP_PROCESS_ID"])
def test_removed_multi_host_variables_are_refused(var):
    """A pod started the old multi-process way would run each host as
    an independent runtime writing partial tiles to the shared store:
    each removed variable is refused when the config loads, with the
    fleet deployment that replaces it."""
    with pytest.raises(ValueError, match=f"{var}.*one runtime per host.*"
                                         "HEATMAP_SHARDS"):
        load_config({var: "0"})
    assert load_config({var: ""}).shards == 1


@pytest.mark.parametrize("value", ["auto", "1", "0"])
def test_mesh_partitioned_accepts_only_the_partitioned_path(value):
    """``mesh_partitioned`` is kept so configs that set it still load:
    auto and 1 both mean the partitioned mesh; 0 asked for the removed
    shuffle path and is refused."""
    if value == "0":
        with pytest.raises(ValueError, match="removed"):
            load_config({}, mesh_partitioned=value)
    else:
        assert load_config({}, mesh_partitioned=value).mesh_partitioned \
            == value


def test_process_shards_over_partitioned_meshes_match_one_chip(tmp_path):
    """The deployment that serves a pod: one runtime per host, split by
    HEATMAP_SHARDS, each partitioning its own cells over its own
    chips.  Two process shards, each over a 2-device mesh of its own
    and polling one batch of the stream at a time (oversample 1, the
    batch boundaries of one chip), write one store whose tiles are
    byte-identical to one chip's."""
    import jax

    events = mk_stream()
    _, s1 = run_one(tmp_path, events, "fleet1")
    store = MemoryStore()
    devs = jax.devices()
    fleet = [run_one(tmp_path, events, f"fleet-s{i}", store=store,
                     mesh=make_mesh(devices=devs[2 * i:2 * i + 2]),
                     shards=2, shard_index=i, shard_oversample=1)[0]
             for i in range(2)]
    for rt in fleet:
        assert rt._mesh_mode == "partitioned"
        assert rt.metrics.counters.get("events_out_of_shard", 0) > 0
        assert all(m["rows"] > 0 for m in rt.mesh_shard_stats())
    assert len(s1._tiles) > 100
    assert s1._tiles.keys() == store._tiles.keys()
    for k in s1._tiles:
        assert s1._tiles[k] == store._tiles[k], k
    assert s1._positions == store._positions


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


def _compile_counter(this_thread_only: bool = False):
    """Count XLA compile requests (compiled or read from the persistent
    cache) in this process from now on — or, with
    ``this_thread_only``, only those the calling thread makes (JAX
    reports a compile request on the thread that requests it)."""
    import threading

    import jax

    box = {"n": 0}
    me = threading.get_ident()

    def on_event(event: str, **_kw) -> None:
        if (event == "/jax/compilation_cache/compile_requests_use_cache"
                and not (this_thread_only
                         and threading.get_ident() != me)):
            box["n"] += 1

    jax.monitoring.register_event_listener(on_event)
    return box


def test_mesh_shard_counters_sum_to_rows_and_dispatch(tmp_path):
    """The per-shard window counters: rows sum to every event the feed
    handed the fold (valid and late; the invalid never leave the host),
    and rows plus padding to the rows dispatched, one pad bucket per
    shard entry."""
    events = mk_stream()
    rt, _ = run_one(tmp_path, events, "count", mesh=make_mesh(N_DEV))
    c = rt.metrics.counters
    rows = [c[f"mesh_rows.{d}"] for d in range(N_DEV)]
    pads = [c[f"mesh_pad_rows.{d}"] for d in range(N_DEV)]
    assert sum(rows) == c["events_valid"] + c["events_late"] \
        == len(events) - c["events_invalid"]
    assert rows == [m["rows"] for m in rt.mesh_shard_stats()]
    entries = sum(m["emit_pull_batches"] for m in rt.mesh_shard_stats())
    assert sum(rows) + sum(pads) == entries * BATCH
    assert all(p > 0 for p in pads)


@pytest.mark.parametrize("emit_pull", ["prefix", "full"])
def test_mesh_ring_flush_compiles_nothing_after_start_up(tmp_path,
                                                         emit_pull):
    """After start-up, a flush of each mesh ring at every depth it can
    reach (idle entries park up to 8x its capacity) and, under the
    prefix pull, every emitted-rows bucket, reads programs the runtime
    compiled at start-up: no compile request.  The counter sees only
    this thread's requests, so threads other tests left in the process
    cannot reach it."""
    import jax

    cfg = load_config(
        {}, batch_size=BATCH, state_capacity_log2=12, speed_hist_bins=8,
        store="memory", emit_flush_k=3, emit_pull=emit_pull,
        checkpoint_dir=str(tmp_path / "ckpt"))
    rt = MicroBatchRuntime(cfg, MemorySource([]), MemoryStore(),
                           mesh=make_mesh(N_DEV), checkpoint_every=0)
    from heatmap_tpu.engine.step import EmitRing

    parked = []
    for d in range(N_DEV):
        ch = rt._mesh_idle_chunk(d)
        f = ch["feed"]
        packed = rt._parted.step_shard(d, f["lat"], f["lng"], f["speed"],
                                       f["ts"], f["valid"], -(2**31),
                                       prekeys=ch["prekeys"])
        host = np.asarray(packed)
        emit_cap = host.shape[1] - 1
        for n in sorted({1, 3, 17, emit_cap // 2 + 1, emit_cap}):
            host = host.copy()
            host[:, 0, 0] = n
            parked.append((d, jax.device_put(host, packed.sharding)))
    cap = rt._mesh_rings[0].capacity
    compiles = _compile_counter(this_thread_only=True)
    try:
        for d, entry in parked:
            for depth in range(1, 8 * cap + 1):
                ring = EmitRing(cap)
                for _ in range(depth):
                    ring.append(entry, live=False)
                assert len(rt._pull_mesh_ring(ring)) == depth
    finally:
        rt.close()
    assert compiles["n"] == 0


def test_mesh_slab_growth_compiles_every_device_before_the_step(
        tmp_path, monkeypatch):
    """A slab growth on the partitioned mesh compiles every device's
    grown step at once, in threads, without running it: the dispatch
    after it compiles nothing, and the results are the one-device
    fold's."""
    from heatmap_tpu.stream import runtime as rtmod

    events = mk_stream()
    compiles = _compile_counter()
    grown = []
    grow = rtmod.MicroBatchRuntime._maybe_grow

    def spy(self):
        cap, n0 = self._agg().capacity_per_shard, compiles["n"]
        grow(self)
        if self._agg().capacity_per_shard != cap:
            grown.append(compiles["n"] - n0)

    dispatch = rtmod.MicroBatchRuntime._dispatch
    after = []

    def spy_dispatch(self, entry, cutoff):
        n0 = compiles["n"]
        dispatch(self, entry, cutoff)
        if grown and len(after) < len(grown):
            after.append(compiles["n"] - n0)

    def run(tag, mesh=None):
        cfg = load_config(
            {}, batch_size=BATCH, state_capacity_log2=7, speed_hist_bins=8,
            store="memory", emit_flush_k=3,
            checkpoint_dir=str(tmp_path / f"ckpt-{tag}"))
        source = MemorySource(copy.deepcopy(events))
        source.finish()
        store = MemoryStore()
        MicroBatchRuntime(cfg, source, store, mesh=mesh,
                          checkpoint_every=0).run()
        return store

    with monkeypatch.context() as m:
        m.setattr(rtmod.MicroBatchRuntime, "_maybe_grow", spy)
        m.setattr(rtmod.MicroBatchRuntime, "_dispatch", spy_dispatch)
        sN = run("grow", make_mesh(N_DEV))
    s1 = run("grow1")
    assert grown and all(n == N_DEV for n in grown), grown
    assert after == [0] * len(grown)
    assert_stores_equal(s1, sN)
