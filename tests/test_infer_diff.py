"""Inference-reducer runtime differentials (ISSUE 19).

Three pins, all exact-arithmetic over a hazard corpus (invalid rows,
hour-late rows, duplicates, teleport jumps):

1. **Count-path byte-identity** — composing the kalman reducer must
   not move the count fold by one byte: tile docs (minus the reducer's
   OWN optional velocity columns), positions, window digests, and the
   event-conservation counters are identical with the reducer on vs
   off.
2. **Re-batching / replay determinism** — filter state, velocity
   fields, and forecasts are byte-identical across batch sizes and
   across a kill + checkpoint-resume; the anomaly stream is the same
   multiset.
3. **1-vs-N shard fan-in** — filter slots are keyed by (vehicle,
   owner shard), so a 1-shard run configured with N LOGICAL entity
   shards maintains exactly the union of a real N-shard fleet's
   per-shard tables — stale re-entry tracks included: the fleet's
   merged anomaly stream and count-weight-merged velocity fields
   equal the single run's, and every (vehicle, owner) slot's final
   state byte-matches the owning shard's.

Plus the acceptance path: anomaly continuous queries flow end-to-end
from the fold to a CQ subscriber with ZERO writer-side evaluation
cost (evaluations happen in the subscriber's drain, never on the
writer thread).
"""

import copy
import time

import numpy as np
import pytest

from heatmap_tpu.config import load_config
from heatmap_tpu.query import TileMatView
from heatmap_tpu.sink import MemoryStore
from heatmap_tpu.stream import MemorySource, MicroBatchRuntime

T_NOW = int(time.time()) - 600
BATCH = 256
N_SHARDS = 3
_VEL_KEYS = ("vxKmh", "vyKmh")


def mk_stream(late=True):
    """37 vehicles doing plausible city motion (so the Kalman filter
    has real tracks), plus every hazard class: invalid rows, an
    8x-duplicated row, hour-late rows, and one vehicle teleporting
    across the box.

    ``late=False`` drops the hour-late rows: the filter's fold order
    is (ts, stream order) WITHIN each batch, so an out-of-order row's
    position relative to rows of other batches moves with the batch
    boundaries — re-batching invariance is claimed (and pinned) for
    per-entity in-order streams, while late rows stay deterministic
    for any FIXED partitioning (the checkpoint-replay pin below)."""
    rng = np.random.default_rng(11)
    pos = {v: (42.3 + 0.2 * rng.random(), -71.2 + 0.2 * rng.random())
           for v in range(37)}
    vel = {v: (rng.uniform(-8e-5, 8e-5), rng.uniform(-8e-5, 8e-5))
           for v in range(37)}

    def ev(i, t, lat=None, lon=None):
        v = i % 37
        la, lo = pos[v]
        dla, dlo = vel[v]
        pos[v] = (la + dla, lo + dlo)
        return {
            "provider": "mbta" if v % 3 else "opensky",
            "vehicleId": f"veh-{v}",
            "lat": la if lat is None else lat,
            "lon": lo if lon is None else lon,
            "speedKmh": float(np.hypot(dla, dlo) * 111_320 * 3.6 / 5.0),
            "bearing": 0.0,
            "accuracyM": 5.0,
            "ts": t,
        }

    out = [ev(i, T_NOW + 5 * (i // 37)) for i in range(3 * BATCH)]
    bad = [
        ev(1, T_NOW + 130, lat=95.0),            # lat out of range
        ev(2, T_NOW + 130, lon=-200.0),          # lon out of range
        ev(3, -5),                               # negative ts
        ev(4, T_NOW + 130, lat=float("nan")),    # non-finite lat
    ]
    dup = ev(0, T_NOW + 200, lat=42.35, lon=-71.05)
    out += bad + [copy.deepcopy(dup) for _ in range(8)]
    if late:
        out += [ev(i, T_NOW - 3600) for i in range(24)]      # late
    # one vehicle teleports 60 km and keeps reporting from there
    out += [ev(0, T_NOW + 260, lat=42.95, lon=-71.1)]
    out += [ev(i, T_NOW + 270 + 5 * (i // 37)) for i in range(BATCH - 29)]
    return out


def run_rt(tmp_path, events, store, tag, reducers=("count",), view=None,
           batch=BATCH, shards=1, index=0, entity_shards=0,
           checkpoint_every=0, source=None, run=True, flush_k=3):
    cfg = load_config(
        {}, batch_size=batch, state_capacity_log2=12, speed_hist_bins=8,
        store="memory", emit_flush_k=flush_k, reducers=reducers,
        shards=shards, shard_index=index, entity_shards=entity_shards,
        checkpoint_dir=str(tmp_path / f"ckpt-{tag}"))
    if source is None:
        source = MemorySource(copy.deepcopy(events))
        source.finish()
    rt = MicroBatchRuntime(cfg, source, store,
                           checkpoint_every=checkpoint_every, view=view)
    if run:
        rt.run()
    return rt


def _tiles_sans_velocity(store):
    out = {}
    for k, d in store._tiles.items():
        d = dict(d)
        for vk in _VEL_KEYS:
            d.pop(vk, None)
        out[k] = d
    return out


def _anoms_of(view):
    """Anomaly event multiset captured off the view's mutation feed
    (sorted: publication order shifts with batch boundaries)."""
    evs = []
    for rec in view.captured_anomalies:
        evs.extend(rec["events"])
    return sorted((e["entity"], e["reason"], e["t"], e["cell"],
                   e["score"], e["lat"], e["lon"]) for e in evs)


def _watching_view():
    view = TileMatView(delta_log=8192, pyramid_levels=2)
    view.captured_anomalies = []
    view.add_watcher(
        lambda rec: view.captured_anomalies.append(rec)
        if rec.get("kind") == "anomaly" else None)
    return view


def _conservation_keys(rt):
    snap = rt.metrics.snapshot()
    return {k: snap.get(k, 0) for k in
            ("events_valid", "events_invalid", "events_late",
             "batches", "tiles_emitted", "positions_emitted")}


# ------------------------------------------------- count-path identity
def test_count_path_byte_identity_reducers_on_vs_off(tmp_path):
    events = mk_stream()
    off_store, on_store = MemoryStore(), MemoryStore()
    off_view, on_view = _watching_view(), _watching_view()
    rt_off = run_rt(tmp_path, events, off_store, "off", view=off_view)
    rt_on = run_rt(tmp_path, events, on_store, "on",
                   reducers=("count", "kalman"), view=on_view)

    assert rt_off.infer is None and rt_on.infer is not None
    # tile docs: byte-identical once the reducer's OWN optional
    # velocity columns are stripped — the count fold itself never moves
    base = _tiles_sans_velocity(off_store)
    enriched = _tiles_sans_velocity(on_store)
    assert base.keys() == enriched.keys() and len(base) > 50
    for k in base:
        assert base[k] == enriched[k], k
    # ... and the reducer DID add velocity somewhere, or the strip
    # above proved nothing
    assert any(any(vk in d for vk in _VEL_KEYS)
               for d in on_store._tiles.values())
    assert off_store._positions == on_store._positions
    # conservation counters: the reducer consumes the same dispatched
    # batches, drops nothing, adds nothing
    assert _conservation_keys(rt_off) == _conservation_keys(rt_on)
    # view state identical too (anomaly records deliberately never
    # touch window content): same latest window, same docs once the
    # optional velocity columns are stripped
    assert (off_view.latest_ws_of("h3r8")
            == on_view.latest_ws_of("h3r8") is not None)
    ws_off, docs_off = off_view.latest_docs("h3r8")
    ws_on, docs_on = on_view.latest_docs("h3r8")
    assert ws_off == ws_on

    def _strip(docs):
        return sorted(({k: v for k, v in d.items() if k not in _VEL_KEYS}
                       for d in docs), key=lambda d: str(d))
    assert _strip(docs_off) == _strip(docs_on)
    # the hazard corpus did exercise the filter: anomalies flowed
    assert not off_view.captured_anomalies
    assert _anoms_of(on_view)


@pytest.mark.parametrize("flush_k", [1, 8])
def test_velocity_columns_follow_the_batch_not_the_flush(tmp_path, flush_k):
    """A tile's velocity columns are the filter's as of the batch that
    emitted the tile, so how long its emit ring parked the batch (the
    per-device cadence of a mesh, emit_flush_k, the governor) leaves
    every doc byte-identical."""
    events = mk_stream()
    base, other = MemoryStore(), MemoryStore()
    run_rt(tmp_path, events, base, "k3", reducers=("count", "kalman"))
    run_rt(tmp_path, events, other, f"k{flush_k}",
           reducers=("count", "kalman"), flush_k=flush_k)
    assert any(any(vk in d for vk in _VEL_KEYS)
               for d in base._tiles.values())
    assert base._tiles == other._tiles


# --------------------------------------------- re-batching determinism
def test_batch_size_invariance_filter_and_anomalies(tmp_path):
    events = mk_stream(late=False)
    outs = []
    for tag, batch in (("b256", BATCH), ("b512", 2 * BATCH)):
        view = _watching_view()
        rt = run_rt(tmp_path, events, MemoryStore(), tag,
                    reducers=("count", "kalman"), view=view, batch=batch)
        outs.append((rt, view))
    (rt_a, va), (rt_b, vb) = outs
    ta, tb = rt_a.infer.table, rt_b.infer.table
    names = sorted(n for n in ta.names if n)
    assert names == sorted(n for n in tb.names if n) and names
    for n in names:
        sa = [i for i, nm in enumerate(ta.names) if nm == n][0]
        sb = [i for i, nm in enumerate(tb.names) if nm == n][0]
        np.testing.assert_array_equal(ta.x[sa], tb.x[sb], err_msg=n)
        np.testing.assert_array_equal(ta.P[sa], tb.P[sb], err_msg=n)
    assert (rt_a.infer.forecast_cells(300.0, 8)
            == rt_b.infer.forecast_cells(300.0, 8))
    assert (rt_a.infer.velocity_field(8)
            == rt_b.infer.velocity_field(8))
    assert _anoms_of(va) == _anoms_of(vb)


def test_checkpoint_resume_replay_equals_uninterrupted(tmp_path):
    events = mk_stream()
    solid = run_rt(tmp_path, events, MemoryStore(), "solid",
                   reducers=("count", "kalman"))

    # kill after 2 committed batches (manual stepping models a process
    # killed before close), then a fresh runtime resumes the same
    # checkpoint dir: the entity table restores WITH the window state
    src = MemorySource(copy.deepcopy(events))
    src.finish()
    rt1 = run_rt(tmp_path, events, MemoryStore(), "crash",
                 reducers=("count", "kalman"), checkpoint_every=1,
                 source=src, run=False)
    for _ in range(2):
        rt1.step_once()
    rt1._checkpoint()
    rt1._ckpt_join()
    assert rt1.infer.table.occupancy > 0

    src2 = MemorySource(copy.deepcopy(events))
    src2.finish()
    rt2 = run_rt(tmp_path, events, MemoryStore(), "crash",
                 reducers=("count", "kalman"), source=src2, run=False)
    assert rt2.infer.table.occupancy == rt1.infer.table.occupancy
    rt2.run()

    ts_, tr = solid.infer.table, rt2.infer.table
    names = sorted(n for n in ts_.names if n)
    assert names == sorted(n for n in tr.names if n) and names
    for n in names:
        ss = [i for i, nm in enumerate(ts_.names) if nm == n][0]
        sr = [i for i, nm in enumerate(tr.names) if nm == n][0]
        np.testing.assert_array_equal(ts_.x[ss], tr.x[sr], err_msg=n)
        np.testing.assert_array_equal(ts_.P[ss], tr.P[sr], err_msg=n)
    assert (solid.infer.forecast_cells(300.0, 8)
            == rt2.infer.forecast_cells(300.0, 8))


# ------------------------------------------------------ shard fan-in
def test_one_vs_n_shard_fanin_with_handoffs(tmp_path):
    # in-order corpus: shard batch boundaries fall at different stream
    # positions than the single run's (the ownership filter compacts),
    # so the cross-partitioning invariance needs per-entity in-order
    # streams — exactly as for the batch-size pin above
    events = mk_stream(late=False)
    single_view = _watching_view()
    single = run_rt(tmp_path, events, MemoryStore(), "single",
                    reducers=("count", "kalman"), view=single_view,
                    entity_shards=N_SHARDS)
    assert single.infer.partition is not None
    # the corpus must actually cross entity-shard boundaries
    assert single.infer.table.n_reseed_handoff > 0

    fleet, fleet_views = [], []
    fleet_store = MemoryStore()
    for i in range(N_SHARDS):
        v = _watching_view()
        fleet.append(run_rt(tmp_path, events, fleet_store, f"s{i}",
                            reducers=("count", "kalman"), view=v,
                            shards=N_SHARDS, index=i))
        fleet_views.append(v)

    # merged anomaly stream == the single logical-N run's, exactly —
    # including teleports gated off a STALE track an entity resumed on
    # re-entering a shard (slots are keyed (vehicle, owner), so the
    # logical table IS the union of the fleet's)
    merged = sorted(sum((_anoms_of(v) for v in fleet_views), []))
    assert merged == _anoms_of(single_view) and merged

    # the logical table is the exact union of the fleet's per-shard
    # tables: every (vehicle, owner) slot byte-matches the state the
    # owning shard holds for that vehicle, stale tracks included
    st = single.infer.table
    assert (sum(f.infer.table.occupancy for f in fleet)
            == st.occupancy)
    checked = 0
    for slot in np.nonzero(st.vid >= 0)[0]:
        name, owner = st.names[int(slot)], int(st.owner[slot])
        ft = fleet[owner].infer.table
        fs = [i for i, nm in enumerate(ft.names) if nm == name]
        assert fs, f"{name} missing from owning shard {owner}"
        np.testing.assert_array_equal(st.x[slot], ft.x[fs[0]],
                                      err_msg=name)
        np.testing.assert_array_equal(st.P[slot], ft.P[fs[0]],
                                      err_msg=name)
        checked += 1
    assert checked > 10

    # velocity outputs fan in exactly too: the fleet's per-shard
    # fields, count-weight merged, equal the single run's field
    single_vel = single.infer.velocity_field(8)
    merged_vel: dict = {}
    for f in fleet:
        for c, (vx, vy, ct) in f.infer.velocity_field(8).items():
            pvx, pvy, pct = merged_vel.get(c, (0.0, 0.0, 0))
            tot = pct + ct
            merged_vel[c] = ((pvx * pct + vx * ct) / tot,
                             (pvy * pct + vy * ct) / tot, tot)
    assert merged_vel.keys() == single_vel.keys() and merged_vel
    for c, (vx, vy, ct) in single_vel.items():
        mvx, mvy, mct = merged_vel[c]
        assert mct == ct
        np.testing.assert_allclose((mvx, mvy), (vx, vy), rtol=1e-9,
                                   err_msg=hex(c))


# ----------------------------------------------- anomaly CQ end-to-end
def test_anomaly_cq_end_to_end_zero_writer_cost(tmp_path):
    from heatmap_tpu.query.continuous import ContinuousQueryEngine

    events = mk_stream()
    view = _watching_view()
    cq = ContinuousQueryEngine(view)
    city = [-71.3, 42.2, -70.9, 43.05]  # covers the teleport target too
    qid = cq.register({"type": "anomaly", "bbox": city,
                       "ttl_s": 0}, "h3r8")["id"]
    rt = run_rt(tmp_path, events, MemoryStore(), "cq",
                reducers=("count", "kalman"), view=view)
    # writer-side cost is ZERO: every cq_* counter on the WRITER's
    # registry stays untouched — matching happens in the subscriber's
    # drain below, never on the writer thread
    writer_cq = {k: v for k, v in rt.metrics.snapshot().items()
                 if k.startswith("cq_")}
    assert all(v == 0 for v in writer_cq.values()), writer_cq
    cq.drain()
    anoms = [m for m in cq.events_since(qid, -1, max_n=100000)
             if m.get("kind") == "anomaly"]
    assert anoms, "subscriber must receive the fold's anomalies"
    assert "teleport" in {m["reason"] for m in anoms}
    for m in anoms:
        assert m["query"] == qid
        assert m["entity"].startswith("veh-")
        assert m["reason"] in ("stopped", "teleport", "deviation")
        assert m["cell"] and m["score"] is not None
    # the reason filter composes: a stopped-only query sees none of
    # the teleports
    q2 = cq.register({"type": "anomaly", "reasons": ["stopped"],
                      "bbox": city, "ttl_s": 0}, "h3r8")["id"]
    view.publish_anomalies("h3r8", [
        {"entity": "veh-0", "reason": "teleport", "cell":
         anoms[0]["cell"], "lat": 42.0, "lon": -71.0, "t": T_NOW,
         "score": 20.0, "speedKmh": 3.0}])
    cq.drain()
    assert not cq.events_since(q2, -1)
    cq.close()
