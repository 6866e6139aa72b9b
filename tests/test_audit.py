"""Integrity observatory (obs/audit.py, ISSUE 12): the conservation
ledger and per-window content digests.

Acceptance properties pinned here:

- **Conservation**: a clean run — unsharded, 2-shard fan-in +
  2-replica, and a 2-device partitioned mesh — reports ZERO ledger
  residual at every boundary and zero digest mismatches, with
  per-shard digests XOR-combining exactly to the merged-view digest.
- **Observe-only**: an audited run's emits and view are byte-identical
  to an unaudited run over the invalid/late/dup corpus.
- **Chaos**: a corrupted repl segment record is detected by the
  replica within ONE seq advance, `/healthz` degrades naming the
  (grid, window, seq), and the flight recorder dumps under exactly one
  correlated fleet episode; a shard whose view merge went missing is
  caught by the /fleet/audit digest combine.
- **Closed drop reasons**: every drop path is reason-tagged; an
  unknown reason raises (an untagged drop would be a permanent
  residual).
"""

import copy
import datetime as dt
import glob
import json
import os
import time

import numpy as np
import pytest

from heatmap_tpu.config import load_config
from heatmap_tpu.obs.audit import (
    AuditState,
    DigestTable,
    combine_digests,
    doc_hash,
    residuals_from_counts,
)
from heatmap_tpu.obs.registry import Registry
from heatmap_tpu.sink import MemoryStore
from heatmap_tpu.sink.base import TileDoc, UTC
from heatmap_tpu.stream import MemorySource, MicroBatchRuntime

T_NOW = int(time.time()) - 600
BATCH = 256


# ------------------------------------------------------------ corpus
def mk_stream():
    """Clean traffic over a wide box + every hazard the ledger must
    account: invalid rows, duplicates, and hour-late rows."""
    rng = np.random.default_rng(7)

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
    out += [ev(1, T_NOW + 130, lat=95.0),          # invalid lat
            ev(3, -5)]                             # invalid ts
    dup = ev(0, T_NOW + 200, lat=42.35, lon=-71.05)
    out += [copy.deepcopy(dup) for _ in range(6)]
    out += [ev(i, T_NOW - 3600) for i in range(24)]  # late
    out += [ev(i, T_NOW + 210 + i % 30) for i in range(BATCH - 30)]
    return out


def run_rt(tmp_path, events, store, tag, audit=True, shards=1, index=0,
           view=None, mesh=None):
    cfg = load_config(
        {}, batch_size=BATCH, state_capacity_log2=12, speed_hist_bins=8,
        store="memory", emit_flush_k=3, audit=audit, shards=shards,
        shard_index=index,
        checkpoint_dir=str(tmp_path / f"ckpt-{tag}"))
    src = MemorySource(copy.deepcopy(events))
    src.finish()
    rt = MicroBatchRuntime(cfg, src, store, mesh=mesh,
                           checkpoint_every=0, view=view)
    rt.run()
    return rt


def _doc(cell, ws, count, grid="h3r8"):
    return TileDoc("bos", 8, cell, ws, ws + dt.timedelta(minutes=5),
                   count=count, avg_speed_kmh=30.0, avg_lat=42.3,
                   avg_lon=-71.05, ttl_minutes=45, grid=grid)


# ------------------------------------------------------- drop reasons
def test_drop_reason_set_is_closed():
    """The reason set is closed: the declared tuple is exactly the
    contract, every reason maps to a legacy counter, and an unknown
    reason RAISES instead of minting an untagged drop path."""
    from heatmap_tpu.stream.metrics import DROP_REASONS, Metrics

    assert DROP_REASONS == ("invalid", "late", "out_of_shard",
                            "oversample", "handoff")
    m = Metrics()
    for r in DROP_REASONS:
        m.drop(r, 2)
    assert m.counters["events_invalid"] == 2
    assert m.counters["events_late"] == 2
    assert m.counters["events_out_of_shard"] == 4  # + oversample
    assert m.counters["infer_handoff_reseed"] == 2
    text = m.registry.expose_text()
    for r in DROP_REASONS:
        assert f'heatmap_events_dropped_total{{reason="{r}"}} 2' in text
    with pytest.raises(ValueError):
        m.drop("mystery", 1)
    m.drop("late", 0)  # zero is a no-op, not an error


def test_drop_forwards_to_audit_ledger():
    from heatmap_tpu.stream.metrics import Metrics

    m = Metrics()
    aud = AuditState(tag="t")
    m.audit = aud
    m.drop("invalid", 3)
    m.drop("late", 5, audit=False)  # secondary-pair drops stay out
    assert aud.counts() == {"dropped_invalid": 3}


# ------------------------------------------------------------ digests
def test_digest_algebra():
    ws = dt.datetime.fromtimestamp(1_900_000_000, UTC)
    a = _doc("8a2a1072b59ffff", ws, 5)
    b = _doc("8a2a1072b5bffff", ws, 7)
    c = _doc("8a2a1072b5dffff", ws, 9)

    # order independence: any apply order, same digest
    t1, t2 = DigestTable(), DigestTable()
    t1.apply_docs([a, b, c])
    t2.apply_docs([c, a, b])
    ws_i = int(ws.timestamp())
    assert t1.digest("h3r8", ws_i) == t2.digest("h3r8", ws_i) != 0

    # upsert replaces: re-applying the same doc is a no-op; a changed
    # doc moves the digest and equals a fresh build of the final state
    before = t1.digest("h3r8", ws_i)
    t1.apply_doc(copy.deepcopy(a))
    assert t1.digest("h3r8", ws_i) == before
    a2 = _doc("8a2a1072b59ffff", ws, 6)
    t1.apply_doc(a2)
    fresh = DigestTable()
    fresh.apply_docs([a2, b, c])
    assert t1.digest("h3r8", ws_i) == fresh.digest("h3r8", ws_i) != before

    # shard XOR-combine: disjoint cell spaces combine to the merged
    # digest, in any grouping
    s1, s2, merged = DigestTable(), DigestTable(), DigestTable()
    s1.apply_docs([a, c])
    s2.apply_docs([b])
    merged.apply_docs([a, b, c])
    assert combine_digests([s1.digest("h3r8", ws_i),
                            s2.digest("h3r8", ws_i)]) \
        == merged.digest("h3r8", ws_i)
    assert combine_digests([]) == 0  # empty identity

    # eviction retires the window's digest entirely
    merged.drop_window("h3r8", ws_i)
    assert merged.digest("h3r8", ws_i) is None
    assert merged.snapshot() == {}

    # content sensitivity: the hash moves with any field change
    assert doc_hash(a) != doc_hash(a2)
    assert doc_hash(a) == doc_hash(copy.deepcopy(a))


def test_digest_snapshot_prunes_stale_windows():
    ws = dt.datetime.now(UTC) - dt.timedelta(hours=3)
    t = DigestTable()
    t.apply_doc(_doc("8a2a1072b59ffff", ws, 1))  # staleAt long past
    assert t.snapshot(now=time.time()) == {}
    assert t.snapshot() != {}  # un-clocked snapshot keeps everything


# ------------------------------------------------------------- ledger
def test_leak_detection_names_boundary():
    """A residual that never drains degrades naming the boundary; one
    that drains (in-flight pipeline depth) never does."""
    aud = AuditState(tag="t", settle_s=5.0, clock=lambda: 0.0)
    aud.add("polled", 10)
    aud.add("folded", 8)  # 2 rows vanished untagged
    assert aud.residuals()["feed_fold"] == 2
    assert aud.leaking(now=0.0) == {}          # first sight: not yet
    assert aud.leaking(now=4.9) == {}          # inside the window
    leaks = aud.leaking(now=5.0)
    assert leaks == {"feed_fold": 2}
    checks, degraded = aud.healthz_checks(now=5.0)
    assert degraded
    assert checks["audit_residual"]["ok"] is False
    assert checks["audit_residual"]["boundary"] == "feed_fold"

    # draining resets the timer: residual decreased at t=6, so even at
    # t=10.9 nothing leaks; hitting zero keeps it clean forever
    aud.add("dropped_invalid", 1)
    assert aud.residuals()["feed_fold"] == 1
    assert aud.leaking(now=6.0) == {}
    assert aud.leaking(now=10.9) == {}
    aud.add("folded", 1)
    assert aud.leaking(now=100.0) == {}
    checks, degraded = aud.healthz_checks(now=100.0)
    assert not degraded and checks["audit_residual"]["value"] == "conserved"


def test_residuals_from_counts_identities():
    counts = {"polled": 100, "folded": 90, "dropped_invalid": 4,
              "dropped_late": 3, "dropped_out_of_shard": 3,
              "docs_emitted": 40, "docs_committed": 40,
              "docs_view_applied": 39}
    res = residuals_from_counts(counts, has_view=True)
    assert res == {"feed_fold": 0, "emit_sink": 0, "sink_view": 1}
    assert "sink_view" not in residuals_from_counts(counts,
                                                    has_view=False)


# ------------------------------------- clean-run conservation + diff
def test_clean_run_conserves_and_is_byte_identical_to_unaudited(
        tmp_path):
    """The headline differential: HEATMAP_AUDIT=1 over the
    invalid/late/dup corpus is byte-identical to HEATMAP_AUDIT=0
    (audit is observe-only), every ledger boundary reports zero
    residual after the drained close, and the emit-shard digest equals
    the view digest for every window."""
    events = mk_stream()
    s_off, s_on = MemoryStore(), MemoryStore()
    rt_off = run_rt(tmp_path, events, s_off, "off", audit=False)
    rt_on = run_rt(tmp_path, events, s_on, "on", audit=True)
    assert rt_off.audit is None and rt_on.audit is not None

    # byte-identical sink + view state
    assert s_off._tiles.keys() == s_on._tiles.keys()
    assert len(s_off._tiles) > 100
    for k in s_off._tiles:
        assert s_off._tiles[k] == s_on._tiles[k], k
    assert s_off._positions == s_on._positions
    assert rt_off.matview.export_state() == rt_on.matview.export_state()

    # zero residual at every boundary; healthz fully green
    aud = rt_on.audit
    res = aud.residuals()
    assert res and all(v == 0 for v in res.values()), res
    assert aud.leaking() == {}
    checks, degraded = aud.healthz_checks()
    assert not degraded
    # the corpus exercised the drop reasons the ledger subtracts
    counts = aud.counts()
    assert counts["dropped_invalid"] == 2
    assert counts["dropped_late"] > 0
    assert counts["polled"] == counts["folded"] \
        + counts["dropped_invalid"] + counts["dropped_late"]
    assert counts["docs_emitted"] == counts["docs_committed"] \
        == counts["docs_view_applied"] > 0

    # the emit-shard digest table IS the view digest table
    assert aud.shard_table(None).snapshot() \
        == rt_on.matview.audit_table.snapshot() != {}

    # artifact stamp: clean books
    stamp = aud.bench_stamp()
    assert stamp["max_residual"] == 0 and stamp["mismatches"] == 0


def test_sharded_replicated_conservation(tmp_path):
    """ISSUE 12 conservation proof: 2 H3 shards fanning into one
    merged view, replicated to 2 followers — zero residual on every
    shard, per-shard digests XOR-combine exactly to the merged-view
    digest (checked via the same fleet stitch /fleet/audit serves),
    and both replicas verify every published digest with zero
    mismatches."""
    from heatmap_tpu.obs.fleet import fleet_audit
    from heatmap_tpu.query import TileMatView
    from heatmap_tpu.query.repl import (DeltaLogPublisher,
                                        FileFeedSource,
                                        ReplicaViewFollower)

    events = mk_stream()
    merged_view = TileMatView(delta_log=4096, pyramid_levels=2,
                              audit=DigestTable())
    pub = DeltaLogPublisher(merged_view, str(tmp_path / "feed"),
                            start=False)
    replicas = []
    for i in range(2):
        reg = Registry()
        aud = AuditState(reg, tag=f"replica{i}")
        r_view = TileMatView(replica=True, audit=DigestTable())
        fol = ReplicaViewFollower(r_view,
                                  FileFeedSource(str(tmp_path / "feed")),
                                  registry=reg, audit=aud)
        aud.attach(view=r_view, follower=fol)
        replicas.append((aud, r_view, fol))

    store = MemoryStore()
    fleet = [run_rt(tmp_path, events, store, f"s{i}", audit=True,
                    shards=2, index=i, view=merged_view)
             for i in range(2)]
    pub.flush()
    for _aud, _v, fol in replicas:
        while fol.step():
            pass
    pub.close()

    members = {}
    for i, rt in enumerate(fleet):
        assert rt.audit.leaking() == {}
        assert all(v == 0 for v in rt.audit.residuals().values())
        assert rt.metrics.counters.get("events_out_of_shard", 0) > 0
        rt.audit.attach(view=merged_view)  # the shared fan-in view
        members[f"shard{i}"] = {"audit": rt.audit.member_block()}

    # per-shard digests combine EXACTLY to the merged-view digest,
    # via the same stitch /fleet/audit serves
    stitched = fleet_audit(members)
    assert stitched["combine"], "fan-in windows must be checked"
    assert all(c["ok"] for c in stitched["combine"]), stitched["combine"]
    assert stitched["combine_mismatches"] == 0
    assert stitched["ok"]

    # both replicas: synced, verified > 0, zero mismatches, and a view
    # byte-identical to the writer's
    for aud, r_view, fol in replicas:
        assert fol.synced and fol.seq_lag() == 0
        assert aud.mismatches == 0
        assert aud.verified > 0
        assert aud.last_verified_seq == merged_view.seq
        assert r_view.export_state() == merged_view.export_state()


def test_mesh_conservation(tmp_path):
    """Partitioned-mesh (D>=2) clean run: zero residuals, and the
    per-DEVICE digest tables XOR-combine to the view digest per
    window."""
    import jax

    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    from heatmap_tpu.parallel import make_mesh

    store = MemoryStore()
    rt = run_rt(tmp_path, mk_stream(), store, "mesh", audit=True,
                mesh=make_mesh(2))
    assert rt._parted is not None and rt.audit is not None
    assert all(v == 0 for v in rt.audit.residuals().values())
    assert rt.audit.leaking() == {}
    view_snap = rt.matview.audit_table.snapshot()
    assert view_snap
    tables = [rt.audit.shard_table(d) for d in range(2)]
    assert any(t.snapshot() for t in tables)
    for grid, per_ws in view_snap.items():
        for ws_s, d in per_ws.items():
            ws = int(ws_s)
            got = combine_digests(
                t.digest(grid, ws) or 0 for t in tables)
            assert format(got, "016x") == d["digest"], (grid, ws)


# --------------------------------------------------------------- chaos
def test_corrupt_repl_record_detected_within_one_seq(tmp_path,
                                                     monkeypatch):
    """Chaos acceptance: one corrupted (valid-JSON) record in a repl
    segment → the replica detects it AT that record's seq, /healthz
    degrades naming (grid, window, seq), and the flight recorder dumps
    under exactly ONE correlated fleet episode."""
    from heatmap_tpu.obs.flightrec import FlightRecorder
    from heatmap_tpu.obs.xproc import read_episode
    from heatmap_tpu.query import TileMatView
    from heatmap_tpu.query import repl as replmod
    from heatmap_tpu.query.repl import (DeltaLogPublisher,
                                        FileFeedSource,
                                        ReplicaViewFollower)

    chan = str(tmp_path / "chan")
    feed = str(tmp_path / "feed")
    rec_dir = str(tmp_path / "flightrec")
    w = TileMatView(audit=DigestTable())
    pub = DeltaLogPublisher(w, feed, start=False)
    reg = Registry()
    aud = AuditState(reg, tag="replica0", channel_path=chan,
                     flightrec=FlightRecorder(rec_dir))
    r = TileMatView(replica=True, audit=DigestTable())
    fol = ReplicaViewFollower(r, FileFeedSource(feed), registry=reg,
                              audit=aud)
    aud.attach(view=r, follower=fol)

    ws = dt.datetime.now(UTC).replace(microsecond=0) - \
        dt.timedelta(minutes=2)
    w.apply_docs([_doc("8a2a1072b59ffff", ws, 5),
                  _doc("8a2a1072b5bffff", ws, 7)])
    pub.flush()
    while fol.step():
        pass
    assert aud.verified >= 1 and aud.mismatches == 0

    # corrupt the NEXT record's content (valid JSON, same seq/dg)
    w.apply_docs([_doc("8a2a1072b59ffff", ws, 6)])
    pub.flush()
    seg = sorted(glob.glob(os.path.join(feed, "seg-*.jsonl")))[-1]
    lines = open(seg).read().splitlines()
    bad = json.loads(lines[-1])
    bad["docs"][0]["count"] = 999
    corrupt_seq = bad["seq"]
    lines[-1] = replmod.dumps(bad)
    open(seg, "w").write("\n".join(lines) + "\n")

    fol.step()
    # detected AT the corrupted seq — within one seq advance
    assert aud.mismatches == 1
    assert aud.last_mismatch["seq"] == corrupt_seq
    assert aud.last_mismatch["grid"] == "h3r8"
    assert aud.last_mismatch["ws"] == int(ws.timestamp())
    checks, degraded = aud.healthz_checks()
    assert degraded and not checks["audit_digest"]["ok"]
    for token in ("h3r8", str(int(ws.timestamp())), str(corrupt_seq)):
        assert token in checks["audit_digest"]["value"]
    assert f'heatmap_audit_digest_mismatch_total 1' \
        in reg.expose_text()

    # one correlated episode: the broadcast exists, the dump carries
    # its id, and a SECOND mismatch in the same incident neither mints
    # a new id nor re-dumps
    ep = read_episode(chan)
    assert ep and ep.get("origin") == "replica0"
    dumps = glob.glob(os.path.join(rec_dir, "flightrec-*.json"))
    assert len(dumps) == 1
    dumped = json.load(open(dumps[0]))
    assert dumped.get("episode_id") == ep["episode_id"]
    assert "audit" in dumped
    aud.note_digest_mismatch("h3r8", int(ws.timestamp()),
                             corrupt_seq + 1)
    assert read_episode(chan)["episode_id"] == ep["episode_id"]
    assert len(glob.glob(os.path.join(rec_dir,
                                      "flightrec-*.json"))) == 1


def test_fleet_combine_detects_skipped_shard_merge():
    """The SIGKILL-skip chaos, in its constructed form: shard B's docs
    never reached the merged view — the per-window XOR combine catches
    it and names the window."""
    from heatmap_tpu.obs.fleet import fleet_audit

    ws = dt.datetime.fromtimestamp(1_900_000_000, UTC)
    ws_i = int(ws.timestamp())
    a, b = _doc("8a2a1072b59ffff", ws, 5), _doc("8a2a1072b5bffff", ws, 7)
    shard_a, shard_b, view = DigestTable(), DigestTable(), DigestTable()
    shard_a.apply_doc(a)
    shard_b.apply_doc(b)
    view.apply_doc(a)  # B's merge was skipped

    def blk(table, with_view):
        out = {"ledger": {}, "residuals": {},
               "digests": {"shard": {"self": table.snapshot()}},
               "verify": {"verified": 0, "mismatches": 0}}
        if with_view:
            out["digests"]["view"] = view.snapshot()
        return out

    stitched = fleet_audit({
        "shard0": {"audit": blk(shard_a, with_view=True)},
        "shard1": {"audit": blk(shard_b, with_view=False)},
    })
    assert stitched["combine_mismatches"] == 1
    assert not stitched["ok"]
    bad = [c for c in stitched["combine"] if not c["ok"]]
    assert bad[0]["grid"] == "h3r8" and bad[0]["ws"] == ws_i
    assert "shard1/self" in bad[0]["shards"]

    # the healthy world: view holds BOTH docs -> exact combine
    view.apply_doc(b)
    stitched = fleet_audit({
        "shard0": {"audit": blk(shard_a, with_view=True)},
        "shard1": {"audit": blk(shard_b, with_view=False)},
    })
    assert stitched["ok"] and stitched["combine"][0]["ok"]


# ----------------------------------------------------------- surfaces
def _get(app, path):
    out = {}

    def start_response(status, headers):
        out["status"] = status

    body = b"".join(app({"PATH_INFO": path, "REQUEST_METHOD": "GET",
                         "QUERY_STRING": ""}, start_response))
    return out["status"], body


def test_debug_audit_endpoint_and_fleet_audit(tmp_path, monkeypatch):
    from heatmap_tpu.obs.xproc import publish_member_snapshot
    from heatmap_tpu.serve.api import make_wsgi_app

    store = MemoryStore()
    rt = run_rt(tmp_path, mk_stream()[:300], store, "serve", audit=True)
    cfg = load_config({}, store="memory", audit=True)
    app = make_wsgi_app(store, cfg, runtime=rt)
    status, body = _get(app, "/debug/audit")
    assert status.startswith("200")
    payload = json.loads(body)
    assert payload["residuals"] and payload["ledger"]["polled"] > 0
    assert payload["worst_boundary"] is None  # clean books

    # /fleet/audit: 503 channel-less, stitched with a channel
    status, _ = _get(app, "/fleet/audit")
    assert status.startswith("503")
    chan = str(tmp_path / "chan")
    monkeypatch.setenv("HEATMAP_SUPERVISOR_CHANNEL", chan)
    publish_member_snapshot(chan, "p0", role="runtime",
                            audit=rt.audit.member_block())
    status, body = _get(app, "/fleet/audit")
    assert status.startswith("200")
    stitched = json.loads(body)
    assert stitched["member_tags"] == ["p0"]
    assert stitched["ok"] and all(c["ok"] for c in stitched["combine"])
    assert stitched["ledger"]["polled"] \
        == rt.audit.counts()["polled"]

    # audit off -> /debug/audit is 503
    cfg_off = load_config({}, store="memory")
    app_off = make_wsgi_app(MemoryStore(), cfg_off, runtime=None)
    status, _ = _get(app_off, "/debug/audit")
    assert status.startswith("503")


def test_obs_top_renders_audit_rows():
    import importlib.util

    repo = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        os.pardir))
    spec = importlib.util.spec_from_file_location(
        "obs_top", os.path.join(repo, "tools", "obs_top.py"))
    obs_top = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(obs_top)

    m = {
        "heatmap_audit_residual": {'{boundary="emit_sink"}': 4.0,
                                   '{boundary="feed_fold"}': 0.0},
        "heatmap_audit_digests_verified_total": {"": 12.0},
        "heatmap_audit_digest_mismatch_total": {"": 1.0},
        "heatmap_audit_last_verified_seq": {"": 42.0},
    }
    frame = obs_top.render_frame(m, None, 0.0, None)
    assert "audit" in frame and "emit_sink" in frame
    assert "ok 12" in frame and "bad 1" in frame and "MISMATCH" in frame
    # audit off: no row at all
    assert "audit" not in obs_top.render_frame({}, None, 0.0, None)

    fleet_m = {
        "heatmap_fleet_member_up": {
            '{proc="shard0",role="runtime"}': 1.0,
            '{proc="replica0",role="serve"}': 1.0},
        "heatmap_audit_residual": {
            '{proc="shard0",boundary="feed_fold"}': 0.0},
        "heatmap_audit_digest_mismatch_total": {'{proc="replica0"}': 2.0},
        "heatmap_audit_digests_verified_total": {
            '{proc="replica0"}': 9.0},
        "heatmap_audit_last_verified_seq": {'{proc="replica0"}': 17.0},
    }
    frame = obs_top.render_fleet_frame(fleet_m, None, 0.0, None)
    assert "audit" in frame and "replica0" in frame
    assert "MISMATCH" in frame


def test_member_snapshot_carries_audit_block(tmp_path):
    from heatmap_tpu.obs.xproc import members_from, \
        publish_member_snapshot

    chan = str(tmp_path / "chan")
    aud = AuditState(tag="p0")
    aud.add("polled", 5)
    publish_member_snapshot(chan, "p0", role="runtime",
                            audit=aud.member_block())
    publish_member_snapshot(chan, "p1", role="runtime")  # audit off
    members, _ = members_from(chan)
    assert members["p0"]["audit"]["ledger"] == {"polled": 5}
    assert "audit" not in members["p1"]  # byte-compatible when off


def test_fleet_combine_skips_unverifiable_seeded_windows():
    """A window no shard emitted into this boot (store-seeded after a
    restart) is reported skipped — never a false mismatch."""
    from heatmap_tpu.obs.fleet import fleet_audit

    ws = dt.datetime.fromtimestamp(1_900_000_000, UTC)
    ws2 = ws + dt.timedelta(minutes=5)
    a = _doc("8a2a1072b59ffff", ws, 5)
    seeded = _doc("8a2a1072b5bffff", ws2, 9)  # restart seed: no emitter
    shard, view = DigestTable(), DigestTable()
    shard.apply_doc(a)
    view.apply_doc(a)
    view.apply_doc(seeded)
    stitched = fleet_audit({"p0": {"audit": {
        "ledger": {}, "residuals": {},
        "digests": {"shard": {"self": shard.snapshot()},
                    "view": view.snapshot()},
        "verify": {"verified": 0, "mismatches": 0}}}})
    assert stitched["ok"] and stitched["combine_mismatches"] == 0
    by_ws = {c["ws"]: c for c in stitched["combine"]}
    assert by_ws[int(ws.timestamp())]["ok"] is True
    assert by_ws[int(ws2.timestamp())]["ok"] is None
    assert "skipped" in by_ws[int(ws2.timestamp())]


def test_channelless_mismatch_dumps_once_per_incident(tmp_path):
    """Without a fleet channel, each diverged (grid, window) is its own
    incident: the first mismatch of a NEW window still dumps, repeats
    of the same window don't."""
    from heatmap_tpu.obs.flightrec import FlightRecorder

    rec_dir = str(tmp_path / "fr")
    aud = AuditState(tag="r", channel_path="",
                     flightrec=FlightRecorder(rec_dir))
    aud.note_digest_mismatch("h3r8", 100, 1)
    aud.note_digest_mismatch("h3r8", 100, 2)  # same window: no re-dump
    assert len(glob.glob(os.path.join(rec_dir, "flightrec-*"))) == 1
    aud.note_digest_mismatch("h3r8", 200, 3)  # NEW window: new incident
    assert len(glob.glob(os.path.join(rec_dir, "flightrec-*"))) == 2


def test_shard_tables_prune_stale_windows(monkeypatch):
    """The emit-shard digest tables evict expired windows (rate-limited
    sweep riding the ledger adds) — an audited 24/7 run must not retain
    every dead window's cell-hash map forever."""
    aud = AuditState(tag="p0")
    stale_ws = dt.datetime.now(UTC) - dt.timedelta(hours=3)
    aud.shard_table(None).apply_doc(_doc("8a2a1072b59ffff", stale_ws, 1))
    assert aud.shard_table(None).windows("h3r8")
    aud._prune_last = -1e9  # lapse the 60 s limiter
    aud.add("polled", 1)
    assert aud.shard_table(None).windows("h3r8") == []
