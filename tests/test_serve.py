"""Serving layer: endpoint contracts against a live threaded server."""

import datetime as dt
import json
import os
import time
import urllib.request

import pytest

from heatmap_tpu.config import load_config
from heatmap_tpu.serve import make_wsgi_app, start_background
from heatmap_tpu.serve.api import cell_ring
from heatmap_tpu.sink import MemoryStore
from heatmap_tpu.sink.base import PositionDoc, TileDoc, UTC
from heatmap_tpu import hexgrid


@pytest.fixture()
def store():
    s = MemoryStore()
    now = dt.datetime.now(UTC).replace(microsecond=0)
    ws = now - dt.timedelta(minutes=2)
    cell = hexgrid.latlng_to_cell(42.3601, -71.0589, 8)
    s.upsert_tiles([
        TileDoc("bos", 8, cell, ws, ws + dt.timedelta(minutes=5),
                count=7, avg_speed_kmh=33.0, avg_lat=42.36, avg_lon=-71.05,
                ttl_minutes=45, extra={"p95SpeedKmh": 55.0}),
    ])
    s.upsert_positions([
        PositionDoc("mbta", "veh-1", now, 42.36, -71.05),
    ])
    return s


@pytest.fixture()
def server(store):
    cfg = load_config({}, serve_port=0)
    httpd, t, port = start_background(store, cfg)
    yield f"http://127.0.0.1:{port}"
    httpd.shutdown()


def get_json(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read())


def test_tiles_latest_contract(server):
    fc = get_json(server + "/api/tiles/latest")
    assert fc["type"] == "FeatureCollection"
    assert len(fc["features"]) == 1
    f = fc["features"][0]
    assert f["type"] == "Feature"
    geom = f["geometry"]
    assert geom["type"] == "Polygon"
    ring = geom["coordinates"][0]
    assert ring[0] == ring[-1]  # closed, like the reference (app.py:39-40)
    assert len(ring) == 7       # hexagon + closing vertex
    props = f["properties"]
    assert set(props) >= {"cellId", "count", "avgSpeedKmh",
                          "windowStart", "windowEnd"}
    assert props["count"] == 7
    assert props["p95SpeedKmh"] == 55.0
    # ring coordinates are [lng, lat] pairs around the actual cell
    lats = [c[1] for c in ring]
    lngs = [c[0] for c in ring]
    assert 42.2 < sum(lats) / len(lats) < 42.5
    assert -71.2 < sum(lngs) / len(lngs) < -70.9


def test_positions_latest_contract(server):
    fc = get_json(server + "/api/positions/latest")
    assert fc["type"] == "FeatureCollection"
    f = fc["features"][0]
    assert f["geometry"]["type"] == "Point"
    lon, lat = f["geometry"]["coordinates"]
    assert lat == pytest.approx(42.36, abs=1e-6)
    props = f["properties"]
    assert props["provider"] == "mbta"
    assert props["vehicleId"] == "veh-1"
    assert "T" in props["ts"]  # ISO format


def test_empty_store_empty_collections():
    cfg = load_config({}, serve_port=0)
    httpd, t, port = start_background(MemoryStore(), cfg)
    try:
        fc = get_json(f"http://127.0.0.1:{port}/api/tiles/latest")
        assert fc == {"type": "FeatureCollection", "features": []}
        fc = get_json(f"http://127.0.0.1:{port}/api/positions/latest")
        assert fc["features"] == []
    finally:
        httpd.shutdown()


def test_index_and_health_and_metrics(server):
    with urllib.request.urlopen(server + "/", timeout=10) as r:
        html = r.read().decode()
        assert r.headers["Content-Type"].startswith("text/html")
    assert "leaflet" in html.lower()
    assert "/api/tiles/latest" in html
    assert "/api/positions/latest" in html
    hz = get_json(server + "/healthz")
    assert hz["ok"] is True and hz["status"] == "ok"
    assert get_json(server + "/metrics.json") == {}  # no runtime attached
    with urllib.request.urlopen(server + "/metrics", timeout=10) as r:
        assert r.headers["Content-Type"].startswith("text/plain")
    assert get_json(server + "/trace/recent") == {"traces": []}
    with pytest.raises(urllib.error.HTTPError):
        urllib.request.urlopen(server + "/nope", timeout=10)


def test_cell_ring_consistency():
    cell = hexgrid.latlng_to_cell(42.3601, -71.0589, 8)
    ring = cell_ring(cell)
    # center must be inside the ring's bounding box
    lat, lng = hexgrid.cell_to_latlng(cell)
    lats = [c[1] for c in ring]
    lngs = [c[0] for c in ring]
    assert min(lats) < lat < max(lats)
    assert min(lngs) < lng < max(lngs)


def test_serving_reads_over_wire_store():
    """The full read path (find + getMore cursors, datetime round-trips,
    grid filter) over the framework's own Mongo wire client — the serving
    deployment the reference runs with pymongo (app.py:16,45-88)."""
    from heatmap_tpu.sink.mongo import MongoStore, _WireBackend
    from heatmap_tpu.testing.mock_mongod import MockMongod

    now = dt.datetime.now(UTC).replace(microsecond=0)
    ws = now - dt.timedelta(minutes=2)
    import functools

    with MockMongod() as uri:
        s = MongoStore(uri, "mobility", ensure_indexes=True,
                       backend=_WireBackend(uri, "mobility"))
        # force multi-page cursors so the getMore leg genuinely runs
        # (the client default batchSize of 1000 would fit everything in
        # firstBatch and silently skip it)
        s._b.client.find = functools.partial(s._b.client.find, batch_size=40)
        cells = [hexgrid.latlng_to_cell(42.3 + i * 1e-2, -71.05, 8)
                 for i in range(150)]  # 4 cursor pages at batch_size=40
        s.upsert_tiles([
            TileDoc("bos", 8, c, ws, ws + dt.timedelta(minutes=5),
                    count=i + 1, avg_speed_kmh=30.0, avg_lat=42.3,
                    avg_lon=-71.05, ttl_minutes=45)
            for i, c in enumerate(cells)
        ])
        s.upsert_positions([
            PositionDoc("mbta", f"veh-{i}", now, 42.36, -71.05)
            for i in range(5)
        ])

        cfg = load_config({}, serve_port=0)
        httpd, t, port = start_background(s, cfg)
        try:
            base = f"http://127.0.0.1:{port}"
            fc = get_json(base + "/api/tiles/latest")
            assert len(fc["features"]) == len(set(cells))
            counts = {f["properties"]["cellId"]: f["properties"]["count"]
                      for f in fc["features"]}
            assert counts[cells[0]] >= 1
            pc = get_json(base + "/api/positions/latest")
            assert len(pc["features"]) == 5
            assert {f["properties"]["vehicleId"] for f in pc["features"]} == \
                {f"veh-{i}" for i in range(5)}
        finally:
            httpd.shutdown()
            s.close()


def test_index_embeds_multi_res_grids(tmp_path):
    """With a multi-res pyramid configured, the UI gets the [res, grid]
    pairs for zoom-adaptive selection; single-res stays fixed."""
    from heatmap_tpu.serve.ui import render_index

    multi = render_index(5000, (9, 7, 8))
    assert 'const GRIDS = [[7, "h3r7"], [8, "h3r8"], [9, "h3r9"]];' in multi
    single = render_index(5000, (8,))
    assert 'const GRIDS = [[8, "h3r8"]];' in single
    none = render_index(5000)
    assert "const GRIDS = [];" in none


def test_gzip_negotiation():
    """Large JSON bodies gzip when the client accepts it; small bodies
    and non-accepting clients get identity, and content round-trips."""
    import gzip
    import json as _json

    from heatmap_tpu.serve.api import make_wsgi_app

    store = MemoryStore()
    now = dt.datetime.now(UTC).replace(microsecond=0)
    ws = now - dt.timedelta(minutes=2)
    cells = {hexgrid.latlng_to_cell(42.2 + i * 7e-3, -71.05, 8)
             for i in range(200)}
    store.upsert_tiles([
        TileDoc("bos", 8, c, ws, ws + dt.timedelta(minutes=5),
                count=i + 1, avg_speed_kmh=30.0, avg_lat=42.3,
                avg_lon=-71.05, ttl_minutes=45)
        for i, c in enumerate(sorted(cells))
    ])
    n_docs = len(cells)
    app = make_wsgi_app(store)

    def req(path, accept_gzip):
        captured = {}

        def sr(status, headers):
            captured["status"] = status
            captured["headers"] = dict(headers)

        env = {"PATH_INFO": path, "QUERY_STRING": ""}
        if accept_gzip:
            env["HTTP_ACCEPT_ENCODING"] = "gzip, deflate"
        body = b"".join(app(env, sr))
        return captured, body

    cap, body = req("/api/tiles/latest", accept_gzip=True)
    assert cap["headers"].get("Content-Encoding") == "gzip"
    fc = _json.loads(gzip.decompress(body))
    assert len(fc["features"]) == n_docs

    cap2, body2 = req("/api/tiles/latest", accept_gzip=False)
    assert "Content-Encoding" not in cap2["headers"]
    assert len(_json.loads(body2)["features"]) == n_docs

    cap3, body3 = req("/healthz", accept_gzip=True)  # tiny: identity
    assert "Content-Encoding" not in cap3["headers"]
    assert _json.loads(body3)["ok"] is True


def test_gzip_qvalue_refusal():
    from heatmap_tpu.serve.api import _accepts_gzip

    assert _accepts_gzip("gzip")
    assert _accepts_gzip("gzip, deflate")
    assert _accepts_gzip("deflate, gzip;q=0.5")
    assert not _accepts_gzip("gzip;q=0, identity")
    assert not _accepts_gzip("gzip;q=0.0")
    assert not _accepts_gzip("identity")
    assert not _accepts_gzip("")

def test_bare_tiles_default_grid_without_default_window():
    """With WINDOW_MINUTES not containing TILE_MINUTES (e.g. 1,15 vs 5)
    the untagged h3r{res} grid is NEVER written — the runtime tags every
    window h3r{res}m{w}.  The bare /api/tiles/latest must then default to
    the first configured window's tagged grid instead of returning a
    permanently empty FeatureCollection (regression)."""
    cfg = load_config({"WINDOW_MINUTES": "1,15", "TILE_MINUTES": "5"},
                      serve_port=0)
    assert 5 not in cfg.windows_minutes
    s = MemoryStore()
    now = dt.datetime.now(UTC).replace(microsecond=0)
    ws = now - dt.timedelta(minutes=2)
    cell = hexgrid.latlng_to_cell(42.3601, -71.0589, 8)
    for wmin in (1, 15):
        s.upsert_tiles([
            TileDoc("bos", 8, cell, ws, ws + dt.timedelta(minutes=wmin),
                    count=wmin, avg_speed_kmh=30.0, avg_lat=42.36,
                    avg_lon=-71.05, ttl_minutes=45, grid=f"h3r8m{wmin}"),
        ])
    httpd, t, port = start_background(s, cfg)
    try:
        fc = get_json(f"http://127.0.0.1:{port}/api/tiles/latest")
        assert len(fc["features"]) == 1
        assert fc["features"][0]["properties"]["count"] == 1  # the m1 grid
        # explicit grid param still selects the other window
        fc15 = get_json(
            f"http://127.0.0.1:{port}/api/tiles/latest?grid=h3r8m15")
        assert fc15["features"][0]["properties"]["count"] == 15
    finally:
        httpd.shutdown()


def test_render_cache_invalidates_on_upsert(store, server):
    """The serve render cache must re-render the MOMENT this process
    upserts (store write-version keying, r5) — a pure-TTL cache would
    serve a sub-second-stale FeatureCollection right after a write."""
    first = get_json(server + "/api/tiles/latest")
    assert len(first["features"]) == 1
    # warm the cache again, then write a second tile into the SAME window
    get_json(server + "/api/tiles/latest")
    now = dt.datetime.now(UTC).replace(microsecond=0)
    ws = now - dt.timedelta(minutes=2)
    cell2 = hexgrid.latlng_to_cell(42.40, -71.10, 8)
    store.upsert_tiles([
        TileDoc("bos", 8, cell2, ws, ws + dt.timedelta(minutes=5),
                count=3, avg_speed_kmh=10.0, avg_lat=42.40,
                avg_lon=-71.10, ttl_minutes=45),
    ])
    fresh = get_json(server + "/api/tiles/latest")
    assert len(fresh["features"]) == 2, (
        "upsert invisible through the render cache")


def test_render_cache_disabled_by_env(monkeypatch, store):
    from heatmap_tpu.config import load_config
    from heatmap_tpu.serve.api import start_background

    monkeypatch.setenv("HEATMAP_SERVE_CACHE_MS", "0")
    cfg = load_config({}, serve_port=0)
    httpd, _t, port = start_background(store, cfg)
    try:
        body = get_json(f"http://127.0.0.1:{port}/api/tiles/latest")
        assert body["type"] == "FeatureCollection"
    finally:
        httpd.shutdown()


def test_fast_tiles_json_byte_identical(store):
    """The string-assembled hot-path renderer must produce EXACTLY what
    json.dumps of the dict spec produces — any drift (separators, float
    repr, key order, extras) silently changes the wire contract."""
    from heatmap_tpu.serve.api import (tiles_feature_collection,
                                       tiles_feature_collection_json)

    # widen the store: several cells, extras present and absent,
    # non-round floats
    now = dt.datetime.now(UTC).replace(microsecond=0)
    ws = now - dt.timedelta(minutes=2)
    docs = []
    for i, (la, lo) in enumerate(
            [(42.31, -71.01), (42.52, -71.22), (42.405, -70.95)]):
        cell = hexgrid.latlng_to_cell(la, lo, 8)
        extra = ({"p95SpeedKmh": 41.7 + i, "stddevSpeedKmh": 3.3}
                 if i % 2 else None)
        docs.append(TileDoc("bos", 8, cell, ws,
                            ws + dt.timedelta(minutes=5), count=i + 1,
                            avg_speed_kmh=17.123456 + i, avg_lat=la,
                            avg_lon=lo, ttl_minutes=45, extra=extra))
    store.upsert_tiles(docs)
    want = json.dumps(tiles_feature_collection(store))
    got = tiles_feature_collection_json(store)
    assert got == want
    # and the empty case
    empty = MemoryStore()
    assert (tiles_feature_collection_json(empty)
            == json.dumps(tiles_feature_collection(empty)))


def test_metrics_reports_resolved_policies(tmp_path):
    """/metrics surfaces the engine policies this run resolved (hwbank
    winners or static fallbacks) so operators can see which snap/pull/
    merge choices actually engaged."""
    import tempfile
    import time as _t

    from heatmap_tpu.sink import MemoryStore as _MS
    from heatmap_tpu.stream import MicroBatchRuntime
    from heatmap_tpu.stream.source import MemorySource

    t0 = int(_t.time()) - 60
    evs = [{"provider": "p", "vehicleId": f"v{i}", "lat": 42.0,
            "lon": -71.0, "speedKmh": 1.0, "ts": t0} for i in range(32)]
    cfg = load_config({}, batch_size=16, state_capacity_log2=8,
                      speed_hist_bins=4, store="memory", serve_port=0,
                      checkpoint_dir=tempfile.mkdtemp())
    src = MemorySource(evs)
    src.finish()
    st = _MS()
    rt = MicroBatchRuntime(cfg, src, st, checkpoint_every=0)
    try:
        httpd, _t2, port = start_background(st, cfg, runtime=rt)
        try:
            m = get_json(f"http://127.0.0.1:{port}/metrics.json")
            assert m["policy_snap_impl"] in ("native", "xla", "pallas")
            assert m["policy_emit_pull"] in ("full", "prefix")
            assert m["policy_merge_banked"] in (None, "sort", "rank",
                                                "probe")
            # the same policies ride the Prometheus exposition as an
            # info-style gauge
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
                txt = r.read().decode()
            assert "heatmap_policy_info{" in txt
            assert f'snap_impl="{m["policy_snap_impl"]}"' in txt
        finally:
            httpd.shutdown()
    finally:
        rt.close()


def test_render_cache_eviction_keeps_hot_entries(monkeypatch, store):
    """64 bogus ?grid= values must not wipe the hot default-grid render
    (single-entry eviction, not clear()) — and junk grids simply return
    empty collections, cached or not."""
    cfg = load_config({}, serve_port=0)
    httpd, _t, port = start_background(store, cfg)
    try:
        base = f"http://127.0.0.1:{port}"
        hot = get_json(base + "/api/tiles/latest")
        assert len(hot["features"]) == 1
        for i in range(70):
            fc = get_json(base + f"/api/tiles/latest?grid=junk{i}")
            assert fc["features"] == []
        hot2 = get_json(base + "/api/tiles/latest")
        assert hot2 == hot
    finally:
        httpd.shutdown()


def test_render_cache_bad_env_disables_not_crashes(monkeypatch, store):
    monkeypatch.setenv("HEATMAP_SERVE_CACHE_MS", "half-a-second")
    cfg = load_config({}, serve_port=0)
    httpd, _t, port = start_background(store, cfg)
    try:
        fc = get_json(f"http://127.0.0.1:{port}/api/tiles/latest")
        assert len(fc["features"]) == 1
    finally:
        httpd.shutdown()


def test_fast_tiles_json_grid_filter_byte_identical(store):
    """Byte identity must hold under the ?grid= filter too (the pyramid
    UI's zoom-adaptive requests)."""
    from heatmap_tpu.serve.api import (tiles_feature_collection,
                                       tiles_feature_collection_json)

    now = dt.datetime.now(UTC).replace(microsecond=0)
    ws = now - dt.timedelta(minutes=2)
    c7 = hexgrid.latlng_to_cell(42.37, -71.06, 7)
    store.upsert_tiles([
        TileDoc("bos", 7, c7, ws, ws + dt.timedelta(minutes=5),
                count=2, avg_speed_kmh=20.0, avg_lat=42.37,
                avg_lon=-71.06, ttl_minutes=45),
    ])
    for grid in ("h3r7", "h3r8", "h3r9"):
        assert (tiles_feature_collection_json(store, grid)
                == json.dumps(tiles_feature_collection(store, grid))), grid


# ---------------------------------------------------------------- obs
def _mini_runtime(tmpdir, events=32, batch=16, **cfg_over):
    """A tiny real runtime, run to exhaustion (closed), with its metrics
    intact for the serving layer."""
    import tempfile
    import time as _t

    from heatmap_tpu.sink import MemoryStore as _MS
    from heatmap_tpu.stream import MicroBatchRuntime
    from heatmap_tpu.stream.source import MemorySource

    t0 = int(_t.time()) - 5  # recent: keeps the freshness SLO green
    evs = [{"provider": "p", "vehicleId": f"v{i}", "lat": 42.0 + i * 1e-4,
            "lon": -71.0, "speedKmh": 1.0, "ts": t0} for i in range(events)]
    cfg = load_config({}, batch_size=batch, state_capacity_log2=8,
                      speed_hist_bins=4, store="memory", serve_port=0,
                      checkpoint_dir=tempfile.mkdtemp(dir=tmpdir),
                      **cfg_over)
    src = MemorySource(evs)
    src.finish()
    st = _MS()
    rt = MicroBatchRuntime(cfg, src, st, checkpoint_every=0)
    rt.run()
    return cfg, st, rt


def _parse_prom(text):
    """Minimal Prometheus text-format parser: {series_name: {labels_str:
    value}} plus {name: type}.  Raises on malformed lines, duplicate
    TYPE declarations, and duplicate samples — the things the real
    Prometheus parser rejects — so using it IS the format check."""
    series, types = {}, {}
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            _, _, name, mtype = line.split(" ", 3)
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = mtype
            continue
        if line.startswith("#"):
            continue
        name_labels, value = line.rsplit(" ", 1)
        float(value)  # must parse
        if "{" in name_labels:
            name, _, rest = name_labels.partition("{")
            labels = rest.rstrip("}")
        else:
            name, labels = name_labels, ""
        assert labels not in series.get(name, {}), (
            f"duplicate sample {name}{{{labels}}}")
        series.setdefault(name, {})[labels] = float(value)
    return series, types


def test_metrics_prometheus_exposition(tmp_path):
    """/metrics is valid text exposition with counter, gauge, and
    histogram (_bucket/_sum/_count) series whose invariants hold."""
    cfg, st, rt = _mini_runtime(str(tmp_path))
    httpd, _t, port = start_background(st, cfg, runtime=rt, port=0)
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                    timeout=10) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            txt = r.read().decode()
        series, types = _parse_prom(txt)
        assert types["heatmap_batch_latency_seconds"] == "histogram"
        assert types["heatmap_events_valid_total"] == "counter"
        assert types["heatmap_state_capacity_rows"] == "gauge"
        # histogram invariants: buckets cumulative and monotone, +Inf
        # bucket == _count, _sum present
        buckets = series["heatmap_batch_latency_seconds_bucket"]
        bounds = sorted(buckets.items(),
                        key=lambda kv: float(kv[0].split('"')[1])
                        if "+Inf" not in kv[0] else float("inf"))
        vals = [v for _, v in bounds]
        assert vals == sorted(vals)
        count = series["heatmap_batch_latency_seconds_count"][""]
        assert buckets['le="+Inf"'] == count > 0
        assert "heatmap_batch_latency_seconds_sum" in series
        # per-span histogram labels
        assert any('span="poll"' in k for k in
                   series["heatmap_batch_span_seconds_bucket"])
        # counters conserve: 32 events through a 16-batch
        assert series["heatmap_events_valid_total"][""] == 32
        # /metrics.json still carries every historical key
        mj = get_json(f"http://127.0.0.1:{port}/metrics.json")
        for k in ("events_valid", "uptime_s", "events_per_sec",
                  "batch_latency_p50_ms", "batch_latency_p95_ms",
                  "tiles_written", "positions_written", "sink_retries"):
            assert k in mj, k
    finally:
        httpd.shutdown()


def test_trace_recent_records(tmp_path):
    cfg, st, rt = _mini_runtime(str(tmp_path), events=48, batch=16)
    httpd, _t, port = start_background(st, cfg, runtime=rt, port=0)
    try:
        tr = get_json(f"http://127.0.0.1:{port}/trace/recent?n=2")
        assert len(tr["traces"]) == 2
        newest = tr["traces"][0]
        assert newest["epoch"] > tr["traces"][1]["epoch"]
        assert set(newest) >= {"epoch", "t_wall", "latency_ms", "spans_ms",
                               "n_events", "n_late", "overflow_groups"}
        assert set(newest["spans_ms"]) >= {"poll", "pad", "device",
                                           "sink_submit"}
    finally:
        httpd.shutdown()


def test_healthz_slo_transitions(tmp_path, monkeypatch):
    """ok with sane budgets; degraded once the (real, observed) batch
    p50 exceeds an absurdly tight budget; down (503) when the sink is
    poisoned."""
    cfg, st, rt = _mini_runtime(str(tmp_path))
    httpd, _t, port = start_background(st, cfg, runtime=rt, port=0)
    try:
        base = f"http://127.0.0.1:{port}"
        # generous budget for the ok phase: with only two batches, the
        # p50 sample IS the first-step XLA compile batch
        monkeypatch.setenv("HEATMAP_SLO_BATCH_P50_MS", "60000")
        hz = get_json(base + "/healthz")
        assert hz["status"] == "ok" and hz["ok"]
        assert hz["checks"]["batch_p50_ms"]["ok"]

        monkeypatch.setenv("HEATMAP_SLO_BATCH_P50_MS", "0.000001")
        hz = get_json(base + "/healthz")
        assert hz["status"] == "degraded" and hz["ok"]  # still serving
        assert not hz["checks"]["batch_p50_ms"]["ok"]
        monkeypatch.setenv("HEATMAP_SLO_BATCH_P50_MS", "60000")

        rt.writer._exc = IOError("injected")  # poisoned sink -> down
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/healthz", timeout=10)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["status"] == "down"
        rt.writer._exc = None
    finally:
        httpd.shutdown()


def test_trace_recent_fields_projection(tmp_path):
    """?fields= returns slim traces; an invalid name answers 400 with
    an error body instead of guessing."""
    cfg, st, rt = _mini_runtime(str(tmp_path), events=48, batch=16)
    httpd, _t, port = start_background(st, cfg, runtime=rt, port=0)
    try:
        base = f"http://127.0.0.1:{port}"
        tr = get_json(base + "/trace/recent?n=2&fields=epoch,n_events")
        assert len(tr["traces"]) == 2
        assert all(set(r) == {"epoch", "n_events"} for r in tr["traces"])
        # unknown-but-valid names simply drop out of the projection
        tr = get_json(base + "/trace/recent?n=1&fields=epoch,nope")
        assert set(tr["traces"][0]) == {"epoch"}
        # percent-encoded commas (any urlencode-ing client) decode fine
        tr = get_json(base + "/trace/recent?n=1&fields=epoch%2Cn_events")
        assert set(tr["traces"][0]) == {"epoch", "n_events"}
        for bad in ("fields=", "fields=bad-name",
                    "fields=" + ",".join(f"f{i}" for i in range(17))):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"{base}/trace/recent?{bad}", timeout=10)
            assert ei.value.code == 400
            assert "error" in json.loads(ei.value.read())
    finally:
        httpd.shutdown()


def test_debug_freshness_endpoint(tmp_path):
    """/debug/freshness returns the per-stage decomposition for the
    last N lineage records plus the event-age summary."""
    cfg, st, rt = _mini_runtime(str(tmp_path), events=64, batch=16,
                                emit_flush_k=2)
    httpd, _t, port = start_background(st, cfg, runtime=rt, port=0)
    try:
        base = f"http://127.0.0.1:{port}"
        d = get_json(base + "/debug/freshness")
        assert d["stage_order"] == ["poll_wait", "prefetch_queue",
                                    "fold", "ring", "sink_commit",
                                    "view_apply"]
        assert len(d["records"]) == 4  # 64 events / 16-batch
        newest = d["records"][0]
        # writer-fed view present -> the cross-process view_apply stage
        # is stamped in-process too (≈0; the stage exists for the fleet
        # stitch — obs.fleet)
        assert set(newest["stages"]) == set(d["stage_order"])
        assert newest["epoch"] > d["records"][1]["epoch"]
        # the decomposition conserves: stages telescope to the view-
        # visible age (the mean age through sink commit, plus the
        # in-process view apply)
        assert sum(newest["stages"].values()) == pytest.approx(
            newest["age_s"]["visible"], abs=5e-3)
        assert d["summary"]["event_age_p50_s"] > 0
        assert "ring_residency_mean_s" in d["summary"]
        assert len(get_json(base + "/debug/freshness?n=1")["records"]) == 1
        # the tiles render samples the ingest->serve freshness gauge
        with urllib.request.urlopen(base + "/api/tiles/latest",
                                    timeout=10):
            pass
        v = rt._g_serve_fresh.value
        assert v == v and 0 < v < 120  # not NaN; sane recent freshness
    finally:
        httpd.shutdown()


def test_debug_freshness_without_runtime():
    httpd, _t, port = start_background(MemoryStore(),
                                       load_config({}, serve_port=0),
                                       port=0)
    try:
        d = get_json(f"http://127.0.0.1:{port}/debug/freshness")
        assert d["records"] == [] and d["summary"] == {}
    finally:
        httpd.shutdown()


def test_healthz_event_age_freshness_slo(tmp_path, monkeypatch):
    """The acceptance transition: a ring-held runtime (K>1) breaches a
    tight HEATMAP_SLO_FRESHNESS_P50_MS — /healthz degrades on the
    END-TO-END event age while every batch-span SLO stays green."""
    cfg, st, rt = _mini_runtime(str(tmp_path), events=64, batch=16,
                                emit_flush_k=4, trigger_ms=10)
    httpd, _t, port = start_background(st, cfg, runtime=rt, port=0)
    try:
        base = f"http://127.0.0.1:{port}"
        monkeypatch.setenv("HEATMAP_SLO_BATCH_P50_MS", "60000")
        monkeypatch.setenv("HEATMAP_SLO_FRESHNESS_P50_S", "600")
        monkeypatch.setenv("HEATMAP_SLO_FRESHNESS_P50_MS", "600000")
        hz = get_json(base + "/healthz")
        assert hz["status"] == "ok"
        assert hz["checks"]["event_age_p50_ms"]["ok"]
        # the ring hold (4 batches deep, 10 ms trigger) pushes event age
        # past a budget the batch spans stay comfortably inside
        monkeypatch.setenv("HEATMAP_SLO_FRESHNESS_P50_MS", "0.001")
        hz = get_json(base + "/healthz")
        assert hz["status"] == "degraded" and hz["ok"]  # still serving
        assert not hz["checks"]["event_age_p50_ms"]["ok"]
        assert hz["checks"]["batch_p50_ms"]["ok"]       # spans green
        assert hz["checks"]["freshness_p50_s"]["ok"]
    finally:
        httpd.shutdown()


def test_healthz_degrades_on_supervisor_restart_rate(tmp_path,
                                                     monkeypatch):
    """A supervisor channel recording recent failures past the restart
    SLO flips /healthz to degraded, and the supervisor_* series appear
    in /metrics — without any runtime attached (the channel is
    cross-process state)."""
    from heatmap_tpu.obs import ENV_CHANNEL, SupervisorChannel

    chan = SupervisorChannel(str(tmp_path / "chan"))
    for _ in range(3):
        chan.note_failure("exit code 1")
    chan.update(restarts_total=3, child_running=1)
    monkeypatch.setenv(ENV_CHANNEL, chan.path)
    monkeypatch.setenv("HEATMAP_SLO_RESTARTS_PER_H", "2")
    httpd, _t, port = start_background(MemoryStore(),
                                       load_config({}, serve_port=0),
                                       port=0)
    try:
        base = f"http://127.0.0.1:{port}"
        hz = get_json(base + "/healthz")
        assert hz["status"] == "degraded"
        assert hz["checks"]["supervisor_restarts_1h"]["value"] == 3
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            txt = r.read().decode()
        assert "heatmap_supervisor_restarts_total 3" in txt
        assert "heatmap_supervisor_failures_total 3" in txt
        # under the rate budget it is ok again
        monkeypatch.setenv("HEATMAP_SLO_RESTARTS_PER_H", "10")
        assert get_json(base + "/healthz")["status"] == "ok"
    finally:
        httpd.shutdown()


# ------------------------------------------------------- query tier (PR 4)
def test_tiles_etag_304_with_cache_disabled(monkeypatch, store):
    """The ETag path is independent of the render cache: with
    HEATMAP_SERVE_CACHE_MS=0 an If-None-Match hit still answers 304
    (previously every poll forced a full rebuild)."""
    monkeypatch.setenv("HEATMAP_SERVE_CACHE_MS", "0")
    cfg = load_config({}, serve_port=0)
    httpd, _t, port = start_background(store, cfg, port=0)
    try:
        base = f"http://127.0.0.1:{port}"
        with urllib.request.urlopen(base + "/api/tiles/latest",
                                    timeout=10) as r:
            etag = r.headers["ETag"]
        assert etag.startswith('"') and etag.endswith('"')
        for url in ("/api/tiles/latest", "/api/positions/latest"):
            with urllib.request.urlopen(base + url, timeout=10) as r:
                tag = r.headers["ETag"]
            req = urllib.request.Request(base + url)
            req.add_header("If-None-Match", tag)
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 304
            assert ei.value.headers["ETag"] == tag
            assert ei.value.read() == b""
    finally:
        httpd.shutdown()


def test_etag_304_skips_renderer(tmp_path):
    """ACCEPTANCE: an unchanged view answers 304 without invoking the
    renderer — the serve_renders counter stays flat while the 304
    counter climbs."""
    cfg, st, rt = _mini_runtime(str(tmp_path))
    httpd, _t, port = start_background(st, cfg, runtime=rt, port=0)
    try:
        base = f"http://127.0.0.1:{port}"
        with urllib.request.urlopen(base + "/api/tiles/latest",
                                    timeout=10) as r:
            etag = r.headers["ETag"]

        def counters():
            with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
                txt = r.read().decode()
            series, _ = _parse_prom(txt)
            return (series.get("heatmap_serve_renders_total", {}).get(
                        'endpoint="tiles"', 0),
                    series.get("heatmap_serve_304_total", {}).get(
                        'endpoint="tiles"', 0))

        renders0, n304_0 = counters()
        assert renders0 >= 1
        for _ in range(5):
            req = urllib.request.Request(base + "/api/tiles/latest")
            req.add_header("If-None-Match", etag)
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 304
        renders1, n304_1 = counters()
        assert renders1 == renders0, "304s must not invoke the renderer"
        assert n304_1 == n304_0 + 5
    finally:
        httpd.shutdown()


def test_delta_endpoint_with_runtime(tmp_path):
    cfg, st, rt = _mini_runtime(str(tmp_path), events=48, batch=16)
    httpd, _t, port = start_background(st, cfg, runtime=rt, port=0)
    try:
        base = f"http://127.0.0.1:{port}"
        d = get_json(base + "/api/tiles/delta?since=0")
        assert d["mode"] == "full" and d["features"]
        assert d["grid"] == cfg.default_grid()
        d2 = get_json(base + f"/api/tiles/delta?since={d['seq']}")
        assert d2["mode"] == "delta" and d2["features"] == []
        assert d2["seq"] == d["seq"]
        # delta features are byte-identical to the full render's
        full = get_json(base + "/api/tiles/latest")
        assert sorted(json.dumps(f, sort_keys=True)
                      for f in d["features"]) == \
            sorted(json.dumps(f, sort_keys=True)
                   for f in full["features"])
    finally:
        httpd.shutdown()


def test_topk_and_bbox(store):
    now = dt.datetime.now(UTC).replace(microsecond=0)
    ws = now - dt.timedelta(minutes=2)
    lats = (42.30, 42.40, 42.50)
    for i, la in enumerate(lats):
        cell = hexgrid.latlng_to_cell(la, -71.05, 8)
        store.upsert_tiles([
            TileDoc("bos", 8, cell, ws, ws + dt.timedelta(minutes=5),
                    count=100 * (i + 1), avg_speed_kmh=20.0, avg_lat=la,
                    avg_lon=-71.05, ttl_minutes=45),
        ])
    cfg = load_config({}, serve_port=0)
    httpd, _t, port = start_background(store, cfg, port=0)
    try:
        base = f"http://127.0.0.1:{port}"
        fc = get_json(base + "/api/tiles/topk?k=2")
        counts = [f["properties"]["count"] for f in fc["features"]]
        assert counts == [300, 200]  # count desc, k-bounded
        # bbox keeps only the northern tile (centroid filter)
        fc = get_json(base + "/api/tiles/topk?k=10&"
                             "bbox=-71.2,42.45,-70.9,42.6")
        assert [f["properties"]["count"] for f in fc["features"]] == [300]
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/api/tiles/topk?bbox=1,2,3",
                                   timeout=10)
        assert ei.value.code == 400
    finally:
        httpd.shutdown()


def test_res_rollup_over_http(store):
    """?res= zoom-out: counts sum into parent cells; avg speed is the
    count-weighted mean; p95/stddev (non-combinable) are absent."""
    base_fc_cfg = load_config({}, serve_port=0)
    httpd, _t, port = start_background(store, base_fc_cfg, port=0)
    try:
        base = f"http://127.0.0.1:{port}"
        full = get_json(base + "/api/tiles/latest")
        want_total = sum(f["properties"]["count"]
                         for f in full["features"])
        fc6 = get_json(base + "/api/tiles/latest?res=6")
        assert fc6["features"]
        assert sum(f["properties"]["count"]
                   for f in fc6["features"]) == want_total
        for f in fc6["features"]:
            assert "p95SpeedKmh" not in f["properties"]
            ring = f["geometry"]["coordinates"][0]
            assert ring[0] == ring[-1]
        # an unmaintained resolution answers 400, not garbage
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/api/tiles/latest?res=1",
                                   timeout=10)
        assert ei.value.code == 400
    finally:
        httpd.shutdown()


def test_sse_stream_pushes_on_store_write(store):
    """SSE: the first event carries the full set; a store write (version
    bump, picked up by the serve-only refresher poll) pushes a delta."""
    import socket

    # short heartbeat: the disconnect is only observable at the next
    # write, so the gauge-drop assertion below needs pings to fail fast
    cfg = load_config({"HEATMAP_VIEW_POLL_MS": "50",
                       "HEATMAP_SSE_HEARTBEAT_S": "0.3"}, serve_port=0)
    httpd, _t, port = start_background(store, cfg, port=0)
    try:
        sk = socket.create_connection(("127.0.0.1", port), timeout=10)
        sk.sendall(b"GET /api/tiles/stream?since=0 HTTP/1.0\r\n\r\n")
        sk.settimeout(10)
        buf = b""
        while buf.count(b"event: tiles") < 1:
            buf += sk.recv(65536)
        first = buf
        assert b"text/event-stream" in first
        assert b'"mode": "full"' in first
        # out-of-band write -> a second, delta-mode push
        now = dt.datetime.now(UTC).replace(microsecond=0)
        ws = now - dt.timedelta(minutes=2)
        cell2 = hexgrid.latlng_to_cell(42.44, -71.11, 8)
        store.upsert_tiles([
            TileDoc("bos", 8, cell2, ws, ws + dt.timedelta(minutes=5),
                    count=4, avg_speed_kmh=12.0, avg_lat=42.44,
                    avg_lon=-71.11, ttl_minutes=45),
        ])
        while buf.count(b"event: tiles") < 2:
            buf += sk.recv(65536)
        assert cell2.encode() in buf
        sk.close()
        # the SSE client gauge returns to zero once the socket closes
        deadline = time.time() + 10
        while time.time() < deadline:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
                txt = r.read().decode()
            series, _ = _parse_prom(txt)
            if series.get("heatmap_serve_sse_clients", {}).get("") == 0:
                break
            time.sleep(0.2)
        assert series["heatmap_serve_sse_clients"][""] == 0
    finally:
        httpd.shutdown()


def test_query_view_disabled_falls_back(monkeypatch, store):
    """HEATMAP_QUERY_VIEW=0: /latest serves the legacy store path (no
    ETag), delta/topk/stream answer 503 with an error body."""
    cfg = load_config({"HEATMAP_QUERY_VIEW": "0"}, serve_port=0)
    httpd, _t, port = start_background(store, cfg, port=0)
    try:
        base = f"http://127.0.0.1:{port}"
        with urllib.request.urlopen(base + "/api/tiles/latest",
                                    timeout=10) as r:
            assert "ETag" not in r.headers
            assert json.loads(r.read())["features"]
        for url in ("/api/tiles/delta?since=0", "/api/tiles/topk",
                    "/api/tiles/stream"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + url, timeout=10)
            assert ei.value.code == 503
            assert "error" in json.loads(ei.value.read())
    finally:
        httpd.shutdown()


def test_debug_view_endpoint(tmp_path):
    cfg, st, rt = _mini_runtime(str(tmp_path))
    httpd, _t, port = start_background(st, cfg, runtime=rt, port=0)
    try:
        v = get_json(f"http://127.0.0.1:{port}/debug/view")
        assert v["enabled"] and v["mode"] == "writer-fed"
        assert v["poisoned"] is False
        assert v["seq"] >= 1 and v["cells"] >= 1
        assert cfg.default_grid() in v["store_grids"]
    finally:
        httpd.shutdown()


def test_index_references_delta_with_fallback():
    from heatmap_tpu.serve.ui import render_index

    html = render_index(5000, (8,))
    assert "/api/tiles/delta" in html      # the query-tier poll
    assert "/api/tiles/latest" in html     # the full-fetch fallback
    assert "/metrics.json" in html         # HUD reads the JSON surface


def test_grid_param_header_injection_rejected(store):
    """?grid= is embedded in the ETag HEADER: CR/LF or quote-bearing
    values must 400, never reach the header block (response splitting)."""
    cfg = load_config({}, serve_port=0)
    httpd, _t, port = start_background(store, cfg, port=0)
    try:
        base = f"http://127.0.0.1:{port}"
        for bad in ("h3r8%0d%0aX-Injected:%20evil", "h3r8%22%20x",
                    "a" * 65):
            for path in ("/api/tiles/latest?grid=", "/api/tiles/delta?grid=",
                         "/api/tiles/topk?grid=", "/api/tiles/stream?grid="):
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(base + path + bad, timeout=10)
                assert ei.value.code == 400, path
                assert "X-Injected" not in ei.value.headers
        # sane labels still pass
        fc = get_json(base + "/api/tiles/latest?grid=h3r8m15")
        assert fc["features"] == []
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_debug_stacks_endpoint(tmp_path):
    """GET /debug/stacks returns the aggregated top-of-stack payload
    (lazily starting the sampler); non-GET answers 405."""
    cfg, st, rt = _mini_runtime(str(tmp_path))
    httpd, _t, port = start_background(st, cfg, runtime=rt, port=0)
    try:
        base = f"http://127.0.0.1:{port}"
        d = get_json(base + "/debug/stacks?n=5")
        assert d["enabled"] is True and d["running"] is True
        assert {"samples", "hz", "frames", "uptime_s"} <= set(d)
        # the sampler accumulates across requests; frames are bounded
        deadline = time.time() + 5.0
        while not d["frames"] and time.time() < deadline:
            time.sleep(0.05)
            d = get_json(base + "/debug/stacks?n=5")
        assert len(d["frames"]) <= 5
        if d["frames"]:
            assert {"thread", "frame", "count", "share"} <= set(d["frames"][0])
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/debug/stacks", data=b"",
                                   timeout=10)  # POST
        assert ei.value.code == 405
        assert ei.value.headers["Allow"] == "GET"
    finally:
        httpd.shutdown()


def test_debug_profile_method_gate_and_conflict(tmp_path):
    """POST /debug/profile arms a capture window; GET answers 405; a
    second POST while the window is pending answers 409; a stopped
    window re-arms."""
    cfg, st, rt = _mini_runtime(str(tmp_path))
    httpd, _t, port = start_background(st, cfg, runtime=rt, port=0)
    try:
        base = f"http://127.0.0.1:{port}"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/debug/profile", timeout=10)
        assert ei.value.code == 405
        assert ei.value.headers["Allow"] == "POST"

        prof_dir = str(tmp_path / "prof")
        url = (base + "/debug/profile?batches=4&skip=1&dir=" + prof_dir)
        with urllib.request.urlopen(url, data=b"", timeout=10) as r:
            d = json.loads(r.read())
        assert d["armed"] is True and d["dir"] == prof_dir
        assert d["batches"] == 4
        assert d["from_epoch"] == rt.epoch + 1
        assert rt.tracer.busy

        # concurrent-capture rejection
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url, data=b"", timeout=10)
        assert ei.value.code == 409
        assert "already" in json.loads(ei.value.read())["error"]

        rt.tracer.stop()  # cancel the pending window -> re-armable
        with urllib.request.urlopen(base + "/debug/profile", data=b"",
                                    timeout=10) as r:
            d = json.loads(r.read())
        assert d["armed"] is True and d["dir"]  # server-chosen tmp dir
    finally:
        httpd.shutdown()


def test_debug_profile_without_runtime_503():
    httpd, _t, port = start_background(MemoryStore(),
                                       load_config({}, serve_port=0),
                                       port=0)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/profile", data=b"",
                timeout=10)
        assert ei.value.code == 503
    finally:
        httpd.shutdown()


def test_healthz_degrades_on_post_warmup_retrace(tmp_path, monkeypatch):
    """The acceptance transition over HTTP: a forced post-warmup
    retrace flips /healthz to degraded on the retrace check while the
    batch-latency SLO stays green."""
    monkeypatch.setenv("HEATMAP_SLO_FRESHNESS_P50_MS", "1e9")
    # enough batches that the recent batch-p50 is a steady-state step,
    # not the first-compile outlier
    cfg, st, rt = _mini_runtime(str(tmp_path), events=96, batch=16)
    httpd, _t, port = start_background(st, cfg, runtime=rt, port=0)
    try:
        base = f"http://127.0.0.1:{port}"
        assert get_json(base + "/healthz")["status"] == "ok"
        # grow the slab and fold one more batch: new shapes retrace the
        # warmed fused step
        rt._multi.grow(2 * rt._multi.capacity_per_shard)
        from heatmap_tpu.stream.source import MemorySource
        import time as _t

        t0 = int(_t.time()) - 2
        src = MemorySource([
            {"provider": "p", "vehicleId": "v1", "lat": 42.0,
             "lon": -71.0, "speedKmh": 1.0, "ts": t0}])
        src.finish()
        rt.source = src
        while rt.step_once():
            pass
        hz = get_json(base + "/healthz")
        assert hz["status"] == "degraded"
        chk = hz["checks"]["retrace_after_warmup"]
        assert chk["value"] >= 1 and not chk["ok"]
        assert hz["checks"]["batch_p50_ms"]["ok"]
        # /metrics exposes the retrace family with the fn label
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            txt = r.read().decode()
        assert 'heatmap_retrace_after_warmup_total{fn="multi_step' in txt
    finally:
        httpd.shutdown()


def test_debug_profile_dir_constrained_and_no_tempdir_leak(tmp_path):
    """dir= outside the allowed base answers 400 (auth-free endpoint,
    clients must not pick arbitrary write paths), and a no-dir POST
    losing the capture race does not leak its fallback tempdir."""
    import glob
    import os
    import tempfile

    cfg, st, rt = _mini_runtime(str(tmp_path))
    httpd, _t, port = start_background(st, cfg, runtime=rt, port=0)
    try:
        base = f"http://127.0.0.1:{port}"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                base + "/debug/profile?dir=/root/forbidden-prof",
                data=b"", timeout=10)
        assert ei.value.code == 400
        assert "dir=" in json.loads(ei.value.read())["error"]
        assert not os.path.exists("/root/forbidden-prof")

        # occupy the window, then lose the race without a dir
        assert rt.tracer.arm(str(tmp_path / "w"), batches=4)
        pat = os.path.join(tempfile.gettempdir(), "heatmap-profile-*")
        before = set(glob.glob(pat))
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/debug/profile", data=b"",
                                   timeout=10)
        assert ei.value.code == 409
        assert set(glob.glob(pat)) == before  # no orphan dir
    finally:
        rt.tracer.stop()
        httpd.shutdown()


# ------------------------------------------------------ fleet surfaces
def test_fleet_endpoints_503_without_channel(monkeypatch):
    from heatmap_tpu.obs.xproc import ENV_CHANNEL

    monkeypatch.delenv(ENV_CHANNEL, raising=False)
    httpd, _t, port = start_background(MemoryStore(),
                                       load_config({}, serve_port=0),
                                       port=0)
    try:
        for path in ("/fleet/metrics", "/fleet/healthz",
                     "/fleet/freshness"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=10)
            assert ei.value.code == 503
            assert "channel" in json.loads(ei.value.read())["error"]
    finally:
        httpd.shutdown()


def test_fleet_endpoints_over_http(tmp_path, monkeypatch):
    """Any process holding the channel path serves the federation: the
    three /fleet surfaces against a synthetic two-member channel."""
    from heatmap_tpu.obs.xproc import ENV_CHANNEL, publish_member_snapshot

    chan = str(tmp_path / "chan")
    publish_member_snapshot(
        chan, "p0", role="runtime",
        metrics_text=("# TYPE heatmap_events_valid_total counter\n"
                      "heatmap_events_valid_total 100\n"),
        freshness={"event_age_p50_s": 0.4},
        healthz={"status": "ok", "checks": {}},
        lineage=[{"lid": "p0-1", "ev_mean_ts": 1000.0,
                  "stages": {"sink_commit": 2.0}, "t_last": 1002.0}])
    publish_member_snapshot(
        chan, "serve1", role="serve",
        healthz={"status": "degraded",
                 "checks": {"event_age_p50_ms": {"ok": False}}},
        lineage=[{"lid": "p0-1", "ev_mean_ts": 1000.0,
                  "stages": {"view_apply": 0.5}, "t_last": 1002.5}])
    monkeypatch.setenv(ENV_CHANNEL, chan)
    httpd, _t, port = start_background(MemoryStore(),
                                       load_config({}, serve_port=0),
                                       port=0)
    try:
        base = f"http://127.0.0.1:{port}"
        with urllib.request.urlopen(base + "/fleet/metrics",
                                    timeout=10) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            txt = r.read().decode()
        assert 'heatmap_events_valid_total{proc="p0"} 100' in txt
        assert "heatmap_fleet_members 2" in txt
        hz = get_json(base + "/fleet/healthz")
        assert hz["status"] == "degraded"
        assert hz["checks"]["member_serve1"]["failing"] == [
            "event_age_p50_ms"]
        fr = get_json(base + "/fleet/freshness?n=8")
        assert len(fr["records"]) == 1
        rec = fr["records"][0]
        assert rec["residual_s"] == pytest.approx(0.0)
        assert sorted(rec["procs"]) == ["p0", "serve1"]
    finally:
        httpd.shutdown()


def test_fleet_healthz_503_when_fleet_down(tmp_path, monkeypatch):
    from heatmap_tpu.obs.xproc import ENV_CHANNEL, publish_member_snapshot

    chan = str(tmp_path / "chan")
    publish_member_snapshot(chan, "p0", role="runtime",
                            healthz={"status": "down", "checks": {}})
    monkeypatch.setenv(ENV_CHANNEL, chan)
    httpd, _t, port = start_background(MemoryStore(),
                                       load_config({}, serve_port=0),
                                       port=0)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/fleet/healthz", timeout=10)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["status"] == "down"
    finally:
        httpd.shutdown()


def test_sse_admission_under_client_churn_at_cap(store):
    """r9 satellite: the check-then-claim SSE admission (one lock, gauge
    moved before the body is iterated) holds under rapid connect/drop
    churn AT the cap — the live-client gauge never exceeds the cap, the
    overflow answers are clean 503s, and every slot is released (gauge
    returns to 0) even for clients that vanish before reading a byte."""
    import socket
    import threading

    cap = 4
    cfg = load_config({"HEATMAP_SSE_MAX_CLIENTS": str(cap),
                       "HEATMAP_VIEW_POLL_MS": "50",
                       "HEATMAP_SSE_HEARTBEAT_S": "0.2"}, serve_port=0)
    httpd, _t, port = start_background(store, cfg, port=0)
    app = httpd.get_app()
    # the admission gauge lives in the app's serve registry
    gauge = None
    for fam in app.serve_registry._families.values():
        if fam.name == "heatmap_serve_sse_clients":
            gauge = fam
    assert gauge is not None

    stop = threading.Event()
    seen_max = [0]

    def watch():
        while not stop.is_set():
            seen_max[0] = max(seen_max[0], int(gauge.value))
            time.sleep(0.001)

    stats = {"ok": 0, "refused": 0, "lock": threading.Lock()}

    def churn(n):
        for _ in range(n):
            try:
                sk = socket.create_connection(("127.0.0.1", port),
                                              timeout=10)
                sk.sendall(b"GET /api/tiles/stream?since=0 "
                           b"HTTP/1.0\r\n\r\n")
                sk.settimeout(5)
                head = sk.recv(256)
                with stats["lock"]:
                    if b"503" in head:
                        stats["refused"] += 1
                    else:
                        stats["ok"] += 1
                # half the clients slam the door before reading the
                # body; the other half read one event first
                if b"200" in head:
                    sk.recv(1024)
                sk.close()
            except OSError:
                pass

    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    try:
        threads = [threading.Thread(target=churn, args=(6,))
                   for _ in range(cap * 3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        # churned hard enough to hit the cap at least once
        assert stats["ok"] + stats["refused"] == cap * 3 * 6
        assert stats["ok"] > 0
        # every slot released: the gauge drains back to 0
        deadline = time.time() + 15
        while time.time() < deadline:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics",
                    timeout=10) as r:
                txt = r.read().decode()
            series, _ = _parse_prom(txt)
            live = series.get("heatmap_serve_sse_clients", {}).get("")
            if live == 0:
                break
            time.sleep(0.1)
        assert live == 0
        # and the cap was never exceeded while churning
        assert seen_max[0] <= cap
    finally:
        stop.set()
        httpd.shutdown()


# =================================================================
# Serve-tier wire path (ISSUE 14): binary frames, format-keyed ETags,
# coalesced SSE fan-out, admission control, multi-process workers.

def _get_raw(url, headers=None):
    """(status, body, headers) tolerating non-2xx."""
    req = urllib.request.Request(url)
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def test_binary_latest_and_delta_differential_http(server):
    """decode(binary) == JSON byte-for-byte over real HTTP, for
    /latest and /delta, on a store-fed worker — plus Content-Type and
    unknown-fmt rejection."""
    from heatmap_tpu.serve import wire
    from heatmap_tpu.serve.api import (_delta_body,
                                       _features_collection_json)

    st, jbody, jh = _get_raw(server + "/api/tiles/latest")
    st2, bbody, bh = _get_raw(server + "/api/tiles/latest?fmt=bin")
    assert st == st2 == 200
    assert bh["Content-Type"] == wire.CONTENT_TYPE
    # the representation varies on Accept: BOTH formats must say so
    # (as a full token, not the Accept-Encoding prefix), or a shared
    # cache could replay the wrong representation
    for q in ("", "?fmt=bin"):
        with urllib.request.urlopen(server + "/api/tiles/latest" + q,
                                    timeout=10) as r:
            vary = ",".join(r.headers.get_all("Vary") or [])
        assert "Accept" in [v.strip() for v in vary.split(",")], vary
    assert len(bbody) < len(jbody)
    dec = wire.decode(bbody)
    assert _features_collection_json(dec["docs"]).encode() == jbody
    st3, jd, _ = _get_raw(server + "/api/tiles/delta?since=0")
    st4, bd, _ = _get_raw(server + "/api/tiles/delta?since=0&fmt=bin")
    d = wire.decode(bd)
    assert _delta_body(d, "h3r8").encode() == jd
    assert d["seq"] == json.loads(jd)["seq"]
    # Accept-header negotiation selects binary too
    _, _, ah = _get_raw(server + "/api/tiles/latest",
                        {"Accept": wire.CONTENT_TYPE})
    assert ah["Content-Type"] == wire.CONTENT_TYPE
    # unknown fmt is a 400, not a guess
    st5, body5, _ = _get_raw(server + "/api/tiles/delta?fmt=nope")
    assert st5 == 400 and b"fmt" in body5


def _assert_format_keyed_etags(base):
    """No cross-format 304: a JSON ETag against a binary request (and
    vice versa) re-renders; same-format If-None-Match still 304s."""
    st, _, jh = _get_raw(base + "/api/tiles/latest")
    st2, _, bh = _get_raw(base + "/api/tiles/latest?fmt=bin")
    assert st == st2 == 200
    assert jh["ETag"] != bh["ETag"]
    assert bh["ETag"].endswith('.bin"')
    checks = (
        ("/api/tiles/latest?fmt=bin", jh["ETag"], 200),
        ("/api/tiles/latest?fmt=bin", bh["ETag"], 304),
        ("/api/tiles/latest", bh["ETag"], 200),
        ("/api/tiles/latest", jh["ETag"], 304),
    )
    for path, etag, want in checks:
        got, _, _ = _get_raw(base + path, {"If-None-Match": etag})
        assert got == want, (path, etag, got, want)


def test_format_keyed_etags_store_fed(server):
    _assert_format_keyed_etags(server)


def test_format_keyed_etags_writer_fed(tmp_path):
    """Same no-cross-format-304 contract on the runtime's writer-fed
    view."""
    cfg, st, rt = _mini_runtime(tmp_path)
    httpd, _t, port = start_background(st, cfg, runtime=rt)
    try:
        _assert_format_keyed_etags(f"http://127.0.0.1:{port}")
    finally:
        httpd.shutdown()
        rt.close()


def test_format_keyed_etags_and_differential_replica_fed(tmp_path):
    """The replica topology: a serve worker following the replication
    feed with an EMPTY store serves format-keyed ETags and the
    binary==JSON differential like the writer."""
    from heatmap_tpu.query import TileMatView
    from heatmap_tpu.query.repl import DeltaLogPublisher
    from heatmap_tpu.serve import wire
    from heatmap_tpu.serve.api import _features_collection_json

    feed = str(tmp_path / "feed")
    view = TileMatView()
    pub = DeltaLogPublisher(view, feed, flush_s=0.02)
    now = dt.datetime.now(UTC).replace(microsecond=0)
    ws = now - dt.timedelta(minutes=2)
    cells = [hexgrid.latlng_to_cell(42.3 + i * 7e-3, -71.05, 8)
             for i in range(4)]
    view.apply_docs([
        TileDoc("bos", 8, c, ws, ws + dt.timedelta(minutes=5),
                count=i + 1, avg_speed_kmh=20.0 + i, avg_lat=42.3,
                avg_lon=-71.05, ttl_minutes=45)
        for i, c in enumerate(cells)])
    cfg = load_config({}, store="memory", serve_port=0,
                      repl_feed=feed, repl_poll_ms=50)
    httpd, _t, port = start_background(MemoryStore(), cfg)
    base = f"http://127.0.0.1:{port}"
    try:
        fol = httpd.get_app().repl_follower
        deadline = time.time() + 20
        while time.time() < deadline and not (fol.synced
                                              and fol.seq_lag() == 0):
            time.sleep(0.02)
        assert fol.synced
        _assert_format_keyed_etags(base)
        st1, jbody, _ = _get_raw(base + "/api/tiles/latest")
        st2, bbody, _ = _get_raw(base + "/api/tiles/latest?fmt=bin")
        dec = wire.decode(bbody)
        assert _features_collection_json(dec["docs"]).encode() == jbody
    finally:
        httpd.shutdown()
        httpd.get_app().close_repl()
        pub.close()


def test_sse_coalesced_encodes_o_formats_not_o_clients(store):
    """The fan-out acceptance metric: with N subscribers on one (grid,
    format) channel, M view advances cost ~M encodes — never N*M."""
    import socket

    n_clients = 4
    cfg = load_config({"HEATMAP_VIEW_POLL_MS": "30",
                       "HEATMAP_SSE_HEARTBEAT_S": "0.2"}, serve_port=0)
    httpd, _t, port = start_background(store, cfg, port=0)
    app = httpd.get_app()
    enc = None
    for fam in app.serve_registry._families.values():
        if fam.name == "heatmap_sse_encodes_total":
            enc = fam
    assert enc is not None
    socks = []
    try:
        for _ in range(n_clients):
            sk = socket.create_connection(("127.0.0.1", port),
                                          timeout=10)
            sk.sendall(b"GET /api/tiles/stream?since=0 "
                       b"HTTP/1.0\r\n\r\n")
            sk.settimeout(10)
            socks.append(sk)
        bufs = [b""] * n_clients
        for i, sk in enumerate(socks):
            while bufs[i].count(b"event: tiles") < 1:
                bufs[i] += sk.recv(65536)
        base_encodes = enc.labels(fmt="json").value
        mutations = 5
        now = dt.datetime.now(UTC).replace(microsecond=0)
        ws = now - dt.timedelta(minutes=2)
        for m in range(mutations):
            cell = hexgrid.latlng_to_cell(42.5 + m * 0.01, -71.2, 8)
            store.upsert_tiles([
                TileDoc("bos", 8, cell, ws,
                        ws + dt.timedelta(minutes=5), count=m + 1,
                        avg_speed_kmh=10.0, avg_lat=42.5,
                        avg_lon=-71.2, ttl_minutes=45)])
            for i, sk in enumerate(socks):
                while bufs[i].count(b"event: tiles") < m + 2 \
                        or not bufs[i].endswith(b"\n\n"):
                    bufs[i] += sk.recv(65536)
        # every client saw every frame...
        frames = [[f for f in b.split(b"\n\n") if b"event: tiles" in f]
                  for b in bufs]
        assert all(fr == frames[0] for fr in frames)  # SHARED bytes
        # ...but the encode counter moved once per advance, not once
        # per (advance x client)
        encodes = enc.labels(fmt="json").value - base_encodes
        assert mutations <= encodes <= mutations + 2, encodes
    finally:
        for sk in socks:
            sk.close()
        httpd.shutdown()


def test_sse_slow_client_shed_with_lagged_others_unaffected(store):
    """ISSUE 14 chaos satellite: a subscriber that stops reading
    mid-stream is shed with ``event: lagged`` once its bounded queue
    overflows, its admission slot is released, the client gauge drains
    to zero, and the OTHER subscribers on the same coalesced buffer
    see every frame (zero missed seqs)."""
    import socket

    cfg = load_config({"HEATMAP_VIEW_POLL_MS": "30",
                       "HEATMAP_SSE_HEARTBEAT_S": "0.1",
                       "HEATMAP_SSE_QUEUE": "2"}, serve_port=0)
    httpd, _t, port = start_background(store, cfg, port=0)
    app = httpd.get_app()
    gauge = lagged = None
    for fam in app.serve_registry._families.values():
        if fam.name == "heatmap_serve_sse_clients":
            gauge = fam
        if fam.name == "heatmap_sse_lagged_total":
            lagged = fam

    def connect(rcvbuf=None):
        sk = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        if rcvbuf:
            sk.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
        sk.settimeout(10)
        sk.connect(("127.0.0.1", port))
        sk.sendall(b"GET /api/tiles/stream?since=0 HTTP/1.0\r\n\r\n")
        return sk

    slow = connect(rcvbuf=4096)
    good = [connect(), connect()]
    bufs = {id(s): b"" for s in good}
    try:
        # everyone reads the first (catch-up) frame
        sbuf = b""
        while sbuf.count(b"event: tiles") < 1:
            sbuf += slow.recv(65536)
        for s in good:
            while bufs[id(s)].count(b"event: tiles") < 1:
                bufs[id(s)] += s.recv(65536)
        # the slow client STOPS READING; each mutation touches a
        # ~200-cell batch (a ~120 KB frame), so the stalled
        # connection's in-flight socket capacity (~1 MB on this
        # kernel) plus its 2-frame queue overflow within a few
        # mutations while the good clients keep draining
        now = dt.datetime.now(UTC).replace(microsecond=0)
        ws = now - dt.timedelta(minutes=2)
        batch_cells = sorted({
            hexgrid.latlng_to_cell(42.6 + (j % 20) * 8e-3,
                                   -71.3 + (j // 20) * 8e-3, 8)
            for j in range(200)})
        mutations = 28
        for m in range(mutations):
            store.upsert_tiles([
                TileDoc("bos", 8, c, ws,
                        ws + dt.timedelta(minutes=5),
                        count=m * 100 + j + 1,
                        avg_speed_kmh=9.0, avg_lat=42.6, avg_lon=-71.3,
                        ttl_minutes=45)
                for j, c in enumerate(batch_cells)])
            for s in good:
                while bufs[id(s)].count(b"event: tiles") < m + 2 \
                        or not bufs[id(s)].endswith(b"\n\n"):
                    bufs[id(s)] += s.recv(65536)
        # good clients: identical shared frames, all advances seen
        frames = [[f for f in bufs[id(s)].split(b"\n\n")
                   if b"event: tiles" in f] for s in good]
        assert frames[0] == frames[1]
        assert len(frames[0]) == mutations + 1
        # the slow client was shed: lagged counter bumped, and when it
        # finally drains its socket it finds the lagged event + EOF
        deadline = time.time() + 15
        while time.time() < deadline and lagged.value < 1:
            time.sleep(0.05)
        assert lagged.value >= 1
        while True:
            try:
                chunk = slow.recv(65536)
            except socket.timeout:
                raise AssertionError("slow client never saw EOF")
            if not chunk:
                break
            sbuf += chunk
        assert b"event: lagged" in sbuf
        # shed + closed clients release every slot: gauge drains to 0
        for s in good:
            s.close()
        slow.close()
        deadline = time.time() + 15
        while time.time() < deadline and gauge.value != 0:
            time.sleep(0.1)
        assert gauge.value == 0
    finally:
        for s in good:
            s.close()
        slow.close()
        httpd.shutdown()


def test_admission_control_sheds_with_retry_after(store):
    """HEATMAP_SERVE_MAX_INFLIGHT=1: with one render parked inside the
    store, a concurrent data request sheds 503 + Retry-After and bumps
    the shed counter; the operator surface (/healthz) is never shed."""
    import threading as _th

    from heatmap_tpu.serve.api import make_wsgi_app

    release = _th.Event()
    entered = _th.Event()

    class SlowStore(MemoryStore):
        def latest_window_start(self, grid=None):
            entered.set()
            release.wait(10)
            return super().latest_window_start(grid)

    slow = SlowStore()
    # same content as the fixture store
    now = dt.datetime.now(UTC).replace(microsecond=0)
    ws = now - dt.timedelta(minutes=2)
    cell = hexgrid.latlng_to_cell(42.3601, -71.0589, 8)
    slow.upsert_tiles([TileDoc("bos", 8, cell, ws,
                               ws + dt.timedelta(minutes=5), count=7,
                               avg_speed_kmh=33.0, avg_lat=42.36,
                               avg_lon=-71.05, ttl_minutes=45)])
    cfg = load_config({"HEATMAP_QUERY_VIEW": "0",
                       "HEATMAP_SERVE_CACHE_MS": "0"},
                      serve_port=0, serve_max_inflight=1)
    app = make_wsgi_app(slow, cfg)

    def call(path):
        out = {}

        def sr(status, headers):
            out["status"] = status
            out["headers"] = dict(headers)

        body = b"".join(app({"PATH_INFO": path, "QUERY_STRING": "",
                             "REQUEST_METHOD": "GET"}, sr))
        out["body"] = body
        return out

    slow_result = {}
    t = _th.Thread(target=lambda: slow_result.update(
        call("/api/tiles/latest")), daemon=True)
    t.start()
    assert entered.wait(10)
    shed = call("/api/tiles/latest")
    assert shed["status"].startswith("503")
    assert shed["headers"].get("Retry-After") == "1"
    hz = call("/healthz")          # operator surface never shed
    assert hz["status"].startswith("200")
    release.set()
    t.join(timeout=10)
    assert slow_result["status"].startswith("200")
    shed_ctr = None
    for fam in app.serve_registry._families.values():
        if fam.name == "heatmap_serve_shed_total":
            shed_ctr = fam
    assert shed_ctr.labels(endpoint="tiles").value == 1


def test_multi_process_serve_workers_reuseport(tmp_path):
    """``python -m heatmap_tpu.serve --workers 2``: two worker
    processes answer on ONE port (SO_REUSEPORT), each publishing its
    own fleet member snapshot, and SIGTERM stops the fleet cleanly."""
    import signal
    import socket
    import subprocess
    import sys

    from heatmap_tpu.obs.xproc import members_from

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    chan = str(tmp_path / "chan.json")
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "HEATMAP_STORE": "memory",
                "HEATMAP_SUPERVISOR_CHANNEL": chan,
                "HEATMAP_FLEET_PUBLISH_S": "0.5"})
    proc = subprocess.Popen(
        [sys.executable, "-m", "heatmap_tpu.serve", "--workers", "2",
         "--port", str(port)], env=env)
    try:
        pids = set()
        deadline = time.time() + 90
        while time.time() < deadline and len(pids) < 2:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/debug/view",
                        timeout=3) as r:
                    pids.add(json.loads(r.read())["pid"])
            except (OSError, ValueError):
                time.sleep(0.3)
        assert len(pids) == 2, f"saw worker pids {pids}"
        # each worker published its own serve member on the channel
        deadline = time.time() + 30
        serve_members = {}
        while time.time() < deadline and len(serve_members) < 2:
            members, _skipped = members_from(chan, max_age_s=30.0)
            serve_members = {t: m for t, m in members.items()
                             if m.get("role") == "serve"}
            time.sleep(0.3)
        assert len(serve_members) == 2, sorted(serve_members)
    finally:
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=20) == 0


def test_index_embeds_binary_wire_decoder():
    """The embedded UI ships the DataView wire-frame parser, negotiates
    ?fmt=bin on the delta poll, and reports the format on the HUD."""
    from heatmap_tpu.serve.ui import render_index

    html = render_index()
    assert "decodeWireFrame" in html
    assert "fmt=bin" in html
    assert "wireSaved" in html and "wireFmt" in html
