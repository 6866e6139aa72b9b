#!/usr/bin/env python3
"""Bring-up smoke: the streaming fold and serve path on the TPU.

    python chip_smoke.py            # one chip: stream, serve, Pallas
    python chip_smoke.py --chips 4  # only the mesh path: 4 devices vs 1

One process owns the chip and starts no child that touches JAX.  The
runs go through the stream job's own wiring (``build_runtime``, the
same call ``python -m heatmap_tpu.stream`` makes) and the serve
layer's ``start_background``.  Results are checked against a plain
reference: the f64 C++ host snap plus a NumPy group-by over the same
seeded events.  Every rate or time printed is a smoke reading of one
run, not a benchmark.  Any failed check exits non-zero; only a run
where every phase passed prints the last line,
``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import tempfile
import time
import urllib.request

import numpy as np

WINDOW_S = 300
# BASELINE config #3 (heatmap_tpu/models/pipelines.py): a 20k-vehicle
# single-city backfill at res 9, batch 2^19, slab 2^20
BACKFILL = dict(n_events=12 << 19, n_vehicles=20_000, span_s=1800)
PYRAMID = dict(n_events=16 << 17, n_vehicles=20_000, span_s=1800)
KERNEL_POINTS = 1 << 19
REDUCERS = ("count", "kalman")
# f32 device snap vs the f64 oracle (tests/test_hexgrid_device.py): a
# point within ~0.6 m of a cell edge may land in the neighbouring cell
MIN_AGREEMENT = {7: 0.9985, 8: 0.997, 9: 0.994}


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"# {msg}", flush=True)


def ok_line(devices) -> str:
    """The contract's last line, from the devices as JAX reports them."""
    d = devices[0]
    return json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}})


def peak_bytes(device) -> "int | None":
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def source_args(n_events: int, n_vehicles: int, span_s: int,
                seed: int = 0) -> dict:
    """SyntheticSource arguments whose event time spans ``span_s``
    seconds ending now, so windows close under the watermark and the
    tiles are still inside their TTL when served."""
    t0 = (int(time.time()) - span_s) // WINDOW_S * WINDOW_S
    return dict(n_events=n_events, n_vehicles=n_vehicles, t0=t0,
                events_per_second=max(1, n_events // span_s), seed=seed)


def smoke_pipeline(name: str, ckpt_dir: str, **overrides):
    """Pipeline ``name`` with the smoke's store, reducers and a fresh
    checkpoint directory (a stale checkpoint would resume mid-stream).
    The slab is pinned at the configuration's own size
    (``state_max_log2``): the runtime's worst-case growth margin (2x the
    batch) would otherwise grow it on the first batch and compile the
    fold a second time."""
    from heatmap_tpu.models.pipelines import get_pipeline

    p = get_pipeline(name)
    cfg = dataclasses.replace(p.config, **{
        "store": "memory", "reducers": REDUCERS, "checkpoint_dir": ckpt_dir,
        **overrides})
    cfg = dataclasses.replace(cfg, state_max_log2=cfg.state_capacity_log2)
    return dataclasses.replace(p, config=cfg)


def run_stream(p, src_args: dict, n_devices: int = 1, serve: bool = False):
    """Fold the seeded events through ``MicroBatchRuntime`` to
    exhaustion; with ``serve`` the HTTP layer is started first, as the
    demo does, and its four endpoints are fetched after the run."""
    from heatmap_tpu.models.pipelines import build_runtime
    from heatmap_tpu.stream import SyntheticSource

    rt, store = build_runtime(p, source=SyntheticSource(**src_args),
                              n_devices=n_devices)
    # the snap policy is frozen for the runtime's life; close() releases
    # it, so read it before the run
    snap = rt._snap_impl_name
    httpd = None
    if serve:
        from heatmap_tpu.serve import start_background

        httpd, _, port = start_background(store, p.config, rt,
                                          host="127.0.0.1", port=0)
    t0 = time.monotonic()
    try:
        rt.run()
        wall = time.monotonic() - t0
        # wall of every step call that compiled (obs.runtimeinfo)
        compiles = rt.metrics.registry.histogram("heatmap_compile_seconds",
                                                 labels=("fn",))
        out = {
            "rt": rt, "store": store, "snap": snap, "wall_s": wall,
            "compile_s": sum(c.sum for c in compiles.children.values()),
            "counters": dict(rt.metrics.counters),
            # mean host seconds per batch of each step stage: the host
            # snap is "snap" on one device, "partition" + "pad" on the
            # partitioned mesh
            "span_mean_s": {k: h.sum / h.count
                            for k, h in rt.metrics.spans.items()
                            if h.count},
        }
        if httpd is not None:
            out["serve"] = fetch_endpoints(port)
        return out
    finally:
        if httpd is not None:
            httpd.shutdown()
        store.close()


def fetch_endpoints(port: int) -> dict:
    """GET the four serve endpoints; each must answer 200 and the tile
    body must be a GeoJSON FeatureCollection."""
    got = {}
    for path in ("/api/tiles/latest", "/api/positions/latest", "/healthz",
                 "/metrics"):
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                    timeout=60) as r:
            check(r.status == 200, f"{path} answered {r.status}")
            got[path] = r.read()
    tiles = json.loads(got["/api/tiles/latest"])
    check(tiles.get("type") == "FeatureCollection",
          "/api/tiles/latest is not a GeoJSON FeatureCollection")
    check(len(tiles.get("features", ())) > 0,
          "/api/tiles/latest served no tiles")
    return {"n_features": len(tiles["features"]),
            "bytes": {k: len(v) for k, v in got.items()}}


def oracle_groups(src_args: dict, res: int, sample: int = 2000):
    """Per-(cell, window) event counts of the plain reference: the f64
    C++ host snap and a NumPy group-by over the same seeded events.
    The C++ snap is itself spot-checked against the pure-Python H3
    oracle on ``sample`` events."""
    from heatmap_tpu.hexgrid import host, native_snap
    from heatmap_tpu.stream import SyntheticSource

    cols = SyntheticSource(**src_args).poll(src_args["n_events"])
    hi, lo = native_snap.snap_arrays(cols.lat_rad, cols.lng_rad, res)
    cells = (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)
    pick = np.random.default_rng(res).choice(len(cells), sample,
                                             replace=False)
    want = [host.latlng_to_cell_int(float(cols.lat_rad[i]),
                                    float(cols.lng_rad[i]), res)
            for i in pick]
    check(cells[pick].tolist() == want,
          f"C++ oracle disagrees with the host H3 oracle at res {res}")
    ws = cols.ts_s.astype(np.int64) // WINDOW_S * WINDOW_S
    keys = np.empty(len(cells), [("cell", np.uint64), ("ws", np.int64)])
    keys["cell"], keys["ws"] = cells, ws
    uniq, counts = np.unique(keys, return_counts=True)
    return {(int(c), int(w)): int(n) for (c, w), n in zip(uniq, counts)}


def tile_groups(store, grid: str) -> dict:
    """(cell, window start) -> count of the store's tile docs on
    ``grid``."""
    out = {}
    for d in store._tiles.values():
        if d.get("grid") == grid:
            ws = int(d["windowStart"].timestamp())
            out[(int(d["cellId"], 16), ws)] = int(d["count"])
    return out


def check_conservation(run: dict, p, n_events: int) -> dict:
    """``events_valid`` counts the events folded after the watermark's
    late drop, so conservation is exact on both sides: valid + late +
    invalid = events fed, and Σ tile count = valid on every grid."""
    c = run["counters"]
    valid, late = c.get("events_valid", 0), c.get("events_late", 0)
    fed = valid + late + c.get("events_invalid", 0)
    check(fed == n_events, f"accounted {fed} events of {n_events} fed")
    grids = {}
    for res in p.config.resolutions:
        got = tile_groups(run["store"],
                          p.config.pair_grid(res, p.config.tile_minutes))
        check(sum(got.values()) == valid,
              f"res {res}: tiles hold {sum(got.values())} events, "
              f"events_valid = {valid}")
        grids[res] = got
    return grids


def check_reference(run: dict, p, src_args: dict) -> dict:
    """Conservation, and per-(cell, window) agreement with the plain
    reference: the share of events the run put in the oracle's group."""
    grids = check_conservation(run, p, src_args["n_events"])
    rates = {}
    for res, got in grids.items():
        want = oracle_groups(src_args, res)
        agree = sum(min(n, got.get(k, 0)) for k, n in want.items())
        rates[res] = agree / src_args["n_events"]
        check(rates[res] >= MIN_AGREEMENT.get(res, 0.99),
              f"res {res}: oracle agreement {rates[res]:.6f} below "
              f"{MIN_AGREEMENT.get(res, 0.99)}")
    c = run["counters"]
    return {"events_folded": c.get("events_valid", 0),
            "events_late": c.get("events_late", 0), "agreement": rates}


def window_stats(run: dict, p) -> dict:
    """Windows the watermark closed, and whether their state rows were
    evicted (live slab groups fewer than tiles written)."""
    cfg = p.config
    grid = cfg.pair_grid(cfg.h3_res, cfg.tile_minutes)
    groups = tile_groups(run["store"], grid)
    cutoff = run["rt"].max_event_ts - cfg.watermark_minutes * 60
    windows = {ws for _, ws in groups}
    closed = sum(1 for ws in windows if ws + WINDOW_S <= cutoff)
    live = run["rt"]._prev_active.get((cfg.h3_res, cfg.tile_minutes))
    return {"windows": len(windows), "windows_closed": closed,
            "tiles": len(groups), "live_groups": live}


def phase_backfill(ckpt_dir: str, scale: dict = BACKFILL,
                   **overrides) -> dict:
    """synthetic_backfill at its published widths, served and checked."""
    p = smoke_pipeline("synthetic_backfill", ckpt_dir, **overrides)
    args = source_args(**scale)
    run = run_stream(p, args, serve=True)
    check(run["snap"] in ("xla", "native"), f"unexpected snap {run['snap']}")
    ref = check_reference(run, p, args)
    win = window_stats(run, p)
    check(win["windows_closed"] >= 3,
          f"only {win['windows_closed']} windows closed")
    check(win["live_groups"] is not None and win["live_groups"] < win["tiles"],
          "closed windows were not evicted from the slab")
    return {"compile_s": run["compile_s"], "wall_s": run["wall_s"],
            "snap": run["snap"], "serve": run["serve"],
            **ref, **win}


@contextlib.contextmanager
def h3_impl(name: str):
    """Run with ``HEATMAP_H3_IMPL=name``, as an operator would set it."""
    prior = os.environ.get("HEATMAP_H3_IMPL")
    os.environ["HEATMAP_H3_IMPL"] = name
    try:
        yield
    finally:
        if prior is None:
            os.environ.pop("HEATMAP_H3_IMPL")
        else:
            os.environ["HEATMAP_H3_IMPL"] = prior


def phase_pyramid_pallas(ckpt_dir: str, scale: dict = PYRAMID) -> dict:
    """hex_pyramid (res 7/8/9) with an explicit Pallas snap request:
    the run must really use the kernel, and match the reference."""
    p = smoke_pipeline("hex_pyramid", ckpt_dir)
    args = source_args(**scale)
    with h3_impl("pallas"):
        run = run_stream(p, args)
    check(run["snap"] == "pallas",
          f"HEATMAP_H3_IMPL=pallas ran {run['snap']!r}")
    ref = check_reference(run, p, args)
    return {"compile_s": run["compile_s"], "wall_s": run["wall_s"],
            "snap": run["snap"], **ref}


def phase_kernel(n: int = KERNEL_POINTS) -> dict:
    """The Pallas snap called directly at ``n`` points per resolution,
    against the f64 C++ oracle."""
    import jax

    from heatmap_tpu.hexgrid import native_snap
    from heatmap_tpu.hexgrid.pallas_kernel import latlng_to_cell_pallas

    rng = np.random.default_rng(7)
    lat = np.radians(rng.uniform(42.2, 42.5, n)).astype(np.float32)
    lng = np.radians(rng.uniform(-71.3, -70.8, n)).astype(np.float32)
    out = {}
    for res in (7, 8, 9):
        t0 = time.monotonic()
        hi, lo = jax.block_until_ready(latlng_to_cell_pallas(lat, lng, res))
        first_s = time.monotonic() - t0
        whi, wlo = native_snap.snap_arrays(lat, lng, res)
        rate = float(((np.asarray(hi) == whi) & (np.asarray(lo) == wlo))
                     .mean())
        check(rate >= MIN_AGREEMENT[res],
              f"pallas kernel res {res}: agreement {rate:.6f}")
        out[res] = {"first_call_s": first_s, "agreement": rate}
    return out


def compare_tiles(a: dict, b: dict) -> dict:
    """Tile docs of two runs: byte identity, and for the report when
    they differ, the tiles on one side only and each differing field's
    count and largest absolute difference."""
    def as_bytes(docs):
        return json.dumps([docs[k] for k in sorted(docs)], sort_keys=True,
                          default=str).encode()

    out = {"byte_identical": as_bytes(a) == as_bytes(b),
           "only_one_side": len(a.keys() ^ b.keys()), "differing": {}}
    if out["byte_identical"]:
        return out
    for k in a.keys() & b.keys():
        for f in a[k].keys() | b[k].keys():
            va, vb = a[k].get(f), b[k].get(f)
            if va == vb:
                continue
            if f == "centroid":
                gap = max(abs(x - y) for x, y in zip(va["coordinates"],
                                                     vb["coordinates"]))
            elif isinstance(va, (int, float)) and isinstance(vb, (int, float)):
                gap = abs(va - vb)
            else:  # a field on one side only, or not a number
                gap = float("inf")
            n, d = out["differing"].get(f, (0, 0.0))
            out["differing"][f] = (n + 1, max(d, gap))
    return out


def phase_mesh(ckpt_dir: str, n_devices: int = 4,
               scale: dict = BACKFILL, **overrides) -> dict:
    """synthetic_backfill on an ``n_devices`` mesh and on one device in
    this process: byte-identical tiles, every event conserved.  Both
    runs snap on the host (``native``), as the partitioned mesh always
    does: its rows are routed by their host cell."""
    import jax

    devices = jax.devices()
    check(len(devices) >= n_devices,
          f"--chips {n_devices} needs {n_devices} devices, JAX has "
          f"{len(devices)}")
    args = source_args(**scale)
    p = smoke_pipeline("synthetic_backfill", f"{ckpt_dir}/mesh",
                       **overrides)
    p1 = smoke_pipeline("synthetic_backfill", f"{ckpt_dir}/one",
                        **overrides)
    with h3_impl("native"):
        mesh = run_stream(p, args, n_devices=n_devices)
        peaks = [peak_bytes(d) for d in devices[:n_devices]]
        one = run_stream(p1, args, n_devices=1)
    check(mesh["rt"]._mesh_mode is not None, "the mesh run built no mesh")
    check(mesh["snap"] == one["snap"] == "native",
          f"mesh snapped {mesh['snap']!r}, one device {one['snap']!r}")
    cmp = compare_tiles(mesh["store"]._tiles, one["store"]._tiles)
    out = {"mesh_mode": mesh["rt"]._mesh_mode,
           "tiles": len(one["store"]._tiles), **cmp,
           "peak_bytes_in_use": peaks,
           "compile_s": {"mesh": mesh["compile_s"], "one": one["compile_s"]},
           "wall_s": {"mesh": mesh["wall_s"], "one": one["wall_s"]},
           "span_mean_s": {"mesh": mesh["span_mean_s"],
                           "one": one["span_mean_s"]},
           "events_folded": {"mesh": mesh["counters"].get("events_valid", 0),
                             "one": one["counters"].get("events_valid", 0)}}
    say("mesh_compare " + json.dumps(out))  # before the checks can fail
    for run, pp in ((mesh, p), (one, p1)):
        check_conservation(run, pp, args["n_events"])
    check(cmp["byte_identical"], "mesh tiles differ from the one-device "
          f"tiles: {cmp['differing']}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh phase (4 devices vs 1)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX reports "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1

    from heatmap_tpu.utils.jaxenv import enable_compile_cache

    say(f"device {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{enable_compile_cache()}")
    say("every time and rate below is a smoke reading, not a benchmark")
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as ckpt:
        try:
            if args.chips == 4:
                say(f"mesh scale {BACKFILL}, reducers {','.join(REDUCERS)}")
                phase_mesh(ckpt, 4)  # prints its own comparison
            else:
                say(f"backfill scale {BACKFILL}; pyramid scale {PYRAMID}; "
                    "slabs pinned at each configuration's size")
                say("backfill " + json.dumps(phase_backfill(f"{ckpt}/b")))
                say(f"peak_bytes_in_use {peak_bytes(devices[0])}")
                say("pyramid_pallas "
                    + json.dumps(phase_pyramid_pallas(f"{ckpt}/p")))
                say("pallas_kernel " + json.dumps(phase_kernel()))
                say(f"peak_bytes_in_use {peak_bytes(devices[0])}")
        except SmokeFailure as e:
            print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
            return 1
    print(ok_line(jax.devices()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
