"""chip_smoke.py off the chip: it refuses to run, its last line is the
contract, and its phases pass at tiny sizes when called directly.

The phases run here on the CPU (the tests chose it), on the same
pipelines and entry points the chip run uses; only the scale is cut.
There is no CPU option in the script itself.
"""

import copy
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

TINY = dict(n_events=1 << 14, n_vehicles=200, span_s=1800)
TINY_CFG = dict(batch_size=1 << 11, state_capacity_log2=15)


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_script_fails_without_a_tpu(tmp_path, where):
    """Under JAX_PLATFORMS=cpu — in the checkout, and in a directory
    holding chip_smoke.py and nothing else — the script exits non-zero
    and prints no result."""
    cwd = REPO
    if where == "alone":
        cwd = str(tmp_path)
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), cwd)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("count", [1, 4])
def test_ok_line_is_the_contract(count):
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    line = cs.ok_line([dev] * count)
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": count}}
    assert "\n" not in line


def test_backfill_scale_closes_three_windows():
    """The published-scale feed spans enough event time that at least
    three 5-minute windows close under the 10-minute watermark."""
    args = cs.source_args(**cs.BACKFILL)
    span = args["n_events"] // args["events_per_second"]
    assert span >= 3 * cs.WINDOW_S + 600
    assert args["t0"] % cs.WINDOW_S == 0


def test_backfill_phase_tiny(tmp_path):
    """Stream (count + kalman) → serve (four endpoints) → reference
    check, at a cut scale on the CPU."""
    out = cs.phase_backfill(str(tmp_path), TINY, **TINY_CFG)
    assert out["events_folded"] == TINY["n_events"]
    assert out["agreement"][9] >= cs.MIN_AGREEMENT[9]
    assert out["windows_closed"] >= 3
    assert out["live_groups"] < out["tiles"]
    assert out["serve"]["n_features"] > 0


def test_mesh_phase_tiny_on_four_cpu_devices(tmp_path):
    """The mesh's tiles (count + kalman) are byte-identical to one
    device's (tests/test_mesh_diff.py's guarantee); the phase raises
    otherwise."""
    out = cs.phase_mesh(str(tmp_path), 4, TINY, **TINY_CFG)
    assert out["byte_identical"]
    assert out["mesh_mode"] == "partitioned"
    assert out["events_folded"] == {"mesh": TINY["n_events"],
                                    "one": TINY["n_events"]}


def test_compare_tiles_reports_what_differs():
    a = {"k": {"count": 3, "avgSpeedKmh": 50.0,
               "centroid": {"coordinates": [-71.0, 42.0]}}}
    b = copy.deepcopy(a)
    assert cs.compare_tiles(a, b) == {"byte_identical": True,
                                      "only_one_side": 0, "differing": {}}
    b["k"]["avgSpeedKmh"] = 50.5
    b["k"]["centroid"]["coordinates"][1] = 42.25
    b["k"]["vxKmh"] = 1.0
    b["j"] = {"count": 1}
    got = cs.compare_tiles(a, b)
    assert not got["byte_identical"] and got["only_one_side"] == 1
    assert got["differing"] == {"avgSpeedKmh": (1, 0.5),
                                "centroid": (1, 0.25),
                                "vxKmh": (1, float("inf"))}


def test_pyramid_phase_requests_pallas(tmp_path, monkeypatch):
    """The Pallas phase runs with HEATMAP_H3_IMPL=pallas set as an
    operator would, and restores the environment after."""
    seen = {}

    def fake_run(p, args, **kw):
        seen["impl"] = os.environ.get("HEATMAP_H3_IMPL")
        raise cs.SmokeFailure("stop")

    monkeypatch.setattr(cs, "run_stream", fake_run)
    monkeypatch.delenv("HEATMAP_H3_IMPL", raising=False)
    with pytest.raises(cs.SmokeFailure):
        cs.phase_pyramid_pallas(str(tmp_path), TINY)
    assert seen["impl"] == "pallas"
    assert "HEATMAP_H3_IMPL" not in os.environ


def test_reference_check_catches_a_lost_event(tmp_path):
    """The reference check is not vacuous: one event missing from a
    tile fails conservation."""
    p = cs.smoke_pipeline("synthetic_backfill", str(tmp_path), **TINY_CFG)
    args = cs.source_args(**TINY)
    run = cs.run_stream(p, args)
    cs.check_reference(run, p, args)
    doc = next(iter(run["store"]._tiles.values()))
    doc["count"] -= 1
    with pytest.raises(cs.SmokeFailure, match="tiles hold"):
        cs.check_reference(run, p, args)


def test_oracle_groups_conserve_events():
    args = cs.source_args(**TINY)
    groups = cs.oracle_groups(args, 9, sample=200)
    assert sum(groups.values()) == TINY["n_events"]
    assert all(ws % cs.WINDOW_S == 0 for _, ws in groups)
