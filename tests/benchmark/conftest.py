"""Cells that only the tests run: the benchmark's own files copied into a
temporary directory, plus mixes, configurations and cells that use the
generator's other keys (open arrival, a ramp, late bands, a hot set,
Zipf hubs, three window sizes).  They are found by name, as a later
cell's files would be."""

import json
import shutil
from pathlib import Path

import pytest

from benchmark import spec as specmod

ROOT = Path(__file__).resolve().parents[2]
FLEET = {"n_vehicles": 20000, "center": [42.3601, -71.0589],
         "radius_deg": 0.15, "report_interval_s": 5,
         "capture_events": 10_000_000}
LATE = [{"share": 0.3, "min_s": 0, "max_s": 30},
        {"share": 0.05, "min_s": 1000, "max_s": 1500}]
MIXES = {
    "open_test": {"arrival": "open", "rate_events_per_s": 4000},
    "late_test": {"arrival": "closed", "late": LATE},
    "hot_ramp_test": {
        "arrival": "open", "rate_schedule": [[0, 2000], [0.5, 8000]],
        "late": [{"share": 0.5, "min_s": 0, "max_s": 5},
                 {"share": 0.02, "min_s": 2000, "max_s": 2500}],
        "hot": {"share": 0.4, "radius_deg": 0.003, "orbit_deg": 0.05,
                "period_s": 600}},
}
CONFIGS = {
    "multi_window_test": {"preset": "multi_window", "fleet": FLEET},
    "hubs_test": {"preset": "synthetic_backfill", "fleet": {
        **FLEET, "hubs": {"count": 30, "zipf_s": 1.2, "radius_deg": 0.01}}},
}
CELLS = {
    "live_test": ("synthetic_backfill", "open_test"),
    "late_test": ("synthetic_backfill", "late_test"),
    "multi_late_test": ("multi_window_test", "late_test"),
    "hot_ramp_test": ("hubs_test", "hot_ramp_test"),
}


@pytest.fixture
def bench_spec(tmp_path):
    """The benchmark's spec with the test cells added."""
    base = tmp_path / "benchmark"
    shutil.copytree(ROOT / "benchmark", base,
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name, mix in MIXES.items():
        (base / "mixes" / f"{name}.json").write_text(json.dumps(mix))
    for name, cfg in CONFIGS.items():
        (base / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"benchmark/configs/{name}.json",
                                 "reduced": [], "why": "test"})
    for name, (config, mix) in CELLS.items():
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": mix, "chips": 1, "why": "test"})
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    e2e["events_per_s"]["workloads"] += [
        c for c, (_, mix) in CELLS.items() if MIXES[mix]["arrival"] == "closed"]
    open_cells = [c for c, (_, mix) in CELLS.items()
                  if MIXES[mix]["arrival"] == "open"]
    for q in (50, 99):
        bench["end_to_end"].append({
            "name": f"freshness_p{q}_ms", "unit": "ms", "better": "lower",
            "bound": 0.25, "source": "host_clock", "workloads": open_cells})
    return specmod.Spec(bench, base)
