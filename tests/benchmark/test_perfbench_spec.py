"""The benchmark finds its data by name, and refuses what breaks its rules."""

import json
import shutil
from pathlib import Path

import pytest

from benchmark import spec as specmod

ROOT = Path(__file__).resolve().parents[2]


def _tmp_spec(tmp_path, bench=None):
    base = tmp_path / "benchmark"
    shutil.copytree(ROOT / "benchmark", base,
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = bench or json.loads((ROOT / "BENCHMARK.json").read_text())
    return specmod.Spec(bench, base), base, bench


def test_benchmark_json_is_whole():
    """Every cell's configuration and mix has its file, every per-layer
    metric its reader, and each metric moves an end-to-end metric that
    its cells report."""
    spec = specmod.Spec.load()
    e2e = {m["name"]: m for m in spec.bench["end_to_end"]}
    assert "setup_s" in e2e
    for w in spec.bench["workloads"]:
        cfg = spec.config(w["config"])
        assert cfg["preset"] and cfg["fleet"]["capture_events"] > 0
        assert spec.mix(w["traffic"])["arrival"] in ("open", "closed")
        assert len(spec.end_to_end(w["name"])) >= 2
        assert spec.per_layer(w["name"])
    for m in spec.bench["per_layer"]:
        assert callable(specmod.reader(m["name"]))
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in moved.get("workloads", [cell])
    for c in spec.bench["configs"]:
        # the file BENCHMARK.json names is the one the harness finds by name
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        assert (ROOT / c["file"]).exists()


def test_new_config_mix_and_metric_found_by_name(tmp_path):
    spec, base, bench = _tmp_spec(tmp_path)
    (base / "configs" / "other_city.json").write_text(json.dumps(
        {"preset": "synthetic_backfill", "fleet": {"capture_events": 1}}))
    (base / "mixes" / "bursty.json").write_text(json.dumps(
        {"arrival": "open", "rate_events_per_s": 5}))
    (base / "metrics" / "queue_depth.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["configs"].append({"name": "other_city", "source": "s",
                             "file": "benchmark/configs/other_city.json",
                             "reduced": [], "why": "w"})
    bench["workloads"].append({"name": "other_bursty", "config": "other_city",
                               "traffic": "bursty", "chips": 1, "why": "w"})
    spec = specmod.Spec(bench, base)
    w = spec.workload("other_bursty")
    assert spec.config(w["config"])["preset"] == "synthetic_backfill"
    assert spec.mix(w["traffic"])["rate_events_per_s"] == 5
    assert specmod.reader("queue_depth.bursty", base)(None) == 42.0
    assert specmod.reader("queue_depth", base)(None) == 42.0


@pytest.mark.parametrize("lookup,name", [
    ("workload", "no_such_cell"), ("mix", "no_such_mix"),
    ("config", "no_such_config")])
def test_unknown_names_refused(lookup, name):
    spec = specmod.Spec.load()
    with pytest.raises(specmod.SpecError):
        getattr(spec, lookup)(name)


def test_unknown_metric_reader_refused():
    with pytest.raises(specmod.SpecError):
        specmod.reader("no_such_metric.replay")


@pytest.mark.parametrize("name", ["has space", "a,b", "a/b", "", "-lead",
                                  "x" * 65, "naïve"])
def test_bad_names_refused(name):
    with pytest.raises(specmod.SpecError):
        specmod.check_name(name)


@pytest.mark.parametrize("unit", ["tokens per second", "", "x" * 17, "µs"])
def test_bad_units_refused(unit):
    with pytest.raises(specmod.SpecError):
        specmod.check_unit(unit)


def test_bad_name_in_benchmark_json_refused(tmp_path):
    _, base, bench = _tmp_spec(tmp_path)
    bench["workloads"][0]["name"] = "r9 replay"
    with pytest.raises(specmod.SpecError):
        specmod.Spec(bench, base)


def test_bad_unit_in_benchmark_json_refused(tmp_path):
    _, base, bench = _tmp_spec(tmp_path)
    bench["end_to_end"][0]["unit"] = "events per second"
    with pytest.raises(specmod.SpecError):
        specmod.Spec(bench, base)


def test_peaks_known_and_unknown_device_kind():
    assert specmod.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(specmod.SpecError):
        specmod.peaks("cpu")
