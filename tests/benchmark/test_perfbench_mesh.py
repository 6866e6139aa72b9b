"""The four-chip cell ``r9_replay_4chip`` run whole on the CPU at a small
size, on four of the suite's virtual devices, past the harness's look
for a chip: sound, it runs the partitioned mesh and is correct with no
compile in its window, under either emit pull; with one device's rows
dropped from its chunks, ``correct`` comes out false."""

import numpy as np
import pytest

from benchmark import harness

SCALE = {"fleet": {"capture_events": 100_000, "n_vehicles": 200},
         "runtime": {"batch_size": 1 << 12, "state_capacity_log2": 16}}
CELL = "r9_replay_4chip"


def _run(monkeypatch, emit_pull="prefix", trace=False):
    """One run of the cell; returns (result line, the runtime)."""
    built = {}
    build = harness.build

    def spy(*a, **kw):
        out = build(*a, **kw)
        built["rt"] = out[0]
        return out
    monkeypatch.setattr(harness, "build", spy)
    scale = {**SCALE, "runtime": {**SCALE["runtime"], "emit_pull": emit_pull}}
    out = harness.run_cell(CELL, 2**31 + 11, 1.5, trace, require_tpu=False,
                           scale=scale)
    return out, built["rt"]


@pytest.mark.parametrize("emit_pull", ["prefix", "full"])
def test_sound_mesh_run_is_correct(monkeypatch, emit_pull):
    out, rt = _run(monkeypatch, emit_pull, trace=emit_pull == "prefix")
    assert rt._mesh_mode == "partitioned" and rt._parted.n_shards == 4
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"]["compiles_in_window"]["value"] == 0
    if emit_pull == "prefix":
        m = out["metrics"]
        # 2^12 rows a batch over four devices, each padded to 2^12
        assert m["mesh_pad_share.replay"]["value"] == pytest.approx(75.0,
                                                                   abs=1.0)
        assert 100.0 <= m["mesh_row_skew.replay"]["value"] < 125.0


def test_device_rows_dropped_is_not_correct(monkeypatch):
    """Device 1 folds none of its rows: the events of its cells are
    missing from the view."""
    from heatmap_tpu.parallel import PartitionedAggregator

    orig = PartitionedAggregator.step_shard

    def step(self, shard, lat, lng, speed, ts, valid, cutoff, prekeys=None):
        if shard == 1:
            valid = np.zeros(np.shape(valid), bool)
        return orig(self, shard, lat, lng, speed, ts, valid, cutoff,
                    prekeys=prekeys)
    monkeypatch.setattr(PartitionedAggregator, "step_shard", step)
    out, _ = _run(monkeypatch)
    assert not out["correct"]
    assert out["checks"]["events_gap"]["value"] > 0
