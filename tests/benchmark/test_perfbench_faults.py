"""A whole run of a cell on the CPU at a small size, past the harness's
look for a chip: sound, it is correct (in the benchmark's cells and in
test cells that use the generator's other keys, so the reference's
watermark, hot set and open loop are held to the program too); with the
timed path broken underneath, ``correct`` comes out false, once for each
fault a one-chip cell can have."""

import numpy as np
import pytest

from benchmark import harness

SCALE = {"fleet": {"capture_events": 100_000, "n_vehicles": 200},
         "runtime": {"batch_size": 1 << 12, "state_capacity_log2": 16},
         "mix": {"rate_events_per_s": 4000}}


def _run(workload="r9_replay", seed=2**31 + 5, spec=None):
    return harness.run_cell(workload, seed, 1.5, False, require_tpu=False,
                            scale=SCALE, spec=spec)


def _patch_valid(monkeypatch, edit):
    """Rewrite the valid mask the fused step is handed."""
    from heatmap_tpu.engine.multi import MultiAggregator

    orig = MultiAggregator.step_packed_all

    def step(self, lat, lng, speed, ts, valid, cutoff, prekeys=None):
        return orig(self, lat, lng, speed, ts, edit(np.array(valid)), cutoff,
                    prekeys=prekeys)
    monkeypatch.setattr(MultiAggregator, "step_packed_all", step)


@pytest.mark.parametrize("workload", ["r9_replay", "pyramid_replay",
                                      "live_test", "late_test",
                                      "multi_late_test", "hot_ramp_test"])
def test_sound_run_is_correct(workload, bench_spec):
    out = _run(workload, spec=bench_spec)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["compiles_in_window"]["value"] == 0


def test_late_event_folded_past_the_watermark_is_not_correct(
        monkeypatch, bench_spec):
    """The fold keeping what the watermark drops (every cutoff lowered)
    reads not correct in the late cell."""
    from heatmap_tpu.engine.multi import MultiAggregator

    orig = MultiAggregator.step_packed_all

    def step(self, lat, lng, speed, ts, valid, cutoff, prekeys=None):
        return orig(self, lat, lng, speed, ts, valid,
                    max(int(cutoff) - 10_000, -2**31), prekeys=prekeys)
    monkeypatch.setattr(MultiAggregator, "step_packed_all", step)
    out = _run("late_test", spec=bench_spec)
    assert not out["correct"]
    assert out["checks"]["events_gap"]["value"] > 0


def test_step_leaving_state_unchanged_is_not_correct(monkeypatch):
    _patch_valid(monkeypatch, lambda v: np.zeros_like(v))
    out = _run()
    assert not out["correct"]
    assert out["checks"]["events_gap"]["value"] > 0


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    def half(v):
        v[len(v) // 2:] = False
        return v
    _patch_valid(monkeypatch, half)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["events_gap"]["value"] > 0


def test_answer_altered_where_produced_is_not_correct(monkeypatch):
    from heatmap_tpu.sink import memory

    orig = memory.packed_tile_docs

    def altered(body, meta):
        docs = orig(body, meta)
        if docs:
            docs[0]["count"] += 1
        return docs
    monkeypatch.setattr(memory, "packed_tile_docs", altered)
    out = _run()
    assert not out["correct"]


def test_position_altered_is_not_correct(monkeypatch):
    from heatmap_tpu.sink.memory import MemoryStore

    orig = MemoryStore.upsert_positions

    def altered(self, docs):
        docs = [dict(d) for d in docs]
        if docs:
            lng, lat = docs[0]["loc"]["coordinates"]
            docs[0]["loc"] = {"type": "Point", "coordinates": [lng, lat + 1.0]}
        return orig(self, docs)
    monkeypatch.setattr(MemoryStore, "upsert_positions", altered)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["positions_gap"]["value"] > 0
