"""hwbank: measured-winner ``auto`` defaults from an operator-supplied
bank (HEATMAP_HW_BANK).

These tests pin the reader's contract: platform gating, the snap
decision rule, fallback without a bank, and the engine/runtime wiring
points.  (The reference tunes the analogous knobs
by hand via Spark conf, /root/reference/heatmap_stream.py:241-249.)
"""
import json

import pytest

from heatmap_tpu import hwbank


_bank_seq = 0


def _write_bank(tmp_path, units: dict):
    # unique filename per call: hwbank.units() caches on (path, mtime)
    # and Linux mtime granularity is coarse enough that two writes to
    # the same path in one tick would serve the first bank's contents
    global _bank_seq
    _bank_seq += 1
    path = tmp_path / f"bank{_bank_seq}.json"
    path.write_text(json.dumps(
        {"units": {k: {"data": v, "ts": "t"} for k, v in units.items()},
         "attempts": {}, "log": []}))
    return str(path)


def _merge_units(winner, platform="cpu"):
    return {f"merge_{shape}": {"winner": winner, "_platform": platform}
            for shape in ("stream", "backfill", "balanced")}


@pytest.fixture(autouse=True)
def _isolated_bank(monkeypatch, tmp_path):
    """Default every test to an ABSENT bank (an operator's bank in the
    environment must not leak into assertions)."""
    monkeypatch.setenv("HEATMAP_HW_BANK", str(tmp_path / "absent.json"))


def test_no_bank_by_default(monkeypatch):
    """No bank ships with the checkout: without HEATMAP_HW_BANK every
    ``auto`` takes its static rule."""
    monkeypatch.delenv("HEATMAP_HW_BANK")
    assert hwbank._bank_path() == ""
    assert hwbank.units() == {}
    assert hwbank.merge_winner() is None
    assert hwbank.snap_winner() is None


def test_no_bank_file_means_no_winners():
    assert hwbank.units() == {}
    assert hwbank.merge_winner() is None
    assert hwbank.pull_winner() is None
    assert hwbank.snap_winner() is None


def test_empty_env_disables_bank(monkeypatch):
    monkeypatch.setenv("HEATMAP_HW_BANK", "")
    assert hwbank.units() == {}


def test_platform_gating_rejects_foreign_stamps(monkeypatch, tmp_path):
    # a bank harvested on TPU must never steer this CPU-backend process
    monkeypatch.setenv("HEATMAP_HW_BANK", _write_bank(
        tmp_path, _merge_units("sort", platform="tpu")))
    assert hwbank.merge_winner() is None


def test_device_kind_gating(monkeypatch, tmp_path):
    """A platform match is not enough when the entry names a device
    kind: one chip kind's winners must not steer another's."""
    units = _merge_units("sort")
    for u in units.values():
        u["_device_kind"] = "TPU v9 mega"  # not this host's device
    monkeypatch.setenv("HEATMAP_HW_BANK", _write_bank(tmp_path, units))
    assert hwbank.merge_winner() is None
    for u in units.values():
        u["_device_kind"] = hwbank._device_kind()  # live kind -> applies
    monkeypatch.setenv("HEATMAP_HW_BANK", _write_bank(tmp_path, units))
    assert hwbank.merge_winner() == "sort"


def test_merge_winner_unanimous(monkeypatch, tmp_path):
    monkeypatch.setenv("HEATMAP_HW_BANK",
                       _write_bank(tmp_path, _merge_units("sort")))
    assert hwbank.merge_winner() == "sort"


def test_merge_winner_split_or_partial_is_none(monkeypatch, tmp_path):
    units = _merge_units("sort")
    units["merge_stream"]["winner"] = "rank"
    monkeypatch.setenv("HEATMAP_HW_BANK", _write_bank(tmp_path, units))
    assert hwbank.merge_winner() is None
    del units["merge_stream"]
    monkeypatch.setenv("HEATMAP_HW_BANK", _write_bank(tmp_path, units))
    assert hwbank.merge_winner() is None


def test_pull_winner_majority(monkeypatch, tmp_path):
    rows = [{"live": 256, "winner": "full"},
            {"live": 4096, "winner": "full"},
            {"live": 32768, "winner": "prefix"}]
    monkeypatch.setenv("HEATMAP_HW_BANK", _write_bank(
        tmp_path, {"pull": {"rows": rows, "_platform": "cpu"}}))
    assert hwbank.pull_winner() == "full"
    rows[1]["winner"] = "prefix"
    monkeypatch.setenv("HEATMAP_HW_BANK", _write_bank(
        tmp_path, {"pull": {"rows": rows, "_platform": "cpu"}}))
    assert hwbank.pull_winner() == "prefix"
    # an even split is NOT a majority for full -> conservative prefix
    rows.append({"live": 65536, "winner": "full"})
    monkeypatch.setenv("HEATMAP_HW_BANK", _write_bank(
        tmp_path, {"pull": {"rows": rows, "_platform": "cpu"}}))
    assert hwbank.pull_winner() == "prefix"


def test_pull_winner_fused_ab_overrides_single_pair(monkeypatch, tmp_path):
    """n_pairs>1 consults the fused A/B units: the single-pair unit may
    say full wins while the 3-pair A/B says prefix — a full pull moves
    n_pairs whole emit buffers, so D2H bytes weigh more."""
    rows = [{"live": 256, "winner": "full"},
            {"live": 4096, "winner": "full"}]
    units = {"pull": {"rows": rows, "_platform": "cpu"},
             "hex_pyramid": {"events_per_sec": 83740.4,
                             "_platform": "cpu"},
             "hex_pyramid_prefix": {"events_per_sec": 281720.4,
                                    "_platform": "cpu"}}
    monkeypatch.setenv("HEATMAP_HW_BANK", _write_bank(tmp_path, units))
    assert hwbank.pull_winner() == "full"          # single-pair verdict
    assert hwbank.pull_winner(n_pairs=3) == "prefix"   # fused verdict
    # no fused A/B banked -> fused programs fall back to the
    # single-pair verdict rather than guessing
    monkeypatch.setenv("HEATMAP_HW_BANK", _write_bank(
        tmp_path, {"pull": {"rows": rows, "_platform": "cpu"}}))
    assert hwbank.pull_winner(n_pairs=3) == "full"
    # fused A/Bs vote; a split between the two fused shapes leans
    # prefix (the conservative: never move n_pairs full buffers on a
    # tie)
    units["multi_window"] = {"events_per_sec": 300000.0,
                             "_platform": "cpu"}
    units["multi_window_prefix"] = {"events_per_sec": 200000.0,
                                    "_platform": "cpu"}
    monkeypatch.setenv("HEATMAP_HW_BANK", _write_bank(tmp_path, units))
    assert hwbank.pull_winner(n_pairs=3) == "prefix"


def test_snap_winner_decision_rule(monkeypatch, tmp_path):
    good = {"lowering": "ok", "speedup_vs_xla": 2.64,
            "agree_frac": 0.999919, "_platform": "cpu"}
    monkeypatch.setenv("HEATMAP_HW_BANK",
                       _write_bank(tmp_path, {"snap_pal_r8": good}))
    assert hwbank.snap_winner() == "pallas"
    for breaker in ({"lowering": "FAILED"}, {"speedup_vs_xla": 0.9},
                    {"agree_frac": 0.99}):
        monkeypatch.setenv("HEATMAP_HW_BANK", _write_bank(
            tmp_path, {"snap_pal_r8": {**good, **breaker}}))
        assert hwbank.snap_winner() is None, breaker


def test_bank_reload_on_mtime_change(monkeypatch, tmp_path):
    import os
    import time

    # deliberately rewrite the SAME path (this test pins the
    # mtime-triggered reload; _write_bank's unique names would dodge it)
    def write_same(units):
        (tmp_path / "reload.json").write_text(json.dumps(
            {"units": {k: {"data": v, "ts": "t"} for k, v in units.items()},
             "attempts": {}, "log": []}))
        return str(tmp_path / "reload.json")

    path = write_same(_merge_units("sort"))
    monkeypatch.setenv("HEATMAP_HW_BANK", path)
    assert hwbank.merge_winner() == "sort"
    write_same(_merge_units("probe"))
    # same-second rewrites can share an mtime; force it forward
    os.utime(path, (time.time() + 2, time.time() + 2))
    assert hwbank.merge_winner() == "probe"


def test_corrupt_bank_is_ignored(monkeypatch, tmp_path):
    path = tmp_path / "bank.json"
    path.write_text("{not json")
    monkeypatch.setenv("HEATMAP_HW_BANK", str(path))
    assert hwbank.units() == {}
    assert hwbank.merge_winner() is None


def test_engine_auto_merge_consults_bank(monkeypatch, tmp_path):
    """merge_batch's `auto` takes the unanimous banked winner over the
    capacity-ratio heuristic (and the results stay bit-identical because
    every merge impl is)."""
    from heatmap_tpu.engine import step as engine_step

    monkeypatch.setenv("HEATMAP_HW_BANK",
                       _write_bank(tmp_path, _merge_units("probe")))
    # capacity >= 4x batch would pick "rank" statically; the bank must
    # override.  Resolution is observable via hwbank directly plus the
    # impl actually routed — probe leaves a distinct trace: patch the
    # impl table entry and observe it being selected.
    called = {}
    real = engine_step._merge_probe

    def spy(*a, **k):
        called["probe"] = True
        return real(*a, **k)

    monkeypatch.setattr(engine_step, "_merge_probe", spy)
    monkeypatch.setattr(engine_step, "MERGE_IMPL", None)
    monkeypatch.delenv("HEATMAP_MERGE_IMPL", raising=False)
    # fastpath would bypass the slow impl table on steady batches; force
    # the plain route so the spy sees the dispatch
    monkeypatch.setattr(engine_step, "_resolve_fastpath", lambda: False)

    import numpy as np

    from heatmap_tpu.engine.state import init_state
    from heatmap_tpu.engine.step import AggParams, merge_batch

    params = AggParams(res=8, window_s=300, emit_capacity=64)
    state = init_state(256, hist_bins=0)  # 256 >= 4 * 64 -> static "rank"
    n = 64
    hi = np.full(n, 1, np.uint32)
    lo = (np.arange(n, dtype=np.int64) % 7).astype(np.uint32)
    ws = np.full(n, 300, np.int32)
    f = np.ones(n, np.float32)
    ts = np.full(n, 300, np.int32)
    valid = np.ones(n, bool)
    merge_batch(state, hi, lo, ws, f, f, f, ts, valid,
                np.int32(-2**31), params)
    assert called.get("probe"), "banked winner was not routed"


def test_merge_bank_pin_overrides_live_consult(monkeypatch, tmp_path):
    """A frozen MERGE_BANK_PIN of None (a no-bank runtime snapshot) sends
    `auto` to the static rule even with a valid live bank present —
    merge_batch must not re-read the file once a runtime pinned it."""
    from heatmap_tpu.engine import step as engine_step

    monkeypatch.setenv("HEATMAP_HW_BANK",
                       _write_bank(tmp_path, _merge_units("probe")))
    assert hwbank.merge_winner() == "probe"
    monkeypatch.setattr(engine_step, "MERGE_BANK_PIN", None)
    called = {}
    real = engine_step._merge_probe

    def spy(*a, **k):
        called["probe"] = True
        return real(*a, **k)

    monkeypatch.setattr(engine_step, "_merge_probe", spy)
    monkeypatch.setattr(engine_step, "MERGE_IMPL", None)
    monkeypatch.delenv("HEATMAP_MERGE_IMPL", raising=False)
    monkeypatch.setattr(engine_step, "_resolve_fastpath", lambda: False)

    import numpy as np

    from heatmap_tpu.engine.state import init_state
    from heatmap_tpu.engine.step import AggParams, merge_batch

    params = AggParams(res=8, window_s=300, emit_capacity=64)
    state = init_state(256, hist_bins=0)
    n = 64
    hi = np.full(n, 1, np.uint32)
    lo = (np.arange(n, dtype=np.int64) % 7).astype(np.uint32)
    ws = np.full(n, 300, np.int32)
    f = np.ones(n, np.float32)
    ts = np.full(n, 300, np.int32)
    valid = np.ones(n, bool)
    merge_batch(state, hi, lo, ws, f, f, f, ts, valid,
                np.int32(-2**31), params)
    assert "probe" not in called, (
        "gated-off bank still routed the banked winner")


def test_runtime_close_restores_engine_globals(monkeypatch, tmp_path):
    """A finished runtime must hand standalone merge_batch/bench callers
    the documented live-bank consult back: run() freezes SNAP_IMPL and
    MERGE_BANK_PIN at init, close() restores them (r5 review — the leak
    made later same-process callers inherit the runtime's snapshot)."""
    import tempfile
    import time as _t

    import numpy as np

    from heatmap_tpu.config import load_config
    from heatmap_tpu.engine import step as engine_step
    from heatmap_tpu.sink import MemoryStore
    from heatmap_tpu.stream import MemorySource, MicroBatchRuntime

    monkeypatch.setenv("HEATMAP_HW_BANK",
                       _write_bank(tmp_path, _merge_units("sort")))
    t0 = int(_t.time()) - 60
    evs = [{"provider": "p", "vehicleId": f"v{i}", "lat": 42.0,
            "lon": -71.0, "speedKmh": 1.0, "bearing": 0.0,
            "accuracyM": 1.0, "ts": t0} for i in range(64)]
    cfg = load_config({}, batch_size=32, state_capacity_log2=8,
                      speed_hist_bins=4, store="memory",
                      checkpoint_dir=tempfile.mkdtemp())
    src = MemorySource(evs)
    src.finish()
    rt = MicroBatchRuntime(cfg, src, MemoryStore(), checkpoint_every=2)
    # init froze the knobs
    assert engine_step.MERGE_BANK_PIN == "sort"
    assert engine_step.SNAP_IMPL is not None
    rt.run()
    assert engine_step.MERGE_BANK_PIN is engine_step._BANK_LIVE
    assert engine_step.SNAP_IMPL is None


def test_inprogram_snap_name_pins_and_refuses(monkeypatch, tmp_path):
    """SNAP_IMPL slot wins over env/bank; a pallas policy on a backend
    where the kernel cannot run (CPU) raises instead of becoming xla."""
    from heatmap_tpu.engine import step as engine_step

    monkeypatch.setattr(engine_step, "SNAP_IMPL", None)
    monkeypatch.delenv("HEATMAP_H3_IMPL", raising=False)
    assert engine_step.inprogram_snap_name(8) == "xla"
    # bank says pallas (cpu-stamped to pass gating) — on the CPU backend
    # the Mosaic kernel cannot run, so resolving the name must fail
    monkeypatch.setenv("HEATMAP_HW_BANK", _write_bank(
        tmp_path, {"snap_pal_r8": {"lowering": "ok",
                                   "speedup_vs_xla": 2.6,
                                   "agree_frac": 0.9999,
                                   "_platform": "cpu"}}))
    assert hwbank.snap_winner() == "pallas"
    with pytest.raises(RuntimeError, match="Pallas"):
        engine_step.inprogram_snap_name(8)
    # res > 10 is outside the kernel's range: the policy's per-res rule
    # picks xla there, deterministically
    assert engine_step.inprogram_snap_name(11) == "xla"
    monkeypatch.setattr(engine_step, "SNAP_IMPL", "xla")
    assert engine_step.inprogram_snap_name(8) == "xla"
