"""bench.py's `_run_config` — the function every headline/autotune/
insurance measurement runs through — must work at tiny shapes for each
snap impl and for the fused multi-pair pipelines (smoke: the round-end
artifact depends on this path)."""

import importlib.util
import os

import pytest

_spec = importlib.util.spec_from_file_location(
    "bench_under_test",
    os.path.join(os.path.dirname(__file__), os.pardir, "bench.py"))
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


def _native_available():
    from heatmap_tpu.hexgrid import native_snap

    return native_snap.available()


@pytest.mark.parametrize("h3", [
    "xla",
    pytest.param("native", marks=pytest.mark.skipif(
        not _native_available(), reason="no C++ toolchain")),
])
def test_run_config_small(h3):
    flat = bench._gen_capture(bench._required_events(4096, 1024, 2), 1024)
    eps, info = bench._run_config(
        flat, res=8, cap=1 << 12, bins=8, emit_cap=1024, batch=1024,
        chunk=2, merge_impl="rank", n_events=4096, h3_impl=h3, pull="full")
    assert eps > 0
    assert info["state_overflow"] == 0
    assert info["emitted_rows"] > 0
    assert info["n_active"] > 0
    # roofline floor model: slab dominates at this shape — 2 slabs of
    # (16 + 8 bins)*4 B rows per batch of 1024 events, plus the 16 B
    # feed (native adds 8 B/event of prekeys)
    exp = (2 * (1 << 12) * (12 + 4 + 8) * 4
           + 1024 * (16 + (8 if h3 == "native" else 0))) / 1024
    assert info["modeled_bytes_per_event"] == pytest.approx(exp)
    assert info["hbm_gbps_achieved"] > 0


@pytest.mark.skipif(not _native_available(), reason="no C++ toolchain")
def test_run_config_multi_pair_native():
    """The fused hex-pyramid shape (BASELINE #4) through the prekeys
    path: every unique res pre-snapped on the host."""
    pairs = [(7, 300), (8, 300), (9, 300)]
    flat = bench._gen_capture(bench._required_events(4096, 1024, 2), 1024)
    eps, info = bench._run_config(
        flat, res=8, cap=1 << 12, bins=8, emit_cap=1024, batch=1024,
        chunk=2, merge_impl="sort", n_events=4096, h3_impl="native",
        pull="full", pairs=pairs)
    assert eps > 0
    assert info["state_overflow"] == 0


def test_ref_cpu_baseline_attach(tmp_path, monkeypatch):
    """vs_cpu_reference = headline / banked reenactment rate; absent or
    degenerate bank files attach nothing."""
    import json

    path = tmp_path / "REF_CPU_BASELINE.json"
    monkeypatch.setattr(bench, "_ref_baseline_path", lambda: str(path))
    assert bench._ref_cpu_baseline_attach(1e6) == {}
    path.write_text(json.dumps({"ref_cpu_events_per_sec": 12500.0,
                                "note": "n", "measured_at": "t"}))
    got = bench._ref_cpu_baseline_attach(2.5e6)
    assert got["vs_cpu_reference"] == 200.0
    assert got["ref_cpu_events_per_sec"] == 12500.0
    path.write_text(json.dumps({"ref_cpu_events_per_sec": 0}))
    assert bench._ref_cpu_baseline_attach(1e6) == {}


def test_main_refuses_a_cpu_backend(capsys):
    """A measurement path that finds no TPU exits; it never measures the
    CPU under a device's name."""
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert "measures the TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""  # no result line


def test_native_request_without_toolchain_exits(monkeypatch):
    from heatmap_tpu.hexgrid import native_snap

    monkeypatch.setenv("HEATMAP_H3_IMPL", "native")
    monkeypatch.setattr(native_snap, "available", lambda: False)
    with pytest.raises(SystemExit):
        bench._resolve_h3_env()
    monkeypatch.setenv("HEATMAP_H3_IMPL", "xla")
    assert bench._resolve_h3_env() == "xla"
