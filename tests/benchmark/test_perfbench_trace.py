"""The reduction from a profiler trace to busy time, step device time,
top ops and labelled idle gaps: on hand-made intervals, and on a small
trace recorded on one v5e and committed beside this file."""

import gzip
from pathlib import Path

import pytest

from benchmark import trace as tr

DATA = Path(__file__).with_name("data")


def test_union_merges_overlaps():
    assert tr.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [[0, 3], [5, 10]]


def test_reduce_hand_made_intervals():
    ms = 1_000_000
    dev = tr.DeviceTrace(
        ops=[("fusion.1", 0, 4 * ms), ("sort", 2 * ms, 4 * ms),
             ("copy", 10 * ms, 2 * ms), ("fusion.1", 15 * ms, 1 * ms)],
        modules=[("jit__step", 0, 6 * ms), ("jit_concatenate", 10 * ms, 2 * ms),
                 ("jit__step_pre", 15 * ms, 1 * ms)])
    host = [("bench.step_once", 5 * ms, 6 * ms),
            ("source.poll", 6 * ms, 3 * ms),
            ("bench.idle_sleep", 12 * ms, 3 * ms)]
    got = tr.reduce({"/device:TPU:0": dev}, host, 0, 20 * ms)
    assert got["window_s"] == pytest.approx(0.020)
    assert got["busy_s"] == pytest.approx(0.009)          # 0-6, 10-12, 15-16
    assert got["steps"] == 2
    assert got["step_device_s"] == pytest.approx(0.007)
    assert got["device_ops"][0] == ["fusion.1", pytest.approx(0.005)]
    # longest first, ties in time order, each named by the innermost
    # span open at its midpoint
    assert got["idle_gaps"] == [
        ["source.poll", pytest.approx(0.004)],        # 6-10
        ["none", pytest.approx(0.004)],               # 16-20
        ["bench.idle_sleep", pytest.approx(0.003)]]   # 12-15


def test_reduce_two_devices_takes_mean_busy_and_busiest_step():
    ms = 1_000_000
    a = tr.DeviceTrace(ops=[("x", 0, 8 * ms)], modules=[("jit__step", 0, 8 * ms)])
    b = tr.DeviceTrace(ops=[("x", 0, 2 * ms)], modules=[("jit__step", 0, 2 * ms)])
    got = tr.reduce({"/device:TPU:0": a, "/device:TPU:1": b}, [], 0, 10 * ms)
    assert got["busy_s"] == pytest.approx(0.005)
    assert got["step_device_s"] == pytest.approx(0.008)


def test_recorded_chip_trace(tmp_path):
    """A traced ``pyramid_replay`` run on one v5e (``--seconds 2 --trace
    1 --keep-trace``), gzipped."""
    files = sorted(DATA.glob("*.xplane.pb.gz"))
    if not files:
        pytest.skip("no recorded trace")
    path = tmp_path / "trace.xplane.pb"
    path.write_bytes(gzip.decompress(files[0].read_bytes()))
    devices, host = tr.load(str(path))
    assert devices and any(d.ops for d in devices.values())
    window = [(s, s + d) for n, s, d in host if n == "bench.window"]
    assert len(window) == 1
    got = tr.reduce(devices, host, *window[0])
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["steps"] > 0 and got["step_device_s"] > 0
    assert got["step_device_s"] <= got["busy_s"] * len(devices) + 1e-9
    assert len(got["device_ops"]) <= tr.TOP and len(got["idle_gaps"]) <= tr.TOP
    assert any(label != "none" for label, _ in got["idle_gaps"])
