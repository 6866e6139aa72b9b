"""The step's device time by the program's named scopes
(``benchmark/xplane.py``, ``benchmark/scopes.py``) and the four readers
built on them and on the program's spans: on hand-made traces, readings
and reductions, and on the trace recorded on one v5e and committed
beside this file (a program that predates the scopes: its stages show
as the jitted functions they call)."""

import gzip
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark import scopes, trace as tr, xplane
from benchmark.harness import Readings

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).with_name("data")
MS = 1_000_000


def _reader(name):
    path = ROOT / "benchmark" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"_m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The committed chip trace, laid out as a traced harness run leaves
    it: ``<run dir>/trace/…/*.xplane.pb`` beside ``<run dir>/ckpt``."""
    files = sorted(DATA.glob("*.xplane.pb.gz"))
    if not files:
        pytest.skip("no recorded trace")
    run_dir = tmp_path_factory.mktemp("run")
    (run_dir / "trace" / "plugins").mkdir(parents=True)
    path = run_dir / "trace" / "plugins" / "t.xplane.pb"
    path.write_bytes(gzip.decompress(files[0].read_bytes()))
    return run_dir, str(path)


@pytest.fixture(scope="module")
def recorded_scopes(recorded):
    return scopes.reduce_file(recorded[1])


def test_scope_path_strips_step_primitive_and_control_flow():
    op = ("jit(_step)/merge_r9_w5/jit(_merge_fastpath)/cond/branch_0_fun/"
          "cond/branch_1_fun/tier_insert/jit(_where)/select_n:")
    assert scopes.scope_path(op) == (
        "merge_r9_w5", "jit(_merge_fastpath)", "tier_insert", "jit(_where)")
    assert scopes.scope_path("jit(_step_pre)/snap_r7/select_n:") == (
        "snap_r7",)
    assert scopes.scope_path("jit(_step)/add:") == ()
    assert scopes.scope_path("reduce_window_sum:") is None
    assert scopes.scope_path("jit(concatenate)/concatenate:") is None


def test_reduce_hand_made_scopes():
    meta = {"/device:TPU:0": {
        "cond": xplane.OpMeta("jit(_step)/merge_r9_w5/jit(_mf)/cond:",
                              "conditional"),
        "snap": xplane.OpMeta("jit(_step)/snap_r9/jit(ll)/mul:", "fusion"),
        "ins": xplane.OpMeta(
            "jit(_step)/merge_r9_w5/jit(_mf)/cond/branch_0_fun/cond/"
            "branch_1_fun/tier_insert/gather:", "fusion"),
        "probe": xplane.OpMeta("jit(_step)/merge_r9_w5/jit(_mf)/probe/x:",
                               "fusion"),
        "copy": xplane.OpMeta("", "copy-start"),
        "other": xplane.OpMeta("jit(concatenate)/concatenate:", "fusion"),
    }}
    dev = tr.DeviceTrace(
        ops=[("snap", 0, 1 * MS), ("cond", 1 * MS, 6 * MS),
             ("probe", 1 * MS, 2 * MS), ("ins", 3 * MS, 3 * MS),
             ("copy", 7 * MS, 1 * MS),
             ("other", 12 * MS, 2 * MS),          # outside any step run
             ("snap", 20 * MS, 1 * MS), ("ins", 21 * MS, 1 * MS),
             ("snap", 40 * MS, 1 * MS)],          # run outside the window
        modules=[("jit__step(1)", 0, 9 * MS),
                 ("jit_concatenate(2)", 12 * MS, 2 * MS),
                 ("jit__step_pre(3)", 20 * MS, 2 * MS),
                 ("jit__step(1)", 40 * MS, 1 * MS)])
    got = scopes.reduce({"/device:TPU:0": dev}, meta, 0, 30 * MS)
    assert got["steps"] == 2
    assert got["step_device_s"] == pytest.approx(0.011)
    # the conditional holds probe and insert: left out, not counted twice
    assert got["leaf_s"] == pytest.approx(0.009)
    assert got["unscoped_s"] == pytest.approx(0.001)
    assert got["scopes"] == {"merge_r9_w5": pytest.approx(0.006),
                             "snap_r9": pytest.approx(0.002)}
    assert got["scope_paths"] == {
        "merge_r9_w5/jit(_mf)/probe": pytest.approx(0.002),
        "merge_r9_w5/jit(_mf)/tier_insert": pytest.approx(0.004),
        "snap_r9/jit(ll)": pytest.approx(0.002)}
    assert scopes.device_ms_per_step(got, "snap_") == pytest.approx(1.0)
    assert scopes.device_ms_per_step(got, "merge_") == pytest.approx(3.0)
    assert scopes.device_ms_per_step(got, "pack") is None
    assert scopes.device_ms_per_step({}, "snap_") is None


def test_idle_gaps_named_by_program_spans():
    dev = tr.DeviceTrace(ops=[("a", 0, 4 * MS), ("b", 10 * MS, 2 * MS)])
    host = [("bench.step_once", 0, 20 * MS),
            ("heatmap.prefetch", 3 * MS, 6 * MS),
            ("heatmap.poll", 5 * MS, 3 * MS),
            ("heatmap.checkpoint", 12 * MS, 8 * MS)]
    got = tr.reduce({"/device:TPU:0": dev}, host, 0, 20 * MS)["idle_gaps"]
    assert got == [["heatmap.checkpoint", pytest.approx(0.008)],
                   ["heatmap.poll", pytest.approx(0.006)]]


def test_recorded_trace_metadata(recorded):
    meta = xplane.read(recorded[1])
    assert list(meta) == ["/device:TPU:0"]
    ops = meta["/device:TPU:0"]
    cats = {m.category for m in ops.values()}
    assert "conditional" in cats
    assert any(m.tf_op.startswith("jit(_step)/") for m in ops.values())


def test_recorded_trace_by_scope(recorded, recorded_scopes):
    """Leaf ops add up to the step's module time; the merges hold 94-95%
    of it and the snap 2-3% (three resolutions, 2^17 events a batch)."""
    got = recorded_scopes
    step = got["step_device_s"]
    assert got["steps"] == 3
    assert abs(got["leaf_s"] - step) <= 0.001 * step
    assert 0.94 <= got["scopes"]["jit(_merge_fastpath)"] / step <= 0.95
    assert 0.02 <= got["scopes"]["jit(latlng_to_cell_vec)"] / step <= 0.03
    assert got["unscoped_s"] <= 0.05 * step
    assert sum(got["scopes"].values()) + got["unscoped_s"] == \
        pytest.approx(got["leaf_s"])
    # the same step time the existing reduction reads
    devices, host = tr.load(recorded[1])
    window = [(s, s + d) for n, s, d in host if n == "bench.window"][0]
    assert tr.reduce(devices, host, *window)["step_device_s"] == \
        pytest.approx(step)


def test_recorded_trace_existing_reduction_unchanged(recorded):
    devices, host = tr.load(recorded[1])
    window = [(s, s + d) for n, s, d in host if n == "bench.window"][0]
    got = tr.reduce(devices, host, *window)
    assert sorted(got) == ["busy_s", "device_ops", "idle_gaps",
                           "step_device_s", "steps", "window_s"]
    assert got["busy_s"] == pytest.approx(1.567670623)
    assert got["window_s"] == pytest.approx(1.624245877)
    assert got["step_device_s"] == pytest.approx(1.567404994)
    assert got["steps"] == 3
    assert got["device_ops"][0] == ["%conditional.5 (conditional)",
                                    pytest.approx(0.395710865)]
    assert got["idle_gaps"][0] == ["bench.step_once",
                                   pytest.approx(0.035277284)]


def _run(run_dir=None, spans=None, batches=10, trace=None):
    rt = None
    if run_dir is not None:
        rt = SimpleNamespace(cfg=SimpleNamespace(
            checkpoint_dir=str(run_dir / "ckpt")))
    return Readings(spans=spans or {}, batches=batches, lineage=[],
                    counters={}, trace=trace, peaks=None, rt=rt)


def test_scope_readers_on_recorded_run(recorded, recorded_scopes):
    """The recorded program has no snap_/merge_ scopes: both readers
    give nothing, as they do on a program without them."""
    run = _run(recorded[0], trace={"steps": 3})
    assert scopes.for_run(run)["scopes"] == recorded_scopes["scopes"]
    assert _reader("snap_device_ms")(run) is None
    assert _reader("merge_device_ms")(run) is None


def test_scope_readers_without_a_trace(tmp_path):
    assert _reader("snap_device_ms")(_run(tmp_path)) is None
    assert _reader("merge_device_ms")(_run(tmp_path, trace={"x": 1})) is None
    assert scopes.for_run(_run(None, trace={"steps": 1})) is None


def test_scope_readers_on_hand_made_reduction(tmp_path, monkeypatch):
    red = {"steps": 4, "scopes": {"snap_r7": 0.004, "snap_r9": 0.008,
                                  "merge_r7_w5": 0.2, "merge_r9_w5": 0.4,
                                  "pack": 0.01}}
    monkeypatch.setattr(scopes, "for_run", lambda run: red)
    run = _run(tmp_path, trace={"steps": 4})
    assert _reader("snap_device_ms")(run) == pytest.approx(3.0)
    assert _reader("merge_device_ms")(run) == pytest.approx(150.0)


def test_span_readers_on_hand_made_readings():
    spans = {"checkpoint": 0.5, "sink_tiles": 2.0, "sink_positions": 0.5,
             "sink_view": 1.0, "pull": 9.0}
    run = _run(spans=spans, batches=10)
    assert _reader("checkpoint_ms")(run) == pytest.approx(50.0)
    assert _reader("sink_apply_ms")(run) == pytest.approx(350.0)
    # a window with no checkpoint reads 0, a program without the span
    # reads nothing
    assert _reader("checkpoint_ms")(
        _run(spans={"checkpoint": 0.0})) == 0.0
    older = _run(spans={"pull": 9.0}, batches=10)
    assert _reader("checkpoint_ms")(older) is None
    assert _reader("sink_apply_ms")(older) is None
    assert _reader("checkpoint_ms")(_run(spans=spans, batches=0)) is None
