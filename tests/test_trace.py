"""obs/spans.py's span helper (histogram sums, ``heatmap.<name>``
profiler annotations, thread-safe off-step observations) and
stream/trace.py's ProfilerTracer (window accounting, env handling,
partial-capture flush, double-stop safety, runtime re-arming)."""

import sys
import threading

import pytest

from heatmap_tpu.obs import spans as spans_mod
from heatmap_tpu.obs.spans import span
from heatmap_tpu.stream.metrics import OFF_STEP_SPANS, Metrics
from heatmap_tpu.stream.trace import ProfilerTracer


class FakeProfiler:
    """Stands in for jax.profiler: records start/stop calls."""

    def __init__(self, start_raises=None):
        self.starts = []
        self.stops = 0
        self._start_raises = start_raises

        # (event, annotation name) in order: "enter" / "exit" of each
        # TraceAnnotation, "step" for each StepTraceAnnotation
        self.events = []
        fp = self

        class TraceAnnotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                fp.events.append(("enter", self.name))
                return self

            def __exit__(self, *exc):
                fp.events.append(("exit", self.name))
                return False

        class StepTraceAnnotation(TraceAnnotation):
            def __init__(self, name, step_num=0):
                super().__init__(name)
                self.step_num = step_num

            def __enter__(self):
                fp.events.append(("step", self.name, self.step_num))
                return self

        self.TraceAnnotation = TraceAnnotation
        self.StepTraceAnnotation = StepTraceAnnotation

    def start_trace(self, d):
        if self._start_raises:
            raise self._start_raises
        self.starts.append(d)

    def stop_trace(self):
        self.stops += 1


@pytest.fixture()
def fake(monkeypatch):
    import jax

    fp = FakeProfiler()
    monkeypatch.setattr(jax, "profiler", fp)
    return fp


def _run_batches(tr, n, start=0):
    for epoch in range(start, start + n):
        with tr.batch(epoch):
            pass


class FakeClock:
    """Stands in for time.monotonic: each read advances by ``step``."""

    def __init__(self, step=0.25):
        self.t, self.step = 100.0, step

    def __call__(self):
        self.t += self.step
        return self.t


@pytest.fixture()
def clock(monkeypatch):
    c = FakeClock()
    monkeypatch.setattr(spans_mod.time, "monotonic", c)
    return c


def test_span_times_into_batch_dict_inside_annotation(fake, clock):
    spans = {}
    with span("pad", spans) as sp:
        fake.events.append(("body",))
    # one clock read on entry, one on exit: exactly the pair it replaces
    assert spans == {"pad": 0.25} and sp.seconds == 0.25
    assert fake.events == [("enter", "heatmap.pad"), ("body",),
                           ("exit", "heatmap.pad")]


def test_span_adds_to_a_name_already_timed_and_nests(fake, clock):
    spans = {"poll_fetch": 1.0}
    with span("poll", spans):
        with span("shard_filter", spans):
            pass
    with span("poll", spans):
        pass
    assert spans == {"poll_fetch": 1.0, "shard_filter": 0.25,
                     "poll": 0.75 + 0.25}
    names = [e[1] for e in fake.events]
    assert names == ["heatmap.poll", "heatmap.shard_filter",
                     "heatmap.shard_filter", "heatmap.poll",
                     "heatmap.poll", "heatmap.poll"]


def test_span_closes_annotation_and_records_on_exception(fake, clock):
    spans = {}
    with pytest.raises(RuntimeError):
        with span("device", spans):
            raise RuntimeError("boom")
    assert spans == {"device": 0.25}
    assert fake.events[-1] == ("exit", "heatmap.device")


def test_span_without_sink_only_annotates(fake, clock):
    with span("pull") as sp:
        pass
    assert sp.seconds == 0.25
    assert fake.events == [("enter", "heatmap.pull"),
                           ("exit", "heatmap.pull")]


def test_batch_spans_write_the_same_histogram_sums(fake, clock):
    """Spans timed by the helper reach heatmap_batch_span_seconds through
    observe_batch exactly as the dict values say; total is the step's
    own latency, not a sum of spans."""
    m = Metrics()
    for _ in range(3):
        spans = {}
        for name in ("poll", "pad", "transfer", "device"):
            with span(name, spans):
                pass
        m.observe_batch(2.0, spans)
    for name in ("poll", "pad", "transfer", "device"):
        h = m.spans[name]
        assert h.count == 3 and h.sum == pytest.approx(0.75)
    assert m.spans["total"].count == 3
    assert m.spans["total"].sum == pytest.approx(6.0)


def test_observe_span_outside_total(fake, clock):
    m = Metrics()
    assert set(OFF_STEP_SPANS) <= set(m.spans)   # children exist up front
    with span("checkpoint", m):
        pass
    assert m.spans["checkpoint"].count == 1
    assert m.spans["checkpoint"].sum == pytest.approx(0.25)
    assert "total" not in m.spans and m.batch_latency.count == 0
    assert fake.events == [("enter", "heatmap.checkpoint"),
                           ("exit", "heatmap.checkpoint")]


def test_observe_span_from_eight_threads_loses_nothing():
    """Eight threads observe off-step spans, two of them the same one,
    while the step thread records batches: every observation lands."""
    m = Metrics()
    n, per = 8, 2000
    names = [OFF_STEP_SPANS[i % len(OFF_STEP_SPANS)] for i in range(n)]
    barrier = threading.Barrier(n + 1)

    def work(name):
        barrier.wait()
        for _ in range(per):
            m.observe_span(name, 0.001)

    ts = [threading.Thread(target=work, args=(nm,)) for nm in names]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)   # switch threads as often as possible
    try:
        [t.start() for t in ts]
        barrier.wait(timeout=60)
        for _ in range(per):
            m.observe_batch(0.01, {"device": 0.002})
        for t in ts:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(switch)
    for name in set(names):
        k = names.count(name)
        assert m.spans[name].count == k * per
        assert m.spans[name].sum == pytest.approx(0.001 * k * per)
    assert m.spans["total"].count == m.spans["device"].count == per


def test_step_marker_is_heatmap_step(fake):
    with spans_mod.step(7):
        pass
    assert fake.events[0] == ("step", "heatmap.step", 7)


def test_tracer_window_adds_no_step_annotation(fake, tmp_path):
    """The always-on heatmap.step marker replaced the window's own."""
    tr = ProfilerTracer(env={"HEATMAP_PROFILE_DIR": str(tmp_path),
                             "HEATMAP_PROFILE_SKIP": "0",
                             "HEATMAP_PROFILE_BATCHES": "2"})
    _run_batches(tr, 2)
    assert fake.starts == [str(tmp_path)] and fake.stops == 1
    assert fake.events == []


def test_disabled_without_dir_never_touches_profiler(fake):
    tr = ProfilerTracer(env={})
    _run_batches(tr, 5)
    tr.stop()
    assert fake.starts == [] and fake.stops == 0


def test_skip_and_batches_accounting(fake, tmp_path):
    """skip=2 batches untraced; the window spans exactly `batches`
    epochs and stops at its end (not one late, not one early)."""
    tr = ProfilerTracer(env={"HEATMAP_PROFILE_DIR": str(tmp_path),
                             "HEATMAP_PROFILE_SKIP": "2",
                             "HEATMAP_PROFILE_BATCHES": "3"})
    _run_batches(tr, 2)
    assert fake.starts == []          # still skipping
    _run_batches(tr, 1, start=2)
    assert fake.starts == [str(tmp_path)] and fake.stops == 0
    _run_batches(tr, 1, start=3)
    assert fake.stops == 0            # mid-window
    _run_batches(tr, 1, start=4)      # epoch 4 = 3rd traced batch
    assert fake.stops == 1
    assert not tr.busy
    _run_batches(tr, 5, start=5)      # window done: no re-start
    assert fake.starts == [str(tmp_path)] and fake.stops == 1


def test_bad_env_values_fall_back_to_defaults(fake, tmp_path):
    tr = ProfilerTracer(env={"HEATMAP_PROFILE_DIR": str(tmp_path),
                             "HEATMAP_PROFILE_SKIP": "banana",
                             "HEATMAP_PROFILE_BATCHES": "2"})
    # the ValueError aborts parsing; BOTH knobs keep their defaults
    assert tr.skip == 2 and tr.batches == 16


def test_negative_env_values_clamped(fake, tmp_path):
    tr = ProfilerTracer(env={"HEATMAP_PROFILE_DIR": str(tmp_path),
                             "HEATMAP_PROFILE_SKIP": "-5",
                             "HEATMAP_PROFILE_BATCHES": "0"})
    assert tr.skip == 0 and tr.batches == 1
    _run_batches(tr, 2)
    assert fake.starts == [str(tmp_path)]
    assert fake.stops == 1            # 1-batch window closed itself


def test_partial_capture_flushed_on_early_close(fake, tmp_path):
    """runtime.close() calls stop() mid-window: the partial trace must
    be written (stop_trace called), not dangle."""
    tr = ProfilerTracer(env={"HEATMAP_PROFILE_DIR": str(tmp_path),
                             "HEATMAP_PROFILE_SKIP": "0",
                             "HEATMAP_PROFILE_BATCHES": "100"})
    _run_batches(tr, 3)
    assert fake.starts and fake.stops == 0
    tr.stop()
    assert fake.stops == 1


def test_double_stop_safe(fake, tmp_path):
    tr = ProfilerTracer(env={"HEATMAP_PROFILE_DIR": str(tmp_path),
                             "HEATMAP_PROFILE_SKIP": "0"})
    _run_batches(tr, 1)
    tr.stop()
    tr.stop()
    assert fake.stops == 1
    # and stop() before any start is a no-op on the profiler
    tr2 = ProfilerTracer(env={"HEATMAP_PROFILE_DIR": str(tmp_path)})
    tr2.stop()
    assert fake.stops == 1


def test_exception_escaping_batch_flushes(fake, tmp_path):
    tr = ProfilerTracer(env={"HEATMAP_PROFILE_DIR": str(tmp_path),
                             "HEATMAP_PROFILE_SKIP": "0",
                             "HEATMAP_PROFILE_BATCHES": "50"})
    with pytest.raises(RuntimeError):
        with tr.batch(0):
            raise RuntimeError("boom")
    assert fake.stops == 1            # dangling trace would block re-arm
    assert not tr.busy


def test_start_failure_disables_window(fake, monkeypatch, tmp_path):
    import jax

    fp = FakeProfiler(start_raises=RuntimeError("unsupported"))
    monkeypatch.setattr(jax, "profiler", fp)
    tr = ProfilerTracer(env={"HEATMAP_PROFILE_DIR": str(tmp_path),
                             "HEATMAP_PROFILE_SKIP": "0"})
    _run_batches(tr, 3)
    assert fp.stops == 0 and not tr.busy


def test_arm_runtime_window_and_busy_refusal(fake, tmp_path):
    """arm() opens a window relative to the CURRENT epoch; while it is
    pending or active a second arm refuses (the 409 contract)."""
    tr = ProfilerTracer(env={})
    assert not tr.busy
    assert tr.arm(str(tmp_path), batches=2, skip=1, base_epoch=10)
    assert tr.busy
    assert not tr.arm(str(tmp_path), batches=2)      # pending -> refuse
    _run_batches(tr, 1, start=10)                    # skip batch
    assert fake.starts == []
    _run_batches(tr, 1, start=11)                    # window starts
    assert fake.starts == [str(tmp_path)]
    assert not tr.arm(str(tmp_path), batches=2)      # active -> refuse
    _run_batches(tr, 1, start=12)                    # window ends
    assert fake.stops == 1 and not tr.busy
    # idle again: a SECOND window arms and captures
    assert tr.arm(str(tmp_path / "w2"), batches=1, base_epoch=13)
    _run_batches(tr, 1, start=13)
    assert fake.starts[-1] == str(tmp_path / "w2") and fake.stops == 2


def test_arm_rejects_empty_dir_and_clamps(fake, tmp_path):
    tr = ProfilerTracer(env={})
    assert not tr.arm("")
    assert tr.arm(str(tmp_path), batches=0, skip=-3, base_epoch=5)
    assert tr.batches == 1 and tr.skip == 5


def test_stop_cancels_pending_window(fake, tmp_path):
    tr = ProfilerTracer(env={})
    assert tr.arm(str(tmp_path), batches=4, skip=100, base_epoch=0)
    tr.stop()                         # cancelled before it ever started
    assert not tr.busy and fake.stops == 0
    _run_batches(tr, 200)
    assert fake.starts == []


def test_arm_is_thread_safe_single_winner(fake, tmp_path):
    """N racing arms admit exactly one window (the HTTP threads race
    the step thread for the state transition)."""
    tr = ProfilerTracer(env={})
    wins = []
    barrier = threading.Barrier(8)

    def try_arm(i):
        barrier.wait()
        if tr.arm(str(tmp_path / f"w{i}"), batches=1):
            wins.append(i)

    ts = [threading.Thread(target=try_arm, args=(i,)) for i in range(8)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert len(wins) == 1
