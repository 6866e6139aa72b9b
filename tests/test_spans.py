"""The runtime's spans where their work runs: one ``checkpoint`` span per
checkpoint on the step thread, outside the step's ``total`` and its
``pull``; the commit thread's stages and ``checkpoint_bytes``; the sink
writer's store and view applies."""

import os
import subprocess
import sys
import time

import pytest

from heatmap_tpu.config import load_config
from heatmap_tpu.sink import AsyncWriter, MemoryStore
from heatmap_tpu.stream import MicroBatchRuntime, SyntheticSource
from heatmap_tpu.stream.metrics import Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STALL_S = 1.0


def test_checkpoint_span_once_per_checkpoint_outside_total_and_pull(
        tmp_path, monkeypatch):
    cfg = load_config({}, checkpoint_dir=str(tmp_path / "ckpt"),
                      batch_size=256, state_capacity_log2=11,
                      speed_hist_bins=8, store="memory", emit_flush_k=2)
    src = SyntheticSource(n_events=256 * 8, n_vehicles=50,
                          events_per_second=256)
    rt = MicroBatchRuntime(cfg, src, MemoryStore(), checkpoint_every=4)
    observed = []
    record = rt.metrics.observe_batch

    def spy(latency_s, spans):
        observed.append((latency_s, spans["pull"]))
        record(latency_s, spans)

    capture = rt._checkpoint_capture

    def stalled():
        time.sleep(STALL_S)   # a checkpoint far slower than any step
        capture()

    monkeypatch.setattr(rt.metrics, "observe_batch", spy)
    monkeypatch.setattr(rt, "_checkpoint_capture", stalled)
    for _ in range(8):
        assert rt.step_once()
    rt._ckpt_join()
    m = rt.metrics
    n = m.counters["checkpoints"]
    assert n == 2
    assert m.spans["checkpoint"].count == n
    assert m.spans["checkpoint"].sum >= n * STALL_S
    for stage in ("checkpoint_drain", "checkpoint_to_host",
                  "checkpoint_write"):
        assert m.spans[stage].count == n, stage
    # neither a step's total nor its pull holds the stall
    assert len(observed) == 8 == m.spans["total"].count
    # (the first batch compiles the fold)
    assert max(lat for lat, _ in observed[1:]) < STALL_S
    assert max(pull for _, pull in observed[1:]) < STALL_S
    assert sum(pull > 0 for _, pull in observed) >= 2   # the ring flushed
    state_bytes = sum(int(leaf.nbytes) for agg in rt.aggs.values()
                      for leaf in agg.snapshot())
    assert m.counters["checkpoint_bytes"] == n * state_bytes
    rt.close()


class _CountingStore:
    """Applies nothing; counts what the writer hands it."""

    def upsert_tiles(self, docs):
        return len(docs)

    def upsert_positions(self, docs):
        return len(docs)

    def flush(self):
        pass


class _View:
    poisoned = False
    seq = 0

    def apply_docs(self, docs):
        self.seq += 1

    def poison(self):
        self.poisoned = True


@pytest.mark.parametrize("with_metrics", [True, False])
def test_writer_spans_recorded(with_metrics):
    m = Metrics() if with_metrics else None
    view = _View()
    w = AsyncWriter(_CountingStore(), metrics=m, view=view)
    w.submit_tiles([{"_id": "a"}, {"_id": "b"}])
    w.submit_tiles([{"_id": "c"}])
    w.submit_positions([{"_id": "p"}])
    w.drain()
    w.close()
    assert w.counters["tiles_written"] == 3 and view.seq == 2
    if m is not None:
        assert m.spans["sink_tiles"].count == 2
        assert m.spans["sink_view"].count == 2
        assert m.spans["sink_positions"].count == 1
        assert "total" not in m.spans


def test_sink_times_its_writes_without_the_stream_layer():
    """The sink sits below the runtime: the span helper it times its
    writes with loads no module of ``heatmap_tpu.stream``."""
    code = ("import sys\n"
            "import heatmap_tpu.sink.writer\n"
            "print(sorted(m for m in sys.modules\n"
            "             if m.startswith('heatmap_tpu.stream')))\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=REPO, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "[]"
