"""Adaptive micro-batching governor (stream/govern.py, ISSUE 10).

Three layers:

- unit: the AIMD control law driven by scripted observations (ages fed
  into a real obs histogram, fill/idle via the note_* API, a fake
  clock) — bucket-ladder walking in both directions, hysteresis, the
  memory and growth-pressure guardrails, the retrace freeze;
- integration: a REAL governed runtime — ladder warmup compiles every
  bucket (zero post-warmup retraces across forced bucket cycling), the
  governed run over a fixed exact-arithmetic corpus is BYTE-IDENTICAL
  to the ungoverned run, /healthz degrades naming the latched bucket;
- chaos: a 100x offered-load swing against a real backlog queue
  (stream.RampSource) under an accelerated virtual clock — the
  governor climbs the ladder under saturation and the event-age p50
  re-enters the SLO within a bounded number of intervals, with zero
  post-warmup retraces.
"""

import tempfile
import threading
import time

import numpy as np
import pytest

from heatmap_tpu.config import load_config
from heatmap_tpu.obs.registry import Registry
from heatmap_tpu.stream.govern import BatchGovernor, bucket_ladder


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def tick(self, dt=10.0):
        self.t += dt
        return self.t


def mk_gov(batch=1024, min_batch=128, flush_k=8, prefetch=1,
           interval=1.0, tracker=None, memory=None, **cfg_over):
    cfg = load_config({}, batch_size=batch, govern=True,
                      govern_min_batch=min_batch, emit_flush_k=flush_k,
                      prefetch_batches=prefetch,
                      govern_interval_s=interval, **cfg_over)
    reg = Registry()
    clock = FakeClock()
    age = reg.histogram("test_event_age_seconds", "test ages")
    gov = BatchGovernor(cfg, reg, event_age=age,
                        compile_tracker=tracker, memory=memory,
                        clock=clock)
    return gov, age, clock, reg


def drive(gov, age, clock, *, age_s, rows, disp, idles=0):
    """One observed interval -> one control step."""
    for a in ([age_s] if isinstance(age_s, (int, float)) else age_s):
        age.observe(a)
    for _ in range(disp):
        gov.note_dispatch(rows // max(1, disp))
    for _ in range(idles):
        gov.note_idle()
    clock.tick(gov.interval_s + 0.01)
    return gov.decide()


# --------------------------------------------------------------- ladder
def test_bucket_ladder_shapes():
    assert bucket_ladder(1 << 17, 4096) == [4096, 8192, 16384, 32768,
                                            65536, 1 << 17]
    # non-power-of-two top rides as its own bucket
    assert bucket_ladder(100_000, 16384) == [16384, 32768, 65536,
                                             100_000]
    # min rounded up to a power of two
    assert bucket_ladder(1024, 100) == [128, 256, 512, 1024]
    # degenerate: floor at/above the ceiling = the single static shape
    assert bucket_ladder(256, 256) == [256]
    assert bucket_ladder(256, 4096) == [256]


def test_config_validation():
    with pytest.raises(ValueError):
        load_config({"HEATMAP_GOVERN_INTERVAL_S": "0"})
    with pytest.raises(ValueError):
        load_config({"HEATMAP_GOVERN_MIN_BATCH": "8"})
    with pytest.raises(ValueError):
        load_config({"HEATMAP_GOVERN": "1",
                     "HEATMAP_GOVERN_MIN_BATCH": "999999999"})
    with pytest.raises(ValueError):
        load_config({"HEATMAP_GOVERN_MAX_FLUSH_K": "0"})
    with pytest.raises(ValueError):
        load_config({"HEATMAP_GOVERN_MAX_PREFETCH": "99"})
    with pytest.raises(ValueError):
        load_config({"HEATMAP_GOVERN_HEALTHY_FRAC": "1.5"})
    # the kill switch: govern defaults OFF
    assert load_config({}).govern is False
    assert load_config({"HEATMAP_GOVERN": "1"}).govern is True


# ---------------------------------------------------------- control law
def test_static_knobs_become_initial_values():
    gov, _age, _clock, _ = mk_gov(batch=1024, flush_k=4, prefetch=2)
    assert gov.batch_rows == 1024          # top of the ladder
    assert gov.flush_k == 4
    assert gov.prefetch == 2


def test_breach_backs_flush_k_off_multiplicatively(monkeypatch):
    monkeypatch.setenv("HEATMAP_SLO_FRESHNESS_P50_MS", "1000")
    gov, age, clock, _ = mk_gov(flush_k=8)
    # underfilled trickle over the SLO: flush-K halves per interval,
    # bucket untouched while flush-K still has room
    assert drive(gov, age, clock, age_s=5.0, rows=600, disp=1)
    assert (gov.flush_k, gov.batch_rows) == (4, 1024)
    assert drive(gov, age, clock, age_s=5.0, rows=600, disp=1)
    assert gov.flush_k == 2
    assert drive(gov, age, clock, age_s=5.0, rows=600, disp=1)
    assert gov.flush_k == 1
    # flush-K exhausted + low fill: now the bucket steps down
    assert drive(gov, age, clock, age_s=5.0, rows=100, disp=1)
    assert (gov.flush_k, gov.batch_rows) == (1, 512)
    trail = list(gov.trail)
    assert all(t["reason"] == "latency" and t["dir"] == "down"
               for t in trail)


def test_breach_while_saturated_grows_instead(monkeypatch):
    monkeypatch.setenv("HEATMAP_SLO_FRESHNESS_P50_MS", "1000")
    gov, age, clock, _ = mk_gov(batch=1024, min_batch=128, prefetch=0)
    gov.force(batch_rows=128, reason="pin")
    # full batches + breach = throughput-bound: climb the ladder
    assert drive(gov, age, clock, age_s=5.0, rows=128, disp=1)
    assert gov.batch_rows == 256
    assert drive(gov, age, clock, age_s=5.0, rows=512, disp=2)
    assert gov.batch_rows == 512
    assert gov.prefetch == 2
    assert list(gov.trail)[-1]["reason"] == "saturated"


def test_starved_recovery_is_additive_toward_initial(monkeypatch):
    monkeypatch.setenv("HEATMAP_SLO_FRESHNESS_P50_MS", "1000")
    gov, age, clock, _ = mk_gov(flush_k=4, prefetch=1)
    gov.force(batch_rows=128, flush_k=1, prefetch=0, reason="pin")
    # healthy + idle polls: one bucket up per interval; flush-K and
    # prefetch recover toward their CONFIGURED initials, not the caps
    assert drive(gov, age, clock, age_s=0.1, rows=10, disp=1, idles=3)
    assert (gov.batch_rows, gov.flush_k, gov.prefetch) == (256, 2, 1)
    for _ in range(8):
        drive(gov, age, clock, age_s=0.1, rows=10, disp=1, idles=3)
    assert gov.batch_rows == 1024       # back at the top
    assert gov.flush_k == 4             # == initial, not flush_k_max
    assert gov.prefetch == 1            # == initial, not prefetch_max


def test_headroom_growth_reaches_hard_bounds(monkeypatch):
    monkeypatch.setenv("HEATMAP_SLO_FRESHNESS_P50_MS", "1000")
    gov, age, clock, _ = mk_gov(flush_k=4, prefetch=1)
    for _ in range(40):
        drive(gov, age, clock, age_s=0.1, rows=1024, disp=1)
    assert gov.flush_k == gov.flush_k_max
    assert gov.prefetch == gov.prefetch_max


def test_hysteresis_band_holds(monkeypatch):
    monkeypatch.setenv("HEATMAP_SLO_FRESHNESS_P50_MS", "1000")
    gov, age, clock, _ = mk_gov()
    # between healthy_frac*SLO and the SLO: no move either way
    assert not drive(gov, age, clock, age_s=0.8, rows=1024, disp=1)
    assert not drive(gov, age, clock, age_s=0.8, rows=10, disp=1,
                     idles=2)
    assert len(gov.trail) == 0


def test_no_fresh_samples_holds(monkeypatch):
    monkeypatch.setenv("HEATMAP_SLO_FRESHNESS_P50_MS", "1000")
    gov, age, clock, _ = mk_gov()
    age.observe(99.0)                      # stale: before the interval
    drive(gov, age, clock, age_s=99.0, rows=10, disp=1)   # consumes it
    # a later interval with NO new samples must not act on the old ones
    gov.note_dispatch(10)
    clock.tick(gov.interval_s + 0.01)
    assert not gov.decide()


def test_interval_rate_limit():
    gov, age, clock, _ = mk_gov(interval=5.0)
    age.observe(99.0)
    gov.note_dispatch(10)
    clock.tick(1.0)
    assert not gov.decide()                # inside the interval: no-op


def test_memory_guardrail_steps_down(monkeypatch):
    monkeypatch.setenv("HEATMAP_SLO_FRESHNESS_P50_MS", "1000")
    monkeypatch.setenv("HEATMAP_SLO_MEM_BYTES", "1000")

    class Mem:
        watermark_bytes = 5000.0

    gov, age, clock, _ = mk_gov(prefetch=2, memory=Mem())
    # over budget: growth is blocked and prefetch/bucket step DOWN even
    # while the feed is saturated-and-breaching (which would otherwise
    # grow)
    assert drive(gov, age, clock, age_s=5.0, rows=1024, disp=1)
    assert (gov.batch_rows, gov.prefetch) == (512, 0)
    assert list(gov.trail)[-1]["reason"] == "mem"


def test_growth_pressure_forces_flush_k_down(monkeypatch):
    monkeypatch.setenv("HEATMAP_SLO_FRESHNESS_P50_MS", "1000")
    gov, age, clock, _ = mk_gov(flush_k=8)
    gov.note_growth_pressure()
    drive(gov, age, clock, age_s=0.1, rows=1024, disp=1)
    assert gov.flush_k == 4
    assert list(gov.trail)[-1]["reason"] == "growth_pressure"


def test_retrace_freezes_and_latches_bucket():
    class Tracker:
        retraces = 0

        def snapshot(self):
            return {"retraces_after_warmup": self.retraces}

    tr = Tracker()
    gov, age, clock, reg = mk_gov(tracker=tr)
    gov.force(batch_rows=512, reason="pin")
    assert not gov.check_retrace()
    tr.retraces = 1
    assert gov.check_retrace()
    assert gov.frozen
    assert gov.latched_bucket == 512
    assert 512 not in gov.ladder           # latched OUT of the ladder
    # ...but the LIVE value stays pinned at the latched bucket: the
    # current shape just (re)compiled, and stepping off it on freeze
    # would retrace AGAIN (found in the live verify drive)
    assert gov.batch_rows == 512
    # frozen: decide() is inert no matter what the signals say
    age.observe(99.0)
    gov.note_dispatch(1024)
    clock.tick(gov.interval_s + 0.01)
    assert not gov.decide()
    fams = {f.name: f for f in reg._families.values()}
    assert fams["heatmap_govern_frozen"].value == 1.0


def test_force_rejects_off_ladder_bucket():
    gov, _age, _clock, _ = mk_gov()
    with pytest.raises(ValueError):
        gov.force(batch_rows=777)


def test_metric_families_registered_and_tracking():
    gov, _age, _clock, reg = mk_gov()
    fams = {f.name: f for f in reg._families.values()}
    for name in ("heatmap_govern_batch_rows", "heatmap_govern_flush_k",
                 "heatmap_govern_prefetch", "heatmap_govern_frozen",
                 "heatmap_govern_adjust_total",
                 "heatmap_govern_last_adjust_age_seconds"):
        assert name in fams, name
        assert fams[name].help.strip()
    assert fams["heatmap_govern_batch_rows"].value == 1024
    gov.force(batch_rows=256, flush_k=2)
    assert fams["heatmap_govern_batch_rows"].value == 256
    assert fams["heatmap_govern_flush_k"].value == 2
    c = fams["heatmap_govern_adjust_total"].labels(dir="set",
                                                   reason="forced")
    assert c.value == 1


# ------------------------------------------------------- real runtime
from heatmap_tpu.sink import MemoryStore  # noqa: E402
from heatmap_tpu.stream import (  # noqa: E402
    MemorySource, MicroBatchRuntime, RampSource,
)

T0 = int(time.time()) - 600


def mk_exact_events(n=3000):
    """Exact-arithmetic corpus: every per-group f32 accumulation is
    exact regardless of batch partitioning — fixed position per
    vehicle (centroid residuals exactly 0), speeds on a 0.25 grid
    (sums/squares exact at these counts) — so byte-identity across
    REGROUPED batch boundaries is decidable, not luck."""
    return [{"provider": "p", "vehicleId": f"v{i % 7}",
             "lat": 42.0 + (i % 7) * 1e-2, "lon": -71.0,
             "speedKmh": (i % 40) * 0.25, "ts": T0 + i % 30}
            for i in range(n)]


def _run_corpus(tmp_path, governed, cycle=()):
    cfg = load_config(
        {}, batch_size=256, state_capacity_log2=10, speed_hist_bins=4,
        store="memory", govern=governed, govern_min_batch=64,
        checkpoint_dir=str(tempfile.mkdtemp(
            dir=tmp_path, prefix="govern-diff-")))
    src = MemorySource(mk_exact_events())
    src.finish()
    store = MemoryStore()
    rt = MicroBatchRuntime(cfg, src, store, checkpoint_every=0)
    i = 0
    while True:
        progressed = rt.step_once()
        if governed and cycle:
            rt.governor.force(batch_rows=cycle[i % len(cycle)],
                              flush_k=1 + i % 4, prefetch=i % 2)
            i += 1
        if not progressed and src.exhausted:
            break
    rt.close()
    return rt, store


def test_governed_run_byte_identical_and_retrace_free(tmp_path):
    """The differential safety net: a governed run that walks the
    whole ladder (and retargets flush-K/prefetch) over a fixed corpus
    produces byte-identical sink state to the ungoverned run — knob
    changes re-partition batching, never results — with ZERO
    post-warmup retraces (every bucket was warmed at startup)."""
    rt_g, store_g = _run_corpus(tmp_path, True,
                                cycle=(64, 256, 128, 256, 64, 128))
    rt_u, store_u = _run_corpus(tmp_path, False)
    snap = rt_g.runtimeinfo.compile.snapshot()
    assert snap["retraces_after_warmup"] == 0
    assert len(list(rt_g.governor.trail)) >= 6    # it really moved
    assert store_g._tiles.keys() == store_u._tiles.keys()
    assert len(store_g._tiles) > 0
    for k in store_g._tiles:
        assert store_g._tiles[k] == store_u._tiles[k], k
    assert store_g._positions == store_u._positions
    # identical cutoff trajectory endpoint: same watermark, same
    # late/valid accounting
    assert rt_g.max_event_ts == rt_u.max_event_ts
    for key in ("events_valid", "events_late", "events_invalid"):
        assert rt_g.metrics.counters.get(key, 0) \
            == rt_u.metrics.counters.get(key, 0), key


def test_governed_runtime_wiring(tmp_path):
    """Runtime plumbing: decisions actually retarget the live feed
    shape, ring capacity and prefetch depth; a flush is forced at the
    flush-K transition; /healthz degrades naming the latched bucket
    when frozen."""
    from heatmap_tpu.serve.api import healthz_payload

    cfg = load_config(
        {}, batch_size=256, state_capacity_log2=10, speed_hist_bins=4,
        store="memory", govern=True, govern_min_batch=64,
        checkpoint_dir=str(tmp_path / "wiring"))
    src = MemorySource(mk_exact_events(800))
    rt = MicroBatchRuntime(cfg, src, MemoryStore(), checkpoint_every=0)
    assert rt.governor is not None
    assert rt.governor.ladder == [64, 128, 256]
    rt.step_once()
    rt.governor.force(batch_rows=64, flush_k=2, prefetch=0)
    rt.step_once()
    assert rt._feed_batch == 64
    assert rt._ring.capacity == 2
    assert rt._prefetch_n == 0
    # healthz: active governor reports ok; frozen degrades NAMING the
    # latched bucket
    payload, down = healthz_payload(rt)
    assert payload["checks"]["govern_frozen"]["ok"]
    rt.governor.freeze("test-induced", bucket=64)
    payload, down = healthz_payload(rt)
    assert not down
    assert payload["status"] == "degraded"
    chk = payload["checks"]["govern_frozen"]
    assert not chk["ok"]
    assert "64" in str(chk["value"])
    src.finish()
    rt.close()


def test_no_governor_when_govern_off(tmp_path):
    """With HEATMAP_GOVERN off the runtime builds no governor: the
    attribute contract the step loop guards on.  (A partitioned mesh
    governs one BatchGovernor per device instead; test_mesh_diff
    covers it.)"""
    cfg = load_config({}, batch_size=128, state_capacity_log2=10,
                      speed_hist_bins=4, store="memory", govern=False,
                      checkpoint_dir=str(tmp_path / "nogov"))
    src = MemorySource([])
    src.finish()
    rt = MicroBatchRuntime(cfg, src, MemoryStore(), checkpoint_every=0)
    assert rt.governor is None
    rt.close()


# ------------------------------------------------------------- chaos
def test_chaos_ramp_100x_recovers(tmp_path, monkeypatch):
    """ISSUE 10 acceptance: offered load ramps 100x up and back down
    against a REAL backlog queue.  The governor (pinned at the ladder
    floor, the converged low-load state) climbs under saturation; the
    event-age p50 breaches during the swing and re-enters the SLO
    within a bounded number of governor intervals; zero post-warmup
    retraces.

    Time runs on an accelerated virtual clock (event timestamps are
    int seconds — sub-second real dynamics don't resolve otherwise):
    the RampSource produces against it and the lineage tracker stamps
    with it, so event ages are exact in virtual seconds while the test
    wall-clocks ~15 s."""
    SPEED = 20.0
    BASE = 1_700_000_000.0
    t_real0 = time.monotonic()

    def vclock():
        return BASE + (time.monotonic() - t_real0) * SPEED

    SLO_VS = 3.0                           # virtual-seconds budget
    monkeypatch.setenv("HEATMAP_SLO_FRESHNESS_P50_MS",
                       str(int(SLO_VS * 1000)))
    low, high = 60.0, 6000.0               # ev per VIRTUAL second, 100x
    schedule = [(low, 50.0), (high, 120.0), (low, 90.0)]
    src = RampSource(schedule, clock=vclock)
    cfg = load_config(
        {}, batch_size=8192, state_capacity_log2=15, speed_hist_bins=4,
        store="memory", govern=True, govern_min_batch=512,
        govern_interval_s=0.5,             # REAL seconds
        trigger_ms=25, emit_flush_k=8, query_view=False,
        checkpoint_dir=str(tmp_path / "ramp"))
    rt = MicroBatchRuntime(cfg, src, MemoryStore(),
                           positions_enabled=False, checkpoint_every=0)
    rt.lineage.clock = vclock              # ages in virtual seconds
    assert rt.governor.ladder == [512, 1024, 2048, 4096, 8192]
    # the converged low-load state: smallest bucket, per-batch flush
    rt.governor.force(batch_rows=512, flush_k=1, prefetch=0,
                      reason="low-load-converged")

    samples = []
    th = threading.Thread(target=rt.run, daemon=True)
    th.start()
    while th.is_alive():
        time.sleep(0.2)
        now_v = vclock()
        tail = rt.lineage.tail(64)
        ages = sorted(r["age_s"]["mean"] for r in tail
                      if "age_s" in r
                      and r.get("t_sink", 0) >= now_v - 10.0)
        samples.append({
            "t_v": now_v - BASE,
            "p50_v": ages[len(ages) // 2] if ages else None,
            "batch": rt.governor.batch_rows,
        })
    th.join(timeout=60)
    assert src.exhausted                   # backlog fully drained

    snap = rt.runtimeinfo.compile.snapshot()
    assert snap["retraces_after_warmup"] == 0, snap
    # the swing was real: a breach was observed during the high phase
    high_t0, high_t1 = 50.0, 170.0
    breaches = [s for s in samples
                if s["p50_v"] is not None and s["p50_v"] > SLO_VS
                and s["t_v"] >= high_t0]
    assert breaches, "the 100x ramp never breached the SLO"
    # the governor climbed the ladder under saturation
    assert rt.governor.batch_rows >= 4096, rt.governor.snapshot()
    ups = [t for t in rt.governor.trail if t.get("dir") == "up"]
    assert any(t["reason"] == "saturated" for t in ups)
    # recovery: within a bounded number of governor intervals of the
    # first breach, the p50 re-enters the SLO — and STAYS there by the
    # end of the run (the ramp-down side)
    t_breach = breaches[0]["t_v"]
    bound_v = 24 * cfg.govern_interval_s * SPEED   # 24 intervals
    recovered = [s for s in samples
                 if s["p50_v"] is not None and s["p50_v"] <= SLO_VS
                 and s["t_v"] > t_breach]
    assert recovered, "p50 never re-entered the SLO after the breach"
    assert recovered[0]["t_v"] - t_breach <= bound_v, (
        f"recovery took {recovered[0]['t_v'] - t_breach:.0f} virtual s "
        f"(> {bound_v:.0f})")
    settled = [s for s in samples if s["p50_v"] is not None][-3:]
    assert settled and all(s["p50_v"] <= SLO_VS for s in settled), \
        samples[-6:]


# ------------------------------------------------------ obs_top rows
def _load_obs_top():
    import importlib.util
    import os

    repo = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        os.pardir))
    spec = importlib.util.spec_from_file_location(
        "obs_top", os.path.join(repo, "tools", "obs_top.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_obs_top_governor_row_single_view():
    ot = _load_obs_top()
    m = {
        "heatmap_govern_batch_rows": {"": 8192.0},
        "heatmap_govern_flush_k": {"": 2.0},
        "heatmap_govern_prefetch": {"": 1.0},
        "heatmap_govern_frozen": {"": 0.0},
        "heatmap_govern_last_adjust_age_seconds": {"": 12.0},
        "heatmap_govern_adjust_total": {
            '{dir="down",reason="latency"}': 3.0},
    }
    prev = {"heatmap_govern_adjust_total": {
        '{dir="down",reason="latency"}': 2.0}}
    frame = ot.render_frame(m, prev, 2.0, None)
    assert "governor" in frame
    assert "8,192" in frame and "flush-K 2" in frame
    assert "down/latency" in frame        # the last adjust's reason
    assert "FROZEN" not in frame
    m["heatmap_govern_frozen"][""] = 1.0
    assert "FROZEN" in ot.render_frame(m, prev, 2.0, None)
    # no governor series -> no governor row (static runtimes)
    assert "governor" not in ot.render_frame({}, None, 0.0, None)


def test_obs_top_governor_table_fleet_view():
    ot = _load_obs_top()
    m = {
        "heatmap_fleet_members": {"": 2.0},
        "heatmap_fleet_member_up": {
            '{proc="shard0",role="runtime"}': 1.0,
            '{proc="shard1",role="runtime"}': 1.0},
        "heatmap_govern_batch_rows": {'{proc="shard0"}': 65536.0,
                                      '{proc="shard1"}': 4096.0},
        "heatmap_govern_flush_k": {'{proc="shard0"}': 8.0,
                                   '{proc="shard1"}': 1.0},
        "heatmap_govern_prefetch": {'{proc="shard0"}': 2.0,
                                    '{proc="shard1"}': 0.0},
        "heatmap_govern_frozen": {'{proc="shard0"}': 0.0,
                                  '{proc="shard1"}': 1.0},
    }
    frame = ot.render_fleet_frame(m, None, 0.0, None)
    assert "governor" in frame
    assert "65,536" in frame and "4,096" in frame   # skew is visible
    assert "FROZEN" in frame and "active" in frame


def test_initial_values_above_ceilings_raise_the_ceiling():
    """An operator's configured emit_flush_k/prefetch above the growth
    ceilings must survive enable intact — the static knobs BECOME the
    initial values; the ceiling adapts rather than silently clamping
    (review finding: a clamp would force-flush a 64-deep ring to 32 on
    the first governed step with no adjustment logged)."""
    gov, _age, _clock, _ = mk_gov(flush_k=64, prefetch=6)
    assert gov.flush_k == 64
    assert gov.flush_k_max == 64
    assert gov.prefetch == 6
    assert gov.prefetch_max == 6
