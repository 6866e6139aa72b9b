"""Supervisor tests: crash restart, stall (heartbeat) detection, restart
budget, platform failover, and the runtime-side heartbeat beacon
(SURVEY.md §5.3 — failure detection / elastic recovery, which the
reference delegates to Spark's restart-from-checkpoint model).

The children are tiny inline python scripts (no device, no jax) so each
failure mode is deterministic and fast; the beacon itself is separately
pinned against the real MicroBatchRuntime in test_runtime_heartbeat.
"""

import os
import subprocess
import sys
import time

import pytest

from heatmap_tpu.stream.supervisor import (FleetSupervisor, RestartPolicy,
                                           Supervisor)

FAST = dict(backoff_s=0.05, backoff_max_s=0.1, term_grace_s=1.0,
            window_s=60.0)


def _child(body: str) -> list[str]:
    return [sys.executable, "-c", body]


# a child that appends one line per launch so tests can count restarts,
# then acts per-launch: fail until the Nth run, then succeed
COUNTING = """
import os, sys, time
log = os.environ["LAUNCH_LOG"]
with open(log, "a") as fh:
    fh.write("launch\\n")
n = sum(1 for _ in open(log))
sys.exit(0 if n >= {succeed_on} else 1)
"""


def test_restarts_until_clean_exit(tmp_path):
    log = tmp_path / "launches"
    sup = Supervisor(
        _child(COUNTING.format(succeed_on=3)),
        RestartPolicy(max_restarts=5, **FAST),
        env={**os.environ, "LAUNCH_LOG": str(log)},
        heartbeat_path=str(tmp_path / "hb"), poll_s=0.02)
    assert sup.run() == 0
    assert sum(1 for _ in open(log)) == 3
    assert sup.restarts == 2


def test_restart_budget_exhausts(tmp_path):
    log = tmp_path / "launches"
    sup = Supervisor(
        _child(COUNTING.format(succeed_on=99)),
        RestartPolicy(max_restarts=2, **FAST),
        env={**os.environ, "LAUNCH_LOG": str(log)},
        heartbeat_path=str(tmp_path / "hb"), poll_s=0.02)
    assert sup.run() == 1          # the child's failing exit code
    # budget = max_restarts failures in window → 3 launches total
    assert sum(1 for _ in open(log)) == 3


def test_stall_detected_and_killed(tmp_path):
    """A child that starts its beacon then wedges (sleeps forever, like a
    device op that never returns) must be killed and restarted; the
    second launch exits 0 immediately."""
    log = tmp_path / "launches"
    body = """
import os, sys, time
log = os.environ["LAUNCH_LOG"]
with open(log, "a") as fh:
    fh.write("launch\\n")
n = sum(1 for _ in open(log))
if n == 1:
    hb = os.environ["HEATMAP_HEARTBEAT_FILE"]
    open(hb, "w").write(str(time.time()))
    time.sleep(3600)   # wedged: beacon never updates again
sys.exit(0)
"""
    sup = Supervisor(
        _child(body),
        RestartPolicy(max_restarts=5, stall_timeout_s=8.0, **FAST),
        env={**os.environ, "LAUNCH_LOG": str(log)},
        heartbeat_path=str(tmp_path / "hb"), poll_s=0.02)
    t0 = time.monotonic()
    assert sup.run() == 0
    assert time.monotonic() - t0 < 120  # killed the sleeper, didn't wait it out
    # exactly one stall-kill-restart on an idle box; a loaded box may
    # false-stall a starting child, which just restarts again — every
    # path still ends in the clean exit asserted above
    assert sum(1 for _ in open(log)) >= 2


def test_stall_covers_wedged_startup(tmp_path):
    """A child that never writes a beacon at all (wedged inside backend
    init) is still stalled — age counts from child start."""
    log = tmp_path / "launches"
    body = """
import os, sys, time
log = os.environ["LAUNCH_LOG"]
with open(log, "a") as fh:
    fh.write("launch\\n")
if sum(1 for _ in open(log)) == 1:
    time.sleep(3600)
sys.exit(0)
"""
    sup = Supervisor(
        _child(body),
        RestartPolicy(max_restarts=5, stall_timeout_s=8.0,
                      startup_grace_s=8.0, **FAST),
        env={**os.environ, "LAUNCH_LOG": str(log)},
        heartbeat_path=str(tmp_path / "hb"), poll_s=0.02)
    assert sup.run() == 0
    assert sum(1 for _ in open(log)) >= 2


def test_failover_is_off_by_default():
    """The CPU failover is opt-in: a default policy, and one built from
    an environment that does not name HEATMAP_SUPERVISE_FAILOVER_AFTER,
    insist on the accelerator."""
    assert RestartPolicy().failover_after is None
    assert RestartPolicy.from_env({}).failover_after is None


def test_failover_sets_platform(tmp_path):
    """After failover_after consecutive failures the child env gains
    JAX_PLATFORMS=<failover_platform>; the child proves it by
    succeeding only once it sees the override."""
    log = tmp_path / "launches"
    body = """
import os, sys
with open(os.environ["LAUNCH_LOG"], "a") as fh:
    fh.write(os.environ.get("JAX_PLATFORMS", "-") + "\\n")
sys.exit(0 if os.environ.get("JAX_PLATFORMS") == "cpu" else 1)
"""
    sup = Supervisor(
        _child(body),
        RestartPolicy(max_restarts=5, failover_after=2, **FAST),
        env={**{k: v for k, v in os.environ.items()
                if k != "JAX_PLATFORMS"}, "LAUNCH_LOG": str(log)},
        heartbeat_path=str(tmp_path / "hb"), poll_s=0.02)
    assert sup.run() == 0
    launches = open(log).read().split()
    assert launches == ["-", "-", "cpu"]
    assert sup.failed_over


def test_startup_grace_outlasts_stall_timeout(tmp_path):
    """A child that takes longer than stall_timeout_s before its first
    beacon (first-step compile) must NOT be killed while within
    startup_grace_s."""
    log = tmp_path / "launches"
    body = """
import os, sys, time
with open(os.environ["LAUNCH_LOG"], "a") as fh:
    fh.write("launch\\n")
time.sleep(2.0)   # "compiling": no beacon yet
sys.exit(0)
"""
    sup = Supervisor(
        _child(body),
        RestartPolicy(max_restarts=2, stall_timeout_s=0.2,
                      startup_grace_s=60.0, **FAST),
        env={**os.environ, "LAUNCH_LOG": str(log)},
        heartbeat_path=str(tmp_path / "hb"), poll_s=0.02)
    assert sup.run() == 0
    assert sum(1 for _ in open(log)) == 1


def test_healthy_run_resets_failover_streak(tmp_path):
    """Failures separated by healthy-for-a-window runs never trip
    failover_after (one blip a day must not degrade to CPU forever)."""
    log = tmp_path / "launches"
    body = """
import os, sys, time
with open(os.environ["LAUNCH_LOG"], "a") as fh:
    fh.write(os.environ.get("JAX_PLATFORMS", "-") + "\\n")
n = sum(1 for _ in open(os.environ["LAUNCH_LOG"]))
time.sleep(1.0)   # healthy past the (tiny) budget window
sys.exit(0 if n >= 3 else 1)
"""
    sup = Supervisor(
        _child(body),
        RestartPolicy(max_restarts=10, window_s=0.3, failover_after=2,
                      backoff_s=0.05, backoff_max_s=0.1, term_grace_s=1.0),
        env={**{k: v for k, v in os.environ.items()
                if k != "JAX_PLATFORMS"}, "LAUNCH_LOG": str(log)},
        heartbeat_path=str(tmp_path / "hb"), poll_s=0.02)
    assert sup.run() == 0
    assert not sup.failed_over
    assert open(log).read().split() == ["-", "-", "-"]


def test_wedged_child_still_trips_failover(tmp_path):
    """A child that only ever wedges (no beacon, killed by the startup
    grace) must NOT count as healthy — its streak accumulates and
    failover trips.  (The stall-detection wait itself is not health.)"""
    log = tmp_path / "launches"
    body = """
import os, sys, time
with open(os.environ["LAUNCH_LOG"], "a") as fh:
    fh.write(os.environ.get("JAX_PLATFORMS", "-") + "\\n")
if os.environ.get("JAX_PLATFORMS") == "cpu":
    sys.exit(0)
time.sleep(3600)   # wedged before any beacon
"""
    sup = Supervisor(
        _child(body),
        RestartPolicy(max_restarts=10, stall_timeout_s=2.0,
                      startup_grace_s=2.0, window_s=1.0,
                      failover_after=2, backoff_s=0.05,
                      backoff_max_s=0.1, term_grace_s=1.0),
        env={**{k: v for k, v in os.environ.items()
                if k != "JAX_PLATFORMS"}, "LAUNCH_LOG": str(log)},
        heartbeat_path=str(tmp_path / "hb"), poll_s=0.02)
    assert sup.run() == 0
    assert sup.failed_over
    assert open(log).read().split()[-1] == "cpu"


def test_separate_incidents_mint_fresh_episode_ids(tmp_path):
    """A child failure AFTER a full healthy window is a separate
    incident: the supervisor closes its previous episode broadcast
    before claiming, so the new incident gets a fresh id — joined
    stale, every surviving watchdog would skip it as already-dumped
    and the second incident would leave no correlated dump set."""
    import threading

    from heatmap_tpu.obs.xproc import read_episode

    log = tmp_path / "launches"
    chan = str(tmp_path / "chan")
    body = """
import os, sys, time
with open(os.environ["LAUNCH_LOG"], "a") as fh:
    fh.write("launch\\n")
n = sum(1 for _ in open(os.environ["LAUNCH_LOG"]))
if n >= 3:
    sys.exit(0)
time.sleep(0.5)   # healthy past the (tiny) budget window, then fail
sys.exit(1)
"""
    sup = Supervisor(
        _child(body),
        RestartPolicy(max_restarts=10, window_s=0.3, backoff_s=0.05,
                      backoff_max_s=0.1, term_grace_s=1.0),
        env={**os.environ, "LAUNCH_LOG": str(log)},
        heartbeat_path=str(tmp_path / "hb"), poll_s=0.02,
        channel_path=chan)
    rcs: list = []
    t = threading.Thread(target=lambda: rcs.append(sup.run()), daemon=True)
    t.start()
    deadline = time.monotonic() + 30
    first = None
    while time.monotonic() < deadline and first is None:
        first = read_episode(chan).get("episode_id")
        time.sleep(0.01)
    assert first, "first failure never broadcast an episode"
    t.join(timeout=30)
    assert rcs == [0]
    # the second failure's broadcast survives the run: fresh id, ours
    final = read_episode(chan)
    assert final.get("origin") == "supervisor"
    assert final["episode_id"] != first, \
        "second incident joined the stale episode id"


def test_policy_from_env():
    env = {"HEATMAP_SUPERVISE_MAX_RESTARTS": "9",
           "HEATMAP_SUPERVISE_STALL_TIMEOUT_S": "7.5",
           "HEATMAP_SUPERVISE_FAILOVER_AFTER": "2"}
    env["HEATMAP_SUPERVISE_STARTUP_GRACE_S"] = "11"
    p = RestartPolicy.from_env(env)
    assert p.max_restarts == 9
    assert p.stall_timeout_s == 7.5
    assert p.startup_grace_s == 11
    assert p.failover_after == 2
    assert p.failover_platform == "cpu"
    d = RestartPolicy.from_env({})
    assert d == RestartPolicy()


def test_runtime_heartbeat(tmp_path, monkeypatch):
    """The real MicroBatchRuntime writes the beacon from its step loop
    when HEATMAP_HEARTBEAT_FILE is set."""
    from heatmap_tpu.config import load_config
    from heatmap_tpu.sink import MemoryStore
    from heatmap_tpu.stream import MemorySource, MicroBatchRuntime

    hb = tmp_path / "hb"
    monkeypatch.setenv("HEATMAP_HEARTBEAT_FILE", str(hb))
    cfg = load_config({}, batch_size=64, state_capacity_log2=10,
                      speed_hist_bins=8, store="memory",
                      checkpoint_dir=str(tmp_path / "ckpt"))
    t0 = int(time.time()) - 600
    evs = [{"provider": "t", "vehicleId": f"v{i}", "lat": 42.0 + i * 1e-3,
            "lon": -71.0, "speedKmh": 10.0, "bearing": 0.0,
            "accuracyM": 1.0, "ts": t0 + i} for i in range(64)]
    src = MemorySource(evs)
    src.finish()
    rt = MicroBatchRuntime(cfg, src, MemoryStore())
    rt.run()
    content = open(hb).read()
    assert content.startswith(tuple("0123456789"))
    assert "epoch=" in content


def test_sigterm_during_backoff_exits_promptly(tmp_path):
    """A REAL SIGTERM delivered while the supervisor sleeps in a long
    restart backoff must stop it within ~poll_s.  stop() runs inside the
    signal handler on the sleeping main thread, so it must be
    async-signal-safe: the round-4 Event-based stop could self-deadlock
    there (Event.set() needs the Condition lock the interrupted wait
    holds); the plain-bool flag + sliced _wait cannot."""
    import signal

    repo = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        os.pardir))
    prog = (
        "import sys; sys.path.insert(0, %r); "
        "from heatmap_tpu.stream.supervisor import supervise_cli; "
        "sys.exit(supervise_cli([sys.executable, '-c', "
        "'raise SystemExit(3)']))" % repo)
    env = {**os.environ, "PYTHONPATH": "",  # skip slow interpreter hooks
           "HEATMAP_SUPERVISE_BACKOFF_S": "60",
           "HEATMAP_SUPERVISE_BACKOFF_MAX_S": "60",
           "HEATMAP_SUPERVISE_MAX_RESTARTS": "9"}
    p = subprocess.Popen([sys.executable, "-c", prog], env=env)
    try:
        time.sleep(3.0)  # child exits code 3 fast -> 60s backoff begins
        assert p.poll() is None, "supervisor ended before the signal"
        t0 = time.monotonic()
        p.send_signal(signal.SIGTERM)
        rc = p.wait(timeout=10)
        assert time.monotonic() - t0 < 5.0
        assert rc == 0  # stop() during backoff is a clean stop
    finally:
        if p.poll() is None:
            p.kill()


def test_watchdog_vouches_for_in_flight_step_up_to_grace(tmp_path,
                                                         monkeypatch):
    """The in-flight beacon watchdog keeps the beacon fresh while a step
    is dispatching (so a slow mid-run recompile outlives
    stall_timeout_s), but stops vouching once HEATMAP_DISPATCH_GRACE_S
    lapses — a truly wedged device op must still go quiet and trip the
    supervisor."""
    from heatmap_tpu.config import load_config
    from heatmap_tpu.sink import MemoryStore
    from heatmap_tpu.stream import MemorySource, MicroBatchRuntime

    hb = tmp_path / "hb"
    monkeypatch.setenv("HEATMAP_HEARTBEAT_FILE", str(hb))
    monkeypatch.setenv("HEATMAP_DISPATCH_GRACE_S", "2.5")
    cfg = load_config({}, batch_size=64, state_capacity_log2=10,
                      speed_hist_bins=8, store="memory",
                      checkpoint_dir=str(tmp_path / "ckpt"))
    t0 = int(time.time()) - 600
    src = MemorySource([{"provider": "t", "vehicleId": "v0", "lat": 42.0,
                         "lon": -71.0, "speedKmh": 10.0, "bearing": 0.0,
                         "accuracyM": 1.0, "ts": t0}])
    rt = MicroBatchRuntime(cfg, src, MemoryStore())
    rt.step_once()
    rt._touch_heartbeat()  # first beacon: the watchdog thread starts now
    assert rt._hb_watchdog is not None and rt._hb_watchdog.is_alive()

    # simulate a long in-flight step: the watchdog must refresh the
    # beacon while the (fake) dispatch is younger than the grace
    rt._step_began = time.monotonic()
    before = os.stat(hb).st_mtime
    time.sleep(1.6)
    assert os.stat(hb).st_mtime > before, "watchdog never touched beacon"

    # past the grace the watchdog stops vouching: beacon goes quiet
    rt._step_began = time.monotonic() - 10.0  # "dispatching" for 10s > 2.5s
    quiet_from = os.stat(hb).st_mtime
    time.sleep(1.6)
    assert os.stat(hb).st_mtime == quiet_from, (
        "watchdog kept vouching past the dispatch grace")
    rt._step_began = None
    rt.close()


# ------------------------------------------------- fleet observatory
CHAOS_CHILD = """
import os, sys, time
from heatmap_tpu.obs.xproc import publish_member_snapshot
chan = os.environ["HEATMAP_SUPERVISOR_CHANNEL"]
open(os.environ["CHILD_PID_FILE"], "w").write(str(os.getpid()))
hb = os.environ["HEATMAP_HEARTBEAT_FILE"]
while True:
    with open(hb, "w") as fh:
        fh.write(str(time.time()))
    publish_member_snapshot(chan, "c1", role="runtime",
                            freshness={"event_age_p50_s": 0.1},
                            healthz={"status": "ok", "checks": {}})
    time.sleep(0.05)
"""


def test_fleet_chaos_child_killed_mid_stream(tmp_path, monkeypatch):
    """ISSUE 6 acceptance (pinned on JAX_PLATFORMS=cpu via conftest): a
    supervisor-managed fleet with one child KILLED mid-stream yields
    /fleet/healthz degraded NAMING the dead member, and one
    flight-recorder dump per surviving member — supervisor + a
    serve-only watchdog member here — sharing a single episode id."""
    import glob
    import json
    import signal
    import threading

    from heatmap_tpu.obs.fleet import FleetAggregator
    from heatmap_tpu.obs.flightrec import FlightRecorder
    from heatmap_tpu.obs.runtimeinfo import SloWatchdog
    from heatmap_tpu.obs.xproc import (member_path,
                                       publish_member_snapshot,
                                       read_episode)

    chan = str(tmp_path / "chan")
    pid_file = tmp_path / "child.pid"
    fr_sup = tmp_path / "fr-supervisor"
    fr_srv = tmp_path / "fr-serve1"
    monkeypatch.setenv("HEATMAP_FLEET_PUBLISH_S", "0.05")
    env = {**os.environ,
           "CHILD_PID_FILE": str(pid_file),
           "HEATMAP_FLIGHTREC_DIR": str(fr_sup),
           "JAX_PLATFORMS": "cpu"}
    # long backoff: after the kill the supervisor must NOT resurrect
    # the child inside the test window — the fleet has to actually see
    # the member go dark
    sup = Supervisor(
        _child(CHAOS_CHILD),
        RestartPolicy(max_restarts=5, backoff_s=60.0, backoff_max_s=60.0,
                      term_grace_s=1.0, window_s=60.0,
                      stall_timeout_s=120.0),
        env=env, heartbeat_path=str(tmp_path / "hb"), poll_s=0.02,
        channel_path=chan)
    t = threading.Thread(target=sup.run, daemon=True)
    t.start()
    try:
        # the fleet assembles: child + supervisor member snapshots
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            if (pid_file.exists() and os.path.exists(member_path(chan, "c1"))
                    and os.path.exists(member_path(chan, "supervisor"))):
                break
            time.sleep(0.05)
        else:
            raise AssertionError("fleet never assembled")
        sup_snap = json.loads(open(member_path(chan, "supervisor")).read())
        assert sup_snap["role"] == "supervisor"
        assert "heatmap_supervisor_restarts_total" in sup_snap["metrics_text"]

        # the surviving serve-only member: publishes its snapshot and
        # runs its own SLO watchdog against the shared channel
        publish_member_snapshot(chan, "serve1", role="serve",
                                healthz={"status": "ok", "checks": {}})
        wd = SloWatchdog(None, interval_s=0.0, cooldown_s=0.0,
                         channel_path=chan, tag="serve1",
                         flightrec=FlightRecorder(str(fr_srv)))
        assert wd.check_once() is None   # healthy fleet: no episode yet

        # chaos: SIGKILL the child mid-stream (a hard death the child's
        # own recorder cannot see — exactly the supervisor's job)
        os.kill(int(pid_file.read_text()), signal.SIGKILL)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            ep = read_episode(chan)
            if ep:
                break
            time.sleep(0.05)
        else:
            raise AssertionError("supervisor never broadcast an episode")
        assert ep["origin"] == "supervisor"
        assert "child failed" in ep["reason"]
        eid = ep["episode_id"]

        # the supervisor's own dump carries the episode id
        deadline = time.monotonic() + 15
        sup_dumps = []
        while time.monotonic() < deadline and not sup_dumps:
            sup_dumps = [json.loads(open(p).read()) for p in
                         glob.glob(str(fr_sup / "flightrec-*.json"))]
            time.sleep(0.05)
        assert sup_dumps and sup_dumps[0]["episode_id"] == eid

        # the surviving member's watchdog follows the broadcast and
        # writes its correlated dump under the SAME id
        path = wd.check_once()
        assert path is not None
        srv_dump = json.loads(open(path).read())
        assert srv_dump["episode_id"] == eid

        # /fleet/healthz degrades NAMING the dead member once its
        # snapshot goes stale (it stopped publishing at the kill);
        # supervisor + serve1 keep publishing and stay fresh members
        publish_member_snapshot(chan, "serve1", role="serve",
                                healthz={"status": "ok", "checks": {}})
        agg = FleetAggregator(chan, max_age_s=0.75)
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            payload, down = agg.healthz()
            if "c1" in payload.get("stale_members", []):
                break
            time.sleep(0.1)
        else:
            raise AssertionError(
                f"dead member never went stale: {payload}")
        assert payload["status"] == "degraded" and not down
        assert payload["checks"]["member_c1"]["ok"] is False
        assert "stale" in payload["checks"]["member_c1"]["value"]
        assert "supervisor" in payload["members"]
        assert "serve1" in payload["members"]
        assert payload["episode"]["episode_id"] == eid
        txt = agg.metrics_text()
        assert 'heatmap_fleet_member_up{proc="c1",role="?"} 0' in txt
    finally:
        sup.stop()
        t.join(timeout=30)


# ------------------------------------------------- sharded fleet (ISSUE 7)
# One child = one H3-partitioned runtime shard.  These children are tiny
# scripts again: the REAL sharded runtime's checkpoint-resume and merged
# byte-identity are pinned in-process by tests/test_shard_diff.py; what
# the FleetSupervisor tests own is the LIFECYCLE — per-shard env fanout,
# per-child restart budgets, episode correlation, and the fleet surfaces
# naming the failing shard.

SHARD_COUNTING = """
import os, sys
log = os.environ["LAUNCH_LOG"] + os.environ["HEATMAP_SHARD_INDEX"]
with open(log, "a") as fh:
    fh.write(os.environ["HEATMAP_SHARDS"] + ":"
             + os.environ["HEATMAP_SHARD_INDEX"] + "\\n")
n = sum(1 for _ in open(log))
sys.exit(0 if n >= int(os.environ["SUCCEED_ON"]) else 1)
"""


def test_fleet_spawns_per_shard_env_and_restarts_each(tmp_path):
    """Every child gets HEATMAP_SHARDS=N + its own HEATMAP_SHARD_INDEX;
    restart bookkeeping is PER SHARD (each child here needs 2 launches,
    so each must be restarted once — a shared budget would conflate
    them)."""
    sup = FleetSupervisor(
        _child(SHARD_COUNTING), 3,
        RestartPolicy(max_restarts=5, **FAST),
        env={**os.environ, "LAUNCH_LOG": str(tmp_path / "log"),
             "SUCCEED_ON": "2"},
        heartbeat_dir=str(tmp_path), poll_s=0.02,
        channel_path=str(tmp_path / "chan"))
    assert sup.run() == 0
    for i in range(3):
        lines = open(str(tmp_path / "log") + str(i)).read().split()
        assert lines == [f"3:{i}", f"3:{i}"]
        assert sup.children[i].restarts == 1
        assert sup.children[i].done
    assert sup.restarts == 3


def test_fleet_one_shard_exhausting_budget_degrades_not_kills(tmp_path):
    """One shard crash-looping past its budget marks THAT shard down;
    the others still run to completion and run() returns the failing
    shard's exit code (the fleet keeps serving its remaining cell
    space instead of dying wholesale)."""
    body = """
import os, sys
i = os.environ["HEATMAP_SHARD_INDEX"]
log = os.environ["LAUNCH_LOG"] + i
with open(log, "a") as fh:
    fh.write("launch\\n")
sys.exit(3 if i == "1" else 0)
"""
    sup = FleetSupervisor(
        _child(body), 3,
        RestartPolicy(max_restarts=1, **FAST),
        env={**os.environ, "LAUNCH_LOG": str(tmp_path / "log")},
        heartbeat_dir=str(tmp_path), poll_s=0.02,
        channel_path=str(tmp_path / "chan"))
    assert sup.run() == 3
    assert sup.children[1].gave_up and not sup.children[1].done
    assert sup.children[0].done and sup.children[2].done
    # budget = max_restarts failures in window -> 2 launches of shard 1
    assert sum(1 for _ in open(str(tmp_path / "log") + "1")) == 2
    # the whole fleet did NOT give up: the channel only reports gave_up
    # when every shard exhausted its budget
    from heatmap_tpu.obs import SupervisorChannel

    assert SupervisorChannel.metrics_from(str(tmp_path / "chan"))[
        "gave_up"] == 0


def test_fleet_needs_two_shards():
    with pytest.raises(ValueError):
        FleetSupervisor(["true"], 1)


# A "runtime shard" small enough to SIGKILL deterministically: streams a
# shared corpus in batches, folds ONLY the rows its ShardMap owns into
# an append-only per-shard sink, commits its own offset file AFTER each
# batch's rows land (the offsets-after-commit discipline — replay-safe
# because the assertion dedups like the real sink's idempotent upserts),
# heartbeats + publishes a fleet member snapshot per batch, and leaves a
# departure tombstone on clean exit.
SHARD_STREAM_CHILD = """
import json, os, sys, time
import numpy as np
from heatmap_tpu.obs.xproc import publish_member_snapshot
from heatmap_tpu.stream.shardmap import ShardMap

n = int(os.environ["HEATMAP_SHARDS"])
i = int(os.environ["HEATMAP_SHARD_INDEX"])
chan = os.environ["HEATMAP_SUPERVISOR_CHANNEL"]
hb = os.environ["HEATMAP_HEARTBEAT_FILE"]
outdir = os.environ["FLEET_OUTDIR"]
batch = int(os.environ["FLEET_BATCH"])
tag = "shard%d" % i
with open(os.path.join(outdir, tag + ".launches"), "a") as fh:
    fh.write("launch\\n")
open(os.path.join(outdir, tag + ".pid"), "w").write(str(os.getpid()))
rows = [json.loads(l) for l in open(os.environ["FLEET_CORPUS"])]
lat = np.radians([r["lat"] for r in rows]).astype(np.float32)
lng = np.radians([r["lon"] for r in rows]).astype(np.float32)
own = ShardMap(n, i, 8).owned_mask(lat, lng)
off_path = os.path.join(outdir, tag + ".offset")
out_path = os.path.join(outdir, tag + ".rows")
off = int(open(off_path).read()) if os.path.exists(off_path) else 0
while off < len(rows):
    hi = min(off + batch, len(rows))
    with open(out_path, "a") as fh:
        for j in range(off, hi):
            if own[j]:
                fh.write("%d\\n" % j)
    with open(off_path + ".tmp", "w") as fh:
        fh.write(str(hi))
    os.replace(off_path + ".tmp", off_path)   # offset AFTER commit
    off = hi
    open(hb, "w").write(str(time.time()))
    publish_member_snapshot(chan, tag, role="runtime",
                            healthz={"status": "ok", "checks": {}})
    time.sleep(0.05)
publish_member_snapshot(chan, tag, role="runtime",
                        healthz={"status": "ok", "checks": {}}, left=True)
"""


def test_fleet_chaos_shard_killed_revived_converges(tmp_path, monkeypatch):
    """ISSUE 7 chaos satellite: SIGKILL one shard mid-stream — the
    restart policy revives it, the resume replays only THAT shard's own
    offsets, /fleet/healthz degrades NAMING the shard while it is dark
    and recovers, and the merged per-shard sinks converge to the
    single-shard baseline (every row exactly once across the fleet)."""
    import json
    import signal
    import threading

    import numpy as np

    from heatmap_tpu.obs.fleet import FleetAggregator
    from heatmap_tpu.obs.xproc import read_episode
    from heatmap_tpu.stream.shardmap import ShardMap

    monkeypatch.setenv("HEATMAP_FLEET_PUBLISH_S", "0.05")
    outdir = tmp_path / "out"
    outdir.mkdir()
    corpus = tmp_path / "corpus.jsonl"
    rng = np.random.default_rng(29)
    rows = [{"lat": float(rng.uniform(42.3, 42.5)),
             "lon": float(rng.uniform(-71.2, -71.0))} for _ in range(160)]
    with open(corpus, "w") as fh:
        for r in rows:
            fh.write(json.dumps(r) + "\n")
    chan = str(tmp_path / "chan")
    env = {**os.environ, "FLEET_OUTDIR": str(outdir),
           "FLEET_CORPUS": str(corpus), "FLEET_BATCH": "4",
           "JAX_PLATFORMS": "cpu"}
    # backoff ~3s: wide enough for the fleet to SEE the dead member go
    # stale before the revival even on a loaded host, short enough to
    # keep the test fast
    sup = FleetSupervisor(
        _child(SHARD_STREAM_CHILD), 2,
        RestartPolicy(max_restarts=5, backoff_s=3.0, backoff_max_s=3.0,
                      term_grace_s=1.0, window_s=60.0,
                      stall_timeout_s=120.0),
        env=env, heartbeat_dir=str(tmp_path), poll_s=0.02,
        channel_path=chan)
    rcs: list = []
    t = threading.Thread(target=lambda: rcs.append(sup.run()), daemon=True)
    t.start()
    try:
        # wait until shard 1 is genuinely MID-stream, then SIGKILL it
        off1 = outdir / "shard1.offset"
        pid1 = outdir / "shard1.pid"
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if off1.exists() and 0 < int(off1.read_text()) < len(rows) - 8:
                break
            time.sleep(0.01)
        else:
            raise AssertionError("shard1 never got mid-stream")
        os.kill(int(pid1.read_text()), signal.SIGKILL)
        killed_at = int(off1.read_text())
        assert 0 < killed_at < len(rows)

        # ONE probe loop from the moment of the kill: the failure claims
        # an episode NAMING the shard, and /fleet/healthz degrades
        # naming the dead member once its snapshot goes stale (it
        # stopped publishing at the kill).  Probing both concurrently
        # matters — the degraded window only spans the restart backoff,
        # and a sequential wait could eat it on a loaded host, after
        # which the revived fleet finishes and departs cleanly
        agg = FleetAggregator(chan, max_age_s=0.5)
        deadline = time.monotonic() + 30
        ep, degraded_payload = {}, None
        while time.monotonic() < deadline:
            if not ep:
                ep = read_episode(chan)
            if degraded_payload is None:
                payload, down = agg.healthz()
                if not payload.get("checks", {}).get(
                        "member_shard1", {}).get("ok", True):
                    assert payload["status"] == "degraded" and not down
                    degraded_payload = payload
            if ep and degraded_payload is not None:
                break
            time.sleep(0.02)
        assert ep and "shard1" in ep["reason"]
        assert degraded_payload is not None, \
            "dead shard never went stale on /fleet/healthz"

        # revival: the whole fleet runs to clean completion
        t.join(timeout=120)
        assert rcs == [0]
        launches = open(outdir / "shard1.launches").read().split()
        assert len(launches) >= 2, "restart policy never revived shard1"
        assert open(outdir / "shard0.launches").read().split() == ["launch"]

        # the resume replayed only shard 1's OWN offsets: shard 0 was
        # never killed, so its append-only sink holds exactly its owned
        # rows once; shard 1 may replay at most the one batch whose
        # offset commit the SIGKILL could have preempted
        lat = np.radians([r["lat"] for r in rows]).astype(np.float32)
        lng = np.radians([r["lon"] for r in rows]).astype(np.float32)
        owned = [np.flatnonzero(ShardMap(2, i, 8).owned_mask(lat, lng))
                 for i in range(2)]
        got0 = [int(x) for x in open(outdir / "shard0.rows").read().split()]
        got1 = [int(x) for x in open(outdir / "shard1.rows").read().split()]
        assert got0 == list(owned[0])
        assert len(got1) - len(set(got1)) <= 4  # <= one replayed batch
        # merged sinks converge to the single-shard baseline: every row
        # exactly once across the fleet (dedup = the sink's idempotent
        # upsert), cell spaces disjoint
        assert sorted(set(got0) | set(got1)) == list(range(len(rows)))
        assert not set(got0) & set(got1)

        # recovered: the supervisor's final control-plane verdict shows
        # both shards done
        from heatmap_tpu.obs.xproc import member_path

        snap = json.loads(open(member_path(chan, "supervisor")).read())
        assert snap["healthz"]["status"] == "ok"
        assert snap["healthz"]["checks"]["shard0"]["value"] == "done"
        assert snap["healthz"]["checks"]["shard1"]["value"] == "done"
    finally:
        sup.stop()
        t.join(timeout=30)
