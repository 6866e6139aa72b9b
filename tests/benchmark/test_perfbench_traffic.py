"""The generator: the same seed gives the same events, the open loop
builds a backlog when it is polled slowly, and each mix and fleet key
shapes the traffic it names."""

import numpy as np
import pytest

from benchmark import traffic
from benchmark.spec import SpecError

FLEET = {"n_vehicles": 50, "center": [42.36, -71.06], "radius_deg": 0.1,
         "report_interval_s": 5, "capture_events": 1000}


def _cols_equal(a, b):
    return all(np.array_equal(getattr(a, f), getattr(b, f)) for f in
               ("lat_deg", "lng_deg", "speed_kmh", "ts_s", "vehicle_id"))


def test_capture_deterministic_per_seed():
    a = traffic.Capture(FLEET, 1000, seed=2**31 + 11)
    b = traffic.Capture(FLEET, 1000, seed=2**31 + 11)
    c = traffic.Capture(FLEET, 1000, seed=12)
    assert np.array_equal(a.lat, b.lat) and np.array_equal(a.speed, b.speed)
    assert not np.array_equal(a.lat, c.lat)
    # the same sizes for every seed: fleet and capture length
    assert len(a.lat) == len(c.lat) == 1000
    assert np.array_equal(a.vid, c.vid)
    assert abs(a.lat.mean() - 42.36) < 0.1 and (a.speed >= 0).all()


def test_replay_deterministic_and_clock_continues_across_laps():
    mix = {"arrival": "closed"}
    cfg = {"fleet": FLEET}
    s1 = traffic.make_source(mix, cfg, seed=5)
    s2 = traffic.make_source(mix, cfg, seed=5)
    s1.start(), s2.start()
    polls1 = [s1.poll(300) for _ in range(5)]
    polls2 = [s2.poll(300) for _ in range(5)]
    assert all(_cols_equal(a, b) for a, b in zip(polls1, polls2))
    ts = np.concatenate([p.ts_s for p in polls1])
    assert (np.diff(ts) >= 0).all()
    # 1,500 events at 10 per event-second: lap two (row 1000 on) goes on
    # from the first lap's clock rather than starting over
    assert ts[1000] == traffic.T0 + 100 and ts[-1] == traffic.T0 + 149
    # the lap repeats the capture's positions
    lat = np.concatenate([p.lat_deg for p in polls1])
    assert np.array_equal(lat[1000:1500], lat[:500])
    assert s1.polls == [(300 * k, 300 * k + 300) for k in range(5)]
    s1.stop()
    assert s1.poll(300) is None and s1.exhausted


def test_live_backlog_grows_when_polled_slowly():
    now = [1000.0]
    cap = traffic.Capture(FLEET, 1000, seed=1)
    src = traffic.LiveSource(cap, 100.0, clock=lambda: now[0])
    src.start()
    now[0] += 0.5
    got = src.poll(1000)
    assert len(got) == 50 and src.backlog() == 0
    # a slow step: 3 s pass, the poll takes a batch of 64, 236 wait
    now[0] += 3.0
    got = src.poll(64)
    assert len(got) == 64 and src.backlog() == 236
    # due times are the schedule's, timestamps the due second
    assert np.array_equal(got.ts_s, np.floor(
        1000.0 + np.arange(50, 114) / 100.0).astype(np.int32))
    src.stop()
    now[0] += 10.0
    assert src.backlog() == 236          # nothing more falls due
    assert len(src.poll(1000)) == 236 and src.poll(1000) is None
    assert src.exhausted


def test_live_restart_begins_a_segment_and_needs_an_empty_backlog():
    now = [50.0]
    src = traffic.LiveSource(traffic.Capture(FLEET, 1000, seed=1), 10.0,
                             clock=lambda: now[0])
    src.start()
    now[0] += 2.0
    src.stop()
    with pytest.raises(RuntimeError):
        src.start()
    src.poll(100)
    now[0] = 80.0
    src.start()
    now[0] += 1.0
    got = src.poll(100)
    assert len(got) == 10 and (got.ts_s == 80).all()
    assert np.allclose(src.due_times(20, 30), 80.0 + np.arange(10) / 10.0)
    assert np.allclose(src.due_times(0, 2), [50.0, 50.1])
    assert src.window_due(82.55) == (20, 46)


def test_live_timestamps_match_due_times_for_the_reference():
    now = [10.0]
    src = traffic.LiveSource(traffic.Capture(FLEET, 1000, seed=3), 7.0,
                             clock=lambda: now[0])
    src.start()
    now[0] += 30.0
    got = src.poll(1000)
    assert np.array_equal(got.ts_s, src.timestamps(0, len(got)))
    assert np.array_equal(got.ts_s, src.columns(0, len(got))["ts"])


def test_rate_schedule_ramps_the_offered_load():
    now = [0.0]
    mix = {"arrival": "open", "rate_schedule": [[0, 10], [2, 1000], [3, 10]]}
    src = traffic.make_source(mix, {"fleet": FLEET}, seed=4)
    src.clock = lambda: now[0]
    src.start()
    for t, due in [(1.0, 10), (2.0, 20), (2.5, 520), (3.0, 1020),
                   (4.0, 1030)]:
        now[0] = t
        assert src.produced() == due
    # the 100x burst: events 20-1019 fall due within one second
    assert np.allclose(src.due_times(19, 22), [1.9, 2.0, 2.001])
    assert src.due_times(1020, 1021)[0] == pytest.approx(3.0)
    assert src.window_due(2.5) == (0, 520)


@pytest.mark.parametrize("schedule", [[[1, 10]], [[0, 10], [0, 20]],
                                      [[0, 0]], -5])
def test_bad_rate_schedule_refused(schedule):
    with pytest.raises(SpecError):
        traffic.Schedule(schedule)


def test_late_bands_same_events_however_polls_cut_the_stream():
    mix = {"arrival": "closed",
           "late": [{"share": 0.5, "min_s": 0, "max_s": 3},
                    {"share": 0.1, "min_s": 900, "max_s": 1000}]}
    a = traffic.make_source(mix, {"fleet": FLEET}, seed=6)
    b = traffic.make_source(mix, {"fleet": FLEET}, seed=6)
    a.start(), b.start()
    ta = np.concatenate([a.poll(2000).ts_s])
    tb = np.concatenate([b.poll(n).ts_s for n in (7, 993, 1000)])
    assert np.array_equal(ta, tb)
    late = a.base_times(0, 2000) - ta.astype(np.int64)
    assert late.min() == 0 and late.max() <= 1000
    share_out_of_order = np.mean((late >= 1) & (late <= 3))
    assert 0.3 < share_out_of_order < 0.45        # 0.5 x 3/4
    assert 0.07 < np.mean(late >= 900) < 0.13
    assert not ((late > 3) & (late < 900)).any()
    other = traffic.make_source(mix, {"fleet": FLEET}, seed=7)
    other.start()
    assert not np.array_equal(other.poll(2000).ts_s, ta)


@pytest.mark.parametrize("bands", [[{"share": 0.7, "min_s": 0, "max_s": 1},
                                    {"share": 0.5, "min_s": 0, "max_s": 1}],
                                   [{"share": 0.1, "min_s": 5, "max_s": 1}]])
def test_bad_late_bands_refused(bands):
    with pytest.raises(SpecError):
        traffic.make_source({"arrival": "closed", "late": bands},
                            {"fleet": FLEET}, seed=1)


def test_hot_set_moves_a_share_into_a_circling_disc():
    hot = {"share": 0.3, "radius_deg": 0.002, "orbit_deg": 0.05,
           "period_s": 400}
    src = traffic.make_source({"arrival": "closed", "hot": hot},
                              {"fleet": FLEET}, seed=8)
    ev = src.columns(0, 4000)
    moved = src.hot_mask(0, 4000)
    assert 0.25 < moved.mean() < 0.35
    plain = traffic.make_source({"arrival": "closed"}, {"fleet": FLEET}, 8)
    ev0 = plain.columns(0, 4000)
    assert np.array_equal(ev["lat"][~moved], ev0["lat"][~moved])
    assert np.array_equal(ev["speed"], ev0["speed"])
    t = src.base_times(0, 4000)[moved].astype(np.float64)
    ang = 2 * np.pi * t / 400.0
    d = np.hypot(ev["lat"][moved] - (42.36 + 0.05 * np.cos(ang)),
                 ev["lng"][moved] - (-71.06 + 0.05 * np.sin(ang)))
    assert d.max() < 0.002 + 1e-5
    # the centre has moved: a quarter period apart the discs do not meet
    first = ev["lat"][moved][t < traffic.T0 + 20]
    later = ev["lat"][moved][(t >= traffic.T0 + 100) & (t < traffic.T0 + 120)]
    assert abs(first.mean() - later.mean()) > 0.03


def test_zipf_hubs_concentrate_the_fleet():
    fleet = {**FLEET, "n_vehicles": 2000, "capture_events": 2000,
             "hubs": {"count": 20, "zipf_s": 1.5, "radius_deg": 0.001}}
    cap = traffic.Capture(fleet, 2000, seed=9)
    even = traffic.Capture({**fleet, "hubs": None, "n_vehicles": 2000}, 2000, 9)

    def top_cell_share(c):
        """Share of the anchors in the fullest 0.01-degree square."""
        sq = np.floor(c.anchors / 0.01)
        _, counts = np.unique(sq, axis=0, return_counts=True)
        return counts.max() / len(sq)

    assert top_cell_share(cap) > 0.1 > 5 * top_cell_share(even)


def test_unknown_mix_key_and_arrival_refused():
    with pytest.raises(SpecError):
        traffic.make_source({"arrival": "closed", "burst": 3},
                            {"fleet": FLEET}, seed=1)
    with pytest.raises(SpecError):
        traffic.make_source({"arrival": "sometimes"}, {"fleet": FLEET}, 1)
    with pytest.raises(SpecError):
        traffic.make_source({"arrival": "open"}, {"fleet": FLEET}, 1)


def test_hash_uniform_deterministic_and_even():
    g = np.arange(100_000)
    u = traffic.hash_uniform(2**31 + 9, g, 1)
    assert np.array_equal(u, traffic.hash_uniform(2**31 + 9, g, 1))
    assert not np.array_equal(u, traffic.hash_uniform(2**31 + 9, g, 2))
    assert u.min() >= 0 and u.max() < 1
    assert np.allclose(np.histogram(u, 10, (0, 1))[0] / len(u), 0.1, atol=0.01)
