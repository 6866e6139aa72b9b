"""Rate, freshness, byte-count and comparison arithmetic at hand-made
inputs."""

import numpy as np
import pytest

from benchmark import check, harness, roofline, traffic
from benchmark.reference.groupby import Groups, p95_from_hist


def _rec(t_dispatch, n, offset=None, t_sink=None):
    r = {"t_dispatch": t_dispatch, "n_events": n, "offset": offset}
    if t_sink is not None:
        r["t_sink"] = t_sink
        r["t_flush"] = t_sink - 0.25
        r["t_ring"] = t_sink - 1.0
    return r


def test_events_per_s_counts_window_batches_and_drain():
    recs = [_rec(99.0, 500, t_sink=101.0),     # dispatched before the window
            _rec(100.5, 1000, t_sink=102.0),
            _rec(104.0, 1000, t_sink=111.0)]   # committed in the drain
    val, attempted, failed = harness.end_to_end("events_per_s", recs, 100.0,
                                                110.0, None)
    assert val == pytest.approx(2000 / 11.0)
    assert (attempted, failed) == (2000, 0)


def test_events_per_s_uncommitted_batch_is_failed():
    recs = [_rec(101.0, 1000, t_sink=103.0), _rec(105.0, 300)]
    val, attempted, failed = harness.end_to_end("events_per_s", recs, 100.0,
                                                110.0, None)
    assert val == pytest.approx(1000 / 3.0)
    assert (attempted, failed) == (1300, 300)


class _Live(traffic.LiveSource):
    """A live source of 10 events per second with a window segment
    starting at t=100 from event 40 (events 0-39 were warm-up)."""

    def __init__(self):
        self.schedule = traffic.Schedule(10.0)
        self.segments = [(0, 90.0), (40, 100.0)]


def test_freshness_samples_only_window_events_with_real_commit_times():
    src = _Live()
    recs = [_rec(99.0, 40, offset=40, t_sink=99.5),     # warm-up batch
            _rec(100.2, 10, offset=50, t_sink=101.5),   # due 100.0-100.9
            _rec(101.0, 60, offset=110, t_sink=108.0),  # 50 due in window
            _rec(108.0, 5, offset=115)]                 # never committed
    ages, due_n = harness.freshness_samples(recs, src, 100.0, 106.0)
    assert due_n == 60
    want = np.concatenate([101.5 - (100.0 + np.arange(10) / 10.0),
                           108.0 - (101.0 + np.arange(50) / 10.0)])
    assert np.allclose(np.sort(ages), np.sort(want))
    p50, attempted, failed = harness.end_to_end("freshness_p50_ms", recs,
                                                100.0, 106.0, src)
    assert p50 == pytest.approx(1e3 * np.percentile(want, 50))
    assert (attempted, failed) == (60, 0)
    p99, _, _ = harness.end_to_end("freshness_p99_ms", recs, 100.0, 106.0, src)
    assert p99 == pytest.approx(1e3 * np.percentile(want, 99))


def test_freshness_events_never_committed_are_failed():
    src = _Live()
    recs = [_rec(100.2, 10, offset=50, t_sink=101.0),
            _rec(101.0, 20, offset=70)]
    _, attempted, failed = harness.end_to_end("freshness_p50_ms", recs,
                                              100.0, 103.0, src)
    assert (attempted, failed) == (30, 20)


def test_fold_bytes_at_hand_computed_shapes():
    # 2^19 rows, in-program snap, 30,000 touched groups, 324-byte rows,
    # 52-byte emit rows: 2^19 * 17 + 30,000 * (2 * 324 + 52)
    got = roofline.fold_bytes(1 << 19, 0, 30_000, 324, 52)
    assert got == (1 << 19) * 17 + 30_000 * 700
    # host snap at three resolutions adds two 4-byte key lanes each
    assert roofline.fold_bytes(1 << 17, 3, 0, 324, 52) == (1 << 17) * (17 + 24)
    assert roofline.roofline_share(819e9, 2.0, 819e9) == pytest.approx(50.0)
    assert roofline.roofline_share(1.0, 0.0, 819e9) is None


def test_state_row_bytes():
    class Leaf:
        def __init__(self, shape, itemsize):
            self.shape = shape
            self.dtype = type("d", (), {"itemsize": itemsize})

    state = [Leaf((1024,), 4)] * 11 + [Leaf((1024, 64), 4), Leaf((1024, 4), 4)]
    assert roofline.state_row_bytes(state) == 11 * 4 + 64 * 4 + 16


def test_p95_from_hist_interpolates_within_the_bin():
    hist = np.array([[10, 10, 0, 0], [0, 0, 0, 0], [0, 0, 0, 20]])
    count = np.array([20, 0, 20])
    got = p95_from_hist(hist, count, hist_max=8.0)
    # 19th of 20 events: bin 1 holds events 11-20, 9 of its 10 -> 1.9 bins
    assert got[0] == pytest.approx(2.0 * 1.9)
    assert got[1] == 0.0
    assert got[2] == pytest.approx(2.0 * (3 + 19 / 20))


def _groups(cells, counts, speed=None):
    cells = np.array(cells, np.uint64)
    counts = np.array(counts, np.int64)
    speed = np.array(speed if speed is not None else counts * 10.0)
    return Groups(cell=cells, count=counts, speed_sum=speed,
                  lat_sum=counts * 42.0, lng_sum=counts * -71.0,
                  p95=np.full(len(cells), 30.0))


def test_compare_window_numbers():
    ref = _groups([1, 2, 3], [10, 20, 30])
    same = check.compare_window(_groups([1, 2, 3], [10, 20, 30]), ref)
    assert same["events_gap"] == 0 and same["moved"] == 0
    assert same["speed_sum_gap"] == 0 and not same["p95_gaps"].any()
    moved = check.compare_window(_groups([1, 2, 4], [11, 20, 29]), ref)
    assert moved["events_gap"] == 0          # every event still counted
    assert moved["moved"] == 30.0             # 1 + 30 + 29, halved
    # the group the program lacks reads 0 km/h against the reference's 30
    assert moved["p95_gaps"].tolist() == [0.0, 0.0, 30.0]
    lost = check.compare_window(_groups([1, 2], [10, 20]), ref)
    assert lost["events_gap"] == 30
    missing = check.compare_window(None, ref)
    assert missing["events_gap"] == 60 and missing["moved"] == 30.0


def test_compare_tiles_and_judge():
    ref = {(9, 300): {0: _groups([1, 2], [10, 10])}}
    prog = {(9, 300): {0: _groups([1, 2], [10, 10], speed=[100.0, 100.2])}}
    n = check.compare_tiles(prog, ref)
    assert n["speed_sum_gap"] == pytest.approx(0.2 / 200.0)
    assert n["p95_gap"] == 0.0
    n["positions_gap"] = 0.0
    lim = check.limits()
    assert check.judge(n, {**lim, "speed_sum_gap": 1e-2})
    assert not check.judge(n, {**lim, "speed_sum_gap": 1e-4})


def test_compare_tiles_counts_a_window_only_the_program_has():
    ref = {(9, 300): {0: _groups([1, 2], [10, 10])}}
    prog = {(9, 300): {0: _groups([1, 2], [10, 10]),
                       300: _groups([5], [4])}}
    n = check.compare_tiles(prog, ref)
    assert n["events_gap"] == 4 and n["speed_sum_gap"] > 1.0


def test_watermark_keeps_in_order_events_and_the_first_batch():
    ts = np.array([1000, 100, 2000, 2100, 1300, 1100])
    # nothing before the first batch: its stale event (100) is kept
    kept = check.watermark_kept(ts, [(0, 2), (2, 4), (4, 6)], [300], 600)
    # batch 3 sees newest 2100, cutoff 1500: window [1200, 1500) ends at
    # the cutoff and is dropped; [1500, 1800) would be kept
    assert kept[300].tolist() == [True, True, True, True, False, False]


def test_watermark_per_window_size():
    ts = np.array([3000, 2000, 2450, 5000, 2500])
    polls = [(0, 1), (1, 3), (3, 4), (4, 5)]
    kept = check.watermark_kept(ts, polls, [300, 900], 600)
    # cutoff 2400 for batch 2: 2000 is in [1800, 2100) (dropped) of the
    # 5-minute pair and [1800, 2700) (kept) of the 15-minute pair
    assert kept[300].tolist() == [True, False, True, True, False]
    assert kept[900].tolist() == [True, True, True, True, False]
    # batch 4's cutoff is 5000 - 600: 2500 is dropped from both; in one
    # batch nothing is
    assert check.watermark_kept(ts, [(0, 5)], [300], 600)[300].all()
