"""The benchmark's plain reference: its H3 snap agrees with the
program's scalar host oracle on a sample (the reference itself imports
nothing of the program; this test compares the two), and its group-by
with a loop."""

import numpy as np
import pytest

from benchmark.reference import groupby, h3


@pytest.mark.parametrize("res", [7, 8, 9])
def test_reference_snap_matches_host_oracle(res):
    from heatmap_tpu.hexgrid import host

    rng = np.random.default_rng(res)
    n = 400
    lat = np.radians(np.concatenate([rng.uniform(42.2, 42.5, n),
                                     rng.uniform(-85, 85, n)])).astype(np.float32)
    lng = np.radians(np.concatenate([rng.uniform(-71.3, -70.8, n),
                                     rng.uniform(-179, 179, n)])).astype(np.float32)
    got = h3.snap(lat, lng, res)
    want = [host.latlng_to_cell_int(float(a), float(b), res)
            for a, b in zip(lat, lng)]
    assert got.tolist() == want


def test_window_groups_match_a_loop():
    rng = np.random.default_rng(0)
    n = 3000
    lat = rng.uniform(42.30, 42.32, n).astype(np.float32)
    lng = rng.uniform(-71.07, -71.05, n).astype(np.float32)
    speed = rng.uniform(0, 300, n).astype(np.float32)
    cells = h3.snap(lat * groupby.D2R, lng * groupby.D2R, 9)
    assert np.array_equal(groupby.snap_cells(lat, lng, 9), cells)
    g = groupby.window_groups(cells, lat, lng, speed, 64, 256.0)
    assert g.count.sum() == n and len(g) == len(set(cells.tolist()))
    for k in range(0, len(g), max(1, len(g) // 7)):
        sel = cells == g.cell[k]
        assert g.count[k] == sel.sum()
        assert g.speed_sum[k] == pytest.approx(speed[sel].astype(np.float64).sum())
        assert g.lat_sum[k] == pytest.approx(lat[sel].astype(np.float64).sum())
        hist = np.bincount(np.clip((speed[sel] / 4.0).astype(int), 0, 63),
                           minlength=64)[None, :]
        assert g.p95[k] == pytest.approx(
            groupby.p95_from_hist(hist, np.array([sel.sum()]), 256.0)[0])


def test_bf16_control_moves_events_and_rounds_sums():
    rng = np.random.default_rng(1)
    n = 5000
    lat = rng.uniform(42.2, 42.5, n).astype(np.float32)
    lng = rng.uniform(-71.3, -70.8, n).astype(np.float32)
    speed = rng.uniform(0, 100, n).astype(np.float32)
    ref = groupby.window_groups(groupby.snap_cells(lat, lng, 9), lat, lng,
                                speed, 64, 256.0)
    ctl = groupby.window_groups(groupby.snap_cells(lat, lng, 9, "bf16"),
                                lat, lng, speed, 64, 256.0, "bf16")
    assert len(ctl) < len(ref) / 10     # bfloat16 radians: ~25 km apart


def test_latest_positions_ties_take_any_newest_event():
    vid = np.array([0, 1, 0, 0, 1])
    ts = np.array([5, 5, 7, 7, 6])
    lat = np.array([1, 2, 3, 4, 5], np.float32)
    lng = np.array([9, 8, 7, 6, 5], np.float32)
    newest, keys = groupby.latest_positions(vid, ts, lat, lng)
    assert newest.tolist() == [7, 6]
    bits = lambda x: int(np.float32(x).view(np.uint32))  # noqa: E731
    assert keys == {(0, bits(3), bits(7)), (0, bits(4), bits(6)),
                    (1, bits(5), bits(5))}
