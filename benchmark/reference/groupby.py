"""Plain reference of the windowed tile aggregate and of
``positions_latest``, in NumPy float64.

For each (resolution, window) pair the tiles are a group-by of the
events on (H3 cell, window start): the count, the sums of speed,
latitude and longitude, and a speed histogram whose 95th percentile is
read with the interpolation the configuration states (linear within
the bin that holds the 0.95 * count-th event).  ``precision="bf16"`` is
the control: the same computation with its inputs and results rounded
to bfloat16, the next precision below the float32 the configuration
states for its sums.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from benchmark.reference import h3

D2R = np.float32(np.pi / 180.0)


@dataclass
class Groups:
    """One window's groups, sorted by cell."""

    cell: np.ndarray       # uint64
    count: np.ndarray      # int64 (float64 under the control)
    speed_sum: np.ndarray  # float64
    lat_sum: np.ndarray
    lng_sum: np.ndarray
    p95: np.ndarray

    def __len__(self) -> int:
        return len(self.cell)


def p95_from_hist(hist: np.ndarray, count: np.ndarray,
                  hist_max: float) -> np.ndarray:
    """95th percentile of each row's speed histogram: linear within the
    bin where the cumulative count first reaches 0.95 * count; the
    histogram's top edge when it never does."""
    n_bins = hist.shape[1]
    bin_w = hist_max / n_bins
    target = 0.95 * count.astype(np.float64)
    cum = np.cumsum(hist, axis=1).astype(np.float64)
    i = np.sum(cum < target[:, None], axis=1)
    ic = np.clip(i, 0, n_bins - 1)
    prev = np.where(ic > 0, cum[np.arange(len(ic)), np.maximum(ic - 1, 0)], 0.0)
    in_bin = hist[np.arange(len(ic)), ic].astype(np.float64)
    frac = np.where(in_bin > 0, (target - prev) / np.maximum(in_bin, 1), 0.0)
    p95 = np.where(i >= n_bins, hist_max, (ic + frac) * bin_w)
    return np.where(count > 0, p95, 0.0)


def _bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float64)


def snap_cells(lat_deg, lng_deg, res: int, precision: str = "f64"
               ) -> np.ndarray:
    """H3 cells at ``res`` of events given in float32 degrees, as they
    were sent; under the control the radians are rounded to bfloat16."""
    lat_rad = np.asarray(lat_deg, np.float32) * D2R
    lng_rad = np.asarray(lng_deg, np.float32) * D2R
    if precision == "bf16":
        lat_rad, lng_rad = _bf16(lat_rad), _bf16(lng_rad)
    elif precision != "f64":
        raise ValueError(f"unknown precision {precision!r}")
    return h3.snap(lat_rad, lng_rad, res)


def window_groups(cells, lat_deg, lng_deg, speed, n_bins: int,
                  hist_max: float, precision: str = "f64") -> Groups:
    """Group-by of one window's events on their cells (``snap_cells``)."""
    lat = np.asarray(lat_deg, np.float64)
    lng = np.asarray(lng_deg, np.float64)
    spd = np.asarray(speed, np.float64)
    if precision == "bf16":
        lat, lng, spd = _bf16(lat), _bf16(lng), _bf16(spd)
    uniq, inv, count = np.unique(cells, return_inverse=True,
                                 return_counts=True)
    g = len(uniq)
    bins = np.clip((np.asarray(speed, np.float64) / (hist_max / n_bins))
                   .astype(np.int64), 0, n_bins - 1)
    hist = np.bincount(inv * n_bins + bins, minlength=g * n_bins
                       ).reshape(g, n_bins)
    out = Groups(
        cell=uniq, count=count.astype(np.int64),
        speed_sum=np.bincount(inv, spd, g),
        lat_sum=np.bincount(inv, lat, g),
        lng_sum=np.bincount(inv, lng, g),
        p95=p95_from_hist(hist, count, hist_max))
    if precision == "bf16":
        out.count = _bf16(out.count)
        out.speed_sum, out.lat_sum, out.lng_sum, out.p95 = (
            _bf16(out.speed_sum), _bf16(out.lat_sum), _bf16(out.lng_sum),
            _bf16(out.p95))
    return out


def latest_positions(vid: np.ndarray, ts: np.ndarray, lat_deg: np.ndarray,
                     lng_deg: np.ndarray) -> tuple[np.ndarray, set]:
    """Per vehicle the newest timestamp, and the set of (vehicle,
    latitude bits, longitude bits) of its events at that timestamp: a
    position whose timestamp ties is any of them."""
    n_veh = int(vid.max()) + 1
    newest = np.full(n_veh, np.iinfo(np.int64).min, np.int64)
    np.maximum.at(newest, vid, ts.astype(np.int64))
    at = ts.astype(np.int64) == newest[vid]
    keys = set(zip(vid[at].tolist(),
                   np.asarray(lat_deg, np.float32)[at].view(np.uint32).tolist(),
                   np.asarray(lng_deg, np.float32)[at].view(np.uint32).tolist()))
    return newest, keys
