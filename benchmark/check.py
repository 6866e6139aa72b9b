"""The comparison that decides ``correct``.

What the run produced is read back from the store after the final
flush: the tile docs of every (resolution, window) pair and the
``positions_latest`` docs.  The plain reference (``reference/``)
recomputes both from every event the run sent, warm-up, window and
drain alike.  Tiles are compared in every window that either side has
(an event window of a pair, not the measured window), positions for
every vehicle.

The reference applies the watermark the configuration states, as a
micro-batch stream does: an event is dropped from a pair when its
window ended at or before the newest event time kept from earlier
micro-batches less the watermark.  The micro-batches are the source's
polls, which the generator records; events stamped in order are never
dropped.

The numbers compared, each against its limit in ``limits.json``:

- ``events_gap``: the largest gap, over the windows compared, between
  the events the window's tiles count and the events the run sent into
  that window that the watermark keeps.  Every valid event folds into exactly one group of each
  pair, whatever cell it lands in, so this is exact.
- ``moved_share``: the share of events whose group differs from the
  reference's, the sum over groups of |count - reference count| over
  twice the events.  The program snaps in float32 and the reference in
  float64, so points within a fraction of a metre of a cell edge may
  land in the neighbouring cell; a wrong snap moves most of them.
- ``speed_sum_gap`` / ``centroid_gap``: per window, the gap between the
  sum over groups of (average speed, centroid) times count and the sum
  over the window's events, relative to that sum.  Independent of
  which cell an event lands in, these check the fold's sums.
- ``p95_gap``: the median, over the reference's groups, of the gap in
  km/h between the program's p95 speed and the reference's (a group the
  program lacks reads 0 km/h).  Events moved across a cell edge shift a
  few groups' p95 a little; the median stays with the many groups whose
  events all agree.
- ``positions_gap``: the share of vehicles whose ``positions_latest``
  doc is not their newest event.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from benchmark.reference.groupby import (Groups, latest_positions,
                                         snap_cells, window_groups)

HERE = Path(__file__).resolve().parent
NUMBERS = ("events_gap", "moved_share", "speed_sum_gap", "centroid_gap",
           "p95_gap", "positions_gap")


def limits(base: Path = HERE) -> dict:
    with open(Path(base) / "limits.json", encoding="utf-8") as fh:
        return {k: float(v) for k, v in json.load(fh)["limits"].items()}


def groups_from_docs(docs, grid: str) -> dict:
    """{window start: Groups} of the store's tile docs on ``grid``."""
    rows = [(int(d["cellId"], 16), int(d["windowStart"].timestamp()),
             int(d["count"]), float(d["avgSpeedKmh"]),
             float(d["centroid"]["coordinates"][1]),
             float(d["centroid"]["coordinates"][0]),
             float(d.get("p95SpeedKmh", 0.0)))
            for d in docs if d.get("grid") == grid]
    out = {}
    if not rows:
        return out
    cell = np.array([r[0] for r in rows], np.uint64)
    ws = np.array([r[1] for r in rows], np.int64)
    vals = np.array([r[2:] for r in rows], np.float64)
    for w in np.unique(ws):
        sel = np.nonzero(ws == w)[0]
        sel = sel[np.argsort(cell[sel], kind="stable")]
        c = vals[sel, 0]
        out[int(w)] = Groups(cell=cell[sel], count=c.astype(np.int64),
                             speed_sum=vals[sel, 1] * c,
                             lat_sum=vals[sel, 2] * c,
                             lng_sum=vals[sel, 3] * c, p95=vals[sel, 4])
    return out


def compare_window(prog: Groups | None, ref: Groups) -> dict:
    """Partial sums of the numbers for one window."""
    n = float(ref.count.sum())
    if prog is None:
        prog = Groups(*(np.zeros(0, a.dtype) for a in (
            ref.cell, ref.count, ref.speed_sum, ref.lat_sum, ref.lng_sum,
            ref.p95)))
    cells = np.union1d(prog.cell, ref.cell)
    pc = np.zeros(len(cells))
    rc = np.zeros(len(cells))
    pc[np.searchsorted(cells, prog.cell)] = prog.count
    rc[np.searchsorted(cells, ref.cell)] = ref.count

    def rel(p, r):
        return abs(float(np.sum(p)) - float(np.sum(r))) / max(
            abs(float(np.sum(r))), 1e-30)

    p95 = np.zeros(len(ref))
    _, pi, ri = np.intersect1d(prog.cell, ref.cell, return_indices=True)
    p95[ri] = prog.p95[pi]
    return {
        "events_gap": abs(float(prog.count.sum()) - n),
        "moved": float(np.abs(pc - rc).sum()) / 2.0, "events": n,
        "speed_sum_gap": rel(prog.speed_sum, ref.speed_sum),
        "centroid_gap": max(rel(prog.lat_sum, ref.lat_sum),
                            rel(prog.lng_sum, ref.lng_sum)),
        "p95_gaps": np.abs(p95 - ref.p95),
    }


def compare_tiles(prog_by_pair: dict, ref_by_pair: dict) -> dict:
    """Numbers over every (pair, window) that either side has."""
    parts = []
    for pair, refs in ref_by_pair.items():
        prog = prog_by_pair.get(pair, {})
        for w in sorted(set(refs) | set(prog)):
            ref = refs.get(w)
            if ref is None:   # a window the program has and the events not
                ref = Groups(*(np.zeros(0, a.dtype) for a in (
                    prog[w].cell, prog[w].count, prog[w].speed_sum,
                    prog[w].lat_sum, prog[w].lng_sum, prog[w].p95)))
            parts.append(compare_window(prog.get(w), ref))
    moved = sum(p["moved"] for p in parts)
    events = sum(p["events"] for p in parts)
    return {
        "events_gap": max(p["events_gap"] for p in parts),
        "moved_share": moved / max(events, 1.0),
        "speed_sum_gap": max(p["speed_sum_gap"] for p in parts),
        "centroid_gap": max(p["centroid_gap"] for p in parts),
        "p95_gap": float(np.median(np.concatenate(
            [p["p95_gaps"] for p in parts]))),
    }


def compare_positions(docs, events: dict) -> float:
    """Share of vehicles whose positions_latest doc is not one of its
    newest events (or is missing).  ``events`` holds every sent event."""
    newest, keys = latest_positions(events["vid"], events["ts"],
                                    events["lat"], events["lng"])
    seen = {}
    for d in docs:
        k = int(d["vehicleId"].rsplit("-", 1)[1])
        lng, lat = d["loc"]["coordinates"]
        seen[k] = (int(round(d["ts"].timestamp())),
                   int(np.float32(lat).view(np.uint32)),
                   int(np.float32(lng).view(np.uint32)))
    sent = np.nonzero(newest > np.iinfo(np.int64).min)[0]
    bad = 0
    for k in sent.tolist():
        got = seen.get(k)
        if got is None or got[0] != newest[k] or (k, got[1], got[2]) not in keys:
            bad += 1
    return bad / max(len(sent), 1)


def watermark_kept(ts: np.ndarray, polls, windows_s, watermark_s: int
                   ) -> dict:
    """{window seconds: mask of the events a pair of that window size
    keeps}.  ``polls`` are the (g0, g1) bounds of the micro-batches in
    the order they were sent; before the first, nothing is dropped."""
    kept = {w: np.ones(len(ts), bool) for w in windows_s}
    newest = None
    for g0, g1 in polls:
        t = ts[g0:g1].astype(np.int64)
        if newest is None:
            any_kept = np.ones(len(t), bool)
        else:
            cutoff = newest - watermark_s
            any_kept = np.zeros(len(t), bool)
            for w in windows_s:
                k = (t // w) * w + w > cutoff
                kept[w][g0:g1] = k
                any_kept |= k
        if any_kept.any():
            top = int(t[any_kept].max())
            newest = top if newest is None else max(newest, top)
    return kept


def event_cells(source, ev: dict, res: int, precision: str = "f64"
                ) -> np.ndarray:
    """Each sent event's cell at ``res``.  An event sits at its capture
    row's position unless the hot set moved it, so each row is snapped
    once and the moved events on their own."""
    cap = source.capture
    n = len(ev["ts"])
    m = min(n, cap.n)
    cells = snap_cells(cap.lat[:m], cap.lng[:m], res, precision)[
        np.arange(n, dtype=np.int64) % cap.n]
    hot = source.hot_mask(0, n)
    if hot is not None and hot.any():
        cells[hot] = snap_cells(ev["lat"][hot], ev["lng"][hot], res,
                                precision)
    return cells


def reference(source, cfg, precision: str = "f64", ev: dict | None = None
              ) -> tuple[dict, dict]:
    """({(res, window seconds): {window start: Groups}}, the events):
    the plain reference's tiles of every event ``source`` sent, in
    ``precision`` (the control's is ``"bf16"``)."""
    ev = ev if ev is not None else source.columns(0, source.consumed)
    pairs = [(r, m * 60) for r in cfg.resolutions for m in cfg.windows_minutes]
    kept = watermark_kept(ev["ts"], source.polls, sorted({w for _, w in pairs}),
                          60 * cfg.watermark_minutes)
    out = {}
    for res in sorted({r for r, _ in pairs}):
        cells = event_cells(source, ev, res, precision)
        for r, window_s in pairs:
            if r != res:
                continue
            idx = np.nonzero(kept[window_s])[0]
            ws = (ev["ts"][idx].astype(np.int64) // window_s) * window_s
            order = np.argsort(ws, kind="stable")
            idx, ws = idx[order], ws[order]
            starts, first = np.unique(ws, return_index=True)
            bounds = list(first) + [len(idx)]
            out[(r, window_s)] = {
                int(w): window_groups(
                    cells[sel], ev["lat"][sel], ev["lng"][sel],
                    ev["speed"][sel], cfg.speed_hist_bins,
                    cfg.speed_hist_max_kmh, precision)
                for w, sel in ((w, idx[bounds[i]:bounds[i + 1]])
                               for i, w in enumerate(starts))}
    return out, ev


def judge(numbers: dict, lim: dict) -> bool:
    return all(numbers[k] <= lim[k] for k in NUMBERS)
