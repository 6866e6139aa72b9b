"""The one traffic generator.  A mix file (``mixes/<name>.json``) holds
its parameters and the configuration's ``fleet`` its vehicles, so a new
mix or a new fleet is a new data file, read by the code here.

Every event is a function of its global index ``g`` and the seed.  A
capture of ``capture_events`` rows is drawn once at set-up: vehicle
``g % n_vehicles`` on a circular orbit about its own anchor (the way
the program's ``SyntheticSource`` moves its fleet), the anchors spread
evenly over the configuration's city box or, with ``hubs``, about hubs
picked with Zipf weights.  Event ``g`` is capture row
``g % capture_events``; the mix sets its time and may move it.

Fleet keys (the configuration's ``fleet``): ``n_vehicles``, ``center``
(degrees), ``radius_deg`` (half the box's side), ``report_interval_s``
(so ``n_vehicles / report_interval_s`` events per event-second),
``capture_events`` and, optionally, ``hubs``:
``{"count", "zipf_s", "radius_deg"}``.

Mix keys:

- ``arrival``: ``"closed"`` replays the capture as fast as the runtime
  polls, event time advancing ``1 / events per event-second`` per event
  and going on from lap to lap; ``"open"`` makes events fall due on the
  wall clock, each stamped with its due second, and a poll returns
  every event due (up to the batch), so a slow step leaves a backlog.
- ``rate_events_per_s`` (open): the offered rate; or ``rate_schedule``,
  ``[[start_s, rate], ...]`` from ``start_s`` 0: rates that hold from
  each start, in seconds from the start of the run's segment (a ramp,
  a burst).
- ``late``: bands ``[{"share", "min_s", "max_s"}, ...]``: that share of
  the events is stamped a whole number of seconds in [min_s, max_s]
  before its place in the stream: a few seconds for reports out of
  order, past the watermark for events the fold has to drop.
- ``hot``: ``{"share", "radius_deg", "orbit_deg", "period_s"}``: that
  share of the events is moved into a disc of ``radius_deg`` whose
  centre circles the city's centre at ``orbit_deg`` once every
  ``period_s`` of event time (a moving hot set).
- ``runtime``: the cell's changes to the program's ``Config`` (feed
  batch, trigger, reducers, governor).
- ``why``: one line.

Which events a band or the hot set takes, and how far, comes from a
hash of (seed, g), so it does not depend on where polls cut the stream.
A source can be stopped (it produces nothing more and hands out what
is already due) and restarted; it records the bounds of every poll it
answered, which the reference needs to apply the watermark micro-batch
by micro-batch.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmark.spec import SpecError
from heatmap_tpu.stream.events import columns_from_arrays
from heatmap_tpu.stream.source import Source

T0 = 1_699_999_800   # replay event-time origin (a 5-minute boundary)
MIX_KEYS = frozenset({"arrival", "rate_events_per_s", "rate_schedule",
                      "late", "hot", "runtime", "why"})
# hash streams of the per-event draws
_LATE_BAND, _LATE_BY, _HOT, _HOT_R, _HOT_TH = range(1, 6)


def hash_uniform(seed: int, g: np.ndarray, stream: int) -> np.ndarray:
    """A uniform draw in [0, 1) for each index in ``g`` (splitmix64 of
    the index under a key made from ``seed`` and ``stream``)."""
    key = (int(seed) * 0x632BE59BD9B4E019 + stream * 0x85EBCA77C2B2AE63) \
        % (1 << 64)
    x = np.asarray(g, np.int64).astype(np.uint64)
    x = x * np.uint64(0x9E3779B97F4A7C15) ^ np.uint64(key)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return (x >> np.uint64(11)).astype(np.float64) * (1.0 / (1 << 53))


class Capture:
    """``n`` events of a seeded fleet: degrees and km/h as float32,
    vehicle index as int32."""

    def __init__(self, fleet: dict, n: int, seed: int, block: int = 1 << 20):
        nv = int(fleet["n_vehicles"])
        lat0, lng0 = fleet["center"]
        rad = float(fleet["radius_deg"])
        eps = nv / float(fleet["report_interval_s"])  # events per event-second
        rng = np.random.default_rng(int(seed))
        hubs = fleet.get("hubs")
        if hubs is None:
            anchor_lat = lat0 + rng.uniform(-rad, rad, nv)
            anchor_lng = lng0 + rng.uniform(-rad, rad, nv)
        else:
            k = int(hubs["count"])
            hub_lat = lat0 + rng.uniform(-rad, rad, k)
            hub_lng = lng0 + rng.uniform(-rad, rad, k)
            w = 1.0 / np.arange(1, k + 1) ** float(hubs["zipf_s"])
            h = rng.choice(k, nv, p=w / w.sum())
            spread = float(hubs["radius_deg"])
            anchor_lat = hub_lat[h] + rng.uniform(-spread, spread, nv)
            anchor_lng = hub_lng[h] + rng.uniform(-spread, spread, nv)
        orbit = rng.uniform(0.002, 0.03, nv)              # degrees
        speed = rng.uniform(10.0, 90.0, nv)               # km/h
        omega = (speed / 3.6) / (orbit * 111_000.0)       # rad per second
        phase = rng.uniform(0.0, 2.0 * math.pi, nv)
        self.n, self.n_vehicles, self.events_per_s = int(n), nv, eps
        self.center = (float(lat0), float(lng0))
        self.anchors = np.stack([anchor_lat, anchor_lng], 1)
        self.lat = np.empty(self.n, np.float32)
        self.lng = np.empty(self.n, np.float32)
        self.speed = np.empty(self.n, np.float32)
        self.vid = np.empty(self.n, np.int32)
        for s in range(0, self.n, block):
            i = np.arange(s, min(s + block, self.n), dtype=np.int64)
            v = i % nv
            ang = omega[v] * (i / eps) + phase[v]
            self.lat[s:s + len(i)] = anchor_lat[v] + orbit[v] * np.cos(ang)
            self.lng[s:s + len(i)] = anchor_lng[v] + orbit[v] * np.sin(ang)
            self.speed[s:s + len(i)] = np.maximum(
                speed[v] + 2.0 * np.sin(0.7 * i), 0.0)
            self.vid[s:s + len(i)] = v
        self.vehicles = [f"veh-{k}" for k in range(nv)]

    def rows(self, g0: int, g1: int) -> np.ndarray:
        """Capture rows of global events [g0, g1)."""
        return np.arange(g0, g1, dtype=np.int64) % self.n


def _late_bands(bands) -> list[tuple[float, int, int]]:
    out, total = [], 0.0
    for b in bands or ():
        share, lo, hi = float(b["share"]), int(b["min_s"]), int(b["max_s"])
        if share < 0 or not 0 <= lo <= hi:
            raise SpecError(f"bad late band {b!r}")
        total += share
        out.append((share, lo, hi))
    if total > 1.0:
        raise SpecError(f"late bands take {total} of the events")
    return out


class _Stream(Source):
    """Shared by both arrivals: the events' columns, the mix's late
    bands and hot set, consumed count, stop/restart and the polls."""

    def __init__(self, capture: Capture, mix: dict, seed: int,
                 annotate=None):
        self.capture = capture
        self.seed = int(seed)
        self.late = _late_bands(mix.get("late"))
        self.hot = mix.get("hot")
        if self.hot is not None and not 0 <= float(self.hot["share"]) <= 1:
            raise SpecError(f"bad hot set {self.hot!r}")
        self.consumed = 0
        self.stopped = False
        self.polls: list[tuple[int, int]] = []
        self._annotate = annotate

    def _due(self, max_events: int) -> int:
        raise NotImplementedError

    def base_times(self, g0: int, g1: int) -> np.ndarray:
        """Event time (int64 seconds) of events [g0, g1) at their place
        in the stream, before the late bands."""
        raise NotImplementedError

    def timestamps(self, g0: int, g1: int) -> np.ndarray:
        """The timestamps events [g0, g1) are sent with (int32)."""
        ts = self.base_times(g0, g1)
        if self.late:
            g = np.arange(g0, g1, dtype=np.int64)
            u = hash_uniform(self.seed, g, _LATE_BAND)
            by = hash_uniform(self.seed, g, _LATE_BY)
            lo = 0.0
            for share, a, b in self.late:
                sel = (u >= lo) & (u < lo + share)
                lo += share
                ts = np.where(sel, ts - a - np.floor(by * (b - a + 1))
                              .astype(np.int64), ts)
        return ts.astype(np.int32)

    def hot_mask(self, g0: int, g1: int) -> np.ndarray | None:
        """Which of events [g0, g1) the hot set moved (None: no hot set)."""
        if self.hot is None:
            return None
        g = np.arange(g0, g1, dtype=np.int64)
        return hash_uniform(self.seed, g, _HOT) < float(self.hot["share"])

    def columns(self, g0: int, g1: int) -> dict:
        """Events [g0, g1) as sent: ``lat``, ``lng`` (float32 degrees),
        ``speed`` (float32 km/h), ``vid`` (int32), ``ts`` (int32 s)."""
        c = self.capture
        r = c.rows(g0, g1)
        lat, lng = c.lat[r], c.lng[r]
        hot = self.hot_mask(g0, g1)
        if hot is not None and hot.any():
            g = np.arange(g0, g1, dtype=np.int64)[hot]
            h = self.hot
            ang = (2.0 * math.pi / float(h["period_s"])) * \
                self.base_times(g0, g1)[hot].astype(np.float64)
            rr = float(h["radius_deg"]) * np.sqrt(
                hash_uniform(self.seed, g, _HOT_R))
            th = 2.0 * math.pi * hash_uniform(self.seed, g, _HOT_TH)
            orbit = float(h["orbit_deg"])
            lat[hot] = c.center[0] + orbit * np.cos(ang) + rr * np.cos(th)
            lng[hot] = c.center[1] + orbit * np.sin(ang) + rr * np.sin(th)
        return {"lat": lat, "lng": lng, "speed": c.speed[r], "vid": c.vid[r],
                "ts": self.timestamps(g0, g1)}

    def poll(self, max_events: int):
        if self._annotate is not None:
            with self._annotate("source.poll"):
                return self._poll(max_events)
        return self._poll(max_events)

    def _poll(self, max_events: int):
        n = min(int(max_events), self._due(max_events))
        if n <= 0:
            return None
        g0, g1 = self.consumed, self.consumed + n
        ev = self.columns(g0, g1)
        self.consumed = g1
        self.polls.append((g0, g1))
        return columns_from_arrays(
            ev["lat"], ev["lng"], ev["speed"], ev["ts"], vehicle_id=ev["vid"],
            providers=["bench"], vehicles=self.capture.vehicles)

    def offset(self):
        return self.consumed

    @property
    def exhausted(self) -> bool:
        return self.stopped and self._due(1) <= 0

    def stop(self) -> None:
        self.stopped = True

    def start(self) -> None:
        self.stopped = False


class ReplaySource(_Stream):
    """Closed loop: a full batch whenever polled, until stopped."""

    def __init__(self, capture: Capture, mix: dict | None = None,
                 seed: int = 0, annotate=None):
        super().__init__(capture, mix or {}, seed, annotate)
        self.t0 = T0

    def _due(self, max_events: int) -> int:
        return 0 if self.stopped else int(max_events)

    def base_times(self, g0: int, g1: int) -> np.ndarray:
        g = np.arange(g0, g1, dtype=np.int64)
        return self.t0 + np.floor(g / self.capture.events_per_s).astype(
            np.int64)


class Schedule:
    """Offered rate over the seconds of a segment: ``[[start_s, rate],
    ...]`` from 0 with rising starts, or one number for a fixed rate."""

    def __init__(self, spec):
        pieces = [[0.0, spec]] if np.isscalar(spec) else spec
        self.starts = np.array([float(s) for s, _ in pieces])
        self.rates = np.array([float(r) for _, r in pieces])
        if self.starts[0] != 0 or (np.diff(self.starts) <= 0).any() \
                or (self.rates <= 0).any():
            raise SpecError(f"bad rate schedule {spec!r}")
        # events due by each piece's start
        self.cum = np.concatenate([[0.0], np.cumsum(
            self.rates[:-1] * np.diff(self.starts))])

    def events_by(self, tau: float) -> float:
        """Events due in the first ``tau`` seconds."""
        i = int(np.searchsorted(self.starts, tau, side="right")) - 1
        return self.cum[i] + self.rates[i] * (tau - self.starts[i])

    def offsets(self, k: np.ndarray) -> np.ndarray:
        """Seconds into the segment at which event ``k`` (0-based) falls
        due."""
        i = np.searchsorted(self.cum, k, side="right") - 1
        return self.starts[i] + (k - self.cum[i]) / self.rates[i]


class LiveSource(_Stream):
    """Open loop at ``schedule`` (events per wall second).  Each
    (re)start begins a segment: event ``g`` of a segment started at wall
    time ``t`` with first event ``g0`` falls due at ``t`` plus the
    schedule's offset of event ``g - g0``."""

    def __init__(self, capture: Capture, schedule, clock=time.time,
                 mix: dict | None = None, seed: int = 0, annotate=None):
        super().__init__(capture, mix or {}, seed, annotate)
        self.schedule = Schedule(schedule)
        self.clock = clock
        self.segments: list[tuple[int, float]] = []   # (g0, t0)
        self.stopped = True
        self._frozen = 0

    def start(self) -> None:
        """Begin a segment now; the backlog must be empty."""
        if self.produced() != self.consumed:
            raise RuntimeError("restart with events still due")
        self.segments.append((self.consumed, self.clock()))
        self.stopped = False

    def stop(self) -> None:
        """Produce nothing more; what is already due is still handed out."""
        self._frozen = self.produced()
        self.stopped = True

    def produced(self) -> int:
        """Events due so far."""
        if self.stopped or not self.segments:
            return self._frozen
        g0, t0 = self.segments[-1]
        return g0 + int(self.schedule.events_by(self.clock() - t0))

    def backlog(self) -> int:
        return self.produced() - self.consumed

    def window_due(self, t_end: float) -> tuple[int, int]:
        """Events [g0, g1) of the newest segment due before ``t_end``."""
        g0, t0 = self.segments[-1]
        n = max(0, int(np.ceil(self.schedule.events_by(t_end - t0))))
        return g0, g0 + n

    def _due(self, max_events: int) -> int:
        return self.backlog()

    def due_times(self, g0: int, g1: int) -> np.ndarray:
        """Wall-clock due time of events [g0, g1)."""
        g = np.arange(g0, g1, dtype=np.int64)
        starts = np.array([s for s, _ in self.segments], np.int64)
        t_starts = np.array([t for _, t in self.segments], np.float64)
        k = np.searchsorted(starts, g, side="right") - 1
        return t_starts[k] + self.schedule.offsets(g - starts[k])

    def base_times(self, g0: int, g1: int) -> np.ndarray:
        return np.floor(self.due_times(g0, g1)).astype(np.int64)


def make_source(mix: dict, config: dict, seed: int, annotate=None):
    """The mix's source over a capture drawn from ``seed``."""
    unknown = sorted(set(mix) - MIX_KEYS)
    if unknown:
        raise SpecError(f"unknown mix keys {unknown}")
    fleet = config["fleet"]
    capture = Capture(fleet, int(fleet["capture_events"]), seed)
    if mix.get("arrival") == "closed":
        return ReplaySource(capture, mix, seed, annotate=annotate)
    if mix.get("arrival") == "open":
        schedule = mix.get("rate_schedule", mix.get("rate_events_per_s"))
        if schedule is None:
            raise SpecError("an open mix needs rate_events_per_s or "
                            "rate_schedule")
        return LiveSource(capture, schedule, mix=mix, seed=seed,
                          annotate=annotate)
    raise SpecError(f"unknown arrival {mix.get('arrival')!r}")
