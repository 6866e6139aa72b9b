"""Kafka ingest in a separate OS process over shared memory.

Replaces what the reference gets from Spark's executor/driver split
(reference: heatmap_stream.py:241-249 — the Kafka receiver runs in
executor JVMs while the driver schedules): here a FEEDER process owns
the wire fetch + columnar decode and hands finished `EventColumns`
batches to the runtime through a SharedMemory slot ring, so the
runtime's fold never shares a GIL (or an XLA-spinning core slice) with
socket reads and record decoding.

Round-5 motivation (PERF_E2E.md): inside the single-process runtime the
identical consume loop that standalone does ~70 ms per 262k batch
inflates ~10x — the fetch threads starve against the fold's device
dispatch in the same interpreter.  A second process gets its own GIL
and OS-scheduled core share; on a multi-core host the legs genuinely
overlap, and even on one core the OS time-slices far better than
Python's switch interval.

Protocol
--------
* a SharedMemory block holds `slots` fixed-capacity columnar slabs
  (8 f32/i32 lanes x `cap` rows, the EventColumns array fields);
* `full_q` carries (slot, n, gen, final, offsets, prov_delta,
  veh_delta, n_dropped) metas feeder -> runtime; `free_q` returns slot
  ids.  A poll that overshoots the slot capacity (the wire source
  consumes whole columnar records) spans MULTIPLE slots: only the last
  carries `final=True` and the post-poll offset, and the runtime side
  reassembles them into one logical batch — so a checkpointed offset
  can never advance past rows still sitting in the ring;
* provider/vehicle intern tables are synchronized by DELTA: the feeder
  sends only newly-interned names, both sides append in order, so the
  id arrays index identical tables;
* `seek` bumps a generation counter: the feeder flushes, re-seeks its
  KafkaSource, and stamps subsequent metas with the new generation —
  stale in-flight metas are discarded (slots recycled) on arrival.

The feeder child imports only the wire client + decode path (no jax —
the chip belongs to the parent process, and a second backend init must
never block ingest).
"""

from __future__ import annotations

import logging
import multiprocessing as mp
import os
import queue as queue_mod
import time
from multiprocessing import shared_memory
from typing import Any

import numpy as np

from heatmap_tpu.stream.events import EventColumns, empty_columns
from heatmap_tpu.stream.source import Source

log = logging.getLogger(__name__)

# lane name -> dtype; fixed order defines the shm layout
_LANES = (
    ("lat_rad", np.float32), ("lng_rad", np.float32),
    ("lat_deg", np.float32), ("lng_deg", np.float32),
    ("speed_kmh", np.float32), ("ts_s", np.int32),
    ("provider_id", np.int32), ("vehicle_id", np.int32),
)
_IDLE_SLEEP_S = 0.01


def _slot_views(buf, slots: int, cap: int):
    """Per-slot dict of lane views into the shared buffer."""
    out = []
    lane_bytes = cap * 4
    slot_bytes = lane_bytes * len(_LANES)
    for s in range(slots):
        views = {}
        off = s * slot_bytes
        for name, dt in _LANES:
            views[name] = np.frombuffer(buf, dtype=dt, count=cap,
                                        offset=off)
            off += lane_bytes
        out.append(views)
    return out


def _feeder_main(shm_name: str, slots: int, cap: int, bootstrap: str,
                 topic: str, full_q, free_q, cmd_q, ready_evt,
                 env: dict) -> None:
    """Child entry: attach the shm, run the loop in its own frame (so
    every numpy view into the mmap is freed before close), detach."""
    os.environ.update(env)
    shm = shared_memory.SharedMemory(name=shm_name)
    try:
        _feeder_loop(shm, slots, cap, bootstrap, topic, full_q, free_q,
                     cmd_q, ready_evt)
    finally:
        shm.close()


def _feeder_loop(shm, slots: int, cap: int, bootstrap: str, topic: str,
                 full_q, free_q, cmd_q, ready_evt) -> None:
    from heatmap_tpu.stream.source import KafkaSource

    src = KafkaSource(bootstrap, topic)
    # the consumer is ATTACHED (offsets pinned at latest) only now —
    # producers waiting to publish a bounded replay can go ahead
    ready_evt.set()
    try:
        views = _slot_views(shm.buf, slots, cap)
        gen = 0
        sent_p = sent_v = 0
        providers: list = []
        vehicles: list = []
        while True:
            # commands take priority (seek must not race new fills)
            try:
                cmd = cmd_q.get_nowait()
            except queue_mod.Empty:
                cmd = None
            if cmd is not None:
                if cmd[0] == "stop":
                    break
                if cmd[0] == "seek":
                    _g, off = cmd[1], cmd[2]
                    src.seek(off)
                    gen = _g
                    continue
            try:
                slot = free_q.get(timeout=0.25)
            except queue_mod.Empty:
                continue
            cols = src.poll(cap)
            n = len(cols) if cols is not None else 0
            if n == 0:
                free_q.put(slot)
                # an EMPTY meta keeps the runtime's poll from blocking a
                # full timeout when the topic is simply drained — but
                # only when none is pending, or a slow-polling runtime
                # accumulates stale metas without bound (r5 review)
                if full_q.empty():
                    full_q.put((None, 0, gen, True, src.offset(), [],
                                [], 0))
                time.sleep(_IDLE_SLEEP_S)
                continue
            # intern-table deltas: cols carries the source's GLOBAL
            # tables; send only what the runtime has not seen
            providers, vehicles = cols.providers, cols.vehicles
            pd = providers[sent_p:]
            vd = vehicles[sent_v:]
            sent_p, sent_v = len(providers), len(vehicles)
            off = src.offset()
            # the wire source consumes whole records and may overshoot
            # cap: span slots, final flag + offset on the LAST slice
            start = 0
            while start < n:
                if start > 0:
                    slot = free_q.get()  # blocking: the batch must land
                take = min(cap, n - start)
                v = views[slot]
                for name, _dt in _LANES:
                    v[name][:take] = getattr(cols, name)[start:start + take]
                final = start + take >= n
                full_q.put((slot, take, gen, final, off,
                            pd if final else [], vd if final else [],
                            cols.n_dropped if final else 0))
                start += take
    finally:
        src.close()


class ShmFeederSource(Source):
    """A `KafkaSource` running in its own OS process, delivering decoded
    columnar batches through shared memory (see module docstring)."""

    def __init__(self, bootstrap: str, topic: str, batch_size: int,
                 slots: int = 4):
        self.cap = int(batch_size)
        self.slots = int(slots)
        nbytes = self.slots * self.cap * 4 * len(_LANES)
        self._shm = shared_memory.SharedMemory(create=True, size=nbytes)
        self._views = _slot_views(self._shm.buf, self.slots, self.cap)
        ctx = mp.get_context("spawn")
        self._full_q = ctx.Queue()
        self._free_q = ctx.Queue()
        self._cmd_q = ctx.Queue()
        for s in range(self.slots):
            self._free_q.put(s)
        # the child must come up on the CPU decode path: the parent
        # owns the chip, and a child that touched it would fail or hang
        env = {k: v for k, v in os.environ.items()
               if k.startswith(("HEATMAP_", "KAFKA_"))}
        env["JAX_PLATFORMS"] = "cpu"
        self._ready = ctx.Event()
        self._proc = ctx.Process(
            target=_feeder_main,
            args=(self._shm.name, self.slots, self.cap, bootstrap, topic,
                  self._full_q, self._free_q, self._cmd_q, self._ready,
                  env),
            daemon=True)
        self._proc.start()
        # interpreter startup in the child is seconds on this host; the
        # construction contract matches KafkaSource's (consumer attached,
        # offsets pinned at latest, before __init__ returns).  Watch
        # child liveness too: a broker that died between the caller's
        # probe and the child's attach makes the child EXIT, and waiting
        # the full budget for a dead process would stall pipeline
        # startup ~2 minutes before the synthetic fallback engages
        deadline = time.monotonic() + 120
        while not self._ready.wait(timeout=0.25):
            if not self._proc.is_alive():
                self.close()
                raise RuntimeError(
                    "shm feeder process exited before attaching to the "
                    "broker (unreachable or incompatible)")
            if time.monotonic() >= deadline:
                self.close()
                raise RuntimeError("shm feeder process failed to attach "
                                   "to the broker")
        self._gen = 0
        self._offset: Any = None
        self._providers: list[str] = []
        self._vehicles: list[str] = []
        self.n_dropped_total = 0
        # poll sub-spans (Source.take_spans): wall spent WAITING on the
        # feeder process (full_q) vs copying slot lanes out of the shm
        # ring — a big "wait" means the feeder can't keep up (or shares
        # the core), a big "decode" means the slot memcpy itself costs
        self._spans = {"wait": 0.0, "decode": 0.0}

    def take_spans(self):
        out = {k: v for k, v in self._spans.items() if v > 0.0}
        self._spans = {"wait": 0.0, "decode": 0.0}
        return out

    # ------------------------------------------------------------- source
    def poll(self, max_events: int):
        """Like KafkaSource's columnar behavior, a poll may return MORE
        than ``max_events``: the feeder consumes whole records, and an
        oversize poll arrives as a multi-slot spanning batch reassembled
        here (offset stamped only on the final slice).  The runtime
        absorbs oversize returns through its carry path and defers
        checkpoints mid-carry, so offsets never advance past
        undelivered rows."""
        deadline = time.monotonic() + 1.0
        parts: list[dict] = []
        while True:
            timeout = max(0.05, deadline - time.monotonic())
            t_wait = time.monotonic()
            try:
                (slot, n, gen, final, off, pd, vd,
                 dropped) = self._full_q.get(timeout=timeout)
                self._spans["wait"] += time.monotonic() - t_wait
            except queue_mod.Empty:
                self._spans["wait"] += time.monotonic() - t_wait
                if parts:  # mid-assembly: the final slice is coming
                    deadline = time.monotonic() + 1.0
                    continue
                return empty_columns(self._providers, self._vehicles)
            # intern deltas are generation-INDEPENDENT (append-only, and
            # the feeder never resends them): a stale post-seek meta must
            # still contribute its names or later ids point past the
            # runtime-side tables (r5 review finding)
            self._providers.extend(pd)
            self._vehicles.extend(vd)
            if gen != self._gen:
                if slot is not None:
                    self._free_q.put(slot)  # pre-seek leftover
                parts = []  # any assembly in flight was pre-seek too
                continue
            if slot is None:
                if parts:
                    continue  # stray empty meta between slices
                self._offset = off
                return empty_columns(self._providers, self._vehicles)
            t_copy = time.monotonic()
            v = self._views[slot]
            parts.append({name: v[name][:n].copy()
                          for name, _dt in _LANES})
            self._free_q.put(slot)
            self._spans["decode"] += time.monotonic() - t_copy
            if not final:
                continue
            self._offset = off
            self.n_dropped_total += dropped
            t_copy = time.monotonic()
            if len(parts) == 1:
                lanes = parts[0]
            else:
                lanes = {name: np.concatenate([p[name] for p in parts])
                         for name, _dt in _LANES}
            self._spans["decode"] += time.monotonic() - t_copy
            return EventColumns(**lanes, providers=self._providers,
                                vehicles=self._vehicles,
                                n_dropped=dropped)

    def offset(self):
        return self._offset

    def seek(self, offset) -> None:
        self._gen += 1
        self._cmd_q.put(("seek", self._gen, offset))
        self._offset = offset

    def close(self) -> None:
        if self._proc.is_alive():
            self._cmd_q.put(("stop",))
            self._proc.join(timeout=5)
            if self._proc.is_alive():  # wedged on a dead broker socket
                self._proc.terminate()
                self._proc.join(timeout=5)
        self._views = None  # release exported pointers into the mmap
        try:
            self._shm.close()
            self._shm.unlink()
        except FileNotFoundError:  # pragma: no cover - already unlinked
            pass
