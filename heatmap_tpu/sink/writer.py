"""AsyncWriter — background sink thread overlapping store I/O with compute.

The reference's foreachBatch writes block the driver between micro-batches
(SURVEY.md §3.3 bottleneck #2).  Here the device step for batch N+1 runs
while batch N's docs are upserted; the runtime's checkpoint commit waits on
``drain()`` so offsets only advance past durably-written batches
(SURVEY.md §7 hard part #5).

Transient sink failures are retried with backoff before the writer poisons
(the reference's producer survives API hiccups the same way,
mbta_to_kafka.py:86-97); every store write is an idempotent upsert, so a
retry after a half-applied bulk is safe.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Sequence

from heatmap_tpu.obs.spans import span
from heatmap_tpu.sink.base import Store

log = logging.getLogger(__name__)


class AsyncWriter:
    def __init__(self, store: Store, max_queue: int = 64,
                 retries: int = 3, backoff_s: float = 0.2, metrics=None,
                 view=None, audit=None):
        self.store = store
        # integrity-observatory ledger (obs.audit, HEATMAP_AUDIT=1):
        # this thread stamps the sink-commit and view-apply boundaries
        # (docs_committed / docs_view_applied) so the conservation
        # identity closes on the writer side.  Observe-only: counting
        # arithmetic, never on the write path's failure surface.  The
        # runtime may also assign the attribute post-construction.
        self.audit = audit
        # materialized tile view (query.matview): fed on THIS thread
        # right after each tile write returns from the store — i.e.
        # strictly after the rows are durable, so the query tier never
        # exposes a tile a Store read-back couldn't return.  A view
        # apply failure poisons the VIEW only (serving falls back to
        # Store renders); telemetry/read-path trouble never takes the
        # pipeline down.
        self.view = view
        # view seq recorded right after each successful apply, read by
        # the runtime's lineage view_applied stamp (obs.lineage): the
        # batch whose commit-ack barrier runs next is visible in the
        # view AT this seq.  Written only on the writer thread; torn
        # reads are impossible (int store).
        self.last_view_seq: int | None = None
        self.retries = retries
        self.backoff_s = backoff_s
        self._q: queue.Queue = queue.Queue(maxsize=max_queue)
        self._exc: BaseException | None = None
        self._written_tiles = 0
        self._written_positions = 0
        self._retried = 0
        # wall spent BLOCKED on a full queue at submit time.  The emit
        # ring hands the writer up to K batches of packed bodies in one
        # flush; if the store can't absorb the burst, the step thread
        # stalls HERE — this counter makes that visible at /metrics
        # (vs. a mystery gap in the batch spans).
        self._backpressure_s = 0.0
        # the store and view applies are timed on this thread into the
        # runtime's span family (sink_tiles / sink_positions / sink_view)
        self._metrics = metrics
        if metrics is not None:
            # queue depth read at scrape time (callback gauge) — a deep
            # queue means the sink can't keep up with the device step;
            # retry/poison counters live in the registry so /metrics
            # shows sink trouble without waiting for a snapshot merge
            metrics.gauge("heatmap_sink_queue_depth",
                          "pending write batches in the async sink queue",
                          fn=self._q.qsize)
            self._c_retries = metrics.registry.counter(
                "heatmap_sink_retries_total",
                "sink write attempts that failed and were retried")
            self._g_poisoned = metrics.gauge(
                "heatmap_sink_poisoned",
                "1 once a sink write exhausted its retries (writer "
                "permanently failed; offsets can no longer advance)")
        else:
            self._c_retries = self._g_poisoned = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="sink-writer")
        self._thread.start()

    def _apply(self, kind: str, docs) -> int:
        """One write with bounded retry (idempotent upserts → safe)."""
        delay = self.backoff_s
        for attempt in range(self.retries + 1):
            try:
                if kind == "tiles":
                    return self.store.upsert_tiles(docs)
                if kind == "tiles_packed":
                    body, meta = docs
                    return self.store.upsert_tiles_packed(body, meta)
                if kind == "positions_packed":
                    return self.store.upsert_positions_packed(docs)
                return self.store.upsert_positions(docs)
            except Exception:
                if attempt == self.retries:
                    raise
                self._retried += 1
                if self._c_retries is not None:
                    self._c_retries.inc()
                log.warning("sink write failed (attempt %d/%d); retrying "
                            "in %.1fs", attempt + 1, self.retries, delay,
                            exc_info=True)
                time.sleep(delay)
                delay *= 4
        raise AssertionError("unreachable")

    def _run(self) -> None:
        while True:
            item = self._q.get()
            try:
                if item is None:
                    return
                kind, docs = item
                if kind == "mark":
                    # commit-ack barrier (submit_mark): every write
                    # submitted before it has been applied by now.  A
                    # broken callback must not poison the writer —
                    # telemetry never takes the pipeline down — and a
                    # poisoned writer runs no marks: its writes were
                    # dropped, so acking them would lie.
                    if self._exc is None:
                        try:
                            docs()
                        except Exception:
                            log.exception("sink commit-mark callback "
                                          "failed")
                elif self._exc is None:
                    tiles = kind.startswith("tiles")
                    with span("sink_tiles" if tiles else "sink_positions",
                              self._metrics):
                        n = self._apply(kind, docs)
                    if tiles:
                        self._written_tiles += n
                        if self.audit is not None:
                            self.audit.add("docs_committed", n)
                        if n and self.view is not None \
                                and not self.view.poisoned:
                            with span("sink_view", self._metrics):
                                self._feed_view(kind, docs)
                    else:
                        self._written_positions += n
            except BaseException as e:  # poisons the writer permanently
                log.exception("sink write failed after %d retries",
                              self.retries)
                self._exc = e
                if self._g_poisoned is not None:
                    self._g_poisoned.set(1)
            finally:
                self._q.task_done()

    def _feed_view(self, kind: str, docs) -> None:
        try:
            if kind == "tiles_packed":
                # decode once here (apply_packed would decode
                # internally) so the audit ledger can count the docs
                # PRESENTED to the view — the same predicate the store
                # write counted, which is what makes the sink→view
                # boundary residual meaningful
                from heatmap_tpu.sink.base import packed_tile_docs

                body, meta = docs
                docs = packed_tile_docs(body, meta)
            self.view.apply_docs(docs)
            self.last_view_seq = getattr(self.view, "seq", None)
            if self.audit is not None:
                self.audit.add("docs_view_applied", len(docs))
        except Exception:
            log.exception("materialized view apply failed; query tier "
                          "falls back to store renders")
            self.view.poison()

    @property
    def poisoned(self) -> bool:
        return self._exc is not None

    def _check(self) -> None:
        # sticky: once a write is lost the writer stays failed, so a later
        # checkpoint can never commit offsets past the dropped batch
        if self._exc is not None:
            raise RuntimeError("async sink write failed") from self._exc

    def _put(self, item) -> None:
        """Enqueue, booking any time spent blocked on a full queue."""
        try:
            self._q.put_nowait(item)
            return
        except queue.Full:
            pass
        t0 = time.monotonic()
        self._q.put(item)
        self._backpressure_s += time.monotonic() - t0

    def submit_tiles(self, docs: Sequence[dict]) -> None:
        self._check()
        if docs:
            self._put(("tiles", docs))

    def submit_tiles_packed(self, body, meta) -> None:
        """Packed emit body rows + TilePackMeta; the store-side encode
        (C++ when available) runs on this writer thread, overlapping the
        next batch's device step."""
        self._check()
        self._put(("tiles_packed", (body, meta)))

    def submit_positions_packed(self, rows) -> None:
        """Columnar changed-vehicle rows (sink.base.PositionRows)."""
        self._check()
        if len(rows.ts_ms):
            self._put(("positions_packed", rows))

    def submit_positions(self, docs: Sequence[dict]) -> None:
        self._check()
        if docs:
            self._put(("positions", docs))

    def submit_mark(self, fn) -> None:
        """Run ``fn`` on the writer thread once every previously
        submitted write has been applied — the sink-commit ack hook the
        freshness lineage stamps its final stage with (obs.lineage)."""
        self._check()
        self._put(("mark", fn))

    def drain(self) -> None:
        """Block until every submitted write has been applied."""
        self._q.join()
        self._check()
        self.store.flush()

    def close(self) -> None:
        if not self.poisoned:
            self.drain()
        self._q.put(None)
        self._thread.join(timeout=10)
        self._check()

    @property
    def counters(self) -> dict:
        return {"tiles_written": self._written_tiles,
                "positions_written": self._written_positions,
                "sink_retries": self._retried,
                "sink_backpressure_ms": int(self._backpressure_s * 1e3)}
