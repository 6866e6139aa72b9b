"""Profiling hooks (SURVEY.md §5.1: the reference has none; the BASELINE
metric is p50 micro-batch latency, so the hot loop must be traceable).

Two layers:

- spans: ``obs.spans.span`` times a region of the runtime with
  ``time.monotonic()`` into ``stream.metrics`` (the
  ``heatmap_batch_span_seconds{span=...}`` family, served at /metrics)
  and opens ``jax.profiler.TraceAnnotation("heatmap.<name>")`` around
  it, so the same regions name the host's time on the profiler's clock
  in a device trace.  ``obs.spans.step`` marks each micro-batch as a
  profiler step (``heatmap.step``).  Always on: with no profiler
  session an annotation is a few hundred nanoseconds.
- a ``jax.profiler`` device trace over a WINDOW of micro-batches,
  armed two ways:

  * at boot by env: ``HEATMAP_PROFILE_DIR=/tmp/trace`` captures
    ``HEATMAP_PROFILE_BATCHES`` (default 16) batches starting at
    ``HEATMAP_PROFILE_SKIP`` (default 2, skipping compile batches);
  * at runtime via :meth:`ProfilerTracer.arm` — the ``POST
    /debug/profile`` endpoint (serve.api) re-arms the stream runtime's
    tracer for a fresh window without a restart, the operability gap
    the boot-only env left open.

  The capture is viewable in TensorBoard / Perfetto.  One window may
  be in flight at a time: ``arm`` refuses (returns False → HTTP 409)
  while a window is pending or active.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading

log = logging.getLogger(__name__)


def _parse_window(e, skip: int, batches: int) -> tuple[int, int]:
    """Window knobs from env, defaults on garbage, clamped to sane
    bounds (a negative skip or a zero-batch window would arm a capture
    that can never produce a usable trace)."""
    try:
        skip = int(e.get("HEATMAP_PROFILE_SKIP", skip))
        batches = int(e.get("HEATMAP_PROFILE_BATCHES", batches))
    except ValueError as err:
        log.warning("bad profiler env value (%s); using skip=%d "
                    "batches=%d", err, skip, batches)
    return max(0, skip), max(1, batches)


class ProfilerTracer:
    """jax.profiler trace over a window of micro-batches.

    State machine: idle → pending (armed, epoch < skip) → active
    (tracing) → idle (window complete / stop()).  ``arm`` may re-enter
    only from idle.  The lock covers state TRANSITIONS; the per-batch
    fast path (idle, nothing armed) is one attribute read.
    """

    def __init__(self, env=None):
        e = os.environ if env is None else env
        self._lock = threading.Lock()
        self.dir = e.get("HEATMAP_PROFILE_DIR", "")
        self.skip, self.batches = 2, 16
        if self.dir:  # only parse knobs when profiling is requested
            self.skip, self.batches = _parse_window(e, self.skip,
                                                    self.batches)
        self._active = False
        self._done = bool(not self.dir)
        self._stop_at = 0

    # ------------------------------------------------------------ status
    @property
    def busy(self) -> bool:
        """A window is pending or actively tracing (arm would refuse)."""
        return self._active or not self._done

    def arm(self, dir_path: str, batches: int = 16, skip: int = 0,
            base_epoch: int = 0) -> bool:
        """Arm a capture window at runtime: trace ``batches``
        micro-batches starting ``skip`` batches after ``base_epoch``
        (the caller passes the runtime's current epoch, so ``skip``
        counts forward from NOW — the boot-time env counts from epoch
        0, where skipping compiles was the point).  False when a window
        is already pending/active — the caller answers 409."""
        if not dir_path:
            return False
        with self._lock:
            if self.busy:
                return False
            self.dir = dir_path
            self.skip = base_epoch + max(0, int(skip))
            self.batches = max(1, int(batches))
            self._done = False
            self._active = False
        log.info("profiler armed: %d batches from epoch %d -> %s",
                 self.batches, self.skip, self.dir)
        return True

    # ------------------------------------------------------------ window
    def batch(self, epoch: int):
        """Context manager wrapping one micro-batch."""
        if self._done and not self._active:
            return contextlib.nullcontext()
        return self._batch_ctx(epoch)

    @contextlib.contextmanager
    def _batch_ctx(self, epoch: int):
        import jax

        with self._lock:
            start = (not self._active and not self._done
                     and epoch >= self.skip)
            if start:
                try:
                    jax.profiler.start_trace(self.dir)
                    self._active = True
                    self._stop_at = epoch + self.batches
                    log.info("profiler: tracing %d batches -> %s",
                             self.batches, self.dir)
                except Exception as e:  # profiler races / unsupported
                    log.warning("profiler start failed: %s", e)
                    self._done = True
            active = self._active
        if active:
            try:
                yield
            finally:
                # stop at window end, and on an exception escaping the
                # batch — a dangling trace would be lost and would block
                # any later capture in this process
                if epoch + 1 >= self._stop_at or self._exception_pending():
                    self.stop()
        else:
            yield

    @staticmethod
    def _exception_pending() -> bool:
        import sys

        return sys.exc_info()[0] is not None

    def stop(self) -> None:
        """Flush an in-flight trace (runtime.close() calls this so a
        short stream still writes its partial capture).  Safe to call
        twice, and from a pending-but-not-started window (which it
        cancels)."""
        with self._lock:
            was_active, self._active = self._active, False
            self._done = True
        if not was_active:
            return
        import jax

        try:
            jax.profiler.stop_trace()
            log.info("profiler: trace written to %s", self.dir)
        except Exception as e:
            log.warning("profiler stop failed: %s", e)
