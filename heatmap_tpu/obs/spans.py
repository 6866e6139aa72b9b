"""Spans: timed regions that also show on the profiler's clock.

:class:`span` times a region with ``time.monotonic()`` and opens
``jax.profiler.TraceAnnotation("heatmap.<name>")`` around it, so the
same region is a ``heatmap_batch_span_seconds{span=...}`` observation
(``stream.metrics``) and a named host span in a device trace.
:func:`step` marks one micro-batch as a profiler step
(``heatmap.step``).  Always on: with no profiler session an annotation
is a few hundred nanoseconds.
"""

from __future__ import annotations

import time

ANNOTATION_PREFIX = "heatmap."


class span:
    """``with span(name, into):`` times the block with
    ``time.monotonic()`` inside ``jax.profiler.TraceAnnotation(
    "heatmap.<name>")``.

    ``into`` receives the seconds: a dict (one batch's spans, recorded
    by ``Metrics.observe_batch`` when the batch is dispatched) gains
    them under ``name``, added to what is there; an object with
    ``observe_span`` (a ``Metrics``, from any thread) observes them;
    None only annotates.  ``seconds`` holds them after the block."""

    __slots__ = ("name", "into", "seconds", "_ann", "_t0")

    def __init__(self, name: str, into=None):
        self.name = name
        self.into = into
        self.seconds = 0.0

    def __enter__(self) -> "span":
        import jax

        self._ann = jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + self.name)
        self._ann.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.monotonic() - self._t0
        self._ann.__exit__(*exc)
        into = self.into
        if isinstance(into, dict):
            into[self.name] = into.get(self.name, 0.0) + self.seconds
        elif into is not None:
            into.observe_span(self.name, self.seconds)
        return False


def step(epoch: int):
    """The profiler's step marker around one micro-batch."""
    import jax

    return jax.profiler.StepTraceAnnotation(ANNOTATION_PREFIX + "step",
                                            step_num=epoch)
