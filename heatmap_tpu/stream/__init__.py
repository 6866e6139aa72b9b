"""stream — the micro-batch runtime (replaces Spark Structured Streaming).

The reference delegates micro-batch scheduling, offset/state checkpointing
and watermark bookkeeping to the Spark JVM (reference:
heatmap_stream.py:41-48,79-86,241-249).  This package owns all of it
in-framework:

- ``events``      — the canonical 8-field GPS event schema + columnar
                    parsing/validation (reference schema:
                    heatmap_stream.py:52-61, filters :96-108).
- ``source``      — pluggable pull sources with replayable offsets:
                    in-memory, JSONL replay, synthetic generator, Kafka
                    (gated on a client lib being installed).
- ``runtime``     — the driver loop: poll → fixed-shape batch → device
                    aggregation step(s) → async sink upserts → watermark →
                    checkpoint commit.
- ``checkpoint``  — offsets + device-state snapshots, atomic on disk
                    (replaces the Spark checkpointLocation contract,
                    heatmap_stream.py:37,244).
- ``metrics``     — the counters/latency spans BASELINE.json measures.
"""

from heatmap_tpu.stream.events import EventColumns, parse_events  # noqa: F401
from heatmap_tpu.stream.source import (  # noqa: F401
    JsonlReplaySource,
    MemorySource,
    RampSource,
    Source,
    SyntheticSource,
)

# The runtime (and engine behind it) touch jax at import; resolving them
# lazily keeps `import heatmap_tpu.stream` — and the package import that
# `python -m heatmap_tpu.stream` performs — free of device init, so the
# supervisor parent never claims the chip its children need.
_LAZY = {"MicroBatchRuntime", "StateOverflowError"}


def __getattr__(name):  # PEP 562
    if name in _LAZY:
        from heatmap_tpu.stream import runtime

        return getattr(runtime, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_LAZY))
