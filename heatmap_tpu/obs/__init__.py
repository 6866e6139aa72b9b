"""obs — pipeline-wide observability substrate.

Nine pieces, all dependency-free:

- :mod:`registry` — counters / gauges / fixed-bucket histograms with
  Prometheus text exposition (``Registry.expose_text``);
- :mod:`tracebuf` — bounded ring of structured per-micro-batch trace
  records (``/trace/recent``; optional size-rotated JSONL export via
  ``HEATMAP_TRACE_JSONL`` / ``HEATMAP_TRACE_JSONL_MAX_BYTES``);
- :mod:`lineage` — per-batch freshness lineage (event ts → sink-commit
  ack, staged through poll/prefetch/fold/ring/sink), the substrate of
  ``heatmap_event_age_seconds`` and ``/debug/freshness``;
- :mod:`delivery` — read-path delivery lineage (publish enqueue →
  feed transit → replica apply → fan-out → subscriber socket write),
  the substrate of ``heatmap_delivered_age_seconds``,
  ``/debug/delivery`` and ``/fleet/delivery`` (``HEATMAP_DELIVERY``);
- :mod:`flightrec` — crash-time state dump (trace tail, lineage tail,
  metrics snapshot, config) to ``HEATMAP_FLIGHTREC_DIR``;
- :mod:`runtimeinfo` — compile/retrace tracking on the jitted entry
  points, device memory watermarks, and the SLO watchdog that
  auto-captures an enriched flight record when /healthz degrades;
- :mod:`prof` — the always-available sampling Python stack profiler
  behind ``/debug/stacks`` (``HEATMAP_STACKPROF_HZ``);
- :mod:`xproc` — the file-backed supervisor→child metrics channel
  (``HEATMAP_SUPERVISOR_CHANNEL``), so the child's ``/metrics`` reports
  its parent supervisor's restart counters and they survive restarts;
  plus the per-child freshness summary files next to it;
- :mod:`spans` — the timed-region helper every layer uses: a
  ``heatmap_batch_span_seconds`` observation that is also a
  ``heatmap.<name>`` host span on the profiler's clock.

stream.metrics.Metrics builds on the registry and keeps its historical
``snapshot()`` JSON keys — served at ``/metrics.json`` — while
``/metrics`` serves the scrape-able exposition.  Metric names and SLO
knobs are documented in ARCHITECTURE.md §Observability.
"""

from heatmap_tpu.obs.delivery import (  # noqa: F401
    DELIVERY_STAGES,
    DeliveryTracker,
    delivery_enabled,
)
from heatmap_tpu.obs.flightrec import FlightRecorder  # noqa: F401
from heatmap_tpu.obs.lineage import LineageTracker  # noqa: F401
from heatmap_tpu.obs.prof import StackSampler, get_sampler  # noqa: F401
from heatmap_tpu.obs.registry import (  # noqa: F401
    DEFAULT_LAG_BUCKETS,
    DEFAULT_TIME_BUCKETS,
    Registry,
    render_flat_counters,
)
from heatmap_tpu.obs.tracebuf import TraceRing  # noqa: F401
from heatmap_tpu.obs.xproc import ENV_CHANNEL, SupervisorChannel  # noqa: F401
