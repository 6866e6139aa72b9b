"""Named pipeline configurations mapping BASELINE.json's five configs onto
Config + source factories."""

from __future__ import annotations

import dataclasses
from typing import Callable

from heatmap_tpu.config import Config, load_config
from heatmap_tpu.stream.source import Source, SyntheticSource


@dataclasses.dataclass(frozen=True)
class Pipeline:
    name: str
    description: str
    config: Config
    make_source: Callable[[Config], Source]


def _kafka_or_synthetic(cfg: Config) -> Source:
    """Live pipelines consume the Kafka ingress when a broker is reachable
    (the reference contract; the framework's own wire client needs no
    client library); otherwise fall back to synthetic data so the pipeline
    still runs hermetically.

    ``HEATMAP_FEEDER=proc`` moves the fetch+decode leg into its own OS
    process over a shared-memory ring (stream/shmfeed.py) — the
    executor/driver split the reference gets from Spark; measured 7.3x
    end-to-end on a contended host (PERF_E2E.md).  The in-process source
    remains the default: one fewer moving part when the host has cores
    to spare."""
    import logging
    import os

    from heatmap_tpu.stream.source import KafkaSource

    try:
        if os.environ.get("HEATMAP_FEEDER") == "proc":
            from heatmap_tpu.stream.shmfeed import ShmFeederSource

            # probe reachability BEFORE spawning the feeder so the
            # synthetic fallback engages promptly.  Pinned to the wire
            # impl: it contacts the broker in its constructor and fails
            # fast, whereas a confluent client connects lazily and would
            # vacuously pass this probe
            KafkaSource(cfg.kafka_bootstrap, cfg.kafka_topic,
                        impl="wire").close()
            return ShmFeederSource(cfg.kafka_bootstrap, cfg.kafka_topic,
                                   batch_size=cfg.batch_size)
        return KafkaSource(cfg.kafka_bootstrap, cfg.kafka_topic)
    except (ImportError, ConnectionError, OSError, RuntimeError) as e:
        # RuntimeError covers KafkaError (unknown topic / leaderless)
        logging.getLogger(__name__).warning(
            "kafka unreachable (%s); using synthetic source", e)
        return SyntheticSource(n_vehicles=1000, events_per_second=1000)


def _synthetic_backfill(cfg: Config) -> Source:
    return SyntheticSource(
        n_events=10_000_000, n_vehicles=20_000, events_per_second=1_000_000,
    )


PIPELINES: dict[str, Pipeline] = {}


def _register(name, description, make_source, **cfg_overrides):
    # env (MONGO_URI, KAFKA_BOOTSTRAP, ...) applies like the reference's
    # import-time reads; the preset's own axes (res/windows/...) win on top
    cfg = load_config(None, **cfg_overrides)
    PIPELINES[name] = Pipeline(name, description, cfg, make_source)


# 1. the reference's default configuration (BASELINE config #1)
_register(
    "mbta_default",
    "MBTA Boston feed, H3_RES=8, TILE_MINUTES=5 (reference defaults)",
    _kafka_or_synthetic,
    # nothing pinned but the city: this is the "reference defaults"
    # preset, so H3_RES / TILE_MINUTES / etc. flow from env exactly as
    # they do in the reference (load_config derives the tuple axes)
    city="bos",
)

# 2. OpenSky global aircraft (BASELINE config #2)
_register(
    "opensky_global",
    "OpenSky global aircraft, H3_RES=7, 5-min window",
    _kafka_or_synthetic,
    city="global", h3_res=7, resolutions=(7,), windows_minutes=(5,),
    tile_minutes=5,
    state_capacity_log2=19,   # global cardinality
    # aircraft ground speeds run to ~1100 km/h; the default 256 km/h
    # range would saturate every cruise-speed cell's p95.  128 bins keep
    # the one-bin p95 error bound at 10 km/h over the wider range.
    speed_hist_bins=128, speed_hist_max_kmh=1280.0,
)

# 3. synthetic 10M-event backfill (BASELINE config #3)
_register(
    "synthetic_backfill",
    "Synthetic replay: 10M-event single-city backfill, H3_RES=9",
    _synthetic_backfill,
    city="bos", h3_res=9, resolutions=(9,), windows_minutes=(5,),
    tile_minutes=5,
    batch_size=1 << 19, state_capacity_log2=20,
)

# 4. multi-resolution hex pyramid (BASELINE config #4)
_register(
    "hex_pyramid",
    "Merged MBTA+OpenSky, multi-resolution 7/8/9 hex pyramid",
    _kafka_or_synthetic,
    city="bos", h3_res=8, resolutions=(7, 8, 9), windows_minutes=(5,),
    tile_minutes=5,
)

# 5. sliding multi-window with extended stats (BASELINE config #5)
_register(
    "multi_window",
    "Sliding multi-window (1/5/15-min), count + avgSpeed + p95-speed stats",
    _kafka_or_synthetic,
    city="bos", h3_res=8, resolutions=(8,), windows_minutes=(1, 5, 15),
    tile_minutes=5,  # the 5-min window keeps the reference grid/_id naming
)


def get_pipeline(name: str) -> Pipeline:
    if name not in PIPELINES:
        raise KeyError(f"unknown pipeline {name!r}; have {sorted(PIPELINES)}")
    return PIPELINES[name]


def build_runtime(p: Pipeline, source: Source | None = None,
                  n_devices: int | None = None):
    """The streaming job's wiring (``python -m heatmap_tpu.stream``):
    store, source and ``MicroBatchRuntime`` for pipeline ``p``.  Returns
    ``(runtime, store)``; the caller runs the runtime and closes the
    store.

    ``source`` replaces the pipeline's own source factory.  Devices:
    ``n_devices=None`` takes the pipeline's ``num_shards`` or, when that
    is 0, every visible device (a mesh once there are several), or one
    device for a multi-resolution pyramid, which a mesh refuses;
    ``n_devices=1`` pins the run to ``jax.devices()[0]``;
    ``n_devices=N`` builds an N-device mesh.  Raises when JAX found no
    accelerator and ``JAX_PLATFORMS=cpu`` did not choose the CPU."""
    import jax

    from heatmap_tpu.parallel import make_mesh
    from heatmap_tpu.sink import make_store
    from heatmap_tpu.stream import MicroBatchRuntime
    from heatmap_tpu.utils.jaxenv import require_accelerator

    require_accelerator()
    one_res = len(set(p.config.resolutions)) == 1
    n = (n_devices or p.config.num_shards
         or (len(jax.devices()) if one_res else 1))
    mesh = make_mesh(n) if n > 1 else None
    store = make_store(p.config)
    src = source if source is not None else p.make_source(p.config)
    return MicroBatchRuntime(p.config, src, store, mesh=mesh), store
