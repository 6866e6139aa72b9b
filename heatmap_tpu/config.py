"""Flat env-var configuration, drop-in compatible with the reference.

The reference reads all configuration from environment variables with inline
defaults at import time (reference: heatmap_stream.py:21-37, app.py:11-13,
mbta_to_kafka.py:17-19; documented in its README.md:163-188).  We honor the
same names and defaults so a reference deployment can switch frameworks
without touching its environment, and add TPU-specific knobs on top.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Mapping, Sequence


def _int(env: Mapping[str, str], name: str, default: int) -> int:
    return int(env.get(name, default))


def _float(env: Mapping[str, str], name: str, default: float) -> float:
    return float(env.get(name, default))


def _ints(env: Mapping[str, str], name: str, default: str) -> tuple[int, ...]:
    return tuple(int(x) for x in str(env.get(name, default)).split(",") if x != "")


@dataclasses.dataclass(frozen=True)
class Config:
    # --- reference-compatible knobs (heatmap_stream.py:21-37) ---
    mongo_uri: str = "mongodb://127.0.0.1:27017"
    mongo_db: str = "mobility"
    city: str = "ath"
    h3_res: int = 8                    # typical 7-9 for city heatmaps
    tile_minutes: int = 5              # aggregation window size
    ttl_minutes: int = 45              # tile TTL after window end
    kafka_bootstrap: str = "localhost:9092"
    kafka_topic: str = "mobility.positions.v1"
    checkpoint_dir: str = "/tmp/heatmap-checkpoint"
    # --- reference-compatible knobs (app.py:11-13, mbta_to_kafka.py:17-19) ---
    refresh_ms: int = 5000
    mbta_api_key: str = ""
    # --- watermark (heatmap_stream.py:107 hardcodes "10 minutes") ---
    watermark_minutes: int = 10
    # --- TPU-native extensions (BASELINE.json) ---
    backend: str = "tpu"               # HEATMAP_BACKEND: "tpu" | "cpu"
    resolutions: tuple[int, ...] = (8,)     # multi-res hex pyramid, e.g. 7,8,9
    windows_minutes: tuple[int, ...] = (5,)  # sliding multi-window, e.g. 1,5,15
    batch_size: int = 1 << 17          # events per fixed-shape micro-batch
    state_capacity_log2: int = 17      # open-addressing table slots per shard
    state_max_log2: int = 0            # growth ceiling; 0 = capacity+4 (16x);
                                       # == state_capacity_log2 disables growth
    # Per-cell speed histogram driving the p95 stats.  ACCURACY BOUND:
    # interpolated hist-p95 is exact to within one bin width
    # (speed_hist_max_kmh / speed_hist_bins — 4 km/h at the defaults;
    # tested in tests/test_emit_pack.py), and speeds >= the max saturate
    # into the last bin, capping reported p95 at the max.  Size the max
    # for the fleet: city traffic fits 256; aircraft need ~1280 (the
    # opensky_global pipeline preset raises both knobs).
    speed_hist_bins: int = 64
    speed_hist_max_kmh: float = 256.0
    num_shards: int = 0                # 0 = use all local devices
    bucket_factor: float = 2.0         # all_to_all lane skew tolerance
    trigger_ms: int = 0                # 0 = as fast as possible (ref default)
    on_overflow: str = "error"         # "error": metric + rate-limited log;
                                       # "fail": stop the run (data loss is
                                       # never silent either way)
    serve_host: str = "127.0.0.1"
    serve_port: int = 5000
    store: str = "auto"                # "auto" | "memory" | "mongo" | "jsonl"
    grow_margin: str = "worst"         # "worst" | "observed": free-slot
                                       # margin the auto-grower keeps.
                                       # worst = 2x batch (a batch CAN
                                       # mint one group per event, so
                                       # overflow is structurally
                                       # impossible below the ceiling —
                                       # but the slab ends up 4x batch
                                       # and the bandwidth-bound fold
                                       # pays ~3x for the guarantee).
                                       # observed = 4x the largest
                                       # per-batch group minting seen so
                                       # far (floor batch/8): near-peak
                                       # throughput for real workloads.
                                       # A burst beyond the observed
                                       # margin overflows LOUDLY
                                       # (/metrics + log); pair with
                                       # HEATMAP_ON_OVERFLOW=fail for a
                                       # lossless stop-and-replay
                                       # backstop — without it the
                                       # overflowing groups are dropped
                                       # (the runtime warns at startup)
    emit_pull: str = "auto"            # "auto" | "full" | "prefix": prefix
                                       # pulls head row + live-rows bucket
                                       # (2 transfers, far fewer bytes);
                                       # auto = prefix off-CPU (single-
                                       # device paths; sharded pulls stay
                                       # full)
    emit_flush_k: int = 8              # HEATMAP_EMIT_FLUSH_K: device-
                                       # resident emit-ring depth — packed
                                       # emits of up to K batches stay on
                                       # device and are pulled in ONE
                                       # flush, amortizing the per-batch
                                       # D2H round trip.  Flush is
                                       # forced before checkpoints, on
                                       # idle polls, at close, and under
                                       # watermark/growth pressure, so
                                       # sink semantics and replay
                                       # equivalence are unchanged.  1 =
                                       # per-batch pull (the pre-ring
                                       # behavior); multi-host runs force
                                       # 1 (lockstep accounting).
    prefetch_batches: int = 1          # HEATMAP_PREFETCH_BATCHES: batches
                                       # the runtime polls/pads/transfers
                                       # AHEAD of the fold so the H2D feed
                                       # overlaps device compute (double
                                       # buffering).  0 disables; multi-
                                       # host runs force 0 (the lockstep
                                       # collectives pin poll ordering).
    flightrec_dir: str = ""            # HEATMAP_FLIGHTREC_DIR: directory
                                       # for post-mortem flight records
                                       # (obs.flightrec) — on abnormal
                                       # exit / SIGTERM the runtime dumps
                                       # trace tail, lineage tail, metrics
                                       # snapshot, and config there.
                                       # Empty disables.  A NORMAL close
                                       # writes nothing unless
                                       # HEATMAP_FLIGHTREC_ALWAYS=1.
    lineage_tail: int = 256            # HEATMAP_LINEAGE_TAIL: closed
                                       # freshness-lineage records kept
                                       # for /debug/freshness and the
                                       # flight recorder (obs.lineage)
    query_view: bool = True            # HEATMAP_QUERY_VIEW: maintain the
                                       # materialized tile view (query/
                                       # matview) feeding /api/tiles/
                                       # delta, ETag 304s, SSE, topk and
                                       # ?res= rollups.  0 disables —
                                       # reads fall back to direct Store
                                       # renders.  Multi-host runs skip
                                       # the writer-fed view (each host
                                       # sinks only its shards); serve
                                       # processes rebuild from the
                                       # store instead.
    delta_log: int = 4096              # HEATMAP_DELTA_LOG: per-grid
                                       # changed-cell changelog depth
                                       # backing /api/tiles/delta; a
                                       # client whose ?since= predates
                                       # the retained log gets a full
                                       # resync instead of a delta
    pyramid_levels: int = 2            # HEATMAP_PYRAMID_LEVELS: coarser
                                       # H3 parent resolutions the view
                                       # maintains incrementally per
                                       # grid for ?res= zoom-out (base
                                       # res-1 .. base res-levels); 0
                                       # disables rollups
    view_poll_ms: int = 1000           # HEATMAP_VIEW_POLL_MS: serve-only
                                       # view rebuild TTL — the bound
                                       # covering stores written by
                                       # OTHER processes, which version
                                       # polling cannot see
    sse_max_clients: int = 64          # HEATMAP_SSE_MAX_CLIENTS: open
                                       # /api/tiles/stream connections
                                       # before new ones get 503 (each
                                       # holds one server thread)
    sse_heartbeat_s: float = 15.0      # HEATMAP_SSE_HEARTBEAT_S: SSE
                                       # comment-ping cadence keeping
                                       # idle connections (and their
                                       # proxies) alive
    sse_queue: int = 64                # HEATMAP_SSE_QUEUE: bounded
                                       # per-subscriber send-queue
                                       # depth (frames) on the
                                       # coalesced SSE fan-out; a
                                       # subscriber whose queue
                                       # overflows is shed with
                                       # `event: lagged` instead of
                                       # wedging the shared broadcast
    sse_send_timeout_s: float = 30.0   # HEATMAP_SSE_SEND_TIMEOUT_S:
                                       # socket send timeout on SSE
                                       # connections — a subscriber
                                       # that stops reading the socket
                                       # is disconnected (and its
                                       # admission slot released)
                                       # after this long instead of
                                       # parking the writer thread
                                       # forever; 0 disables
    serve_max_inflight: int = 256      # HEATMAP_SERVE_MAX_INFLIGHT:
                                       # bounded in-flight render/
                                       # encode concurrency on the
                                       # data endpoints; past it
                                       # requests shed with 503 +
                                       # Retry-After (counted in
                                       # heatmap_serve_shed_total) so
                                       # overload degrades predictably.
                                       # 0 disables admission control.
    serve_workers: int = 1             # HEATMAP_SERVE_WORKERS: serve
                                       # worker processes `python -m
                                       # heatmap_tpu.serve` forks, each
                                       # binding the same port via
                                       # SO_REUSEPORT, running its own
                                       # replica follower, and
                                       # publishing its own fleet
                                       # member snapshot
    serve_core: str = "thread"         # HEATMAP_SERVE_CORE: which HTTP
                                       # core hosts the serve app —
                                       # "thread" (wsgiref, a thread
                                       # per request + per SSE
                                       # subscriber) or "epoll" (the
                                       # selectors event loop with
                                       # zero-copy SSE fan-out,
                                       # serve/evloop.py)
    serve_loop_handlers: int = 8       # HEATMAP_SERVE_LOOP_HANDLERS:
                                       # WSGI handler threads behind
                                       # the epoll core's loop — app
                                       # calls (store reads, history
                                       # scans) run here so blocking
                                       # work never stalls the loop
    shards: int = 1                    # HEATMAP_SHARDS: total runtime
                                       # shard processes partitioning
                                       # the event stream by H3 parent
                                       # cell (stream/shardmap.py); 1 =
                                       # unsharded (the default)
    shard_index: int = 0               # HEATMAP_SHARD_INDEX: this
                                       # process's shard in 0..N-1 (the
                                       # fleet supervisor sets it per
                                       # child)
    shard_res: int = -1                # HEATMAP_SHARD_RES: H3 parent
                                       # resolution of the partition
                                       # key; -1 = the snap resolution
                                       # itself (parent == cell).  Must
                                       # not exceed min(resolutions).
    repl_dir: str = ""                 # HEATMAP_REPL_DIR: directory the
                                       # writer process publishes the
                                       # view-replication feed into
                                       # (query/repl.py: segment log +
                                       # snapshots + meta, one writer
                                       # per dir).  The serve app also
                                       # re-exposes the feed at
                                       # /api/repl/* for remote
                                       # replicas.  Empty disables
                                       # publishing.
    repl_feed: str = ""                # HEATMAP_REPL_FEED: what a
                                       # serve-only worker FOLLOWS to
                                       # hold a hot seq-consistent
                                       # replica view with zero
                                       # steady-state store reads: a
                                       # feed directory (same host) or
                                       # an http(s):// base URL of a
                                       # process serving /api/repl/*.
                                       # Empty keeps the PR 4 store-
                                       # scan polling behavior.
    repl_seg_bytes: int = 1 << 22      # HEATMAP_REPL_SEG_BYTES: feed
                                       # segment rotation bound; each
                                       # rotation also refreshes the
                                       # catch-up snapshot
    repl_segments: int = 4             # HEATMAP_REPL_SEGMENTS: feed
                                       # segments retained on disk
                                       # (including the live one); a
                                       # follower that falls behind the
                                       # oldest re-bootstraps from the
                                       # snapshot
    repl_poll_ms: int = 200            # HEATMAP_REPL_POLL_MS: replica
                                       # follower tail-poll cadence
    hist_dir: str = ""                 # HEATMAP_HIST_DIR: space-time
                                       # history store (query/
                                       # history.py).  On the writer:
                                       # rotated repl segments retire
                                       # here instead of being deleted
                                       # and a compactor folds them
                                       # into immutable (grid, parent
                                       # cell, time bucket) chunks.
                                       # On any serve worker: enables
                                       # /api/tiles/range|at|diff and
                                       # the /api/hist/* re-export.
                                       # Empty disables the tier.
    hist_retention_s: float = 604800.0  # HEATMAP_HIST_RETENTION_S:
                                       # history retention (7 days).
                                       # Chunks age out past it; raw
                                       # segments prune only once
                                       # digest-verified chunks cover
                                       # them AND they age past it.
    hist_bucket_s: int = 3600          # HEATMAP_HIST_BUCKET_S: time-
                                       # bucket width of one chunk key
    hist_parent_res: int = 3           # HEATMAP_HIST_PARENT_RES: H3
                                       # parent resolution of the
                                       # chunk partition key (clamped
                                       # per cell to its own res)
    hist_compact_s: float = 2.0        # HEATMAP_HIST_COMPACT_S:
                                       # compaction cadence of the
                                       # writer-side compactor thread
    hist_backfill: bool = True         # HEATMAP_HIST_BACKFILL: replica
                                       # cold-start backfill of pre-
                                       # snapshot windows from history
                                       # chunks (query/repl.py); 0
                                       # disables
    govern: bool = False               # HEATMAP_GOVERN: adaptive
                                       # micro-batching (stream/
                                       # govern.py) — a feedback
                                       # governor on the step loop
                                       # resizes the live batch size
                                       # (power-of-two pad buckets,
                                       # precompiled at startup),
                                       # emit_flush_k, and
                                       # prefetch_batches within the
                                       # bounds below to hold
                                       # HEATMAP_SLO_FRESHNESS_P50_MS
                                       # under load swings.  The static
                                       # knobs above become INITIAL
                                       # values.  0 (the default) is
                                       # the kill switch: all knobs
                                       # stay static.
    govern_interval_s: float = 5.0     # HEATMAP_GOVERN_INTERVAL_S:
                                       # governor control-loop cadence
    govern_min_batch: int = 4096       # HEATMAP_GOVERN_MIN_BATCH:
                                       # bucket-ladder floor — the
                                       # smallest pad bucket the
                                       # governor may shrink the live
                                       # batch to (ladder = powers of
                                       # two from here up to
                                       # BATCH_SIZE, every bucket
                                       # warmed/compiled at startup)
    govern_max_flush_k: int = 32       # HEATMAP_GOVERN_MAX_FLUSH_K:
                                       # emit-ring depth ceiling the
                                       # governor may grow flush-K to
                                       # (floor is always 1)
    govern_max_prefetch: int = 4       # HEATMAP_GOVERN_MAX_PREFETCH:
                                       # prefetch-depth ceiling
                                       # (floor is always 0);
                                       # prefetch x batch growth is
                                       # additionally capped by the
                                       # HEATMAP_SLO_MEM_BYTES
                                       # watermark budget
    govern_healthy_frac: float = 0.5   # HEATMAP_GOVERN_HEALTHY_FRAC:
                                       # recovery hysteresis — the
                                       # governor only takes upward
                                       # (throughput) moves while the
                                       # recent event-age p50 is below
                                       # this fraction of the SLO
    mesh_partitioned: str = "auto"     # HEATMAP_MESH_PARTITIONED: mesh
                                       # execution mode when a
                                       # multi-device mesh is attached.
                                       # "auto" (default) = the
                                       # shard-per-device PARTITIONED
                                       # fast path on single-process
                                       # meshes (feed pre-partitions
                                       # each batch by H3 parent cell,
                                       # every device runs the fused
                                       # fold collective-free with its
                                       # own emit ring and governor);
                                       # multi-host meshes always keep
                                       # the ICI-shuffle lockstep path.
                                       # "1" forces partitioned (warns
                                       # and falls back on multi-host),
                                       # "0" forces the shuffle path.
    audit: bool = False                # HEATMAP_AUDIT: the integrity
                                       # observatory (obs/audit.py) —
                                       # observe-only event-conservation
                                       # ledger at every pipeline
                                       # boundary plus per-(grid,
                                       # window) content digests
                                       # verified across shards, mesh
                                       # devices, and replicas.  Zero
                                       # data-path mutation; 0 (the
                                       # default) disables entirely.
                                       # Multi-host runs ignore it
                                       # (lockstep accounting).
    audit_settle_s: float = 10.0       # HEATMAP_AUDIT_SETTLE_S: how
                                       # long a non-zero ledger
                                       # residual must go without
                                       # draining before /healthz
                                       # degrades naming the boundary
                                       # (in-flight pipeline depth is
                                       # not a leak; a book that stops
                                       # balancing is)
    cq: bool = True                    # HEATMAP_CQ: the continuous
                                       # spatial query engine (query/
                                       # continuous.py) on view-backed
                                       # serve surfaces — standing
                                       # bbox/polygon range
                                       # subscriptions, regional topk,
                                       # geofence enter/exit and
                                       # per-cell threshold alerts over
                                       # /api/queries.  Costs nothing
                                       # until the first registration
                                       # (the view carries no watcher);
                                       # 0 removes the endpoints.
    cq_max_queries: int = 1 << 20      # HEATMAP_CQ_MAX_QUERIES:
                                       # standing queries one worker
                                       # accepts before POST
                                       # /api/queries answers 400
    cq_ttl_s: float = 3600.0           # HEATMAP_CQ_TTL_S: default
                                       # standing-query TTL (a
                                       # registration may override via
                                       # ttl_s; 0 = never expires) —
                                       # abandoned subscriptions must
                                       # not accumulate forever
    cq_events: int = 256               # HEATMAP_CQ_EVENTS: match/alert
                                       # records buffered per query for
                                       # /api/queries/stream resume;
                                       # older events fall off
    cq_max_cells: int = 4096           # HEATMAP_CQ_MAX_CELLS: compiled
                                       # cell-set budget per query
                                       # (coarse parents + boundary
                                       # sliver); larger regions are
                                       # refused at registration
    tsdb: bool = False                 # HEATMAP_TSDB: the telemetry
                                       # time machine (obs/tsdb.py) —
                                       # a sampler thread records this
                                       # member's /metrics exposition +
                                       # /healthz verdict into fixed-
                                       # step history rings, persisted
                                       # as append-only blocks under
                                       # HEATMAP_TSDB_DIR, and the SLO
                                       # error-budget burn-rate engine
                                       # (obs/slo.py) evaluates on each
                                       # scrape.  0 (the default)
                                       # disables: no thread, no
                                       # families, no behavior change.
    tsdb_dir: str = ""                 # HEATMAP_TSDB_DIR: per-member
                                       # telemetry-history directory
                                       # (shared across the fleet so
                                       # /fleet/timeline can stitch
                                       # members).  Empty with tsdb=1:
                                       # rings + SLO engine run, but
                                       # nothing persists and the
                                       # retrospective endpoints 503.
    tsdb_scrape_s: float = 5.0         # HEATMAP_TSDB_SCRAPE_S:
                                       # history scrape cadence — also
                                       # the SLO engine's evaluation
                                       # tick and budget-spend unit
    tsdb_retain_s: float = 259200.0    # HEATMAP_TSDB_RETAIN_S: history
                                       # retention (3 days); blocks
                                       # past it are deleted
    tsdb_hot_s: float = 3600.0         # HEATMAP_TSDB_HOT_S: raw-
                                       # resolution span; older blocks
                                       # are merged into a coarser
                                       # downsampled tier
    tsdb_flush_s: float = 60.0         # HEATMAP_TSDB_FLUSH_S: block
                                       # persistence cadence (an SLO
                                       # alert flushes immediately)
    slo_budget_frac: float = 0.01      # HEATMAP_SLO_BUDGET_FRAC:
                                       # error-budget fraction — the
                                       # share of scrape ticks allowed
                                       # to breach an SLO threshold
                                       # inside the budget window
    slo_budget_window_s: float = 86400.0  # HEATMAP_SLO_BUDGET_WINDOW_S:
                                       # rolling error-budget window;
                                       # the canonical 30-day burn-rate
                                       # alert windows scale to it
    shard_oversample: int = 0          # HEATMAP_SHARD_OVERSAMPLE: how
                                       # many feed-batches worth of
                                       # stream rows a shard polls per
                                       # step before the ownership
                                       # filter compacts them (0 = auto:
                                       # the shard count, so a shard's
                                       # fold stays full; 1 = poll
                                       # exactly one feed shape — the
                                       # byte-exact differential mode)
    reducers: tuple[str, ...] = ("count",)  # HEATMAP_REDUCERS: the
                                       # per-step reducer set riding the
                                       # dispatched columnar batches
                                       # (infer/reducer.py); "count" is
                                       # the fused device fold itself
                                       # and is always a member —
                                       # default leaves the hot path
                                       # byte-identical to pre-reducer
                                       # runtimes
    entity_capacity: int = 1 << 17     # HEATMAP_ENTITY_CAPACITY:
                                       # per-shard entity slot-table
                                       # bound (infer/entities.py);
                                       # TTL then exact-LRU eviction
                                       # past it
    entity_ttl_s: float = 900.0        # HEATMAP_ENTITY_TTL_S: entity
                                       # silent past this (event time)
                                       # is evicted; also the dt clamp
                                       # on filter transitions
    entity_shards: int = 0             # HEATMAP_ENTITY_SHARDS: logical
                                       # entity-partition shard count
                                       # for handoff re-seeds (0 = the
                                       # runtime's HEATMAP_SHARDS); set
                                       # N on a 1-process run to apply
                                       # the exact re-seed decisions an
                                       # N-shard fleet would
    entity_stop_s: float = 120.0       # HEATMAP_ENTITY_STOP_S: filtered
                                       # speed below the stop gate for
                                       # this long (after having moved)
                                       # raises the stopped-vehicle
                                       # anomaly
    quality: bool = False              # HEATMAP_QUALITY: the inference
                                       # quality observatory
                                       # (obs/quality.py) — live
                                       # forecast scoring, filter-
                                       # calibration ledgers, drift
                                       # SLOs.  0 (the default)
                                       # disables: no families, no
                                       # scorecards, runtime byte-
                                       # identical to pre-quality
                                       # builds.
    quality_window_s: float = 600.0    # HEATMAP_QUALITY_WINDOW_S:
                                       # rolling event-time window for
                                       # the calibration ledger (NIS
                                       # coverage, bias, anomaly rates)
    quality_lookback_s: float = 300.0  # HEATMAP_QUALITY_LOOKBACK_S:
                                       # history span summed around the
                                       # base/target instants when
                                       # scoring (matches the offline
                                       # CLI's --window default, so the
                                       # differential is exact)
    quality_mature_s: float = 60.0     # HEATMAP_QUALITY_MATURE_S:
                                       # event-time slack past a
                                       # scorecard's target before it
                                       # scores (lets the target span
                                       # finish filling)
    quality_ttl_s: float = 3600.0      # HEATMAP_QUALITY_TTL_S: a
                                       # matured scorecard whose span
                                       # stays unanswerable this long
                                       # expires as expired_unscorable
                                       # (the conservation identity's
                                       # second sink)

    @property
    def tile_seconds(self) -> int:
        return self.tile_minutes * 60

    @property
    def grid_name(self) -> str:
        """Grid label used in tile _ids, e.g. "h3r8" (heatmap_stream.py:179)."""
        return f"h3r{self.h3_res}"

    def pair_grid(self, res: int, wmin: int) -> str:
        """Sink grid label for a (res, window) pair — the single source of
        truth for the tagging rule: the reference's bare "h3r{res}" when
        the window IS the reference tile window (tile _ids stay drop-in
        compatible, heatmap_stream.py:173), tagged "h3r{res}m{wmin}"
        otherwise.  The runtime writes under these labels and the API
        derives its bare-endpoint default from them."""
        return (f"h3r{res}" if wmin == self.tile_minutes
                else f"h3r{res}m{wmin}")

    def default_grid(self) -> str:
        """The grid bare /api/tiles/latest serves: the configured h3_res
        (or the first resolution), under the reference tile window when
        it is configured, else the first window — always a grid the
        runtime actually writes."""
        res_list = self.resolutions or (self.h3_res,)
        res = self.h3_res if self.h3_res in res_list else res_list[0]
        wins = self.windows_minutes or (self.tile_minutes,)
        wmin = self.tile_minutes if self.tile_minutes in wins else wins[0]
        return self.pair_grid(res, wmin)


def load_config(env: Mapping[str, str] | None = None, **overrides) -> Config:
    """Build a Config from env vars (same names as the reference) + overrides."""
    e = dict(os.environ if env is None else env)
    cfg = Config(
        mongo_uri=e.get("MONGO_URI", Config.mongo_uri),
        mongo_db=e.get("MONGO_DB", Config.mongo_db),
        city=e.get("CITY", Config.city),
        h3_res=_int(e, "H3_RES", Config.h3_res),
        tile_minutes=_int(e, "TILE_MINUTES", Config.tile_minutes),
        ttl_minutes=_int(e, "TTL_MINUTES", Config.ttl_minutes),
        kafka_bootstrap=e.get("KAFKA_BOOTSTRAP", Config.kafka_bootstrap),
        kafka_topic=e.get("KAFKA_TOPIC", Config.kafka_topic),
        checkpoint_dir=e.get("CHECKPOINT", Config.checkpoint_dir),
        refresh_ms=_int(e, "REFRESH_MS", Config.refresh_ms),
        mbta_api_key=e.get("MBTA_API_KEY", ""),
        watermark_minutes=_int(e, "WATERMARK_MINUTES", Config.watermark_minutes),
        backend=e.get("HEATMAP_BACKEND", Config.backend),
        resolutions=_ints(e, "H3_RESOLUTIONS", e.get("H3_RES", "8")),
        windows_minutes=_ints(e, "WINDOW_MINUTES", e.get("TILE_MINUTES", "5")),
        batch_size=_int(e, "BATCH_SIZE", Config.batch_size),
        state_capacity_log2=_int(e, "STATE_CAPACITY_LOG2", Config.state_capacity_log2),
        state_max_log2=_int(e, "HEATMAP_STATE_MAX_LOG2", Config.state_max_log2),
        speed_hist_bins=_int(e, "SPEED_HIST_BINS", Config.speed_hist_bins),
        speed_hist_max_kmh=_float(e, "SPEED_HIST_MAX_KMH", Config.speed_hist_max_kmh),
        num_shards=_int(e, "NUM_SHARDS", Config.num_shards),
        bucket_factor=_float(e, "EXCHANGE_BUCKET_FACTOR", Config.bucket_factor),
        trigger_ms=_int(e, "TRIGGER_MS", Config.trigger_ms),
        on_overflow=e.get("HEATMAP_ON_OVERFLOW", Config.on_overflow),
        serve_host=e.get("SERVE_HOST", Config.serve_host),
        serve_port=_int(e, "SERVE_PORT", Config.serve_port),
        store=e.get("HEATMAP_STORE", Config.store),
        emit_pull=e.get("HEATMAP_EMIT_PULL", Config.emit_pull),
        grow_margin=e.get("HEATMAP_GROW_MARGIN", Config.grow_margin),
        emit_flush_k=_int(e, "HEATMAP_EMIT_FLUSH_K", Config.emit_flush_k),
        prefetch_batches=_int(e, "HEATMAP_PREFETCH_BATCHES",
                              Config.prefetch_batches),
        flightrec_dir=e.get("HEATMAP_FLIGHTREC_DIR", Config.flightrec_dir),
        lineage_tail=_int(e, "HEATMAP_LINEAGE_TAIL", Config.lineage_tail),
        query_view=e.get("HEATMAP_QUERY_VIEW", "1") not in ("0", "false", ""),
        delta_log=_int(e, "HEATMAP_DELTA_LOG", Config.delta_log),
        pyramid_levels=_int(e, "HEATMAP_PYRAMID_LEVELS",
                            Config.pyramid_levels),
        view_poll_ms=_int(e, "HEATMAP_VIEW_POLL_MS", Config.view_poll_ms),
        sse_max_clients=_int(e, "HEATMAP_SSE_MAX_CLIENTS",
                             Config.sse_max_clients),
        sse_heartbeat_s=_float(e, "HEATMAP_SSE_HEARTBEAT_S",
                               Config.sse_heartbeat_s),
        sse_queue=_int(e, "HEATMAP_SSE_QUEUE", Config.sse_queue),
        sse_send_timeout_s=_float(e, "HEATMAP_SSE_SEND_TIMEOUT_S",
                                  Config.sse_send_timeout_s),
        serve_max_inflight=_int(e, "HEATMAP_SERVE_MAX_INFLIGHT",
                                Config.serve_max_inflight),
        serve_workers=_int(e, "HEATMAP_SERVE_WORKERS",
                           Config.serve_workers),
        serve_core=e.get("HEATMAP_SERVE_CORE", Config.serve_core),
        serve_loop_handlers=_int(e, "HEATMAP_SERVE_LOOP_HANDLERS",
                                 Config.serve_loop_handlers),
        repl_dir=e.get("HEATMAP_REPL_DIR", Config.repl_dir),
        repl_feed=e.get("HEATMAP_REPL_FEED", Config.repl_feed),
        repl_seg_bytes=_int(e, "HEATMAP_REPL_SEG_BYTES",
                            Config.repl_seg_bytes),
        repl_segments=_int(e, "HEATMAP_REPL_SEGMENTS",
                           Config.repl_segments),
        repl_poll_ms=_int(e, "HEATMAP_REPL_POLL_MS",
                          Config.repl_poll_ms),
        hist_dir=e.get("HEATMAP_HIST_DIR", Config.hist_dir),
        hist_retention_s=_float(e, "HEATMAP_HIST_RETENTION_S",
                                Config.hist_retention_s),
        hist_bucket_s=_int(e, "HEATMAP_HIST_BUCKET_S",
                           Config.hist_bucket_s),
        hist_parent_res=_int(e, "HEATMAP_HIST_PARENT_RES",
                             Config.hist_parent_res),
        hist_compact_s=_float(e, "HEATMAP_HIST_COMPACT_S",
                              Config.hist_compact_s),
        hist_backfill=e.get("HEATMAP_HIST_BACKFILL", "1")
        not in ("0", "false", ""),
        tsdb=e.get("HEATMAP_TSDB", "0") not in ("0", "false", ""),
        tsdb_dir=e.get("HEATMAP_TSDB_DIR", Config.tsdb_dir),
        tsdb_scrape_s=_float(e, "HEATMAP_TSDB_SCRAPE_S",
                             Config.tsdb_scrape_s),
        tsdb_retain_s=_float(e, "HEATMAP_TSDB_RETAIN_S",
                             Config.tsdb_retain_s),
        tsdb_hot_s=_float(e, "HEATMAP_TSDB_HOT_S", Config.tsdb_hot_s),
        tsdb_flush_s=_float(e, "HEATMAP_TSDB_FLUSH_S",
                            Config.tsdb_flush_s),
        slo_budget_frac=_float(e, "HEATMAP_SLO_BUDGET_FRAC",
                               Config.slo_budget_frac),
        slo_budget_window_s=_float(e, "HEATMAP_SLO_BUDGET_WINDOW_S",
                                   Config.slo_budget_window_s),
        govern=e.get("HEATMAP_GOVERN", "0") not in ("0", "false", ""),
        govern_interval_s=_float(e, "HEATMAP_GOVERN_INTERVAL_S",
                                 Config.govern_interval_s),
        govern_min_batch=_int(e, "HEATMAP_GOVERN_MIN_BATCH",
                              Config.govern_min_batch),
        govern_max_flush_k=_int(e, "HEATMAP_GOVERN_MAX_FLUSH_K",
                                Config.govern_max_flush_k),
        govern_max_prefetch=_int(e, "HEATMAP_GOVERN_MAX_PREFETCH",
                                 Config.govern_max_prefetch),
        govern_healthy_frac=_float(e, "HEATMAP_GOVERN_HEALTHY_FRAC",
                                   Config.govern_healthy_frac),
        shards=_int(e, "HEATMAP_SHARDS", Config.shards),
        shard_index=_int(e, "HEATMAP_SHARD_INDEX", Config.shard_index),
        shard_res=_int(e, "HEATMAP_SHARD_RES", Config.shard_res),
        shard_oversample=_int(e, "HEATMAP_SHARD_OVERSAMPLE",
                              Config.shard_oversample),
        reducers=tuple(
            s.strip() for s in e.get("HEATMAP_REDUCERS", "count").split(",")
            if s.strip()),
        entity_capacity=_int(e, "HEATMAP_ENTITY_CAPACITY",
                             Config.entity_capacity),
        entity_ttl_s=_float(e, "HEATMAP_ENTITY_TTL_S",
                            Config.entity_ttl_s),
        entity_shards=_int(e, "HEATMAP_ENTITY_SHARDS",
                           Config.entity_shards),
        entity_stop_s=_float(e, "HEATMAP_ENTITY_STOP_S",
                             Config.entity_stop_s),
        quality=e.get("HEATMAP_QUALITY", "0") not in ("0", "false", ""),
        quality_window_s=_float(e, "HEATMAP_QUALITY_WINDOW_S",
                                Config.quality_window_s),
        quality_lookback_s=_float(e, "HEATMAP_QUALITY_LOOKBACK_S",
                                  Config.quality_lookback_s),
        quality_mature_s=_float(e, "HEATMAP_QUALITY_MATURE_S",
                                Config.quality_mature_s),
        quality_ttl_s=_float(e, "HEATMAP_QUALITY_TTL_S",
                             Config.quality_ttl_s),
        cq=e.get("HEATMAP_CQ", "1") not in ("0", "false", ""),
        cq_max_queries=_int(e, "HEATMAP_CQ_MAX_QUERIES",
                            Config.cq_max_queries),
        cq_ttl_s=_float(e, "HEATMAP_CQ_TTL_S", Config.cq_ttl_s),
        cq_events=_int(e, "HEATMAP_CQ_EVENTS", Config.cq_events),
        cq_max_cells=_int(e, "HEATMAP_CQ_MAX_CELLS",
                          Config.cq_max_cells),
        audit=e.get("HEATMAP_AUDIT", "0") not in ("0", "false", ""),
        audit_settle_s=_float(e, "HEATMAP_AUDIT_SETTLE_S",
                              Config.audit_settle_s),
        mesh_partitioned=e.get("HEATMAP_MESH_PARTITIONED",
                               Config.mesh_partitioned),
    )
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    if cfg.on_overflow not in ("error", "fail"):
        # a typo here would silently downgrade a stop-on-data-loss knob
        raise ValueError(
            f"HEATMAP_ON_OVERFLOW must be 'error' or 'fail', "
            f"got {cfg.on_overflow!r}")
    if cfg.state_max_log2 and cfg.state_max_log2 < cfg.state_capacity_log2:
        raise ValueError(
            f"HEATMAP_STATE_MAX_LOG2 ({cfg.state_max_log2}) below "
            f"STATE_CAPACITY_LOG2 ({cfg.state_capacity_log2})")
    if cfg.grow_margin not in ("worst", "observed"):
        raise ValueError(
            f"HEATMAP_GROW_MARGIN must be 'worst' or 'observed', "
            f"got {cfg.grow_margin!r}")
    if cfg.emit_pull not in ("auto", "full", "prefix"):
        raise ValueError(
            f"HEATMAP_EMIT_PULL must be auto|full|prefix, "
            f"got {cfg.emit_pull!r}")
    if cfg.emit_flush_k < 1:
        raise ValueError(
            f"HEATMAP_EMIT_FLUSH_K must be >= 1, got {cfg.emit_flush_k}")
    if not (0 <= cfg.prefetch_batches <= 32):
        raise ValueError(
            f"HEATMAP_PREFETCH_BATCHES must be in 0..32, "
            f"got {cfg.prefetch_batches}")
    if cfg.lineage_tail < 1:
        raise ValueError(
            f"HEATMAP_LINEAGE_TAIL must be >= 1, got {cfg.lineage_tail}")
    if cfg.delta_log < 1:
        raise ValueError(
            f"HEATMAP_DELTA_LOG must be >= 1, got {cfg.delta_log}")
    if not (0 <= cfg.pyramid_levels <= 15):
        raise ValueError(
            f"HEATMAP_PYRAMID_LEVELS must be in 0..15, "
            f"got {cfg.pyramid_levels}")
    if cfg.view_poll_ms < 0:
        raise ValueError(
            f"HEATMAP_VIEW_POLL_MS must be >= 0, got {cfg.view_poll_ms}")
    if cfg.sse_max_clients < 1:
        raise ValueError(
            f"HEATMAP_SSE_MAX_CLIENTS must be >= 1, "
            f"got {cfg.sse_max_clients}")
    if cfg.sse_heartbeat_s <= 0:
        raise ValueError(
            f"HEATMAP_SSE_HEARTBEAT_S must be > 0, "
            f"got {cfg.sse_heartbeat_s}")
    if cfg.sse_queue < 1:
        raise ValueError(
            f"HEATMAP_SSE_QUEUE must be >= 1, got {cfg.sse_queue}")
    if cfg.sse_send_timeout_s < 0:
        raise ValueError(
            f"HEATMAP_SSE_SEND_TIMEOUT_S must be >= 0 (0 = no "
            f"timeout), got {cfg.sse_send_timeout_s}")
    if cfg.serve_max_inflight < 0:
        raise ValueError(
            f"HEATMAP_SERVE_MAX_INFLIGHT must be >= 0 (0 = "
            f"unbounded), got {cfg.serve_max_inflight}")
    if cfg.serve_workers < 1:
        raise ValueError(
            f"HEATMAP_SERVE_WORKERS must be >= 1, "
            f"got {cfg.serve_workers}")
    if cfg.serve_core not in ("thread", "epoll"):
        raise ValueError(
            f"HEATMAP_SERVE_CORE must be 'thread' or 'epoll', "
            f"got {cfg.serve_core!r}")
    if cfg.serve_loop_handlers < 1:
        raise ValueError(
            f"HEATMAP_SERVE_LOOP_HANDLERS must be >= 1, "
            f"got {cfg.serve_loop_handlers}")
    if cfg.repl_seg_bytes < 4096:
        raise ValueError(
            f"HEATMAP_REPL_SEG_BYTES must be >= 4096, "
            f"got {cfg.repl_seg_bytes}")
    if cfg.repl_segments < 1:
        raise ValueError(
            f"HEATMAP_REPL_SEGMENTS must be >= 1, got {cfg.repl_segments}")
    if cfg.repl_poll_ms < 10:
        raise ValueError(
            f"HEATMAP_REPL_POLL_MS must be >= 10, got {cfg.repl_poll_ms}")
    if cfg.hist_retention_s <= 0:
        raise ValueError(
            f"HEATMAP_HIST_RETENTION_S must be > 0, "
            f"got {cfg.hist_retention_s}")
    if cfg.hist_bucket_s < 60:
        raise ValueError(
            f"HEATMAP_HIST_BUCKET_S must be >= 60, "
            f"got {cfg.hist_bucket_s}")
    if not 0 <= cfg.hist_parent_res <= 15:
        raise ValueError(
            f"HEATMAP_HIST_PARENT_RES must be in 0..15, "
            f"got {cfg.hist_parent_res}")
    if cfg.hist_compact_s <= 0:
        raise ValueError(
            f"HEATMAP_HIST_COMPACT_S must be > 0, "
            f"got {cfg.hist_compact_s}")
    if cfg.shards < 1:
        raise ValueError(f"HEATMAP_SHARDS must be >= 1, got {cfg.shards}")
    if not 0 <= cfg.shard_index < cfg.shards:
        raise ValueError(
            f"HEATMAP_SHARD_INDEX must be in 0..{cfg.shards - 1}, "
            f"got {cfg.shard_index}")
    if cfg.shards > 1:
        snap_res = min(cfg.resolutions)
        if not (cfg.shard_res == -1 or 0 <= cfg.shard_res <= snap_res):
            raise ValueError(
                f"HEATMAP_SHARD_RES must be -1 or in 0..{snap_res} "
                f"(the coarsest fold resolution), got {cfg.shard_res}")
    if cfg.govern_interval_s <= 0:
        raise ValueError(
            f"HEATMAP_GOVERN_INTERVAL_S must be > 0, "
            f"got {cfg.govern_interval_s}")
    if cfg.govern_min_batch < 64:
        raise ValueError(
            f"HEATMAP_GOVERN_MIN_BATCH must be >= 64, "
            f"got {cfg.govern_min_batch}")
    if cfg.govern and cfg.govern_min_batch > cfg.batch_size:
        raise ValueError(
            f"HEATMAP_GOVERN_MIN_BATCH ({cfg.govern_min_batch}) above "
            f"BATCH_SIZE ({cfg.batch_size}); the ladder floor cannot "
            f"exceed its ceiling")
    if cfg.govern_max_flush_k < 1:
        raise ValueError(
            f"HEATMAP_GOVERN_MAX_FLUSH_K must be >= 1, "
            f"got {cfg.govern_max_flush_k}")
    if not 0 <= cfg.govern_max_prefetch <= 32:
        raise ValueError(
            f"HEATMAP_GOVERN_MAX_PREFETCH must be in 0..32, "
            f"got {cfg.govern_max_prefetch}")
    if not 0 < cfg.govern_healthy_frac < 1:
        raise ValueError(
            f"HEATMAP_GOVERN_HEALTHY_FRAC must be in (0, 1), "
            f"got {cfg.govern_healthy_frac}")
    if cfg.mesh_partitioned not in ("auto", "0", "1"):
        raise ValueError(
            f"HEATMAP_MESH_PARTITIONED must be auto|0|1, "
            f"got {cfg.mesh_partitioned!r}")
    if not 0 <= cfg.shard_oversample <= 64:
        raise ValueError(
            f"HEATMAP_SHARD_OVERSAMPLE must be in 0..64, "
            f"got {cfg.shard_oversample}")
    # reducer-set validation lives with the protocol (canonical order,
    # closed name set, mandatory count member)
    from heatmap_tpu.infer.reducer import parse_reducers

    object.__setattr__(cfg, "reducers", parse_reducers(
        ",".join(cfg.reducers) if isinstance(cfg.reducers, (tuple, list))
        else cfg.reducers))
    if cfg.entity_capacity < 8:
        raise ValueError(
            f"HEATMAP_ENTITY_CAPACITY must be >= 8, "
            f"got {cfg.entity_capacity}")
    if cfg.entity_ttl_s <= 0:
        raise ValueError(
            f"HEATMAP_ENTITY_TTL_S must be > 0, got {cfg.entity_ttl_s}")
    if cfg.entity_shards < 0:
        raise ValueError(
            f"HEATMAP_ENTITY_SHARDS must be >= 0 (0 = HEATMAP_SHARDS), "
            f"got {cfg.entity_shards}")
    if cfg.entity_stop_s <= 0:
        raise ValueError(
            f"HEATMAP_ENTITY_STOP_S must be > 0, "
            f"got {cfg.entity_stop_s}")
    if cfg.quality_window_s <= 0:
        raise ValueError(
            f"HEATMAP_QUALITY_WINDOW_S must be > 0, "
            f"got {cfg.quality_window_s}")
    if cfg.quality_lookback_s <= 0:
        raise ValueError(
            f"HEATMAP_QUALITY_LOOKBACK_S must be > 0, "
            f"got {cfg.quality_lookback_s}")
    if cfg.quality_mature_s < 0:
        raise ValueError(
            f"HEATMAP_QUALITY_MATURE_S must be >= 0, "
            f"got {cfg.quality_mature_s}")
    if cfg.quality_ttl_s < cfg.quality_mature_s:
        raise ValueError(
            f"HEATMAP_QUALITY_TTL_S ({cfg.quality_ttl_s}) below "
            f"HEATMAP_QUALITY_MATURE_S ({cfg.quality_mature_s}) — a "
            f"scorecard cannot expire before it matures")
    if cfg.cq_max_queries < 1:
        raise ValueError(
            f"HEATMAP_CQ_MAX_QUERIES must be >= 1, "
            f"got {cfg.cq_max_queries}")
    if cfg.cq_ttl_s < 0:
        raise ValueError(
            f"HEATMAP_CQ_TTL_S must be >= 0 (0 = no expiry), "
            f"got {cfg.cq_ttl_s}")
    if cfg.cq_events < 1:
        raise ValueError(
            f"HEATMAP_CQ_EVENTS must be >= 1, got {cfg.cq_events}")
    if cfg.cq_max_cells < 1:
        raise ValueError(
            f"HEATMAP_CQ_MAX_CELLS must be >= 1, "
            f"got {cfg.cq_max_cells}")
    if cfg.audit_settle_s <= 0:
        raise ValueError(
            f"HEATMAP_AUDIT_SETTLE_S must be > 0, "
            f"got {cfg.audit_settle_s}")
    if cfg.tsdb_scrape_s <= 0:
        raise ValueError(
            f"HEATMAP_TSDB_SCRAPE_S must be > 0, "
            f"got {cfg.tsdb_scrape_s}")
    if cfg.tsdb_flush_s < 0:
        raise ValueError(
            f"HEATMAP_TSDB_FLUSH_S must be >= 0, "
            f"got {cfg.tsdb_flush_s}")
    if cfg.tsdb_retain_s < cfg.tsdb_hot_s:
        raise ValueError(
            f"HEATMAP_TSDB_RETAIN_S ({cfg.tsdb_retain_s}) below "
            f"HEATMAP_TSDB_HOT_S ({cfg.tsdb_hot_s}) — retention "
            f"cannot be shorter than the raw tier it feeds")
    if not 0 < cfg.slo_budget_frac <= 1:
        raise ValueError(
            f"HEATMAP_SLO_BUDGET_FRAC must be in (0, 1], "
            f"got {cfg.slo_budget_frac}")
    if cfg.slo_budget_window_s <= 0:
        raise ValueError(
            f"HEATMAP_SLO_BUDGET_WINDOW_S must be > 0, "
            f"got {cfg.slo_budget_window_s}")
    return cfg
