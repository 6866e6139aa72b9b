"""MicroBatchRuntime — the driver loop (replaces the Spark streaming query).

One iteration ≈ one Spark micro-batch (SURVEY.md §3.3), but everything
between the source poll and the sink upsert runs in-framework:

    poll source (EventColumns — zero per-event Python on the hot
      sources; the feed stage runs up to HEATMAP_PREFETCH_BATCHES ahead
      of the fold, its device_put overlapping the in-flight step)
      → pad to the fixed batch shape → per-(res, window) device
      aggregation step (engine / parallel)
      → packed emits PARK in the device-resident emit ring
      (engine.step.EmitRing, HEATMAP_EMIT_FLUSH_K batches deep) and are
      pulled in ONE transfer per flush → tile docs → async sink upserts
      → host positions_latest fold (monotonic per vehicle)
      → watermark advance (host-side device-mask replica, per batch)
      → periodic checkpoint commit (ring flushed first, after sink
      drain)

The reference's defaults are preserved: update-mode emission per touched
group (heatmap_stream.py:243), as-fast-as-possible triggering unless
``trigger_ms`` is set (:241-247, README.md:134-136), 10-minute watermark
(:107), and the tiles/positions doc contracts via sink.base.
"""

from __future__ import annotations

import collections
import functools
import logging
import os
import threading
import time
from typing import NamedTuple

import jax
import numpy as np

from heatmap_tpu.config import Config
from heatmap_tpu.engine import AggParams
from heatmap_tpu.engine.state import TileState
from heatmap_tpu.sink import AsyncWriter, Store
from heatmap_tpu.sink.base import PositionRows
from heatmap_tpu.stream.checkpoint import CheckpointManager
from heatmap_tpu.stream.events import EventColumns, parse_events
from heatmap_tpu.stream.metrics import Metrics
from heatmap_tpu.stream.source import Source
from heatmap_tpu.stream.trace import Tracer

log = logging.getLogger(__name__)


class StateOverflowError(RuntimeError):
    """Raised (HEATMAP_ON_OVERFLOW=fail) when distinct (cell,window) groups
    exceed the state slab capacity and aggregates would be dropped."""

I32_MIN = -(2**31)


class _FeedBatch(NamedTuple):
    """One decoded/padded/pre-snapped feed batch, ready to dispatch.

    Built by ``_next_batch`` — either synchronously at the top of a step
    or AHEAD of it by the prefetch stage (then the arrays in ``feed`` /
    ``prekeys`` are already device-resident, their H2D transfer
    overlapping the in-flight fold).  ``offset`` is the source position
    captured right after THIS batch's poll: a prefetched batch's offsets
    advance only when it is dispatched, so checkpoints never cover rows
    that were polled ahead but not folded.  ``carried`` marks a
    record-granular overshoot whose tail rows are still undispatched
    (offsets must not advance past the record)."""

    cols: object          # EventColumns (host; positions fold reads it)
    n: int                # live rows
    feed: dict            # lat/lng/speed/ts/valid, padded (host or device)
    prekeys: object       # host C++ snap keys per res, or None
    offset: object        # source offset AFTER this batch's poll
    carried: bool         # overshoot tail pending (record incomplete)
    spans: dict           # feed-stage sub-span seconds (poll/pad/snap/…)
    lineage: object = None  # freshness lineage record opened at poll
                            # time (obs.lineage); None on idle batches
    wm_ts: object = None  # PRE-ownership-filter ts column (sharded
                          # runs): the watermark must advance with the
                          # full stream's event time, not just this
                          # shard's cells, so the cutoff sequence stays
                          # identical to the unsharded fold's
    mesh: object = None   # partitioned-mesh feed: per-device chunk
                          # lists ([[{n, feed, prekeys} | None, ...]])
                          # built by _mesh_feed — each device's owned
                          # rows compacted/padded/device_put to ITS
                          # chip; None marks an empty dispatch (the
                          # device still dispatches all-invalid so its
                          # per-batch slab rewrite count matches the
                          # single-device fold's)


def _make_global_pair(mesh):
    """Cross-host agreement channel: every host contributes a triple of
    flags, everyone reads the global sums.  This is a collective — hosts
    must call it at the same point of every step (stream lockstep)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from heatmap_tpu.parallel.multihost import put_global
    from heatmap_tpu.parallel.sharded import AXIS

    sharding = NamedSharding(mesh, P(AXIS, None))
    n_local = sum(1 for d in mesh.devices.ravel()
                  if d.process_index == jax.process_index())
    f = jax.jit(lambda x: jnp.sum(x, axis=0))

    def gpair(a: float, b: float, c: float = 0.0) -> np.ndarray:
        local = np.tile(np.array([[a, b, c]], np.float32), (n_local, 1))
        return np.asarray(jax.device_get(f(put_global(sharding, local))))

    return gpair


class MicroBatchRuntime:
    def __init__(
        self,
        cfg: Config,
        source: Source,
        store: Store,
        mesh=None,
        positions_enabled: bool = True,
        checkpoint_every: int = 20,
        view=None,
    ):
        self.cfg = cfg
        self.source = source
        self.store = store
        self.metrics = Metrics()
        # H3-parent stream partitioning (stream/shardmap.py): with
        # HEATMAP_SHARDS > 1 this process folds only the cell space its
        # shard index owns; out-of-shard rows are dropped in the feed
        # stage before pad/device_put.  The ownership filter preserves
        # row order and the watermark advances from the PRE-filter rows
        # (the full stream's event time), so the cutoff sequence — and
        # with it late-drop and eviction behavior on owned rows — is
        # identical to the unsharded fold's (the 1-vs-N differential
        # test's byte-identity rests on both properties).
        from heatmap_tpu.stream.shardmap import ShardMap

        self.shardmap = ShardMap.from_config(cfg)
        self._shard_oversample = 1
        if self.shardmap is not None:
            self._shard_oversample = cfg.shard_oversample or cfg.shards
            log.info("sharded runtime: %s, oversample %d",
                     self.shardmap.describe(), self._shard_oversample)
        self._shard_wm_pub_last = 0.0   # aligned-watermark publish limit
        self._shard_wm_read_last = 0.0  # aligned-watermark read cache
        self._shard_wm_floor = None     # cached fleet low bound
        self._shard_wm_eff_last = I32_MIN  # monotone cutoff floor
        # Materialized tile view (query.matview): fed by the writer
        # thread after each durable tile write, read by the serve layer
        # (delta/ETag/SSE/topk/?res=) so polls stop touching the Store.
        # Multi-host and sharded runs skip the self-owned view — each
        # process sinks only its own cell space, so a process-local view
        # would expose a partial city; serve processes rebuild the
        # merged city from the shared store instead, or a caller passes
        # ``view=`` to fan several shards into one shared view.
        # Integrity observatory (obs/audit.py, HEATMAP_AUDIT=1):
        # observe-only event-conservation ledger + per-window content
        # digests.  Multi-host runs are not audited — their accounting
        # is replicated across hosts and a host-local ledger could
        # never telescope.  (AuditState itself is constructed below,
        # after the fleet tag it is named by.)
        self._audit_on = bool(cfg.audit) and jax.process_count() == 1
        if cfg.audit and not self._audit_on:
            log.warning("HEATMAP_AUDIT=1 ignored: multi-host runs are "
                        "not audited (replicated lockstep accounting)")
        self.matview = None
        if view is not None:
            # externally shared view (sharded fan-in): every shard's
            # writer applies its emits into ONE merged TileMatView —
            # cell spaces are disjoint by the shardmap, so the merge is
            # upsert-only with no cross-shard conflicts by construction
            self.matview = view
        elif (cfg.query_view and jax.process_count() == 1
                and self.shardmap is None):
            from heatmap_tpu.query import TileMatView

            view_audit = None
            if self._audit_on:
                from heatmap_tpu.obs.audit import DigestTable

                view_audit = DigestTable()
            # (no store scan here: runtime construction stays read-only
            # — the serve layer seeds unmaterialized grids lazily from
            # the store on first access, so a restart against a durable
            # sink still serves the current window immediately)
            self.matview = TileMatView(
                delta_log=cfg.delta_log,
                pyramid_levels=cfg.pyramid_levels,
                registry=self.metrics.registry,
                audit=view_audit)
        # Delta-log view replication (query.repl): with HEATMAP_REPL_DIR
        # set, every view mutation the writer thread applies is
        # published to the feed, so serve-only replicas
        # (HEATMAP_REPL_FEED) hold a hot seq-consistent copy with zero
        # steady-state store reads.  Only the SELF-OWNED view publishes
        # here — an externally shared fan-in view gets one publisher
        # from whoever owns it, never one per shard.
        self.repl_pub = None
        self.hist_compactor = None
        if self.matview is not None and view is None and cfg.repl_dir:
            from heatmap_tpu.query.repl import DeltaLogPublisher

            # space-time history tier (query/history.py,
            # HEATMAP_HIST_DIR): the publisher retires rotated
            # segments into the durable log instead of deleting them,
            # and a compactor thread folds them into the immutable
            # chunk store — built BEFORE the publisher so the boot
            # sweep retires the dead epoch's tail instead of erasing it
            hist_log = None
            if cfg.hist_dir:
                from heatmap_tpu.query.history import (HistoryCompactor,
                                                       HistoryLog)

                hist_log = HistoryLog(cfg.hist_dir)
            # the delivery-lineage event_age leg (obs.delivery): the
            # publisher stamps the newest sink-acked event's age into
            # each record at hook-enqueue when HEATMAP_DELIVERY=1.
            # Late-bound — the lineage tracker is constructed below,
            # and the hook only fires once the step loop mutates the
            # view, long after __init__ completes.
            self.repl_pub = DeltaLogPublisher(
                self.matview, cfg.repl_dir,
                seg_bytes=cfg.repl_seg_bytes,
                segments=cfg.repl_segments,
                registry=self.metrics.registry,
                hist=hist_log,
                event_age_fn=lambda: self.lineage.newest_event_age_s())
            if hist_log is not None:
                self.hist_compactor = HistoryCompactor(
                    cfg.hist_dir, feed_dir=cfg.repl_dir,
                    bucket_s=cfg.hist_bucket_s,
                    parent_res=cfg.hist_parent_res,
                    retention_s=cfg.hist_retention_s,
                    registry=self.metrics.registry,
                    interval_s=cfg.hist_compact_s)
                self.hist_compactor.start()
        self.writer = AsyncWriter(store, metrics=self.metrics,
                                  view=self.matview)
        self.tracer = Tracer()
        from heatmap_tpu.obs import LineageTracker, TraceRing

        self.tracering = TraceRing()
        # Freshness lineage (obs.lineage): one record per polled batch,
        # stamped at poll -> dispatch -> ring-enter -> flush -> sink
        # commit ack, so heatmap_event_age_seconds measures the
        # END-TO-END staleness the prefetch stage and the emit ring hide
        # from the per-stage spans.  Records open at poll and park in
        # _lineage_open (epoch-keyed) from dispatch until their flush.
        self._fresh_pub_last = 0.0  # child-freshness publish rate limit
        self._member_pub_last = 0.0  # fleet member-snapshot rate limit
        from heatmap_tpu.obs.xproc import ENV_FLEET_TAG

        # a HEATMAP_FLEET_TAG override reaches every shard of a
        # multi-process runtime through the shared env — compose it
        # with the process index so shards can never collide on one
        # member file (a dead shard hiding behind a live one's
        # snapshot) or one lineage-id namespace
        tag = os.environ.get(ENV_FLEET_TAG)
        idx = jax.process_index()
        if tag and jax.process_count() > 1:
            tag = f"{tag}-p{idx}"
        # shard runtimes default to a shard<i> tag so fleet surfaces
        # (/fleet/metrics, /fleet/healthz, the per-shard watermark
        # files) name the shard, not a generic process index — and two
        # shards can never collide on one member file
        default_tag = (f"shard{cfg.shard_index}" if self.shardmap is not None
                       else f"p{idx}")
        self._fresh_tag = tag or default_tag
        # integrity observatory state, named by the fleet tag so the
        # /fleet/audit stitch can attribute per-member ledgers; the
        # ledger rides this registry (heatmap_audit_* families), the
        # writer thread stamps the sink/view boundaries, and every
        # tagged drop (Metrics.drop) forwards into it
        self.audit = None
        if self._audit_on:
            from heatmap_tpu.obs.audit import AuditState

            self.audit = AuditState(self.metrics.registry,
                                    tag=self._fresh_tag,
                                    settle_s=cfg.audit_settle_s)
            self.audit.attach(view=self.matview,
                              repl_pub=self.repl_pub)
            self.writer.audit = self.audit
            self.metrics.audit = self.audit
        # Streaming inference engine (heatmap_tpu.infer): the reducer
        # set riding the dispatched columnar batches.  With the default
        # HEATMAP_REDUCERS=count NOTHING is constructed here and no
        # per-batch work is added — the count path stays byte-identical
        # to the pre-reducer runtime by construction.  With kalman on,
        # the engine folds every dispatched batch (post-ownership-filter
        # on sharded runs: this shard's entities only), raises anomaly
        # events through the view feed, and enriches tile docs with the
        # per-cell velocity field.
        self.infer = None
        # velocity view per dispatched epoch still parked in an emit
        # ring: tiles take the velocity of the batch that emitted them
        self._vel_views: dict = {}
        if "kalman" in cfg.reducers:
            from heatmap_tpu.infer import InferenceEngine

            self.infer = InferenceEngine(cfg, metrics=self.metrics)
            log.info("inference engine on: reducers=%s capacity=%d "
                     "partition=%s", ",".join(cfg.reducers),
                     cfg.entity_capacity,
                     self.infer.partition.n_shards
                     if self.infer.partition is not None else 1)
        # Inference quality observatory (obs.quality): live forecast
        # scoring + filter-calibration ledgers + drift SLOs, attached
        # to the engine's fold.  Gated HEATMAP_QUALITY=1 AND the kalman
        # reducer: knob-off nothing is constructed, no family
        # registers, the runtime stays byte-identical; knob-ON it is
        # observe-only (registration after the forecast body, scoring
        # never mutates view state) so the same surfaces stay
        # byte-identical too.
        self.quality = None
        if cfg.quality and self.infer is not None:
            from heatmap_tpu.obs.quality import QualityObservatory

            self.quality = QualityObservatory(
                cfg, registry=self.metrics.registry,
                view=self.matview, tag=self._fresh_tag)
            self.infer.quality = self.quality
            log.info("quality observatory on: band=%s skill_floor=%s",
                     self.quality.band, self.quality.skill_floor)
        # lineage ids are origin-tagged so the fleet aggregator
        # (obs.fleet) can stitch this shard's stage contributions with
        # other members' (e.g. a serve worker's view_apply) by lid
        self.lineage = LineageTracker(capacity=cfg.lineage_tail,
                                      origin=self._fresh_tag)
        self._lineage_open: dict[int, dict] = {}
        # Flight recorder (obs.flightrec): armed when
        # HEATMAP_FLIGHTREC_DIR is set; close() dumps on abnormal exit
        # (fatal overflow, poisoned sink, an exception unwinding through
        # run(), SIGTERM via stream.__main__'s SystemExit handler).
        self.flightrec = None
        if cfg.flightrec_dir:
            import dataclasses as _dc

            from heatmap_tpu.obs import FlightRecorder

            fr = FlightRecorder(cfg.flightrec_dir)
            fr.add_source("trace_tail", lambda: self.tracering.recent(64))
            fr.add_source("lineage_tail", lambda: self.lineage.tail(64))
            fr.add_source("metrics", lambda: self.metrics.snapshot())
            fr.add_source("config", lambda: _dc.asdict(self.cfg))
            fr.add_source("run_state", lambda: {
                "epoch": self.epoch,
                "max_event_ts": self.max_event_ts,
                "ring_pending": self._ring_pending(),
                "prefetched": len(self._prefetched),
                "writer_poisoned": self.writer.poisoned,
            })
            # integrity-observatory enrichment: the conservation
            # ledger's residuals and digest state ride every dump
            # (reads self.audit dynamically — it is assigned above
            # only when HEATMAP_AUDIT=1)
            fr.add_source("audit", lambda: (self.audit.snapshot()
                                            if self.audit else None))
            # quality-observatory enrichment: the calibration picture
            # (NIS coverage, skill ledger, pending scorecards) rides
            # every dump — including the SLO engine's drift-burn dump
            fr.add_source("quality", lambda: (self.quality.snapshot()
                                              if self.quality else None))
            # runtime-introspection enrichment (obs.runtimeinfo /
            # obs.prof): compile counts + memory watermarks + the
            # stack-sample tail ride every dump — crash AND the SLO
            # watchdog's auto-captures (sources evaluate at dump time;
            # self.runtimeinfo is assigned later in this __init__)
            fr.add_source("runtimeinfo",
                          lambda: self.runtimeinfo.snapshot())
            from heatmap_tpu.obs.prof import get_sampler

            fr.add_source("stacks", lambda: get_sampler().tail(20))
            self.flightrec = fr
            if self.audit is not None:
                # digest-mismatch dumps correlate under the fleet
                # episode via this recorder (obs.audit._dump_mismatch)
                self.audit.flightrec = fr
        # pipeline-state gauges: watermark/event-time lag, state slab
        # occupancy vs capacity (the overflow early-warning), and the
        # per-shard device dispatch clock (engine.multi accumulates it;
        # callback gauges read it at scrape time)
        self._g_watermark = self.metrics.gauge(
            "heatmap_watermark_age_seconds",
            "wall clock minus the event-time high watermark "
            "(max event ts seen)")
        self._g_capacity = self.metrics.gauge(
            "heatmap_state_capacity_rows",
            "state slab capacity per shard (rows)")
        self._g_active = self.metrics.gauge(
            "heatmap_state_active_groups_peak",
            "max live (cell,window) groups seen on any pair")
        # sampled by serve/api.py at every /api/tiles/latest render:
        # render wall time minus the newest SINK-COMMITTED event
        # timestamp (lineage watermark) — the ingest->serve freshness
        # the paper's real-time claim is about.  NaN until the first
        # render after the first commit.
        self._g_serve_fresh = self.metrics.gauge(
            "heatmap_serve_freshness_seconds",
            "/tiles render wall time minus the newest sink-committed "
            "event timestamp (ingest-to-serve freshness; NaN before "
            "the first render)")
        self._g_serve_fresh.set(float("nan"))
        self._g_shard_wm_lag = None
        if self.shardmap is not None:
            self.metrics.gauge(
                "heatmap_shard_index",
                "this runtime's shard in the H3-partitioned fleet "
                "(stream/shardmap.py)").set(cfg.shard_index)
            self.metrics.gauge(
                "heatmap_shard_count",
                "total runtime shards partitioning the stream "
                "(HEATMAP_SHARDS)").set(cfg.shards)
            # own watermark minus the fleet low bound: how far this
            # shard runs ahead of the slowest peer (0 = aligned or no
            # channel; the cutoff is held at the low bound either way)
            self._g_shard_wm_lag = self.metrics.gauge(
                "heatmap_shard_watermark_lag_seconds",
                "this shard's event-time high watermark minus the "
                "fleet's low watermark bound (how far ahead of the "
                "slowest shard this one runs; 0 when aligned or "
                "channel-less)")
            self._g_shard_wm_lag.set(0.0)
        self.positions_enabled = positions_enabled
        self.checkpoint_every = checkpoint_every
        # per-shard checkpoint namespace: N shard children share one
        # CHECKPOINT env, but each owns its own offsets/state — a
        # restarted shard resumes and replays ONLY its own stream
        # position (the multi-host p<idx> subdirectory discipline,
        # applied to the shard axis)
        ckpt_dir = (f"{cfg.checkpoint_dir}/shard{cfg.shard_index}"
                    if self.shardmap is not None else cfg.checkpoint_dir)
        self.ckpt = CheckpointManager(ckpt_dir)
        self.epoch = 0
        self.max_event_ts = I32_MIN
        self._intern_p: dict[str, int] = {}
        self._intern_v: dict[str, int] = {}
        # per-vehicle-intern-id last emitted ts (monotonic guard), grown on
        # demand; -2^62 = "never seen" sentinel below any valid epoch
        self._pos_ts = np.full(1024, -(2**62), np.int64)
        self._overflow_logged_at = -float("inf")
        self._fatal = False  # suppresses the exit checkpoint (close())
        self._ckpt_thread: threading.Thread | None = None
        self._ckpt_err: BaseException | None = None
        # On-device emit accumulation: packed emits of up to
        # emit_flush_k batches park in a device-resident ring and are
        # pulled in ONE transfer (engine.step.EmitRing), so K batches pay
        # one pull's round trips.  Flush is forced before
        # every checkpoint capture, on idle polls, at close, and under
        # watermark/growth pressure, so sink semantics and
        # replay-equivalence are unchanged.  Multi-host forces K=1:
        # accounting feeds the replicated grow/overflow decisions, which
        # must advance in lockstep.
        from heatmap_tpu.engine.step import EmitRing

        self._ring = EmitRing(cfg.emit_flush_k)
        self._prefetched: collections.deque = collections.deque()
        self._prefetch_n = max(0, cfg.prefetch_batches)
        self._closing = False       # stops the prefetch refill at close
        self._carried_last = False  # last DISPATCHED batch overshot
        self._last_flush_cutoff = I32_MIN  # watermark-pressure tracking
        self.metrics.gauge(
            "heatmap_emit_ring_pending",
            "packed emit batches parked on device awaiting the next flush",
            fn=lambda: (sum(len(r) for r in self._mesh_rings)
                        if getattr(self, "_mesh_rings", None) is not None
                        else len(self._ring)))
        # Runtime introspection (obs.runtimeinfo): the compile/retrace
        # tracker wraps the jitted entry points below; the memory
        # monitor samples on the step loop (1 Hz) and keeps the HBM /
        # live-buffer watermarks /healthz budgets compare against.
        # The ring-bytes callback reads self._ring dynamically — the
        # multi-host branch may swap the ring for a depth-1 one.
        from heatmap_tpu.obs.runtimeinfo import RuntimeIntrospection

        self.runtimeinfo = RuntimeIntrospection(
            self.metrics.registry,
            ring_bytes_fn=lambda: (
                sum(r.nbytes for r in self._mesh_rings)
                if getattr(self, "_mesh_rings", None) is not None
                else self._ring.nbytes))
        # live-prefix emit pulls (flush_pending): explicit knob wins;
        # auto = on for accelerators (where D2H bytes cost), off for CPU
        # (an extra round trip with nothing to save).  A banked pull A/B
        # for this platform (hwbank) overrides the static off-CPU
        # choice.
        # the ONE (res, window_s) pair list every consumer below shares:
        # aggregator construction AND the banked pull verdict must see
        # the same pair count
        pairs = list(dict.fromkeys(
            (res, wmin * 60) for res in cfg.resolutions
            for wmin in cfg.windows_minutes))
        # unique window lengths, for the host-side watermark advance
        # (_host_batch_max_ts) and the watermark-pressure flush trigger
        self._uniq_windows = sorted({win_s for _, win_s in pairs})
        if cfg.emit_pull == "auto" and jax.default_backend() != "cpu":
            from heatmap_tpu import hwbank

            # fused multi-pair programs get their own banked verdict —
            # the single-pair winner does not transfer (hwbank)
            self._prefix_pull = (hwbank.pull_winner(len(pairs))
                                 or "prefix") == "prefix"
        else:
            self._prefix_pull = cfg.emit_pull == "prefix"
        self._carry_cols = None  # overshoot remainder of a batch-granular poll
        self._carry_polled_at = 0.0  # lineage poll stamp of that remainder
        self._carry_shard_cells = None  # that remainder's partition-key cells
        self._ckpt_due = False  # cadence hit while mid-carry; commit ASAP
        self._last_pull_s = 0.0  # wall of the most recent deferred pull
        self._n_active_peak = 0  # max live groups (any pair) since startup
        self._prev_active: dict[tuple, int] = {}  # last n_active per pair
        self._mint_peak = 0      # max per-batch new-group count seen
        if cfg.grow_margin == "observed" and cfg.on_overflow != "fail":
            log.warning(
                "HEATMAP_GROW_MARGIN=observed with HEATMAP_ON_OVERFLOW=%s:"
                " a minting burst beyond the observed margin DROPS groups"
                " (loudly, at /metrics) — set HEATMAP_ON_OVERFLOW=fail for"
                " the lossless stop-and-replay backstop", cfg.on_overflow)
        self._step_began = None  # monotonic start of the in-flight step
        self._hb_watchdog = None  # in-flight beacon thread (lazy, daemon)
        self._cap_max = 1 << (cfg.state_max_log2
                              or cfg.state_capacity_log2 + 4)

        # one aggregator per (resolution, window) pair (BASELINE configs 4/5)
        self.aggs: dict[tuple[int, int], object] = {}
        cap = 1 << cfg.state_capacity_log2
        n_shards_planned = (mesh.devices.size
                            if mesh is not None and mesh.devices.size > 1
                            else 1)
        if (cfg.grow_margin == "worst" and self._cap_max > cap
                and cap * n_shards_planned < 2 * cfg.batch_size):
            # one batch can mint up to batch_size new groups: below this
            # floor the first batches could overflow before stats-driven
            # growth sees them.  Start at the floor (loudly) — cheap here,
            # before any state exists.
            grown = cap
            while (grown * n_shards_planned < 2 * cfg.batch_size
                   and grown < self._cap_max):
                grown *= 2
            log.warning(
                "STATE_CAPACITY_LOG2=%d holds less than one batch of new "
                "groups; starting at 2^%d rows/shard (set "
                "HEATMAP_STATE_MAX_LOG2=%d to pin the configured size)",
                cfg.state_capacity_log2, grown.bit_length() - 1,
                cfg.state_capacity_log2)
            cap = grown
        bins = cfg.speed_hist_bins
        self._multi = None
        self._sharded = None
        self._parted = None
        self._mesh_mode = None          # "partitioned" | "shuffle" | None
        self.meshmap = None             # MeshPartition (partitioned mode)
        self._mesh_rings = None         # per-device EmitRings
        self._mesh_governors = None     # per-device BatchGovernors
        # knob-pin telemetry (satellite bugfix): any place that silently
        # degrades the fast path (multi-host forcing emit_flush_k=1 /
        # prefetch=0, a governor request a topology can't honor) records
        # its reason here AND pins heatmap_fastpath_pinned{reason=}=1,
        # so an attached run that lost the ring is diagnosable from
        # /metrics and /healthz instead of one INFO log line
        self._fastpath_pinned: dict[str, str] = {}
        self._g_fastpath_pinned = self.metrics.gauge(
            "heatmap_fastpath_pinned",
            "1 per reason the runtime pinned fast-path knobs down "
            "(emit_flush_k=1 / prefetch_batches=0 for multi-host "
            "lockstep, a governor request the topology cannot honor) — "
            "a run silently serving degraded throughput is diagnosable "
            "from telemetry",
            labels=("reason",))
        if mesh is not None and mesh.devices.size > 1:
            mesh_multiproc = jax.process_count() > 1
            want_part = (cfg.mesh_partitioned in ("auto", "1")
                         and not mesh_multiproc)
            if cfg.mesh_partitioned == "1" and mesh_multiproc:
                log.warning(
                    "HEATMAP_MESH_PARTITIONED=1 ignored: multi-host "
                    "meshes keep the ICI-shuffle lockstep path")
            if want_part:
                # partitioned fast path (ISSUE 11 tentpole): the feed
                # pre-partitions each batch by H3 parent cell and every
                # device runs the fused single-device program over ITS
                # OWN rows — no all_to_all, no lockstep, per-device
                # emit rings + governors (parallel.sharded
                # .PartitionedAggregator); merged at the view
                # upsert-only exactly like the PR 7 process fleet.
                from heatmap_tpu.engine.step import EmitRing as _Ring
                from heatmap_tpu.parallel import PartitionedAggregator
                from heatmap_tpu.stream.shardmap import MeshPartition

                self._parted = PartitionedAggregator(
                    mesh,
                    [AggParams(res=res, window_s=win_s,
                               emit_capacity=min(cfg.batch_size, cap),
                               speed_hist_max=cfg.speed_hist_max_kmh)
                     for res, win_s in pairs],
                    capacity_per_shard=cap, batch_size=cfg.batch_size,
                    hist_bins=bins,
                )
                self._parted.instrument(self.runtimeinfo.compile.wrap)
                self._mesh_mode = "partitioned"
                self.meshmap = MeshPartition(
                    self._parted.n_shards, min(cfg.resolutions),
                    cfg.shard_res, outer_shards=cfg.shards)
                log.info("partitioned mesh runtime: %s",
                         self.meshmap.describe())
                for res, win_s in pairs:
                    self.aggs[(res, win_s // 60)] = \
                        self._parted.view(res, win_s)
                n_dev = self._parted.n_shards
                self._mesh_rings = [_Ring(cfg.emit_flush_k)
                                    for _ in range(n_dev)]
                self._mesh_epoch_pend: dict[int, int] = {}
                self._mesh_shard_active: dict[tuple, int] = {}
                self._shard_active_peak = 0
                self._mesh_idle: dict[tuple, tuple] = {}
                self._mesh_rows = [0] * n_dev
                self._mesh_pulls = [0] * n_dev
                self._mesh_pull_batches = [0] * n_dev
                self.metrics.gauge(
                    "heatmap_mesh_devices",
                    "mesh devices running the partitioned shard-per-"
                    "device fast path (0/absent = not a partitioned "
                    "mesh run)").set(n_dev)
                self._c_mesh_rows = self.metrics.registry.counter(
                    "heatmap_mesh_rows_total",
                    "live rows folded per mesh shard (the feed's H3 "
                    "partition of each batch)", labels=("shard",))
                self._c_mesh_pulls = self.metrics.registry.counter(
                    "heatmap_mesh_pulls_total",
                    "device->host emit pulls per mesh shard (one per "
                    "ring flush; the idle-flush floor on cold shards)",
                    labels=("shard",))
                ring_fam = self.metrics.gauge(
                    "heatmap_mesh_ring_pending",
                    "packed emit batches parked on each mesh shard's "
                    "device awaiting its next flush",
                    labels=("shard",))
                for d in range(n_dev):
                    ring_fam.labels(shard=str(d)).fn = (
                        lambda i=d: len(self._mesh_rings[i]))
                    # materialize the per-shard counter children so the
                    # exposition carries every shard from step one
                    self._c_mesh_rows.labels(shard=str(d))
                    self._c_mesh_pulls.labels(shard=str(d))
            else:
                from heatmap_tpu.parallel import ShardedAggregator

                # ALL pairs fused into one sharded program: one
                # dispatch, one all_to_all, one addressable pull per
                # batch (parallel.sharded)
                self._sharded = ShardedAggregator(
                    mesh,
                    [AggParams(res=res, window_s=win_s,
                               emit_capacity=min(cfg.batch_size, cap),
                               speed_hist_max=cfg.speed_hist_max_kmh)
                     for res, win_s in pairs],
                    capacity_per_shard=cap, batch_size=cfg.batch_size,
                    hist_bins=bins, bucket_factor=cfg.bucket_factor,
                )
                self._sharded.instrument(self.runtimeinfo.compile.wrap)
                self._mesh_mode = "shuffle"
                for res, win_s in pairs:
                    self.aggs[(res, win_s // 60)] = \
                        self._sharded.view(res, win_s)
        else:
            # single device: ALL pairs fused into one program — one
            # dispatch and one device->host pull per batch regardless of
            # how many (res, window) pairs are configured (engine.multi)
            from heatmap_tpu.engine.multi import MultiAggregator

            self._multi = MultiAggregator(
                pairs, capacity=cap, batch_size=cfg.batch_size,
                emit_capacity=min(cfg.batch_size, cap), hist_bins=bins,
                speed_hist_max=cfg.speed_hist_max_kmh,
            )
            self._multi.instrument(self.runtimeinfo.compile.wrap)
            for res, win_s in pairs:
                self.aggs[(res, win_s // 60)] = self._multi.view(res, win_s)
        self._g_capacity.set(cap)
        # per-shard device dispatch clock: the fused aggregator keeps a
        # host-wall accumulator per local shard; a callback gauge reads
        # it at scrape time so the step loop pays nothing extra
        agg_obs = self._agg()
        fam = self.metrics.gauge(
            "heatmap_device_dispatch_seconds",
            "cumulative host wall seconds spent dispatching the fused "
            "device step (one clock per local dispatch stream)",
            labels=("shard",))
        for shard, _ in enumerate(getattr(agg_obs, "device_seconds", ())):
            fam.labels(shard=str(shard)).fn = (
                lambda a=agg_obs, s=shard: a.device_seconds[s])
        # HEATMAP_H3_IMPL=native: snap on the host (C++, ~11x faster per
        # CPU core than the XLA-CPU snap and f64-exact) and feed the fold
        # pre-computed keys — both paths: the fused single-device step
        # (engine.multi prekeys) and the sharded step (each host snaps
        # its LOCAL slice; parallel.sharded prekeys).
        self._host_snap = None
        self._idle_keys: dict[int, dict] = {}  # zero snap keys by shape
        h3_impl = os.environ.get("HEATMAP_H3_IMPL", "auto")
        self._h3_env = h3_impl
        # Freeze the in-program snap POLICY now (r5 review): resolving
        # lazily at trace time would let a hardware bank file appearing
        # or changing MID-RUN flip the kernel at a slab-growth retrace
        # and float the checkpointed impl name.  The slot is
        # module-global: concurrent runtimes in one process share one
        # policy (a resumed runtime's checkpoint pin overwrites this
        # below; mixing runtimes with conflicting policies is
        # unsupported and warned about).
        from heatmap_tpu.engine import step as engine_step

        snap_policy = engine_step.resolve_snap_policy(ignore_pin=True)
        if engine_step.SNAP_IMPL not in (None, snap_policy):
            log.warning(
                "overriding in-process H3 snap policy pin %r -> %r; "
                "concurrent runtimes with different snap policies in "
                "one process are unsupported",
                engine_step.SNAP_IMPL, snap_policy)
        engine_step.SNAP_IMPL = snap_policy
        # a Pallas policy this backend cannot run fails here, at start-up,
        # rather than at the first trace (inprogram_snap_name raises)
        engine_step.inprogram_snap_name(min(cfg.resolutions))
        # Freeze the merge-impl bank verdict the same way (r5 review):
        # one snapshot at init — never the live file from inside a
        # trace — so a bank rewritten mid-run cannot
        # recompile a different lockstep program after the multihost
        # collective below validated this snapshot.
        from heatmap_tpu import hwbank

        merge_pin = hwbank.merge_winner()
        prior = engine_step.MERGE_BANK_PIN
        if prior is not engine_step._BANK_LIVE and prior != merge_pin:
            log.warning(
                "overriding in-process merge bank pin %r -> %r; "
                "concurrent runtimes with different bank verdicts in "
                "one process are unsupported", prior, merge_pin)
        engine_step.MERGE_BANK_PIN = merge_pin
        # auto: on the CPU backend the C++ host pre-snap is the measured
        # winner (round-3 autotune on this host: native+sort 1.11M ev/s
        # vs xla+sort 0.23M — the in-program snap dominates the batch);
        # on accelerators stay with the in-program snap until an on-chip
        # measurement says otherwise — except on a partitioned stream
        # (process shards, the partitioned mesh): rows are routed by
        # their host-snapped cell, so the fold must group by that same
        # cell.  Grouped by the f32 in-program snap instead, a point on
        # a cell edge opens its (cell, window) group on a second owner,
        # the store keeps one of the two partial groups, and events are
        # lost (measured on a v5e mesh, PR 21).
        self._partitioned = (self.shardmap is not None
                             or self._parted is not None)
        want_native = (h3_impl == "native" or
                       (h3_impl == "auto"
                        and (self._partitioned
                             or jax.default_backend() == "cpu")))
        if want_native and all(r <= 10 for r in cfg.resolutions):
            from heatmap_tpu.hexgrid import native_snap

            if native_snap.available():
                self._host_snap = native_snap.snap_arrays
            elif h3_impl == "native":
                log.warning("HEATMAP_H3_IMPL=native but no C++ toolchain; "
                            "using the in-program snap")
        self._require_partition_snap()
        # static sink context per pair (packed fast path, sink.base)
        from heatmap_tpu.sink.base import TilePackMeta

        self._pack_meta = {}
        for res in cfg.resolutions:
            for wmin in cfg.windows_minutes:
                default = wmin == cfg.tile_minutes
                self._pack_meta[(res, wmin)] = TilePackMeta(
                    city=cfg.city,
                    grid=cfg.pair_grid(res, wmin),
                    window_s=wmin * 60,
                    ttl_minutes=cfg.ttl_minutes,
                    window_minutes_tag=0 if default else wmin,
                    with_p95=bins > 0,
                )
        # multi-host: each process feeds its share of the global batch and
        # checkpoints its own shards under a per-process subdirectory
        # (per-host Kafka partitions → per-host offsets; parallel.multihost)
        self._feed_batch = cfg.batch_size
        self._multiproc = jax.process_count() > 1
        if self._multiproc and (self._ring.capacity > 1
                                or self._prefetch_n):
            # lockstep runs: accounting (the replicated grow/overflow
            # inputs) and poll ordering must advance identically on every
            # host, so emit accumulation and prefetch stay single-host
            # optimizations for now (EmitRing imported above)
            log.info("multi-host run: forcing emit_flush_k=1 and "
                     "prefetch_batches=0 (lockstep accounting)")
            self._note_fastpath_pinned(
                "multihost_lockstep",
                f"emit_flush_k {self._ring.capacity}->1, "
                f"prefetch_batches {self._prefetch_n}->0")
            self._ring = EmitRing(1)
            self._prefetch_n = 0
        if self._multiproc:
            from heatmap_tpu.parallel.multihost import global_batch_to_local

            if mesh is None or len(
                    {d.process_index for d in mesh.devices.ravel()}) < 2:
                # independent per-host SingleAggregators would upsert
                # partial counts for the SAME tile _ids (silent clobbering)
                raise ValueError(
                    "multi-process run requires a global sharded mesh "
                    "spanning all processes (parallel.make_mesh after "
                    "multihost.init_from_env)")
            self._feed_batch = global_batch_to_local(cfg.batch_size)
            self.ckpt = CheckpointManager(
                f"{cfg.checkpoint_dir}/p{jax.process_index()}")
            self._gpair = _make_global_pair(mesh)
            self._global_live = 1.0
            # cross-host agreement on the native host snap: hosts with
            # and without the C++ toolchain would dispatch DIFFERENT
            # jitted programs (_step_packed_pre vs _step_packed) into the
            # same lockstep collectives — and even benignly, f64-exact
            # C++ keys on one host vs f32 XLA keys on another would make
            # tile membership depend on which host ingested the event.
            # One startup collective (run unconditionally: an env var
            # skewed across hosts must not desync the barrier itself)
            # keeps the choice all-or-nothing.
            have, total, _ = self._gpair(
                1.0 if self._host_snap is not None else 0.0, 1.0)
            if self._host_snap is not None and have != total:
                log.warning(
                    "HEATMAP_H3_IMPL=native disabled: only %d/%d shards "
                    "have the C++ toolchain — a split would desync the "
                    "lockstep programs", int(have), int(total))
                self._host_snap = None
            elif self._host_snap is None and have > 0:
                log.warning(
                    "peer hosts requested the native snap but this host "
                    "can't provide it; all hosts fall back to in-program")
            self._require_partition_snap()
            # cross-host agreement on the BANK-derived trace-time
            # choices (r5 review): each host resolved its snap policy
            # and merge winner from its LOCAL bank (hwbank) above; a
            # skewed checkout/bank must not let hosts trace different
            # kernels (pallas-vs-xla snaps re-key f32 cell-edge events
            # by ingesting host; divergent merge impls compile
            # different lockstep programs).  Unanimity per value via
            # zero-variance over (code, code^2) sums — every host
            # reaches the same verdict, so the fallbacks converge.
            from heatmap_tpu.engine import step as engine_step

            def _unanimous(code: float) -> bool:
                s, s2, n = self._gpair(code, code * code, 1.0)
                return bool(s == code * n and s2 == code * code * n)

            # the RESOLVED kernel must match on every host.  A host that
            # cannot lower a Pallas policy raised above; what remains is
            # policy skew.  A bank-derived "pallas" yields to the XLA
            # snap; an explicit HEATMAP_H3_IMPL=pallas does not.
            snap_resolved = engine_step.inprogram_snap_name(
                min(cfg.resolutions))
            if not _unanimous(1.0 if snap_resolved == "pallas" else 0.0):
                if snap_resolved == "pallas" and h3_impl == "pallas":
                    raise RuntimeError(
                        "HEATMAP_H3_IMPL=pallas but not every host "
                        "resolves the Pallas snap")
                if snap_resolved == "pallas":
                    log.warning(
                        "banked pallas snap disabled: not every host "
                        "resolves it — all hosts use the XLA snap")
                engine_step.SNAP_IMPL = "xla"
            mw = engine_step.MERGE_BANK_PIN  # frozen snapshot from above
            if not _unanimous(
                    float({"sort": 1, "rank": 2, "probe": 3}.get(mw, 0))):
                if mw is not None:
                    log.warning(
                        "banked merge winner %r ignored: hosts' "
                        "hardware banks disagree — every host uses the "
                        "static auto rule", mw)
                engine_step.MERGE_BANK_PIN = None

        # the pair whose stats define the batch-level counters
        self._primary = (
            (cfg.h3_res, cfg.tile_minutes)
            if (cfg.h3_res, cfg.tile_minutes) in self.aggs
            else next(iter(self.aggs))
        )

        self._maybe_resume()
        # Adaptive micro-batching (stream/govern.py): with
        # HEATMAP_GOVERN=1 the static batch/flush-K/prefetch knobs
        # become INITIAL values and a feedback governor on the step
        # loop resizes them against HEATMAP_SLO_FRESHNESS_P50_MS.
        # Single-device fused path only: multi-host lockstep pins the
        # knobs (accounting must advance identically on every host),
        # and the mesh-sharded program's collective shapes are not on
        # the warmed ladder.  Each H3-partitioned shard process
        # (stream/shardmap.py) governs independently — skewed shards
        # converge to different batch sizes while the watermark-aligned
        # cutoff stays fleet-bounded; the fleet member snapshot carries
        # the decisions via the govern gauge families.  Constructed
        # AFTER the resume: a restored-and-grown slab must be warmed at
        # its final shape.
        self.governor = None
        if cfg.govern:
            if self._multiproc or (self._multi is None
                                   and self._parted is None):
                log.warning(
                    "HEATMAP_GOVERN=1 ignored: the governor runs the "
                    "fused single-device path and the partitioned mesh "
                    "path only (multi-host and ICI-shuffle runs pin "
                    "their knobs for lockstep)")
                self._note_fastpath_pinned(
                    "govern_unsupported_topology",
                    "HEATMAP_GOVERN=1 ignored (multi-host or "
                    "ICI-shuffle mesh: knobs pinned for lockstep)")
            elif self._parted is not None:
                # per-mesh-shard governing (ISSUE 11 tentpole (3)): one
                # AIMD governor per device over a SHARED warmed ladder —
                # skewed devices converge to different batch buckets
                # while the cutoff trajectory stays batch-granular (the
                # watermark advances from the pre-partition rows).  The
                # retrace-freeze guardrail latches per-LADDER: all
                # governors poll one CompileTracker, so a post-warmup
                # retrace anywhere on the mesh freezes every shard.
                from heatmap_tpu.stream.govern import BatchGovernor

                govs = []
                for d in range(self._parted.n_shards):
                    govs.append(BatchGovernor(
                        cfg, self.metrics.registry,
                        event_age=self.metrics.event_age.labels(
                            bound="mean"),
                        compile_tracker=self.runtimeinfo.compile,
                        memory=self.runtimeinfo.memory, shard=d))
                self.runtimeinfo.compile.warmup += len(govs[0].ladder)
                self._warm_mesh_ladder(govs[0].ladder)
                for gov in govs:
                    gov._retrace_base = gov._retraces()
                self._mesh_governors = govs
                if self.flightrec is not None:
                    self.flightrec.add_source(
                        "govern", lambda: (
                            [g.snapshot() for g in self._mesh_governors]
                            if self._mesh_governors else None))
            else:
                from heatmap_tpu.stream.govern import BatchGovernor

                gov = BatchGovernor(
                    cfg, self.metrics.registry,
                    event_age=self.metrics.event_age.labels(bound="mean"),
                    compile_tracker=self.runtimeinfo.compile,
                    memory=self.runtimeinfo.memory)
                # the ladder warmup below is len(ladder) extra calls into
                # the instrumented step before steady state; widen the
                # tracker's warmup so they never read as retraces
                self.runtimeinfo.compile.warmup += len(gov.ladder)
                self._warm_ladder(gov.ladder)
                # re-baseline AFTER warming: any retrace from here on
                # (a slab grow invalidating the warmed shapes, an
                # unwarmed shape slipping through) freezes the governor
                gov._retrace_base = gov._retraces()
                self.governor = gov
                if self.flightrec is not None:
                    self.flightrec.add_source(
                        "govern", lambda: (self.governor.snapshot()
                                           if self.governor else None))
        if self._parted is not None and self._mesh_governors is None:
            # the ungoverned mesh warms its one bucket the same way
            self.runtimeinfo.compile.warmup += 1
            self._warm_mesh_ladder((self._feed_batch,))
        # offsets as of the last DISPATCHED batch: checkpoints commit these,
        # never the live source offsets, so a batch polled but not yet
        # dispatched (exception between poll and dispatch) always replays
        self._offsets_dispatched = self.source.offset()
        # SLO watchdog + stack sampler, armed with the flight recorder:
        # auto-capture an enriched dump when /healthz degrades, even
        # when nobody is polling it (obs.runtimeinfo.SloWatchdog;
        # HEATMAP_SLO_WATCHDOG_S=0 disables).  Started LAST — the
        # watchdog thread evaluates healthz against this runtime, so
        # every attribute it reads must exist.
        self.slo_watchdog = None
        if self.flightrec is not None:
            from heatmap_tpu.obs.prof import get_sampler
            from heatmap_tpu.obs.runtimeinfo import SloWatchdog

            get_sampler().ensure_started()
            # fleet mode: degraded transitions broadcast an episode id
            # over the channel (env default) so every member's dump for
            # the incident correlates; the tag names this member
            self.slo_watchdog = SloWatchdog(self, tag=self._fresh_tag)
            self.slo_watchdog.start()
        # Telemetry time machine (obs.tsdb) + SLO burn-rate engine
        # (obs.slo): a sampler thread records this member's exposition
        # and /healthz verdict into history rings (persisted under
        # HEATMAP_TSDB_DIR) and evaluates error-budget burn on every
        # scrape.  Knob-off, neither module is imported and no family
        # registers (the differential test pins the exposition
        # byte-identical).
        self.tsdb = None
        self.slo_engine = None
        if cfg.tsdb:
            from heatmap_tpu.obs import ENV_CHANNEL
            from heatmap_tpu.obs.slo import SloEngine
            from heatmap_tpu.obs.tsdb import TsdbRecorder

            def _tsdb_scrape() -> str:
                extra = dict(self.writer.counters)
                extra.pop("sink_retries", None)
                extra.update(getattr(self.source, "counters", None)
                             or {})
                return self.metrics.expose_text(extra_counters=extra)

            def _tsdb_healthz() -> dict:
                from heatmap_tpu.serve.api import healthz_payload

                return healthz_payload(self)[0]

            self.tsdb = TsdbRecorder(
                _tsdb_scrape, tag=self._fresh_tag,
                dir_path=cfg.tsdb_dir or None,
                healthz_fn=_tsdb_healthz,
                registry=self.metrics.registry,
                scrape_s=cfg.tsdb_scrape_s,
                retain_s=cfg.tsdb_retain_s, hot_s=cfg.tsdb_hot_s,
                flush_s=cfg.tsdb_flush_s)
            self.slo_engine = SloEngine(
                self.tsdb, registry=self.metrics.registry,
                tag=self._fresh_tag,
                budget_frac=cfg.slo_budget_frac,
                budget_window_s=cfg.slo_budget_window_s,
                channel_path=os.environ.get(ENV_CHANNEL),
                flightrec=self.flightrec)
            self.tsdb.start()

    # ------------------------------------------------------------------
    def _maybe_resume(self) -> None:
        at_epoch: int | None = None
        if self._multiproc:
            # hosts may have crashed between each other's commits; agree on
            # the newest epoch EVERY host retains, or start fresh together.
            # KEEP_COMMITS=2 covers the <=1-commit divergence the commit
            # barrier allows.
            from jax.experimental import multihost_utils

            local = self.ckpt.available_epochs()
            latest = local[-1] if local else -1
            common = int(np.min(multihost_utils.process_allgather(
                np.int64(latest))))
            if common < 0:
                if latest >= 0:
                    log.warning(
                        "peer host has no checkpoint; discarding local "
                        "commits (epochs %s) and starting fresh", local)
                return
            if common not in local:
                raise RuntimeError(
                    f"hosts diverged beyond checkpoint retention: common "
                    f"epoch {common} not in local commits {local}; clear "
                    f"{self.cfg.checkpoint_dir} on every host")
            if common != latest:
                log.warning("resuming at common epoch %d (local latest %d)",
                            common, latest)
            at_epoch = common
        meta = self.ckpt.load_meta(epoch=at_epoch)
        if not meta:
            return
        log.info("resuming from checkpoint: %s", meta)
        self._pin_snap_impl(meta.get("snap_impl"))
        snap_shards = meta.get("shards")
        if snap_shards is not None and snap_shards != self._local_shards:
            # even an exact-shape restore would be wrong: rows would be
            # reinterpreted as different shard blocks (per-shard sorted
            # runs, key ownership) — silently corrupting aggregates
            raise RuntimeError(
                f"checkpoint written with {snap_shards} local shard(s), "
                f"this run has {self._local_shards}; restore the original "
                f"device topology or clear {self.cfg.checkpoint_dir}")
        ck_mode = meta.get("mesh_mode")
        if ck_mode is None and snap_shards is not None and snap_shards > 1:
            # pre-mesh-mode multi-shard checkpoints all came from the
            # ICI-shuffle path (the only mesh mode that existed)
            ck_mode = "shuffle"
        if (ck_mode or self._mesh_mode) and ck_mode != self._mesh_mode:
            # same block layout, DIFFERENT key ownership (mix32 hash vs
            # H3 parent): a cross-mode restore would silently duplicate
            # groups across devices
            raise RuntimeError(
                f"checkpoint state was keyed in mesh mode {ck_mode!r} "
                f"but this run is {self._mesh_mode!r}; restore the "
                f"original mode (HEATMAP_MESH_PARTITIONED) or clear "
                f"{self.cfg.checkpoint_dir}")
        self.epoch = meta.get("epoch", 0)
        self.max_event_ts = meta.get("max_event_ts", I32_MIN)
        self.source.seek(meta.get("offset"))
        for (res, wmin), agg in self.aggs.items():
            st = self.ckpt.load_state(res, wmin * 60, epoch=at_epoch)
            if st is None:
                continue
            st = TileState(*st)
            try:
                agg.restore(st)
            except ValueError as e:
                # capacity changes across restarts are absorbed: pad the
                # snapshot up to the configured capacity, or grow the
                # aggregators to a LARGER snapshot (a grown run).  Anything
                # else — hist bins, a shard-count change (rows would be
                # reinterpreted as the wrong shard blocks), legacy metas
                # without a recorded shard count, shrink below live rows —
                # still refuses: seeking past processed offsets with an
                # unloadable state would silently lose aggregates.
                try:
                    self._restore_resized(agg, st, meta.get("shards"))
                except (ValueError, RuntimeError) as e2:
                    raise RuntimeError(
                        f"checkpoint state for (res={res}, window={wmin}m) "
                        f"does not match the config ({e}; resize: {e2}); "
                        f"restore STATE_CAPACITY_LOG2/SPEED_HIST_BINS or "
                        f"clear {self.cfg.checkpoint_dir}"
                    ) from e2
        if self.infer is not None:
            # extras are auxiliary: a commit predating the reducer (or
            # written with kalman off) yields None and the engine simply
            # starts cold — filters re-seed from the replayed stream
            data = self.ckpt.load_extra("infer", epoch=at_epoch)
            if data is not None:
                self.infer.restore(data, self._intern_v)
                log.info("restored inference entity table: %d entities",
                         self.infer.table.occupancy)
        if self.quality is not None:
            # pending scorecards survive the restart and score against
            # the HISTORY tier when their target spans have already
            # left the rebuilt live view
            data = self.ckpt.load_extra("quality", epoch=at_epoch)
            if data is not None:
                n = self.quality.restore_extra(data)
                log.info("restored quality ledger: %d pending "
                         "scorecards", n)

    @property
    def _snap_impl_name(self) -> str:
        """The H3 snap keying this run's state: host C++ pre-snap vs the
        RESOLVED in-program snap ("pallas" | "xla" — under "auto" a
        banked on-chip A/B can pick pallas, engine.step.inprogram_snap_name).
        Recorded in every checkpoint so the pin below survives the bank
        file appearing or vanishing across a resume.  Stable for the
        life of the runtime: the policy behind it was frozen into
        engine_step.SNAP_IMPL at init.  Probed at min(resolutions) —
        pallas eligibility is per-res (res <= 10) and the LOWEST res is
        the one eligible whenever any is; higher ineligible resolutions
        degrade to xla deterministically from the same recorded policy."""
        if self._host_snap is not None:
            return "native"
        from heatmap_tpu.engine import step as engine_step

        return engine_step.inprogram_snap_name(min(self.cfg.resolutions))

    def _require_partition_snap(self) -> None:
        """A partitioned stream (process shards, the partitioned mesh)
        routes rows by their host-snapped cell, so its fold must group
        by that same cell: raise whenever the host snap is off, at
        start-up, after the multihost agreement and after a checkpoint
        pin alike."""
        if self._partitioned and self._host_snap is None:
            raise RuntimeError(
                "a partitioned stream (HEATMAP_SHARDS > 1 or a partitioned "
                "mesh) groups by its host-snapped partition cells: it "
                "needs the C++ toolchain, resolutions <= 10, "
                "HEATMAP_H3_IMPL=auto|native and no checkpoint keyed with "
                "an in-program snap (HEATMAP_MESH_PARTITIONED=0 keeps a "
                "mesh on the shuffle path)")

    def _pin_snap_impl(self, ck_snap: str | None) -> None:
        """Keep the snap impl FIXED across a resume (ADVICE r4 #1).

        The native C++ (f64) and XLA (f32) snaps agree except for points
        landing exactly on a cell edge after f32 rounding; flipping impls
        mid-stream (e.g. a supervisor TPU→CPU failover where
        HEATMAP_H3_IMPL=auto re-resolves to native on the CPU backend)
        would re-key those edge events and split their groups across the
        resume.  Under ``auto`` the checkpointed impl wins; an explicit
        env override is honored but the re-keying hazard is logged.
        """
        if ck_snap not in ("native", "xla", "pallas"):
            # host-uniform branch: the field is written post-agreement,
            # so every host sees the same (absent/legacy) value and none
            # reaches the collective below — no desync
            return
        if ck_snap != self._snap_impl_name:
            if self._h3_env != "auto":
                log.warning(
                    "checkpoint state was keyed with the %r H3 snap but "
                    "HEATMAP_H3_IMPL=%s forces %r; events on f32 cell "
                    "edges may re-key across this resume", ck_snap,
                    self._h3_env, self._snap_impl_name)
            elif ck_snap == "native":
                from heatmap_tpu.hexgrid import native_snap

                was = self._snap_impl_name
                if native_snap.available():
                    self._host_snap = native_snap.snap_arrays
                    log.info("pinned H3 snap impl 'native' from "
                             "checkpoint (was %r under "
                             "HEATMAP_H3_IMPL=auto)", was)
                else:
                    log.warning(
                        "checkpoint state was keyed with the native C++ "
                        "snap but no C++ toolchain is available; "
                        "continuing with the in-program snap (f32 "
                        "cell-edge events may re-key)")
            else:
                # in-program impl recorded ("xla" | "pallas"): disable
                # any host pre-snap and pin the engine's trace-time
                # resolution so a hardware bank appearing/vanishing
                # across the resume (hwbank's "auto" input) cannot flip
                # the in-program kernel mid-stream.  A "pallas" pin on a
                # backend that cannot run the kernel raises here
                # (engine.step.inprogram_snap_name).
                from heatmap_tpu.engine import step as engine_step

                was = self._snap_impl_name
                self._host_snap = None
                engine_step.SNAP_IMPL = ck_snap
                log.info("pinned H3 snap impl %r from checkpoint "
                         "(was %r under HEATMAP_H3_IMPL=auto)",
                         self._snap_impl_name, was)
        if self._multiproc:
            # same all-or-nothing rule as startup.  EVERY host must reach
            # this collective whenever ck_snap is valid — the pin outcome
            # is per-host (toolchain loss, skewed HEATMAP_H3_IMPL), so an
            # early return above on one host would strand its peers in
            # the barrier (r5 review finding)
            have, total, _ = self._gpair(
                1.0 if self._host_snap is not None else 0.0, 1.0)
            if self._host_snap is not None and have != total:
                log.warning(
                    "only %d/%d hosts resolved the native snap after the "
                    "checkpoint pin; all hosts fall back to in-program "
                    "(f32 cell-edge events may re-key)", int(have),
                    int(total))
                self._host_snap = None
            elif self._host_snap is None and have > 0:
                log.warning(
                    "peer hosts resolved the native snap but this host "
                    "cannot; all hosts fall back to in-program")
        self._require_partition_snap()

    def _agg(self):
        """Whichever aggregator this runtime drives: the fused
        single-device program, the ICI-shuffle mesh, or the partitioned
        shard-per-device mesh."""
        if self._multi is not None:
            return self._multi
        if self._sharded is not None:
            return self._sharded
        return self._parted

    @property
    def _local_shards(self) -> int:
        """Shard blocks in THIS process's snapshots (1 on the fused
        single-device path)."""
        if self._sharded is not None:
            return self._sharded.local_shards
        if self._parted is not None:
            return self._parted.local_shards
        return 1

    def _note_fastpath_pinned(self, reason: str, detail: str) -> None:
        """Record a fast-path knob pin (satellite bugfix): gauge child
        per reason + the dict /healthz surfaces, so a run silently
        serving degraded throughput is diagnosable from telemetry."""
        self._fastpath_pinned[reason] = detail
        self._g_fastpath_pinned.labels(reason=reason).set(1.0)

    def _ring_pending(self) -> int:
        """Parked emit batches bounding the stats lag: the single ring's
        depth, or the DEEPEST per-device ring on a partitioned mesh
        (each shard's slab lags by its own ring; growth margins must
        cover the worst one)."""
        if self._mesh_rings is not None:
            return max((len(r) for r in self._mesh_rings), default=0)
        return len(self._ring)

    def _restore_resized(self, agg, st: TileState,
                         snap_shards: int | None) -> None:
        from heatmap_tpu.engine.state import resize_state

        shards = self._local_shards
        if snap_shards is None:
            raise ValueError(
                "checkpoint does not record its shard count; only an "
                "exact-shape restore is safe")
        if snap_shards != shards:
            raise ValueError(
                f"checkpoint written with {snap_shards} local shard(s), "
                f"this run has {shards}")
        snap_cap = st.key_hi.shape[0] // shards
        if snap_cap > agg.capacity_per_shard:
            grower = self._agg()
            grower.grow(snap_cap)  # capacity is shared across pairs
            agg.restore(st)
        else:
            agg.restore(resize_state(st, agg.capacity_per_shard, shards))

    def _checkpoint(self) -> None:
        if self._multiproc:
            # The mid-carry skip must be decided COLLECTIVELY.  close()
            # reaches this point on every host (lockstep exits: the
            # max_batches counter advances on the global had-events flag,
            # and _fatal derives from replicated stats), but the carry is
            # per-host — run(max_batches=N) can end with one host
            # mid-carry while its peers are carry-free.  A local early
            # return here would strand those peers in the commit barrier
            # below forever.  All hosts agree first: if ANY carries, ALL
            # skip (the uncommitted tail just replays on resume — every
            # sink write is an idempotent upsert).  The step-loop call
            # site gates on the same global flag, so this collective is
            # reached on all hosts there too (it reads carry_any == 0).
            _, _, carry_any = self._gpair(
                0.0, 0.0, float(self._carried_last))
            if carry_any > 0:
                return
        elif self._carried_last:
            # mid-record: the last DISPATCHED batch overshot and its
            # record's tail rows are still undispatched (in _carry_cols
            # or the prefetch queue) — state would double-fold the
            # already-dispatched slices on replay.  Wait for the tail to
            # drain (a step or two); the next eligible epoch commits.
            return
        # the commit must cover every batch whose offsets it advances past
        self.flush_pending()
        if self._multiproc:
            # all hosts reach the commit point (same epoch — epochs advance
            # in lockstep) before any commits, so retained commits can
            # never diverge by more than one epoch across hosts.  Stays
            # synchronous: collectives must not run off the step thread.
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(f"heatmap-ckpt-{self.epoch}")
            # commit AFTER sink writes are durable (idempotent replay window)
            self.writer.drain()
            states = {
                (res, wmin * 60): agg.snapshot()
                for (res, wmin), agg in self.aggs.items()
            }
            self.ckpt.commit(self._offsets_dispatched, self.max_event_ts,
                             self.epoch, states, shards=self._local_shards,
                             snap_impl=self._snap_impl_name,
                             mesh_mode=self._mesh_mode,
                             extras=self._infer_extras())
            self.metrics.count("checkpoints")
            return
        # Single host: capture fresh-buffer device copies + offsets now
        # (device copies dispatch asynchronously), then drain/transfer/
        # write on a background thread so checkpoint batches don't stall
        # the step loop.
        self._ckpt_join()  # serialize with the previous in-flight commit
        snaps = {
            (res, wmin * 60): (agg.device_snapshot(), agg.to_host)
            for (res, wmin), agg in self.aggs.items()
        }
        offset = self._offsets_dispatched
        epoch, max_ts = self.epoch, self.max_event_ts
        # reducer state is captured SYNCHRONOUSLY on the step thread —
        # it must cover exactly the dispatched batches the offsets
        # cover, and the next step's fold would mutate it under the
        # background thread
        extras = self._infer_extras()

        def commit():
            try:
                # writes queued before the snapshot must be durable before
                # offsets move; later writes draining too is harmless
                # (idempotent upserts)
                self.writer.drain()
                states = {k: to_host(s) for k, (s, to_host) in snaps.items()}
                self.ckpt.commit(offset, max_ts, epoch, states,
                                 shards=self._local_shards,
                                 snap_impl=self._snap_impl_name,
                                 mesh_mode=self._mesh_mode,
                                 extras=extras)
                self.metrics.count("checkpoints")
            except BaseException as e:  # surfaced on the step thread
                self._ckpt_err = e

        self._ckpt_thread = threading.Thread(target=commit,
                                             name="ckpt-commit", daemon=True)
        self._ckpt_thread.start()

    def _infer_extras(self) -> dict | None:
        """Checkpoint extras payload: the inference engine's entity
        table, committed atomically WITH the window state + offsets
        (torn, a resume would re-fold replayed batches into
        already-folded filter state).  The quality ledger's pending
        scorecards ride the same commit — torn, a resume would double-
        count or lose cards and break the conservation identity."""
        if self.infer is None:
            return None
        out = {"infer": self.infer.snapshot()}
        if self.quality is not None:
            out["quality"] = self.quality.snapshot_extra()
        return out

    def _ckpt_join(self, raise_errors: bool = True) -> None:
        t = self._ckpt_thread
        if t is not None:
            t.join()
            self._ckpt_thread = None
        if self._ckpt_err is not None:
            err, self._ckpt_err = self._ckpt_err, None
            if raise_errors:
                raise RuntimeError("async checkpoint commit failed") from err
            log.error("async checkpoint commit failed", exc_info=err)

    # ------------------------------------------------------------------
    def _build_batch(self, polled) -> EventColumns | None:
        if isinstance(polled, EventColumns):
            cols = polled
        else:
            if not polled:
                return None
            cols = parse_events(polled, self._intern_p, self._intern_v)
        if cols.n_dropped:
            self.metrics.drop("invalid", cols.n_dropped)
        if self.audit is not None and (len(cols) or cols.n_dropped):
            # conservation ledger: rows polled = rows kept + parse
            # drops (the ledger's feed-side term; carry drains re-use
            # rows already counted at their original poll)
            self.audit.add("polled", len(cols) + cols.n_dropped)
        return cols if len(cols) else None

    def _pad(self, arr: np.ndarray, fill=0):
        n = self._feed_batch
        if len(arr) == n:
            return arr
        out = np.full((n,), fill, dtype=arr.dtype)
        out[: len(arr)] = arr
        return out

    def _fold_positions(self, cols: EventColumns):
        """Latest position per vehicle, monotonic in ts (the *intent* of the
        reference's conditional upsert, heatmap_stream.py:198-228, without
        its duplicate-key race).  The per-vehicle newest-event selection
        and the newer-than-stored comparison are fully vectorized; returns
        columnar PositionRows for the changed vehicles (None when none) —
        the sink encodes them to pipeline-update ops, in C++ on the wire
        backend (native/positions_ops.cpp)."""
        if not len(cols):
            return None
        vid = cols.vehicle_id
        n = len(vid)
        # newest row per vehicle WITHOUT a sort: scatter-max of the
        # packed key ts * 2^shift + row_index (row index tie-breaks
        # equal timestamps toward the later row, matching the previous
        # stable lexsort's last-pick; arithmetic, not bitwise, so
        # pre-1970 negative ts still orders correctly; shift sized to
        # the batch, and int32 ts * 2^32 + idx still fits int64).
        # O(N) vs O(N log N) — this fold runs on the host for every
        # batch on every backend, so at the 5M ev/s target its
        # per-event cost is a hard ceiling.
        shift = max(20, int(n - 1).bit_length())
        key = cols.ts_s.astype(np.int64) * (1 << shift) + np.arange(n)
        # grow the persistent per-vehicle last-ts table to cover new ids
        need = int(vid.max()) + 1
        if need > len(self._pos_ts):
            grown = np.full(max(need, 2 * len(self._pos_ts)), -(2**62),
                            np.int64)
            grown[:len(self._pos_ts)] = self._pos_ts
            self._pos_ts = grown
        # persistent scatter buffer, reset only at this batch's ids so
        # the fold stays O(batch) even with millions of known vehicles
        if (getattr(self, "_pos_win", None) is None
                or len(self._pos_win) < len(self._pos_ts)):
            self._pos_win = np.empty(len(self._pos_ts), np.int64)
        self._pos_win[vid] = -(2**62)     # below any key, incl. negatives
        np.maximum.at(self._pos_win, vid, key)
        # row i wins iff it holds its vehicle's max key (one winner per
        # vehicle present in the batch)
        rows = np.nonzero(self._pos_win[vid] == key)[0]
        v_ids = vid[rows]
        ts_new = cols.ts_s[rows].astype(np.int64)
        newer = ts_new > self._pos_ts[v_ids]
        rows = rows[newer]
        if rows.size == 0:
            return None
        self._pos_ts[vid[rows]] = cols.ts_s[rows]
        providers, vehicles = cols.providers, cols.vehicles
        pid = cols.provider_id
        return PositionRows(
            lat=cols.lat_deg[rows],
            lon=cols.lng_deg[rows],
            ts_ms=cols.ts_s[rows].astype(np.int64) * 1000,
            providers=[providers[int(p)] if int(p) < len(providers) else "?"
                       for p in pid[rows]],
            vehicles=[vehicles[int(v)] if int(v) < len(vehicles) else str(v)
                      for v in vid[rows]],
        )

    def _account_pair_packed(self, res: int, wmin: int, body, stats,
                             epoch: int | None = None,
                             shard: int | None = None) -> int:
        """Sink one pair's packed emit body rows + book its stats; returns
        its batch_max_ts.  The writer thread turns the rows into store
        writes (columnar->BSON in C++ when the store supports it);
        ``stats`` is any object with StepStats-named int attributes;
        ``epoch`` is the batch's dispatching epoch (accounting runs one
        batch behind); ``shard`` is the mesh shard on the partitioned
        path (stats are then per-device, accounted at that device's own
        flush cadence)."""
        n_docs = int(np.count_nonzero(
            (body[:, 8] != 0) & (body[:, 3].view(np.int32) > 0)))
        if n_docs:
            vel = None
            if self.infer is not None:
                view = self._vel_views.get(epoch)
                vel = (view if view is not None
                       else self.infer.velocity_view()).field(res)
            if vel:
                # kalman reducer on: decode the packed rows host-side and
                # ride the smoothed per-cell velocity field into the docs
                # as optional columns.  The audit digest table applies the
                # SAME enriched docs, so digest coverage of the new
                # columns is automatic (doc_hash spans every key).  With
                # count-only reducers self.infer is None and this branch
                # is dead — the packed fast path below stays byte-for-
                # byte what it was.
                from heatmap_tpu.sink.base import packed_tile_docs

                docs = packed_tile_docs(body, self._pack_meta[(res, wmin)])
                for d in docs:
                    v = vel.get(int(d["cellId"], 16))
                    if v is not None:
                        # round(·, 2) keeps the serve wire's fixed-point
                        # x100 encoding exact (serve/wire.py ENC_FIXED)
                        d["vxKmh"] = round(v[0], 2)
                        d["vyKmh"] = round(v[1], 2)
                self.writer.submit_tiles(docs)
                if self.audit is not None:
                    self.audit.add("docs_emitted", n_docs)
                    self.audit.shard_table(shard).apply_docs(docs)
            else:
                self.writer.submit_tiles_packed(
                    body, self._pack_meta[(res, wmin)])
                if self.audit is not None:
                    # integrity observatory: the emit-side ledger stamp
                    # and THIS shard's digest table (obs.audit) — decoded
                    # with the same oracle the store/view use, so the
                    # table is exactly the docs downstream will hold for
                    # this shard's (disjoint) cell space.  Audit-on cost
                    # only; observe-only either way.
                    from heatmap_tpu.sink.base import packed_tile_docs

                    self.audit.add("docs_emitted", n_docs)
                    self.audit.shard_table(shard).apply_docs(
                        packed_tile_docs(body, self._pack_meta[(res, wmin)]))
        self.metrics.count("tiles_emitted", n_docs)
        return self._account_stats(res, wmin, stats, epoch, shard=shard)

    def _prune_vel_views(self) -> None:
        """Drop the velocity views of epochs no ring still parks."""
        if not self._vel_views:
            return
        rings = (self._mesh_rings if self._mesh_rings is not None
                 else [self._ring])
        parked = [r.oldest_tag for r in rings if len(r)]
        low = min(parked) if parked else self.epoch + 1
        for e in [e for e in self._vel_views if e < low]:
            del self._vel_views[e]

    def flush_pending(self) -> None:
        """Pull + account every batch parked in the emit ring, in order.

        Runs on the step thread.  Called by the step loop when the ring
        reaches its flush interval (or under watermark/growth pressure),
        before every checkpoint capture (so commits cover every accounted
        batch), on idle polls, and from close().  One call = ONE pull
        covering up to emit_flush_k batches — the round-trip amortization
        the fused pipelines were missing (VERDICT r5 §3).  On the
        partitioned mesh this is the global barrier form: EVERY shard's
        ring drains (checkpoints, close, idle polls, window/growth
        pressure); steady-state flushes instead run per shard
        (_flush_mesh_shard) on each ring's own cadence."""
        t_flush = time.monotonic()
        if self._mesh_rings is not None:
            for d in range(len(self._mesh_rings)):
                self._flush_mesh_shard(d)
            return
        if not len(self._ring):
            return
        n_batches = len(self._ring)
        batch_max = I32_MIN
        if self._multi is not None:
            from heatmap_tpu.engine.multi import stats_from_packed

            # emit_pull=prefix (the off-CPU auto choice): head rows +
            # one shared live-prefix bucket instead of the full (K*P,
            # E+1, L) stack — KB instead of MB per flush on remote-
            # attached chips (engine.step.pull_packed_stack)
            flushed = self._ring.flush_stacked(self._prefix_pull)
            residency = self._ring.last_flush_residency
            for i, (bufs, epoch) in enumerate(flushed):
                bm = I32_MIN
                for idx, (res, win_s) in enumerate(self._multi.pairs):
                    stats = stats_from_packed(bufs[idx])
                    bm = max(
                        bm,
                        self._account_pair_packed(res, win_s // 60,
                                                  bufs[idx][1:], stats,
                                                  epoch),
                    )
                batch_max = self._book_flushed_batch(bm, batch_max)
                self._note_flushed(
                    epoch, residency[i] if i < len(residency) else None)
        else:
            from heatmap_tpu.parallel import multihost
            from heatmap_tpu.parallel.sharded import packed_pair_bodies

            # sharded path: per-entry addressable pulls (stacking global
            # sharded arrays eagerly would bounce through collectives);
            # accumulation still lets the device run ahead K batches
            entries = self._ring.take()
            residency = self._ring.last_flush_residency
            for i, (packed, epoch) in enumerate(entries):
                rows = multihost.addressable_rows(packed)
                bodies = packed_pair_bodies(
                    rows, self._sharded.params.emit_capacity,
                    len(self._sharded.pairs))
                bm = I32_MIN
                for (res, win_s), (body, stats) in zip(self._sharded.pairs,
                                                       bodies):
                    bm = max(
                        bm,
                        self._account_pair_packed(res, win_s // 60, body,
                                                  stats, epoch),
                    )
                batch_max = self._book_flushed_batch(bm, batch_max)
                self._note_flushed(
                    epoch, residency[i] if i < len(residency) else None)
        self._prune_vel_views()
        # pull accounting: the fused path crosses the link once per
        # flush (the stacked transfer); the sharded path pays one
        # addressable pull PER parked entry — count what was paid
        self.metrics.count("emit_pulls",
                           1 if self._multi is not None else n_batches)
        self.metrics.count("emit_pull_batches", n_batches)
        if batch_max > I32_MIN:
            # device truth catches any undercount of the host-side
            # advance (_host_batch_max_ts is built to never OVERcount)
            self.max_event_ts = max(self.max_event_ts, batch_max)
        if self.max_event_ts > I32_MIN:
            self._g_watermark.set(time.time() - self.max_event_ts)
        self._last_flush_cutoff = (
            self.max_event_ts - self.cfg.watermark_minutes * 60
            if self.max_event_ts > I32_MIN else I32_MIN)
        self._last_pull_s = time.monotonic() - t_flush

    def _book_flushed_batch(self, bm: int, batch_max: int) -> int:
        """Per-flushed-batch bookkeeping: freshness at the emit boundary
        (wall clock now minus the batch's newest event time — the
        reference's implied budget is ~10s, SURVEY.md §3.5; replays of
        old data show the replay lag, which is itself the honest
        answer)."""
        if bm > I32_MIN:
            self.metrics.freshness.add(time.time() - bm)
            return max(batch_max, bm)
        return batch_max

    def _note_flushed(self, epoch: int, residency) -> None:
        """Per-flushed-batch freshness accounting: emit-ring residency
        histograms (from the ring's own enter stamps) and the lineage
        flush stamp, then a sink-commit mark so the record closes on
        the writer thread once every write of this batch is applied."""
        if residency is not None:
            self.metrics.ring_residency.observe(residency[0])
            self.metrics.ring_residency_batches.observe(residency[1])
        rec = self._lineage_open.pop(epoch, None)
        if rec is None:
            return
        self.lineage.flushed(
            rec, ring_batches=residency[1] if residency else None)
        self.writer.submit_mark(functools.partial(self._lineage_commit,
                                                  rec))

    def _lineage_commit(self, rec: dict) -> None:
        """Sink-commit ack (runs ON THE WRITER THREAD, after every write
        of the batch has been applied): close the lineage record and
        observe the end-to-end event ages."""
        rec = self.lineage.committed(rec)
        for bound, age in rec["age_s"].items():
            self.metrics.event_age.labels(bound=bound).observe(age)
        # view_apply stage (obs.lineage): the writer's view hook already
        # applied this batch to the materialized view before the ack
        # barrier ran, so the batch is view-visible NOW — stamp the
        # stage (≈0 in-process; a replicated serve worker stamps its
        # own, meaningful, contribution in the scale-out shape) with
        # the seq the writer recorded at apply time
        view = self.writer.view
        if view is not None and not view.poisoned:
            self.lineage.view_applied(rec,
                                      view_seq=self.writer.last_view_seq)
        self._publish_child_freshness()

    def _publish_child_freshness(self) -> None:
        """Cross-process freshness summary (obs.xproc): when a
        supervisor channel is attached, publish this host's event-age /
        ring-residency summary next to it (rate-limited 1/s; runs on
        the writer thread, so the step loop pays nothing)."""
        from heatmap_tpu.obs import ENV_CHANNEL
        from heatmap_tpu.obs.xproc import publish_child_freshness

        path = os.environ.get(ENV_CHANNEL)
        if not path:
            return
        now = time.monotonic()
        if now - self._fresh_pub_last < 1.0:
            return
        self._fresh_pub_last = now
        publish_child_freshness(path, self._fresh_tag,
                                self.metrics.freshness_summary())

    def _publish_member_snapshot(self, force: bool = False,
                                 left: bool = False) -> None:
        """Fleet observatory publish (obs.xproc/obs.fleet): this
        process's FULL registry exposition, freshness summary, /healthz
        verdict, and compact lineage tail, written atomically next to
        the supervisor channel so the fleet aggregator can federate
        them.  Rate-limited to HEATMAP_FLEET_PUBLISH_S (default 2 s;
        0 disables); runs on the step loop, guarded — telemetry never
        takes the pipeline down."""
        from heatmap_tpu.obs import ENV_CHANNEL
        from heatmap_tpu.obs.xproc import (fleet_publish_s,
                                           publish_member_snapshot)

        path = os.environ.get(ENV_CHANNEL)
        if not path:
            return
        interval = fleet_publish_s()
        if interval <= 0:
            return
        now = time.monotonic()
        if not force and now - self._member_pub_last < interval:
            return
        self._member_pub_last = now
        try:
            from heatmap_tpu.obs.fleet import compact_lineage
            from heatmap_tpu.serve.api import healthz_payload

            extra = dict(self.writer.counters)
            extra.pop("sink_retries", None)  # first-class registry
            extra.update(getattr(self.source, "counters", None) or {})
            publish_member_snapshot(
                path, self._fresh_tag, role="runtime",
                metrics_text=self.metrics.expose_text(
                    extra_counters=extra),
                freshness=self.metrics.freshness_summary(),
                healthz=healthz_payload(self)[0],
                lineage=compact_lineage(self.lineage.tail(16)),
                audit=(self.audit.member_block()
                       if self.audit is not None else None),
                hist=(self.hist_compactor.member_block()
                      if self.hist_compactor is not None else None),
                infer=(self.infer.member_block()
                       if self.infer is not None else None),
                quality=(self.quality.member_block()
                         if self.quality is not None else None),
                left=left)
        except Exception:  # noqa: BLE001 - never kill the step loop
            log.warning("fleet member snapshot publish failed",
                        exc_info=True)

    def _host_batch_max_ts(self, ts_s: np.ndarray) -> int:
        """Watermark advance for one batch, computed HOST-side with
        exactly the device fold's per-pair late/future masks
        (engine.step._drop_and_evict, int32 wrap semantics replicated).

        With the emit ring the device-computed batch_max_ts arrives up
        to K batches late; advancing the watermark from the pull would
        lag the cutoff — changing late-drop/eviction timing vs the
        per-batch-pull behavior.  This keeps the cutoff sequence
        batch-granular and flush-independent.  Built to never OVERcount:
        a row is counted only if at least one pair's mask keeps it (late
        rows can never hold a new max — their ts is below the cutoff —
        and clock-skew poison rows are excluded with the same wrapped
        int32 arithmetic the device uses); any undercount is healed by
        the flush, which maxes in the device truth."""
        if ts_s.size == 0:
            return I32_MIN
        if int(ts_s.max()) <= self.max_event_ts:
            return I32_MIN          # nothing can advance the watermark
        from heatmap_tpu.engine.step import FUTURE_WINDOWS

        cutoff = (self.max_event_ts - self.cfg.watermark_minutes * 60
                  if self.max_event_ts > I32_MIN else I32_MIN)
        cand = ts_s[ts_s > self.max_event_ts].astype(np.int64)

        def wrap32(x):      # int64 -> int32 two's-complement wrap
            return ((x + 2**31) % 2**32) - 2**31

        best = I32_MIN
        for win in self._uniq_windows:
            ws = (cand // win) * win
            keep = wrap32(ws + win) > cutoff            # ~late
            if FUTURE_WINDOWS and cutoff > I32_MIN:
                keep &= wrap32(ws - cutoff) < FUTURE_WINDOWS * win
            if keep.any():
                best = max(best, int(cand[keep].max()))
        return best

    def _effective_max_ts(self) -> int:
        """The event-time high watermark the fold cutoff derives from.

        Unsharded (and channel-less) runs: this process's own
        ``max_event_ts``, unchanged.  Sharded runs with a supervisor
        channel: own max BOUNDED by the fleet's low watermark — the min
        over every fresh peer shard's published watermark
        (obs.xproc.shard_watermarks_from) — so no shard closes (evicts
        and finalizes) a window a straggling peer is still folding
        events into.  Peers are read at most 1/s (cached between); a
        peer whose file goes stale past HEATMAP_FLEET_MAX_AGE_S drops
        out of the bound, so a dead shard cannot freeze eviction
        fleet-wide forever."""
        own = self.max_event_ts
        if self.shardmap is None or own <= I32_MIN:
            return own
        from heatmap_tpu.obs import ENV_CHANNEL

        path = os.environ.get(ENV_CHANNEL)
        if not path:
            return own
        now = time.monotonic()
        if now - self._shard_wm_read_last >= 1.0:
            self._shard_wm_read_last = now
            from heatmap_tpu.obs.xproc import shard_watermarks_from

            wms = shard_watermarks_from(path)
            wms.pop(self._fresh_tag, None)  # own max is live, not a file
            self._shard_wm_floor = min(wms.values()) if wms else None
        floor = self._shard_wm_floor
        eff = own if floor is None else min(own, int(floor))
        # monotone: alignment only ever HOLDS a cutoff back, never rolls
        # it back.  A peer that crashed and resumed from a checkpoint up
        # to checkpoint_every batches behind republishes an OLDER
        # watermark; without the clamp this shard's cutoff would regress,
        # re-admitting rows into windows it already evicted and
        # finalized — and their fresh partial counts would upsert over
        # the complete tile docs.
        eff = max(eff, self._shard_wm_eff_last)
        self._shard_wm_eff_last = eff
        if self._g_shard_wm_lag is not None:
            self._g_shard_wm_lag.set(max(0, own - eff))
        return eff

    def _publish_shard_watermark(self) -> None:
        """Publish this shard's own high watermark next to the channel
        (rate-limited 1/s) so peers can hold their cutoffs at the fleet
        low bound; no channel / unsharded = no-op."""
        if self.shardmap is None or self.max_event_ts <= I32_MIN:
            return
        from heatmap_tpu.obs import ENV_CHANNEL

        path = os.environ.get(ENV_CHANNEL)
        if not path:
            return
        now = time.monotonic()
        if now - self._shard_wm_pub_last < 1.0:
            return
        self._shard_wm_pub_last = now
        from heatmap_tpu.obs.xproc import publish_shard_watermark

        publish_shard_watermark(path, self._fresh_tag, self.max_event_ts)

    def _wm_flush_due(self) -> bool:
        """Watermark pressure: the cutoff crossed a boundary of the
        smallest configured window since the last flush — closed windows
        may evict this step, and their final emits should reach the sink
        now instead of up to K batches later."""
        if not self._ring_pending():
            return False
        cutoff = (self.max_event_ts - self.cfg.watermark_minutes * 60
                  if self.max_event_ts > I32_MIN else I32_MIN)
        if cutoff == I32_MIN:
            return False
        win = self._uniq_windows[0]
        return cutoff // win > self._last_flush_cutoff // win

    def _account_stats(self, res: int, wmin: int, stats,
                       epoch: int | None = None,
                       shard: int | None = None) -> int:
        ovf = int(stats.state_overflow)
        if ovf > 0:
            # Data loss is never silent: every overflowing batch bumps the
            # /metrics counters; the ERROR log is rate-limited to once a
            # minute so a sustained overflow can't drown the log.
            self.metrics.count("state_overflow_groups", ovf)
            self.metrics.counters["state_overflow_last_epoch"] = (
                self.epoch if epoch is None else epoch)
            now = time.monotonic()
            if now - self._overflow_logged_at >= 60.0:
                self._overflow_logged_at = now
                log.error(
                    "STATE OVERFLOW: %d distinct (cell,window) groups "
                    "dropped this batch (%d total); raise "
                    "STATE_CAPACITY_LOG2 (currently 2^%d per shard)",
                    ovf,
                    self.metrics.counters["state_overflow_groups"],
                    self.cfg.state_capacity_log2,
                )
            if self.cfg.on_overflow == "fail":
                # the exit checkpoint must NOT commit: offsets/state stay at
                # the last good checkpoint so the lost batch replays after
                # the operator raises the capacity
                self._fatal = True
                raise StateOverflowError(
                    f"{ovf} aggregate groups dropped at state capacity "
                    f"2^{self.cfg.state_capacity_log2} per shard; raise "
                    f"STATE_CAPACITY_LOG2, or set HEATMAP_ON_OVERFLOW=error "
                    f"to keep running with the loss surfaced at /metrics")
        dropped = int(getattr(stats, "bucket_dropped", 0))
        if dropped:
            # ledger forwarding only for the primary pair: the event
            # conservation identity counts each event once, and
            # secondary pairs' exchange drops are a per-pair detail
            self.metrics.drop("exchange", dropped,
                              audit=(res, wmin) == self._primary)
            log.error(
                "EXCHANGE OVERFLOW: %d events dropped by all_to_all lane "
                "skew for (res=%d, window=%dm); raise bucket_factor",
                dropped, res, wmin,
            )
        if (res, wmin) == self._primary:
            self.metrics.count("events_valid", int(stats.n_valid))
            # watermark-late (incl. the future-window poison drop the
            # device folds into the same mask) — a tagged drop, so the
            # conservation identity closes: polled == folded + dropped
            self.metrics.drop("late", int(stats.n_late))
            if self.audit is not None:
                self.audit.add("folded", int(stats.n_valid))
        else:
            self.metrics.count(f"events_late_r{res}m{wmin}",
                               int(stats.n_late))
        n_active = int(stats.n_active)
        if shard is None:
            self._n_active_peak = max(self._n_active_peak, n_active)
        else:
            # partitioned mesh: n_active is ONE device's live groups.
            # The per-shard peak drives the (exact, per-slab) growth
            # inequality; the global gauge tracks the summed last-known
            # occupancy per pair so the overflow early-warning still
            # reads city-wide.
            self._mesh_shard_active[(res, wmin, shard)] = n_active
            self._shard_active_peak = max(self._shard_active_peak,
                                          n_active)
            pair_total = sum(
                v for (r, w, _s), v in self._mesh_shard_active.items()
                if (r, w) == (res, wmin))
            self._n_active_peak = max(self._n_active_peak, pair_total)
        self._g_active.set(self._n_active_peak)
        # per-batch group minting (for grow_margin=observed): the raw
        # n_active delta UNDERcounts minting when eviction freed rows the
        # same batch, so add evictions back in.  The FIRST observation
        # for a pair only seeds the baseline — after a checkpoint
        # restore n_active starts at the whole restored population, and
        # counting that as one batch's minting would permanently
        # oversize the observed margin to ~4x the live group count
        key = (res, wmin) if shard is None else (res, wmin, shard)
        prev = self._prev_active.get(key)
        self._prev_active[key] = n_active
        if prev is not None:
            minted = n_active - prev + int(stats.n_evicted)
            self._mint_peak = max(self._mint_peak, minted)
        return int(stats.batch_max_ts)

    def _maybe_grow(self) -> None:
        """Grow the state slabs BEFORE they can overflow.

        A batch adds at most one new group per event per pair, so keeping
        free slots above 2x the global batch (the 2 covers the one-batch
        stats lag) makes single-slab overflow structurally impossible
        below the growth ceiling.  Sharded slabs overflow per shard; the
        extra 2x on the occupancy term tolerates up to 2x accumulated
        key-ownership skew (far above what mix32 produces at real group
        counts), with the overflow accounting as the loud backstop.
        Runs on the step thread between the flush and the next dispatch —
        the emit ring is drained first (the step loop pressure-flushes
        whenever growth may trigger), so no packed emit ever straddles an
        emit-capacity resize and the resize is a plain state swap plus a
        retrace on the next step.  In multi-host mode every host derives
        the same decision from the replicated stats.  On the partitioned
        mesh per-shard occupancy is EXACT (each device holds only its
        own cells), so the inequality runs against the hottest shard
        with the full margin — one batch CAN mint its whole row count
        into a single device under total geographic skew."""
        agg = self._agg()
        margin = self._grow_margin()
        cap = agg.capacity_per_shard
        if self._parted is not None:
            peak, shards, skew = self._shard_active_peak, 1, 1
        else:
            shards = agg.n_shards
            skew = 2 if shards > 1 else 1
            peak = self._n_active_peak
        if peak * skew + margin <= cap * shards:
            return
        new_cap = cap
        while (peak * skew + margin > new_cap * shards
               and new_cap < self._cap_max):
            new_cap *= 2
        if new_cap == cap:
            return  # at the ceiling; the overflow accounting stands guard
        t0 = time.monotonic()
        agg.grow(new_cap)
        self.metrics.count("state_grown")
        self.metrics.counters["state_capacity_per_shard"] = new_cap
        self._g_capacity.set(new_cap)
        log.warning(
            "state slabs grown 2^%d -> 2^%d rows/shard (%d live groups; "
            "%.2fs; next step retraces)", cap.bit_length() - 1,
            new_cap.bit_length() - 1, self._n_active_peak,
            time.monotonic() - t0)

    def _grow_margin(self) -> int:
        """Free-slot margin the grower keeps, scaled by the emit-ring
        depth: the stats that feed the occupancy peak lag (1 + pending)
        batches behind the dispatch, so each parked batch adds one
        batch's worth of worst-case minting (or half the observed
        margin's headroom) on top of the base rule.  Per-device mesh
        rings lag independently; the DEEPEST one bounds the stats lag.

        Base rules (pending == 0, today's formulas): worst = 2x batch (a
        batch can mint one group per event; the 2 covers the one-batch
        stats lag — overflow structurally impossible below the growth
        ceiling); observed = 4x the largest per-batch minting seen (2x
        lag + 2x headroom), floored at batch/8.  An adversarial key
        stream can still outrun `observed` — the overflow accounting and
        HEATMAP_ON_OVERFLOW=fail's checkpoint replay are the loud,
        lossless backstop (config.grow_margin)."""
        pend = self._ring_pending()
        if self.cfg.grow_margin == "observed":
            base = max(4 * self._mint_peak, self.cfg.batch_size // 8)
        else:
            base = 2 * self.cfg.batch_size
        return base * (pend + 2) // 2

    def _grow_would_trigger(self) -> bool:
        """The growth inequality on the CURRENT (possibly ring-stale)
        stats — the step loop's growth-pressure flush trigger: when true,
        flush first (fresh stats), then let _maybe_grow decide."""
        agg = self._agg()
        if self._parted is not None:
            return (self._shard_active_peak + self._grow_margin()
                    > agg.capacity_per_shard)
        shards = agg.n_shards
        skew = 2 if shards > 1 else 1
        return (self._n_active_peak * skew + self._grow_margin()
                > agg.capacity_per_shard * shards)

    # ------------------------------------------------------- governor
    def _warm_ladder(self, ladder) -> None:
        """Precompile the fused step at every governor pad bucket.

        One all-invalid dispatch per bucket (through the instrumented
        entry point, so the jit cache the CompileTracker probes is the
        one that warms): every row masked invalid makes the fold an
        identity on the EMPTY state — zero sums re-normalize to zero
        bits, no key slots mint, nothing emits, and the results are
        discarded without touching the ring/epoch/offsets.  After this,
        a governed bucket move is a pure cache hit; any later compile
        IS a retrace and freezes the governor (stream/govern.py
        guardrail 1).  On a resumed non-empty state the dispatch is
        value-preserving (the per-batch Kahan re-normalization), which
        is why the governor is constructed after the resume and warmed
        exactly once."""
        t0 = time.monotonic()
        for n in ladder:
            zf = np.zeros(n, np.float32)
            feed = {"lat": zf, "lng": zf, "speed": zf,
                    "ts": np.zeros(n, np.int32),
                    "valid": np.zeros(n, bool)}
            prekeys = None
            if self._host_snap is not None:
                prekeys = self._presnap(feed["lat"], feed["lng"],
                                        feed["valid"], None,
                                        self._multi._uniq_res)
            self._multi.step_packed_all(
                feed["lat"], feed["lng"], feed["speed"], feed["ts"],
                feed["valid"], I32_MIN, prekeys=prekeys)
        log.info("governor bucket ladder warmed: %s rows (%.2fs)",
                 ladder, time.monotonic() - t0)

    def _govern_step(self) -> None:
        """Apply the governor's decisions at a step boundary (the feed
        stage re-reads ``_feed_batch`` per poll; per-entry offset
        snapshots keep checkpoints dispatch-aligned across size
        changes).  On the partitioned mesh every shard's governor runs
        its own control step: per-shard buckets steer the feed
        partitioner's chunking, per-shard flush-K retargets that
        shard's ring (with the forced transition flush), and the
        runtime-global prefetch depth follows the deepest shard's
        decision (the feed stage is shared)."""
        if self._mesh_governors is not None:
            for d, gov in enumerate(self._mesh_governors):
                gov.check_retrace()
                gov.decide()
                k = gov.flush_k
                ring = self._mesh_rings[d]
                if k != ring.capacity:
                    self._flush_mesh_shard(d)
                    ring.capacity = max(1, int(k))
            pf = max(g.prefetch for g in self._mesh_governors)
            if pf != self._prefetch_n:
                self._prefetch_n = pf
            return
        gov = self.governor
        gov.check_retrace()
        gov.decide()
        if gov.batch_rows != self._feed_batch:
            self._feed_batch = gov.batch_rows
        k = gov.flush_k
        if k != self._ring.capacity:
            # forced flush at the transition: pending entries drain
            # under the OLD interval, so sink ordering and replay
            # equivalence are untouched by the retarget (and a shrink
            # can never strand more entries than the new capacity)
            self.flush_pending()
            self._ring.capacity = max(1, int(k))
        if gov.prefetch != self._prefetch_n:
            self._prefetch_n = gov.prefetch

    # ------------------------------------------------------------------
    def step_once(self) -> bool:
        """Run one micro-batch; returns False when the source yielded nothing."""
        self._step_began = time.monotonic()
        try:
            with self.tracer.batch(self.epoch):
                return self._step_once_inner()
        finally:
            self._step_began = None
            # device-memory telemetry rides the loop at 1 Hz: cheap
            # (live-array walk + per-device stats), and the watermark
            # it maintains is what the /healthz memory budget reads
            self.runtimeinfo.memory.sample(min_interval_s=1.0)

    def _next_batch(self) -> "_FeedBatch | None":
        """Produce the next feed batch: carry-drain or source poll,
        overshoot sliced into the carry, lanes padded to the feed shape,
        host pre-snap, and an async device_put of the feed lanes so the
        H2D transfer overlaps the in-flight fold when called from the
        prefetch stage.  Returns None when the source yielded nothing.

        Sub-span seconds land in the entry (poll with the source's
        fetch/decode split, build with its pad portion, snap, transfer)
        and are recorded when the batch is DISPATCHED, so the span
        percentiles describe the batch they fed regardless of which
        step paid the work."""
        spans: dict[str, float] = {}
        t0 = time.monotonic()
        if self._carry_cols is not None:
            # a batch-granular source (columnar values) overshot the feed
            # shape: drain the remainder before polling again.  The
            # lineage poll stamp is the ORIGINAL poll's — the tail rows
            # have been waiting since then, and that wait must show up
            # as queue time in the decomposition, not vanish into
            # poll_wait.
            cols, self._carry_cols = self._carry_cols, None
            shard_cells, self._carry_shard_cells = \
                self._carry_shard_cells, None
            t_polled = self._carry_polled_at
            wm_ts = None  # booked by the head entry of the same poll
        else:
            polled = self.source.poll(
                self._feed_batch * self._shard_oversample)
            # fetch-vs-decode split of the poll (Source.take_spans) —
            # the sub-span telemetry that makes the next feed-wall
            # regression diagnosable from /metrics alone
            for k, v in self.source.take_spans().items():
                spans[f"poll_{k}"] = spans.get(f"poll_{k}", 0.0) + v
            cols = self._build_batch(polled)
            t_polled = self.lineage.clock()
            wm_ts = None
            shard_cells = None
            if self.shardmap is not None and cols is not None:
                # ownership filter: out-of-shard rows drop HERE, before
                # pad/device_put, so the fold/sink only ever see this
                # shard's cell space.  The watermark still advances
                # from the PRE-filter rows (wm_ts) — the full stream's
                # event time — keeping the cutoff sequence identical to
                # the unsharded fold's.  A batch whose rows are ALL
                # foreign still dispatches (empty): offsets must
                # advance, and the dispatch count must match the
                # unsharded run's (the slab's per-batch Kahan rewrite
                # makes state bits a function of it).
                t_f = time.monotonic()
                wm_ts = cols.ts_s
                cols, n_foreign, shard_cells = \
                    self.shardmap.filter_columns(cols)
                if n_foreign:
                    # closed drop-reason accounting: oversample-mode
                    # polls EXPECT ~(N-1)/N foreign rows per poll —
                    # labeled apart from plain out_of_shard so
                    # partition-skew drops don't read as a misrouted
                    # topic (stream.metrics.DROP_REASONS)
                    self.metrics.drop(
                        "oversample" if self._shard_oversample > 1
                        else "out_of_shard", n_foreign)
                spans["shard_filter"] = time.monotonic() - t_f
        if cols is not None and len(cols) > self._feed_batch:
            from heatmap_tpu.stream.events import slice_columns

            self._carry_cols = slice_columns(cols, self._feed_batch,
                                             len(cols))
            if shard_cells is not None:
                self._carry_shard_cells = shard_cells[self._feed_batch:]
                shard_cells = shard_cells[:self._feed_batch]
            self._carry_polled_at = t_polled
            cols = slice_columns(cols, 0, self._feed_batch)
        # span_poll keeps its historical meaning — source poll PLUS any
        # host columnarize/parse (_build_batch): the r5 feed-wall was
        # diagnosed from exactly this span, so dict-fed parse time must
        # keep landing here (carry drains bill ~0, as before)
        spans["poll"] = time.monotonic() - t0
        if cols is None:
            return None
        # offsets as of THIS poll, applied only when the batch is
        # dispatched — the prefetch stage may poll further ahead
        offset = self.source.offset()
        carried = self._carry_cols is not None
        n = len(cols)
        # freshness lineage opens HERE, at poll time (wall clock +
        # event-time extrema of the rows this batch will dispatch), so
        # the prefetch-queue stage is measured from the poll that paid
        # the work, not from the step that consumed it.  Clock-skew
        # poison rows (far-future timestamps, e.g. an ms-for-s unit
        # error) are excluded from the extrema the same way the device
        # fold drops them: one such row would otherwise latch the
        # newest-committed watermark into the future forever, pinning
        # heatmap_serve_freshness_seconds negative and hiding real
        # staleness from the event-age SLO.
        ts_col = cols.ts_s
        sane = ts_col.astype(np.int64) <= int(t_polled) + 3600
        lin = None
        if sane.any():
            tv = ts_col if sane.all() else ts_col[sane]
            lin = self.lineage.open(
                n_events=n, ev_min_ts=int(tv.min()),
                ev_max_ts=int(tv.max()), ev_mean_ts=float(tv.mean()),
                offset=offset, t_poll=t_polled)
        if self._parted is not None:
            # partitioned mesh: the single padded feed is replaced by
            # per-device row blocks (H3-parent partition, compacted to
            # each block's prefix, device_put to the owning chip)
            mesh_blocks = self._mesh_feed(cols, shard_cells, spans)
            return _FeedBatch(cols=cols, n=n, feed=None, prekeys=None,
                              offset=offset, carried=carried,
                              spans=spans, lineage=lin, wm_ts=wm_ts,
                              mesh=mesh_blocks)
        t1 = time.monotonic()
        valid = np.zeros(self._feed_batch, bool)
        valid[:n] = True
        feed = {
            "lat": self._pad(cols.lat_rad),
            "lng": self._pad(cols.lng_rad),
            "speed": self._pad(cols.speed_kmh),
            "ts": self._pad(cols.ts_s),
            "valid": valid,
        }
        t2 = time.monotonic()
        spans["pad"] = t2 - t1
        # host pre-snap (HEATMAP_H3_IMPL=native), shared by both paths
        agg = self._multi if self._multi is not None else self._sharded
        prekeys = self._presnap(feed["lat"], feed["lng"], valid, cols,
                                agg._uniq_res, shard_cells=shard_cells)
        t3 = time.monotonic()
        spans["snap"] = t3 - t2
        if self._multi is not None:
            # dispatch the H2D transfers NOW (device_put is async): by
            # the time this batch is folded, its lanes are already
            # device-resident — from the prefetch stage the transfer
            # overlaps the previous batch's fold (double buffering).
            # The sharded path keeps host arrays: its step applies the
            # mesh shardings itself (ShardedAggregator._puts).
            feed = {k: jax.device_put(v) for k, v in feed.items()}
            if prekeys is not None:
                prekeys = {r: (jax.device_put(hi), jax.device_put(lo))
                           for r, (hi, lo) in prekeys.items()}
        spans["transfer"] = time.monotonic() - t3
        spans["build"] = spans["pad"] + spans["transfer"]
        return _FeedBatch(cols=cols, n=n, feed=feed, prekeys=prekeys,
                          offset=offset, carried=carried, spans=spans,
                          lineage=lin, wm_ts=wm_ts)

    def _step_once_inner(self) -> bool:
        t0 = time.monotonic()
        if self.governor is not None or self._mesh_governors is not None:
            # control step + decision apply at the step boundary — the
            # feed poll below reads the (possibly resized) bucket
            self._govern_step()
        if self._prefetched:
            entry = self._prefetched.popleft()
        else:
            entry = self._next_batch()
        if entry is None and not self._multiproc:
            # idle poll: settle the parked batches so stats/sink catch up
            self.flush_pending()
            if self.governor is not None:
                self.governor.note_idle()
            if self._mesh_governors is not None:
                for gov in self._mesh_governors:
                    gov.note_idle()
            return False
        if entry is None:
            # multi-host lockstep: peers may have events and are entering
            # the global collectives this step — participate with an
            # all-invalid batch (also keeps watermark eviction ticking)
            zf = np.zeros(self._feed_batch, np.float32)
            entry = _FeedBatch(
                cols=None, n=0,
                feed={"lat": zf, "lng": zf, "speed": zf,
                      "ts": np.zeros(self._feed_batch, np.int32),
                      "valid": np.zeros(self._feed_batch, bool)},
                prekeys=None, offset=self.source.offset(),
                carried=self._carry_cols is not None, spans={})
        cols, n, feed = entry.cols, entry.n, entry.feed

        # Deferred-pull window: parked batches are pulled when the emit
        # ring hits its flush interval, or earlier under watermark
        # pressure (a window is closing — its final emits should reach
        # the sink now) or growth pressure (occupancy nears the slab
        # with the parked batches' minting unaccounted).  flush_pending
        # is also the barrier (checkpoint, close, idle polls) that keeps
        # commit ordering and end-of-stream semantics exact.
        self._last_pull_s = 0.0  # only THIS window's pull is attributed
        grow_due = self._grow_would_trigger()
        if self._mesh_rings is not None:
            # partitioned mesh: global pressure (closing windows,
            # growth) drains EVERY shard's ring; otherwise each shard
            # flushes on its own live-batch cadence — independence is
            # the point (a hot shard must not pull the idle ones)
            if self._wm_flush_due() or grow_due:
                if grow_due and self._mesh_governors is not None:
                    for gov in self._mesh_governors:
                        gov.note_growth_pressure()
                self.flush_pending()
                self._maybe_grow()
            else:
                for d, ring in enumerate(self._mesh_rings):
                    if ring.full:
                        self._flush_mesh_shard(d)
        elif self._ring.full or self._wm_flush_due() or grow_due:
            if grow_due and self.governor is not None:
                # the EmitRing growth-pressure path can force the
                # governor a step down (guardrail 2): parked batches
                # were holding unaccounted minting against the slab
                self.governor.note_growth_pressure()
            self.flush_pending()
            self._maybe_grow()
        wm_max = self._effective_max_ts()
        cutoff = (
            wm_max - self.cfg.watermark_minutes * 60
            if wm_max > I32_MIN else I32_MIN
        )
        infer_s = 0.0
        if self.infer is not None and cols is not None:
            # reducer fold BEFORE the device dispatch, not after: the
            # Kalman scan shares the XLA CPU queue with the window-fold
            # program, and a scan dispatched right after step_packed
            # serializes behind that entire program (~8x the idle-device
            # scan time, measured) — whereas here the ring flush above
            # has already drained the device, so the scan runs against
            # an idle queue and the window fold then overlaps the NEXT
            # batch's feed exactly as before
            t_inf = time.monotonic()
            self.infer.fold_batch(cols)
            ievents = self.infer.drain_anomalies()
            if ievents and self.matview is not None:
                # anomaly records ride the writer thread like every view
                # mutation (single-writer discipline), then fan out via
                # the view's feed hook + watchers: repl followers and
                # the anomaly continuous-query engine see them at zero
                # extra writer cost.  They carry no doc mutations, so
                # queueing ahead of this batch's (deferred) doc applies
                # is order-safe.
                grid = self.cfg.default_grid()
                view = self.matview
                self.writer.submit_mark(
                    lambda: view.publish_anomalies(grid, ievents))
            infer_s = time.monotonic() - t_inf
        if self.infer is not None:
            # frozen as of this batch's fold: the velocity columns of its
            # tiles do not depend on when its emit ring is pulled (the
            # per-device cadence of a mesh, emit_flush_k, the governor)
            self._vel_views[self.epoch] = self.infer.velocity_view()
        t_ready = time.monotonic()
        prekeys = entry.prekeys
        if cols is None and self._host_snap is not None:
            # idle lockstep batch under the native snap: cached zero keys
            agg_ = (self._multi if self._multi is not None
                    else self._sharded)
            prekeys = self._presnap(feed["lat"], feed["lng"],
                                    feed["valid"], None, agg_._uniq_res)
        lin = entry.lineage
        if lin is not None:
            # lineage: the batch leaves the prefetch queue and enters
            # the fold under THIS epoch
            self.lineage.dispatched(lin, self.epoch)
        if self._parted is not None:
            # partitioned mesh path: every device dispatches ITS block
            # of this batch (collective-free fused program, async — the
            # per-device folds overlap); each packed emit parks in its
            # OWN device's ring.  Empty blocks still dispatch
            # all-invalid so per-batch slab rewrite counts match the
            # single-device fold's (the byte-identity differential).
            n_entries = 0
            for d, chunks in enumerate(entry.mesh):
                for ch in chunks:
                    if ch is None:
                        ch = self._mesh_idle_chunk(d)
                    f = ch["feed"]
                    packed = self._parted.step_shard(
                        d, f["lat"], f["lng"], f["speed"], f["ts"],
                        f["valid"], cutoff, prekeys=ch["prekeys"])
                    self._mesh_rings[d].append(packed, self.epoch,
                                               live=ch["n"] > 0)
                    n_entries += 1
                    if ch["n"]:
                        self._mesh_rows[d] += ch["n"]
                        self._c_mesh_rows.labels(shard=str(d)).inc(
                            ch["n"])
                    if self._mesh_governors is not None:
                        self._mesh_governors[d].note_dispatch(ch["n"])
            self._parted.n_steps += 1
            if lin is not None:
                # the batch's lineage closes when its LAST shard entry
                # flushes (per-shard flushes run independently)
                self._mesh_epoch_pend[self.epoch] = n_entries
        elif self._multi is not None:
            # fused path: one dispatch for every (res, window) pair; the
            # packed emits + stats park in the device-resident ring and
            # cross the link in one pull per flush interval (engine.multi
            # + engine.step.EmitRing)
            packed = self._multi.step_packed_all(
                feed["lat"], feed["lng"], feed["speed"], feed["ts"],
                feed["valid"], cutoff, prekeys=prekeys)
            self._ring.append(packed, self.epoch)
        else:
            # sharded path: ONE dispatch folds every pair (single fused
            # all_to_all); the deferred pull covers this host's emit
            # shards AND the replicated stats for all pairs (packed head
            # rows; parallel.sharded)
            packed = self._sharded.step_packed(
                feed["lat"], feed["lng"], feed["speed"], feed["ts"],
                feed["valid"], cutoff, prekeys=prekeys)
            self._ring.append(packed, self.epoch)
        if self.governor is not None:
            self.governor.note_dispatch(n)
        if self.audit is not None and n:
            # conservation ledger: rows entering the device fold (the
            # fold-side counts arrive at flush time, so the in-between
            # shows as a draining in-flight residual, never a leak)
            self.audit.add("dispatched", n)
        if lin is not None:
            self.lineage.ring_entered(lin)
            self._lineage_open[self.epoch] = lin
        self._carried_last = entry.carried
        if not entry.carried:
            # offsets only advance once EVERY row of the polled records
            # has been dispatched — a checkpoint mid-carry would
            # otherwise cover rows that exist nowhere but in this
            # process's memory.  The snapshot is the entry's own: the
            # prefetch stage may have polled the source further ahead.
            self._offsets_dispatched = entry.offset
        if cols is not None and not self._multiproc:
            # host-side watermark advance (exact device-mask replica):
            # keeps the cutoff batch-granular while the emit pull runs
            # up to K batches behind (_host_batch_max_ts).  Multi-host
            # keeps the flush-time advance: its watermark must derive
            # from the REPLICATED stats, not this host's local rows.
            # Sharded runs advance from the PRE-ownership-filter rows
            # (entry.wm_ts): the watermark tracks the full stream, not
            # just this shard's cells.
            bm = self._host_batch_max_ts(
                entry.wm_ts if entry.wm_ts is not None else cols.ts_s)
            if bm > self.max_event_ts:
                if (self.max_event_ts == I32_MIN
                        and self._last_flush_cutoff == I32_MIN):
                    # first activation: seed the pressure tracker so
                    # _wm_flush_due measures window-boundary CROSSINGS,
                    # not the jump from "no watermark yet"
                    self._last_flush_cutoff = (
                        bm - self.cfg.watermark_minutes * 60)
                self.max_event_ts = bm
                self._g_watermark.set(time.time() - bm)
        self._publish_shard_watermark()
        t_device = time.monotonic()

        if self.positions_enabled and cols is not None:
            prows = self._fold_positions(cols)
            if prows is not None:
                self.writer.submit_positions_packed(prows)
                self.metrics.count("positions_emitted", len(prows.ts_ms))
        self.epoch += 1
        t_sink = time.monotonic()
        # refill the prefetch queue AFTER the dispatch: the next batch's
        # poll/decode/pad and its device_put run while the device folds
        # the batch just dispatched (the double-buffered feed)
        if self._prefetch_n and not self._multiproc and not self._closing:
            while len(self._prefetched) < self._prefetch_n:
                nxt = self._next_batch()
                if nxt is None:
                    break
                self._prefetched.append(nxt)
        t_end = time.monotonic()
        pull_s, self._last_pull_s = self._last_pull_s, 0.0
        espans = entry.spans
        spans = {
            # feed-stage spans describe THIS batch even when the work
            # was paid by an earlier step's prefetch stage
            "poll": espans.get("poll", 0.0),
            "build": espans.get("build", 0.0),
            # sub-splits of poll/build (satellite telemetry): source
            # fetch vs decode, pad vs H2D transfer
            "pad": espans.get("pad", 0.0),
            "transfer": espans.get("transfer", 0.0),
            # the deferred pull of up to K parked batches (waits out
            # their folds) vs this batch's own dispatch — the split that
            # shows whether checkpoint/pull work ever gaps the step loop
            "pull": pull_s,
            # host pre-snap (HEATMAP_H3_IMPL=native) is host work
            # billed separately from the device dispatch it precedes
            "snap": espans.get("snap", 0.0),
            "device": (t_device - t_ready),
            "sink_submit": t_sink - t_device,
            # this step's prefetch refill (the NEXT batch's feed stage,
            # overlapping the fold just dispatched)
            "prefetch": t_end - t_sink,
        }
        for k in ("poll_fetch", "poll_decode", "poll_wait", "partition",
                  "shard_filter"):
            if k in espans:
                spans[k] = espans[k]
        if self.infer is not None:
            # reducer-set fold cost as ITS OWN span (it runs pre-
            # dispatch, between feed and device, so no other span
            # absorbs it) — a composed-fold regression shows up here,
            # not as a mystery elsewhere
            spans["infer"] = infer_s
        self.metrics.observe_batch(t_end - t0, spans)
        # structured trace record (obs.tracebuf -> /trace/recent, JSONL).
        # Late/overflow counts account up to emit_flush_k batches behind
        # (the deferred pull), so the record carries the delta since the
        # last record — a nonzero flag points at the incident window
        # either way.
        c = self.metrics.counters
        cum = (c.get("events_late", 0), c.get("state_overflow_groups", 0),
               c.get("events_bucket_dropped", 0))
        last = getattr(self, "_trace_cum", (0, 0, 0))
        self._trace_cum = cum
        self.tracering.record(
            self.epoch - 1, t_end - t0, spans, n_events=n,
            n_late=cum[0] - last[0], overflow_groups=cum[1] - last[1],
            late_dropped=cum[2] - last[2])
        progressed = cols is not None
        carrying = self._carried_last
        if self._multiproc:
            # fixed-position collective: every host contributes
            # (had-events, still-live, mid-carry); the summed triple is
            # identical everywhere, so all hosts take the same run()-loop
            # branch AND the same checkpoint-skip decision (a one-sided
            # skip would deadlock the checkpoint barrier)
            had, live, carry_any = self._gpair(
                float(progressed),
                0.0 if self.source.exhausted else 1.0,
                float(carrying))
            self._global_live = live
            progressed = had > 0
            carrying = carry_any > 0
        if self.checkpoint_every and self.epoch % self.checkpoint_every == 0:
            # cadence hit; if mid-carry, the flag holds the commit until
            # the FIRST carry-free step (a fixed record:feed size ratio can
            # make "cadence epoch AND carry-free" never align, so waiting
            # for the next cadence hit could starve checkpoints forever)
            self._ckpt_due = True
        if self._ckpt_due and not carrying:
            self._ckpt_due = False
            self._checkpoint()
        return progressed

    def _presnap(self, lat, lng, valid, cols, uniq_res, shard_cells=None):
        """Host C++ cell keys for this batch (HEATMAP_H3_IMPL=native), or
        None for the in-program snap.  Idle lockstep batches (cols is
        None, all rows invalid — the keys get masked to EMPTY anyway)
        feed cached zero keys so multi-host idle polls pay no snap, and
        only the LIVE PREFIX of a padded feed is snapped (an underfilled
        poll must not pay the full-batch cost per resolution).

        ``shard_cells`` are the ownership filter's native-snapped uint64
        cells for the live rows (stream/shardmap.py, snapped at the
        COARSEST fold resolution): splitting them back into hi/lo words
        reuses the exact bits the fold would recompute, so a sharded
        feed pays the coarsest resolution's host snap once, not twice."""
        if self._host_snap is None:
            return None
        if cols is None:
            # cached zero keys PER FEED SHAPE: the governor's bucket
            # ladder (and the warmup over it) dispatches several pad
            # shapes through one runtime
            cached = self._idle_keys.get(len(lat))
            if cached is None:
                z = np.zeros(len(lat), np.uint32)
                cached = self._idle_keys[len(lat)] = {
                    r: (z, z) for r in uniq_res}
            return cached
        nz = np.flatnonzero(valid)
        n_live = int(nz[-1]) + 1 if nz.size else 0
        reuse_res = None
        if (shard_cells is not None and self.shardmap is not None
                and len(shard_cells) == n_live):
            reuse_res = self.shardmap.snap_res
        prekeys = {}
        for r in uniq_res:
            hi = np.zeros(len(lat), np.uint32)
            lo = np.zeros(len(lat), np.uint32)
            if n_live and r == reuse_res:
                hi[:n_live] = (shard_cells >> np.uint64(32)).astype(
                    np.uint32)
                lo[:n_live] = shard_cells.astype(np.uint32)
            elif n_live:
                hi[:n_live], lo[:n_live] = self._host_snap(
                    lat[:n_live], lng[:n_live], r)
            prekeys[r] = (hi, lo)
        return prekeys

    # ------------------------------------------------- partitioned mesh
    def _mesh_feed(self, cols, shard_cells, spans) -> list:
        """Partition one polled batch into per-device row blocks
        (stream/shardmap.MeshPartition): each device's owned rows are
        compacted to its block prefix IN STREAM ORDER (the per-group
        f32 accumulation order byte-identity rests on), padded to the
        device's live pad bucket, and device_put to the owning chip —
        the H2D transfers overlap the in-flight folds when called from
        the prefetch stage.  A device owning none of the batch's cells
        gets ``None`` (the dispatcher sends its cached all-invalid
        chunk so per-batch slab rewrite counts match the single-device
        fold).  Under a per-shard governor a device whose rows exceed
        its bucket dispatches multiple chunks — regrouping, never
        dropping (the PR 10 exact-regrouping discipline)."""
        t0 = time.monotonic()
        reuse = None
        if (shard_cells is not None and self.meshmap.native
                and len(shard_cells) == len(cols)):
            # composed process+mesh sharding: the ownership filter
            # already snapped these rows at the same (coarsest-res)
            # partition key — no second host snap
            reuse = shard_cells
        ids, cells = self.meshmap.partition(cols.lat_rad, cols.lng_rad,
                                            cells=reuse)
        spans["partition"] = time.monotonic() - t0
        t1 = time.monotonic()
        govs = self._mesh_governors
        blocks = []
        for d in range(self._parted.n_shards):
            idx = np.flatnonzero(ids == d)
            bucket = (govs[d].batch_rows if govs is not None
                      else self._feed_batch)
            if idx.size == 0:
                blocks.append([None])
                continue
            chunks = []
            for lo in range(0, int(idx.size), bucket):
                chunks.append(self._mesh_chunk(
                    cols, idx[lo:lo + bucket], cells, bucket, d))
            blocks.append(chunks)
        spans["pad"] = time.monotonic() - t1
        spans["build"] = spans["pad"]
        return blocks

    def _mesh_chunk(self, cols, sel, cells, bucket: int, d: int) -> dict:
        """One device's padded feed chunk: lanes gathered by ``sel``
        (owned-row indices, stream order), padded to ``bucket``, host
        pre-snap keys attached (reusing the partition's own cells for
        the coarsest resolution — the PR 7 handoff), everything
        committed to device ``d``."""
        n = int(sel.size)
        lat = np.zeros(bucket, np.float32)
        lat[:n] = cols.lat_rad[sel]
        lng = np.zeros(bucket, np.float32)
        lng[:n] = cols.lng_rad[sel]
        speed = np.zeros(bucket, np.float32)
        speed[:n] = cols.speed_kmh[sel]
        ts = np.zeros(bucket, np.int32)
        ts[:n] = cols.ts_s[sel]
        valid = np.zeros(bucket, bool)
        valid[:n] = True
        prekeys = None
        if self._host_snap is not None:
            sub_cells = (cells[sel] if (cells is not None
                                        and self.meshmap.native) else None)
            prekeys = {}
            for r in self._parted._uniq_res:
                hi = np.zeros(bucket, np.uint32)
                lo = np.zeros(bucket, np.uint32)
                if sub_cells is not None and r == self.meshmap.snap_res:
                    hi[:n] = (sub_cells >> np.uint64(32)).astype(np.uint32)
                    lo[:n] = sub_cells.astype(np.uint32)
                else:
                    hi[:n], lo[:n] = self._host_snap(lat[:n], lng[:n], r)
                prekeys[r] = (hi, lo)
        dev = self._parted.devices[d]
        feed = {"lat": jax.device_put(lat, dev),
                "lng": jax.device_put(lng, dev),
                "speed": jax.device_put(speed, dev),
                "ts": jax.device_put(ts, dev),
                "valid": jax.device_put(valid, dev)}
        if prekeys is not None:
            prekeys = {r: (jax.device_put(hi, dev),
                           jax.device_put(lo, dev))
                       for r, (hi, lo) in prekeys.items()}
        return {"n": n, "feed": feed, "prekeys": prekeys}

    def _mesh_idle_chunk(self, d: int, bucket: int | None = None) -> dict:
        """Cached all-invalid chunk for device ``d`` at the current (or
        given) pad bucket — empty dispatches and the governor ladder
        warmup share it, so repeat empties pay no pad/transfer.  Safe
        to reuse: the jitted step donates only its STATE arguments."""
        if bucket is None:
            bucket = (self._mesh_governors[d].batch_rows
                      if self._mesh_governors is not None
                      else self._feed_batch)
        key = (d, bucket)
        cached = self._mesh_idle.get(key)
        if cached is None:
            dev = self._parted.devices[d]
            zf = jax.device_put(np.zeros(bucket, np.float32), dev)
            feed = {"lat": zf, "lng": zf, "speed": zf,
                    "ts": jax.device_put(np.zeros(bucket, np.int32), dev),
                    "valid": jax.device_put(np.zeros(bucket, bool), dev)}
            prekeys = None
            if self._host_snap is not None:
                z = jax.device_put(np.zeros(bucket, np.uint32), dev)
                prekeys = {r: (z, z) for r in self._parted._uniq_res}
            cached = self._mesh_idle[key] = {
                "n": 0, "feed": feed, "prekeys": prekeys}
        return cached

    def _warm_mesh_ladder(self, ladder) -> None:
        """Precompile every device's fused step at every pad bucket of
        ``ladder`` (the single-device _warm_ladder, per mesh shard): one
        all-invalid dispatch per (device, bucket) through the
        instrumented entry points — identity on the state, results
        discarded.  Each device runs its own copy of the program, a
        compile of its own, so the devices compile in parallel, one
        thread each; the step loop would compile them one after
        another.  After this a governed bucket move on ANY shard is a
        pure cache hit; any later compile IS a retrace and freezes
        every shard governor (the per-ladder latch)."""
        from concurrent.futures import ThreadPoolExecutor

        def warm(d: int) -> None:
            for n_rows in ladder:
                ch = self._mesh_idle_chunk(d, bucket=n_rows)
                f = ch["feed"]
                self._parted.step_shard(
                    d, f["lat"], f["lng"], f["speed"], f["ts"],
                    f["valid"], I32_MIN, prekeys=ch["prekeys"])

        t0 = time.monotonic()
        with ThreadPoolExecutor(self._parted.n_shards) as ex:
            list(ex.map(warm, range(self._parted.n_shards)))
        log.info("mesh governor bucket ladder warmed on %d devices: %s "
                 "(%.2fs)", self._parted.n_shards, ladder,
                 time.monotonic() - t0)

    def _flush_mesh_shard(self, d: int) -> None:
        """Pull + account every batch parked on ONE mesh shard's device
        (partitioned mode).  One call = one stacked transfer off that
        device ONLY — a hot downtown shard flushing at its own cadence
        never forces a pull on three idle suburb shards, so idle
        shards' pull counts stay at the idle-flush floor (checkpoints,
        idle polls, close)."""
        ring = self._mesh_rings[d]
        if not len(ring):
            return
        t0 = time.monotonic()
        from heatmap_tpu.engine.multi import stats_from_packed

        n_batches = len(ring)
        flushed = ring.flush_stacked(self._prefix_pull)
        residency = ring.last_flush_residency
        live = ring.last_flush_live
        batch_max = I32_MIN
        for i, (bufs, epoch) in enumerate(flushed):
            bm = I32_MIN
            for idx, (res, win_s) in enumerate(self._parted.pairs):
                stats = stats_from_packed(bufs[idx])
                bm = max(bm, self._account_pair_packed(
                    res, win_s // 60, bufs[idx][1:], stats, epoch,
                    shard=d))
            batch_max = self._book_flushed_batch(bm, batch_max)
            # idle entries' residency is synthetic (an empty dispatch
            # can park 8xK deep by design) — keep it OUT of the
            # ring-residency telemetry, which describes data batches
            self._note_mesh_flushed(
                epoch, residency[i] if (i < len(residency)
                                        and i < len(live) and live[i])
                else None)
        self._prune_vel_views()
        self.metrics.count("emit_pulls", 1)
        self.metrics.count("emit_pull_batches", n_batches)
        self._mesh_pulls[d] += 1
        self._mesh_pull_batches[d] += n_batches
        self._c_mesh_pulls.labels(shard=str(d)).inc()
        if batch_max > I32_MIN:
            self.max_event_ts = max(self.max_event_ts, batch_max)
        if self.max_event_ts > I32_MIN:
            self._g_watermark.set(time.time() - self.max_event_ts)
        self._last_flush_cutoff = (
            self.max_event_ts - self.cfg.watermark_minutes * 60
            if self.max_event_ts > I32_MIN else I32_MIN)
        self._last_pull_s += time.monotonic() - t0

    def _note_mesh_flushed(self, epoch: int, residency) -> None:
        """Per-(shard, batch) flush accounting on the partitioned mesh:
        residency histograms per pulled entry; the batch's lineage
        record closes only when its LAST shard entry has flushed (until
        then part of the batch's emits are still device-resident)."""
        if residency is not None:
            self.metrics.ring_residency.observe(residency[0])
            self.metrics.ring_residency_batches.observe(residency[1])
        pend = self._mesh_epoch_pend.get(epoch)
        if pend is None:
            return
        if pend > 1:
            self._mesh_epoch_pend[epoch] = pend - 1
            return
        del self._mesh_epoch_pend[epoch]
        rec = self._lineage_open.pop(epoch, None)
        if rec is None:
            return
        self.lineage.flushed(
            rec, ring_batches=residency[1] if residency else None)
        self.writer.submit_mark(functools.partial(self._lineage_commit,
                                                  rec))

    def mesh_shard_stats(self) -> list:
        """Per-mesh-shard accounting for artifacts and tools (e2e_rate
        --mesh-devices): rows folded,
        device->host pulls vs pulled batches (the ring's amortization),
        current ring depth, and the shard's effective/governed knobs.
        Empty list off the partitioned mesh path."""
        if self._parted is None:
            return []
        out = []
        for d in range(self._parted.n_shards):
            gov = (self._mesh_governors[d]
                   if self._mesh_governors is not None else None)
            out.append({
                "shard": d,
                "device": str(self._parted.devices[d]),
                "rows": int(self._mesh_rows[d]),
                "emit_pulls": int(self._mesh_pulls[d]),
                "emit_pull_batches": int(self._mesh_pull_batches[d]),
                "ring_pending": len(self._mesh_rings[d]),
                "flush_k": self._mesh_rings[d].capacity,
                "effective": ({"batch_rows": gov.batch_rows,
                               "flush_k": gov.flush_k,
                               "prefetch": gov.prefetch}
                              if gov is not None else
                              {"batch_rows": self._feed_batch,
                               "flush_k": self._mesh_rings[d].capacity,
                               "prefetch": self._prefetch_n}),
                "govern": (dict(enabled=True, **gov.snapshot())
                           if gov is not None else {"enabled": False}),
            })
        return out

    def _touch_heartbeat(self) -> None:
        """Liveness beacon for stream.supervisor: overwrite the file named
        by HEATMAP_HEARTBEAT_FILE (set by the supervisor in the child's
        env) with the current wall time, at most once a second.  Written
        from the step loop, so a wedged device op stops the beacon and
        the supervisor can declare a stall."""
        path = os.environ.get("HEATMAP_HEARTBEAT_FILE")
        if not path:
            return
        now = time.monotonic()
        if now - getattr(self, "_hb_last", 0.0) < 1.0:
            return
        self._hb_last = now
        self._hb_write(path)
        if getattr(self, "_hb_watchdog", None) is None:
            # First beacon == first completed step: only now start the
            # in-flight watchdog, so the supervisor's startup grace stays
            # in force through the first compile (an earlier watchdog
            # tick would count as the first beacon and drop the limit to
            # stall_timeout_s).  The watchdog keeps the beacon alive
            # while a step is IN FLIGHT, but only up to
            # HEATMAP_DISPATCH_GRACE_S (default 300 s): a legitimate
            # mid-run recompile (slab growth retrace, post-failover
            # retrace) outlives stall_timeout_s without being killed,
            # while a truly wedged device RPC goes quiet once the grace
            # lapses and still trips the supervisor.
            self._hb_stop = threading.Event()
            self._hb_watchdog = threading.Thread(
                target=self._hb_watchdog_loop, args=(path,), daemon=True)
            self._hb_watchdog.start()

    def _hb_write(self, path: str) -> None:
        try:
            with open(path, "w", encoding="utf-8") as fh:
                fh.write(f"{time.time():.3f} epoch={self.epoch}\n")
        except OSError:  # beacon must never take the pipeline down
            pass

    def _hb_watchdog_loop(self, path: str) -> None:
        grace = float(os.environ.get("HEATMAP_DISPATCH_GRACE_S", "300"))
        while not self._hb_stop.wait(1.0):
            began = getattr(self, "_step_began", None)
            if began is not None and time.monotonic() - began < grace:
                self._hb_write(path)

    def run(self, max_batches: int | None = None) -> None:
        """Drive the loop until the source is exhausted (or forever)."""
        trigger_s = self.cfg.trigger_ms / 1e3
        n = 0
        try:
            while max_batches is None or n < max_batches:
                t0 = time.monotonic()
                progressed = self.step_once()
                # beacon AFTER the step: the first write then proves a
                # completed step (incl. the first-step compile), so the
                # supervisor's startup grace stays in force until real
                # liveness exists — a pre-step beacon would drop it to
                # stall_timeout_s and get a slow first compile killed
                self._touch_heartbeat()
                # fleet member snapshot rides the loop too (idle polls
                # included, so a quiet stream still reads as alive at
                # /fleet/healthz instead of going stale)
                self._publish_member_snapshot()
                done = (self._global_live == 0 if self._multiproc
                        else self.source.exhausted)
                if progressed:
                    n += 1
                elif done:
                    break
                else:
                    time.sleep(0.05)
                    continue
                if trigger_s:
                    dt_left = trigger_s - (time.monotonic() - t0)
                    if dt_left > 0:
                        time.sleep(dt_left)
        finally:
            self.close()

    def close(self) -> None:
        if getattr(self, "slo_watchdog", None) is not None:
            # first: a watchdog tick must not evaluate healthz (or
            # spawn a capture) against a runtime mid-teardown
            self.slo_watchdog.stop()
        if getattr(self, "tsdb", None) is not None:
            # one last scrape (final counters + healthz verdict) and a
            # forced flush, THEN stop — the retrospective timeline must
            # cover the run's final window; same not-mid-teardown
            # ordering as the watchdog above
            try:
                self.tsdb.scrape_once()
            except Exception:  # noqa: BLE001 - telemetry never blocks
                pass           # the teardown
            self.tsdb.stop()
        # Abnormal = fatal overflow, a poisoned sink, or an exception
        # unwinding through run()'s finally into this close
        # (sys.exc_info() sees it) — incl. the SystemExit
        # stream.__main__ raises on SIGTERM.
        import sys as _sys

        exc = _sys.exc_info()[1]
        if isinstance(exc, SystemExit) and not exc.code:
            exc = None  # sys.exit(0) mid-run is a clean shutdown
        clean_close = not (self._fatal or self.writer.poisoned
                           or exc is not None)
        if self.flightrec is not None:
            # Flight record BEFORE the drain, so ring/prefetch depths
            # still describe the incident.  A normal close writes
            # nothing unless HEATMAP_FLIGHTREC_ALWAYS=1; either way the
            # recorder then stands down so the atexit backstop cannot
            # double-dump.
            if not clean_close:
                why = ("fatal state overflow" if self._fatal
                       else "poisoned sink" if self.writer.poisoned
                       else f"abnormal exit: {type(exc).__name__}: {exc}")
                self.flightrec.dump(why)
            elif os.environ.get("HEATMAP_FLIGHTREC_ALWAYS") == "1":
                self.flightrec.dump("clean close "
                                    "(HEATMAP_FLIGHTREC_ALWAYS=1)")
            else:
                self.flightrec.disarm()
        # final fleet snapshot: short bounded runs (and the moments
        # before an exit) leave their last counters/lineage on the
        # channel instead of whatever the 2 s cadence last caught.  A
        # clean close publishes it as a departure tombstone — a
        # finished bounded job must not degrade /fleet/healthz as a
        # "stale" member forever; an abnormal close leaves a live
        # snapshot so the fleet DOES see the member go dark
        self._publish_member_snapshot(force=True, left=clean_close)
        self.tracer.stop()  # flush a partial profiler capture, if any
        self.tracering.close()  # flush/close the JSONL trace export
        self._closing = True  # no further prefetch refills
        if getattr(self, "_hb_stop", None) is not None:
            self._hb_stop.set()
        try:
            try:
                # drain any carry AND any prefetched-but-undispatched
                # batches so the exit commit is record-aligned and a
                # bounded run loses nothing it already consumed from the
                # source.  Multiproc does NOT drain here (extra local
                # steps would desync the lockstep collectives;
                # run(max_batches=N) CAN exit mid-carry) — instead
                # _checkpoint() decides the mid-carry skip collectively,
                # so a carrying host and its carry-free peers all skip
                # the exit commit together and the tail replays on
                # resume.  On a fatal/poisoned exit the commit is skipped
                # anyway and the uncommitted carry replays on resume —
                # don't dispatch into a failed run.
                while ((self._carry_cols is not None or self._prefetched)
                       and not self._multiproc
                       and not self._fatal and not self.writer.poisoned):
                    self._step_once_inner()
                self.flush_pending()
            finally:
                # a fatal flush (e.g. deferred overflow in fail mode) sets
                # _fatal, so the exit commit below is skipped correctly
                if not self.writer.poisoned and not self._fatal:
                    self._checkpoint()
                # wait out the in-flight async commit either way; on the
                # fatal path only log its error so the original exception
                # survives
                self._ckpt_join(raise_errors=not self._fatal)
        finally:
            # a poisoned writer raises here, after source/store cleanup ran,
            # and the uncommitted offsets make the lost batch replayable
            try:
                self.source.close()
            finally:
                try:
                    self.writer.close()
                finally:
                    # AFTER the writer close: every view apply has run
                    # by now, so the final feed flush + closed-meta
                    # marker cover the run's full mutation stream even
                    # when the writer close raised (poisoned)
                    if self.repl_pub is not None:
                        self.repl_pub.close()
                    # AFTER the publisher close: its final flush may
                    # have rotated one last segment into the history
                    # log, and the compactor's closing step drains it
                    if self.hist_compactor is not None:
                        self.hist_compactor.close()
                    # release the runtime-frozen engine policy globals
                    # (r5 review): standalone merge_batch/bench callers
                    # in this process get the documented live-bank
                    # consult back instead of inheriting this runtime's
                    # snapshot forever
                    from heatmap_tpu.engine import step as engine_step

                    engine_step.SNAP_IMPL = None
                    engine_step.MERGE_BANK_PIN = engine_step._BANK_LIVE
