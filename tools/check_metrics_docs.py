#!/usr/bin/env python
"""Fail when an exposed metric family is undocumented.

The metrics table in ARCHITECTURE.md §Observability is the operator
contract — dashboards and alerts are written against it.  Nothing keeps
it honest by itself: a new registry family quietly ships with an empty
HELP string or without a table row, and the next operator greps the
docs for a series that isn't there (exactly what happened to
``heatmap_emit_ring_pending`` in PR 2).

This check smoke-assembles a REAL runtime (tiny CPU micro-batches,
memory store), walks every family the registry would expose at
/metrics, and asserts each one

  1. carries a non-empty HELP string, and
  2. appears (sans ``heatmap_`` prefix) in ARCHITECTURE.md.

Run next to the suite (tests/test_check_metrics_docs.py makes it
tier-1, the same pattern as check_native_build).
"""

import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


def _smoke_runtime():
    """A tiny real runtime run to exhaustion — every layer that
    registers metrics (runtime, writer, engine clocks, serve gauge)
    has registered by the time it returns."""
    from heatmap_tpu.config import load_config
    from heatmap_tpu.sink import MemoryStore
    from heatmap_tpu.stream import MicroBatchRuntime
    from heatmap_tpu.stream.source import MemorySource

    t0 = int(time.time()) - 5
    evs = [{"provider": "p", "vehicleId": f"v{i}", "lat": 42.0 + i * 1e-4,
            "lon": -71.0, "speedKmh": 1.0, "ts": t0} for i in range(32)]
    cfg = load_config({}, batch_size=16, state_capacity_log2=8,
                      speed_hist_bins=4, store="memory", serve_port=0,
                      reducers=("count", "kalman"),
                      checkpoint_dir=tempfile.mkdtemp(
                          prefix="metrics-docs-"))
    src = MemorySource(evs)
    src.finish()
    store = MemoryStore()
    rt = MicroBatchRuntime(cfg, src, store, checkpoint_every=0)
    rt.run()
    # building the WSGI app registers the serve-tier families (render /
    # 304 / delta / SSE counters, view rebuilds) into the runtime's
    # registry, so the docs gate covers the query tier too
    from heatmap_tpu.serve.api import make_wsgi_app

    make_wsgi_app(store, cfg, runtime=rt)
    return rt


def _smoke_shard_runtime():
    """A CONSTRUCTED (never run) H3-partitioned shard runtime: the
    shard gauge families (shard index/count, watermark-alignment lag)
    only register on a sharded config, which the unsharded smoke above
    can never expose.  The out-of-shard drop counter is a flat
    ad-hoc counter (Metrics.count), exposed at /metrics like
    events_valid but — like every flat counter — outside this
    registry-walking gate."""
    from heatmap_tpu.config import load_config
    from heatmap_tpu.sink import MemoryStore
    from heatmap_tpu.stream import MicroBatchRuntime
    from heatmap_tpu.stream.source import MemorySource

    cfg = load_config({}, batch_size=16, state_capacity_log2=8,
                      speed_hist_bins=4, store="memory", serve_port=0,
                      shards=2, shard_index=0,
                      checkpoint_dir=tempfile.mkdtemp(
                          prefix="metrics-docs-shard-"))
    src = MemorySource([])
    src.finish()
    rt = MicroBatchRuntime(cfg, src, MemoryStore(), checkpoint_every=0)
    rt.close()
    return rt


def _smoke_mesh_runtime():
    """A CONSTRUCTED (never run) partitioned-mesh runtime: the
    per-mesh-shard families (mesh devices/rows/pulls/ring gauges and
    the shard-labeled governor gauges) only register when a
    multi-device mesh is attached in partitioned mode.  Needs >= 2
    devices — main() forces 2 CPU host devices before any backend
    initializes; if the forcing is unavailable on this jaxlib the
    smoke is skipped (the families go unenforced on that host, not
    wrongly failed)."""
    import jax

    if jax.device_count() < 2:
        return None
    from heatmap_tpu.config import load_config
    from heatmap_tpu.parallel import make_mesh
    from heatmap_tpu.sink import MemoryStore
    from heatmap_tpu.stream import MicroBatchRuntime
    from heatmap_tpu.stream.source import MemorySource

    cfg = load_config({}, batch_size=64, state_capacity_log2=8,
                      speed_hist_bins=4, store="memory", serve_port=0,
                      govern=True, govern_min_batch=64,
                      checkpoint_dir=tempfile.mkdtemp(
                          prefix="metrics-docs-mesh-"))
    src = MemorySource([])
    src.finish()
    rt = MicroBatchRuntime(cfg, src, MemoryStore(), mesh=make_mesh(2),
                           checkpoint_every=0)
    rt.close()
    return rt


def _smoke_repl():
    """CONSTRUCTED replication publisher + follower (query/repl.py):
    their metric families only register on a replicated config — a
    writer with HEATMAP_REPL_DIR and a serve replica with
    HEATMAP_REPL_FEED — which neither runtime smoke above exposes.
    No threads run; construction alone registers the families."""
    from heatmap_tpu.obs.registry import Registry
    from heatmap_tpu.query import TileMatView
    from heatmap_tpu.query.repl import (DeltaLogPublisher,
                                        FileFeedSource,
                                        ReplicaViewFollower)

    feed = tempfile.mkdtemp(prefix="metrics-docs-repl-")
    reg = Registry()
    DeltaLogPublisher(TileMatView(), feed, registry=reg, start=False)
    ReplicaViewFollower(TileMatView(replica=True), FileFeedSource(feed),
                        registry=reg)
    return list(reg._families.values())


def _smoke_hist():
    """CONSTRUCTED space-time history compactor + reader
    (query/history.py): the ``heatmap_hist_*`` families only register
    under HEATMAP_HIST_DIR, which no runtime smoke above sets.
    Construction alone registers them; no compaction thread starts.
    The reader contributes the ``heatmap_hist_scan_*`` accounting
    counters (chunks opened / blocks scanned / bytes decoded / rows
    surfaced).  The replica backfill counter registers with the
    follower (covered by _smoke_repl)."""
    from heatmap_tpu.obs.registry import Registry
    from heatmap_tpu.query.history import (FileHistorySource,
                                           HistoryCompactor,
                                           HistoryReader)

    reg = Registry()
    hist_dir = tempfile.mkdtemp(prefix="metrics-docs-hist-")
    HistoryCompactor(hist_dir, registry=reg)
    HistoryReader(FileHistorySource(hist_dir), registry=reg)
    return list(reg._families.values())


def _smoke_govern():
    """CONSTRUCTED adaptive-batching governor (stream/govern.py): its
    metric families only register under HEATMAP_GOVERN=1, which none
    of the runtime smokes above enable.  Construction alone registers
    the families; no control loop runs."""
    from heatmap_tpu.config import load_config
    from heatmap_tpu.obs.registry import Registry
    from heatmap_tpu.stream.govern import BatchGovernor

    cfg = load_config({}, batch_size=256, govern=True,
                      govern_min_batch=64)
    reg = Registry()
    BatchGovernor(cfg, reg)
    return list(reg._families.values())


def _smoke_audit():
    """CONSTRUCTED integrity-observatory state (obs/audit.py): the
    ``heatmap_audit_*`` families only register under HEATMAP_AUDIT=1,
    which no runtime smoke above enables.  Construction alone
    registers them (the reason-labeled drop family registers
    unconditionally in stream.metrics and rides the runtime smoke)."""
    from heatmap_tpu.obs.audit import AuditState
    from heatmap_tpu.obs.registry import Registry

    reg = Registry()
    AuditState(reg, tag="docsgate")
    return list(reg._families.values())


def _smoke_cq():
    """CONSTRUCTED continuous-query engine (query/continuous.py): the
    ``heatmap_cq_*`` families register on any view-backed serve app
    (the runtime smoke covers that path too), but constructing the
    engine directly keeps them enforced even if the app wiring gains a
    kill switch.  No watcher attaches, no thread starts."""
    from heatmap_tpu.obs.registry import Registry
    from heatmap_tpu.query import TileMatView
    from heatmap_tpu.query.continuous import ContinuousQueryEngine

    reg = Registry()
    ContinuousQueryEngine(TileMatView(), registry=reg)
    return list(reg._families.values())


def _smoke_tsdb():
    """CONSTRUCTED telemetry-history recorder + SLO engine (obs/tsdb.py
    + obs/slo.py): the ``heatmap_tsdb_*`` and ``heatmap_slo_*``
    families only register under HEATMAP_TSDB=1, which no runtime smoke
    above enables.  Construction alone registers them — no sampler
    thread starts, nothing touches disk (no dir_path)."""
    from heatmap_tpu.obs.registry import Registry
    from heatmap_tpu.obs.slo import SloEngine
    from heatmap_tpu.obs.tsdb import TsdbRecorder

    reg = Registry()
    rec = TsdbRecorder(lambda: "", tag="docsgate", registry=reg,
                       scrape_s=1.0)
    SloEngine(rec, registry=reg, tag="docsgate")
    return list(reg._families.values())


def _smoke_quality():
    """CONSTRUCTED inference-quality observatory (obs/quality.py): the
    ``heatmap_quality_*`` families only register under
    HEATMAP_QUALITY=1 with the kalman reducer, which no runtime smoke
    above enables.  Construction alone registers them — no scoring
    runs, nothing touches the history tier."""
    from heatmap_tpu.config import load_config
    from heatmap_tpu.obs.quality import QualityObservatory
    from heatmap_tpu.obs.registry import Registry

    cfg = load_config({}, quality=True)
    reg = Registry()
    QualityObservatory(cfg, registry=reg, tag="docsgate")
    return list(reg._families.values())


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # the mesh smoke needs >= 2 devices; force 2 CPU host devices
    # BEFORE any backend initializes (lazy init — the first smoke below
    # is the first jax touch)
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_"
                                 "count=2").strip()
    try:
        import jax

        jax.config.update("jax_num_cpu_devices", 2)
    except AttributeError:
        pass  # older jaxlib: the XLA flag above is honored at lazy init
    with open(os.path.join(REPO, "ARCHITECTURE.md"),
              encoding="utf-8") as fh:
        arch = fh.read()
    rt = _smoke_runtime()
    failures = []
    fams = list(rt.metrics.registry._families.values())
    seen = {f.name for f in fams}
    fams += [f for f in
             _smoke_shard_runtime().metrics.registry._families.values()
             if f.name not in seen]
    seen = {f.name for f in fams}
    mesh_rt = _smoke_mesh_runtime()
    if mesh_rt is not None:
        fams += [f for f in mesh_rt.metrics.registry._families.values()
                 if f.name not in seen]
    seen = {f.name for f in fams}
    fams += [f for f in _smoke_repl() if f.name not in seen]
    seen = {f.name for f in fams}
    fams += [f for f in _smoke_hist() if f.name not in seen]
    seen = {f.name for f in fams}
    fams += [f for f in _smoke_govern() if f.name not in seen]
    seen = {f.name for f in fams}
    fams += [f for f in _smoke_audit() if f.name not in seen]
    seen = {f.name for f in fams}
    fams += [f for f in _smoke_cq() if f.name not in seen]
    seen = {f.name for f in fams}
    fams += [f for f in _smoke_tsdb() if f.name not in seen]
    seen = {f.name for f in fams}
    fams += [f for f in _smoke_quality() if f.name not in seen]
    for fam in fams:
        if not fam.help.strip():
            failures.append(f"{fam.name}: empty HELP string")
        short = fam.name.removeprefix("heatmap_")
        if short not in arch and fam.name not in arch:
            failures.append(
                f"{fam.name}: not documented in ARCHITECTURE.md "
                f"(add a row to the §Observability metrics table)")
    # the fleet observatory's own families (obs.fleet.FAMILIES) are
    # emitted as raw exposition text at /fleet/metrics — no registry to
    # walk, so the gate covers the table directly
    from heatmap_tpu.obs.fleet import FAMILIES as FLEET_FAMILIES

    for name, _mtype, help_ in FLEET_FAMILIES:
        if not help_.strip():
            failures.append(f"{name}: empty HELP string")
        short = name.removeprefix("heatmap_")
        if short not in arch and name not in arch:
            failures.append(
                f"{name}: not documented in ARCHITECTURE.md "
                f"(add a row to the §Fleet observatory metrics table)")
    if failures:
        print("FAIL: undocumented metrics:", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
        return 1
    print(f"OK: {len(fams) + len(FLEET_FAMILIES)} metric families "
          f"documented with HELP strings")
    return 0


if __name__ == "__main__":
    sys.exit(main())
