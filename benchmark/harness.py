"""One run of one cell: set-up, warm-up, the measured window, the drain,
the readings and the comparison with the plain reference.

The system under test is ``MicroBatchRuntime`` as the stream job wires
it (``models/pipelines.build_runtime``): the preset's ``Config`` with
the configuration file's overrides, a ``memory`` store from
``make_store``, and the mix's source in place of the preset's.  The
window calls ``step_once`` in the loop ``run`` runs (an idle poll
sleeps 50 ms; with a trigger interval a step is followed by the rest
of it), until the deadline; then the source stops, the runtime
drains what it holds, the emit ring is flushed and the writer has
committed every batch.  Rates and freshness include that drain.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from benchmark import check, spec as specmod, traffic

IDLE_SLEEP_S = 0.05        # run()'s sleep after an idle poll
STABLE_BATCHES = 4         # warm-up ends once the slab held this long
TRACE_LEAD_S = 1.0         # traced runs: profile from here into the window
TRACE_MAX_S = 5.0


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def say(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


class Readings:
    """What the per-layer readers read: span sums over the window, the
    window's lineage records, counters, the trace reduction and shapes."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def span_ms_per_batch(self, *names: str) -> float | None:
        if not self.batches:
            return None
        return 1e3 * sum(self.spans.get(n, 0.0) for n in names) / self.batches

    def lineage_ms(self, a: str, b: str) -> float | None:
        vals = [r[b] - r[a] for r in self.lineage if a in r and b in r]
        return 1e3 * float(np.mean(vals)) if vals else None


def _span_totals(rt) -> dict:
    return {k: (h.sum, h.count) for k, h in list(rt.metrics.spans.items())}


def _compile_counter():
    """Count XLA compile requests (compiled or loaded from the
    persistent cache) in this process from now on."""
    import jax

    box = {"n": 0}

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            box["n"] += 1

    jax.monitoring.register_event_listener(on_event)
    return box


def _annotated(fn, name: str):
    import jax

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return wrapped


def cell_config(spec, workload: dict, scale: dict | None = None):
    """(configuration file, mix, the program's ``Config``) of a cell:
    the preset's ``Config`` with the file's overrides and the mix's
    ``runtime``, the memory store."""
    from heatmap_tpu.models.pipelines import get_pipeline

    cfg_file = spec.config(workload["config"])
    scale = scale or {}
    mix = {**spec.mix(workload["traffic"]), **scale.get("mix", {})}
    if "fleet" in scale:
        cfg_file = {**cfg_file, "fleet": {**cfg_file["fleet"],
                                          **scale["fleet"]}}
    cfg = dataclasses.replace(get_pipeline(cfg_file["preset"]).config, **{
        **cfg_file.get("overrides", {}), **mix.get("runtime", {}),
        **scale.get("runtime", {}), "store": "memory"})
    return cfg_file, mix, cfg


def build(spec, workload: dict, seed: int, ckpt_dir: str, trace: bool,
          scale: dict | None = None):
    """(runtime, store, source, cfg, cfg_file, mix) for one cell."""
    import jax

    from heatmap_tpu.sink import make_store
    from heatmap_tpu.stream import MicroBatchRuntime

    cfg_file, mix, cfg = cell_config(spec, workload, scale)
    cfg = dataclasses.replace(cfg, checkpoint_dir=ckpt_dir)
    annotate = jax.profiler.TraceAnnotation if trace else None
    source = traffic.make_source(mix, cfg_file, seed, annotate=annotate)
    store = make_store(cfg)
    if trace:
        for m in ("upsert_tiles_packed", "upsert_positions"):
            setattr(store, m, _annotated(getattr(store, m), f"store.{m}"))
    mesh = None
    if workload["chips"] > 1:
        from heatmap_tpu.parallel import make_mesh

        mesh = make_mesh(workload["chips"])
    rt = MicroBatchRuntime(cfg, source, store, mesh=mesh)
    return rt, store, source, cfg, cfg_file, mix


def _pause(rt, progressed: bool, t_step: float) -> float:
    """Seconds ``run()`` sleeps after a step that began at ``t_step``:
    50 ms after an idle poll, else the rest of the trigger interval."""
    if not progressed:
        return IDLE_SLEEP_S
    return rt.cfg.trigger_ms / 1e3 - (time.time() - t_step)


def _capacity(rt) -> int:
    return int(rt._agg().capacity_per_shard)


def _drain(rt, source) -> None:
    """Stop the source, dispatch what the runtime holds, flush the emit
    ring and wait for every write and commit mark."""
    source.stop()
    while True:
        t_step = time.time()
        if not rt.step_once():
            break
        time.sleep(max(0.0, _pause(rt, True, t_step)))
    rt.flush_pending()
    rt.writer.drain()


def _join_checkpoint(rt) -> None:
    t = getattr(rt, "_ckpt_thread", None)
    if t is not None:
        t.join()


def warm_up(rt, source, min_batches: int, min_event_s: float = 0.0) -> int:
    """Steps on the cell's own traffic until ``min_batches`` have run,
    event time has advanced ``min_event_s`` (so windows have closed and
    been evicted, and the slab holds its steady set of live groups) and
    the slab has kept its size for STABLE_BATCHES; then drains."""
    source.start()
    caps, n = [], 0
    first_ts = None

    def event_span() -> float:
        if first_ts is None or rt.max_event_ts <= first_ts:
            return 0.0
        return float(rt.max_event_ts - first_ts)

    packed = None
    while n < min_batches or len(set(caps[-STABLE_BATCHES:])) > 1 \
            or len(caps) < STABLE_BATCHES or event_span() < min_event_s:
        if first_ts is None and n == 1:
            first_ts = rt.max_event_ts
        t_step = time.time()
        progressed = rt.step_once()
        if progressed:
            n += 1
            caps.append(_capacity(rt))
            if len(rt._ring):
                packed = rt._ring._entries[-1][0]
        time.sleep(max(0.0, _pause(rt, progressed, t_step)))
    _drain(rt, source)
    _join_checkpoint(rt)
    if packed is not None and rt._multi is not None:
        _warm_flushes(rt, packed)
    return n


def _warm_flushes(rt, packed) -> None:
    """Compile the emit ring's flush for every shape it can take: each
    depth (a flush under watermark pressure or on an idle poll pulls
    fewer than ``emit_flush_k`` parked batches) and, with the live-prefix
    pull, each power-of-two bucket of emitted rows up to the emit
    capacity.  Each is a program of its own."""
    import jax

    from heatmap_tpu.engine.step import EmitRing

    emit_cap = packed.shape[1] - 1
    buckets = [1 << b for b in range(emit_cap.bit_length())
               if (1 << b) < emit_cap] + [emit_cap]
    host = np.zeros(packed.shape, packed.dtype)
    for bucket in buckets if rt._prefix_pull else buckets[:1]:
        host[:, 0, 0] = bucket   # head row: rows emitted by the batch
        # placed as the step's own outputs are (committed or not), so the
        # flush programs are the ones the runtime's flushes look up
        dummy = jax.device_put(host, packed.sharding if packed.committed
                               else None)
        for k in range(1, rt._ring.capacity + 1):
            ring = EmitRing(k)
            for _ in range(k):
                ring.append(dummy)
            ring.flush_stacked(rt._prefix_pull)


def window(rt, source, seconds: float, trace_dir: str | None):
    """The measured window; returns (t_start, t_end, traced span
    (start, end) in wall seconds or None, backlog at the deadline)."""
    import jax

    t0 = time.time()
    source.start()
    # a compile inside the window is a fault of the warm-up: name it
    jax.config.update("jax_log_compiles", True)
    deadline = t0 + seconds
    trace_at = t0 + min(TRACE_LEAD_S, seconds / 4)
    trace_len = min(TRACE_MAX_S, seconds / 2)
    tracing, traced, span = False, None, None
    while True:
        now = time.time()
        if trace_dir is not None and not tracing and traced is None \
                and now >= trace_at:
            # no Python function events: the benchmark's own spans and
            # the device's ops are what the reduction reads
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            span = jax.profiler.TraceAnnotation("bench.window")
            span.__enter__()
            tracing, traced = True, [time.time(), None]
        if tracing and now >= traced[0] + trace_len:
            span.__exit__(None, None, None)
            traced[1] = time.time()
            jax.profiler.stop_trace()
            tracing = False
        if now >= deadline:
            break
        with (jax.profiler.TraceAnnotation("bench.step_once") if tracing
              else contextlib.nullcontext()):
            progressed = rt.step_once()
        pause = _pause(rt, progressed, now)
        if pause > 0:
            with (jax.profiler.TraceAnnotation("bench.idle_sleep")
                  if tracing else contextlib.nullcontext()):
                time.sleep(pause)
    if tracing:
        span.__exit__(None, None, None)
        traced[1] = time.time()
        jax.profiler.stop_trace()
    backlog = source.backlog() if hasattr(source, "backlog") else 0
    _drain(rt, source)
    jax.config.update("jax_log_compiles", False)
    return t0, deadline, traced, backlog


def end_to_end(name: str, lineage: list, t0: float, t_end: float,
               source) -> tuple[float | None, int, int]:
    """(value, attempted, failed) of an end-to-end metric other than
    ``setup_s``, from the window's lineage records."""
    if name == "events_per_s":
        recs = [r for r in lineage if r.get("t_dispatch", 0) >= t0]
        done = [r for r in recs if "t_sink" in r]
        n = sum(r["n_events"] for r in done)
        attempted = sum(r["n_events"] for r in recs)
        if not done:
            return None, attempted, attempted
        return n / (max(r["t_sink"] for r in done) - t0), attempted, \
            attempted - n
    if name.startswith("freshness_p"):
        ages, due_n = freshness_samples(lineage, source, t0, t_end)
        q = float(name[len("freshness_p"):].split("_")[0])
        val = 1e3 * float(np.percentile(ages, q)) if len(ages) else None
        return val, due_n, due_n - len(ages)
    raise KeyError(f"no end-to-end metric {name!r}")


def freshness_samples(lineage: list, source, t0: float, t_end: float):
    """Per event due in [t0, t_end): its batch's sink commit time minus
    its due time.  Returns (samples, events due)."""
    seg_g0, seg_g1 = source.window_due(t_end)   # the window's segment
    ages = []
    for r in lineage:
        if "t_sink" not in r:
            continue
        g1 = int(r["offset"])
        g0 = g1 - int(r["n_events"])
        g0, g1 = max(g0, seg_g0), min(g1, seg_g1)
        if g1 > g0:
            ages.append(r["t_sink"] - source.due_times(g0, g1))
    return (np.concatenate(ages) if ages else np.zeros(0)), seg_g1 - seg_g0


def reference_check(store, source, cfg) -> dict:
    """The numbers compared, from the store and the plain reference."""
    ref, ev = check.reference(source, cfg)
    docs = list(store._tiles.values())
    prog = {p: check.groups_from_docs(docs, cfg.pair_grid(p[0], p[1] // 60))
            for p in ref}
    numbers = check.compare_tiles(prog, ref)
    numbers["positions_gap"] = check.compare_positions(
        store.all_positions(), ev)
    return numbers


def run_cell(workload_name: str, seed: int, seconds: float, trace: bool,
             *, t_process: float | None = None, spec=None,
             require_tpu: bool = True, scale: dict | None = None,
             keep_trace: str | None = None) -> dict:
    """One run; returns the result line as a dict.  ``scale`` overrides
    parts of the cell: ``fleet``, ``runtime`` and ``mix`` keys, and
    ``warmup_batches`` (tests shrink a cell to run it on the CPU with
    ``require_tpu=False``; the live rate sweep sets the mix's rate)."""
    t_process = time.monotonic() if t_process is None else t_process
    spec = spec or specmod.Spec.load()
    workload = spec.workload(workload_name)
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < workload["chips"]):
        raise NoAccelerator(
            f"cell {workload_name} needs {workload['chips']} TPU chip(s); "
            f"JAX reports {len(devices)} {devices[0].platform} device(s)")
    peaks = (specmod.peaks(devices[0].device_kind, spec.base) if require_tpu
             else None)
    from heatmap_tpu.utils.jaxenv import enable_compile_cache

    cache = enable_compile_cache()
    if require_tpu:
        # every program, however quick to compile, is found in the cache
        # by the next run of the cell
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = _compile_counter()
    tmp = tempfile.mkdtemp(prefix="bench-")
    try:
        rt, store, source, cfg, cfg_file, mix = build(
            spec, workload, seed, os.path.join(tmp, "ckpt"), trace, scale)
        lineage = []
        committed = rt.lineage.committed

        def on_commit(rec):
            out = committed(rec)
            lineage.append(out)
            return out
        rt.lineage.committed = on_commit
        # warm-up: past the first checkpoint (its copy programs compile
        # there) and, on replayed event time, past the watermark and two
        # windows, so the first windows have closed and been evicted
        min_batches = (scale or {}).get("warmup_batches",
                                        rt.checkpoint_every + 2)
        min_event_s = 0.0
        if mix["arrival"] == "closed":
            min_event_s = 60.0 * (cfg.watermark_minutes
                                  + 2 * max(cfg.windows_minutes))
        n_warm = warm_up(rt, source, min_batches, min_event_s)
        from heatmap_tpu.engine import step as engine_step

        say(f"resolved snap={rt._snap_impl_name} "
            f"merge={engine_step._resolve_merge_impl()}"
            f"(bank pin {engine_step.MERGE_BANK_PIN!r}) "
            f"slab_rows={_capacity(rt)} feed_batch={rt._feed_batch} "
            f"pairs={len(cfg.resolutions) * len(cfg.windows_minutes)} "
            f"emit_flush_k={cfg.emit_flush_k} prefetch={cfg.prefetch_batches} "
            f"checkpoint_every={rt.checkpoint_every} "
            f"warmup_batches={n_warm} compile_cache={cache}")
        spans0, counters0 = _span_totals(rt), dict(rt.metrics.counters)
        compiles0 = compiles["n"]
        n_lineage0 = len(lineage)
        # set-up's objects (the capture, the runtime) leave the collector's
        # generations: the window's collections walk only what it allocates
        gc.collect()
        gc.freeze()
        setup_s = time.monotonic() - t_process
        trace_dir = os.path.join(tmp, "trace") if trace else None
        t0, t_end, traced, backlog = window(rt, source, seconds, trace_dir)
        in_window = compiles["n"] - compiles0
        spans1 = _span_totals(rt)
        counters = {k: v - counters0.get(k, 0)
                    for k, v in rt.metrics.counters.items()}
        used = devices[:workload["chips"]]
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in used)
        recs = lineage[n_lineage0:]
        span_s = {k: spans1[k][0] - spans0.get(k, (0.0, 0))[0] for k in spans1}
        batches = spans1.get("total", (0, 0))[1] - spans0.get("total",
                                                            (0, 0))[1]
        say(f"window seconds={seconds} batches={batches} "
            f"lineage_records={len(recs)} compiles_in_window={in_window} "
            f"flushes={counters.get('emit_pulls', 0)} "
            f"checkpoints={counters.get('checkpoints', 0)} "
            f"backlog_at_deadline={backlog}")
        metrics, attempted, failed = {}, 0, 0
        for m in spec.end_to_end(workload_name):
            if m["name"] == "setup_s":
                val = setup_s
            else:
                val, attempted, failed = end_to_end(m["name"], recs, t0,
                                                    t_end, source)
            if not trace and val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        if any(m["name"].startswith("freshness_p")
               for m in spec.end_to_end(workload_name)):
            ages, due_n = freshness_samples(recs, source, t0, t_end)
            say(f"freshness samples={len(ages)} due={due_n} "
                f"flushes={counters.get('emit_pulls', 0)}")
        reduced = None
        if trace and traced is not None:
            reduced = _reduce_trace(trace_dir, keep_trace)
        _join_checkpoint(rt)
        rt.writer.close()
        readings = Readings(
            spans=span_s, batches=batches, lineage=recs, counters=counters,
            trace=reduced, peaks=peaks, rt=rt)
        if trace:
            for m in spec.per_layer(workload_name):
                val = specmod.reader(m["name"], spec.base)(readings)
                if val is not None:
                    metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        del readings, rt
        t_ref = time.monotonic()
        numbers = reference_check(store, source, cfg)
        say(f"reference seconds={time.monotonic() - t_ref:.3f} "
            f"events_sent={source.consumed} "
            f"disk_write_bytes={_written_bytes()}")
        lim = check.limits()
        correct = check.judge(numbers, lim) and in_window == 0
        d0 = devices[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(devices), "memory_peak_bytes": int(peak)}
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
        out = {"correct": bool(correct), "attempted": int(attempted),
               "failed": int(failed), "metrics": metrics, "device": device}
        if reduced:
            out["breakdown"] = {"device_ops": reduced["device_ops"],
                                "idle_gaps": reduced["idle_gaps"]}
        out["checks"] = {k: {"value": numbers[k], "limit": lim[k]}
                         for k in check.NUMBERS}
        out["checks"]["compiles_in_window"] = {"value": in_window,
                                               "limit": 0}
        return out
    finally:
        gc.unfreeze()
        shutil.rmtree(tmp, ignore_errors=True)


def _written_bytes() -> int | None:
    """Bytes this process has caused to be written to storage."""
    try:
        with open("/proc/self/io", encoding="ascii") as fh:
            for line in fh:
                if line.startswith("write_bytes:"):
                    return int(line.split()[1])
    except OSError:
        return None
    return None


def _reduce_trace(trace_dir: str, keep: str | None) -> dict | None:
    from benchmark import trace as tr

    path = tr.find_xplane(trace_dir)
    if keep:
        os.makedirs(keep, exist_ok=True)
        shutil.copy(path, os.path.join(keep, os.path.basename(path)))
    devices, host = tr.load(path)
    window = [(s, s + d) for name, s, d in host if name == "bench.window"]
    if not devices or not window:
        say(f"trace: {len(devices)} device planes, "
            f"{len(window)} window spans")
        return None
    return tr.reduce(devices, host, window[0][0], window[0][1])
