#!/usr/bin/env python
"""obs_top — curses-free terminal dashboard over /metrics + /healthz.

Polls a running heatmap serve endpoint and renders the numbers an
operator watches during an incident: ingest rate, batch p50/p95,
end-to-end freshness (event-age p50/p99, through the prefetch queue and
the device emit ring — obs.lineage), emit-ring depth, sink queue/
backpressure, compile/retrace activity and device-memory watermarks
(obs.runtimeinfo), the adaptive micro-batching governor's live
batch/flush-K/prefetch decisions + last-adjust + frozen state
(stream/govern.py — a per-member governor table in ``--fleet``), and
the /healthz SLO verdict.  Rates and recent quantiles
are computed from DELTAS between successive scrapes of the cumulative
Prometheus histograms, so the display tracks the last interval, not the
lifetime distribution.

Plain ANSI only (no curses): one screen clear + reprint per interval,
which also works piped into a file or over the dumbest of SSH hops.

``--fleet`` switches to the fleet observatory view (obs.fleet): per-
member rows — ingest rate, event-age p50, memory watermark, last-seen
age, up/stale — off ``/fleet/metrics``, plus the aggregate
``/fleet/healthz`` verdict.  Needs a serve process holding the
supervisor channel path.  When the members are H3-partitioned runtime
shards (stream/shardmap.py), a per-shard table follows: shard index,
owned-cell share, steady rate, event-age p50, and the max/mean
shard-imbalance ratio that makes a skewed partition obvious.  When
serve-role members (or replication followers, query/repl.py) are on
the channel, a serve-replica table follows too: replication seq lag,
open SSE clients, and the 304 ratio per worker, plus the fleet's max
seq lag.  Workers serving the binary wire path (serve/wire.py) add a
serve-wire table: per-worker open clients, negotiated-format mix
(binary fraction), wire-vs-rendered byte rates, admission-shed count,
and the SSE fan-out send-queue high-water.  Members running the
space-time history tier (query/history.py) add a history row (single
view) and a per-member history table in ``--fleet``: chunks on disk,
covered span, compaction lag, replica backfills.  Members running the
streaming inference engine (heatmap_tpu.infer, kalman in
HEATMAP_REDUCERS) add an infer row (tracked entities, fold p50,
anomaly totals with the loudest reason, table churn) and a per-member
entity-table section in ``--fleet`` — entity tables follow the H3
shard partition, so skewed partitions show as skewed entity counts.  With delivery
lineage on (HEATMAP_DELIVERY=1, obs.delivery) a delivery row joins the
single view — delivered-age p50/p99 to the subscriber socket, worst
stage, slow-request count, worst SSE write stall — and ``--fleet``
adds a per-replica delivery table naming the worst replica.

With the telemetry history recorder on (HEATMAP_TSDB=1, obs.tsdb)
``--since <seconds>`` switches to the TIME-MACHINE view: no live
endpoint needed — the frame is rendered from the retained on-disk
series alone.  One sparkline row per headline family (ingest rate,
tiles rate, ring/sink depth, repl lag, sheds), a healthz strip showing
ok/degraded/down per time slot, the member's SLO error-budget ledger
(remaining %, worst burn-rate multiple, alerts fired — obs.slo), and
the incident-timeline tail.  ``--replay`` animates the same window as
a growing sequence of frames — watching an incident unfold after the
fact.  Point it with ``--tsdb-dir`` (or HEATMAP_TSDB_DIR) and pick a
member with ``--member``.

Usage:
    python tools/obs_top.py [--url http://127.0.0.1:5000] [--interval 2]
    python tools/obs_top.py --once          # single frame (no clear)
    python tools/obs_top.py --fleet         # per-member fleet rows
    python tools/obs_top.py --since 3600 --tsdb-dir /var/lib/heatmap/tsdb
    python tools/obs_top.py --replay --since 600 --tsdb-dir ...
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.error
import urllib.request


def parse_prom(text: str) -> dict:
    """Minimal Prometheus text parser: {name: {labels_str: value}}
    (labels_str is the raw ``{...}`` block, "" for unlabeled)."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        try:
            series, val = line.rsplit(" ", 1)
            v = float(val)
        except ValueError:
            continue
        if "{" in series:
            name, _, rest = series.partition("{")
            labels = "{" + rest
        else:
            name, labels = series, ""
        out.setdefault(name, {})[labels] = v
    return out


def bucket_bounds(samples: dict) -> list:
    """[(le_float, labels_str)] sorted, +Inf last, from a _bucket
    series' samples."""
    out = []
    for labels in samples:
        le = None
        for part in labels.strip("{}").split(","):
            k, _, v = part.partition("=")
            if k.strip() == "le":
                v = v.strip('"')
                le = float("inf") if v == "+Inf" else float(v)
        if le is not None:
            out.append((le, labels))
    return sorted(out, key=lambda t: t[0])


def hist_quantile(cur: dict, prev: dict | None, q: float) -> float | None:
    """Interpolated quantile over the DELTA of two cumulative bucket
    scrapes (prev=None → lifetime).  Returns None on an empty window."""
    bounds = bucket_bounds(cur)
    if not bounds:
        return None
    deltas, cum_prev = [], 0.0
    for le, labels in bounds:
        c = cur.get(labels, 0.0) - (prev.get(labels, 0.0) if prev else 0.0)
        deltas.append((le, max(0.0, c - cum_prev)))
        cum_prev = max(cum_prev, c)
    total = sum(d for _, d in deltas)
    if total <= 0:
        return None
    target = q * total
    run, lo = 0.0, 0.0
    for le, d in deltas:
        if run + d >= target and d > 0:
            if le == float("inf"):
                return lo  # open-ended: report the last finite bound
            frac = (target - run) / d
            return lo + frac * (le - lo)
        run += d
        if le != float("inf"):
            lo = le
    return lo


def _fetch(url: str, timeout: float = 5.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def _val(m: dict, name: str, labels: str = "") -> float | None:
    return m.get(name, {}).get(labels)


def counter_increase(cur: float | None,
                     was: float | None) -> float | None:
    """Increase of a cumulative counter between two scrapes, reset-
    aware: a process restart drops the total back toward zero, so a
    current value BELOW the previous one means the run restarted and
    the post-reset total IS the whole increase — the rate resumes from
    the reset point instead of going hugely negative for one frame."""
    if cur is None or was is None:
        return None
    return cur if cur < was else cur - was


def _sum_increase(m: dict, prev: dict | None, name: str) -> float | None:
    """Reset-aware increase of a family summed across its labelsets —
    each labelset's delta computed independently so one restarted
    member cannot drag the summed delta negative."""
    cur = m.get(name)
    if cur is None or prev is None:
        return None
    prv = prev.get(name) or {}
    total = 0.0
    for labels, v in cur.items():
        was = prv.get(labels)
        total += v if was is None else (counter_increase(v, was) or 0.0)
    return total


def _sum(m: dict, name: str) -> float | None:
    """Sum a family across its labelsets (e.g. per-fn compile counters
    folded into one number an operator can watch)."""
    series = m.get(name)
    if not series:
        return None
    return sum(series.values())


def render_frame(m: dict, prev: dict | None, dt: float,
                 health: dict | None) -> str:
    def rate(name):
        cur = _val(m, name)
        if cur is None or prev is None or dt <= 0:
            return None
        d = counter_increase(cur, _val(prev, name))
        return None if d is None else d / dt

    def fmt(v, unit="", scale=1.0, digits=1):
        return "--" if v is None else f"{v * scale:,.{digits}f}{unit}"

    ev_rate = rate("heatmap_events_valid_total")
    lines = ["heatmap obs_top — " + time.strftime("%H:%M:%S"), ""]
    lines.append(
        f"  ingest    {fmt(ev_rate, ' ev/s', digits=0):>14}   "
        f"tiles {fmt(rate('heatmap_tiles_emitted_total'), '/s', digits=0)}")

    def hq(name, q, pv=prev):
        cur = m.get(name + "_bucket")
        if cur is None:
            return None
        pb = pv.get(name + "_bucket") if pv else None
        return hist_quantile(cur, pb, q)

    lines.append(
        f"  batch     p50 {fmt(hq('heatmap_batch_latency_seconds', .5), ' ms', 1e3):>10}   "
        f"p95 {fmt(hq('heatmap_batch_latency_seconds', .95), ' ms', 1e3)}")
    mean_b = {k: v for k, v in m.get("heatmap_event_age_seconds_bucket",
                                     {}).items() if 'bound="mean"' in k}
    mean_p = ({k: v for k, v in (prev or {}).get(
        "heatmap_event_age_seconds_bucket", {}).items()
        if 'bound="mean"' in k}) or None
    p50 = hist_quantile(mean_b, mean_p, 0.5) if mean_b else None
    p99 = hist_quantile(mean_b, mean_p, 0.99) if mean_b else None
    lines.append(
        f"  freshness p50 {fmt(p50, ' s', digits=2):>10}   "
        f"p99 {fmt(p99, ' s', digits=2)}   (event ts -> sink ack)")
    lines.append(
        f"  serve     {fmt(_val(m, 'heatmap_serve_freshness_seconds'), ' s', digits=2)} behind at last /tiles render")
    # async serve core (ISSUE 17, serve/evloop.py): which loop the
    # process runs (HEATMAP_SERVE_CORE), open event-loop connections,
    # the write backlog the loop is draining, and the loop-iteration
    # p99 — the row that says the single-thread core is keeping up.
    # Absent entirely on builds without the core gauge.
    crow = _serve_core_row(m, prev)
    if crow is not None:
        lines.append(crow)
    lines.append(
        f"  ring      depth {fmt(_val(m, 'heatmap_emit_ring_pending'), digits=0)}   "
        f"residency p50 {fmt(hq('heatmap_emit_ring_residency_seconds', .5), ' ms', 1e3)}")
    lines.append(
        f"  sink      queue {fmt(_val(m, 'heatmap_sink_queue_depth'), digits=0)}   "
        f"retries {fmt(_val(m, 'heatmap_sink_retries_total'), digits=0)}   "
        f"watermark age {fmt(_val(m, 'heatmap_watermark_age_seconds'), ' s', digits=1)}")
    # runtime introspection (obs.runtimeinfo): compile activity as a
    # DELTA between scrapes (a nonzero steady-state compile rate IS the
    # retrace incident), retraces + high-water marks as lifetime values
    compiles = _sum(m, "heatmap_compile_total")
    d_compiles = _sum_increase(m, prev, "heatmap_compile_total")
    retraces = _sum(m, "heatmap_retrace_after_warmup_total")
    lines.append(
        f"  compile   Δ {fmt(d_compiles, digits=0):>12}   "
        f"total {fmt(compiles, digits=0)}   "
        f"post-warmup retraces {fmt(retraces, digits=0)}")
    mem = _val(m, "heatmap_live_buffer_bytes")
    mem_wm = _val(m, "heatmap_live_buffer_watermark_bytes")
    dev_wm = m.get("heatmap_device_hbm_watermark_bytes")
    if dev_wm:  # device stats exist (TPU/GPU): show the hottest device
        mem_wm = max(dev_wm.values())
        in_use = m.get("heatmap_device_bytes_in_use")
        mem = max(in_use.values()) if in_use else mem
    lines.append(
        f"  memory    in-use {fmt(mem, ' MB', 1 / 1e6):>12}   "
        f"watermark {fmt(mem_wm, ' MB', 1 / 1e6)}   "
        f"ring slab {fmt(_val(m, 'heatmap_emit_ring_slab_bytes'), ' MB', 1 / 1e6)}")
    # adaptive micro-batching governor (stream/govern.py): the live
    # knob decisions, the most recent adjustment (reason recovered from
    # the adjust-counter labelset that grew since the last scrape), and
    # the frozen guardrail state
    gb = _val(m, "heatmap_govern_batch_rows")
    if gb is not None:
        last = _last_adjust(m, prev)
        frozen = (_val(m, "heatmap_govern_frozen") or 0) > 0
        age = _val(m, "heatmap_govern_last_adjust_age_seconds")
        lines.append(
            f"  governor  batch {fmt(gb, digits=0):>12}   "
            f"flush-K {fmt(_val(m, 'heatmap_govern_flush_k'), digits=0)}"
            f"   prefetch "
            f"{fmt(_val(m, 'heatmap_govern_prefetch'), digits=0)}   "
            f"last adjust {fmt(age, ' s ago', digits=0)}"
            + (f" ({last})" if last else "")
            + ("   FROZEN" if frozen else ""))
    # streaming inference engine (heatmap_tpu.infer, HEATMAP_REDUCERS
    # with kalman): tracked entities in the slot table, fold latency,
    # anomaly totals with the loudest reason named, and table churn —
    # absent entirely when the reducer set is count-only
    irow = _infer_row(m, prev)
    if irow is not None:
        lines.append(irow)
    # space-time history tier (query/history.py, HEATMAP_HIST_DIR):
    # chunks on disk, the wall-clock span they cover, the compaction
    # lag healthz gates on, and replica backfills — absent entirely
    # when the tier is off
    hist_chunks = _val(m, "heatmap_hist_chunks")
    if hist_chunks is not None:
        mm = _val(m, "heatmap_hist_digest_mismatch_total")
        lines.append(
            f"  history   chunks {fmt(hist_chunks, digits=0):>12}   "
            f"span {fmt(_val(m, 'heatmap_hist_covered_span_seconds'), ' h', 1 / 3600.0)}   "
            f"compaction lag {fmt(_val(m, 'heatmap_hist_compaction_lag_seconds'), ' s')}   "
            f"backfills {fmt(_val(m, 'heatmap_hist_backfill_total'), digits=0)}"
            + ("   MISMATCH" if mm else ""))
    # delivery observatory (obs.delivery, HEATMAP_DELIVERY=1): the
    # delivered-age quantiles to the subscriber socket (last interval),
    # the worst stage of the telescoping decomposition, slow-request
    # captures, and the worst write-stalled SSE subscriber — absent
    # entirely when no stamped frame has been delivered
    drow = _delivery_row(m, prev)
    if drow is not None:
        lines.append(drow)
    # integrity observatory (obs.audit, HEATMAP_AUDIT=1): per-boundary
    # conservation residuals (worst named), digest verification state,
    # and the newest verified seq — absent entirely when auditing is off
    aud = _audit_row(m)
    if aud is not None:
        lines.append(aud)
    # inference quality observatory (obs.quality, HEATMAP_QUALITY=1):
    # worst live forecast skill with its (grid, horizon) named, NIS
    # coverage vs the chi-square band, pending scorecards, and the
    # summed anomaly rate — absent entirely when the observatory is off
    qrow = _quality_row(m)
    if qrow is not None:
        lines.append(qrow)
    if health is not None:
        status = health.get("status", "?")
        bad = [k for k, c in health.get("checks", {}).items()
               if isinstance(c, dict) and not c.get("ok", True)]
        lines.append("")
        lines.append(f"  SLO       {status.upper()}"
                     + (f"   failing: {', '.join(bad)}" if bad else ""))
    return "\n".join(lines) + "\n"


def _serve_core(m: dict | None) -> str | None:
    """The serve loop this process runs: the ``core=`` label of the
    set ``heatmap_serve_core`` sample ("thread" | "epoll"); None when
    the family is absent (pre-ISSUE-17 build or no serve tier)."""
    for labels, v in ((m or {}).get("heatmap_serve_core") or {}).items():
        if v:
            return _label_of(labels, "core")
    return None


def _serve_core_row(m: dict, prev: dict | None) -> str | None:
    """The serve-core dashboard row, or None when no core gauge is
    exported."""
    core = _serve_core(m)
    if core is None:
        return None
    cur = m.get("heatmap_serve_loop_iteration_seconds_bucket")
    p99 = None
    if cur:
        pb = (prev or {}).get(
            "heatmap_serve_loop_iteration_seconds_bucket")
        p99 = hist_quantile(cur, pb, 0.99)

    def fmt(v, unit="", scale=1.0, digits=0):
        return "--" if v is None else f"{v * scale:,.{digits}f}{unit}"

    return (f"  core      {core:<12}"
            f"conns {fmt(_val(m, 'heatmap_serve_open_connections'))}   "
            f"backlog "
            f"{fmt(_val(m, 'heatmap_serve_write_backlog'))}   "
            f"loop p99 {fmt(p99, ' ms', 1e3, 1)}")


def _delivery_row(m: dict, prev: dict | None) -> str | None:
    """The delivery dashboard row, or None when no socket-bound
    delivered-age sample exists (HEATMAP_DELIVERY off, or no
    subscriber has received a stamped frame yet)."""
    def sock(d):
        return {k: v for k, v in
                (d or {}).get("heatmap_delivered_age_seconds_bucket",
                              {}).items() if 'bound="socket"' in k}

    cur = sock(m)
    if not cur:
        return None
    p50 = hist_quantile(cur, sock(prev) or None, 0.5)
    p99 = hist_quantile(cur, sock(prev) or None, 0.99)
    stages: dict = {}
    for labels, v in (m.get("heatmap_delivery_stage_seconds")
                      or {}).items():
        st = _label_of(labels, "stage")
        if st:
            stages[st] = v
    worst = max(stages, key=stages.get) if stages else None
    slow = _sum(m, "heatmap_serve_slow_requests_total")
    stall = _val(m, "heatmap_sse_write_stall_seconds")

    def fmt(v, unit="", digits=2):
        return "--" if v is None else f"{v:,.{digits}f}{unit}"

    return (f"  delivery  p50 {fmt(p50, ' s'):>10}   "
            f"p99 {fmt(p99, ' s')}"
            + (f"   worst {worst}" if worst else "")
            + f"   slow reqs {fmt(slow, digits=0)}"
            + (f"   stall {fmt(stall, ' s', 1)}" if stall else ""))


def _audit_row(m: dict) -> str | None:
    """The audit dashboard row, or None when heatmap_audit_* families
    are absent (HEATMAP_AUDIT off)."""
    res = m.get("heatmap_audit_residual")
    verified = _val(m, "heatmap_audit_digests_verified_total")
    mism = _val(m, "heatmap_audit_digest_mismatch_total")
    if res is None and verified is None and mism is None:
        return None
    worst_b, worst_v = None, 0.0
    for labels, v in (res or {}).items():
        if abs(v) >= abs(worst_v) and (worst_b is None or v):
            worst_b, worst_v = _label_of(labels, "boundary"), v
    last_seq = _val(m, "heatmap_audit_last_verified_seq")

    def fmt(v, digits=0):
        return "--" if v is None else f"{v:,.{digits}f}"

    row = (f"  audit     residual {fmt(worst_v):>12}"
           + (f" ({worst_b})" if worst_b and worst_v else "")
           + f"   digests ok {fmt(verified)} / bad {fmt(mism)}"
           + f"   last seq {fmt(last_seq)}")
    if mism:
        row += "   MISMATCH"
    return row


def _infer_row(m: dict, prev: dict | None) -> str | None:
    """The streaming-inference dashboard row, or None when the
    heatmap_infer_* families are absent (reducer set is count-only —
    the engine only registers with kalman in HEATMAP_REDUCERS)."""
    ents = _val(m, "heatmap_infer_entities")
    if ents is None:
        return None
    cur = m.get("heatmap_infer_fold_seconds_bucket")
    p50 = None
    if cur:
        pb = (prev or {}).get("heatmap_infer_fold_seconds_bucket")
        p50 = hist_quantile(cur, pb, 0.5)
    anom: dict = {}
    for labels, v in (m.get("heatmap_infer_anomalies_total")
                      or {}).items():
        r = _label_of(labels, "reason")
        if r is not None:
            anom[r] = anom.get(r, 0.0) + v
    loudest = (max(anom, key=anom.get)
               if anom and max(anom.values()) > 0 else None)
    churn = _label_sums(m, "heatmap_infer_entity_events_total", "op")
    evicted = churn.get("evicted_ttl", 0.0) + churn.get("evicted_lru", 0.0)
    reseeds = (churn.get("reseed_handoff", 0.0)
               + churn.get("reseed_teleport", 0.0))

    def fmt(v, unit="", scale=1.0, digits=0):
        return "--" if v is None else f"{v * scale:,.{digits}f}{unit}"

    return (f"  infer     entities {fmt(ents):>10}   "
            f"fold p50 {fmt(p50, ' ms', 1e3, 1)}   "
            f"anomalies {fmt(sum(anom.values()) if anom else None)}"
            + (f" (worst {loudest})" if loudest else "")
            + f"   evicted {fmt(evicted)}   reseeds {fmt(reseeds)}")


def _quality_row(m: dict) -> str | None:
    """The inference-quality dashboard row, or None when the
    heatmap_quality_* families are absent (HEATMAP_QUALITY is off —
    the observatory registers nothing when disabled)."""
    skills = m.get("heatmap_quality_forecast_skill") or {}
    cov = _val(m, "heatmap_quality_nis_coverage")
    if not skills and cov is None:
        return None
    worst_k, worst_v = None, None
    for labels, v in skills.items():
        if worst_v is None or v < worst_v:
            g = _label_of(labels, "grid") or "?"
            h = _label_of(labels, "h") or "?"
            worst_k, worst_v = f"{g}|{h}s", v
    band = _val(m, "heatmap_quality_nis_band_error")
    pend = _val(m, "heatmap_quality_pending_scorecards")
    rates = _label_sums(m, "heatmap_quality_anomaly_rate", "reason")

    def fmt(v, unit="", digits=2):
        return "--" if v is None else f"{v:,.{digits}f}{unit}"

    return (f"  quality   skill {fmt(worst_v):>8}"
            + (f" ({worst_k})" if worst_k else "")
            + f"   nis cov {fmt(cov)}"
            + (f" (band err {fmt(band)})" if band else "")
            + f"   pending {fmt(pend, digits=0)}   "
            f"anom/s {fmt(sum(rates.values()) if rates else None)}")


def _label_sums(m: dict | None, name: str, key: str) -> dict:
    """{label_value: summed value} for one family keyed by one label
    (e.g. the per-``op`` entity lifecycle counters folded across any
    other labels present)."""
    out: dict = {}
    for labels, v in ((m or {}).get(name) or {}).items():
        lv = _label_of(labels, key)
        if lv is not None:
            out[lv] = out.get(lv, 0.0) + v
    return out


def _last_adjust(m: dict, prev: dict | None) -> str | None:
    """The governor adjust-counter labelset that grew since the last
    scrape, rendered ``dir/reason`` — the most recent adjustment's
    direction and control-law reason (None on the first frame or a
    quiet interval)."""
    cur = m.get("heatmap_govern_adjust_total") or {}
    was = (prev or {}).get("heatmap_govern_adjust_total") or {}
    for labels, v in cur.items():
        if (counter_increase(v, was.get(labels, 0.0)) or 0.0) > 0:
            d = _label_of(labels, "dir") or "?"
            r = _label_of(labels, "reason") or "?"
            return f"{d}/{r}"
    return None


def _label_of(labels_str: str, key: str) -> str | None:
    """One label's (unescaped-enough) value out of a raw ``{...}``
    block; None when absent."""
    for part in labels_str.strip("{}").split(","):
        k, _, v = part.partition("=")
        if k.strip() == key:
            return v.strip().strip('"')
    return None


def _by_proc(m: dict | None, name: str, skip_shard: bool = False) -> dict:
    """{proc_tag: value} for one family's ``proc=``-labeled samples.
    ``skip_shard`` drops ``shard=``-labeled samples (the partitioned
    mesh's per-device governor children): a dict keyed by proc alone
    would otherwise keep one ARBITRARY shard's value per member —
    masking, e.g., a frozen shard behind an active one."""
    out: dict = {}
    for labels, v in ((m or {}).get(name) or {}).items():
        p = _label_of(labels, "proc")
        if p is None:
            continue
        if skip_shard and _label_of(labels, "shard") is not None:
            continue
        out[p] = v
    return out


def _by_proc_shard(m: dict | None, name: str) -> dict:
    """{(proc_tag, shard): value} for one family's ``proc=`` +
    ``shard=``-labeled samples (the partitioned-mesh per-device
    families; proc falls back to "" on a direct single-runtime
    scrape)."""
    out: dict = {}
    for labels, v in ((m or {}).get(name) or {}).items():
        s = _label_of(labels, "shard")
        if s is not None:
            out[(_label_of(labels, "proc") or "", s)] = v
    return out


def _by_proc_label_sum(m: dict | None, name: str, key: str,
                       wants: tuple) -> dict:
    """{proc_tag: summed value} over one family's samples whose
    ``key`` label is in ``wants`` (e.g. the eviction ops of the entity
    lifecycle counter folded into one per-member column)."""
    out: dict = {}
    for labels, v in ((m or {}).get(name) or {}).items():
        p = _label_of(labels, "proc")
        if p is not None and _label_of(labels, key) in wants:
            out[p] = out.get(p, 0.0) + v
    return out


def _by_proc_sum(m: dict | None, name: str) -> dict:
    """{proc_tag: summed value} for a family whose samples carry extra
    labels besides ``proc`` (e.g. per-endpoint serve counters)."""
    out: dict = {}
    for labels, v in ((m or {}).get(name) or {}).items():
        p = _label_of(labels, "proc")
        if p is not None:
            out[p] = out.get(p, 0.0) + v
    return out


def render_fleet_frame(m: dict, prev: dict | None, dt: float,
                       health: dict | None) -> str:
    """The fleet observatory view: one row per member off the
    federated /fleet/metrics exposition (obs.fleet)."""
    def fmt(v, unit="", scale=1.0, digits=1):
        return "--" if v is None else f"{v * scale:,.{digits}f}{unit}"

    roles: dict = {}
    up: dict = {}
    for labels, v in (m.get("heatmap_fleet_member_up") or {}).items():
        tag = _label_of(labels, "proc")
        if tag is None:
            continue
        up[tag] = v
        roles[tag] = _label_of(labels, "role") or "?"
    ages = _by_proc(m, "heatmap_fleet_member_age_seconds")
    p50s = _by_proc(m, "heatmap_fleet_member_event_age_p50_s")
    mem_wm = _by_proc(m, "heatmap_live_buffer_watermark_bytes")
    valid = _by_proc(m, "heatmap_events_valid_total")
    valid_prev = _by_proc(prev, "heatmap_events_valid_total")
    rate_gauge = _by_proc(m, "heatmap_events_per_sec")
    def member_rate(tag):
        # rate: delta of the member's valid-event counter between
        # scrapes; first frame falls back to the member's own lifetime
        # events_per_sec gauge
        if dt > 0 and tag in valid and tag in valid_prev:
            d = counter_increase(valid[tag], valid_prev[tag])
            return None if d is None else d / dt
        return rate_gauge.get(tag)

    lines = ["heatmap obs_top --fleet — " + time.strftime("%H:%M:%S"), ""]
    lines.append(
        f"  members {fmt(_val(m, 'heatmap_fleet_members'), digits=0)}   "
        f"stale {fmt(_val(m, 'heatmap_fleet_stale_members'), digits=0)}   "
        f"fleet event-age p50 "
        f"{fmt(_val(m, 'heatmap_fleet_event_age_p50_s'), ' s', digits=2)}"
        f"   p99 "
        f"{fmt(_val(m, 'heatmap_fleet_event_age_p99_s'), ' s', digits=2)}")
    lines.append("")
    lines.append(f"  {'member':<14}{'role':<12}{'rate':>12}"
                 f"{'age p50':>10}{'mem wm':>10}{'seen':>8}  state")
    for tag in sorted(up):
        lines.append(
            f"  {tag:<14}{roles.get(tag, '?'):<12}"
            f"{fmt(member_rate(tag), ' ev/s', digits=0):>12}"
            f"{fmt(p50s.get(tag), ' s', digits=2):>10}"
            f"{fmt(mem_wm.get(tag), ' MB', 1 / 1e6, 0):>10}"
            f"{fmt(ages.get(tag), ' s', digits=0):>8}"
            f"  {'up' if up.get(tag) else 'STALE/DOWN'}")
    # sharded runtime fleet (stream/shardmap.py): one row per shard off
    # the shard gauges each shard member's snapshot carries, plus the
    # imbalance ratio that makes a skewed H3 partition visible at a
    # glance — owned-cell share is the fraction of the full stream's
    # rows this shard's cell space owns (valid / (valid + out-of-shard))
    shard_idx = _by_proc(m, "heatmap_shard_index")
    if shard_idx:
        foreign = _by_proc(m, "heatmap_events_out_of_shard_total")
        lines.append("")
        lines.append(f"  {'shard':<14}{'idx':>4}{'own-cell %':>12}"
                     f"{'rate':>14}{'age p50':>10}")
        rates = {}
        for tag in sorted(shard_idx):
            own = None
            v, f = valid.get(tag), foreign.get(tag)
            if v is not None and f is not None and v + f > 0:
                own = v / (v + f)
            rates[tag] = member_rate(tag)
            lines.append(
                f"  {tag:<14}{fmt(shard_idx[tag], digits=0):>4}"
                f"{fmt(own, ' %', 100.0):>12}"
                f"{fmt(rates[tag], ' ev/s', digits=0):>14}"
                f"{fmt(p50s.get(tag), ' s', digits=2):>10}")
        # a wedged shard reports rate 0.0 — it must stay IN the
        # imbalance/aggregate math (a dead shard is the skew this
        # readout exists to expose), only unknown rates drop out
        known = [r for r in rates.values() if r is not None]
        imbalance = (max(known) / (sum(known) / len(known))
                     if len(known) >= 2 and sum(known) > 0 else None)
        lines.append(f"  imbalance max/mean "
                     f"{fmt(imbalance, 'x', digits=2)}   aggregate "
                     f"{fmt(sum(known) if known else None, ' ev/s', digits=0)}")
    # partitioned-mesh shards (parallel.PartitionedAggregator):
    # one row per (member, device) off the heatmap_mesh_* families —
    # owned-cell share (this shard's rows over its member's total, the
    # PR 7 imbalance math per device), ring depth, device->host pulls,
    # and the shard's governor knobs when per-shard governing is on
    mesh_rows = _by_proc_shard(m, "heatmap_mesh_rows_total")
    if mesh_rows:
        mesh_pulls = _by_proc_shard(m, "heatmap_mesh_pulls_total")
        mesh_ring = _by_proc_shard(m, "heatmap_mesh_ring_pending")
        gov_b = _by_proc_shard(m, "heatmap_govern_batch_rows")
        gov_k = _by_proc_shard(m, "heatmap_govern_flush_k")
        gov_f = _by_proc_shard(m, "heatmap_govern_frozen")
        totals: dict = {}
        for (tag, _s), v in mesh_rows.items():
            totals[tag] = totals.get(tag, 0.0) + v
        lines.append("")
        lines.append(f"  {'mesh shard':<14}{'dev':>4}{'own-cell %':>12}"
                     f"{'rows':>14}{'ring':>6}{'pulls':>8}"
                     f"{'gov batch':>11}{'flush-K':>9}")
        def _shard_key(k):
            tag, s = k
            # numeric labels sort as numbers (shard "10" after "9")
            return ((tag, 0, int(s), "") if s.isdigit()
                    else (tag, 1, 0, s))

        for (tag, s) in sorted(mesh_rows, key=_shard_key):
            share = (mesh_rows[(tag, s)] / totals[tag]
                     if totals.get(tag) else None)
            lines.append(
                f"  {tag:<14}{s:>4}"
                f"{fmt(share, ' %', 100.0):>12}"
                f"{fmt(mesh_rows[(tag, s)], digits=0):>14}"
                f"{fmt(mesh_ring.get((tag, s)), digits=0):>6}"
                f"{fmt(mesh_pulls.get((tag, s)), digits=0):>8}"
                f"{fmt(gov_b.get((tag, s)), digits=0):>11}"
                f"{fmt(gov_k.get((tag, s)), digits=0):>9}"
                + ("  FROZEN" if gov_f.get((tag, s)) else ""))
        # the PR 7 imbalance readout, per device: a skewed H3 partition
        # (or a wedged device at 0 rows) is visible at a glance
        vals = list(mesh_rows.values())
        if len(vals) >= 2 and sum(vals) > 0:
            imb = max(vals) / (sum(vals) / len(vals))
            lines.append(f"  mesh imbalance max/mean "
                         f"{fmt(imb, 'x', digits=2)}")
    # per-member adaptive governors (stream/govern.py): each shard
    # governs independently, so skewed load shows up as DIFFERENT
    # converged batch sizes — this table makes that visible, plus the
    # frozen guardrail state per member.  Mesh members' per-device
    # governors (shard=-labeled) live in the mesh table above; keyed
    # by proc alone they would collapse to one arbitrary shard here.
    gov_batch = _by_proc(m, "heatmap_govern_batch_rows",
                         skip_shard=True)
    if gov_batch:
        gov_flush = _by_proc(m, "heatmap_govern_flush_k",
                             skip_shard=True)
        gov_pre = _by_proc(m, "heatmap_govern_prefetch",
                           skip_shard=True)
        gov_frozen = _by_proc(m, "heatmap_govern_frozen",
                              skip_shard=True)
        gov_age = _by_proc(m, "heatmap_govern_last_adjust_age_seconds",
                           skip_shard=True)
        lines.append("")
        lines.append(f"  {'governor':<14}{'batch':>9}{'flush-K':>9}"
                     f"{'prefetch':>10}{'adjusted':>10}  state")
        for tag in sorted(gov_batch):
            lines.append(
                f"  {tag:<14}{fmt(gov_batch[tag], digits=0):>9}"
                f"{fmt(gov_flush.get(tag), digits=0):>9}"
                f"{fmt(gov_pre.get(tag), digits=0):>10}"
                f"{fmt(gov_age.get(tag), ' s ago', digits=0):>10}"
                f"  {'FROZEN' if gov_frozen.get(tag) else 'active'}")
    # replicated serve fleet (query.repl): one row per serve-role
    # member — replication seq lag, open SSE clients, and the 304
    # ratio that says the ETag tier is actually absorbing polls
    seq_lag = _by_proc(m, "heatmap_repl_seq_lag")
    serve_tags = sorted(set(t for t, r in roles.items() if r == "serve")
                        | set(seq_lag))
    if serve_tags:
        sse = _by_proc(m, "heatmap_serve_sse_clients")
        n304 = _by_proc_sum(m, "heatmap_serve_304_total")
        renders = _by_proc_sum(m, "heatmap_serve_renders_total")
        # per-member serve core (ISSUE 17): the core= label of each
        # member's set heatmap_serve_core sample
        cores: dict = {}
        for labels, v in ((m or {}).get("heatmap_serve_core")
                          or {}).items():
            p = _label_of(labels, "proc")
            if p is not None and v:
                cores[p] = _label_of(labels, "core") or "?"
        lines.append("")
        lines.append(f"  {'serve':<14}{'role':<8}{'core':>8}"
                     f"{'seq lag':>9}{'sse':>6}{'304 %':>9}  state")
        for tag in serve_tags:
            r304 = None
            total = n304.get(tag, 0.0) + renders.get(tag, 0.0)
            if total > 0:
                r304 = n304.get(tag, 0.0) / total
            lines.append(
                f"  {tag:<14}{roles.get(tag, '?'):<8}"
                f"{cores.get(tag, '--'):>8}"
                f"{fmt(seq_lag.get(tag), digits=0):>9}"
                f"{fmt(sse.get(tag), digits=0):>6}"
                f"{fmt(r304, ' %', 100.0):>9}"
                f"  {'up' if up.get(tag) else 'STALE/DOWN'}")
        lags = [v for v in seq_lag.values() if v is not None]
        if lags:
            lines.append(f"  repl max seq lag {fmt(max(lags), digits=0)}"
                         f"   replicas {len(lags)}")
        # serve-tier wire path (ISSUE 14): per-worker negotiated-format
        # mix, wire-vs-rendered byte rates, admission sheds, and the
        # SSE fan-out send-queue high-water — the row that says the
        # binary path / coalesced fan-out is actually carrying load
        wf_all = _by_proc_sum(m, "heatmap_serve_wire_format_total")
        wf_bin: dict = {}
        for labels, v in ((m or {}).get(
                "heatmap_serve_wire_format_total") or {}).items():
            p = _label_of(labels, "proc")
            if p is not None and _label_of(labels, "fmt") == "bin":
                wf_bin[p] = wf_bin.get(p, 0.0) + v
        sent = _by_proc_sum(m, "heatmap_serve_sent_bytes_total")
        sent_prev = _by_proc_sum(prev, "heatmap_serve_sent_bytes_total")
        rend = _by_proc_sum(m, "heatmap_serve_rendered_bytes_total")
        rend_prev = _by_proc_sum(prev,
                                 "heatmap_serve_rendered_bytes_total")
        shed = _by_proc_sum(m, "heatmap_serve_shed_total")
        qhw = _by_proc(m, "heatmap_sse_queue_highwater")
        if any(wf_all.get(t) for t in serve_tags):
            def _rate(cur: dict, prv: dict, tag: str):
                if prev is None or dt <= 0 or tag not in cur:
                    return None
                d = counter_increase(cur[tag], prv.get(tag, 0.0))
                return None if d is None else d / dt
            lines.append("")
            lines.append(f"  {'serve wire':<14}{'clients':>8}"
                         f"{'bin %':>8}{'wire B/s':>12}"
                         f"{'rend B/s':>12}{'shed':>7}{'q hw':>6}")
            for tag in serve_tags:
                if not wf_all.get(tag):
                    continue
                binfrac = (wf_bin.get(tag, 0.0) / wf_all[tag]
                           if wf_all.get(tag) else None)
                lines.append(
                    f"  {tag:<14}{fmt(sse.get(tag), digits=0):>8}"
                    f"{fmt(binfrac, ' %', 100.0, 0):>8}"
                    f"{fmt(_rate(sent, sent_prev, tag), digits=0):>12}"
                    f"{fmt(_rate(rend, rend_prev, tag), digits=0):>12}"
                    f"{fmt(shed.get(tag), digits=0):>7}"
                    f"{fmt(qhw.get(tag), digits=0):>6}")
    # space-time history tier (query/history.py): one row per member
    # carrying history state — chunks, covered span, compaction lag,
    # digest mismatches (writer/compactor members) and cold-start
    # backfills (replicas).  Absent without HEATMAP_HIST_DIR anywhere
    # on the channel.
    h_chunks = _by_proc(m, "heatmap_hist_chunks")
    h_bf = _by_proc(m, "heatmap_hist_backfill_total")
    h_tags = sorted(set(h_chunks)
                    | set(t for t, v in h_bf.items() if v))
    if h_tags:
        h_span = _by_proc(m, "heatmap_hist_covered_span_seconds")
        h_lag = _by_proc(m, "heatmap_hist_compaction_lag_seconds")
        h_mm = _by_proc(m, "heatmap_hist_digest_mismatch_total")
        lines.append("")
        lines.append(f"  {'history':<14}{'chunks':>9}{'span':>10}"
                     f"{'lag':>9}{'backfills':>11}")
        for tag in h_tags:
            lines.append(
                f"  {tag:<14}{fmt(h_chunks.get(tag), digits=0):>9}"
                f"{fmt(h_span.get(tag), ' h', 1 / 3600.0):>10}"
                f"{fmt(h_lag.get(tag), ' s'):>9}"
                f"{fmt(h_bf.get(tag), digits=0):>11}"
                + ("  MISMATCH" if h_mm.get(tag) else ""))
        lags = [v for v in h_lag.values() if v is not None]
        if lags:
            lines.append(f"  hist max compaction lag "
                         f"{fmt(max(lags), ' s')}")
    # delivery observatory (obs.delivery, HEATMAP_DELIVERY=1): one row
    # per replica delivering stamped frames — delivered-age p50/p99 to
    # the subscriber socket (the member-published delivery block), the
    # worst stage of its telescoping decomposition, slow-request
    # captures, and the worst write-stalled subscriber.  Absent until
    # a stamped frame reaches a subscriber anywhere on the channel.
    d_p50 = _by_proc(m, "heatmap_fleet_member_delivered_age_p50_s")
    d_tags = sorted(d_p50)
    if d_tags:
        d_p99 = _by_proc(m, "heatmap_fleet_member_delivered_age_p99_s")
        d_stall = _by_proc(m, "heatmap_sse_write_stall_seconds")
        d_slow = _by_proc_sum(m, "heatmap_serve_slow_requests_total")
        d_stage: dict = {}
        for labels, v in (m.get("heatmap_delivery_stage_seconds")
                          or {}).items():
            p = _label_of(labels, "proc")
            st = _label_of(labels, "stage")
            if p is None or st is None:
                continue
            cur = d_stage.get(p)
            if cur is None or v > cur[1]:
                d_stage[p] = (st, v)
        lines.append("")
        lines.append(f"  {'delivery':<14}{'p50':>9}{'p99':>9}  "
                     f"{'worst stage':<14}{'slow':>6}{'stall':>8}")
        for tag in d_tags:
            st, _v = d_stage.get(tag, (None, None))
            lines.append(
                f"  {tag:<14}{fmt(d_p50.get(tag), ' s', digits=2):>9}"
                f"{fmt(d_p99.get(tag), ' s', digits=2):>9}  "
                f"{(st or '-'):<14}"
                f"{fmt(d_slow.get(tag), digits=0):>6}"
                f"{fmt(d_stall.get(tag), ' s', digits=1):>8}")
        worst_tag = max(d_tags, key=lambda t: d_p50.get(t) or 0.0)
        lines.append(f"  delivery worst replica {worst_tag} "
                     f"(p50 {fmt(d_p50.get(worst_tag), ' s', digits=2)})")
    # integrity observatory (obs.audit): one row per audited member —
    # worst conservation residual (boundary named), digests verified /
    # mismatched, last verified seq (replicas).  Absent without
    # HEATMAP_AUDIT=1 anywhere on the channel.
    aud_res: dict = {}
    for labels, v in (m.get("heatmap_audit_residual") or {}).items():
        p = _label_of(labels, "proc")
        b = _label_of(labels, "boundary")
        if p is None:
            continue
        cur = aud_res.get(p)
        if cur is None or abs(v) > abs(cur[1]):
            aud_res[p] = (b, v)
    aud_mm = _by_proc(m, "heatmap_audit_digest_mismatch_total")
    aud_ok = _by_proc(m, "heatmap_audit_digests_verified_total")
    aud_seq = _by_proc(m, "heatmap_audit_last_verified_seq")
    aud_tags = sorted(set(aud_res) | set(aud_mm) | set(aud_ok))
    if aud_tags:
        lines.append("")
        lines.append(f"  {'audit':<14}{'residual':>10}  "
                     f"{'boundary':<14}{'ok':>8}{'bad':>6}"
                     f"{'last seq':>10}")
        for tag in aud_tags:
            b, v = aud_res.get(tag, (None, None))
            lines.append(
                f"  {tag:<14}{fmt(v, digits=0):>10}  "
                f"{(b if b and v else '-'):<14}"
                f"{fmt(aud_ok.get(tag), digits=0):>8}"
                f"{fmt(aud_mm.get(tag), digits=0):>6}"
                f"{fmt(aud_seq.get(tag), digits=0):>10}"
                + ("  MISMATCH" if aud_mm.get(tag) else ""))
    # continuous-query engine (query.continuous): one row per member
    # carrying standing queries — registered count, match/eval totals
    # and rate, eval lag (the HEATMAP_SLO_CQ_LAG_S budget), index
    # size.  Absent until something registers a query on the channel.
    cq_reg = _by_proc(m, "heatmap_cq_registered")
    cq_tags = sorted(t for t, v in cq_reg.items() if v)
    if cq_tags:
        cq_match = _by_proc(m, "heatmap_cq_matches_total")
        cq_match_prev = _by_proc(prev, "heatmap_cq_matches_total")
        cq_evals = _by_proc(m, "heatmap_cq_evaluations_total")
        cq_lag = _by_proc(m, "heatmap_cq_eval_lag_seconds")
        cq_idx = _by_proc(m, "heatmap_cq_index_cells")
        lines.append("")
        lines.append(f"  {'cq':<14}{'queries':>9}{'matches':>10}"
                     f"{'match/s':>9}{'evals':>11}{'lag':>8}"
                     f"{'index':>8}")
        for tag in cq_tags:
            mrate = None
            if dt > 0 and tag in cq_match and tag in cq_match_prev:
                d = counter_increase(cq_match[tag], cq_match_prev[tag])
                mrate = None if d is None else d / dt
            lines.append(
                f"  {tag:<14}{fmt(cq_reg.get(tag), digits=0):>9}"
                f"{fmt(cq_match.get(tag), digits=0):>10}"
                f"{fmt(mrate, digits=1):>9}"
                f"{fmt(cq_evals.get(tag), digits=0):>11}"
                f"{fmt(cq_lag.get(tag), ' s', digits=2):>8}"
                f"{fmt(cq_idx.get(tag), digits=0):>8}")
        lines.append(f"  cq total registered "
                     f"{fmt(sum(cq_reg.values()), digits=0)} across "
                     f"{len(cq_tags)} member(s)")
    # streaming inference engine (heatmap_tpu.infer): one row per
    # member running the kalman reducer — tracked entities in its
    # per-shard slot table, table churn (seeds/evictions/reseeds), and
    # reason-tagged anomaly totals + rate.  Entity tables are per
    # runtime shard (they follow the H3 partition), so a skewed
    # partition shows up as skewed entity counts here.  Absent when no
    # member has kalman in HEATMAP_REDUCERS.
    inf_ents = _by_proc(m, "heatmap_infer_entities")
    if inf_ents:
        inf_seed = _by_proc_label_sum(
            m, "heatmap_infer_entity_events_total", "op", ("seeded",))
        inf_evict = _by_proc_label_sum(
            m, "heatmap_infer_entity_events_total", "op",
            ("evicted_ttl", "evicted_lru"))
        inf_reseed = _by_proc_label_sum(
            m, "heatmap_infer_entity_events_total", "op",
            ("reseed_handoff", "reseed_teleport"))
        inf_anom = _by_proc_sum(m, "heatmap_infer_anomalies_total")
        inf_anom_prev = _by_proc_sum(prev,
                                     "heatmap_infer_anomalies_total")
        lines.append("")
        lines.append(f"  {'infer':<14}{'entities':>10}{'seeded':>10}"
                     f"{'evicted':>9}{'reseeds':>9}{'anomalies':>11}"
                     f"{'anom/s':>8}")
        for tag in sorted(inf_ents):
            arate = None
            if dt > 0 and tag in inf_anom and tag in inf_anom_prev:
                d = counter_increase(inf_anom[tag],
                                     inf_anom_prev[tag])
                arate = None if d is None else d / dt
            lines.append(
                f"  {tag:<14}{fmt(inf_ents[tag], digits=0):>10}"
                f"{fmt(inf_seed.get(tag), digits=0):>10}"
                f"{fmt(inf_evict.get(tag), digits=0):>9}"
                f"{fmt(inf_reseed.get(tag), digits=0):>9}"
                f"{fmt(inf_anom.get(tag), digits=0):>11}"
                f"{fmt(arate, digits=2):>8}")
        lines.append(f"  infer tracked entities "
                     f"{fmt(sum(inf_ents.values()), digits=0)} across "
                     f"{len(inf_ents)} member(s)")
    # inference quality observatory (obs.quality): one row per member
    # running with HEATMAP_QUALITY=1 — worst live forecast skill with
    # its (grid, horizon), NIS coverage vs the chi-square band,
    # scorecard ledger, pending cards.  The total line names the worst
    # shard (largest band error, then lowest skill) — the same ranking
    # /fleet/quality serves.  Absent when no member has quality on.
    q_cov = _by_proc(m, "heatmap_quality_nis_coverage")
    if q_cov:
        q_skill: dict = {}
        for labels, v in (m.get("heatmap_quality_forecast_skill")
                          or {}).items():
            p = _label_of(labels, "proc")
            if p is None:
                continue
            if p not in q_skill or v < q_skill[p][0]:
                q_skill[p] = (v, f"{_label_of(labels, 'grid') or '?'}|"
                              f"{_label_of(labels, 'h') or '?'}s")
        q_band = _by_proc(m, "heatmap_quality_nis_band_error")
        q_pend = _by_proc(m, "heatmap_quality_pending_scorecards")
        q_scored = _by_proc_label_sum(
            m, "heatmap_quality_scorecards_total", "outcome", ("scored",))
        q_exp = _by_proc_label_sum(
            m, "heatmap_quality_scorecards_total", "outcome",
            ("expired_unscorable",))
        lines.append("")
        lines.append(f"  {'quality':<14}{'skill':>8}  {'grid|h':<12}"
                     f"{'nis cov':>9}{'band err':>10}{'scored':>9}"
                     f"{'expired':>9}{'pending':>9}")
        for tag in sorted(q_cov):
            sv, sk = q_skill.get(tag, (None, None))
            lines.append(
                f"  {tag:<14}{fmt(sv, digits=2):>8}  "
                f"{(sk or '-'):<12}"
                f"{fmt(q_cov.get(tag), digits=2):>9}"
                f"{fmt(q_band.get(tag), digits=3):>10}"
                f"{fmt(q_scored.get(tag), digits=0):>9}"
                f"{fmt(q_exp.get(tag), digits=0):>9}"
                f"{fmt(q_pend.get(tag), digits=0):>9}")

        def _rank(tag):
            sv = q_skill.get(tag, (None,))[0]
            return (-(q_band.get(tag) or 0.0),
                    sv if sv is not None else float("inf"))

        worst = min(sorted(q_cov), key=_rank)
        lines.append(f"  quality worst shard {worst} "
                     f"(band err {fmt(q_band.get(worst), digits=3)}, "
                     f"skill {fmt(q_skill.get(worst, (None,))[0], digits=2)})"
                     f" across {len(q_cov)} member(s)")
    if health is not None:
        status = health.get("status", "?")
        bad = [k for k, c in health.get("checks", {}).items()
               if isinstance(c, dict) and not c.get("ok", True)]
        lines.append("")
        lines.append(f"  FLEET SLO {status.upper()}"
                     + (f"   failing: {', '.join(bad)}" if bad else ""))
        ep = health.get("episode")
        if ep:
            lines.append(f"  episode   {ep.get('episode_id', '?')} from "
                         f"{ep.get('origin', '?')}: {ep.get('reason', '')}")
    return "\n".join(lines) + "\n"


# ------------------------------------------------------------ time machine
# Historical rendering off the obs.tsdb on-disk series (--since /
# --replay): everything below reads the retained blocks, never HTTP.

_SPARK_BLOCKS = "▁▂▃▄▅▆▇█"

# (row label, family base name, "rate" | "gauge"): the headline rows
# the historical view sparklines.  Counters render as per-slot
# increases (reset-aware); gauges as the slot's last value.  Rows whose
# family never appears in the window are dropped, so a build without
# e.g. the repl tier just shows fewer rows.
_HISTORY_ROWS = (
    ("ingest ev/s", "heatmap_events_valid_total", "rate"),
    ("tiles/s", "heatmap_tiles_emitted_total", "rate"),
    ("ring depth", "heatmap_emit_ring_pending", "gauge"),
    ("sink queue", "heatmap_sink_queue_depth", "gauge"),
    ("repl lag s", "heatmap_repl_lag_seconds", "gauge"),
    ("shed/s", "heatmap_serve_shed_total", "rate"),
    ("retraces", "heatmap_retrace_after_warmup_total", "rate"),
)

_HZ_CHARS = {0: ".", 1: "▲", 2: "█"}  # ok / degraded / down


def _tsdb_import():
    """obs.tsdb, with a repo-root sys.path fallback so the tool also
    runs as a plain script from a checkout."""
    try:
        from heatmap_tpu.obs import tsdb as tsdbmod
    except ImportError:
        sys.path.insert(0, os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        from heatmap_tpu.obs import tsdb as tsdbmod
    return tsdbmod


def sparkline(values: list, width: int) -> str:
    """``values`` (None = no sample in that slot) as a fixed-width
    block-character strip.  A flat non-zero series renders mid-scale;
    all-None renders as spaces."""
    known = [v for v in values if v is not None]
    if not known:
        return " " * width
    lo, hi = min(known), max(known)
    span = hi - lo
    out = []
    for v in values[:width]:
        if v is None:
            out.append(" ")
        elif span <= 0:
            out.append(_SPARK_BLOCKS[4] if hi else _SPARK_BLOCKS[0])
        else:
            idx = int((v - lo) / span * (len(_SPARK_BLOCKS) - 1))
            out.append(_SPARK_BLOCKS[max(0, min(idx,
                                                len(_SPARK_BLOCKS) - 1))])
    return "".join(out).ljust(width)


def _slot(values_ts: list, t0: float, t1: float, width: int,
          mode: str) -> list:
    """Resample [(t, v)] into ``width`` equal time slots over
    [t0, t1]: rate-mode sums per-slot increases / slot seconds,
    gauge-mode keeps the slot's last value; empty slots are None."""
    if t1 <= t0 or width <= 0:
        return []
    step = (t1 - t0) / width
    slots: list = [None] * width

    def idx(t):
        return max(0, min(width - 1, int((t - t0) / step)))

    if mode == "rate":
        prev = None
        for t, v in values_ts:
            if prev is not None:
                d = v if v < prev else v - prev  # reset-aware
                if d > 0 and t0 <= t <= t1 + step:
                    i = idx(t)
                    slots[i] = (slots[i] or 0.0) + d
            prev = v
        return [None if s is None else s / step for s in slots]
    for t, v in values_ts:
        if t0 <= t <= t1 + step:
            slots[idx(t)] = v
    return slots


def _family_points(series: dict, name: str) -> list:
    """All samples of one family merged across labelsets, time-sorted —
    multi-labelset counters (e.g. per-endpoint sheds) fold into one
    strip per row."""
    merged: dict = {}
    for key, pts in series.items():
        if key.split("{", 1)[0] != name:
            continue
        for t, v in pts:
            merged[t] = merged.get(t, 0.0) + v
    return sorted(merged.items())


def _fmt_clock(t: float) -> str:
    return time.strftime("%H:%M:%S", time.localtime(t))


def render_history(tsdbmod, dir_path: str, tag: str, since_s: float,
                   until: float | None = None, width: int = 48) -> str:
    """One time-machine frame for one member: sparkline rows, healthz
    strip, SLO budget ledger, timeline tail.  ``until`` defaults to the
    newest retained sample so a canned directory replays identically
    whenever it is read."""
    reader = tsdbmod.TsdbReader(dir_path)
    series = reader.series(tag)
    hz = reader.healthz(tag)
    newest = 0.0
    for pts in series.values():
        if pts:
            newest = max(newest, pts[-1][0])
    if hz:
        newest = max(newest, hz[-1][0])
    t1 = until if until is not None else newest
    if t1 <= 0:
        return (f"heatmap obs_top --since — member {tag}: "
                f"no retained samples\n")
    t0 = t1 - since_s
    lines = [f"heatmap obs_top --since — member {tag}   "
             f"window {_fmt_clock(t0)} → {_fmt_clock(t1)} "
             f"({since_s:,.0f} s)", ""]
    for label, fam, mode in _HISTORY_ROWS:
        pts = _family_points(series, fam)
        if not pts:
            continue
        slots = _slot(pts, t0, t1, width, mode)
        known = [v for v in slots if v is not None]
        if not known:
            continue
        lines.append(f"  {label:<12}|{sparkline(slots, width)}| "
                     f"min {min(known):,.1f}  max {max(known):,.1f}")
    # healthz strip: worst status per slot (ok/degraded/down), the
    # at-a-glance shape of the incident
    if hz and t1 > t0:
        step = (t1 - t0) / width
        strip = [None] * width
        for t, status, _failing in hz:
            if t0 <= t <= t1 + step:
                i = max(0, min(width - 1, int((t - t0) / step)))
                strip[i] = max(strip[i] or 0, int(status))
        lines.append("  {:<12}|{}|".format("healthz", "".join(
            " " if s is None else _HZ_CHARS.get(s, "?")
            for s in strip)))
    # SLO error-budget ledger (obs.slo slo-state.json): the budget
    # column — remaining %, worst burn multiple, alerts fired
    state = None
    try:
        with open(os.path.join(dir_path, tag, "slo-state.json"),
                  "r", encoding="utf-8") as fh:
            state = json.load(fh)
    except (OSError, ValueError):
        pass
    if isinstance(state, dict):
        lines.append("")
        lines.append(f"  SLO budget  worst burn "
                     f"{state.get('worst_burn', 0.0):,.1f}x   alerts "
                     f"{state.get('alerts_fired_total', 0)}   consumed "
                     f"{100.0 * state.get('budget_consumed_frac', 0.0):,.1f}%")
        for name, sp in sorted((state.get("specs") or {}).items()):
            firing = sp.get("firing")
            lines.append(
                f"    {name:<18}remaining "
                f"{100.0 * sp.get('remaining_frac', 0.0):>5,.1f}%   "
                f"burn {sp.get('worst_burn', 0.0):,.1f}x"
                + (f"   FIRING ({firing})" if firing else ""))
    # timeline tail: the last few reconstructed incident entries
    entries = [e for e in tsdbmod.member_timeline(reader, tag, since=t0)
               if e.get("t", 0) <= t1 + 1.0]
    if entries:
        lines.append("")
        lines.append("  timeline")
        for e in entries[-8:]:
            kind = e.get("kind", "?")
            if kind == "healthz":
                what = (f"healthz {e.get('from')} → {e.get('to')}"
                        + (f" ({', '.join(e.get('failing') or [])})"
                           if e.get("failing") else ""))
            elif kind == "flightrec":
                what = f"flight record: {e.get('reason', '?')}"
            else:
                what = kind + "".join(
                    f" {k}={e[k]}" for k in ("slo", "rule", "severity",
                                             "reason", "episode")
                    if e.get(k))
            lines.append(f"    {_fmt_clock(e.get('t', 0))}  {what}")
    return "\n".join(lines) + "\n"


def _history_main(args) -> int:
    tsdbmod = _tsdb_import()
    d = args.tsdb_dir or os.environ.get(tsdbmod.ENV_DIR, "")
    if not d or not os.path.isdir(d):
        print("obs_top: --since/--replay read the on-disk telemetry "
              "history — pass --tsdb-dir (or set HEATMAP_TSDB_DIR)",
              file=sys.stderr)
        return 2
    reader = tsdbmod.TsdbReader(d)
    members = reader.members()
    if not members:
        print(f"obs_top: no tsdb members under {d}", file=sys.stderr)
        return 1
    tag = args.member or members[0]
    if tag not in members:
        print(f"obs_top: member {tag!r} not in {members}",
              file=sys.stderr)
        return 1
    since_s = args.since if args.since is not None else 3600.0
    if not args.replay:
        sys.stdout.write(render_history(tsdbmod, d, tag, since_s))
        return 0
    # replay: the same window as a growing sequence of frames — the
    # incident unfolding.  Frame times anchor on the DATA's newest
    # sample, so a canned directory replays identically.
    series = reader.series(tag)
    newest = max((pts[-1][0] for pts in series.values() if pts),
                 default=0.0)
    for t, _s, _f in reader.healthz(tag):
        newest = max(newest, t)
    if newest <= 0:
        print(f"obs_top: member {tag!r} has no retained samples",
              file=sys.stderr)
        return 1
    steps = max(2, min(12, int(args.frames)))
    t_start = newest - since_s
    for i in range(1, steps + 1):
        t1 = t_start + since_s * i / steps
        frame = render_history(tsdbmod, d, tag, t1 - t_start, until=t1)
        if not args.no_clear and sys.stdout.isatty():
            sys.stdout.write("\x1b[2J\x1b[H")
        sys.stdout.write(frame)
        if i < steps:
            sys.stdout.write("---\n")
            sys.stdout.flush()
            time.sleep(max(0.0, args.interval
                           if sys.stdout.isatty() else 0.0))
    sys.stdout.flush()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--url", default="http://127.0.0.1:5000")
    ap.add_argument("--interval", type=float, default=2.0)
    ap.add_argument("--once", action="store_true",
                    help="print one frame and exit (no screen clear)")
    ap.add_argument("--no-clear", action="store_true")
    ap.add_argument("--fleet", action="store_true",
                    help="per-member fleet view off /fleet/metrics "
                         "(needs a supervisor channel)")
    ap.add_argument("--since", type=float, default=None,
                    help="time-machine view: render the last SINCE "
                         "seconds from the on-disk telemetry history "
                         "(obs.tsdb) instead of polling a live "
                         "endpoint")
    ap.add_argument("--replay", action="store_true",
                    help="animate the --since window as a growing "
                         "sequence of frames (default window 3600 s)")
    ap.add_argument("--tsdb-dir", default="",
                    help="telemetry history directory (default "
                         "$HEATMAP_TSDB_DIR)")
    ap.add_argument("--member", default="",
                    help="history member tag (default: first member "
                         "found)")
    ap.add_argument("--frames", type=int, default=8,
                    help="--replay frame count (2..12)")
    args = ap.parse_args(argv)

    if args.since is not None or args.replay:
        return _history_main(args)

    metrics_path = "/fleet/metrics" if args.fleet else "/metrics"
    health_path = "/fleet/healthz" if args.fleet else "/healthz"
    render = render_fleet_frame if args.fleet else render_frame
    prev, t_prev = None, 0.0
    while True:
        try:
            m = parse_prom(_fetch(args.url.rstrip("/") + metrics_path))
        except (urllib.error.URLError, OSError) as e:
            print(f"obs_top: {args.url} unreachable: {e}", file=sys.stderr)
            if args.once:
                return 1
            time.sleep(args.interval)
            continue
        try:
            health = json.loads(_fetch(args.url.rstrip("/") + health_path))
        except (urllib.error.HTTPError) as e:  # 503 = down, still JSON
            try:
                health = json.loads(e.read())
            except ValueError:
                health = None
        except (urllib.error.URLError, OSError, ValueError):
            health = None
        now = time.monotonic()
        frame = render(m, prev, now - t_prev if prev else 0.0, health)
        if not (args.once or args.no_clear):
            sys.stdout.write("\x1b[2J\x1b[H")
        sys.stdout.write(frame)
        sys.stdout.flush()
        if args.once:
            return 0
        prev, t_prev = m, now
        time.sleep(args.interval)


if __name__ == "__main__":
    sys.exit(main())
