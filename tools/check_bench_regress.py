#!/usr/bin/env python
"""Fail when the newest bench artifact regressed vs the previous one.

The repo accumulates one ``BENCH_r*.json`` per round; each embeds the
headline metric line bench.py prints (``{"metric": ..., "value":
<events/sec>, ...}``) in its ``tail``.  Nothing compared consecutive
artifacts, so a change that halved the headline rate would ship
silently unless someone eyeballed the numbers.  This check is that
comparison: parse the newest two artifacts' headline rates and fail
when the newest dropped by more than ``--threshold`` (fraction of the
previous rate, default 0.5 — generous because the measured host's
clock flaps ~3x on a minutes timescale, see the cpu_banked_note in the
artifacts; tighten it on dedicated hardware).

Artifacts whose run failed (``rc != 0``) or whose tail carries no
parseable headline are skipped with a note — a broken bench run should
fail ITS OWN gate, not masquerade as a perf regression here.

Artifacts from DIFFERENT backend paths are refused outright: a round
that silently fell back to CPU (``backend_path: "cpu"``) must never be
compared against an attached-hardware headline — the CPU number being
"within threshold" of the hw number says nothing about either, and the
comparison would mask exactly the regression that matters (ROADMAP
item 3: BENCH_r05's stuck ``vs_target 0.054`` IS such a fallback
round).  Mixed pair → exit 1, naming both paths.  The same refusal
applies to mismatched ``shards`` stamps (ISSUE 7): a 4-shard aggregate
headline compared against a 1-shard round would mask a single-shard
regression behind fan-out — differing shard counts → exit 1.

Serve-tier artifacts (``BENCH_SERVE_r*.json``, tools/bench_serve.py
soak rounds) are ratcheted the same way, on the two numbers a serve
regression shows up in first: ``p99_ms`` (latency tail) and
``bytes_sent_wire`` (the delta/ETag tier's whole point is fewer
bytes) — both LOWER-is-better, so the check fails when the newest
GREW past the threshold.  And mirroring the backend/shards refusal:
pairs whose replica counts differ are refused outright — a 4-replica
fleet's aggregate latency/bytes say nothing about a 1-replica round,
and comparing them would mask exactly the per-replica regression the
ratchet exists to catch.

Adaptive-governor provenance (ISSUE 10) joins the refusal list: a
round measured with HEATMAP_GOVERN=1 (the ``govern`` stamp) is refused
against a static-knob round — a governor that traded freshness for
rate (or vice versa) must not mask a static-path regression.  And the
``BENCH_GOVERN_r*.json`` ramp artifacts (tools/e2e_rate.py --ramp) are
ratcheted on both sides of the swing at once: the governed run's
post-swing low-phase p50 may not grow and its high-phase consumption
rate may not drop past the threshold; artifacts banked over different
ramp schedules are refused outright.

Integrity provenance (ISSUE 12) joins the refusal list: an artifact
stamped with an ``audit`` block (HEATMAP_AUDIT=1 rounds: obs.audit's
{max_residual, digests_verified, mismatches}) whose residual or
mismatch count is NON-ZERO is refused outright — a run whose own
conservation ledger says it dropped or duplicated data is not a
headline, whatever its rate; fix the leak, re-run, re-bank.  Unstamped
artifacts (audit off) are untouched.

Mesh provenance (ISSUE 11) joins the refusal list: ``BENCH_r*`` pairs
whose ``mesh`` stamps (device count, partitioned-vs-shuffle mode)
differ are refused, and the ``MULTICHIP_r*.json`` mesh artifacts
(tools/e2e_rate.py --mesh-devices) are ratcheted on the aggregate
steady rate with the same device-count/mode refusals — a 4-chip
partitioned aggregate must never mask a 2-chip or shuffle-mode
regression.  The r01-r05 dryrun proofs carry no headline and are
skipped with a note.

Continuous-query artifacts (ISSUE 13, ``BENCH_CQ_r*.json`` from
tools/bench_cq.py) are ratcheted on ``match_push_p99_ms`` (end-to-end
mutation→pushed-match tail) and ``eval_us_per_record`` (per-record
incremental evaluation cost), both LOWER-is-better; pairs whose
registered-query counts differ are refused outright — both numbers
scale with the standing set, so a 10k-query round cannot stand in for
a 100k one (or mask its regression), the same reasoning as the
replica-count refusal.

Space-time history artifacts (ISSUE 15, ``BENCH_HIST_r*.json`` from
tools/bench_history.py) are ratcheted on ``range_p99_ms`` (time-travel
range-query tail, LOWER-is-better) and ``compact_records_per_s``
(compaction throughput, HIGHER-is-better); pairs whose
retention/chunk-shape stamps (bucket_s, parent_res, retention_s, days,
windows_per_day) differ are refused outright — both numbers scale with
the chunk shape and retained span, so a 1-hour-bucket round cannot
stand in for a 1-day-bucket one (or mask its regression).  The
integrity audit-stamp refusal composes here too.

Delivery provenance (ISSUE 16) extends both serve and history rounds:
serve artifacts stamped with a ``delivery`` block (HEATMAP_DELIVERY=1
soaks: delivered-age p50/p99 to the subscriber socket, worst stage)
are ratcheted on ``age_p99_ms`` (LOWER-is-better), and a
delivery-stamped round is refused against one whose stamp says the
knob was off — stamping changes what the soak measures, so the pair
is not the same experiment; pre-stamp artifacts (no ``delivery`` key)
stay comparable like every other stamp.  History artifacts carry a
``scan`` block ({chunks_opened, blocks_scanned, blocks_used,
bytes_decoded, rows_surfaced, scan_ratio}); ``scan_ratio`` (blocks
used / blocks scanned, HIGHER-is-better — the reader's pruning
efficiency) may not DROP past the threshold.

SLO provenance (ISSUE 18) joins the refusal list: an artifact stamped
with an ``slo`` block (HEATMAP_TSDB=1 rounds: obs.slo's {alerts_fired,
worst_burn, budget_consumed_frac}) whose run FIRED a burn-rate alert
is refused outright — a number earned while the pipeline was violating
its own SLOs must never become the bar; fix the burn, re-run, re-bank.
And mixed tsdb-knob pairs are refused: the recorder's scrape thread is
part of what a stamped round measures, so a knob-on round is not the
same experiment as a knob-off (or pre-tsdb) one.  Applies to the
headline, serve, and history families — the three whose tools stamp
the block.

Usage:
    python tools/check_bench_regress.py [--dir REPO] [--threshold 0.5]
Exit codes: 0 ok / nothing to compare, 1 regression or mixed-backend /
mixed-replica / mixed-govern / mixed-mesh pair, 2 bad arguments.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


def artifact_round(path: str) -> int | None:
    m = _ROUND_RE.search(os.path.basename(path))
    return int(m.group(1)) if m else None


def headline_rate(path: str) -> float | None:
    """The headline events/sec of one artifact, or None when the run
    failed or no metric line parses."""
    try:
        with open(path, encoding="utf-8") as fh:
            art = json.load(fh)
    except (OSError, ValueError):
        return None
    if art.get("rc", 0) != 0:
        return None
    # the headline is a JSON object on its own line inside the captured
    # tail; scan from the END so a re-run's final metric wins
    for line in reversed(str(art.get("tail", "")).splitlines()):
        line = line.strip()
        if not (line.startswith("{") and '"value"' in line):
            continue
        try:
            d = json.loads(line)
        except ValueError:
            continue
        v = d.get("value")
        if isinstance(v, (int, float)) and v > 0:
            return float(v)
    return None


def _stamped(path: str, key: str, types) -> object:
    """One provenance stamp of an artifact: the top-level key bench.py
    stamps, falling back to the headline metric line's copy inside the
    tail; None when neither is present (pre-provenance artifacts —
    treated as comparable to anything, like before)."""
    try:
        with open(path, encoding="utf-8") as fh:
            art = json.load(fh)
    except (OSError, ValueError):
        return None
    v = art.get(key)
    if isinstance(v, types) and v:
        return v
    for line in reversed(str(art.get("tail", "")).splitlines()):
        line = line.strip()
        if not (line.startswith("{") and f'"{key}"' in line):
            continue
        try:
            d = json.loads(line)
        except ValueError:
            continue
        v = d.get(key)
        if isinstance(v, types) and v:
            return v
    return None


def backend_path(path: str) -> str | None:
    """The artifact's backend provenance (``"hw"`` / ``"cpu"``)."""
    return _stamped(path, "backend_path", str)


def shard_count(path: str) -> int | None:
    """The artifact's runtime shard count (``"shards"`` stamp, ISSUE 7
    sharded rounds); None on pre-sharding artifacts."""
    v = _stamped(path, "shards", int)
    return int(v) if v is not None else None


def govern_enabled(path: str) -> bool | None:
    """The artifact's adaptive-governor provenance (``"govern"`` stamp,
    ISSUE 10): True/False when stamped, None on pre-governor
    artifacts (comparable to anything, like the other stamps)."""
    v = _stamped(path, "govern", dict)
    if not isinstance(v, dict) or "enabled" not in v:
        return None
    return bool(v.get("enabled"))


def mesh_stamp(path: str) -> tuple | None:
    """The artifact's mesh provenance (``"mesh"`` stamp, ISSUE 11):
    (device count, "partitioned"|"shuffle") when stamped, None on
    pre-mesh artifacts (comparable to anything, like the other
    stamps)."""
    v = _stamped(path, "mesh", dict)
    if not isinstance(v, dict):
        return None
    devices, mode = v.get("devices"), v.get("mode")
    if not isinstance(devices, int) or mode not in ("partitioned",
                                                    "shuffle"):
        return None
    return (devices, mode)


def audit_refused(path: str, label: str) -> bool:
    """True (and prints the FAIL) when the artifact carries an
    integrity ``audit`` stamp with a non-zero conservation residual or
    any digest mismatch — such a round must never be banked or
    ratcheted against.  Unstamped artifacts pass untouched."""
    v = _stamped(path, "audit", dict)
    if not isinstance(v, dict) or not v.get("enabled"):
        return False
    residual = v.get("max_residual")
    mismatches = v.get("mismatches")
    bad = []
    if isinstance(residual, (int, float)) and residual != 0:
        bad.append(f"max_residual={residual:g}")
    if isinstance(mismatches, (int, float)) and mismatches != 0:
        bad.append(f"digest mismatches={mismatches:g}")
    if not bad:
        return False
    print(f"FAIL: {label} ({os.path.basename(path)}) is stamped with a "
          f"failed integrity audit ({', '.join(bad)}); a round whose "
          f"own conservation ledger reports lost or diverged data is "
          f"not a headline — fix the leak and re-run", file=sys.stderr)
    return True


def slo_stamp_of(path: str) -> dict | None:
    """The artifact's telemetry-history provenance (``"slo"`` stamp,
    ISSUE 18 — obs.slo.slo_stamp); None on knob-off or pre-tsdb
    artifacts."""
    v = _stamped(path, "slo", dict)
    return v if isinstance(v, dict) else None


def slo_refused(path: str, label: str) -> bool:
    """True (and prints the FAIL) when the artifact's ``slo`` stamp
    says the run FIRED a burn-rate alert — a number earned while the
    pipeline was violating its own SLOs must never be banked or
    ratcheted against.  Unstamped / knob-off artifacts pass
    untouched."""
    v = slo_stamp_of(path)
    if not isinstance(v, dict) or not v.get("enabled"):
        return False
    alerts = v.get("alerts_fired")
    if not isinstance(alerts, (int, float)) or alerts <= 0:
        return False
    worst = v.get("worst_burn", 0.0)
    print(f"FAIL: {label} ({os.path.basename(path)}) fired "
          f"{alerts:g} SLO burn-rate alert(s) during the run "
          f"(worst burn {worst:g}x budget) — a number earned while the "
          f"pipeline was violating its own SLOs must never become the "
          f"bar; fix the burn, re-run, re-bank", file=sys.stderr)
    return True


def slo_mixed_refused(p_prev: str, p_new: str, lbl_prev: str,
                      lbl_new: str) -> bool:
    """True (and prints the FAIL) when exactly one side of the pair ran
    with the telemetry recorder on (``slo.enabled``) — the scrape
    thread is part of what a stamped round measures, so a knob-on
    round and a knob-off (or pre-tsdb) one are different
    experiments."""
    on_prev = bool((slo_stamp_of(p_prev) or {}).get("enabled"))
    on_new = bool((slo_stamp_of(p_new) or {}).get("enabled"))
    if on_prev == on_new:
        return False
    print(f"FAIL: tsdb knob-state mismatch — {lbl_prev} ran with "
          f"HEATMAP_TSDB {'on' if on_prev else 'off'} but {lbl_new} "
          f"ran with it {'on' if on_new else 'off'}; the recorder's "
          f"scrape overhead is part of what a stamped round measures, "
          f"so the pair is not the same experiment — re-run with the "
          f"same knob state", file=sys.stderr)
    return True


def quality_stamp_of(path: str) -> dict | None:
    """The artifact's inference-quality provenance (``"quality"``
    stamp, ISSUE 20 — obs.quality.quality_stamp); None on knob-off or
    pre-quality artifacts."""
    v = _stamped(path, "quality", dict)
    return v if isinstance(v, dict) else None


def quality_refused(path: str, label: str) -> bool:
    """True (and prints the FAIL) when the artifact's ``quality`` stamp
    says the run FIRED a drift alert (forecast-skill collapse or NIS
    coverage out of band) — a number earned while the model was
    drifting must never be banked or ratcheted against.  Unstamped /
    knob-off artifacts pass untouched."""
    v = quality_stamp_of(path)
    if not isinstance(v, dict) or not v.get("enabled"):
        return False
    alerts = v.get("drift_alerts")
    if not isinstance(alerts, (int, float)) or alerts <= 0:
        return False
    print(f"FAIL: {label} ({os.path.basename(path)}) fired "
          f"{alerts:g} quality drift alert(s) during the run "
          f"(forecast-skill / NIS-band SLO burn) — a number earned "
          f"while the model was drifting must never become the bar; "
          f"fix the calibration, re-run, re-bank", file=sys.stderr)
    return True


def quality_mixed_refused(p_prev: str, p_new: str, lbl_prev: str,
                          lbl_new: str) -> bool:
    """True (and prints the FAIL) when exactly one side of the pair ran
    with the quality observatory on (``quality.enabled``) — scorecard
    registration and the per-fold calibration ledger are part of what
    a stamped round measures, so a knob-on round and a knob-off (or
    pre-quality) one are different experiments."""
    on_prev = bool((quality_stamp_of(p_prev) or {}).get("enabled"))
    on_new = bool((quality_stamp_of(p_new) or {}).get("enabled"))
    if on_prev == on_new:
        return False
    print(f"FAIL: quality knob-state mismatch — {lbl_prev} ran with "
          f"HEATMAP_QUALITY {'on' if on_prev else 'off'} but "
          f"{lbl_new} ran with it {'on' if on_new else 'off'}; the "
          f"observatory's per-fold ledger is part of what a stamped "
          f"round measures, so the pair is not the same experiment — "
          f"re-run with the same knob state", file=sys.stderr)
    return True


def newest_pair(dir_path: str) -> list:
    """[(round, path, rate)] for every parseable artifact, round-sorted."""
    out = []
    for p in glob.glob(os.path.join(glob.escape(dir_path),
                                    "BENCH_r*.json")):
        rnd = artifact_round(p)
        if rnd is None:
            continue
        out.append((rnd, p, headline_rate(p)))
    return sorted(out)


# ------------------------------------------------------- serve artifacts
_SERVE_ROUND_RE = re.compile(r"BENCH_SERVE_r(\d+)\.json$")


def serve_artifact_round(path: str) -> int | None:
    m = _SERVE_ROUND_RE.search(os.path.basename(path))
    return int(m.group(1)) if m else None


def serve_metrics(path: str) -> tuple | None:
    """(p99_ms, bytes_sent_wire, replicas|None, wire_format|None,
    serve_workers|None, delivery|None) of one bench_serve artifact —
    the ``soak`` block when present (replicated-fleet rounds), else
    the concurrent delta mode; None when neither parses (a broken run
    fails its own gate, not this one).  ``wire_format`` and
    ``serve_workers`` are the ISSUE 14 provenance stamps (multi-process
    fleet soaks); ``delivery`` is the ISSUE 16 delivered-age stamp
    ({enabled, age_p50_ms, age_p99_ms, worst_stage}); pre-stamp
    artifacts carry none of them and stay comparable, like every other
    stamp.  The ISSUE 17 extras ride at the end: ``serve_core`` (the
    soak's HEATMAP_SERVE_CORE stamp — every pre-stamp artifact ran
    wsgiref, so missing means ``"thread"``) and the artifact's
    ``thread_reference`` leg (same-schedule wsgiref run banked beside
    an epoll soak) for the cross-core fallback."""
    try:
        with open(path, encoding="utf-8") as fh:
            art = json.load(fh)
    except (OSError, ValueError):
        return None
    if not isinstance(art, dict) or art.get("rc", 0) != 0:
        return None
    sec = art.get("soak")
    if not isinstance(sec, dict):
        sec = (art.get("concurrent") or {}).get("delta")
    if not isinstance(sec, dict):
        return None
    p99, wire = sec.get("p99_ms"), sec.get("bytes_sent_wire")
    if not isinstance(p99, (int, float)) \
            or not isinstance(wire, (int, float)) or p99 <= 0:
        return None
    replicas = (art.get("soak") or {}).get("replicas") \
        or (art.get("repl") or {}).get("replicas")
    fmt = (art.get("soak") or {}).get("wire_format") \
        or (art.get("wire") or {}).get("format")
    workers = (art.get("soak") or {}).get("serve_workers")
    delivery = art.get("delivery")
    if not isinstance(delivery, dict) or "enabled" not in delivery:
        delivery = None
    core = (art.get("soak") or {}).get("serve_core")
    thread_ref = art.get("thread_reference")
    if not isinstance(thread_ref, dict):
        thread_ref = None
    return (float(p99), float(wire),
            int(replicas) if isinstance(replicas, int) else None,
            str(fmt) if isinstance(fmt, str) else None,
            int(workers) if isinstance(workers, int) else None,
            delivery,
            str(core) if isinstance(core, str) else "thread",
            thread_ref)


def compare_serve(dir_path: str, threshold: float) -> int:
    """Ratchet the newest two BENCH_SERVE_r*.json artifacts: p99 and
    wire bytes may not GROW past ``threshold``; mixed replica-count
    pairs are refused (exit 1), mirroring the backend/shards logic."""
    arts = []
    for p in glob.glob(os.path.join(glob.escape(dir_path),
                                    "BENCH_SERVE_r*.json")):
        rnd = serve_artifact_round(p)
        if rnd is None:
            continue
        arts.append((rnd, p, serve_metrics(p)))
    arts.sort()
    usable = [(r, p, m) for r, p, m in arts if m is not None]
    for r, p, m in arts:
        if m is None:
            print(f"note: skipping serve r{r:02d} "
                  f"({os.path.basename(p)}): failed run or no "
                  f"parseable p99/bytes")
    if len(usable) < 2:
        print(f"OK: {len(usable)} usable serve artifact(s) — nothing "
              f"to compare")
        return 0
    (r_prev, _p_prev, m_prev), (r_new, _p_new, m_new) = \
        usable[-2], usable[-1]
    if slo_refused(_p_prev, f"serve r{r_prev:02d}") \
            or slo_refused(_p_new, f"serve r{r_new:02d}") \
            or slo_mixed_refused(_p_prev, _p_new,
                                 f"serve r{r_prev:02d}",
                                 f"serve r{r_new:02d}"):
        return 1
    (p99_prev, wire_prev, rep_prev, fmt_prev, wrk_prev,
     delv_prev, core_prev, _tref_prev) = m_prev
    (p99_new, wire_new, rep_new, fmt_new, wrk_new, delv_new,
     core_new, tref_new) = m_new
    if rep_prev is not None and rep_new is not None \
            and rep_prev != rep_new:
        print(f"FAIL: replica-count mismatch — serve r{r_prev:02d} ran "
              f"{rep_prev} replica(s) but r{r_new:02d} ran {rep_new}; "
              f"an N-replica fleet's latency/bytes cannot stand in for "
              f"another fleet width (or mask its regression) — re-run "
              f"the soak at the same replica count", file=sys.stderr)
        return 1
    if fmt_prev is not None and fmt_new is not None \
            and fmt_prev != fmt_new:
        print(f"FAIL: wire-format mismatch — serve r{r_prev:02d} "
              f"negotiated {fmt_prev!r} but r{r_new:02d} negotiated "
              f"{fmt_new!r}; the binary frame's bytes/latency cannot "
              f"stand in for the JSON path's (or mask its regression) "
              f"— re-run the soak with the same --fmt",
              file=sys.stderr)
        return 1
    if wrk_prev is not None and wrk_new is not None \
            and wrk_prev != wrk_new:
        print(f"FAIL: serve-worker-count mismatch — serve "
              f"r{r_prev:02d} ran {wrk_prev} worker process(es) but "
              f"r{r_new:02d} ran {wrk_new}; an N-worker fleet's "
              f"latency cannot stand in for another width (or mask "
              f"its per-worker regression) — re-run the soak at the "
              f"same --serve-workers", file=sys.stderr)
        return 1
    if core_prev != core_new:
        # an epoll soak's p99 cannot ratchet against a wsgiref
        # baseline (or vice versa) — different loop, different
        # experiment.  The escape hatch is the newer artifact's
        # same-schedule thread_reference leg: when the baseline is
        # thread-core and the new artifact banked one, ratchet
        # thread-vs-thread instead of refusing.
        tr = tref_new if core_prev == "thread" else None
        tr_p99 = (tr or {}).get("p99_ms")
        tr_wire = (tr or {}).get("bytes_sent_wire")
        if isinstance(tr_p99, (int, float)) and tr_p99 > 0 \
                and isinstance(tr_wire, (int, float)):
            print(f"note: serve-core mismatch (r{r_prev:02d} ran "
                  f"{core_prev!r}, r{r_new:02d} ran {core_new!r}) — "
                  f"falling back to r{r_new:02d}'s thread_reference "
                  f"leg for a matching-core pair")
            p99_new, wire_new = float(tr_p99), float(tr_wire)
            # the reference leg carries no delivery stamp: skip the
            # delivered-age ratchet rather than compare across cores
            delv_new = None
        else:
            print(f"FAIL: serve-core mismatch — serve r{r_prev:02d} "
                  f"ran the {core_prev!r} core but r{r_new:02d} ran "
                  f"{core_new!r}, and r{r_new:02d} carries no "
                  f"thread_reference leg to fall back to; an event-"
                  f"loop core's latency cannot stand in for the "
                  f"thread core's (or mask its regression) — re-run "
                  f"with the same --serve-core or bank the reference "
                  f"leg", file=sys.stderr)
            return 1
    if delv_prev is not None and delv_new is not None \
            and bool(delv_prev.get("enabled")) \
            != bool(delv_new.get("enabled")):
        print(f"FAIL: delivery knob-state mismatch — serve "
              f"r{r_prev:02d} ran with HEATMAP_DELIVERY "
              f"{'on' if delv_prev.get('enabled') else 'off'} but "
              f"r{r_new:02d} ran with it "
              f"{'on' if delv_new.get('enabled') else 'off'}; the "
              f"stamped soak measures delivered age to the socket and "
              f"the unstamped one doesn't, so the pair is not the same "
              f"experiment — re-run with the same knob state",
              file=sys.stderr)
        return 1
    rc = 0
    for name, prev, new in (("p99_ms", p99_prev, p99_new),
                            ("bytes_sent_wire", wire_prev, wire_new)):
        growth = (new - prev) / prev if prev > 0 else 0.0
        line = (f"serve r{r_prev:02d} {name} {prev:,.0f} -> "
                f"r{r_new:02d} {new:,.0f} ({growth:+.1%})")
        if growth > threshold:
            print(f"FAIL: serve regression beyond {threshold:.0%}: "
                  f"{line}", file=sys.stderr)
            rc = 1
        else:
            print(f"OK: {line} within the {threshold:.0%} threshold")
    # delivered-age ratchet: only when both rounds stamped it on —
    # the age to the subscriber socket is the serve tier's end-to-end
    # freshness headline and may not grow past the threshold
    dl_prev = (delv_prev or {}).get("age_p99_ms")
    dl_new = (delv_new or {}).get("age_p99_ms")
    if isinstance(dl_prev, (int, float)) and dl_prev > 0 \
            and isinstance(dl_new, (int, float)):
        growth = (dl_new - dl_prev) / dl_prev
        line = (f"serve r{r_prev:02d} delivered age_p99_ms "
                f"{dl_prev:,.1f} -> r{r_new:02d} {dl_new:,.1f} "
                f"({growth:+.1%})")
        if growth > threshold:
            print(f"FAIL: delivered-age regression beyond "
                  f"{threshold:.0%}: {line}", file=sys.stderr)
            rc = 1
        else:
            print(f"OK: {line} within the {threshold:.0%} threshold")
    return rc


# ---------------------------------------------------- multichip artifacts
_MULTICHIP_ROUND_RE = re.compile(r"MULTICHIP_r(\d+)\.json$")


def multichip_artifact_round(path: str) -> int | None:
    m = _MULTICHIP_ROUND_RE.search(os.path.basename(path))
    return int(m.group(1)) if m else None


def multichip_metrics(path: str) -> tuple | None:
    """(steady_events_per_sec, devices, mode) of one MULTICHIP_r*.json
    mesh artifact (tools/e2e_rate.py --mesh-devices).  None when the
    run failed, the headline doesn't parse, or the artifact predates
    the mesh stamp (the r01-r05 dryrun_multichip proofs carry only
    {n_devices, rc, tail} — skipped with a note, never compared)."""
    try:
        with open(path, encoding="utf-8") as fh:
            art = json.load(fh)
    except (OSError, ValueError):
        return None
    if not isinstance(art, dict) or art.get("rc", 0) != 0:
        return None
    rate = art.get("steady_events_per_sec")
    mesh = art.get("mesh")
    if not isinstance(rate, (int, float)) or rate <= 0 \
            or not isinstance(mesh, dict):
        return None
    devices, mode = mesh.get("devices"), mesh.get("mode")
    if not isinstance(devices, int) or mode not in ("partitioned",
                                                    "shuffle"):
        return None
    return (float(rate), devices, mode)


def compare_multichip(dir_path: str, threshold: float) -> int:
    """Ratchet the newest two MULTICHIP_r*.json mesh artifacts on the
    aggregate steady rate; REFUSE (exit 1) pairs whose mesh device
    count or partitioned-vs-shuffle mode differ — a 4-chip partitioned
    aggregate cannot stand in for a 2-chip or shuffle-mode round (or
    mask its per-chip regression), mirroring the
    backend/shards/replica/govern refusals."""
    arts = []
    for p in glob.glob(os.path.join(glob.escape(dir_path),
                                    "MULTICHIP_r*.json")):
        rnd = multichip_artifact_round(p)
        if rnd is None:
            continue
        arts.append((rnd, p, multichip_metrics(p)))
    arts.sort()
    usable = [(r, p, m) for r, p, m in arts if m is not None]
    for r, p, m in arts:
        if m is None:
            print(f"note: skipping multichip r{r:02d} "
                  f"({os.path.basename(p)}): failed run, pre-mesh "
                  f"dryrun proof, or no parseable headline")
    if len(usable) < 2:
        print(f"OK: {len(usable)} usable multichip artifact(s) — "
              f"nothing to compare")
        return 0
    (r_prev, _pp, m_prev), (r_new, _pn, m_new) = usable[-2], usable[-1]
    if audit_refused(_pp, f"multichip r{r_prev:02d}") \
            or audit_refused(_pn, f"multichip r{r_new:02d}"):
        return 1
    (rate_prev, dev_prev, mode_prev) = m_prev
    (rate_new, dev_new, mode_new) = m_new
    if dev_prev != dev_new:
        print(f"FAIL: mesh device-count mismatch — multichip "
              f"r{r_prev:02d} ran {dev_prev} device(s) but "
              f"r{r_new:02d} ran {dev_new}; an N-device aggregate "
              f"cannot stand in for another width (or mask its "
              f"per-chip regression) — re-run at the same device "
              f"count", file=sys.stderr)
        return 1
    if mode_prev != mode_new:
        print(f"FAIL: mesh mode mismatch — multichip r{r_prev:02d} "
              f"ran {mode_prev!r} but r{r_new:02d} ran {mode_new!r}; "
              f"the partitioned mesh and the removed ICI-shuffle mesh "
              f"are different experiments — compare artifacts of the "
              f"same mode", file=sys.stderr)
        return 1
    drop = (rate_prev - rate_new) / rate_prev
    line = (f"multichip r{r_prev:02d} {rate_prev:,.0f} ev/s -> "
            f"r{r_new:02d} {rate_new:,.0f} ev/s ({-drop:+.1%})")
    if drop > threshold:
        print(f"FAIL: multichip regression beyond {threshold:.0%}: "
              f"{line}", file=sys.stderr)
        return 1
    print(f"OK: {line} within the {threshold:.0%} threshold")
    return 0


# ---------------------------------------------------------- cq artifacts
_CQ_ROUND_RE = re.compile(r"BENCH_CQ_r(\d+)\.json$")


def cq_artifact_round(path: str) -> int | None:
    m = _CQ_ROUND_RE.search(os.path.basename(path))
    return int(m.group(1)) if m else None


def cq_metrics(path: str) -> tuple | None:
    """(match_push_p99_ms, eval_us_per_record, queries) of one
    BENCH_CQ_r*.json continuous-query artifact (tools/bench_cq.py) —
    the two numbers a standing-query regression shows up in first:
    end-to-end match-push latency tail and the per-record incremental
    evaluation cost, both LOWER-is-better.  None when the run failed
    or the numbers don't parse."""
    try:
        with open(path, encoding="utf-8") as fh:
            art = json.load(fh)
    except (OSError, ValueError):
        return None
    if not isinstance(art, dict) or art.get("rc", 0) != 0:
        return None
    p99 = art.get("match_push_p99_ms")
    cost = art.get("eval_us_per_record")
    queries = art.get("queries")
    if not isinstance(p99, (int, float)) or p99 <= 0 \
            or not isinstance(cost, (int, float)) or cost <= 0:
        return None
    return (float(p99), float(cost),
            int(queries) if isinstance(queries, int) else None)


def compare_cq(dir_path: str, threshold: float) -> int:
    """Ratchet the newest two BENCH_CQ_r*.json artifacts: match-push
    p99 and per-record eval cost may not GROW past ``threshold``;
    pairs whose registered-query counts differ are REFUSED (exit 1) —
    100k standing geofences' incremental cost cannot stand in for 10k's
    (or mask its regression), mirroring the replica-count refusal."""
    arts = []
    for p in glob.glob(os.path.join(glob.escape(dir_path),
                                    "BENCH_CQ_r*.json")):
        rnd = cq_artifact_round(p)
        if rnd is None:
            continue
        arts.append((rnd, p, cq_metrics(p)))
    arts.sort()
    usable = [(r, p, m) for r, p, m in arts if m is not None]
    for r, p, m in arts:
        if m is None:
            print(f"note: skipping cq r{r:02d} "
                  f"({os.path.basename(p)}): failed run or no "
                  f"parseable p99/eval cost")
    if len(usable) < 2:
        print(f"OK: {len(usable)} usable cq artifact(s) — nothing to "
              f"compare")
        return 0
    (r_prev, p_prev, m_prev), (r_new, p_new, m_new) = \
        usable[-2], usable[-1]
    if audit_refused(p_prev, f"cq r{r_prev:02d}") \
            or audit_refused(p_new, f"cq r{r_new:02d}"):
        return 1
    (p99_prev, cost_prev, q_prev) = m_prev
    (p99_new, cost_new, q_new) = m_new
    if q_prev is not None and q_new is not None and q_prev != q_new:
        print(f"FAIL: registered-query-count mismatch — cq "
              f"r{r_prev:02d} ran {q_prev:,} standing quer(ies) but "
              f"r{r_new:02d} ran {q_new:,}; per-record eval cost and "
              f"push latency scale with the registered set, so the "
              f"pair is not the same experiment (and would mask its "
              f"regression) — re-run the bench at the same query "
              f"count", file=sys.stderr)
        return 1
    rc = 0
    for name, prev, new in (("match_push_p99_ms", p99_prev, p99_new),
                            ("eval_us_per_record", cost_prev,
                             cost_new)):
        growth = (new - prev) / prev if prev > 0 else 0.0
        line = (f"cq r{r_prev:02d} {name} {prev:,.2f} -> "
                f"r{r_new:02d} {new:,.2f} ({growth:+.1%})")
        if growth > threshold:
            print(f"FAIL: cq regression beyond {threshold:.0%}: "
                  f"{line}", file=sys.stderr)
            rc = 1
        else:
            print(f"OK: {line} within the {threshold:.0%} threshold")
    return rc


# -------------------------------------------------------- hist artifacts
_HIST_ROUND_RE = re.compile(r"BENCH_HIST_r(\d+)\.json$")


def hist_artifact_round(path: str) -> int | None:
    m = _HIST_ROUND_RE.search(os.path.basename(path))
    return int(m.group(1)) if m else None


def hist_metrics(path: str) -> tuple | None:
    """(range_p99_ms, compact_records_per_s, shape) of one
    BENCH_HIST_r*.json space-time history artifact (tools/
    bench_history.py) — range-query tail latency (LOWER-is-better),
    compaction throughput (HIGHER-is-better), and the
    (bucket_s, parent_res, retention_s, days, windows_per_day)
    chunk-shape/retention signature that decides comparability.  None
    when the run failed or the numbers don't parse."""
    try:
        with open(path, encoding="utf-8") as fh:
            art = json.load(fh)
    except (OSError, ValueError):
        return None
    if not isinstance(art, dict) or art.get("rc", 0) != 0:
        return None
    p99 = art.get("range_p99_ms")
    rps = art.get("compact_records_per_s")
    if not isinstance(p99, (int, float)) or p99 <= 0 \
            or not isinstance(rps, (int, float)) or rps <= 0:
        return None
    shape = tuple(art.get(k) for k in
                  ("bucket_s", "parent_res", "retention_s", "days",
                   "windows_per_day"))
    scan = art.get("scan")
    if not isinstance(scan, dict):
        scan = None
    return (float(p99), float(rps), shape, scan)


def compare_hist(dir_path: str, threshold: float) -> int:
    """Ratchet the newest two BENCH_HIST_r*.json artifacts: range p99
    may not GROW and compaction throughput may not DROP past
    ``threshold``.  Pairs with different retention/chunk-shape stamps
    are REFUSED (exit 1) — a 1-hour-bucket store's range latency says
    nothing about a 1-day-bucket one (or masks its regression), the
    same reasoning as every other provenance refusal.  The integrity
    audit-stamp refusal composes: a leak-stamped round is never banked
    or used as the baseline."""
    arts = []
    for p in glob.glob(os.path.join(glob.escape(dir_path),
                                    "BENCH_HIST_r*.json")):
        rnd = hist_artifact_round(p)
        if rnd is None:
            continue
        arts.append((rnd, p, hist_metrics(p)))
    arts.sort()
    usable = [(r, p, m) for r, p, m in arts if m is not None]
    for r, p, m in arts:
        if m is None:
            print(f"note: skipping hist r{r:02d} "
                  f"({os.path.basename(p)}): failed run or no "
                  f"parseable p99/throughput")
    if len(usable) < 2:
        print(f"OK: {len(usable)} usable hist artifact(s) — nothing "
              f"to compare")
        return 0
    (r_prev, p_prev, m_prev), (r_new, p_new, m_new) = \
        usable[-2], usable[-1]
    if audit_refused(p_prev, f"hist r{r_prev:02d}") \
            or audit_refused(p_new, f"hist r{r_new:02d}"):
        return 1
    if slo_refused(p_prev, f"hist r{r_prev:02d}") \
            or slo_refused(p_new, f"hist r{r_new:02d}") \
            or slo_mixed_refused(p_prev, p_new, f"hist r{r_prev:02d}",
                                 f"hist r{r_new:02d}"):
        return 1
    (p99_prev, rps_prev, shape_prev, scan_prev) = m_prev
    (p99_new, rps_new, shape_new, scan_new) = m_new
    if shape_prev != shape_new:
        print(f"FAIL: history shape mismatch — hist r{r_prev:02d} ran "
              f"(bucket_s, parent_res, retention_s, days, "
              f"windows_per_day) = {shape_prev} but r{r_new:02d} ran "
              f"{shape_new}; range latency and compaction throughput "
              f"scale with the chunk shape and retained span, so the "
              f"pair is not the same experiment (and would mask its "
              f"regression) — re-run the bench at the previous shape",
              file=sys.stderr)
        return 1
    rc = 0
    growth = (p99_new - p99_prev) / p99_prev
    line = (f"hist r{r_prev:02d} range_p99_ms {p99_prev:,.2f} -> "
            f"r{r_new:02d} {p99_new:,.2f} ({growth:+.1%})")
    if growth > threshold:
        print(f"FAIL: hist range-query regression beyond "
              f"{threshold:.0%}: {line}", file=sys.stderr)
        rc = 1
    else:
        print(f"OK: {line} within the {threshold:.0%} threshold")
    drop = (rps_prev - rps_new) / rps_prev
    line = (f"hist r{r_prev:02d} compaction {rps_prev:,.0f} rec/s -> "
            f"r{r_new:02d} {rps_new:,.0f} rec/s ({-drop:+.1%})")
    if drop > threshold:
        print(f"FAIL: hist compaction-throughput regression beyond "
              f"{threshold:.0%}: {line}", file=sys.stderr)
        rc = 1
    else:
        print(f"OK: {line} within the {threshold:.0%} threshold")
    # scan-efficiency ratchet: only when both rounds carry the ISSUE 16
    # scan stamp — the reader's pruning ratio (blocks used / blocks
    # scanned) may not DROP past the threshold; pre-stamp rounds stay
    # comparable on the latency/throughput numbers alone
    sr_prev = (scan_prev or {}).get("scan_ratio")
    sr_new = (scan_new or {}).get("scan_ratio")
    if isinstance(sr_prev, (int, float)) and sr_prev > 0 \
            and isinstance(sr_new, (int, float)):
        drop = (sr_prev - sr_new) / sr_prev
        line = (f"hist r{r_prev:02d} scan_ratio {sr_prev:.4f} -> "
                f"r{r_new:02d} {sr_new:.4f} ({-drop:+.1%})")
        if drop > threshold:
            print(f"FAIL: hist scan-efficiency regression beyond "
                  f"{threshold:.0%}: {line}", file=sys.stderr)
            rc = 1
        else:
            print(f"OK: {line} within the {threshold:.0%} threshold")
    return rc


# ------------------------------------------------------ govern artifacts
_GOVERN_ROUND_RE = re.compile(r"BENCH_GOVERN_r(\d+)\.json$")


def govern_artifact_round(path: str) -> int | None:
    m = _GOVERN_ROUND_RE.search(os.path.basename(path))
    return int(m.group(1)) if m else None


def govern_metrics(path: str) -> tuple | None:
    """(recovery_low_p50_s, high_phase_eps, schedule_sig) of one
    BENCH_GOVERN_r*.json ramp artifact — the governed run's post-swing
    low-phase p50 (lower-better) and high-phase consumption rate
    (higher-better), plus the offered schedule as the comparability
    key.  None when the run failed or the phases don't parse."""
    try:
        with open(path, encoding="utf-8") as fh:
            art = json.load(fh)
    except (OSError, ValueError):
        return None
    if not isinstance(art, dict) or art.get("rc", 0) != 0:
        return None
    gov = art.get("governed")
    phases = (gov or {}).get("phases")
    if not isinstance(phases, list) or not phases:
        return None
    try:
        offered = [p["offered_eps"] for p in phases]
        lows = [p for p in phases if p["offered_eps"] == min(offered)]
        highs = [p for p in phases if p["offered_eps"] == max(offered)]
        low_p50 = lows[-1].get("age_p50_s")      # the post-swing side
        high_eps = highs[-1].get("consumed_eps")
    except (KeyError, TypeError):
        return None
    if not isinstance(low_p50, (int, float)) \
            or not isinstance(high_eps, (int, float)) or high_eps <= 0:
        return None
    sig = tuple((p.get("offered_eps"), p.get("duration_s"))
                for p in phases)
    return (float(low_p50), float(high_eps), sig)


def compare_govern(dir_path: str, threshold: float) -> int:
    """Ratchet the newest two BENCH_GOVERN_r*.json artifacts: the
    governed run's post-swing low-phase p50 may not GROW past
    ``threshold`` and its high-phase rate may not DROP past it.
    Artifacts banked over DIFFERENT ramp schedules are refused (exit
    1) — the phases aren't the same experiment, mirroring the
    backend/shards/replica refusals."""
    arts = []
    for p in glob.glob(os.path.join(glob.escape(dir_path),
                                    "BENCH_GOVERN_r*.json")):
        rnd = govern_artifact_round(p)
        if rnd is None:
            continue
        arts.append((rnd, p, govern_metrics(p)))
    arts.sort()
    usable = [(r, p, m) for r, p, m in arts if m is not None]
    for r, p, m in arts:
        if m is None:
            print(f"note: skipping govern r{r:02d} "
                  f"({os.path.basename(p)}): failed run or no "
                  f"parseable governed phases")
    if len(usable) < 2:
        print(f"OK: {len(usable)} usable govern artifact(s) — nothing "
              f"to compare")
        return 0
    (r_prev, _pp, m_prev), (r_new, _pn, m_new) = usable[-2], usable[-1]
    (p50_prev, eps_prev, sig_prev) = m_prev
    (p50_new, eps_new, sig_new) = m_new
    if sig_prev != sig_new:
        print(f"FAIL: ramp-schedule mismatch — govern r{r_prev:02d} and "
              f"r{r_new:02d} ran different offered-load schedules; the "
              f"phase numbers aren't the same experiment — re-run the "
              f"ramp with the previous schedule", file=sys.stderr)
        return 1
    rc = 0
    growth = (p50_new - p50_prev) / p50_prev if p50_prev > 0 else 0.0
    line = (f"govern r{r_prev:02d} low-phase p50 {p50_prev:.3f}s -> "
            f"r{r_new:02d} {p50_new:.3f}s ({growth:+.1%})")
    if growth > threshold:
        print(f"FAIL: governed low-load freshness regression beyond "
              f"{threshold:.0%}: {line}", file=sys.stderr)
        rc = 1
    else:
        print(f"OK: {line} within the {threshold:.0%} threshold")
    drop = (eps_prev - eps_new) / eps_prev
    line = (f"govern r{r_prev:02d} high-phase {eps_prev:,.0f} ev/s -> "
            f"r{r_new:02d} {eps_new:,.0f} ev/s ({-drop:+.1%})")
    if drop > threshold:
        print(f"FAIL: governed high-load rate regression beyond "
              f"{threshold:.0%}: {line}", file=sys.stderr)
        rc = 1
    else:
        print(f"OK: {line} within the {threshold:.0%} threshold")
    return rc


# -------------------------------------------------------- infer artifacts
_INFER_ROUND_RE = re.compile(r"BENCH_INFER_r(\d+)\.json$")


def infer_artifact_round(path: str) -> int | None:
    m = _INFER_ROUND_RE.search(os.path.basename(path))
    return int(m.group(1)) if m else None


def reducer_set(path: str) -> tuple | None:
    """The artifact's reducer-set provenance (``"reducers": {"set":
    [...]}``, ISSUE 19 — stamped by bench.py / e2e_rate / bench_infer);
    None on pre-inference artifacts."""
    v = _stamped(path, "reducers", dict)
    s = v.get("set") if isinstance(v, dict) else None
    return tuple(s) if isinstance(s, (list, tuple)) else None


def infer_metrics(path: str) -> tuple | None:
    """(entities_per_sec, forecast_skill, overhead_frac, entities) of
    one BENCH_INFER_r*.json streaming-inference artifact
    (tools/bench_infer.py).  entities_per_sec and skill are
    HIGHER-is-better, overhead_frac LOWER-is-better.  None when the
    run failed its own gates or the numbers don't parse."""
    try:
        with open(path, encoding="utf-8") as fh:
            art = json.load(fh)
    except (OSError, ValueError):
        return None
    if not isinstance(art, dict) or art.get("rc", 0) != 0:
        return None
    eps = art.get("entities_per_sec")
    skill = art.get("forecast_skill")
    over = art.get("overhead_frac")
    ents = art.get("entities")
    if not isinstance(eps, (int, float)) or eps <= 0 \
            or not isinstance(skill, (int, float)) \
            or not isinstance(over, (int, float)):
        return None
    return (float(eps), float(skill), float(over),
            int(ents) if isinstance(ents, int) else None)


def compare_infer(dir_path: str, threshold: float) -> int:
    """Ratchet the newest two BENCH_INFER_r*.json artifacts: filter
    throughput (entities/s) and forecast skill may not DROP past
    ``threshold``, composed-fold overhead may not GROW past it (on a
    0.10 floor base — overhead near zero would otherwise fail on
    noise-level point moves).  Pairs banked under DIFFERENT reducer
    sets are REFUSED (exit 1): a count+kalman fold's cost cannot be
    ratcheted against a richer or leaner reducer composition — not the
    same experiment.  Composes with the audit and SLO refusals like
    every family."""
    arts = []
    for p in glob.glob(os.path.join(glob.escape(dir_path),
                                    "BENCH_INFER_r*.json")):
        rnd = infer_artifact_round(p)
        if rnd is None:
            continue
        arts.append((rnd, p, infer_metrics(p)))
    arts.sort()
    usable = [(r, p, m) for r, p, m in arts if m is not None]
    for r, p, m in arts:
        if m is None:
            print(f"note: skipping infer r{r:02d} "
                  f"({os.path.basename(p)}): failed gates or no "
                  f"parseable entities/s + skill + overhead")
    if len(usable) < 2:
        print(f"OK: {len(usable)} usable infer artifact(s) — nothing "
              f"to compare")
        return 0
    (r_prev, p_prev, m_prev), (r_new, p_new, m_new) = \
        usable[-2], usable[-1]
    if audit_refused(p_prev, f"infer r{r_prev:02d}") \
            or audit_refused(p_new, f"infer r{r_new:02d}") \
            or slo_refused(p_prev, f"infer r{r_prev:02d}") \
            or slo_refused(p_new, f"infer r{r_new:02d}") \
            or slo_mixed_refused(p_prev, p_new, f"infer r{r_prev:02d}",
                                 f"infer r{r_new:02d}") \
            or quality_refused(p_prev, f"infer r{r_prev:02d}") \
            or quality_refused(p_new, f"infer r{r_new:02d}") \
            or quality_mixed_refused(p_prev, p_new,
                                     f"infer r{r_prev:02d}",
                                     f"infer r{r_new:02d}"):
        return 1
    rs_prev, rs_new = reducer_set(p_prev), reducer_set(p_new)
    if rs_prev is not None and rs_new is not None and rs_prev != rs_new:
        print(f"FAIL: reducer-set mismatch — infer r{r_prev:02d} "
              f"folded {','.join(rs_prev)} but r{r_new:02d} folded "
              f"{','.join(rs_new)}; the composed fold's cost and skill "
              f"scale with the reducer set, so the pair is not the "
              f"same experiment (and would mask its regression) — "
              f"re-run the bench with the same HEATMAP_REDUCERS",
              file=sys.stderr)
        return 1
    (eps_prev, sk_prev, ov_prev, _e_prev) = m_prev
    (eps_new, sk_new, ov_new, _e_new) = m_new
    rc = 0
    for name, prev, new in (("entities_per_sec", eps_prev, eps_new),
                            ("forecast_skill", sk_prev, sk_new)):
        if prev <= 0:
            continue
        drop = (prev - new) / prev
        line = (f"infer r{r_prev:02d} {name} {prev:,.4g} -> "
                f"r{r_new:02d} {new:,.4g} ({-drop:+.1%})")
        if drop > threshold:
            print(f"FAIL: infer regression beyond {threshold:.0%}: "
                  f"{line}", file=sys.stderr)
            rc = 1
        else:
            print(f"OK: {line} within the {threshold:.0%} threshold")
    # overhead is lower-is-better and typically near zero; growth is
    # judged against max(prev, 0.10) so a 1% -> 2% point move doesn't
    # read as a 2x regression while 1% -> 10%+ still fails
    growth = (ov_new - ov_prev) / max(ov_prev, 0.10)
    line = (f"infer r{r_prev:02d} overhead_frac {ov_prev:.4f} -> "
            f"r{r_new:02d} {ov_new:.4f}")
    if growth > threshold:
        print(f"FAIL: composed-fold overhead regression beyond "
              f"{threshold:.0%} of the floored base: {line}",
              file=sys.stderr)
        rc = 1
    else:
        print(f"OK: {line} within the {threshold:.0%} threshold")
    # live-skill ratchet (ISSUE 20): when both rounds carry the quality
    # observatory's stamp, the LIVE skill (scored against what the
    # pipeline actually served, not synthetic ground truth) may not
    # drop past the threshold either.  Skill is signed and can sit
    # near zero, so the drop is judged against max(prev, 0.10) like
    # overhead growth — point moves at noise level pass, collapses
    # fail.
    q_prev = quality_stamp_of(p_prev) or {}
    q_new = quality_stamp_of(p_new) or {}
    ls_prev, ls_new = q_prev.get("live_skill"), q_new.get("live_skill")
    if isinstance(ls_prev, (int, float)) \
            and isinstance(ls_new, (int, float)):
        drop = (ls_prev - ls_new) / max(ls_prev, 0.10)
        line = (f"infer r{r_prev:02d} live_skill {ls_prev:.4f} -> "
                f"r{r_new:02d} {ls_new:.4f}")
        if drop > threshold:
            print(f"FAIL: live forecast-skill regression beyond "
                  f"{threshold:.0%} of the floored base: {line}",
                  file=sys.stderr)
            rc = 1
        else:
            print(f"OK: {line} within the {threshold:.0%} threshold")
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dir", default=REPO,
                    help="directory holding BENCH_r*.json (default: repo)")
    ap.add_argument("--threshold", type=float, default=0.5,
                    help="max tolerated fractional drop vs the previous "
                         "artifact (default 0.5 = fail below half)")
    args = ap.parse_args(argv)
    if not 0 < args.threshold < 1:
        print("check_bench_regress: --threshold must be in (0, 1)",
              file=sys.stderr)
        return 2
    serve_rc = compare_serve(args.dir, args.threshold)
    serve_rc = compare_govern(args.dir, args.threshold) or serve_rc
    serve_rc = compare_multichip(args.dir, args.threshold) or serve_rc
    serve_rc = compare_cq(args.dir, args.threshold) or serve_rc
    serve_rc = compare_hist(args.dir, args.threshold) or serve_rc
    serve_rc = compare_infer(args.dir, args.threshold) or serve_rc

    arts = newest_pair(args.dir)
    usable = [(r, p, v) for r, p, v in arts if v is not None]
    for r, p, v in arts:
        if v is None:
            print(f"note: skipping r{r:02d} ({os.path.basename(p)}): "
                  f"failed run or no parseable headline")
    # both sides of the would-be pair: a leak-stamped artifact must
    # neither be banked NOR serve as the ratchet baseline
    for rnd, path, _v in usable[-2:]:
        if audit_refused(path, f"r{rnd:02d}") \
                or slo_refused(path, f"r{rnd:02d}") \
                or quality_refused(path, f"r{rnd:02d}"):
            return 1
    if len(usable) < 2:
        print(f"OK: {len(usable)} usable artifact(s) — nothing to compare")
        return serve_rc
    (r_prev, p_prev, prev), (r_new, p_new, new) = usable[-2], usable[-1]
    if slo_mixed_refused(p_prev, p_new, f"r{r_prev:02d}",
                         f"r{r_new:02d}") \
            or quality_mixed_refused(p_prev, p_new, f"r{r_prev:02d}",
                                     f"r{r_new:02d}"):
        return 1
    bp_prev, bp_new = backend_path(p_prev), backend_path(p_new)
    if bp_prev and bp_new and bp_prev != bp_new:
        print(f"FAIL: backend_path mismatch — r{r_prev:02d} ran on "
              f"{bp_prev!r} but r{r_new:02d} ran on {bp_new!r}; a "
              f"fallback round cannot stand in for an attached headline "
              f"(re-run the bench on the same backend)", file=sys.stderr)
        return 1
    gv_prev, gv_new = govern_enabled(p_prev), govern_enabled(p_new)
    if gv_prev is not None and gv_new is not None and gv_prev != gv_new:
        print(f"FAIL: govern mismatch — r{r_prev:02d} ran "
              f"{'governed' if gv_prev else 'static knobs'} but "
              f"r{r_new:02d} ran "
              f"{'governed' if gv_new else 'static knobs'}; an "
              f"adaptively-governed round cannot stand in for a "
              f"static-knob headline (or mask its regression) — re-run "
              f"the bench with the same HEATMAP_GOVERN setting",
              file=sys.stderr)
        return 1
    ms_prev, ms_new = mesh_stamp(p_prev), mesh_stamp(p_new)
    if ms_prev is not None and ms_new is not None and ms_prev != ms_new:
        print(f"FAIL: mesh mismatch — r{r_prev:02d} ran "
              f"{ms_prev[0]} device(s) in {ms_prev[1]!r} mode but "
              f"r{r_new:02d} ran {ms_new[0]} device(s) in "
              f"{ms_new[1]!r}; a mesh aggregate cannot stand in for "
              f"another device count or execution mode (or mask its "
              f"regression) — re-run the bench on the same mesh",
              file=sys.stderr)
        return 1
    sh_prev, sh_new = shard_count(p_prev), shard_count(p_new)
    if sh_prev is not None and sh_new is not None and sh_prev != sh_new:
        print(f"FAIL: shards mismatch — r{r_prev:02d} ran {sh_prev} "
              f"shard(s) but r{r_new:02d} ran {sh_new}; an N-shard "
              f"aggregate cannot stand in for a single-shard headline "
              f"(or mask its regression) — re-run the bench at the same "
              f"shard count", file=sys.stderr)
        return 1
    rs_prev, rs_new = reducer_set(p_prev), reducer_set(p_new)
    if rs_prev is not None and rs_new is not None and rs_prev != rs_new:
        print(f"FAIL: reducer-set mismatch — r{r_prev:02d} folded "
              f"{','.join(rs_prev)} but r{r_new:02d} folded "
              f"{','.join(rs_new)}; a composed-reducer round cannot "
              f"stand in for a count-only headline (or mask its "
              f"regression) — re-run the bench with the same "
              f"HEATMAP_REDUCERS", file=sys.stderr)
        return 1
    drop = (prev - new) / prev
    line = (f"r{r_prev:02d} {prev:,.0f} ev/s -> r{r_new:02d} "
            f"{new:,.0f} ev/s ({-drop:+.1%})")
    if drop > args.threshold:
        print(f"FAIL: headline regression beyond {args.threshold:.0%}: "
              f"{line}", file=sys.stderr)
        return 1
    print(f"OK: {line} within the {args.threshold:.0%} threshold")
    return serve_rc


if __name__ == "__main__":
    sys.exit(main())
