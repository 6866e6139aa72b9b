"""Measured-winner ``auto`` defaults from an operator-supplied bank.

``auto`` config values (merge impl, emit pull, in-program snap) can be
steered by a bank of on-chip measurements: a JSON file of units
(``{"units": {name: {"data": {...}}}}``) named by ``HEATMAP_HW_BANK``.
No bank ships with the checkout, so by default every ``auto`` falls
back to its static rule: the capacity-ratio merge rule
(engine.step.merge_batch), ``prefix`` emit pulls off the CPU, and the
XLA in-program snap.  Entries only apply when their ``_platform`` AND
``_device_kind`` stamps match the live JAX backend, so a bank harvested
on one backend never steers another.  A bank describes the machine it
was measured on — how the chip is attached, not just its kind — so
point ``HEATMAP_HW_BANK`` only at a bank measured on the deployment's
own hardware.  Every banked steer is logged at INFO so it is visible in
production logs.

The reference has no analogue: its perf knobs are Spark conf
(/root/reference/heatmap_stream.py:241-249) tuned by hand.
"""
from __future__ import annotations

import json
import logging
import os
from typing import Any

log = logging.getLogger(__name__)

# one INFO line per distinct (knob, winner) per process — banked steers
# must be visible in production logs without spamming per trace
_logged: "set[tuple[str, str]]" = set()


def _steer(knob: str, winner: str) -> str:
    if (knob, winner) not in _logged:
        _logged.add((knob, winner))
        log.info("hardware bank steers %s=%r (measured winner from %s; "
                 "unset HEATMAP_HW_BANK to disable)", knob, winner,
                 _bank_path())
    return winner


# (path, mtime) -> units dict; the bank is small and read at most a few
# times per process (config/trace time), so one mtime-keyed slot is
# plenty.
_cache: "tuple[tuple[str, float], dict[str, Any]] | None" = None


def _bank_path() -> str:
    return os.environ.get("HEATMAP_HW_BANK", "")


def units() -> "dict[str, Any]":
    """Banked unit-name -> data mapping, or {} when no bank exists."""
    global _cache
    path = _bank_path()
    if not path:
        return {}
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        return {}
    key = (path, mtime)
    if _cache is not None and _cache[0] == key:
        return _cache[1]
    try:
        with open(path, encoding="utf-8") as fh:
            data = {name: entry["data"]
                    for name, entry in json.load(fh)["units"].items()
                    if isinstance(entry, dict) and "data" in entry}
    except (OSError, ValueError, KeyError, TypeError):
        return {}
    _cache = (key, data)
    return data


def _platform() -> str:
    import jax

    return jax.default_backend()


def _device_kind() -> "str | None":
    import jax

    try:
        return jax.devices()[0].device_kind
    except Exception:  # noqa: BLE001 - no devices / backend init failure
        return None


def _on_platform(name: str) -> "dict[str, Any] | None":
    """Unit data iff its platform AND device-kind stamps match the live
    backend: a winner measured on one chip kind must not steer another.
    Entries without a device-kind stamp (CPU units) gate on platform
    only.
    """
    data = units().get(name)
    if not isinstance(data, dict):
        return None
    if data.get("_platform") != _platform():
        return None
    stamped = data.get("_device_kind")
    if stamped is not None and stamped != _device_kind():
        return None
    return data


def merge_winner() -> "str | None":
    """Unanimous banked merge-impl winner for this platform, else None.

    All three shape units (streaming/backfill/balanced) must be banked
    for the live platform and agree; a split verdict falls back to the
    static capacity-ratio heuristic in engine.step.merge_batch.
    """
    winners = set()
    for name in ("merge_stream", "merge_backfill", "merge_balanced"):
        data = _on_platform(name)
        if data is None or data.get("winner") not in ("sort", "rank",
                                                      "probe"):
            return None
        winners.add(data["winner"])
    if len(winners) != 1:
        return None
    return _steer("merge_impl", winners.pop())


def pull_winner(n_pairs: int = 1) -> "str | None":
    """Majority banked emit-pull winner for this platform, else None.

    ``n_pairs`` is the number of fused (res, window) pairs the program
    will run.  The single-pair ``pull`` unit's verdict does NOT
    transfer to fused programs: a full pull moves n_pairs whole emit
    buffers per batch, so D2H bytes weigh more as width grows.  For
    n_pairs > 1, banked fused A/Bs (same shape, pull flipped) vote by
    measured events_per_sec; single-pair verdict is the fallback when
    no fused A/B is banked for this platform.
    """
    if n_pairs > 1:
        votes = []
        for base in ("hex_pyramid", "multi_window"):
            a = _on_platform(base)
            b = _on_platform(base + "_prefix")
            if (a and b and a.get("events_per_sec")
                    and b.get("events_per_sec")):
                votes.append("prefix" if b["events_per_sec"]
                             > a["events_per_sec"] else "full")
        if votes:
            prefix = sum(1 for v in votes if v == "prefix")
            return _steer("emit_pull(fused)",
                          "prefix" if prefix * 2 >= len(votes) else "full")
    data = _on_platform("pull")
    if data is None:
        return None
    rows = data.get("rows") or []
    votes = [r.get("winner") for r in rows
             if r.get("winner") in ("full", "prefix")]
    if not votes:
        return None
    full = sum(1 for v in votes if v == "full")
    return _steer("emit_pull", "full" if full * 2 > len(votes)
                  else "prefix")


def snap_winner() -> "str | None":
    """"pallas" iff the banked A/B passes the decision rule.

    Rule: the kernel lowers,
    wins at the operating res 8, and agrees with the XLA snap on
    >99.7% of 1M uniform points (disagreements are f32 cell-edge
    rounding; the snap impl is pinned across checkpoint resume, see
    stream/checkpoint.py, so a mid-stream impl change cannot re-key
    cells).  Anything else -> None (static default: in-program XLA).
    """
    data = _on_platform("snap_pal_r8")
    if (data is None or data.get("lowering") != "ok"
            or data.get("speedup_vs_xla", 0.0) <= 1.0
            or data.get("agree_frac", 0.0) <= 0.997):
        return None
    return _steer("h3_snap", "pallas")
