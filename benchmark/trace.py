"""Reduction of a JAX profiler trace (``*.xplane.pb``) to numbers.

Device planes are those named ``/device:TPU:<n>``.  On each, the
``XLA Ops`` line holds one event per operation run; their union is the
time the device was busy.  The ``XLA Modules`` line holds one event
per program run, named after the jitted function.  Host threads sit on
the ``/host:CPU`` plane; the benchmark's own ``TraceAnnotation`` spans
(``bench.*``, ``source.*``, ``store.*``) are among their events and
label what the host was doing during each idle gap on the device.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIXES = ("bench.", "source.", "store.")
TOP = 10


@dataclass
class DeviceTrace:
    ops: list = field(default_factory=list)       # (name, start_ns, dur_ns)
    modules: list = field(default_factory=list)   # (name, start_ns, dur_ns)


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(found, key=os.path.getmtime)


def load(path: str):
    """(devices, host spans) of one trace file: ``devices`` maps each
    device plane's name to its DeviceTrace; host spans are (name,
    start_ns, dur_ns) of the benchmark's own annotations."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            dev = DeviceTrace()
            for line in plane.lines:
                dest = (dev.ops if line.name == OPS_LINE
                        else dev.modules if line.name == MODULES_LINE
                        else None)
                if dest is None:
                    continue
                for ev in line.events:
                    dest.append((op_name(ev.name), int(ev.start_ns),
                                 int(ev.duration_ns)))
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)))
    return devices, host


_KIND = re.compile(r"\s([a-z][a-z0-9-]*)\(")


def op_name(text: str) -> str:
    """``%fusion.223 (fusion)`` from an op's full HLO text."""
    head, _, rest = text.partition(" = ")
    kind = _KIND.search(rest)
    return f"{head} ({kind.group(1)})" if kind else head


def union(intervals) -> list:
    """Merged [start, end) intervals of (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _label(host, t_ns: int) -> str:
    """The innermost benchmark span open at ``t_ns``, or "none"."""
    best = None
    for name, s, d in host:
        if s <= t_ns < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "none"


def reduce(devices: dict, host: list, t0_ns: int, t1_ns: int,
           step_names=("_step",)) -> dict:
    """Numbers over the traced window [t0_ns, t1_ns) (profiler clock):

    - ``busy_s``: seconds in which an op ran, mean over devices;
    - ``window_s``: the window's length;
    - ``step_device_s`` / ``steps``: device seconds and runs of the
      programs whose name contains one of ``step_names``, on the device
      that spent most in them;
    - ``device_ops``: the ops that took most time, device seconds
      summed over devices and divided by their number;
    - ``idle_gaps``: the longest gaps on the first device, each named by
      the benchmark span the host was in at its midpoint.
    """
    if not devices:
        return {}
    window = (t1_ns - t0_ns) / 1e9
    busy, per_op = [], {}
    for dev in devices.values():
        ivs = [(max(s, t0_ns), min(s + d, t1_ns)) for _, s, d in dev.ops
               if s + d > t0_ns and s < t1_ns]
        busy.append(sum(e - s for s, e in union(ivs)) / 1e9)
        for name, s, d in dev.ops:
            if t0_ns <= s < t1_ns:
                per_op[name] = per_op.get(name, 0) + d
    n_dev = len(devices)
    step = []
    for dev in devices.values():
        runs = [d for name, s, d in dev.modules
                if t0_ns <= s < t1_ns and any(k in name for k in step_names)]
        step.append((sum(runs) / 1e9, len(runs)))
    step_s, steps = max(step)
    first = devices[sorted(devices)[0]]
    merged = union((s, s + d) for _, s, d in first.ops
                   if s + d > t0_ns and s < t1_ns)
    edges = [t0_ns] + [x for iv in merged for x in iv] + [t1_ns]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": sum(busy) / n_dev,
        "window_s": window,
        "step_device_s": step_s,
        "steps": steps,
        "device_ops": [[k, v / 1e9 / n_dev] for k, v in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_label(host, (s + e) // 2), (e - s) / 1e9]
                      for s, e in gaps[:TOP]],
    }
