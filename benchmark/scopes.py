"""Device time of the fused step by the program's named scopes.

Each op of a traced ``_step`` run carries the JAX name stack it was
traced under (``benchmark/xplane.py``): ``jit(_step)/merge_r9_w5/
jit(_merge_fastpath)/cond/branch_0_fun/cond/branch_1_fun/tier_insert/
gather:``.  Its scope path is what follows the leading ``jit(_step…)``,
without the primitive at the end and without lax's control-flow
components (``cond``, ``branch_<n>_fun``, ``while``, ``body``, …):
``merge_r9_w5/jit(_merge_fastpath)/tier_insert``.  Its scope is the
path's first component.

Only leaf ops count: an op whose ``hlo_category`` is ``conditional``,
``while`` or ``call`` is a container whose event spans the ops it
holds.  The sums are over the ops that start inside a ``_step`` run in
the traced window, on the device that spent most in those runs, so
they add up to that device's ``step_device_s`` less the gaps between
ops inside a run.

The trace a run of the harness recorded lies in the run's scratch
directory, beside its checkpoint directory (``harness.run_cell``);
``for_run`` finds it there and reduces it once per run.
"""

from __future__ import annotations

import bisect
import os
import re

from benchmark import trace as tr
from benchmark import xplane

CONTAINERS = frozenset({"conditional", "while", "call"})
HOST_PREFIXES = tr.HOST_PREFIXES + ("heatmap.",)
PATH_DEPTH = 3
_CONTROL = re.compile(r"cond|while|body|branch_\d+_fun")


def scope_path(tf_op: str, step_names=("_step",)) -> tuple[str, ...] | None:
    """The scope components of ``tf_op`` under a step program, or None
    when the op is not in one (``()`` when it is, unscoped)."""
    parts = tf_op.split("/")
    head = parts[0]
    if not (head.startswith("jit(") and any(k in head for k in step_names)):
        return None
    return tuple(p for p in parts[1:-1] if not _CONTROL.fullmatch(p))


def load(path: str):
    """(devices, host spans) as ``trace.load`` reads them, but with each
    op's full event name (the key of its metadata) and with the
    program's own ``heatmap.*`` spans among the host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(xplane.DEVICE_PREFIX):
            dev = tr.DeviceTrace()
            for line in plane.lines:
                dest = (dev.ops if line.name == tr.OPS_LINE
                        else dev.modules if line.name == tr.MODULES_LINE
                        else None)
                if dest is None:
                    continue
                for ev in line.events:
                    dest.append((ev.name, int(ev.start_ns),
                                 int(ev.duration_ns)))
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)))
    return devices, host


def reduce(devices: dict, meta: dict, t0_ns: int, t1_ns: int,
           step_names=("_step",)) -> dict:
    """Over the ``_step`` runs that start in [t0_ns, t1_ns), on the
    busiest device:

    - ``step_device_s`` / ``steps``: as ``trace.reduce`` reads them;
    - ``leaf_s``: device seconds of the leaf ops inside those runs;
    - ``scopes``: leaf seconds per scope;
    - ``scope_paths``: leaf seconds per scope path, to PATH_DEPTH;
    - ``unscoped_s``: leaf seconds of ops with no scope (no name stack,
      or none below the step's own).
    """
    best = None
    for name, dev in devices.items():
        runs = sorted((s, s + d) for n, s, d in dev.modules
                      if t0_ns <= s < t1_ns
                      and any(k in n for k in step_names))
        total = sum(e - s for s, e in runs)
        if best is None or total > best[0]:
            best = (total, name, runs)
    if best is None:
        return {}
    total, name, runs = best
    ops_meta = meta.get(name, {})
    scopes, paths = {}, {}
    leaf = unscoped = 0
    starts = [s for s, _ in runs]
    for op, s, d in devices[name].ops:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= runs[i][1]:
            continue
        m = ops_meta.get(op)
        if m is not None and m.category in CONTAINERS:
            continue
        leaf += d
        path = scope_path(m.tf_op, step_names) if m is not None else None
        if not path:
            unscoped += d
            continue
        scopes[path[0]] = scopes.get(path[0], 0) + d
        key = "/".join(path[:PATH_DEPTH])
        paths[key] = paths.get(key, 0) + d
    return {
        "step_device_s": total / 1e9,
        "steps": len(runs),
        "leaf_s": leaf / 1e9,
        "scopes": {k: v / 1e9 for k, v in sorted(scopes.items())},
        "scope_paths": {k: v / 1e9 for k, v in sorted(paths.items())},
        "unscoped_s": unscoped / 1e9,
    }


def reduce_file(path: str) -> dict | None:
    """``reduce`` over the ``bench.window`` span of the trace at
    ``path``, with ``trace.reduce``'s ``idle_gaps`` named by the host
    spans ``load`` keeps (the program's ``heatmap.*`` ones included);
    None without a window."""
    devices, host = load(path)
    window = [(s, s + d) for n, s, d in host if n == "bench.window"]
    if not devices or not window:
        return None
    out = reduce(devices, xplane.read(path), *window[0])
    out["idle_gaps"] = tr.reduce(devices, host, *window[0])["idle_gaps"]
    return out


def for_run(run) -> dict | None:
    """The reduction of a traced harness run's trace, or None where the
    run was not traced or left no trace."""
    if not getattr(run, "trace", None):
        return None
    ckpt = getattr(getattr(getattr(run, "rt", None), "cfg", None),
                   "checkpoint_dir", None)
    if not ckpt:
        return None
    try:
        path = tr.find_xplane(os.path.join(os.path.dirname(ckpt), "trace"))
    except FileNotFoundError:
        return None
    cached = getattr(run, "scope_reduction", None)
    if cached is None or cached[0] != path:
        cached = run.scope_reduction = (path, reduce_file(path))
    return cached[1]


def device_ms_per_step(red: dict | None, prefix: str) -> float | None:
    """Device milliseconds per ``_step`` run of the reduction ``red`` in
    the scopes whose name starts with ``prefix``; None where it has no
    such scope."""
    if not red or not red.get("steps"):
        return None
    secs = [v for k, v in red["scopes"].items() if k.startswith(prefix)]
    if not secs:
        return None
    return 1e3 * sum(secs) / red["steps"]
