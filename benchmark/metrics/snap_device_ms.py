"""Fold: device milliseconds per run of the fused step program spent in
the H3 snap (ops under the program's ``snap_r<res>`` named scopes, every
resolution; on a host-snapped feed the scopes hold only the valid-mask).
Read from the traced window's op metadata (``benchmark/scopes.py``);
None where the program names no such scope."""

from benchmark import scopes


def read(run):
    return scopes.device_ms_per_step(scopes.for_run(run), "snap_")
