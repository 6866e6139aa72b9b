"""Fold: device milliseconds per run of the fused step program spent in
the pairs' merges (ops under the program's ``merge_r<res>_w<min>``
named scopes, every pair: the probe and the fast, insert and slow
tiers).  Read from the traced window's op metadata
(``benchmark/scopes.py``); None where the program names no such
scope."""

from benchmark import scopes


def read(run):
    return scopes.device_ms_per_step(scopes.for_run(run), "merge_")
