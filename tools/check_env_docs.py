#!/usr/bin/env python
"""Fail when a HEATMAP_* env knob read in heatmap_tpu/ is not in README.

The README §Configuration tables are the operator contract for the
flat-env configuration surface.  Nothing kept them honest: at PR 4 the
code read 46 distinct HEATMAP_* names and the README documented 33 —
a third of the knobs (device probe, profiler,
native-build cache, heartbeat plumbing) were discoverable only by
grepping the source.

The check is textual on purpose: it scans every ``heatmap_tpu/**/*.py``
for HEATMAP_-shaped tokens (so knobs read via getenv, os.environ
mappings, f-strings, and even ones only named in comments all count)
and requires each to appear in README.md.  Family prefixes that are
line-wrapped in prose (``HEATMAP_FLIGHTREC_`` + ``ALWAYS``) reduce to
their stem, which the full knob's README entry contains.

Run next to the suite (tests/test_check_env_docs.py makes it tier-1,
the same pattern as check_native_build / check_metrics_docs).
"""

from __future__ import annotations

import os
import re
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
KNOB_RE = re.compile(r"HEATMAP_[A-Z0-9_]*[A-Z0-9]")


def knobs_in_code(pkg_dir: str) -> "set[str]":
    knobs: set[str] = set()
    for dirpath, _dirs, files in os.walk(pkg_dir):
        if "__pycache__" in dirpath:
            continue
        for fn in files:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fn), encoding="utf-8") as fh:
                knobs.update(KNOB_RE.findall(fh.read()))
    return knobs


def main() -> int:
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as fh:
        readme = fh.read()
    knobs = knobs_in_code(os.path.join(REPO, "heatmap_tpu"))
    missing = sorted(k for k in knobs if k not in readme)
    if missing:
        print("FAIL: HEATMAP_* knobs read in heatmap_tpu/ but not "
              "documented in README.md:", file=sys.stderr)
        for k in missing:
            print(f"  - {k}", file=sys.stderr)
        print("(add each to the README §Configuration tables)",
              file=sys.stderr)
        return 1
    print(f"OK: {len(knobs)} HEATMAP_* knobs all appear in README.md")
    return 0


if __name__ == "__main__":
    sys.exit(main())
