#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 benchmark/run.py --workload r9_replay --seed 12345 \\
        --seconds 30 --trace 0

Cells, configurations, traffic mixes and per-layer metrics are named in
``BENCHMARK.json`` at the checkout's root and found by name under
``benchmark/``.  The run needs a TPU with as many chips as the cell
asks for; elsewhere it exits non-zero and prints no result.  The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` a ``breakdown``,
and last ``checks``: every number compared beside its limit); the
comparison is repeated as the last lines of standard error.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb into this directory")
    args = ap.parse_args(argv)

    from benchmark import harness, spec

    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_process=T_PROCESS,
                               keep_trace=args.keep_trace)
    except (harness.NoAccelerator, spec.SpecError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
