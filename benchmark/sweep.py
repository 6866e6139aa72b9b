#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains: run it once at
each offered rate, one process per run, and print for each the backlog
left at the window's close and the freshness.  The rate a cell offers
is then fixed in its mix file; this runs only when a cell is defined.

    python3 benchmark/sweep.py --workload <open-loop cell> --seconds 20 \\
        --rates 60000 90000 120000
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys

RUN = """
import json, sys
sys.path.insert(0, ".")
from benchmark import harness
out = harness.run_cell(sys.argv[1], int(sys.argv[2]), float(sys.argv[3]),
                       False, scale={"mix": {"rate_events_per_s": float(sys.argv[4])}})
print(json.dumps(out))
"""


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    for rate in args.rates:
        proc = subprocess.run(
            [sys.executable, "-c", RUN, args.workload, str(args.seed),
             str(args.seconds), str(rate)], capture_output=True, text=True)
        backlog = [ln for ln in proc.stderr.splitlines()
                   if ln.startswith("# window")]
        for ln in proc.stderr.splitlines():
            if "Compiling " in ln:
                print(ln[:300], file=sys.stderr)
        line = proc.stdout.strip().splitlines()[-1:] if proc.stdout else []
        out = json.loads(line[0]) if line else {}
        print(json.dumps({"rate": rate, "rc": proc.returncode,
                          "window": backlog[-1] if backlog else None,
                          "correct": out.get("correct"),
                          "metrics": out.get("metrics")}), flush=True)
        if proc.returncode:
            print(proc.stderr[-2000:], file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
