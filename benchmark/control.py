#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: the plain
reference put in the program's place, computed in bfloat16 (the
precision below the float32 the configurations state for their sums),
compared by ``check`` with the float64 reference exactly as a run's
output is.  It has to come out not correct.

    python3 benchmark/control.py --workload r9_replay --seed 7 \\
        --events 20000000

``--events`` is how many events a run of the cell sent (a run prints it
as ``events_sent``).  The benchmark's own runs never run this; it needs
no chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def readings(workload: str, seed: int, events: int, spec=None,
             scale: dict | None = None) -> dict:
    """The numbers ``check`` compares, for the bfloat16 control, over the
    first ``events`` events of the cell's traffic sent in full feed
    batches."""
    import ml_dtypes
    import numpy as np

    from benchmark import check, harness, spec as specmod, traffic

    spec = spec or specmod.Spec.load()
    cfg_file, mix, cfg = harness.cell_config(spec, spec.workload(workload),
                                             scale)
    source = traffic.make_source(mix, cfg_file, seed)
    if mix["arrival"] == "open":
        source.segments.append((0, time.time()))
    source.consumed = events
    source.polls = [(g, min(g + cfg.batch_size, events))
                    for g in range(0, events, cfg.batch_size)]
    ref, ev = check.reference(source, cfg)
    ctl, _ = check.reference(source, cfg, precision="bf16", ev=ev)
    numbers = check.compare_tiles(ctl, ref)
    # positions: each vehicle's newest event, its coordinates rounded to
    # bfloat16
    newest = np.full(int(ev["vid"].max()) + 1, np.iinfo(np.int64).min)
    np.maximum.at(newest, ev["vid"], ev["ts"].astype(np.int64))
    at = np.nonzero(ev["ts"] == newest[ev["vid"]])[0]
    _, first = np.unique(ev["vid"][at], return_index=True)

    class _T:
        def __init__(self, t):
            self.t = t

        def timestamp(self):
            return self.t

    def bf16(x):
        return float(np.float32(x).astype(ml_dtypes.bfloat16))

    docs = [{"vehicleId": f"veh-{ev['vid'][i]}", "ts": _T(int(ev["ts"][i])),
             "loc": {"coordinates": [bf16(ev["lng"][i]), bf16(ev["lat"][i])]}}
            for i in at[first].tolist()]
    numbers["positions_gap"] = check.compare_positions(docs, ev)
    return numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--events", type=int, required=True)
    args = ap.parse_args(argv)
    from benchmark import check

    numbers = readings(args.workload, args.seed, args.events)
    lim = check.limits()
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "control_correct": check.judge(numbers, lim),
                      "numbers": numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
