#!/usr/bin/env python3
"""Read a cell's control on the chip: the plain reference put in the
program's place one step below the configuration's precision (see each
driver's `control`). Prints one line per seed with every number compared
beside its limit; the control must fail at least one of them.

    python3 bench/controls.py --workload graph500-21.sssp --seeds 11 12 13

Not part of a benchmark run. The program's own readings (the other side
of each limit) come from the runs of bench/run.py.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=25.0,
                    help="window length the serving schedule is drawn for")
    args = ap.parse_args(argv)
    from bench import harness
    for seed in args.seeds:
        t = time.perf_counter()
        run = harness.prepare(args.workload, seed, args.seconds)
        harness.build_graph(run, program=False)
        checks = harness.driver_of(run).control(run)
        failed = [k for k, c in checks.items() if c["value"] > c["limit"]]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_fails": failed, "checks": checks,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
