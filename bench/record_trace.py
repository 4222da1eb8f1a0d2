#!/usr/bin/env python3
"""Record one traced job of a cell as a trace fixture for bench/tests.

    python3 bench/record_trace.py --workload graph500-21.sssp --scale 16 \
        --seed 1 --out bench/tests/data/sssp16.scoped.events.json

Generates the cell's graph at `--scale`, runs the driver's set-up (one
warm-up job), then profiles one more job inside the `bench.window`
annotation and writes its events as `bench.trace.extract` reads them
(host events under 1 us dropped), the scope of each device event in a
list beside them (`bench.scopes.extract` keeps it as a fourth element),
and their summary.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--scale", type=int, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import glob

    import jax
    import numpy as np
    from bench import harness, scopes, trace

    run = harness.prepare(args.workload, args.seed, require_tpu=False,
                          scale=args.scale)
    harness.build_graph(run)
    state = harness.driver_of(run).setup(run)
    tracedir = tempfile.mkdtemp(prefix="bench-record-")
    try:
        jax.profiler.start_trace(tracedir)
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            np.asarray(state["job"]()[0])
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(tracedir, "**", "*.xplane.pb"),
                          recursive=True)
        events = scopes.extract(path)
    finally:
        shutil.rmtree(tracedir, ignore_errors=True)
    events["host"] = [e for e in events["host"] if e[2] >= 1000]
    s = scopes.summarize(events)
    summary = {"busy_s": s["busy_s"], "window_s": s["window_s"],
               "gather_emit_pct": trace.busy_share(s, "gather_emit"),
               "scopes": s["scopes"], "host_spans": s["host_spans"]}
    about = (f"{run.traffic['algorithm'].upper()} job through UniGPS on "
             f"{run.cell['config'].split('-')[0]}-{args.scale} "
             f"({run.graph.num_edges:,} slots), one "
             f"{run.device.device_kind}, jax {jax.__version__}; events as "
             "bench.trace.extract reads them, host events under 1 us "
             "dropped; scopes: each device event's program scope")
    plain = {"device": {p: [e[:3] for e in ev]
                        for p, ev in events["device"].items()},
             "host": events["host"]}
    scoped = {p: [e[3] for e in ev] for p, ev in events["device"].items()}
    with open(args.out, "w") as f:
        json.dump({"about": about, "summary": summary, "events": plain,
                   "scopes": scoped}, f, separators=(",", ":"))
    print(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
