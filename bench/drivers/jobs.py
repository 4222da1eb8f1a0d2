"""Analytics job stream: whole jobs through the user entry point, back to
back.

Traffic file keys:
  algorithm   "sssp" (the `UniGPS` method it calls: `sssp(g, root=r)`)
  root        "max_out_degree": the highest out-degree vertex (lowest id
              on ties); its superstep count barely moves between seeds
  limits      {check name: limit}

Set-up runs one whole job, which compiles every program the window's jobs
replay. The window starts jobs back to back until `--seconds` have passed;
the job in flight always finishes, so a run completes at least one. EVPS
(LDBC Graphalytics) = jobs x (|V| + |E|) over the span from the first
job's call to the last job's host-returned result, with |E| the
undirected edges as generated.
"""
from __future__ import annotations

import time

import numpy as np

from bench.reference import algorithms as ref
from bench.reference import compare
from bench.reference import rounds


def _root(run) -> int:
    rule = run.traffic["root"]
    if rule != "max_out_degree":
        raise ValueError(f"unknown root rule {rule!r}")
    src, dst, _ = run.edges
    return int(np.argmax(compare.degrees(run.num_vertices, src, dst)))


def _job(run, root):
    import repro
    uni = repro.UniGPS()
    algo = run.traffic["algorithm"]
    if algo == "sssp":
        return lambda: uni.sssp(run.graph, root=root)
    raise ValueError(f"unknown algorithm {algo!r}")


def setup(run) -> dict:
    root = _root(run)
    job = _job(run, root)
    t = time.perf_counter()
    out, info = job()
    np.asarray(out)
    warm_s = time.perf_counter() - t
    return {"root": root, "job": job, "outputs": [],
            "spans": {"warmup_job_s": warm_s},
            "warm_iterations": int(info["iterations"])}


def window(run, state) -> dict:
    import jax
    job, outputs = state["job"], state["outputs"]
    iters = []
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("bench.job"):
            out, info = job()
            outputs.append(np.asarray(out))
        iters.append(int(info["iterations"]))
        t1 = time.perf_counter()
        if t1 - t0 >= run.seconds:
            break
    span = t1 - t0
    run.counters.update(jobs=len(outputs), supersteps=sum(iters),
                        iterations=iters, job_span_s=span)
    evps = len(outputs) * (run.num_vertices + run.num_edges) / span
    return {"attempted": len(outputs), "failed": 0, "root": state["root"],
            "jobs": len(outputs), "iterations": iters[:20], "span_s": span,
            "metrics": {"evps": evps}}


def release(run, state):
    state.pop("job", None)


def _compare(run, root, outputs) -> dict:
    """The numbers compared: each job's whole output against the plain
    reference over the benchmark's own edge list."""
    src, dst, w = run.edges
    want = ref.sssp(ref.csr(run.num_vertices, src, dst, w), [root])[0]
    rel, reach = 0.0, 0
    for got in outputs:
        r, m = compare.sssp_errors(got, want)
        rel, reach = max(rel, r), reach + m
    return compare.limits(run.traffic, {"sssp_rel_err": rel,
                                        "reach_mismatch": reach})


def check(run, state) -> dict:
    """Every job of the window against the reference; on traced runs also
    the plane's minimum bytes (reference rounds) for `plane_hbm_pct`."""
    checks = _compare(run, state["root"], state["outputs"])
    if run.trace:
        src, dst, w = run.edges
        _, k, slots = rounds.run_rounds(run.num_vertices, src, dst, w,
                                        root=state["root"])
        per_job = rounds.plane_bytes(run.num_vertices, slots, edge_props=1)
        run.counters["plane_bytes"] = per_job * len(state["outputs"])
        run.counters["reference_rounds"] = k
    return checks


def control(run) -> dict:
    """The reference in the program's place, summed in bfloat16, one step
    below the float32 the configuration states. Must fail a limit."""
    src, dst, w = run.edges
    root = _root(run)
    out, _, _ = rounds.run_rounds(run.num_vertices, src, dst, w, root=root,
                                  dtype="bfloat16")
    return _compare(run, root, [out.astype(np.float64)])
