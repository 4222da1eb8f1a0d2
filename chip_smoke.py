#!/usr/bin/env python3
"""Bring-up smoke test of UniGPS on a TPU, through the user entry points.

    python chip_smoke.py              # one chip: analytics, serving, callback
    python chip_smoke.py --chips 4    # four chips: the distributed engine only

One chip (default knobs: kernel="auto", engine="pushpull"):
  * analytics — Graph500 graph500-22 as LDBC Graphalytics defines it (RMAT
    A,B,C = .57,.19,.19, edge factor 16, undirected, uniform weights),
    BFS, SSSP, PageRank (20 iterations) and WCC through `UniGPS()`;
  * serving — a `ServingSession` warmed up, then 64 seeded sssp/bfs
    submissions pumped to completion with the retrace sentinel on "error"
    and a compile counter around the request loop (must stay 0);
  * callback — one SSSP with engine="callback" on a scale-16 RMAT graph,
    under a wall-clock limit so a nested-dispatch hang fails the run.
Four chips: `run_vcprog_distributed` SSSP and PageRank on graph500-19 over
a 4-device mesh, for each exchange schedule, checking that every part
lives on its own device. (Each of the six calls partitions the graph on
the host again, and the partition pads its buckets to about 5x the edge
count, so every scale step doubles minutes of four-chip time.)

Every result is compared with a plain host reference (scipy.sparse.csgraph
for BFS, SSSP and WCC; numpy power iteration for PageRank). Earlier lines
report per phase: compile and run seconds, peak device bytes, and the
Pallas kernels found in the compiled HLO (`tpu_custom_call`). The last line
is one JSON object, {"ok": true, "device": {...}}. The script exits
non-zero, printing no such line, when any check fails or when JAX finds no
TPU.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
DUMP = os.path.join(HERE, ".smoke_dump")
GRAPH500 = dict(edge_factor=16, a=0.57, b=0.19, c=0.19, weighted=True,
                directed=False)


def log(tag: str, **fields):
    print(f"{tag} " + json.dumps(fields, default=str), flush=True)


# ---------------------------------------------------------------------------
# Host references (independent of the code under test)
# ---------------------------------------------------------------------------

def _csr(g):
    """Out-edge CSR of the graph with its parallel edges kept (scipy's
    shortest paths relax each stored entry, so duplicates act as a min)."""
    import numpy as np
    import scipy.sparse as sp
    _, dst, ep = g.src_sorted()
    w = ep.get("weight", np.ones(dst.shape[0], np.float32))
    return sp.csr_matrix((w.astype(np.float64), dst, g.out_indptr),
                         shape=(g.num_vertices,) * 2)


def ref_bfs(csr, root):
    import numpy as np
    from scipy.sparse import csgraph
    order, pred = csgraph.breadth_first_order(csr, root, directed=True,
                                              return_predecessors=True)
    depth = np.full(csr.shape[0], -1, np.int64)
    depth[root] = 0
    rest = order[1:]
    while True:  # one pass per BFS level
        nxt = np.where(depth[pred[rest]] >= 0, depth[pred[rest]] + 1, -1)
        if np.array_equal(nxt, depth[rest]):
            return depth
        depth[rest] = nxt


def ref_sssp(csr, root):
    from scipy.sparse import csgraph
    return csgraph.dijkstra(csr, directed=True, indices=root)


def ref_wcc(csr):
    """Each vertex labeled with the smallest vertex id of its weakly
    connected component (the label-propagation fixpoint)."""
    import numpy as np
    from scipy.sparse import csgraph
    n, comp = csgraph.connected_components(csr, directed=True,
                                           connection="weak")
    low = np.full(n, csr.shape[0], np.int64)
    np.minimum.at(low, comp, np.arange(csr.shape[0]))
    return low[comp]


def ref_pagerank(csr, num_iters=20, damping=0.85):
    """PageRankProgram's semantics: the uniform start is kept in round 1,
    then num_iters - 1 synchronous updates; dangling mass is dropped."""
    import numpy as np
    import scipy.sparse as sp
    n = csr.shape[0]
    ones = sp.csr_matrix((np.ones_like(csr.data), csr.indices, csr.indptr),
                         shape=csr.shape)
    inv_deg = 1.0 / np.maximum(np.diff(csr.indptr), 1)
    rank = np.full(n, 1.0 / n)
    for _ in range(num_iters - 1):
        rank = (1.0 - damping) / n + damping * (ones.T @ (rank * inv_deg))
    return rank


def check(name, ok, **detail):
    if not ok:
        raise AssertionError(f"{name} does not match the host reference: "
                             f"{detail}")


def check_sssp(name, got, want):
    import numpy as np
    got = np.asarray(got, np.float64)
    fin = np.isfinite(want)
    check(name, np.array_equal(np.isfinite(got), fin),
          unreachable_mismatch=int((np.isfinite(got) != fin).sum()))
    err = float(np.max(np.abs(got[fin] - want[fin]) /
                       np.maximum(np.abs(want[fin]), 1.0), initial=0.0))
    check(name, err <= 1e-5, max_rel_err=err)
    return err


def check_equal(name, got, want):
    import numpy as np
    bad = int((np.asarray(got) != np.asarray(want)).sum())
    check(name, bad == 0, mismatches=bad)
    return 0.0


def check_pagerank(name, got, want):
    import numpy as np
    err = float(np.max(np.abs(np.asarray(got, np.float64) - want))
                / np.max(want))
    check(name, err <= 1e-4, max_rel_err=err)
    return err


# ---------------------------------------------------------------------------
# Instrumentation: compile time, kernels in the compiled HLO, device memory
# ---------------------------------------------------------------------------

class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and persistent
    cache hits, from jax.monitoring events (listeners cannot be removed,
    so one instance lives for the process)."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.backend_compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.EVENTS[-1]:
            self.backend_compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.seconds, self.backend_compiles, self.cache_hits)


_KERNEL_RE = re.compile(r'op_name="[^"]*?([A-Za-z0-9_]+)/pallas_call')


def kernels_in_dump(seen: set) -> set:
    """Pallas kernel names in HLO modules dumped since `seen` (updated in
    place): the name of every tpu_custom_call of the optimized modules."""
    names = set()
    for path in glob.glob(os.path.join(DUMP, "*after_optimizations.txt")):
        if path in seen:
            continue
        seen.add(path)
        with open(path) as f:
            for line in f:
                if "tpu_custom_call" in line:
                    m = _KERNEL_RE.search(line)
                    names.add(m.group(1) if m else "<unnamed>")
    return names


class Phase:
    """Times one phase and reports its compile share, peak device bytes
    and the kernels compiled during it."""

    def __init__(self, name, clock, dump_seen):
        self.name, self.clock, self.dump_seen = name, clock, dump_seen

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.clock.snapshot() if self.clock else (0.0, 0, 0)
        return self

    def __exit__(self, *exc):
        if exc[0] is not None:
            return False
        import jax
        wall = time.perf_counter() - self.t0
        c1 = self.clock.snapshot() if self.clock else (0.0, 0, 0)
        stats = jax.devices()[0].memory_stats() or {}
        self.report = dict(
            wall_s=wall, compile_s=c1[0] - self.c0[0],
            run_s=wall - (c1[0] - self.c0[0]),
            backend_compiles=c1[1] - self.c0[1],
            cache_hits=c1[2] - self.c0[2],
            peak_bytes_in_use=stats.get("peak_bytes_in_use"),
            kernels=sorted(kernels_in_dump(self.dump_seen))
            if self.dump_seen is not None else [])
        log(f"phase {self.name}", **self.report)
        return False


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t0


def _roots(g, seed, n):
    import numpy as np
    rng = np.random.default_rng(seed)
    cand = np.flatnonzero(g.out_degree > 0)
    return [int(r) for r in rng.choice(cand, size=n, replace=False)]


def graph500(scale, seed):
    from repro.core import io
    return io.rmat_graph(scale=scale, seed=seed, **GRAPH500)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def analytics_phase(g, seed, clock=None, dump_seen=None):
    """BFS, SSSP, PageRank and WCC through UniGPS() against the host
    references. Returns the per-algorithm report."""
    import repro
    csr, ref_s = timed(_csr, g)
    root = _roots(g, seed, 1)[0]
    uni = repro.UniGPS()
    out = {"root": root, "reference_csr_s": ref_s}
    algos = {
        "bfs": (lambda: uni.bfs(g, root=root),
                lambda: ref_bfs(csr, root), check_equal),
        "sssp": (lambda: uni.sssp(g, root=root),
                 lambda: ref_sssp(csr, root), check_sssp),
        "pagerank": (lambda: uni.pagerank(g, num_iters=20),
                     lambda: ref_pagerank(csr, 20), check_pagerank),
        "wcc": (lambda: uni.connected_components(g),
                lambda: ref_wcc(csr), check_equal),
    }
    for name, (run, ref, cmp) in algos.items():
        with Phase(f"analytics.{name}", clock, dump_seen) as ph:
            (got, info), _ = timed(run)
        want, ref_s = timed(ref)
        err = cmp(f"analytics.{name}", got, want)
        out[name] = dict(ph.report, iterations=info["iterations"],
                         kernel_on=info.get("kernel_on"),
                         reference_s=ref_s, max_rel_err=err)
    return out


def serving_phase(g, seed, n_requests=64, clock=None, dump_seen=None):
    """Warm a ServingSession, pump seeded sssp/bfs submissions through the
    micro-batcher, and check a sample of answers against the reference.
    The request loop must compile nothing."""
    import numpy as np
    import repro
    from repro.lint.retrace import CompileWatcher
    # occupancy-driven flushes (the deadline is far away): every batch is
    # one full lane bucket, the width warmup compiled
    sess = repro.UniGPS().serve(g, slack=0.05, lane_buckets=(1, 8),
                                occupancy=8, deadline_ms=60_000.0,
                                sentinel="error")
    with Phase("serving.warmup", clock, dump_seen) as warm:
        sess.warmup(ops=("sssp", "bfs"))
        # one throwaway batch per op through submit/pump compiles the
        # per-lane result slicing the request loop replays
        for op in ("sssp", "bfs"):
            for t in [sess.submit(op, s) for s in _roots(g, seed + 3, 8)]:
                t.result()
    sources = _roots(g, seed + 1, n_requests)
    ops = ["sssp" if i % 2 == 0 else "bfs" for i in range(n_requests)]
    with Phase("serving.requests", clock, dump_seen) as req:
        with CompileWatcher() as watcher:
            tickets = []
            for op, s in zip(ops, sources):
                tickets.append(sess.submit(op, s))
                sess.pump()
            answers = [t.result() for t in tickets]
    check("serving.compiles", watcher.count == 0, compiles=watcher.count)
    check("serving.sentinel", sess.sentinel_trips == 0,
          trips=sess.sentinel_trips)
    csr = _csr(g)
    sample = {}
    for i in (0, 1):  # one sssp and one bfs answer
        op, src, (got, _) = ops[i], sources[i], answers[i]
        got = np.asarray(got)
        if op == "sssp":
            got = np.where(got >= 3.4e38 * 0.5, np.inf, got)
            sample[op] = check_sssp("serving.sssp", got, ref_sssp(csr, src))
        else:
            got = np.where(got >= 2**31 - 1, -1, got.astype(np.int64))
            sample[op] = check_equal("serving.bfs", got, ref_bfs(csr, src))
    info = sess.info()
    return {"warmup": warm.report, "requests": req.report,
            "n_requests": n_requests, "request_compiles": watcher.count,
            "sentinel_trips": sess.sentinel_trips, "sample_max_rel_err":
            sample, "cache": info.get("cache")}


def callback_phase(g, seed, limit_s=600.0, clock=None, dump_seen=None):
    """One SSSP on the callback engine (host-side UDFs inside the compiled
    loop), failing the whole process if it outlives `limit_s`."""
    import repro
    root = _roots(g, seed + 2, 1)[0]

    def expire():
        print(f"callback phase exceeded its {limit_s:.0f} s limit "
              f"(nested dispatch hang?)", file=sys.stderr, flush=True)
        os._exit(3)

    timer = threading.Timer(limit_s, expire)
    timer.daemon = True
    timer.start()
    try:
        with Phase("callback.sssp", clock, dump_seen) as ph:
            got, info = repro.UniGPS().sssp(g, root=root, engine="callback")
    finally:
        timer.cancel()
    err = check_sssp("callback.sssp", got, ref_sssp(_csr(g), root))
    return dict(ph.report, root=root, iterations=info["iterations"],
                max_rel_err=err)


def distributed_phase(g, seed, num_parts=4, clock=None, dump_seen=None):
    """run_vcprog_distributed SSSP and PageRank over a num_parts-device
    mesh for every schedule; each part must sit on its own device."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core.engines.distributed import run_vcprog_distributed
    from repro.core.operators import PageRankProgram, SSSPProgram
    devices = jax.devices()[:num_parts]
    check("distributed.devices", len(devices) == num_parts,
          found=len(jax.devices()))
    mesh = Mesh(np.asarray(devices), ("graph",))
    csr, _ = timed(_csr, g)
    root = _roots(g, seed, 1)[0]
    want = {"sssp": ref_sssp(csr, root), "pagerank": ref_pagerank(csr, 20)}
    out = {"root": root}
    for schedule in ("allgather", "ring", "push"):
        for name in ("sssp", "pagerank"):
            prog, iters = ((SSSPProgram(root), 100) if name == "sssp"
                           else (PageRankProgram(g.num_vertices, 20), 20))
            with Phase(f"distributed.{schedule}.{name}", clock,
                       dump_seen) as ph:
                vp, info = run_vcprog_distributed(prog, g, iters, mesh=mesh,
                                                  schedule=schedule)
            parts = info["part_devices"]
            check(f"distributed.{schedule}.{name}.placement",
                  len(set(parts)) == num_parts, part_devices=parts)
            if name == "sssp":
                d = np.asarray(vp["distance"], np.float64)
                err = check_sssp(f"distributed.{schedule}.sssp",
                                 np.where(d >= 3.4e38 * 0.5, np.inf, d),
                                 want["sssp"])
            else:
                err = check_pagerank(f"distributed.{schedule}.pagerank",
                                     vp["rank"], want["pagerank"])
            out[f"{schedule}.{name}"] = dict(
                ph.report, iterations=info["iterations"],
                part_devices=parts, max_rel_err=err)
    return out


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the distributed engine on 4 chips")
    ap.add_argument("--scale", type=int, default=None,
                    help="Graph500 scale of the analytics phase (default "
                         "22) or the distributed phase (default 19)")
    ap.add_argument("--serve-scale", type=int, default=None,
                    help="Graph500 scale of the serving phase "
                         "(default: scale - 2)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    scale = args.scale or (19 if args.chips == 4 else 22)

    shutil.rmtree(DUMP, ignore_errors=True)
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_dump_to={DUMP}"
                               " --xla_dump_hlo_as_text")
    sys.path.insert(0, os.path.join(HERE, "src"))
    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (default backend "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 2
    from repro.envutil import enable_compile_cache
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    log("device", platform=dev.platform, kind=dev.device_kind,
        count=len(jax.devices()), compile_cache=cache_dir)
    clock = CompileClock()
    seen = set()
    try:
        if args.chips == 4:
            g, build_s = timed(graph500, scale, args.seed)
            log("graph", name=f"graph500-{scale}", vertices=g.num_vertices,
                edge_slots=g.num_edges, host_build_s=build_s)
            log("result distributed",
                **distributed_phase(g, args.seed, 4, clock, seen))
        else:
            g, build_s = timed(graph500, scale, args.seed)
            log("graph", name=f"graph500-{scale}", vertices=g.num_vertices,
                edge_slots=g.num_edges, host_build_s=build_s)
            log("result analytics",
                **analytics_phase(g, args.seed, clock, seen))
            del g
            serve_scale = args.serve_scale or scale - 2
            gs, build_s = timed(graph500, serve_scale, args.seed)
            log("graph", name=f"graph500-{serve_scale}",
                vertices=gs.num_vertices, edge_slots=gs.num_edges,
                host_build_s=build_s, reduced="serving: lane-batched "
                "queries gather Q rows per edge slot")
            log("result serving", **serving_phase(gs, args.seed, 64,
                                                  clock, seen))
            del gs
            gc = graph500(16, args.seed)
            log("result callback", **callback_phase(gc, args.seed, 600.0,
                                                    clock, seen))
        ran = kernels_in_dump(set())
        check("mosaic kernels", bool(ran), kernels=sorted(ran))
        log("kernels", compiled=sorted(ran))
    finally:
        shutil.rmtree(DUMP, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
