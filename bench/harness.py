"""One run of one benchmark cell.

`run_cell` reads the cell from BENCHMARK.json, its configuration from
`bench/configs/<config>.json` and its traffic mix from
`bench/traffic/<traffic>.json`; the mix names the general driver in
`bench/drivers/` that serves it. Each per-layer metric is a reader in
`bench/metrics/<metric>.py`. Nothing here names a cell, a configuration,
a mix or a metric: a new one is new files and new BENCHMARK.json entries.

A run: generate the graph on the device from the seed, hand the edge
list to the program's own host build, let the driver warm up the cell's
own shapes (all of that is `setup_s`), measure for `--seconds`, free the
program's state, check every answer of the window against the plain
reference, and print the result as the last line of standard output.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(tag: str, **fields):
    """An earlier line of standard output (never the result line)."""
    print(f"{tag} " + json.dumps(fields, default=str), flush=True)


def load_json(*parts) -> Any:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _module_from_file(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Run:
    """Everything one run knows; drivers and metric readers read it."""
    workload: str
    seed: int
    seconds: float
    trace: bool
    cell: dict
    config: dict
    traffic: dict
    bench: dict
    device: Any = None
    peaks: Any = None
    spans: Dict[str, float] = dataclasses.field(default_factory=dict)
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace_summary: Any = None
    edges: Any = None           # (src, dst, weight) as generated
    graph: Any = None           # the program's PropertyGraph
    scale: Optional[int] = None

    @property
    def num_vertices(self) -> int:
        return 1 << self.scale

    @property
    def num_edges(self) -> int:
        """Undirected edges as generated (stored slots / 2)."""
        return int(self.edges[0].shape[0])


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in reported


def _end_to_end(bench: dict, cell: str) -> list:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def _check_device(chips: int):
    import jax
    if jax.default_backend() != "tpu":
        raise NoDevice(f"JAX found no TPU (default backend "
                       f"{jax.default_backend()!r})")
    if jax.device_count() < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX found "
                       f"{jax.device_count()}")


def enable_compile_cache():
    """JAX_COMPILATION_CACHE_DIR when set (JAX reads it), else a fixed
    directory in the checkout: the path is part of the cache key."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(CHECKOUT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir


def prepare(workload: str, seed: int, seconds: float = 0.0,
            trace: bool = False, *, require_tpu: bool = True,
            scale: Optional[int] = None) -> Run:
    """The Run of one cell, its files read and the device checked."""
    bench = load_json(CHECKOUT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    run = Run(workload=workload, seed=int(seed), seconds=float(seconds),
              trace=bool(trace), cell=cell, bench=bench,
              config=load_json(BENCH, "configs", cell["config"] + ".json"),
              traffic=load_json(BENCH, "traffic", cell["traffic"] + ".json"))
    run.scale = int(scale if scale is not None else run.config["scale"])
    import jax
    if require_tpu:
        _check_device(int(cell["chips"]))
    from bench import peaks
    run.device = jax.devices()[0]
    run.peaks = (peaks.peaks_for(run.device.device_kind) if require_tpu
                 else None)
    return run


def build_graph(run: Run, program: bool = True):
    """Generate the edge list on the device from the seed; with `program`
    hand it to the program's own host build (`core/graph.from_edges`)."""
    t = time.perf_counter()
    from bench import graphgen
    run.edges = graphgen.generate(dict(run.config, scale=run.scale),
                                  run.seed)
    run.spans["generate_s"] = time.perf_counter() - t
    if not program:
        return
    from repro.core.graph import from_edges
    t = time.perf_counter()
    src, dst, w = run.edges
    run.graph = from_edges(src, dst, run.num_vertices,
                           edge_props={"weight": w},
                           directed=run.config["directed"])
    run.spans["host_build_s"] = time.perf_counter() - t
    log("graph", config=run.cell["config"], scale=run.scale,
        vertices=run.num_vertices, edges=run.num_edges,
        edge_slots=run.graph.num_edges, generate_s=run.spans["generate_s"],
        host_build_s=run.spans["host_build_s"])


def driver_of(run: Run):
    return importlib.import_module(f"bench.drivers.{run.traffic['driver']}")


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, scale: Optional[int] = None,
             compile_cache: bool = True,
             t_start: Optional[float] = None) -> dict:
    """One run; returns the result object. `require_tpu=False`, `scale=`
    and `compile_cache=False` exist for the CPU rehearsals in bench/tests
    only."""
    t_start = time.perf_counter() if t_start is None else t_start
    run = prepare(workload, seed, seconds, trace, require_tpu=require_tpu,
                  scale=scale)
    import jax
    from bench import clock
    cache_dir = enable_compile_cache() if compile_cache else None
    log("device", platform=run.device.platform,
        kind=run.device.device_kind, count=jax.device_count(),
        compile_cache=cache_dir)
    compiles = clock.CompileClock()

    # -- set-up: graph on the device, the program's host build, warm-up
    build_graph(run)
    driver = driver_of(run)
    state = driver.setup(run)
    run.spans.update(state.pop("spans", {}) if isinstance(state, dict)
                     else {})
    setup_s = time.perf_counter() - t_start
    run.counters["setup_compiles"] = compiles.snapshot()

    # -- the measured window
    before = compiles.snapshot()
    tracedir = tempfile.mkdtemp(prefix="bench-trace-") if run.trace else None
    if run.trace:
        jax.profiler.start_trace(tracedir)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            window = driver.window(run, state)
    finally:
        if run.trace:
            jax.profiler.stop_trace()
    in_window = clock.CompileClock.since(before, compiles.snapshot())
    run.counters["window_compiles"] = in_window
    log("window", **{k: v for k, v in window.items() if k != "metrics"},
        compiles=in_window)

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())
    driver.release(run, state)
    run.graph = None

    if run.trace:
        from bench import trace as trace_mod
        try:
            run.trace_summary = trace_mod.summarize_dir(tracedir)
        finally:
            shutil.rmtree(tracedir, ignore_errors=True)

    t = time.perf_counter()
    checks = driver.check(run, state)
    run.spans["reference_s"] = time.perf_counter() - t
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    metrics = {}
    if run.trace:
        reported = {m["name"] for m in _end_to_end(run.bench, workload)}
        for m in run.bench["per_layer"]:
            if not _applies(m, workload, reported):
                continue
            reader = _module_from_file(
                os.path.join(BENCH, "metrics", m["name"] + ".py"),
                "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        measured = dict(window["metrics"], setup_s=setup_s)
        for m in _end_to_end(run.bench, workload):
            metrics[m["name"]] = {"value": float(measured[m["name"]]),
                                  "unit": m["unit"]}

    log("spans", **run.spans, setup_s=setup_s)
    device = {"platform": run.device.platform,
              "kind": run.device.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": int(window["attempted"]),
              "failed": int(window["failed"]), "metrics": metrics,
              "device": device}
    if run.trace:
        device["busy_s"] = run.trace_summary["busy_s"]
        device["window_s"] = run.trace_summary["window_s"]
        result["breakdown"] = {
            "device_ops": run.trace_summary["device_ops"][:10],
            "idle_gaps": run.trace_summary["idle_gaps"][:10]}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return result
