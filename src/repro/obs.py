"""Stable names for the layers of one analytics job.

Three kinds of names, all constants here so profilers, the benchmark and
tests import them rather than spell them:

* device scopes (`scope`): `jax.named_scope`, metadata only — every op
  traced inside carries the scope in its `op_name`, so a profile of the
  compiled program attributes each XLA fusion and kernel to a layer. No
  op is added and fusion is unchanged.
* host spans (`span`): `jax.profiler.TraceAnnotation`, on the host plane
  of a profile, on the clock the device planes are aligned to, and inert
  when no profiler session runs. Each span also adds its count and host
  seconds to a process-wide table (`span_totals`): two clock reads.
* counters (`add`): a process-wide table of exact Python ints.

There is no switch: the profile names are there when a profiler session
runs (`jax.profiler.start_trace` / `trace`) and cost nothing otherwise.
docs/architecture.md lists what each name covers.
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
import threading
import time
from typing import Dict, Tuple

import jax

PREFIX = "unigps."

# -- device scopes: every device op of a job falls under exactly one
#    innermost scope of these
VERTEX = "unigps.vertex"                  # init, compute, frontier, finish
PLANE_GATHER = "unigps.plane.gather"      # vertex values into edge order
PLANE_OPERANDS = "unigps.plane.operands"  # per-pass edge operands, tables
PLANE_KERNEL = "unigps.plane.kernel"      # the pallas_calls
PLANE_COMBINE = "unigps.plane.combine"    # unfused permute + combine
SCOPES = (VERTEX, PLANE_GATHER, PLANE_OPERANDS, PLANE_KERNEL, PLANE_COMBINE)

# -- host spans; the spans of one job carry its `job` sequence number
JOB = "unigps.job"
PREPARE = "unigps.prepare"
PREPARE_LAYOUTS = "unigps.prepare.layouts"
PREPARE_WINDOWS = "unigps.prepare.windows"
PREPARE_UPLOAD = "unigps.prepare.upload"
RUN = "unigps.run"

# -- counters
ACTIVE_EDGES = "plane.active_edges"  # slots whose source was on the frontier
EDGE_SLOTS = "plane.edge_slots"      # slots streamed: stored x supersteps
# per traced fused pass (trace time, so a jit cache hit adds nothing): the
# vertex columns it could gather into edge order (frontier flag plus every
# vertex-property leaf), and those it did gather (the ones emit reads)
GATHER_COLUMNS = "plane.gather_columns"
GATHERED_COLUMNS = "plane.gathered_columns"

_job = contextvars.ContextVar("unigps_job", default=None)
_job_ids = itertools.count(1)
_lock = threading.Lock()
_counters: Dict[str, int] = {}
_spans: Dict[str, list] = {}


def scope(name: str):
    """Device scope: `jax.named_scope(name)`."""
    return jax.named_scope(name)


@contextlib.contextmanager
def span(name: str, **ids):
    """Host span `name`; inside a `job()` it carries that job's id."""
    current = _job.get()
    if current is not None:
        ids.setdefault("job", current)
    t = time.perf_counter_ns()
    try:
        with jax.profiler.TraceAnnotation(name, **ids):
            yield
    finally:
        dt = time.perf_counter_ns() - t
        with _lock:
            rec = _spans.setdefault(name, [0, 0])
            rec[0] += 1
            rec[1] += dt


@contextlib.contextmanager
def job():
    """The `JOB` span around one job, with the next sequence number; a
    job started inside another (a lane-chunked run's sub-batches) is
    part of it and opens no span of its own."""
    if _job.get() is not None:
        yield _job.get()
        return
    n = next(_job_ids)
    token = _job.set(n)
    try:
        with span(JOB):
            yield n
    finally:
        _job.reset(token)


def add(name: str, n: int):
    with _lock:
        _counters[name] = _counters.get(name, 0) + int(n)


def counters() -> Dict[str, int]:
    with _lock:
        return dict(_counters)


def span_totals() -> Dict[str, Tuple[int, float]]:
    """{span name: (count, host seconds)} over the process."""
    with _lock:
        return {k: (n, ns * 1e-9) for k, (n, ns) in _spans.items()}


def reset():
    """Clear the counters and the span totals."""
    with _lock:
        _counters.clear()
        _spans.clear()
