"""Layer names inside the program (repro/obs.py): device scopes in the
compiled runner, host spans of one job, and the active-edge counter."""
import contextlib
import glob
import os
import re

import jax
import numpy as np
import pytest

import repro
from repro import obs
from repro.core import io, operators
from repro.core.engines import common, pushpull


@pytest.fixture(scope="module")
def graph():
    return io.rmat_graph(6, 4, seed=3, weighted=True)


def _compiled_hlo(graph, kernel_on: bool) -> str:
    gdev = common.prepare_device_graph(graph)
    pkey = common._ProgramKey(operators.SSSPProgram(0))
    runner = common._jitted_runner("pushpull", pkey, 20, kernel_on)
    return runner.lower(gdev, ()).compile().as_text()


def _innermost_scopes(hlo: str) -> dict:
    """{innermost unigps.* scope or "": count} over the op_names of the
    program's own ops (reducer sub-computations carry bare names)."""
    out = {}
    for name in re.findall(r'op_name="([^"]+)"', hlo):
        if not name.startswith("jit("):
            continue
        found = [c for c in name.split("/") if c.startswith(obs.PREFIX)]
        key = found[-1] if found else ""
        out[key] = out.get(key, 0) + 1
    return out


@pytest.mark.parametrize("kernel_on,want", [
    (True, {obs.VERTEX, obs.PLANE_GATHER, obs.PLANE_OPERANDS,
            obs.PLANE_KERNEL}),
    (False, {obs.VERTEX, obs.PLANE_GATHER, obs.PLANE_COMBINE}),
], ids=["fused", "unfused"])
def test_device_scopes_in_the_compiled_runner(graph, kernel_on, want):
    found = _innermost_scopes(_compiled_hlo(graph, kernel_on))
    assert want <= set(found), found
    assert "" not in found, found  # every op falls under a scope
    assert set(found) <= set(obs.SCOPES)


def test_scopes_add_no_op(graph, monkeypatch):
    """Scopes are metadata: compiled without any name scope, the runner
    is the same program."""
    from jax._src import source_info_util

    def strip(hlo):  # the instructions, without their metadata
        return [re.sub(r", metadata=\{[^}]*\}", "", line)
                for line in hlo.splitlines() if " = " in line]

    scoped = strip(_compiled_hlo(graph, True))
    common._jitted_runner.cache_clear()

    @contextlib.contextmanager
    def no_name(name):
        yield

    monkeypatch.setattr(source_info_util, "extend_name_stack", no_name)
    try:
        plain = strip(_compiled_hlo(graph, True))
    finally:
        common._jitted_runner.cache_clear()
    assert not any(obs.PREFIX in line for line in plain)
    assert scoped == plain


def test_job_spans_share_one_id(graph, tmp_path):
    uni = repro.UniGPS()
    np.asarray(uni.sssp(graph, root=0)[0])  # compile outside the profile
    jax.profiler.start_trace(str(tmp_path))
    np.asarray(uni.sssp(graph, root=0)[0])
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
              dict(e.stats).get("job"))
             for plane in pd.planes for line in plane.lines
             for e in line.events if e.name.startswith(obs.PREFIX)]
    jobs = [s for s in spans if s[0] == obs.JOB]
    assert len(jobs) == 1
    _, lo, hi, job_id = jobs[0]
    assert isinstance(job_id, int)
    inside = {name: jid for name, s, e, jid in spans
              if name != obs.JOB and lo <= s and e <= hi}
    assert set(inside) == {obs.PREPARE, obs.PREPARE_LAYOUTS,
                           obs.PREPARE_WINDOWS, obs.PREPARE_UPLOAD, obs.RUN}
    assert set(inside.values()) == {job_id}
    prep = next(s for s in spans if s[0] == obs.PREPARE)
    for child in (obs.PREPARE_LAYOUTS, obs.PREPARE_WINDOWS,
                  obs.PREPARE_UPLOAD):
        c = next(s for s in spans if s[0] == child)
        assert prep[1] <= c[1] and c[2] <= prep[2]


def test_spans_count_without_a_profiler(graph):
    before = obs.span_totals().get(obs.PREPARE, (0, 0.0))
    repro.UniGPS().sssp(graph, root=0)
    count, seconds = obs.span_totals()[obs.PREPARE]
    assert count == before[0] + 1 and seconds > before[1]


def _sssp_rounds(g, root: int):
    """Synchronous Bellman-Ford in numpy float32, as the engine steps it:
    (distances, per-superstep sum of the frontier's out-degrees)."""
    inf = np.float32(operators.INF)
    src, dst = g.src, g.dst
    w = g.edge_props["weight"].astype(np.float32)
    dist = np.full(g.num_vertices, inf, np.float32)
    dist[root] = 0.0
    active = np.zeros(g.num_vertices, bool)
    active[root] = True
    counts = []
    while True:
        counts.append(int(g.out_degree[active].sum()))
        m = active[src] & (dist[src] < inf)
        inbox = np.full(g.num_vertices, inf, np.float32)
        np.minimum.at(inbox, dst[m], dist[src[m]] + w[m])
        has = np.zeros(g.num_vertices, bool)
        has[dst[m]] = True
        if not (active.any() or has.any()):
            return dist, counts
        better = has & (inbox < dist)
        dist = np.minimum(dist, inbox)
        active = better


@pytest.mark.parametrize("kernel", ["off", "on"])
def test_active_edges_is_the_frontier_out_degree_sum(graph, kernel):
    obs.reset()
    out, info = repro.UniGPS().sssp(graph, root=0, kernel=kernel)
    want_dist, counts = _sssp_rounds(graph, 0)
    want_dist[want_dist >= np.float32(operators.INF)] = np.inf
    assert np.array_equal(np.asarray(out), want_dist)  # bit-identical
    assert info["iterations"] == len(counts)
    assert info["active_edges"] == sum(counts)
    assert info["edge_slots"] == graph.num_edges * len(counts)
    c = obs.counters()
    assert {k: c.pop(k) for k in (obs.ACTIVE_EDGES, obs.EDGE_SLOTS)} == {
        obs.ACTIVE_EDGES: sum(counts),
        obs.EDGE_SLOTS: graph.num_edges * len(counts)}
    # a fused pass traced here also counts its vertex columns: SSSP's emit
    # reads the flag and `distance` of the three it could gather
    if c:
        assert set(c) == {obs.GATHER_COLUMNS, obs.GATHERED_COLUMNS}
        assert kernel == "on"
        assert 2 * c[obs.GATHER_COLUMNS] == 3 * c[obs.GATHERED_COLUMNS] > 0
    obs.reset()


def test_engines_without_a_tally_report_none(graph):
    out, info = repro.UniGPS().sssp(graph, root=0, engine="pregel")
    assert "active_edges" not in info
    ref, _ = repro.UniGPS().sssp(graph, root=0)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_tally_is_exact_past_int32():
    carry = pushpull._tally(jax.numpy.zeros((2,), jax.numpy.int32),
                            jax.numpy.int32(2**30 - 1))
    want = 2**30 - 1
    for _ in range(100):
        carry = pushpull._tally(carry, jax.numpy.int32(2**31 - 1))
        want += 2**31 - 1
    assert pushpull.tally_value(carry) == want
