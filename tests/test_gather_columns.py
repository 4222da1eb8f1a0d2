"""The vertex columns a fused pass gathers into edge order.

A resident or scalar-prefetch pass gathers the frontier flag and only the
vertex-property leaves the user's emit reads (a liveness pass over the
emit's jaxpr); several live columns go through one stacked gather. Dead
leaves reach emit as zeros, so every result stays bit-identical.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import obs
from repro.core import graph as graph_mod
from repro.core import io, operators
from repro.core.engines.common import run_vcprog
from repro.core.graph_device import compute_prefetch_windows
from repro.kernels import fused_gather_emit as fge
from repro.kernels import ref

E_, V_ = 40, 10


def _vp(**dtypes):
    return {k: jnp.zeros((V_,), t) for k, t in dtypes.items()}


def _emit_cond(s, d, sp, ep):
    return True, {"x": jax.lax.cond(s > 0, lambda: sp["a"], lambda: 0.0)}


@jax.jit
def _helper(x, y):
    return x * 2.0


def _emit_nested_jit(s, d, sp, ep):
    return True, {"x": _helper(sp["a"], sp["b"])}


def _emit_where(s, d, sp, ep):
    return sp["b"] > 0, {"x": jnp.where(sp["c"] > 0, sp["a"], 1.0)}


def _emit_debug(s, d, sp, ep):
    jax.debug.print("{}", sp["a"])
    return True, {"x": sp["a"]}


# (emit, vprops, eprops, live per flattened leaf: keys in sorted order)
LIVENESS = {
    "sssp": (operators.SSSPProgram(0).emit_message,
             _vp(distance=jnp.float32, vid=jnp.int32),
             {"weight": jnp.zeros((E_,), jnp.float32)}, (True, False)),
    "bfs": (operators.BFSProgram(0).emit_message,
            _vp(depth=jnp.int32, vid=jnp.int32), {}, (True, False)),
    "wcc": (operators.CCProgram().emit_message, _vp(label=jnp.int32), {},
            (True,)),
    "pagerank": (operators.PageRankProgram(V_, 5).emit_message,
                 _vp(out_degree=jnp.float32, rank=jnp.float32), {},
                 (True, True)),
    "degrees": (operators.DegreeProgram().emit_message,
                _vp(in_degree=jnp.int32, out_degree=jnp.int32), {},
                (False, False)),
    "inside_cond": (_emit_cond, _vp(a=jnp.float32, b=jnp.float32), {},
                    (True, False)),
    "nested_jit": (_emit_nested_jit, _vp(a=jnp.float32, b=jnp.float32), {},
                   (True, False)),
    "where": (_emit_where, _vp(a=jnp.float32, b=jnp.float32, c=jnp.int32,
                               d=jnp.int32), {}, (True, True, True, False)),
    "debug_effect": (_emit_debug, _vp(a=jnp.float32, b=jnp.float32), {},
                     (True, True)),
}


@pytest.mark.parametrize("case", sorted(LIVENESS))
def test_live_vertex_leaves(case):
    emit, vprops, eprops, want = LIVENESS[case]
    assert fge.live_vertex_leaves(emit, E_, vprops, eprops) == want


@pytest.mark.parametrize("error", [NotImplementedError, ValueError,
                                   AssertionError])
def test_live_vertex_leaves_keeps_all_when_analysis_fails(monkeypatch,
                                                          error):
    from jax._src.interpreters import partial_eval as pe

    def no_rule(*args, **kw):
        raise error("the DCE pass failed")

    monkeypatch.setattr(pe, "dce_jaxpr", no_rule)
    emit, vprops, eprops, _ = LIVENESS["sssp"]
    assert fge.live_vertex_leaves(emit, E_, vprops, eprops) == (True, True)


def test_live_vertex_leaves_raises_what_emit_raises():
    def untraceable(s, d, sp, ep):
        raise RuntimeError("no trace")

    with pytest.raises(RuntimeError, match="no trace"):
        fge.live_vertex_leaves(untraceable, E_, _vp(a=jnp.float32), {})


def _dead_leaf_case(seed=5, E=4096, V=2048):
    """Edges with local sources (so windows exist) and a record whose emit
    reads `x` and the weight but never `flag`, `junk` or `vid`."""
    rng = np.random.default_rng(seed)
    dst = np.sort(rng.integers(0, V, E)).astype(np.int32)
    src = np.clip(dst + rng.integers(-40, 41, E), 0, V - 1).astype(np.int32)
    vprops = {"x": jnp.asarray(rng.random(V), jnp.float32),
              "flag": jnp.asarray(rng.random(V) < 0.5),
              "junk": jnp.asarray(rng.random(V), jnp.float32),
              "vid": jnp.arange(V, dtype=jnp.int32)}
    eprops = {"w": jnp.asarray(rng.random(E), jnp.float32)}
    active = jnp.asarray(rng.random(V) < 0.3)
    valid = jnp.asarray(rng.random(E) < 0.8)
    return src, dst, vprops, eprops, active, valid


def _emit_min(s, d, sp, ep):
    return sp["x"] < 0.9, {"v": sp["x"] + ep["w"]}


def _emit_count(s, d, sp, ep):  # int sums are exact in any order
    return sp["x"] < 0.9, {"n": (s % 7 + d % 5).astype(jnp.int32)}


@pytest.mark.parametrize("block_skip", [False, True])
@pytest.mark.parametrize("ids", [False, True])
@pytest.mark.parametrize("with_active", [False, True])
@pytest.mark.parametrize("variant", ["resident", "prefetch"])
@pytest.mark.parametrize("monoid", ["min", "sum"])
def test_dead_leaves_bit_identical(monoid, variant, with_active, ids,
                                   block_skip):
    src, dst, vprops, eprops, active, valid = _dead_leaf_case()
    emit = _emit_min if monoid == "min" else _emit_count
    assert fge.live_vertex_leaves(emit, src.shape[0], vprops, eprops) \
        == (False, False, False, True)  # flag, junk, vid dead; x live
    kw = {}
    if ids:
        kw = dict(valid=valid, src_ids=jnp.asarray(src + 1000),
                  dst_ids=jnp.asarray(dst + 2000))
    if variant == "prefetch":
        blocks, window = compute_prefetch_windows(src, 2048)
        assert 0 < 2 * window < 2048
        kw["prefetch"] = (jnp.asarray(blocks), window, 512)
    act = active if with_active else None
    src, dst = jnp.asarray(src), jnp.asarray(dst)
    out, hm = fge.gather_emit_combine(emit, monoid, src, dst, vprops, eprops,
                                      act, 2048, block_skip=block_skip, **kw)
    want, whm = ref.gather_emit_combine_ref(
        emit, monoid, src, dst, vprops, eprops,
        jnp.ones((2048,), bool) if act is None else act, 2048,
        valid=kw.get("valid"), src_ids=kw.get("src_ids"),
        dst_ids=kw.get("dst_ids"))
    for k in want:
        assert out[k].dtype == want[k].dtype
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(want[k]))
    np.testing.assert_array_equal(np.asarray(hm), np.asarray(whm))


@pytest.fixture(scope="module")
def graph():
    return io.uniform_graph(150, 1200, seed=11, weighted=True)


def _one_in_edge_graph(V=150, E=120, seed=12):
    """Every vertex has at most one in-edge (sources repeat, so out-degrees
    vary): a float sum then adds one message to zero, which is exact in any
    order, so the fused MXU fold and the unfused segment sum agree bitwise."""
    rng = np.random.default_rng(seed)
    return graph_mod.from_edges(rng.integers(0, V, E),
                                rng.permutation(V)[:E], V)


@pytest.mark.parametrize("algo", ["sssp", "bfs", "wcc", "pagerank"])
def test_operators_fused_match_unfused_bitwise(graph, algo):
    if algo == "pagerank":
        graph = _one_in_edge_graph()
    prog, max_iter = {
        "sssp": (operators.SSSPProgram(3), 60),
        "bfs": (operators.BFSProgram(3), 60),
        "wcc": (operators.CCProgram(), 60),
        "pagerank": (operators.PageRankProgram(graph.num_vertices, 6), 6),
    }[algo]
    on, _ = run_vcprog(prog, graph, max_iter=max_iter, kernel="on")
    off, _ = run_vcprog(prog, graph, max_iter=max_iter, kernel="off")
    for k in off:
        np.testing.assert_array_equal(np.asarray(on[k]), np.asarray(off[k]))


@pytest.mark.parametrize("algo,want", [("sssp", (3, 2)), ("wcc", (2, 2))])
def test_gather_column_counters(algo, want):
    """One traced pass adds (columns it could gather, columns gathered)."""
    src, dst, _, eprops, active, _ = _dead_leaf_case(E=600, V=90)
    vid = jnp.arange(90, dtype=jnp.int32)
    prog, vprops = {
        "sssp": (operators.SSSPProgram(0),
                 {"distance": vid.astype(jnp.float32), "vid": vid}),
        "wcc": (operators.CCProgram(), {"label": vid}),
    }[algo]
    obs.reset()
    fge.gather_emit_combine(prog.emit_message, prog.monoid,
                            jnp.asarray(src), jnp.asarray(dst), vprops,
                            {"weight": eprops["w"]}, active, 90)
    c = obs.counters()
    assert (c[obs.GATHER_COLUMNS], c[obs.GATHERED_COLUMNS]) == want
    obs.reset()


def test_gather_column_counters_count_traced_passes():
    """Through the user entry point: each traced pass of an SSSP run adds
    3 columns it could gather and 2 it did; a cache hit adds nothing."""
    g = io.uniform_graph(97, 700, seed=4, weighted=True)
    obs.reset()
    repro.UniGPS().sssp(g, root=1, kernel="on")
    c = obs.counters()
    n = c[obs.GATHER_COLUMNS]
    assert n > 0 and n % 3 == 0
    assert 3 * c[obs.GATHERED_COLUMNS] == 2 * n
    obs.reset()
    repro.UniGPS().sssp(g, root=1, kernel="on")
    assert obs.GATHER_COLUMNS not in obs.counters()
    obs.reset()
