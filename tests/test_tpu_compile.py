"""Ahead-of-time compiles of the message-plane kernels for a TPU v5e.

Nothing here runs on a chip: each test lowers one kernel variant for a
described (not attached) `v5e:2x2` topology and lets the TPU compiler
refuse what Mosaic cannot lower — unaligned blocks, gathers, scalar reads
from vectors, too much scalar or vector memory. Interpret mode accepts all
of those, so these tests are the CPU suite's only guard on the Mosaic path.

The topology is described inside a module fixture (never at import), so
only the worker that runs this file loads the TPU compiler, and the tests
skip where no topology can be described.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.graph_device import PREFETCH_BLOCK_E
from repro.kernels import fused_gather_emit as fge
from repro.kernels import segment_reduce

V, E = 1 << 16, 1 << 20  # graph500-16: the callback phase of chip_smoke.py


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back here
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture
def mosaic(monkeypatch):
    """Lower the kernels for Mosaic although the process backend is the
    CPU (the kernels pick interpret mode from the backend)."""
    monkeypatch.setattr(fge, "interpret_mode", lambda: False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return compiled


def _sssp_emit(s, d, sp, ep):
    return sp["dist"] < 3.0e38, {"dist": sp["dist"] + ep["w"]}


def _rank_emit(s, d, sp, ep):
    return jnp.bool_(True), {"rank": sp["rank"] / sp["deg"]}


def _label_emit(s, d, sp, ep):
    return jnp.bool_(True), {"label": sp["label"]}


def _flag_emit(s, d, sp, ep):
    return sp["seen"], {"any": sp["seen"] | ep["tag"]}


@pytest.mark.parametrize("case", ["sssp_min", "rank_sum", "label_min",
                                  "label_sum", "bool_max"])
def test_fused_resident_compiles(one_chip, mosaic, case):
    emit, monoid, vp, ep = {
        "sssp_min": (_sssp_emit, "min", {"dist": jnp.float32},
                     {"w": jnp.float32}),
        "rank_sum": (_rank_emit, "sum",
                     {"rank": jnp.float32, "deg": jnp.float32}, {}),
        "label_min": (_label_emit, "min", {"label": jnp.int32}, {}),
        "label_sum": (_label_emit, "sum", {"label": jnp.int32}, {}),
        "bool_max": (_flag_emit, "max", {"seen": jnp.bool_},
                     {"tag": jnp.bool_}),
    }[case]

    def plane(src, dst, vprops, eprops, active):
        return fge.gather_emit_combine(emit, monoid, src, dst, vprops,
                                       eprops, active, V)

    _compile(plane, _sds((E,), jnp.int32, one_chip),
             _sds((E,), jnp.int32, one_chip),
             {k: _sds((V,), t, one_chip) for k, t in vp.items()},
             {k: _sds((E,), t, one_chip) for k, t in ep.items()},
             _sds((V,), jnp.bool_, one_chip))


@pytest.mark.parametrize("window", [512, 2048])
def test_fused_prefetch_blockskip_compiles(one_chip, mosaic, window):
    n_blocks = E // PREFETCH_BLOCK_E

    def plane(src, dst, dist, w, active, blocks):
        return fge.gather_emit_combine(
            _sssp_emit, "min", src, dst, {"dist": dist}, {"w": w}, active, V,
            prefetch=(blocks, window, PREFETCH_BLOCK_E), block_skip=True)

    compiled = _compile(
        plane, _sds((E,), jnp.int32, one_chip),
        _sds((E,), jnp.int32, one_chip), _sds((V,), jnp.float32, one_chip),
        _sds((E,), jnp.float32, one_chip), _sds((V,), jnp.bool_, one_chip),
        _sds((n_blocks,), jnp.int32, one_chip))
    assert "gather_emit_prefetch_skip_min" in compiled.as_text()


@pytest.mark.parametrize("lanes", [1, 8])
def test_fused_packed_compiles(one_chip, mosaic, lanes):
    """Mixed-monoid record plus [V, Q] query lanes: one packed launch."""
    def emit(s, d, sp, ep):
        return jnp.bool_(True), {"dist": sp["dist"] + ep["w"][..., None],
                                 "hops": sp["hops"] + 1,
                                 "mass": sp["mass"]}

    def plane(src, dst, vprops, w, active):
        return fge.gather_emit_combine_packed(
            emit, ("min", "min", "sum"), src, dst, vprops, {"w": w}, active,
            V, block_skip=True)

    compiled = _compile(
        plane, _sds((E,), jnp.int32, one_chip),
        _sds((E,), jnp.int32, one_chip),
        {"dist": _sds((V, lanes), jnp.float32, one_chip),
         "hops": _sds((V,), jnp.int32, one_chip),
         "mass": _sds((V,), jnp.float32, one_chip)},
        _sds((E,), jnp.float32, one_chip), _sds((V,), jnp.bool_, one_chip))
    assert "gather_emit_packed_skip" in compiled.as_text()


@pytest.mark.parametrize("monoid", ["sum", "min", "max"])
@pytest.mark.parametrize("dtype,D", [(jnp.float32, 0), (jnp.int32, 0),
                                     (jnp.bfloat16, 0), (jnp.float32, 4)])
def test_segment_combine_compiles(one_chip, mosaic, monoid, dtype, D):
    shape = (E,) if D == 0 else (E, D)

    def combine(vals, seg):
        return segment_reduce.segment_combine_kernel(vals, seg, V,
                                                     monoid=monoid)

    _compile(combine, _sds(shape, dtype, one_chip),
             _sds((E,), jnp.int32, one_chip))


def test_visit_table_fits_scalar_memory(one_chip, mosaic):
    """graph500-22's edge count with a block-skip bitmap: the planner
    widens the edge block until the scalar tables fit SMEM, and the
    program still compiles."""
    big_e, big_v = 1 << 26, 1 << 22
    plan = fge._plan(big_e, big_v, fge.TILE_1D, fge.TILE_1D, 1)
    assert plan.n_steps + 1 + plan.n_e <= fge.SMEM_TABLE_WORDS
    assert plan.be > fge.TILE_1D

    def plane(src, dst, dist, w, active):
        return fge.gather_emit_combine(_sssp_emit, "min", src, dst,
                                       {"dist": dist}, {"w": w}, active,
                                       big_v, block_skip=True)

    _compile(plane, _sds((big_e,), jnp.int32, one_chip),
             _sds((big_e,), jnp.int32, one_chip),
             _sds((big_v,), jnp.float32, one_chip),
             _sds((big_e,), jnp.float32, one_chip),
             _sds((big_v,), jnp.bool_, one_chip))


def _sssp_pass(one_chip, v, e):
    """SSSP's resident pass (the emit reads the frontier flag and
    `distance`, not `vid`) compiled for one chip at V = v, E = e."""
    from repro.core import operators
    prog = operators.SSSPProgram(0)

    def plane(src, dst, vprops, eprops, active):
        return fge.gather_emit_combine(prog.emit_message, "min", src, dst,
                                       vprops, eprops, active, v)

    return _compile(
        plane, _sds((e,), jnp.int32, one_chip),
        _sds((e,), jnp.int32, one_chip),
        {"distance": _sds((v,), jnp.float32, one_chip),
         "vid": _sds((v,), jnp.int32, one_chip)},
        {"weight": _sds((e,), jnp.float32, one_chip)},
        _sds((v,), jnp.bool_, one_chip))


def test_sssp_resident_pass_gathers_once(one_chip, mosaic):
    """At graph500-16 shapes the compiled pass holds one gather into edge
    order: the two live columns as the rows of one [2, E] int32 table,
    laid out densely."""
    text = _sssp_pass(one_chip, V, E).as_text()
    into_edges = re.findall(rf"= (\S*\b{E}\b\S*) gather\(", text)
    assert [shape.split(":")[0] for shape in into_edges] \
        == [f"s32[2,{E}]{{1,0"]
    assert f"s32[2,{E}]{{1,0:T(2,128)}} gather(" in text


@pytest.mark.parametrize("scale,edges", [(21, 67106064), (22, 134213632)])
def test_sssp_resident_pass_memory(one_chip, mosaic, scale, edges):
    """At graph500-21 and -22 shapes the pass's temporaries hold one
    E-sized int32 word per live column (2) and per padded edge operand
    (dst, src, weight): the gathered rows are no wider than the columns
    emit reads, and no second copy of them is made to pad them."""
    e_pad = fge._plan(edges, 1 << scale, fge.TILE_1D, fge.TILE_1D).E_pad
    temp = _sssp_pass(one_chip, 1 << scale, edges).memory_analysis() \
        .temp_size_in_bytes
    assert temp <= (2 + 3) * 4 * e_pad + (1 << 20)


def test_visit_table_covers_every_overlap():
    """The visit table lists exactly the (vertex block, edge block) pairs
    whose ranges meet, each vertex block at least once, in order."""
    rng = np.random.default_rng(0)
    Vs, Es = 5000, 9000
    dst = np.sort(rng.integers(0, Vs, Es)).astype(np.int32)
    dst[:3000] = np.sort(rng.integers(0, 40, 3000))  # a hub-heavy prefix
    dst = np.sort(dst)
    p = fge._plan(Es, Vs, fge.TILE_1D, fge.TILE_1D)
    seg_p = np.full(p.E_pad, p.V_pad, np.int32)
    seg_p[:Es] = dst
    table = np.asarray(fge._visit_table(jnp.asarray(seg_p), p))
    live = int(table[-1])
    code = table[:-1]
    vb, eb = code >> p.shift, code & ((1 << p.shift) - 1)
    want = []
    for b in range(p.n_vb):
        lo, hi = b * p.bv, (b + 1) * p.bv
        blocks = sorted({int(i) // p.be for i in
                         np.nonzero((seg_p >= lo) & (seg_p < hi))[0]})
        want += [(b, e) for e in (blocks or [min(
            int(np.searchsorted(seg_p, lo)) // p.be, p.n_e - 1)])]
    got = list(zip(vb[:live].tolist(), eb[:live].tolist()))
    assert got == want
    assert live <= p.n_steps
    assert (vb[live:] == vb[live - 1]).all()
    assert (eb[live:] == eb[live - 1]).all()


def test_chip_smoke_phases_match_references():
    """chip_smoke.py's phases at a tiny Graph500 scale, on the CPU: each
    result against its scipy / numpy reference, and zero compiles in the
    serving request loop."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    import chip_smoke

    g = chip_smoke.graph500(9, 0)
    rep = chip_smoke.analytics_phase(g, 0)
    assert rep["bfs"]["iterations"] > 1 and rep["wcc"]["iterations"] > 1
    assert rep["pagerank"]["iterations"] == 20
    served = chip_smoke.serving_phase(g, 0, n_requests=16)
    assert served["request_compiles"] == 0
    assert served["sentinel_trips"] == 0
    cb = chip_smoke.callback_phase(chip_smoke.graph500(7, 0), 0, 300.0)
    assert cb["max_rel_err"] <= 1e-5
    dist = chip_smoke.distributed_phase(g, 0, num_parts=1)
    assert dist["ring.sssp"]["part_devices"] == [0]


def test_compile_cache_dir_from_environment_or_checkout(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache goes to the fixed <checkout>/.jax_cache."""
    from repro import envutil
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert envutil.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = envutil.enable_compile_cache()
        assert path == os.path.join(envutil.CHECKOUT, ".jax_cache")
        assert os.path.isfile(os.path.join(envutil.CHECKOUT, "chip_smoke.py"))
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
