"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret=True
executes the exact TPU kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# segment_combine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("monoid", ["sum", "min", "max"])
@pytest.mark.parametrize("E,V,D", [(1, 1, 1), (7, 3, 1), (200, 64, 4),
                                   (777, 133, 5), (1024, 128, 128),
                                   (513, 257, 3)])
def test_segment_combine_shapes(monoid, E, V, D):
    seg = np.sort(RNG.integers(0, V, E)).astype(np.int32)
    vals = RNG.normal(size=(E, D)).astype(np.float32)
    out = ops.segment_combine(jnp.asarray(vals), jnp.asarray(seg), V,
                              monoid=monoid)
    refo = ops.segment_combine_ref(jnp.asarray(vals), jnp.asarray(seg), V,
                                   monoid=monoid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(refo),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("monoid", ["sum", "min", "max"])
def test_segment_combine_dtypes(dtype, monoid):
    E, V, D = 300, 50, 3
    seg = np.sort(RNG.integers(0, V, E)).astype(np.int32)
    if dtype == jnp.int32:
        vals = RNG.integers(-1000, 1000, (E, D)).astype(np.int32)
    else:
        vals = RNG.normal(size=(E, D)).astype(np.float32)
    x = jnp.asarray(vals, dtype)
    out = ops.segment_combine(x, jnp.asarray(seg), V, monoid=monoid)
    refo = ops.segment_combine_ref(x, jnp.asarray(seg), V, monoid=monoid)
    assert out.dtype == x.dtype
    m = (ops.segment_combine_ref(jnp.ones((E, 1), jnp.float32),
                                 jnp.asarray(seg), V, "sum")[:, 0] > 0)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32)[np.asarray(m)],
        np.asarray(refo, np.float32)[np.asarray(m)], rtol=tol, atol=tol)


def test_segment_combine_1d_and_empty_segments():
    seg = jnp.asarray([2, 2, 5], jnp.int32)
    vals = jnp.asarray([1.0, 2.0, 7.0], jnp.float32)
    out = ops.segment_combine(vals, seg, 8, monoid="sum")
    np.testing.assert_allclose(np.asarray(out),
                               [0, 0, 3.0, 0, 0, 7.0, 0, 0])


@pytest.mark.parametrize("monoid", ["min", "max"])
def test_segment_combine_minmax_full_block_e(monoid):
    """min/max over several edge blocks whose segments span block
    boundaries (a requested block_e=512 is raised to the 1-D tile)."""
    E, V, D = 2600, 96, 4  # several 1024-edge blocks, segments span blocks
    seg = np.sort(RNG.integers(0, V, E)).astype(np.int32)
    vals = RNG.normal(size=(E, D)).astype(np.float32)
    out = ops.segment_combine(jnp.asarray(vals), jnp.asarray(seg), V,
                              monoid=monoid, block_e=512)
    refo = ops.segment_combine_ref(jnp.asarray(vals), jnp.asarray(seg), V,
                                   monoid=monoid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(refo),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# fused gather–emit–combine
# ---------------------------------------------------------------------------

def _random_graph_arrays(E, V, seed=3):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, V, E).astype(np.int32)
    dst = np.sort(rng.integers(0, V, E)).astype(np.int32)
    active = rng.random(V) < 0.7
    return jnp.asarray(src), jnp.asarray(dst), jnp.asarray(active)


@pytest.mark.parametrize("monoid", ["sum", "min"])
@pytest.mark.parametrize("E,V", [(5, 3), (700, 90), (2500, 300)])
def test_fused_gather_emit_combine(monoid, E, V):
    """Fused single pass == three-pass oracle, incl. filtered emissions."""
    src, dst, active = _random_graph_arrays(E, V)
    rng = np.random.default_rng(V)
    vprops = {"x": jnp.asarray(rng.random(V), jnp.float32),
              "deg": jnp.asarray(rng.integers(1, 9, V), jnp.float32)}
    eprops = {"w": jnp.asarray(rng.random(E), jnp.float32)}

    def emit(s, d, sp, ep):
        return sp["x"] < 0.8, {"v": sp["x"] / sp["deg"] + ep["w"]}

    out, hm = ops.gather_emit_combine(emit, monoid, src, dst, vprops,
                                      eprops, active, V)
    refo, rhm = ops.gather_emit_combine_ref(emit, monoid, src, dst, vprops,
                                            eprops, active, V)
    np.testing.assert_allclose(np.asarray(out["v"]), np.asarray(refo["v"]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(hm), np.asarray(rhm))


def test_fused_padded_edges_cannot_poison_sum():
    """E not a multiple of block_e: padded rows run emit on zero-filled
    eprops (here: a division -> inf) and must stay invalid — a regression
    guard against inf*0 NaN-poisoning the one-hot accumulate."""
    E, V = 700, 90  # pads to 1024 edge rows
    src, dst, _ = _random_graph_arrays(E, V, seed=2)
    rng = np.random.default_rng(2)
    vprops = {"x": jnp.asarray(rng.random(V), jnp.float32)}
    eprops = {"w": jnp.asarray(rng.random(E).astype(np.float32) + 0.5)}
    active = jnp.ones((V,), bool)

    def emit(s, d, sp, ep):
        return jnp.bool_(True), {"v": sp["x"] / ep["w"]}

    out, hm = ops.gather_emit_combine(emit, "sum", src, dst, vprops, eprops,
                                      active, V)
    refo, _ = ops.gather_emit_combine_ref(emit, "sum", src, dst, vprops,
                                          eprops, active, V)
    assert np.isfinite(np.asarray(out["v"])).all()
    np.testing.assert_allclose(np.asarray(out["v"]), np.asarray(refo["v"]),
                               rtol=1e-5, atol=1e-5)


def test_segment_combine_narrow_int_empty_segments():
    """Empty segments of sub-32-bit int payloads must flush the payload
    dtype's own identity (int32's would wrap on the cast back)."""
    seg = jnp.asarray([2, 2, 5], jnp.int32)
    vals = jnp.asarray([[1], [2], [7]], jnp.int8)
    out = ops.segment_combine(vals, seg, 8, monoid="min")
    assert out.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(out[:, 0]),
                                  [127, 127, 1, 127, 127, 7, 127, 127])


@pytest.mark.parametrize("monoid", ["sum", "min", "max"])
def test_fused_multifield_and_integer_payloads(monoid):
    """Multi-field message records with mixed f32/int payloads; the int
    field must stay exact (int32 accumulation, incl. 2^31-1 sentinels)."""
    E, V = 900, 120
    src, dst, active = _random_graph_arrays(E, V, seed=9)
    rng = np.random.default_rng(1)
    labels = rng.integers(0, V, V).astype(np.int32)
    labels[::11] = 2**31 - 1  # CC-style sentinel
    vprops = {"label": jnp.asarray(labels),
              "score": jnp.asarray(rng.random(V), jnp.float32)}

    def emit(s, d, sp, ep):
        return jnp.bool_(True), {"label": sp["label"],
                                 "score": sp["score"] * 2.0}

    out, hm = ops.gather_emit_combine(emit, monoid, src, dst, vprops, {},
                                      active, V)
    refo, rhm = ops.gather_emit_combine_ref(emit, monoid, src, dst, vprops,
                                            {}, active, V)
    assert out["label"].dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out["label"]),
                                  np.asarray(refo["label"]))
    np.testing.assert_allclose(np.asarray(out["score"]),
                               np.asarray(refo["score"]), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(hm), np.asarray(rhm))


@pytest.mark.parametrize("monoid", ["sum", "min"])
def test_fused_valid_mask_and_ids(monoid):
    """Pre-padded layouts: the `valid` mask must veto padded slots and
    `src_ids`/`dst_ids` must reach emit instead of the gather indices."""
    E, V = 300, 50
    src, dst, active = _random_graph_arrays(E, V, seed=7)
    rng = np.random.default_rng(7)
    vprops = {"x": jnp.asarray(rng.random(V), jnp.float32)}
    valid = jnp.asarray(rng.random(E) < 0.6)
    sid = jnp.asarray(np.asarray(src) + 1000)
    did = jnp.asarray(np.asarray(dst) + 2000)

    def emit(s, d, sp, ep):
        # reads the ids: wrong ids change the result
        return jnp.bool_(True), {"v": sp["x"] + (s - d).astype(jnp.float32)}

    out, hm = ops.gather_emit_combine(emit, monoid, src, dst, vprops, {},
                                      active, V, valid=valid, src_ids=sid,
                                      dst_ids=did)
    refo, rhm = ops.gather_emit_combine_ref(emit, monoid, src, dst, vprops,
                                            {}, active, V, valid=valid,
                                            src_ids=sid, dst_ids=did)
    np.testing.assert_allclose(np.asarray(out["v"]), np.asarray(refo["v"]),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(hm), np.asarray(rhm))


@pytest.mark.parametrize("monoid", ["sum", "min", "max"])
def test_fused_prefetch_variant(monoid):
    """The scalar-prefetch (PrefetchScalarGridSpec) variant — two
    `window`-row src slabs DMA'd per edge block instead of the whole [V]
    resident set — must match the oracle exactly."""
    from repro.core.graph_device import compute_prefetch_windows

    rng = np.random.default_rng(5)
    E, V = 4096, 2048
    dst = np.sort(rng.integers(0, V, E)).astype(np.int32)
    src = np.clip(dst + rng.integers(-40, 41, E), 0, V - 1).astype(np.int32)
    blocks, window = compute_prefetch_windows(src, V)
    assert 0 < 2 * window < V, "workload must exercise real windows"
    vprops = {"x": jnp.asarray(rng.random(V), jnp.float32),
              "deg": jnp.asarray(rng.integers(1, 9, V), jnp.float32)}
    eprops = {"w": jnp.asarray(rng.random(E), jnp.float32)}
    active = jnp.asarray(rng.random(V) < 0.8)

    def emit(s, d, sp, ep):
        return sp["x"] < 0.9, {"v": sp["x"] / sp["deg"] + ep["w"]}

    out, hm = ops.gather_emit_combine(
        emit, monoid, jnp.asarray(src), jnp.asarray(dst), vprops, eprops,
        active, V, prefetch=(jnp.asarray(blocks), window, 512))
    refo, rhm = ops.gather_emit_combine_ref(
        emit, monoid, jnp.asarray(src), jnp.asarray(dst), vprops, eprops,
        active, V)
    np.testing.assert_allclose(np.asarray(out["v"]), np.asarray(refo["v"]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(hm), np.asarray(rhm))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Hq,Hkv,T,S,Dh", [
    (1, 1, 1, 8, 8, 64),
    (2, 4, 2, 100, 100, 64),
    (1, 8, 1, 128, 128, 128),     # MQA (kv=1, recurrentgemma-style)
    (2, 6, 2, 96, 96, 64),        # non-pow2 heads
    (1, 2, 2, 64, 192, 64),       # prefill-style T != S (q is a suffix)
])
def test_flash_attention_shapes(B, Hq, Hkv, T, S, Dh):
    q = jnp.asarray(RNG.normal(size=(B, Hq, T, Dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, S, Dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, S, Dh)), jnp.float32)
    causal = T == S  # cross-length uses full attention in this sweep
    o = ops.flash_attention(q, k, v, causal=causal)
    r = ops.mha_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [1, 16, 17, 100, 4096])
def test_flash_attention_window(window):
    B, Hq, Hkv, T, Dh = 1, 4, 2, 130, 64
    q = jnp.asarray(RNG.normal(size=(B, Hq, T, Dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, T, Dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, T, Dh)), jnp.float32)
    o = ops.flash_attention(q, k, v, causal=True, window=window)
    r = ops.mha_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    B, Hq, Hkv, T, Dh = 2, 4, 4, 64, 64
    q = jnp.asarray(RNG.normal(size=(B, Hq, T, Dh)), jnp.bfloat16)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, T, Dh)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, T, Dh)), jnp.bfloat16)
    o = ops.flash_attention(q, k, v, causal=True)
    r = ops.mha_ref(q, k, v, causal=True)
    assert o.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), rtol=5e-2,
                               atol=5e-2)


def test_flash_attention_block_sweep():
    """Block shapes must not change results (VMEM tiling is semantics-free)."""
    B, Hq, Hkv, T, Dh = 1, 2, 1, 192, 64
    q = jnp.asarray(RNG.normal(size=(B, Hq, T, Dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, T, Dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, T, Dh)), jnp.float32)
    r = ops.mha_ref(q, k, v, causal=True)
    for bq, bk in [(16, 16), (32, 64), (64, 32), (128, 128)]:
        o = ops.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=2e-5,
                                   atol=2e-5, err_msg=f"blocks {bq}x{bk}")
