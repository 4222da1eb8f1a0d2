"""Dry-run machinery tests (fast pieces only — full-cell compiles are
exercised by launch/dryrun.py itself): input_specs coverage, the roofline
parser, and the collective-byte conventions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, SHAPES
from repro.launch import roofline as RL
from repro.launch import specs as SP


def test_input_specs_all_cells():
    """Every (arch × shape) cell yields well-formed templates with the
    mandated skip set: exactly the 8 full-attention archs skip long_500k."""
    skips = []
    for arch in ASSIGNED_ARCHS:
        for shape in SHAPES:
            spec = SP.input_specs(arch, shape)
            if spec["kind"] == "skip":
                skips.append((arch, shape))
                continue
            if spec["kind"] == "train":
                leaves = jax.tree.leaves(spec["state"]) + jax.tree.leaves(
                    spec["batch"])
            elif spec["kind"] == "prefill":
                leaves = jax.tree.leaves(spec["params"]) + [spec["tokens"]]
            else:
                leaves = (jax.tree.leaves(spec["params"])
                          + jax.tree.leaves(spec["state"])
                          + [spec["tokens"]])
            assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
    assert len(skips) == 8
    assert all(s == "long_500k" for _, s in skips)
    assert {"xlstm-350m", "recurrentgemma-9b"}.isdisjoint(
        {a for a, _ in skips})


def test_decode_templates_batch_and_len():
    spec = SP.input_specs("qwen3-14b", "decode_32k")
    k = spec["state"]["groups"][0]["k"]
    assert k.shape[1] == 128 and k.shape[2] == 32768  # [L, B, S, Hkv, hd]
    assert spec["tokens"].shape == (128,)


HLO = """
  %ag = bf16[16,4096,128]{2,1,0} all-gather(bf16[1,4096,128]{2,1,0} %p0), replica_groups={...}
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %p1), to_apply=%sum
  %rs = f32[64]{0} reduce-scatter(f32[1024]{0} %p2), dimensions={0}
  %cp = pred[1048576]{0} collective-permute(%copy.27), channel_id=1
  %aa = s32[128,64]{1,0} all-to-all(s32[128,64]{1,0} %p3), dimensions={0}
  %ag2.1 = (f32[8]{0}, f32[8]{0}) all-gather-start(f32[2]{0} %a, f32[2]{0} %b)
  %agd = f32[8]{0} all-gather-done(%ag2.1)
"""


def test_collective_parser_conventions():
    c = RL.parse_collectives(HLO)
    # all-gather: wire = output bytes
    assert c["all-gather"]["wire_bytes"] == 16 * 4096 * 128 * 2 + 2 * 8 * 4
    # all-reduce: 2x operand bytes
    assert c["all-reduce"]["wire_bytes"] == 2 * 1024 * 4
    # reduce-scatter: operand bytes
    assert c["reduce-scatter"]["wire_bytes"] == 1024 * 4
    # permute with elided operand type falls back to output bytes
    assert c["collective-permute"]["wire_bytes"] == 1048576 * 1
    assert c["all-to-all"]["wire_bytes"] == 128 * 64 * 4
    # -done ops are not double counted
    assert c["all-gather"]["count"] == 2


def test_roofline_terms_and_bottleneck():
    pk = RL.peaks_for("TPU v5 lite")
    rf = RL.Roofline(flops=pk.flops * 0.01, hbm_bytes=pk.hbm_bw * 0.05,
                     wire_bytes=pk.ici_bw * 0.002, chips=256,
                     model_flops=197e12 * 0.008 * 256, collectives={})
    assert abs(rf.compute_s - 0.01) < 1e-9
    assert abs(rf.memory_s - 0.05) < 1e-9
    assert abs(rf.collective_s - 0.002) < 1e-9
    assert rf.bottleneck == "memory"
    assert abs(rf.useful_compute_ratio - 0.8) < 1e-6
    assert abs(rf.roofline_fraction - 0.16) < 1e-6
    with pytest.raises(ValueError, match="no published peaks"):
        RL.peaks_for("TPU v0 imaginary")


def test_mesh_shapes():
    from repro.launch.mesh import make_production_mesh
    if len(jax.devices()) < 256:
        with pytest.raises(RuntimeError):
            make_production_mesh()
    else:  # when run under the dryrun env
        m = make_production_mesh()
        assert m.devices.shape == (16, 16)
