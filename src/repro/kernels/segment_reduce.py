"""Blocked segment-combine Pallas kernel — the TPU-native rewrite of the
paper's Phase-1 message merging (scatter-combine at dst).

GPU systems scatter messages with atomics; on TPU the idiomatic form is a
dense one-hot product per (vertex block, edge block): a matmul on the MXU
for float sums, a masked select + reduce on the VPU for integer sums and
min/max. Edges arrive dst-sorted (the framework's canonical order), so
each vertex block meets only the contiguous run of edge blocks holding
its in-edges, and the grid visits just those pairs.

This is the fused gather–emit–combine kernel with an identity emit over
already-materialized messages: one kernel body, one grid, one set of
tiling rules (see fused_gather_emit.py).

Layout: vals [E] or [E, D] (messages x payload), seg [E] (dst ids,
sorted), out [num_segments] or [num_segments, D]. Accumulation dtype:
float32 for floating payloads, int32 for integer payloads (min/max on
int32 ids — e.g. CC labels at 2^31-1 — stays exact); segments with no
message hold the monoid's identity in the payload dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .fused_gather_emit import TILE_1D, gather_emit_combine, \
    gather_emit_combine_packed


def _identity_emit(s, d, sp, ep):
    return jnp.bool_(True), ep


@functools.partial(
    jax.jit, static_argnames=("num_segments", "monoid", "block_v",
                              "block_e"))
def segment_combine_kernel(vals, seg_ids, num_segments: int,
                           monoid: str = "sum", block_v: int = TILE_1D,
                           block_e: int = TILE_1D):
    """vals [E] / [E, D] combined into [num_segments(, D)] under `monoid`.

    seg_ids must be sorted ascending (dst-sorted canonical edge order).
    """
    E = int(vals.shape[0])
    src = jnp.zeros((E,), jnp.int32)
    kw = dict(block_v=block_v, block_e=block_e)
    if vals.ndim == 1:
        out, _ = gather_emit_combine(_identity_emit, monoid, src, seg_ids,
                                     (), vals, None, num_segments, **kw)
    else:
        out, _ = gather_emit_combine_packed(_identity_emit, (monoid,), src,
                                            seg_ids, (), vals, None,
                                            num_segments, **kw)
    return out
