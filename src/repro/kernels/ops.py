"""Jit'd public wrappers around the Pallas kernels.

On a TPU the kernels compile to Mosaic; on any other backend (the CPU
test path) they run in interpret mode — the kernel body executes in
Python, validating the exact TPU code path. The mode follows the backend
(`fused_gather_emit.interpret_mode`) and is not a caller's choice.
"""
from __future__ import annotations

from . import ref
from .flash_attention import flash_attention_kernel
from .fused_gather_emit import gather_emit_combine as _gather_emit_combine
from .fused_gather_emit import \
    gather_emit_combine_packed as _gather_emit_combine_packed
from .fused_gather_emit import interpret_mode
from .segment_reduce import segment_combine_kernel


def segment_combine(vals, seg_ids, num_segments: int, monoid: str = "sum",
                    **block_kw):
    """Segment combine of dst-sorted messages; vals [E] or [E, D]."""
    return segment_combine_kernel(vals, seg_ids, num_segments, monoid=monoid,
                                  **block_kw)


def gather_emit_combine(emit_fn, monoid, src, dst, vprops, eprops, active,
                        num_vertices: int, **kw):
    """Fused single-pass gather(src props) -> emit -> segment-combine.

    The one-kernel form of the pull-mode message plane; see
    fused_gather_emit.py for the layout contract. Optional kw: `valid`
    (pre-padded layouts), `src_ids`/`dst_ids` (global emit ids),
    `prefetch=(block_idx, window, block_e)` (scalar-prefetch variant),
    `block_skip=True` (frontier bitmap early-out of dead edge blocks),
    plus block sizes."""
    return _gather_emit_combine(emit_fn, monoid, src, dst, vprops, eprops,
                                active, num_vertices, **kw)


def gather_emit_combine_packed(emit_fn, monoids, src, dst, vprops, eprops,
                               active, num_vertices: int, **kw):
    """Packed multi-leaf fused pass: whole record in ONE launch, vertex
    props in per-dtype slabs, per-slice monoid table `monoids` (one named
    monoid per flattened message leaf). Optional kw as above (no
    `prefetch`) plus `pack=` (a precomputed PackSpec)."""
    return _gather_emit_combine_packed(emit_fn, monoids, src, dst, vprops,
                                       eprops, active, num_vertices, **kw)


def flash_attention(q, k, v, causal: bool = True, window: int | None = None,
                    sm_scale: float | None = None, **block_kw):
    """Causal GQA flash attention; q [B,Hq,T,Dh], k/v [B,Hkv,S,Dh]."""
    return flash_attention_kernel(q, k, v, causal=causal, window=window,
                                  sm_scale=sm_scale,
                                  interpret=interpret_mode(), **block_kw)


# re-export oracles for convenience
segment_combine_ref = ref.segment_combine_ref
gather_emit_combine_ref = ref.gather_emit_combine_ref
mha_ref = ref.mha_ref
