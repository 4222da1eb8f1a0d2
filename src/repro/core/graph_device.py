"""Typed device-graph pytrees — the shared vocabulary of the message plane.

Every engine used to thread its own stringly-typed dict of edge arrays
(``gdev["src_s"]`` here, ``edges["edge_src_local"]`` there), which meant
the fused gather–emit–combine kernel was reachable from exactly one call
site. This module replaces those dicts with two registered dataclasses:

  :class:`EdgeLayout`   one *view* of an edge set — endpoints, edge
                        properties, the permutation linking it to the
                        combine (dst-sorted) order, precomputed
                        :class:`~repro.core.vcprog.SegmentMeta`, and an
                        optional valid-slot mask (distributed buckets are
                        padded). ``core/message_plane.py`` dispatches on
                        these fields alone, so any engine that can
                        describe its schedule as an EdgeLayout gets every
                        fast path for free.

  :class:`DeviceGraph`  the device-resident graph: both single-device
                        layouts (canonical dst-sorted + src-sorted) plus
                        degrees and input vertex properties.

Both are pytrees (``jax.tree_util.register_dataclass``): they pass
through ``jax.jit``, ``shard_map``, ``lax.cond`` branches and
``jax.pure_callback`` operand lists unchanged, with the shape-like fields
(`num_segments`, `num_edges`, …) as static aux data.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import vcprog
from .graph import PropertyGraph

#: edge-block size the scalar-prefetch window tables are computed for
#: host-side; the kernel's edge block spans TILE_1D / PREFETCH_BLOCK_E of
#: these table blocks (kernels.fused_gather_emit).
PREFETCH_BLOCK_E = 512

#: default frontier-sparse crossover: the auto dispatch compacts the
#: active edge set into a workset of ceil(SPARSE_CAP_FRAC * E) slots and
#: falls back to the dense pass whenever the frontier is wider. The
#: capacity IS the crossover density — sparse cost is O(cap) record work
#: plus O(E) cheap flag/cumsum ops (~1/4 of a dense pass measured on
#: CPU), so an E/8 workset keeps the sparse arm comfortably ahead of
#: dense everywhere it dispatches (~2.5x at 5% frontier density).
SPARSE_CAP_FRAC = 0.125


def workset_capacity(num_items: int, frac: float = SPARSE_CAP_FRAC) -> int:
    """Static workset slot count for frontier-sparse compaction: a
    fraction of the dense size, sublane-aligned, at least one slot. Used
    for both the message plane's active-edge workset (num_items = E) and
    the distributed delta exchange (num_items = v_per_part).

    ALWAYS 8-aligned: for tiny (n < 8) or unaligned n the capacity may
    exceed n — the excess slots carry sentinel pads (`compact_indices`
    fills them with the sentinel n, and every consumer drops the
    sentinel), so callers can rely on sublane alignment unconditionally.
    """
    n = int(num_items)
    if n <= 0:
        return 1
    cap = max(-(-int(np.ceil(n * float(frac))) // 8) * 8, 8)
    return int(min(cap, -(-n // 8) * 8))


#: lane-chunk width `lane_chunk="auto"` resolves to: past this many query
#: lanes one over-wide slab stops paying (VMEM pressure + aligned-step
#: growth of the packed panels), so `run_vcprog` splits the batch into
#: sub-batches of this width instead — each chunk rides the compiled
#: runner of its width, so a 128-source request costs 4 cached Q=32 runs.
LANE_CHUNK_DEFAULT = 32


def resolve_lane_chunk(lane_chunk) -> int:
    """Resolve the `lane_chunk` knob: None/0 = no chunking (one slab
    regardless of Q), "auto" = LANE_CHUNK_DEFAULT, an int = that width."""
    if lane_chunk in (None, 0, False, "none", "off"):
        return 0
    if lane_chunk == "auto":
        return LANE_CHUNK_DEFAULT
    w = int(lane_chunk)
    if w < 1:
        raise ValueError(f"lane_chunk must be >= 1, got {lane_chunk!r}")
    return w


def lane_slab_width(num_lanes: int) -> int:
    """Slab columns Q query lanes occupy in the packed fused kernel:
    a batched scalar leaf is a [V, Q] record leaf, so its PackSlot takes
    `ncols = Q` and the group slab pads to the sublane quantum
    (kernels.fused_gather_emit.LANE_ALIGN). Per-launch slab work is
    therefore flat in Q up to the alignment width and grows in aligned
    steps after — the quantity the batched-bench rows and the
    Q-crossover guidance in docs/perf.md are stated against."""
    from ..kernels.fused_gather_emit import LANE_ALIGN
    q = max(int(num_lanes), 1)
    return -(-q // LANE_ALIGN) * LANE_ALIGN


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EdgeLayout:
    """One view of an edge set, as the message plane consumes it.

    Data fields (traced):
      src:        [E] indices into the vertex-property batch (gather axis).
                  For distributed buckets these are *local* slot indices.
      dst:        [E] combine segment ids in [0, num_segments); for padded
                  layouts, invalid slots carry the sentinel id
                  ``num_segments`` so the array stays ascending.
      eprops:     edge-property record batch, leading dim E.
      perm:       optional [E'] gather permutation mapping this layout's
                  emission order into the combine (dst-sorted) order —
                  ``None`` when the layout already IS combine-ordered.
                  When set, ``canonical`` must hold the combine-ordered
                  alias (its dst/seg_meta drive the segment reduction).
      seg_meta:   precomputed static SegmentMeta of `dst` (combine-ordered
                  layouts only).
      valid_mask: optional [E] bool — False rows are padding and can never
                  emit (distributed buckets).
      src_ids / dst_ids: optional [E] *global* endpoint ids handed to the
                  user's ``emit_message`` when they differ from src/dst
                  (distributed buckets emit with global ids but combine on
                  local ones). ``None`` means src/dst are the ids.
      canonical:  optional combine-ordered alias of the same edge set —
                  lets the dispatcher run the fused kernel for a permuted
                  (e.g. src-sorted) view.
      prefetch_blocks: optional [ceil(E/PREFETCH_BLOCK_E)] int32 window
                  block index per edge block (scalar-prefetch variant).

    Static fields (aux data, part of the jit cache key):
      num_segments:    combine fan-in (V, or v_per_part for buckets).
      num_edges:       edge SLOT count — the leading dim of src/dst/
                  eprops. Pre-padded layouts count their padding here;
                  ``valid_mask`` is what distinguishes real edges.
      prefetch_window: src-window row count for the scalar-prefetch fused
                  kernel; 0 = no prefetch metadata.
      pack:       optional :class:`~repro.kernels.fused_gather_emit.PackSpec`
                  — the lane-aligned multi-leaf packing table (host-side
                  slab offsets per record leaf) for the packed fused
                  kernel. The spec depends on the PROGRAM's record
                  schemas, so graph builders leave it None and the
                  message plane derives it at trace time; callers running
                  one known program may precompute it with
                  `make_pack_spec` and bake it into their layout (it is
                  hashable and keys the jit cache like the other static
                  fields).
    """

    src: Any
    dst: Any
    eprops: Any
    perm: Any = None
    seg_meta: Optional[vcprog.SegmentMeta] = None
    valid_mask: Any = None
    src_ids: Any = None
    dst_ids: Any = None
    canonical: Optional["EdgeLayout"] = None
    prefetch_blocks: Any = None
    num_segments: int = dataclasses.field(
        default=0, metadata=dict(static=True))
    num_edges: int = dataclasses.field(default=0, metadata=dict(static=True))
    prefetch_window: int = dataclasses.field(
        default=0, metadata=dict(static=True))
    pack: Any = dataclasses.field(default=None, metadata=dict(static=True))

    @property
    def emit_src_ids(self):
        return self.src if self.src_ids is None else self.src_ids

    @property
    def emit_dst_ids(self):
        return self.dst if self.dst_ids is None else self.dst_ids

    @property
    def combine_view(self) -> "EdgeLayout":
        """The combine-ordered (dst-sorted) alias of this edge set."""
        return self if self.perm is None else self.canonical


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DeviceGraph:
    """Device-resident property graph: both single-device edge layouts
    plus the vertex-level arrays every engine needs.

    When the graph was built with a reorder strategy, the layouts index a
    *relabeled* vertex space and ``vertex_perm``/``inv_perm`` record the
    mapping (``vertex_perm[new] = old``; ``inv_perm[old] = new``). The
    engine driver initializes vertices with their OLD ids (what
    ``init_vertex`` sees), the layouts carry the old ids through
    ``src_ids``/``dst_ids`` (what ``emit_message`` sees), and results are
    un-permuted before returning — user-visible ids never change.
    """

    canonical: EdgeLayout      # dst-sorted ("CSR over in-edges")
    src_sorted: EdgeLayout     # out-edge order, perm -> canonical
    out_degree: Any
    in_degree: Any
    vprops_in: Dict[str, Any]
    vertex_perm: Any = None    # [V] int32, new id -> old id (None = natural)
    inv_perm: Any = None       # [V] int32, old id -> new id
    num_vertices: int = dataclasses.field(
        default=0, metadata=dict(static=True))
    num_edges: int = dataclasses.field(default=0, metadata=dict(static=True))


def prefetch_block_bounds(src: np.ndarray,
                          block_e: int = PREFETCH_BLOCK_E,
                          valid: np.ndarray | None = None):
    """Per-edge-block [lo, hi] src bounds — the ONE host-side scan every
    prefetch-window consumer derives from (`compute_prefetch_windows`,
    `engines/distributed.build_bucket_prefetch`). `valid` marks real
    slots of pre-padded layouts: invalid slots are forward-filled with
    the nearest real src (leading pads backfill with the first real
    one), so padding can never stretch a block's span. Returns
    (lo [n_blocks], hi [n_blocks]) int64, or None when there is nothing
    valid to bound (empty edge set / all-pad bucket)."""
    src = np.asarray(src)
    E = int(src.shape[0])
    if E == 0:
        return None
    n_blocks = -(-E // block_e)
    if valid is not None:
        valid = np.asarray(valid, bool)
        if not valid.any():
            return None
        pos = np.maximum.accumulate(np.where(valid, np.arange(E), -1))
        src = np.where(pos >= 0, src[np.maximum(pos, 0)],
                       src[int(valid.argmax())])
    pad = n_blocks * block_e - E
    # pad with the last real src id so padding never widens a window
    src_p = np.concatenate([src, np.full(pad, src[-1], src.dtype)])
    blocks = src_p.reshape(n_blocks, block_e)
    return (blocks.min(axis=1).astype(np.int64),
            blocks.max(axis=1).astype(np.int64))


def min_prefetch_window(span: int, num_vertices: int) -> int:
    """Smallest legal slab width for a block span: the power of two >=
    `span`, or 0 (resident fallback) when the slab pair would reach the
    vertex range."""
    w = 8
    while w < span:
        w *= 2
    return 0 if 2 * w >= num_vertices else w


def compute_prefetch_windows(src: np.ndarray, num_vertices: int,
                             block_e: int = PREFETCH_BLOCK_E,
                             valid: np.ndarray | None = None,
                             window: int | None = None):
    """Host-side window metadata for the scalar-prefetch fused kernel.

    For each block of `block_e` edges, the kernel DMAs TWO adjacent
    `window`-row src slabs (indices ``block_idx[e]`` and
    ``block_idx[e] + 1``) instead of keeping the whole [V] vertex
    property resident in VMEM. With `window` = next power of two >= the
    widest block's src span, the slab pair [q·W, (q+2)·W) with
    q = src_min // W always covers [src_min, src_max] — no start-
    quantization penalty, arbitrary block index maps stay legal.

    `valid` marks real edge slots of pre-padded layouts (distributed
    buckets carry trailing sentinel-dst pads whose src values are
    arbitrary): invalid slots are forward-filled with the nearest real
    src id, so padding can never widen a window. All-invalid input means
    no metadata.

    `window` forces that slab width instead of deriving the minimal one —
    the distributed planes share one static window across parts (and, for
    the ring schedule, across buckets) because shard_map traces ONE
    program for every device. A forced window that does not cover the
    widest block span is refused (returns window 0) rather than silently
    dropping the out-of-slab edges.

    Returns (block_idx [n_blocks] int32, window int). window == 0 means
    no useful metadata (empty edge set, or the window would be at least
    half the vertex range — the resident variant wins there).
    """
    src = np.asarray(src)
    E = int(src.shape[0])
    if E == 0 or num_vertices == 0:
        return np.zeros((1,), np.int32), 0
    n_blocks = -(-E // block_e)
    bounds = prefetch_block_bounds(src, block_e, valid)
    if bounds is None:
        return np.zeros((n_blocks,), np.int32), 0
    lo, hi = bounds

    span = int((hi - lo).max()) + 1
    if window is None:
        w = min_prefetch_window(span, num_vertices)
    elif int(window) < span:
        w = 0  # forced window cannot cover the widest block — refuse
    else:
        w = int(window) if 2 * int(window) < num_vertices else 0
    if w == 0:
        return np.zeros((n_blocks,), np.int32), 0  # resident fallback
    return (lo // w).astype(np.int32), int(w)


def build_device_graph(g: PropertyGraph,
                       reorder: str = "none") -> DeviceGraph:
    """Host→device conversion of the canonical + src-sorted edge layouts.

    Precomputes everything structural that is a loop constant: the
    dst-sorted SegmentMeta (from the CSC row pointers already on the
    graph), the canonical→src-sorted permutation, and the scalar-prefetch
    window table of the canonical order.

    `reorder` ("none"|"rcm"|"degree"|"auto", see core/reorder.py) relabels
    the vertex space host-side first — the layouts (and their recomputed
    SegmentMeta / prefetch windows) then describe the reordered edges,
    while the ORIGINAL ids ride the layouts' `src_ids`/`dst_ids` so the
    user's `emit_message` never sees the relabeling.

    Host spans: `obs.PREPARE` around it all, with the children
    `PREPARE_LAYOUTS` (relabeling, src-sorted order, inverse CSC,
    last_edge), `PREPARE_WINDOWS` (the window table) and `PREPARE_UPLOAD`
    (the host-to-device transfers).
    """
    with obs.span(obs.PREPARE):
        with obs.span(obs.PREPARE_LAYOUTS):
            g, perm_np, inv_np, layouts = _host_layouts(g, reorder)
        with obs.span(obs.PREPARE_WINDOWS):
            windows = compute_prefetch_windows(g.src, int(g.num_vertices))
        with obs.span(obs.PREPARE_UPLOAD):
            return _upload(g, perm_np, inv_np, layouts, windows)


def _host_layouts(g: PropertyGraph, reorder: str):
    """The relabeled graph, its permutations, and the host arrays of the
    src-sorted layout and segment structure."""
    perm_np = inv_np = None
    if reorder not in (None, "none"):
        from .reorder import apply_reorder
        g, perm_np, inv_np = apply_reorder(g, reorder)
    src_s, dst_s, eprops_s = g.src_sorted()
    inv_csc = np.empty_like(g.csc_perm)
    inv_csc[g.csc_perm] = np.arange(g.csc_perm.shape[0])
    last_edge = np.clip(g.in_indptr[1:] - 1, 0, max(g.num_edges - 1, 0))
    return g, perm_np, inv_np, (src_s, dst_s, eprops_s, inv_csc, last_edge)


def _upload(g: PropertyGraph, perm_np, inv_np, layouts, windows
            ) -> DeviceGraph:
    src_s, dst_s, eprops_s, inv_csc, last_edge = layouts
    pf_blocks, pf_window = windows
    V, E = int(g.num_vertices), int(g.num_edges)
    meta = vcprog.SegmentMeta(
        last_edge=jnp.asarray(last_edge.astype(np.int32)),
        has_edge=jnp.asarray(g.in_degree > 0))

    # original (user-visible) endpoint ids of the relabeled edges
    uid = (lambda a: None) if perm_np is None else (
        lambda a: jnp.asarray(perm_np[np.asarray(a)].astype(np.int32)))

    canonical = EdgeLayout(
        src=jnp.asarray(g.src),
        dst=jnp.asarray(g.dst),
        eprops=jax.tree.map(jnp.asarray, g.edge_props),
        seg_meta=meta,
        src_ids=uid(g.src), dst_ids=uid(g.dst),
        prefetch_blocks=jnp.asarray(pf_blocks),
        num_segments=V, num_edges=E, prefetch_window=pf_window)
    src_sorted = EdgeLayout(
        src=jnp.asarray(src_s),
        dst=jnp.asarray(dst_s),
        eprops=jax.tree.map(jnp.asarray, eprops_s),
        # canonical -> src-sorted position: gathering emissions with this
        # permutation scatters them back into combine (dst) order
        perm=jnp.asarray(inv_csc),
        src_ids=uid(src_s), dst_ids=uid(dst_s),
        canonical=canonical,
        num_segments=V, num_edges=E)
    return DeviceGraph(
        canonical=canonical,
        src_sorted=src_sorted,
        out_degree=jnp.asarray(g.out_degree),
        in_degree=jnp.asarray(g.in_degree),
        vprops_in=jax.tree.map(jnp.asarray, g.vertex_props),
        vertex_perm=None if perm_np is None
        else jnp.asarray(perm_np.astype(np.int32)),
        inv_perm=None if inv_np is None
        else jnp.asarray(inv_np.astype(np.int32)),
        num_vertices=V, num_edges=E)


def bucket_layout(src_local, src_global, dst_local, dst_global, eprops,
                  mask, seg_meta, v_per_part: int,
                  prefetch_blocks=None, prefetch_window: int = 0
                  ) -> EdgeLayout:
    """EdgeLayout over ONE distributed src-owner bucket of local in-edges.

    The bucket is combine-ordered already (dst-local ascending with
    sentinel pads), padded to the common slot count L, and emits with
    global endpoint ids. `prefetch_blocks`/`prefetch_window` attach the
    bucket's scalar-prefetch window table (see
    `engines/distributed.build_bucket_prefetch`); window 0 — or no table
    — is the bucket's resident fallback.
    """
    return EdgeLayout(
        src=src_local, dst=dst_local, eprops=eprops,
        valid_mask=mask, seg_meta=seg_meta,
        src_ids=src_global, dst_ids=dst_global,
        prefetch_blocks=prefetch_blocks if prefetch_window else None,
        num_segments=int(v_per_part),
        num_edges=int(dst_local.shape[0]),
        prefetch_window=int(prefetch_window))
