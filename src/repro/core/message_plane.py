"""The message plane: ONE dispatcher for Phase 3 (emit) + Phase 1 (merge).

Every engine is a schedule over the same dataflow — evaluate the user's
``emit_message`` along an edge layout, then fold the messages into
per-vertex inboxes under the user's monoid. This module is the single
place that dataflow is implemented and dispatched:

    emit_and_combine(program, layout, vprops, active, empty,
                     kernel_on=..., mode=...)

``layout`` is an :class:`~repro.core.graph_device.EdgeLayout`; the
dispatcher reads its fields (perm? valid_mask? prefetch table? canonical
alias?) and the program's monoid — one name for the whole record, or a
per-leaf table for mixed records — to pick between

  * the fused gather–emit–combine Pallas kernel (one pass, messages never
    touch HBM) — resident or scalar-prefetch variant, and for multi-leaf
    records the PACKED shape (per-dtype vprops slabs, per-(dtype, monoid)
    message panels, whole record in one launch),
  * the blocked Pallas segment-combine kernel over materialized messages,
  * XLA segment ops (named monoids, uniform or per-leaf) or a flagged
    associative scan (general monoids),

with permute-then-combine inserted automatically for emission orders that
are not combine-ordered (pregel's src-sorted view). Because every engine
routes through this entry point, a fast path added here is immediately
reachable from pregel, GAS, pushpull, callback and each distributed
bucket — the GraphX lesson applied to our Pallas specializations.

The plane is also where frontier sparsity lives (``frontier=`` knob):
convergent programs (SSSP, CC, label propagation) spend most supersteps
on a thin frontier, so the fused kernels consult a per-edge-block
``any_active`` bitmap and early-out dead blocks, and the unfused pass
compacts the active edge set into a static-capacity workset with a dense
fallback above the crossover — pushpull's push/pull density heuristic
promoted into the dispatcher, inherited by every engine. All modes are
bit-identical to dense.

Batched multi-query execution rides this plane for free: a
:class:`~repro.core.vcprog.BatchedProgram` stores Q query lanes as a
trailing axis on every record leaf ([V, Q] vprops, [E, Q] messages), so
``_has_vector_leaves`` routes it to the PACKED fused kernel where the
lanes stream as slab columns — ONE pass over the edge layout per
superstep regardless of Q. The frontier the plane consumes is the
OR-across-lanes union (``vcprog.frontier_mask``), so block-skip and
sparse compaction keep every block/edge that ANY unconverged lane still
needs; converged lanes emit exact monoid identities, so their folds are
per-lane no-ops and each lane's result stays bit-identical to its own
sequential run.

Device scopes (`repro.obs`): `PLANE_GATHER` holds every gather of vertex
values or the frontier into edge order (and the unfused emit evaluated
on them), `PLANE_OPERANDS` the per-pass edge operands and scalar tables,
`PLANE_KERNEL` the Pallas calls and the fused pass's empty-record fill,
`PLANE_COMBINE` the unfused permute and segment combine.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import obs
from . import records
from .graph_device import EdgeLayout, SPARSE_CAP_FRAC, workset_capacity
from .knobs import knob_error
from .vcprog import Record, RecordBatch, SegmentMeta, VCProgram, \
    frontier_mask, make_segment_meta

_MODES = ("auto", "fused", "unfused")
_MULTILEAF = ("auto", "packed", "perleaf")
_FRONTIER = ("auto", "dense", "sparse")
_PREFETCH = ("auto", "on", "off")
_NAMED = ("sum", "min", "max")


# ---------------------------------------------------------------------------
# Per-leaf monoid resolution
# ---------------------------------------------------------------------------

def leaf_monoids(program: VCProgram, msg_tree) -> Optional[Tuple[str, ...]]:
    """Resolve `program.monoid` into a per-leaf named-monoid table.

    `monoid` may be one name for the whole record ("sum"|"min"|"max"), or
    a pytree of names mirroring the message record — the per-slice table
    of the packed fused kernel (e.g. ``{"dist": "min", "count": "sum"}``).
    Returns the table in flattened-leaf order, or None when any leaf needs
    the general (merge_message) path.
    """
    m = program.monoid
    leaves = jax.tree.leaves(msg_tree)
    if isinstance(m, str):
        return tuple([m] * len(leaves)) if m in _NAMED else None
    names, mdef = jax.tree.flatten(m)
    if mdef != jax.tree.structure(msg_tree):
        raise ValueError(
            f"per-leaf monoid table {m!r} does not mirror the message "
            "record returned by empty_message()")
    if any(n not in _NAMED for n in names):
        return None
    return tuple(names)


# ---------------------------------------------------------------------------
# Kernel knob
# ---------------------------------------------------------------------------

def resolve_frontier_mode(frontier) -> str:
    """Validate the frontier knob ("auto"|"dense"|"sparse"; None="dense").

    "dense" runs every plane pass over all E edge slots (the historical
    behavior). "auto" makes iteration cost track the frontier: the fused
    kernels early-out edge blocks with no active src, and the unfused
    pass compacts the active edge set into a `workset_capacity(E)`-slot
    workset whenever it fits (dense fallback above the crossover).
    "sparse" forces the sparse shape of whichever path dispatches —
    block-skip when the fused kernel runs, the compaction arm at full
    (always-exact) capacity otherwise; use kernel_on=False (or
    mode="unfused") to pin the compaction arm for verification/benching.
    Every mode is bit-identical."""
    if frontier is None:
        return "dense"
    if frontier not in _FRONTIER:
        raise knob_error("frontier", frontier, _FRONTIER)
    return frontier


def resolve_kernel_mode(kernel) -> bool:
    """Resolve the tri-state kernel knob to a concrete on/off.

    "auto" picks the Pallas kernels on TPU and the XLA segment ops on CPU
    (where the kernels would run in interpret mode — a correctness path,
    not a fast path). Booleans are accepted as a legacy alias. This is
    THE canonical resolver (``vcprog.resolve_kernel_mode`` is a
    compatibility delegate); anything else raises a ValueError rather
    than falling through to an implicit mode.
    """
    if kernel is None:
        kernel = "auto"
    if isinstance(kernel, bool):
        return kernel
    if kernel == "auto":
        return jax.default_backend() == "tpu"
    if kernel in ("on", "off"):
        return kernel == "on"
    raise knob_error("kernel", kernel, ("auto", "on", "off"),
                     note="(or a legacy bool)")


def resolve_kernel_arg(kernel, use_kernel) -> bool:
    """Resolve the public (kernel=, use_kernel=) argument pair: the
    legacy boolean alias wins when given. One place for the precedence
    rule every entry point (run_vcprog, run_vcprog_distributed, the
    UniGPS session) used to re-implement."""
    return resolve_kernel_mode(
        use_kernel if use_kernel is not None else kernel)


def resolve_prefetch_mode(prefetch) -> str:
    """Validate the scalar-prefetch knob ("auto"|"on"|"off"; None="auto").

    "auto" lets the fused dispatch use whatever window metadata the
    layout carries (and lets the distributed builder attach per-bucket
    tables whenever the kernels are on); "off" ignores the metadata —
    every fused pass runs vprops-resident (the bench/verification
    baseline); "on" forces the distributed builder to attach tables even
    when the kernels are off (at the plane itself it behaves like
    "auto": a layout without metadata — e.g. a bucket whose window would
    be resident-sized — still falls back to resident). Unknown strings
    raise."""
    if prefetch is None:
        return "auto"
    if prefetch not in _PREFETCH:
        raise knob_error("prefetch", prefetch, _PREFETCH)
    return prefetch


# ---------------------------------------------------------------------------
# Segment combination under the user monoid (combine-ordered messages)
# ---------------------------------------------------------------------------

def _has_msg(valid: jnp.ndarray, dst: jnp.ndarray,
             num_segments: int) -> jnp.ndarray:
    """has_msg[v] = some valid emission targets v. The ONE dynamic segment
    reduction per combine — everything else structural comes from meta."""
    return (jax.ops.segment_max(valid.astype(jnp.int32), dst,
                                num_segments=num_segments,
                                indices_are_sorted=True) > 0)


def _segment_general(program: VCProgram, msgs: RecordBatch, dst: jnp.ndarray,
                     valid: jnp.ndarray, num_segments: int, empty: Record,
                     meta: SegmentMeta) -> Tuple[RecordBatch, jnp.ndarray]:
    """Generic segment-combine via a flagged associative scan.

    Edges must be dst-sorted. Works for ANY associative+commutative
    merge_message — the TPU-native replacement for scatter-combine.
    """
    E = dst.shape[0]
    # identity-mask invalid emissions so they cannot contribute
    empty_b = records.tree_tile(empty, E)
    msgs = records.tree_where(valid, msgs, empty_b)

    seg_start = jnp.concatenate([jnp.ones((1,), bool), dst[1:] != dst[:-1]])

    def comb(left, right):
        fl, vl = left
        fr, vr = right
        merged = jax.vmap(program.merge_message)(vl, vr)
        v = records.tree_where(fr, vr, merged)
        return (fl | fr, v)

    _, scanned = jax.lax.associative_scan(comb, (seg_start, msgs))

    # inbox[v] = scanned value at the last in-edge of v (precomputed)
    inbox = records.tree_gather(scanned, meta.last_edge)
    empty_v = records.tree_tile(empty, num_segments)
    inbox = records.tree_where(meta.has_edge, inbox, empty_v)
    return inbox, _has_msg(valid, dst, num_segments)


def _segment_named(program: VCProgram, msgs: RecordBatch, dst: jnp.ndarray,
                   valid: jnp.ndarray, num_segments: int, empty: Record,
                   meta: SegmentMeta, monoids: Tuple[str, ...],
                   seg_op=None) -> Tuple[RecordBatch, jnp.ndarray]:
    """Fast path for named elementwise monoids — `monoids` is the per-leaf
    table (uniform or mixed sum/min/max across the record's fields).
    `seg_op(leaf, monoid)` overrides the reduction (the blocked Pallas
    kernel plugs in here); the default is the XLA segment ops."""
    if seg_op is None:
        ops = {"sum": jax.ops.segment_sum,
               "min": jax.ops.segment_min,
               "max": jax.ops.segment_max}
        seg_op = lambda x, monoid: ops[monoid](
            x, dst, num_segments=num_segments, indices_are_sorted=True)
    E = dst.shape[0]
    empty_b = records.tree_tile(empty, E)
    msgs = records.tree_where(valid, msgs, empty_b)

    def leaf(x, e, monoid):
        out = seg_op(x, monoid)
        if monoid in ("min", "max"):
            # segments with no edges return +/-inf-ish init; clamp to identity
            has = meta.has_edge.reshape(
                meta.has_edge.shape + (1,) * (out.ndim - 1))
            out = jnp.where(has, out, jnp.broadcast_to(e, out.shape).astype(out.dtype))
        return out.astype(x.dtype)

    m_leaves, mdef = jax.tree.flatten(msgs)
    e_leaves = [jnp.asarray(l) for l in jax.tree.leaves(empty)]
    inbox = jax.tree.unflatten(mdef, [leaf(x, e, mo) for x, e, mo in
                                      zip(m_leaves, e_leaves, monoids)])
    return inbox, _has_msg(valid, dst, num_segments)


@obs.scope(obs.PLANE_COMBINE)
def segment_combine(program: VCProgram, msgs, dst, valid, num_segments, empty,
                    kernel_on: bool = False,
                    meta: Optional[SegmentMeta] = None):
    """Combine per-edge messages into per-vertex inboxes (dst-sorted edges).

    kernel_on=True routes named monoids through the Pallas segment kernel
    (MXU one-hot matmul for sum, segmented-scan + pick matmul for min/max).
    `meta` is the precomputed static segment structure; pass it whenever the
    call sits inside a compiled loop so no structural reductions recompute
    per iteration (a traced fallback is derived here otherwise).
    """
    if meta is None:
        meta = make_segment_meta(dst, num_segments)
    monoids = leaf_monoids(program, msgs)
    if monoids is not None:
        seg_op = None
        if kernel_on:
            from repro.kernels import ops as kops
            seg_op = lambda x, monoid: kops.segment_combine(
                x, dst, num_segments, monoid=monoid)
        return _segment_named(program, msgs, dst, valid, num_segments, empty,
                              meta, monoids, seg_op=seg_op)
    return _segment_general(program, msgs, dst, valid, num_segments, empty,
                            meta)


# ---------------------------------------------------------------------------
# Frontier-sparse machinery: device-side compaction of the active edge set
# ---------------------------------------------------------------------------

def compact_indices(flag, cap: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Order-preserving device-side compaction of True positions.

    Returns (idx, count): idx [cap] int32 holds the positions of the
    first `cap` True flags in ascending order, padded with the sentinel
    ``flag.shape[0]``; count is the total number of True flags. Flags
    beyond `cap` are dropped, so exact callers dispatch on
    ``count <= cap`` (the auto crossover) or pass ``cap = len(flag)``.
    """
    n = int(flag.shape[0])
    if n == 0:
        return jnp.zeros((cap,), jnp.int32), jnp.int32(0)
    # idx[k] = position of the (k+1)-th True flag = first index whose
    # running count reaches k+1; k beyond the count lands at n (the
    # sentinel) for free. Binary search beats a scatter ~6x on CPU and
    # avoids serializing XLA scatter semantics on TPU.
    csum = jnp.cumsum(flag.astype(jnp.int32))
    idx = jnp.searchsorted(csum, jnp.arange(1, cap + 1, dtype=jnp.int32),
                           side="left").astype(jnp.int32)
    return idx, csum[-1]


def _sparse_emit_combine(program: VCProgram, cv: EdgeLayout, vprops,
                         empty: Record, kernel_on: bool,
                         monoids: Tuple[str, ...], act_e, cap: int
                         ) -> Tuple[RecordBatch, jnp.ndarray]:
    """The frontier-sparse arm: compact the CSR slices of active sources
    into a `cap`-slot workset, then run emit + segment-combine over the
    workset only — iteration cost O(cap) record work instead of O(E).

    `cv` must be the combine-ordered view and `act_e` the per-edge
    frontier flags in ITS order (active src & valid slot). Compaction is
    order-preserving, so the workset dst run stays ascending (sentinel
    `num_segments` pads keep it so through the tail) and every
    combine-path invariant of the dense pass carries over — the result is
    bit-identical to dense (same emission values folded under the same
    monoid, skipped slots contribute only identities).
    """
    E, V = cv.num_edges, cv.num_segments
    with obs.scope(obs.PLANE_OPERANDS):
        ws, count = compact_indices(act_e, cap)
        ws_valid = jnp.arange(cap, dtype=jnp.int32) < count
        wsc = jnp.minimum(ws, max(E - 1, 0))  # clip sentinel pads
    with obs.scope(obs.PLANE_GATHER):
        src_ws = jnp.take(cv.src, wsc, axis=0)
        dst_ws = jnp.where(ws_valid, jnp.take(cv.dst, wsc, axis=0),
                           jnp.int32(V))
        sid_ws = jnp.take(cv.emit_src_ids, wsc, axis=0)
        did_ws = jnp.where(ws_valid, jnp.take(cv.emit_dst_ids, wsc, axis=0),
                           jnp.int32(V))
        src_prop = records.tree_gather(vprops, src_ws)
        eprops_ws = records.tree_gather(cv.eprops, wsc)
        is_emit, msgs = jax.vmap(program.emit_message)(sid_ws, did_ws,
                                                       src_prop, eprops_ws)
        valid = is_emit.astype(bool) & ws_valid  # act folded into flags
    with obs.scope(obs.PLANE_COMBINE):
        # workset segment structure is dynamic (changes every superstep) —
        # derived in-trace at O(cap), unlike the loop-constant dense meta
        meta = make_segment_meta(dst_ws, V, valid=valid)
        seg_op = None
        if kernel_on:
            from repro.kernels import ops as kops
            seg_op = lambda x, monoid: kops.segment_combine(
                x, dst_ws, V, monoid=monoid)
        return _segment_named(program, msgs, dst_ws, valid, V, empty, meta,
                              monoids, seg_op=seg_op)


# ---------------------------------------------------------------------------
# Layout-level dataflow pieces (what engines compose)
# ---------------------------------------------------------------------------

@obs.scope(obs.PLANE_GATHER)
def edge_active(layout: EdgeLayout, active) -> jnp.ndarray:
    """Per-edge frontier flags in LAYOUT order: src on the frontier and
    the slot not padding. Computed ONCE per plane invocation and shared
    by the emit veto, the permuted combine mask, the sparse-arm
    compaction and the block-skip bitmap (aliased layouts reuse it
    instead of re-gathering `active`)."""
    flags = jnp.take(frontier_mask(active), layout.src, axis=0)
    if layout.valid_mask is not None:
        flags = flags & layout.valid_mask
    return flags


@obs.scope(obs.PLANE_GATHER)
def emit_messages(program: VCProgram, layout: EdgeLayout, vprops, active,
                  src_active=None) -> Tuple[RecordBatch, jnp.ndarray]:
    """Phase 3 on the layout's own edge order: gather src props, vmap the
    user's emit, veto inactive sources and padded slots. `src_active` is
    the hoisted per-edge frontier mask (see :func:`edge_active`); it is
    derived here when the caller has not already computed it.

    Returns (msgs, valid) in LAYOUT order (not necessarily combine order).
    """
    if src_active is None:
        src_active = edge_active(layout, active)
    src_prop = records.tree_gather(vprops, layout.src)
    is_emit, msgs = jax.vmap(program.emit_message)(
        layout.emit_src_ids, layout.emit_dst_ids, src_prop, layout.eprops)
    valid = is_emit.astype(bool) & src_active
    return msgs, valid


@obs.scope(obs.PLANE_COMBINE)
def combine(program: VCProgram, layout: EdgeLayout, msgs, valid, empty,
            kernel_on: bool = False) -> Tuple[RecordBatch, jnp.ndarray]:
    """Phase 1: fold layout-ordered messages into per-vertex inboxes.

    Permutes into the combine (dst-sorted) order first when the layout is
    an emission-order view (``perm`` set), then segment-combines with the
    precomputed metadata of the combine-ordered alias.
    """
    cv = layout.combine_view
    if layout.perm is not None:
        if cv is None:
            raise ValueError(
                "EdgeLayout with perm set needs its combine-ordered alias "
                "in .canonical (see graph_device.EdgeLayout)")
        msgs = records.tree_gather(msgs, layout.perm)
        valid = jnp.take(valid, layout.perm, axis=0)
    meta = cv.seg_meta
    if meta is None:
        meta = make_segment_meta(cv.dst, cv.num_segments,
                                 valid=cv.valid_mask)
    return segment_combine(program, msgs, cv.dst, valid, cv.num_segments,
                           empty, kernel_on, meta=meta)


def _program_monoids(program: VCProgram):
    """program.monoid as the kernel predicate consumes it: one name, a
    per-leaf tuple (mixed records), or None (general path only)."""
    m = program.monoid
    if isinstance(m, str):
        return m if m in _NAMED else None
    return leaf_monoids(program, program.empty_message())


def _has_vector_leaves(program: VCProgram, cv: EdgeLayout, vprops) -> bool:
    """Any [V, D] vertex-property or [E, D] message leaf? (Those are
    packed-variant-only: a vector leaf spans D slab columns.)"""
    from repro.kernels.fused_gather_emit import _emit_schema
    if any(jnp.ndim(a) > 1 for a in jax.tree.leaves(vprops)):
        return True
    emit_sds = _emit_schema(program.emit_message, cv.num_edges, vprops,
                            cv.eprops)
    return any(len(s.shape) > 1 for s in jax.tree.leaves(emit_sds[1]))


def fused_applicable(program: VCProgram, layout: EdgeLayout, vprops,
                     multileaf: str = "auto", has_vec: bool | None = None
                     ) -> bool:
    """Static check: can this (program, layout) pair run as ONE fused
    kernel pass? Needs named monoids (one for the record or one per
    leaf), [N]-or-[N, D] record leaves (vector leaves only when the
    packed variant will run), and a combine-ordered view of the edge set
    (the layout itself or its canonical alias). Delegates to the kernel's
    own `fusable` predicate so the gate and the kernel's schema
    validation can never drift apart. `has_vec` lets the dispatcher pass
    a precomputed :func:`_has_vector_leaves` (it needs an emit-schema
    eval_shape) instead of re-deriving it here."""
    cv = layout.combine_view
    if cv is None:
        return False
    mono = _program_monoids(program)
    if mono is None:
        return False
    if has_vec is None:
        has_vec = _has_vector_leaves(program, cv, vprops)
    n_leaves = len(mono) if isinstance(mono, tuple) else 1
    will_pack = multileaf != "perleaf" and (
        n_leaves > 1 or multileaf == "packed" or has_vec)
    if has_vec and not will_pack:
        return False  # per-leaf scalar launches cannot carry vector leaves
    from repro.kernels.fused_gather_emit import fusable
    return fusable(program.emit_message, mono, vprops, cv.eprops,
                   cv.num_edges, cv.num_segments, allow_vector=will_pack)


def _per_leaf_fused(program: VCProgram, layout: EdgeLayout, vprops, active,
                    monoids, prefetch, block_skip):
    """k scalar-kernel launches, one message leaf each — the baseline the
    packed multi-leaf pass collapses into one launch (kept for the
    multileaf="perleaf" bench/verification path)."""
    from repro.kernels import ops as kops

    empty_rec = program.empty_message()
    mdef = jax.tree.structure(empty_rec)
    out_leaves, has_msg = [], None
    for j, monoid in enumerate(monoids):
        def emit_one(s, d, sp, ep, _j=j):
            is_emit, msg = program.emit_message(s, d, sp, ep)
            return is_emit, {"leaf": jax.tree.leaves(msg)[_j]}

        inbox_j, hm_j = kops.gather_emit_combine(
            emit_one, monoid, layout.src, layout.dst, vprops,
            layout.eprops, active, layout.num_segments,
            valid=layout.valid_mask,
            src_ids=layout.src_ids, dst_ids=layout.dst_ids,
            prefetch=prefetch, block_skip=block_skip)
        out_leaves.append(inbox_j["leaf"])
        has_msg = hm_j if has_msg is None else has_msg
    return jax.tree.unflatten(mdef, out_leaves), has_msg


@obs.scope(obs.PLANE_KERNEL)
def _fused_emit_combine(program: VCProgram, layout: EdgeLayout, vprops,
                        active, empty: Record, multileaf: str = "auto",
                        block_skip: bool = False,
                        has_vec: bool | None = None,
                        use_prefetch: bool = True):
    """Phases 3+1 as ONE streamed pass: gather src props, evaluate emit,
    and fold into per-vertex inboxes inside a single Pallas kernel — no
    E-sized message materialization in HBM. `layout` must be the
    combine-ordered view.

    Records with several leaves (or a per-leaf monoid table, or vector
    [., D] leaves) run the PACKED variant by default: dtype-grouped
    vprops slabs and (dtype, monoid)-grouped message panels make the
    whole record ONE launch. multileaf="perleaf" forces the k-launch
    baseline instead. block_skip=True is the frontier-sparse shape: the
    kernels prefetch a per-edge-block any_active bitmap and early-out
    whole blocks (bit-identical; works for the resident, scalar-prefetch
    and packed variants alike).
    """
    from repro.kernels import ops as kops
    from repro.kernels.fused_gather_emit import make_pack_spec
    from .graph_device import PREFETCH_BLOCK_E

    prefetch = None
    if (use_prefetch and layout.prefetch_window
            and layout.prefetch_blocks is not None):
        prefetch = (layout.prefetch_blocks, layout.prefetch_window,
                    PREFETCH_BLOCK_E)

    active = frontier_mask(active)
    monoids = leaf_monoids(program, empty)
    if has_vec is None:
        has_vec = _has_vector_leaves(program, layout, vprops)
    if multileaf == "perleaf":
        inbox, has_msg = _per_leaf_fused(program, layout, vprops, active,
                                         monoids, prefetch, block_skip)
    elif len(monoids) > 1 or multileaf == "packed" or has_vec:
        pack = layout.pack
        if pack is None:
            pack = make_pack_spec(program.emit_message, monoids, vprops,
                                  layout.eprops, layout.num_edges)
        inbox, has_msg = kops.gather_emit_combine_packed(
            program.emit_message, monoids, layout.src, layout.dst,
            vprops, layout.eprops, active, layout.num_segments,
            valid=layout.valid_mask,
            src_ids=layout.src_ids, dst_ids=layout.dst_ids,
            pack=pack, block_skip=block_skip)
    else:
        inbox, has_msg = kops.gather_emit_combine(
            program.emit_message, monoids[0], layout.src, layout.dst,
            vprops, layout.eprops, active, layout.num_segments,
            valid=layout.valid_mask,
            src_ids=layout.src_ids, dst_ids=layout.dst_ids,
            prefetch=prefetch, block_skip=block_skip)
    # normalize no-message vertices to the user's exact empty record
    empty_v = records.tree_tile(empty, layout.num_segments)
    return records.tree_where(has_msg, inbox, empty_v), has_msg


# ---------------------------------------------------------------------------
# THE entry point
# ---------------------------------------------------------------------------

def emit_and_combine(program: VCProgram, layout: EdgeLayout, vprops, active,
                     empty: Record, *, kernel_on: bool = False,
                     mode: str = "auto", multileaf: str = "auto",
                     frontier: str = "dense", prefetch: str = "auto"
                     ) -> Tuple[RecordBatch, jnp.ndarray]:
    """Run the whole message plane (Phase 3 + Phase 1) for one iteration.

    `active` is the frontier — a :class:`~repro.core.vcprog.Frontier` or
    a bare [num_vertices] bool mask.

    Dispatch (static — every branch resolves at trace time):
      mode="auto"     fuse into one kernel pass when `kernel_on` and the
                      (program, layout) pair qualifies; otherwise the
                      three-pass emit→[permute]→combine dataflow, with
                      the blocked Pallas segment kernel when `kernel_on`.
      mode="fused"    require the fused pass (raises if not applicable).
      mode="unfused"  never fuse (still honors `kernel_on` for the
                      blocked segment-combine kernel).

    multileaf ("auto"|"packed"|"perleaf") picks the fused pass shape for
    multi-leaf records: "auto" packs k leaves into ONE launch (per-dtype
    vprops slabs, per-(dtype, monoid) message panels), "perleaf" forces
    the k-launch baseline, "packed" forces packing even for one leaf.

    frontier ("auto"|"dense"|"sparse") is the sparse fast path — the
    push/pull density idea promoted into the plane, so every engine (and
    every distributed bucket) inherits it:
      "dense"   every pass covers all E edge slots (historical behavior).
      "auto"    fused passes consult a per-edge-block any_active bitmap
                and skip dead blocks; unfused named-monoid passes compact
                the active edge set into a `workset_capacity(E)`-slot
                workset under `lax.cond` (dense fallback above the
                crossover). Bit-identical to dense by construction.
      "sparse"  force the sparse shape of the dispatched path: block-skip
                when the fused kernel runs, otherwise the compaction arm
                at full (E-slot) capacity — always exact (pin the
                compaction arm with kernel_on=False / mode="unfused").
    General (merge_message-only) monoids always run dense: their combine
    is the flagged scan, whose cost is structural, and re-deriving its
    tree shape per superstep would cost more than it saves.

    prefetch ("auto"|"on"|"off") gates the scalar-prefetch fused variant:
    "off" ignores the layout's window metadata (every fused pass runs
    vprops-resident — the verification/bench baseline), the other modes
    use it whenever the layout carries it. Bit-identical either way.

    Returns (inbox [num_segments] record batch, has_msg [num_segments]).
    """
    if mode not in _MODES:
        raise knob_error("mode", mode, _MODES)
    if multileaf not in _MULTILEAF:
        raise knob_error("multileaf", multileaf, _MULTILEAF)
    frontier = resolve_frontier_mode(frontier)
    prefetch = resolve_prefetch_mode(prefetch)
    want_fused = mode == "fused" or (mode == "auto" and kernel_on)
    if want_fused:
        cv0 = layout.combine_view
        # one emit-schema eval_shape per dispatch, shared by the gate and
        # the fused pass
        has_vec = (_has_vector_leaves(program, cv0, vprops)
                   if cv0 is not None else False)
        if fused_applicable(program, layout, vprops, multileaf,
                            has_vec=has_vec):
            return _fused_emit_combine(program, cv0, vprops, active, empty,
                                       multileaf,
                                       block_skip=frontier != "dense",
                                       has_vec=has_vec,
                                       use_prefetch=prefetch != "off")
    if mode == "fused":
        raise ValueError(
            "mode='fused' but the program/layout pair is not fusable "
            "(needs named monoids and scalar record leaves)")

    # unfused dataflow: the per-edge frontier mask is computed ONCE (in
    # layout order) and shared by the emit veto, the permuted combine
    # mask and the sparse arm
    src_active = edge_active(layout, active)
    monoids = leaf_monoids(program, empty)
    cv = layout.combine_view
    if (frontier != "dense" and monoids is not None
            and cv.num_edges > 0 and cv.num_segments > 0):
        # frontier flags in combine order (one permute of the hoisted mask)
        with obs.scope(obs.PLANE_GATHER):
            act_e = (src_active if layout.perm is None
                     else jnp.take(src_active, layout.perm, axis=0))
        cap = workset_capacity(
            cv.num_edges, 1.0 if frontier == "sparse" else SPARSE_CAP_FRAC)
        sparse_fn = lambda _: _sparse_emit_combine(
            program, cv, vprops, empty, kernel_on, monoids, act_e, cap)
        if frontier == "sparse" or cap >= cv.num_edges:
            return sparse_fn(None)

        def dense_fn(_):
            msgs, valid = emit_messages(program, layout, vprops, active,
                                        src_active=src_active)
            return combine(program, layout, msgs, valid, empty, kernel_on)

        with obs.scope(obs.PLANE_OPERANDS):
            n_act = jnp.sum(act_e.astype(jnp.int32))
        return jax.lax.cond(n_act <= cap, sparse_fn, dense_fn, operand=None)

    msgs, valid = emit_messages(program, layout, vprops, active,
                                src_active=src_active)
    return combine(program, layout, msgs, valid, empty, kernel_on)
