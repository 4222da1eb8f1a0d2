"""Distributed VCProg engine: shard_map over a TPU mesh.

The graph is partitioned into P contiguous vertex ranges (Gemini-style
chunking, core/graph.py). Each device owns one range: its vertex
properties, its in-edges (bucketed by the *owner part of their src*), and
its slice of the Algorithm-1 state. One iteration is the dense-pull
dataflow (emissions evaluated on in-edges), with two communication
schedules for reading remote source properties:

  allgather  baseline: `lax.all_gather` the full vertex-property array,
             then scan the P src buckets locally. Simple; memory
             O(V · prop_bytes) per device.
  ring       pipelined: vertex-property slices rotate around the ring via
             `lax.ppermute` while the previous bucket computes — the
             compute/communication overlap the paper lists as future work
             (§VI "organize RPC invocations in a pipeline manner").
             Memory O(V/P), wire bytes identical, latency hidden.

Every bucket is an :class:`~repro.core.graph_device.EdgeLayout` (local
gather/combine indices, global emit ids, valid-slot mask, precomputed
per-bucket SegmentMeta), so each bucket's emit→combine goes through
`core/message_plane.py` exactly like the single-device engines — with
`kernel_on` the per-bucket plane runs as ONE fused Pallas pass, and with
`prefetch` on, `build_bucket_prefetch` attaches per-(part, bucket)
scalar-prefetch window tables so that pass DMAs two `window`-row src
slabs per edge block instead of holding the remote part's vprops
VMEM-resident (per-bucket resident fallback where the window would be
part-sized; see docs/perf.md "Distributed prefetch").

Semantics are identical to the single-device engines (tests assert
equality); the user program is the same VCProgram object — cross-platform
execution in the paper's sense, where the "platform" here is the mesh.
"""
from __future__ import annotations

import warnings
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import message_plane, records, vcprog
from ..graph import PropertyGraph, partition_graph
from ..graph_device import bucket_layout, workset_capacity
from repro.distributed import faults as faults_mod, wire

AXIS = "graph"


# ---------------------------------------------------------------------------
# Delta exchange: ship (indices, values) of frontier vertices only
# ---------------------------------------------------------------------------
# Emissions are vetoed for inactive sources, so a remote part only ever
# *reads* the properties of active vertices — the communication schedules
# can ship the compacted (indices, values) of the frontier and scatter
# them into a zero slab on the receiving side, bit-identically (the zeros
# are never selected). K is a static per-part capacity with a dense
# fallback above it ("auto"), or the full v_pp ("sparse", always exact).

def _compact_active(vprops, active, K: int, v_pp: int):
    """Local frontier as a wire payload: (idx [K] int32 with sentinel
    v_pp pads, vals [K, ...] gathered rows, count)."""
    idx, cnt = message_plane.compact_indices(active, K)
    vals = records.tree_gather(vprops, jnp.minimum(idx, max(v_pp - 1, 0)))
    return idx, vals, cnt


def _scatter_part(vprops_tmpl, v_pp: int, idx, vals):
    """Reconstruct a remote part's (props, active) from its delta payload.
    Rows not shipped stay zero AND inactive — never read by any combine
    path (the active veto masks their emissions before use)."""
    base = jax.tree.map(lambda a: jnp.zeros((v_pp,) + a.shape[1:], a.dtype),
                        vprops_tmpl)
    vp = jax.tree.map(lambda b, v: b.at[idx].set(v, mode="drop"), base, vals)
    act = jnp.zeros((v_pp,), bool).at[idx].set(True, mode="drop")
    return vp, act


# ---------------------------------------------------------------------------
# Host-side: partition -> device arrays (leading dim P, sharded over AXIS)
# ---------------------------------------------------------------------------

def _bucket_segment_meta(edge_dst_local, edge_mask, v_pp: int):
    """Static per-bucket segment structure ([P, B, v_pp] last valid slot +
    has-edge mask) — computed once host-side so no iteration re-derives it
    with segment reductions inside the compiled loop."""
    Pn, B, L = edge_dst_local.shape
    last = np.full((Pn * B, max(v_pp, 1)), -1, np.int64)
    rows, slots = np.nonzero(edge_mask.reshape(Pn * B, L))
    np.maximum.at(last, (rows, edge_dst_local.reshape(Pn * B, L)[rows, slots]),
                  slots)
    has = last >= 0
    last = np.clip(last, 0, max(L - 1, 0))
    shape = (Pn, B, max(v_pp, 1))
    return last.reshape(shape).astype(np.int32), has.reshape(shape)


def build_sharded_graph(g: PropertyGraph, num_parts: int,
                        reorder: str = "none") -> Dict[str, Any]:
    """Partition + bucket a PropertyGraph for `num_parts` devices.

    `reorder` relabels the vertex space host-side BEFORE partitioning
    (core/reorder.py) — buckets, their segment metadata, and the
    contiguous part ranges are all built from the reordered graph. The
    ORIGINAL endpoint ids ride `edge_{src,dst}_uid` (what emit_message
    sees) and `vertex_ids` (what init_vertex sees); `vertex_perm` /
    `inv_perm` let the caller un-permute results.

    Beyond the global strategies, `reorder="rcm:part"` is the
    PARTITION-AWARE variant: RCM applied within each contiguous part
    range (block-diagonal permutation, part ownership unchanged), so
    per-bucket src runs are banded in each part's LOCAL id space — the
    quantity the per-bucket scalar-prefetch windows actually depend on
    (see `bucket_prefetch_windows`).
    """
    perm = inv = None
    if reorder == "rcm:part":
        from ..reorder import apply_permutation, partitioned_rcm_permutation
        p = partitioned_rcm_permutation(g.src, g.dst, g.num_vertices,
                                        num_parts)
        g, perm, inv = apply_permutation(g, p)
    elif reorder not in (None, "none"):
        from ..reorder import apply_reorder
        g, perm, inv = apply_reorder(g, reorder)

    part = partition_graph(g, num_parts)
    Pn, v_pp = part.num_parts, part.v_per_part
    V_pad = Pn * v_pp

    # pad vertex-level arrays to V_pad and reshape to [P, v_pp]
    def pad_v(a, fill=0):
        a = np.asarray(a)
        out = np.full((V_pad,) + a.shape[1:], fill, a.dtype)
        out[:g.num_vertices] = a
        return out.reshape((Pn, v_pp) + a.shape[1:])

    eprops = {k: np.asarray(v)[part.edge_prop_idx]
              for k, v in g.edge_props.items()}
    src_local = part.edge_src % v_pp if v_pp else part.edge_src
    # padded slots carry the sentinel dst == v_pp: each bucket's dst run
    # stays ascending THROUGH its padding, which both the segment ops
    # (indices_are_sorted) and the fused kernel's block-overlap skip rely
    # on; out-of-range ids are dropped by every combine path
    dst_local = np.where(part.edge_mask, part.edge_dst_local,
                         np.int64(v_pp))
    bucket_last, bucket_has = _bucket_segment_meta(dst_local,
                                                   part.edge_mask, v_pp)

    dst_global = (dst_local + part.v_start[:, None, None]).astype(np.int32)
    # ORIGINAL (user-visible) endpoint ids for emit_message. perm_pad maps
    # the padded id range identically (sentinel dst_global can reach V_pad)
    if perm is not None:
        perm_pad = np.arange(V_pad + 1, dtype=np.int64)
        perm_pad[:g.num_vertices] = perm
        src_uid = perm_pad[part.edge_src].astype(np.int32)
        dst_uid = perm_pad[dst_global].astype(np.int32)
        vertex_ids = perm_pad[:V_pad].astype(np.int32)
    else:
        src_uid = part.edge_src.astype(np.int32)
        dst_uid = dst_global
        vertex_ids = np.arange(V_pad, dtype=np.int32)

    # The [P(dst part), B(src-part bucket), L] layout transposes into the
    # push engine's [P(src part), B(dst-part bucket), L] view for free —
    # within-bucket dst order is preserved (segment ops stay valid).
    return {
        "num_parts": Pn,
        "v_per_part": v_pp,
        "num_vertices": g.num_vertices,
        "vertex_perm": perm,
        "inv_perm": inv,
        "vertex_ids": vertex_ids.reshape(Pn, v_pp),
        # [P, B=P, L] edge structure: dst part -> (src-owner bucket, slot)
        "edge_src_local": src_local.astype(np.int32),
        "edge_dst_local": dst_local.astype(np.int32),
        "edge_src_global": part.edge_src.astype(np.int32),
        "edge_dst_global": dst_global,
        "edge_src_uid": src_uid,
        "edge_dst_uid": dst_uid,
        "edge_mask": part.edge_mask,
        # [P, B, v_pp] static segment structure of each bucket's dst runs
        "bucket_last_edge": bucket_last,
        "bucket_has_edge": bucket_has,
        "eprops": eprops,          # [P, B, L, ...]
        "out_degree": pad_v(g.out_degree),
        "vprops_in": {k: pad_v(v) for k, v in g.vertex_props.items()},
        "vertex_valid": pad_v(np.ones(g.num_vertices, bool)),
    }


def bucket_prefetch_windows(sg: Dict[str, Any]) -> np.ndarray:
    """Host-side locality metric of a sharded graph: the achieved
    scalar-prefetch window of every (dst-part, src-owner-bucket)'s local
    src run ([P, B] int64; 0 = resident fallback, i.e. the slab pair
    would cover at least the whole part). The partition-aware reorderer
    ("rcm:part") exists to shrink these. Computed on the PADDED slot
    arrays with the valid mask — the exact layout the per-bucket
    prefetch kernels stream, so sentinel dst pads can never widen a
    reported window."""
    from ..graph_device import compute_prefetch_windows

    v_pp = sg["v_per_part"]
    srcl, mask = sg["edge_src_local"], sg["edge_mask"]
    Pn, B = srcl.shape[0], srcl.shape[1]
    out = np.zeros((Pn, B), np.int64)
    for dp in range(Pn):
        for b in range(B):
            _, out[dp, b] = compute_prefetch_windows(srcl[dp, b], v_pp,
                                                     valid=mask[dp, b])
    return out


def build_bucket_prefetch(srcl: np.ndarray, mask: np.ndarray, v_pp: int,
                          shared: bool = False):
    """Per-(dst-part, src-owner-bucket) scalar-prefetch window tables.

    Returns ``(blocks [P, B, n_blocks] int32, windows tuple[int] of len
    B)``. shard_map traces ONE program for every device, so the STATIC
    slab width of bucket b must be shared by all dst-parts: windows[b]
    is the power-of-two covering the widest block span of bucket b on
    ANY part (the per-part variation lives in the traced block table).
    ``shared=True`` collapses further to one window for every bucket —
    the ring schedule visits buckets with a traced index, so even the
    per-bucket static split is unavailable there.

    windows[b] == 0 is bucket b's RESIDENT fallback: some part's bucket
    b needs a slab pair at least as large as the part's vertex range
    (or, under ``shared``, any bucket does). Empty buckets never force a
    fallback — they carry no span requirement and read whatever window
    their bucket column settled on (every slot is invalid, so the slabs
    are DMA'd and ignored).
    """
    from ..graph_device import (PREFETCH_BLOCK_E, min_prefetch_window,
                                prefetch_block_bounds)

    Pn, B, L = srcl.shape
    nb = max(-(-L // PREFETCH_BLOCK_E), 1)
    # ONE bounds scan per (part, bucket); windows and block tables both
    # derive from it (and bucket_prefetch_windows reports the same scan)
    bounds = [[prefetch_block_bounds(srcl[dp, b], valid=mask[dp, b])
               for b in range(B)] for dp in range(Pn)]
    windows = []
    for b in range(B):
        w_b, resident = 0, False
        for dp in range(Pn):
            bd = bounds[dp][b]
            if bd is None:  # empty bucket: no span requirement
                continue
            w = min_prefetch_window(int((bd[1] - bd[0]).max()) + 1, v_pp)
            if w == 0:
                resident = True  # real edges, span too wide
            w_b = max(w_b, w)
        windows.append(0 if resident else w_b)
    if shared:
        resident = any(w == 0 and mask[:, b].any()
                       for b, w in enumerate(windows))
        w_all = 0 if resident else max(windows, default=0)
        windows = [w_all] * B
    blocks = np.zeros((Pn, B, nb), np.int32)
    for b in range(B):
        if windows[b] == 0:
            continue
        for dp in range(Pn):
            bd = bounds[dp][b]
            if bd is not None:  # empty buckets keep a zero table
                lo = bd[0]
                blocks[dp, b, :lo.shape[0]] = lo // windows[b]
    return blocks, tuple(int(w) for w in windows)


# ---------------------------------------------------------------------------
# Device-side iteration (runs inside shard_map; all args are LOCAL slices)
# ---------------------------------------------------------------------------

def _merge_partial(program, inbox, has_msg, part, ph):
    """Monoid-merge a partial inbox (part, ph) into the running (inbox,
    has_msg) — the shared fold body of the bucket loop and the push
    schedule's all_to_all partial exchange."""
    merged = jax.vmap(program.merge_message)(inbox, part)
    inbox = records.tree_where(ph & has_msg, merged,
                               records.tree_where(ph, part, inbox))
    return inbox, has_msg | ph


def _fold_partials(program):
    """lax.scan body folding [P, v_pp] partial inboxes with the monoid."""

    def fold(carry, x):
        inbox, has_msg = carry
        part, ph = x
        return _merge_partial(program, inbox, has_msg, part, ph), None

    return fold


def make_distributed_step(program: vcprog.VCProgram, v_pp: int,
                          num_parts: int, schedule: str = "ring",
                          unroll_buckets: bool = False,
                          skip_buckets: bool = False,
                          kernel_on: bool = False,
                          frontier: str = "dense",
                          prefetch_windows=None,
                          exchange: str = "exact",
                          overlap: bool = True,
                          guards: bool = False,
                          faults=()):
    """One Algorithm-1 iteration as a shard_map-able local function.

    Local args: vprops/active/inbox/has_msg [v_pp,...] slices, edge arrays
    [B=P, L, ...] for this device's dst range. Returns updated local state
    + global num_active. With ``exchange="q8ef"`` and a sparse frontier
    the step additionally threads the dense error-feedback state: pass it
    as the trailing ``wire_err`` argument and it is returned (updated)
    before the count — the legacy 6-arg/5-tuple shape is unchanged for
    every other configuration.

    exchange ("exact"|"fp16"|"q8ef", repro.distributed.wire) is the wire
    codec applied to the delta-exchange payloads of all three schedules:
    bit-packed u16/u24 local indices plus fp16 or int8-error-feedback
    float leaves. "exact" (default) ships the PR-4 payloads verbatim and
    is bit-identical. The codec only touches the SPARSE exchange — the
    dense fallback always ships full-width rows.

    overlap (default True) software-pipelines every schedule so the
    exchange hides behind the bucket plane passes: the ring issues hop
    h+1's ppermute BEFORE hop h's plane consumes its payload
    (double-buffered carry), the allgather materializes bucket b+1's
    slab (row select + codec decode) before bucket b's plane pass, and
    the push decomposes its all_to_all into per-offset ppermutes issued
    as soon as each bucket's partial is computed (received partials are
    buffered and folded in canonical part order, so the monoid fold is
    bit-identical to the all_to_all path). overlap=False keeps the
    sequential compute-then-exchange shape; results are bit-identical
    either way.

    frontier ("dense"|"auto"|"sparse") switches the schedules to delta
    exchange — allgather/ring rotate only the (indices, values) of active
    boundary vertices, push all_to_alls only the (indices, values) of
    non-empty partial-inbox rows — and threads the same mode into every
    bucket's message plane. "auto" falls back to the dense exchange when
    any part's frontier exceeds the static capacity K (decided with ONE
    pmax so every device takes the same branch); "sparse" uses the
    always-exact capacity (>= v_pp). All modes are bit-identical.

    prefetch_windows (len-B tuple of ints, or None) are the per-bucket
    STATIC scalar-prefetch slab widths from `build_bucket_prefetch`; the
    traced per-(part, bucket) block tables ride
    ``edges["bucket_pf_blocks"]``. With windows attached, every bucket's
    plane pass runs the scalar-prefetch fused kernel (and its block-skip
    / packed shapes) — DMA'ing two `window`-row src slabs per edge block
    instead of keeping the remote part's vprops VMEM-resident — with a
    per-bucket resident fallback where windows[b] == 0. The allgather
    and push schedules unroll their bucket loop so each bucket's static
    window specializes its own kernel; the ring schedule visits buckets
    with a traced index and therefore requires ONE shared window
    (build with shared=True).

    guards=True arms the integrity guards (docs/robustness.md): every
    sparse delta payload carries a `wire.attach_checksum` crc (computed
    by the sender after encoding, verified by every receiver after the
    collective — all three schedules), and each superstep's vertex-state
    transition runs the NaN/Inf + monotonicity watchdogs
    (`faults_mod.guard_alarms`). The step then returns an extra psum'd
    [NUM_ALARMS] alarm vector before the count, and local_step accepts a
    trailing `fault_on` scalar gating any `faults=` specs (seeded
    deterministic injection, baked into the trace so arming costs no
    recompile). With guards off and no faults the wire format and
    return shape are unchanged.
    """
    frontier = message_plane.resolve_frontier_mode(frontier)
    codec = wire.get_codec(wire.resolve_exchange_mode(exchange))
    overlap = bool(overlap)
    # error feedback needs a loop-carried residual state; it exists only
    # when the codec asks for it AND a sparse arm can run
    carry_err = codec.error_feedback and frontier != "dense"
    K = (workset_capacity(v_pp, 1.0) if frontier == "sparse"
         else workset_capacity(v_pp))
    guards = bool(guards)
    faults = faults_mod.resolve_faults(faults)
    wf = faults_mod.wire_faults(faults)
    vf = faults_mod.vprop_faults(faults)
    if prefetch_windows is not None:
        prefetch_windows = tuple(int(w) for w in prefetch_windows)
        if len(prefetch_windows) != num_parts:
            raise ValueError(
                f"prefetch_windows has {len(prefetch_windows)} entries "
                f"for {num_parts} buckets")
        if schedule == "ring" and len(set(prefetch_windows)) > 1:
            raise ValueError(
                "the ring schedule indexes buckets with a traced id and "
                "needs ONE shared prefetch window — build the tables "
                "with build_bucket_prefetch(..., shared=True)")

    def local_step(it, vprops, active, inbox, has_msg, edges,
                   wire_err=None, fault_on=None):
        empty = jax.tree.map(jnp.asarray, program.empty_message())
        my = jax.lax.axis_index(AXIS)
        werr = wire_err if (carry_err and wire_err is not None) else {}
        f_on = jnp.int32(0) if fault_on is None else fault_on

        def guard_payload(payload):
            """Sender side of the wire guard: attach the crc to the
            encoded payload, THEN apply any injected wire faults — the
            receiver-side verify sees what a flaky link would deliver."""
            if guards:
                payload = wire.attach_checksum(payload)
            if wf:
                payload = faults_mod.corrupt_wire(payload, it, f_on, wf,
                                                  my=my)
            return payload

        def count_bad(stacked):
            """Receiver side: verify every row of a [P]-stacked payload
            tree after the collective."""
            ok = jax.vmap(wire.checksum_ok)(stacked)
            return jnp.sum((~ok).astype(jnp.int32))

        # Phase 2: vertex_compute on the local slice. The local frontier
        # is first-class from here on: its popcount is computed once and
        # consumed by the delta-exchange crossover conds AND the global
        # termination count below.
        process = active | has_msg
        prev_vprops = vprops
        vprops, active = vcprog.compute_phase(program, vprops, inbox,
                                              process, it)
        if vf:
            vprops = faults_mod.poison_vprops(vprops, program, it, f_on,
                                              vf, my=my)
        alarms0 = (faults_mod.guard_alarms(program, prev_vprops, vprops)
                   if guards else None)
        crc_bad = jnp.int32(0)
        # batched programs: `active` is the OR across lanes already; the
        # per-lane masks ride the frontier so the delta-exchange payloads
        # (which gather whole [Q]-lane rows of the union frontier) stay
        # inspectable per lane
        lanes = (vprops["_lane_act"] > 0
                 if isinstance(program, vcprog.BatchedProgram) else None)
        front = vcprog.make_frontier(active, lane_mask=lanes)

        # Phases 3+1: emit along in-edges, reading remote src props
        inbox0 = records.tree_tile(empty, v_pp)
        has0 = jnp.zeros((v_pp,), bool)

        def bucket_at(b, pf_window: int = 0):
            if "bucket_last_edge" in edges:  # precomputed (host-side)
                meta = vcprog.SegmentMeta(
                    last_edge=edges["bucket_last_edge"][b],
                    has_edge=edges["bucket_has_edge"][b])
            else:
                # compat fallback for hand-built edges dicts (every
                # in-repo producer — build_sharded_graph and the dry-run
                # templates — precomputes the metadata; this mask-aware
                # in-trace derivation keeps external local_step callers
                # working, at the old per-iteration cost)
                meta = vcprog.make_segment_meta(
                    edges["edge_dst_local"][b], v_pp,
                    valid=edges["edge_mask"][b])
            # emit ids: the ORIGINAL vertex ids when the graph was
            # reordered ("_uid"); the new-id globals otherwise (compat
            # fallback for hand-built edges dicts)
            src_ids = edges.get("edge_src_uid", edges["edge_src_global"])
            dst_ids = edges.get("edge_dst_uid", edges["edge_dst_global"])
            pf_blocks = (edges["bucket_pf_blocks"][b]
                         if pf_window and "bucket_pf_blocks" in edges
                         else None)
            return bucket_layout(
                src_local=edges["edge_src_local"][b],
                src_global=src_ids[b],
                dst_local=edges["edge_dst_local"][b],
                dst_global=dst_ids[b],
                eprops=jax.tree.map(lambda a: a[b], edges["eprops"]),
                mask=edges["edge_mask"][b],
                seg_meta=meta, v_per_part=v_pp,
                prefetch_blocks=pf_blocks,
                prefetch_window=pf_window if pf_blocks is not None else 0)

        def bucket_plane(bk, src_props_part, active_part):
            """One bucket's whole message plane (fused when kernel_on;
            frontier-sparse dispatch inherited from the session knob)."""
            return message_plane.emit_and_combine(
                program, bk, src_props_part, active_part, empty,
                kernel_on=kernel_on, frontier=frontier)

        if skip_buckets:
            # cost-calibration variant: everything EXCEPT the bucket loop
            # (launch/graph_job.py solves cost = outside + P·body from the
            # pair of lowers, because a lax.scan body is cost-counted once).
            # The allgather schedule's gather is per-ITERATION, not
            # per-bucket, so keep it alive here (prevents DCE) to land in
            # the `outside` term.
            inbox, has_msg = inbox0, has0
            if schedule == "allgather":
                all_vp = jax.lax.all_gather(vprops, AXIS)
                all_act = jax.lax.all_gather(active, AXIS)
                alive = jnp.sum(all_act) < 0
                for leaf in jax.tree.leaves(all_vp):
                    alive |= jnp.isnan(jnp.sum(leaf.astype(jnp.float32)))
                has_msg = has_msg | alive
            elif schedule == "push":
                # keep the per-iteration exchange+fold in the outside term;
                # values must be data-DEPENDENT or XLA constant-folds the
                # all_to_all away and the calibration subtraction breaks
                tau = jnp.sum(active.astype(jnp.int32)) * 0
                partials = records.tree_tile(empty, num_parts * v_pp)
                partials = jax.tree.map(
                    lambda a: (a + tau.astype(a.dtype)
                               if a.dtype != jnp.bool_
                               else a | (tau > 0)).reshape(
                        (num_parts, v_pp) + a.shape[1:]),
                    partials)
                phas = jnp.zeros((num_parts, v_pp), bool) | (tau > 0)
                ex = jax.tree.map(
                    lambda a: jax.lax.all_to_all(a, AXIS, split_axis=0,
                                                 concat_axis=0),
                    partials)
                exh = jax.lax.all_to_all(phas, AXIS, split_axis=0,
                                         concat_axis=0)
                (inbox, has_msg), _ = jax.lax.scan(
                    _fold_partials(program), (inbox0, has0), (ex, exh))
        elif schedule == "allgather":
            def ag_run(part_props):
                """Scan the P src buckets; part_props(b) yields bucket b's
                (remote props, remote active). With `overlap`, the loop
                is software-pipelined double-buffered: bucket b+1's slab
                is materialized (gather-row select + codec decode)
                BEFORE bucket b's plane pass consumes the current
                buffer, so the transfer/decode overlaps the fused
                kernel. Values are identical either way."""
                def plane(b, cur, inbox, has_msg, pf_w):
                    b_inbox, b_has = bucket_plane(bucket_at(b, pf_w), *cur)
                    return _merge_partial(program, inbox, has_msg, b_inbox,
                                          b_has)

                if unroll_buckets or prefetch_windows is not None:
                    # python loop: every bucket appears in the HLO, so the
                    # dry-run's cost_analysis counts all P buckets (a
                    # lax.scan body is counted once regardless of trips) —
                    # and each bucket's STATIC prefetch window specializes
                    # its own fused kernel (resident where windows[b]==0)
                    inbox, has_msg = inbox0, has0
                    cur = part_props(0)
                    for b in range(num_parts):
                        pf_w = (prefetch_windows[b]
                                if prefetch_windows is not None else 0)
                        nxt = (part_props(b + 1)
                               if overlap and b + 1 < num_parts else None)
                        inbox, has_msg = plane(b, cur, inbox, has_msg, pf_w)
                        if b + 1 < num_parts:
                            cur = nxt if nxt is not None else part_props(b + 1)
                    return inbox, has_msg
                if overlap:
                    def body(carry, b):
                        inbox, has_msg, cur = carry
                        nxt = part_props((b + 1) % num_parts)  # issued first
                        inbox, has_msg = plane(b, cur, inbox, has_msg, 0)
                        return (inbox, has_msg, nxt), None

                    (inbox, has_msg, _), _ = jax.lax.scan(
                        body, (inbox0, has0, part_props(0)),
                        jnp.arange(num_parts))
                    return inbox, has_msg

                def body(carry, b):
                    inbox, has_msg = carry
                    return plane(b, part_props(b), inbox, has_msg, 0), None

                return jax.lax.scan(body, (inbox0, has0),
                                    jnp.arange(num_parts))[0]

            def ag_dense(werr):
                all_vp = jax.lax.all_gather(vprops, AXIS)   # [P, v_pp, ...]
                all_act = jax.lax.all_gather(active, AXIS)
                inbox, has_msg = ag_run(lambda b: (records.tree_row(all_vp, b),
                                                   all_act[b]))
                return inbox, has_msg, werr, jnp.int32(0)

            def ag_sparse(werr):
                # delta exchange: gather only the ENCODED (indices, values)
                # of each part's frontier — wire P·codec(K·prop_bytes),
                # not V·prop_bytes
                idx, vals, _ = _compact_active(vprops, active, K, v_pp)
                payload, werr = wire.encode_delta(codec, idx, vals, v_pp,
                                                  err=werr)
                payload = guard_payload(payload)
                all_wire = jax.tree.map(
                    lambda a: jax.lax.all_gather(a, AXIS), payload)
                bad = count_bad(all_wire) if guards else jnp.int32(0)
                # decode_delta reads only idx/vals keys — the crc riding
                # `all_wire` is invisible to the reconstruct path
                inbox, has_msg = ag_run(lambda b: _scatter_part(
                    vprops, v_pp, *wire.decode_delta(
                        codec, records.tree_row(all_wire, b), vals, v_pp)))
                return inbox, has_msg, werr, bad

            if frontier == "dense":
                inbox, has_msg, werr, crc_bad = ag_dense(werr)
            elif frontier == "sparse":
                inbox, has_msg, werr, crc_bad = ag_sparse(werr)
            else:
                # one pmax so every device takes the same cond branch
                fits = jax.lax.pmax(front.count, AXIS) <= K
                inbox, has_msg, werr, crc_bad = jax.lax.cond(
                    fits, ag_sparse, ag_dense, werr)
        elif schedule == "ring":
            perm = [(i, (i + 1) % num_parts) for i in range(num_parts)]
            pperm = lambda t: jax.tree.map(
                lambda a: jax.lax.ppermute(a, AXIS, perm), t)

            # the hop's bucket id is data (it depends on axis_index), so
            # every bucket shares ONE static window (shared=True tables)
            ring_pf_w = (prefetch_windows[0]
                         if prefetch_windows is not None else 0)

            def ring_run(payload0, reconstruct):
                """Rotate `payload0` around the ring; reconstruct(payload)
                yields the (props, active) of the part it currently
                holds. With `overlap`, hop h+1's ppermute is issued
                BEFORE hop h's bucket plane consumes the payload
                (double-buffered carry) so the rotation hides behind the
                fused kernel; the rotated data is identical either
                way."""
                def body(carry, r):
                    inbox, has_msg, payload, bad = carry
                    nxt = pperm(payload) if overlap else None
                    b = (my - r) % num_parts    # whose props we hold now
                    if guards:
                        # every hop verifies the payload it now holds
                        # (hop 0 = the owner's own, so sender-side
                        # corruption is caught even before it travels)
                        bad = bad + (~wire.checksum_ok(payload)).astype(
                            jnp.int32)
                    vp_b, act_b = reconstruct(payload)
                    b_inbox, b_has = bucket_plane(bucket_at(b, ring_pf_w),
                                                  vp_b, act_b)
                    inbox, has_msg = _merge_partial(program, inbox, has_msg,
                                                    b_inbox, b_has)
                    # rotate to the next neighbour
                    nxt = nxt if overlap else pperm(payload)
                    return (inbox, has_msg, nxt, bad), None

                if unroll_buckets:
                    carry = (inbox0, has0, payload0, jnp.int32(0))
                    for r in range(num_parts):
                        carry, _ = body(carry, jnp.int32(r))
                    return carry[0], carry[1], carry[3]
                (inbox, has_msg, _, bad), _ = jax.lax.scan(
                    body, (inbox0, has0, payload0, jnp.int32(0)),
                    jnp.arange(num_parts))
                return inbox, has_msg, bad

            def ring_dense(werr):
                inbox, has_msg, bad = ring_run((vprops, active),
                                               lambda p: p)
                return inbox, has_msg, werr, bad

            def ring_sparse(werr):
                # rotate the ENCODED compact (indices, values) of the
                # frontier — per-hop wire codec(K·(prop_bytes + 4))
                # instead of v_pp dense rows; encoded once by the owner,
                # decoded by each receiving hop
                idx, vals, _ = _compact_active(vprops, active, K, v_pp)
                payload, werr = wire.encode_delta(codec, idx, vals, v_pp,
                                                  err=werr)
                payload = guard_payload(payload)
                inbox, has_msg, bad = ring_run(payload,
                                               lambda p: _scatter_part(
                    vprops, v_pp, *wire.decode_delta(codec, p, vals, v_pp)))
                return inbox, has_msg, werr, bad

            if frontier == "dense":
                inbox, has_msg, werr, crc_bad = ring_dense(werr)
            elif frontier == "sparse":
                inbox, has_msg, werr, crc_bad = ring_sparse(werr)
            else:
                fits = jax.lax.pmax(front.count, AXIS) <= K
                inbox, has_msg, werr, crc_bad = jax.lax.cond(
                    fits, ring_sparse, ring_dense, werr)
        elif schedule == "push":
            # §Perf (Gemini push mode): src props are LOCAL; combine
            # per-dst-part partial inboxes locally, exchange them with ONE
            # all_to_all of message-width data, then monoid-fold the P
            # partials. Wire = V·msg_bytes (vs the ring's V·prop_bytes) and
            # one collective launch instead of P permute steps.
            # edges here are the transposed (src-part major) view.
            msg_tmpl = records.tree_tile(empty, K)  # decode dtype template

            def sparse_payload(i_row, v_rows, e_row):
                """Compact one partial-inbox row to its encoded delta."""
                clip = jnp.minimum(i_row, max(v_pp - 1, 0))
                v_o = jax.tree.map(lambda a: jnp.take(a, clip, axis=0),
                                   v_rows)
                return wire.encode_delta(codec, i_row, v_o, v_pp, err=e_row)

            def sparse_fold(carry, w_row):
                """Decode + scatter one received delta, monoid-merge it."""
                i_row, v_row = wire.decode_delta(codec, w_row, msg_tmpl,
                                                 v_pp)
                part = jax.tree.map(
                    lambda e, v: e.at[i_row].set(v, mode="drop"),
                    records.tree_tile(empty, v_pp), v_row)
                ph = jnp.zeros((v_pp,), bool).at[i_row].set(
                    True, mode="drop")
                return _merge_partial(program, carry[0], carry[1], part,
                                      ph), None

            # Software-pipelined exchange (offset decomposition of the
            # all_to_all): at offset o every device computes its partial
            # for dst part (my + o) and immediately issues the one-hop
            # ppermute carrying it, so offset o+1's bucket plane runs
            # while offset o's transfer is in flight. Received partials
            # are buffered by their SENDER part id and folded in
            # canonical 0..P-1 order — bit-identical to the all_to_all
            # fold. The offset loop visits buckets with a TRACED id, so
            # it is mutually exclusive with per-bucket static prefetch
            # windows, and "auto"'s crossover cond inspects every
            # partial row (a global barrier), so only the pinned
            # frontier modes pipeline.
            if overlap and prefetch_windows is None and frontier != "auto":
                recv = []
                for o in range(num_parts):
                    b = (my + jnp.int32(o)) % num_parts
                    one, oneh = bucket_plane(bucket_at(b), vprops, active)
                    if frontier == "sparse":
                        i_o, _ = message_plane.compact_indices(oneh, K)
                        e_row = (jax.tree.map(lambda e: e[b], werr)
                                 if carry_err else None)
                        w_o, e_row = sparse_payload(i_o, one, e_row)
                        if carry_err:
                            werr = jax.tree.map(
                                lambda e, r: e.at[b].set(r), werr, e_row)
                        w_o = guard_payload(w_o)
                    else:
                        w_o = (one, oneh)
                    if o == 0:
                        recv.append((my, w_o))
                    else:
                        perm_o = [(d, (d + o) % num_parts)
                                  for d in range(num_parts)]
                        recv.append(((my - jnp.int32(o)) % num_parts,
                                     jax.tree.map(lambda a: jax.lax.ppermute(
                                         a, AXIS, perm_o), w_o)))
                buf = jax.tree.map(
                    lambda a: jnp.zeros((num_parts,) + a.shape, a.dtype),
                    recv[0][1])
                for s, w in recv:
                    buf = jax.tree.map(lambda bb, a: bb.at[s].set(a), buf, w)
                if guards and frontier == "sparse":
                    crc_bad = count_bad(buf)
                fold = (sparse_fold if frontier == "sparse"
                        else lambda c, x: (_merge_partial(
                            program, c[0], c[1], x[0], x[1]), None))
                (inbox, has_msg), _ = jax.lax.scan(fold, (inbox0, has0), buf)
            else:
                if unroll_buckets or prefetch_windows is not None:
                    # python loop (see ag_run): per-bucket STATIC prefetch
                    # windows specialize each bucket's fused kernel
                    outs = []
                    for b in range(num_parts):
                        pf_w = (prefetch_windows[b]
                                if prefetch_windows is not None else 0)
                        outs.append(bucket_plane(bucket_at(b, pf_w), vprops,
                                                 active))
                    partials = jax.tree.map(lambda *xs: jnp.stack(xs),
                                            *[o[0] for o in outs])
                    phas = jnp.stack([o[1] for o in outs])
                else:
                    def part_body(carry, b):
                        one, oneh = bucket_plane(bucket_at(b), vprops,
                                                 active)
                        return carry, (one, oneh)

                    _, (partials, phas) = jax.lax.scan(
                        part_body, (inbox0, has0), jnp.arange(num_parts))
                # partials: [P, v_pp, ...] — row b = my messages for part b
                a2a = lambda a: jax.lax.all_to_all(a, AXIS, split_axis=0,
                                                   concat_axis=0,
                                                   tiled=False)

                def push_dense(werr):
                    ex = jax.tree.map(a2a, partials)
                    exh = a2a(phas)
                    inbox, has_msg = jax.lax.scan(
                        _fold_partials(program), (inbox0, has0), (ex, exh))[0]
                    return inbox, has_msg, werr, jnp.int32(0)

                def push_sparse(werr):
                    # delta exchange of the partial inboxes: each [v_pp]
                    # row is mostly has_msg=False on a thin frontier —
                    # ship only its ENCODED (indices, values) and rebuild
                    # the dense partial on the receiving side before the
                    # monoid fold
                    idx = jax.vmap(
                        lambda m: message_plane.compact_indices(m, K)[0])(
                        phas)
                    if carry_err:
                        enc, werr = jax.vmap(sparse_payload)(idx, partials,
                                                             werr)
                    else:
                        enc, _ = jax.vmap(
                            lambda i, v: sparse_payload(i, v, None))(
                            idx, partials)
                    if guards:
                        enc = jax.vmap(wire.attach_checksum)(enc)
                    if wf:
                        enc = faults_mod.corrupt_wire(enc, it, f_on, wf,
                                                      my=my)
                    ex_wire = jax.tree.map(a2a, enc)
                    bad = count_bad(ex_wire) if guards else jnp.int32(0)
                    inbox, has_msg = jax.lax.scan(sparse_fold,
                                                  (inbox0, has0), ex_wire)[0]
                    return inbox, has_msg, werr, bad

                if frontier == "dense":
                    inbox, has_msg, werr, crc_bad = push_dense(werr)
                elif frontier == "sparse":
                    inbox, has_msg, werr, crc_bad = push_sparse(werr)
                else:
                    rows = jnp.sum(phas.astype(jnp.int32), axis=1)  # [P]
                    fits = jax.lax.pmax(jnp.max(rows), AXIS) <= K
                    inbox, has_msg, werr, crc_bad = jax.lax.cond(
                        fits, push_sparse, push_dense, werr)
        else:
            raise ValueError(schedule)

        num_active = jax.lax.psum(front.count, AXIS)
        num_msg = jax.lax.psum(jnp.sum(has_msg.astype(jnp.int32)), AXIS)
        ret = (vprops, active, inbox, has_msg)
        if carry_err:
            ret = ret + (werr,)
        if guards:
            alarms = alarms0.at[faults_mod.ALARM_CRC].add(crc_bad)
            ret = ret + (jax.lax.psum(alarms, AXIS),)
        return ret + (num_active + num_msg,)

    local_step.carries_wire_err = carry_err
    local_step.carries_alarms = guards
    return local_step


def make_distributed_runner(program: vcprog.VCProgram, v_pp: int,
                            num_parts: int, mesh: Mesh, max_iter: int,
                            schedule: str = "ring",
                            kernel_on: bool = False,
                            frontier: str = "dense",
                            prefetch_windows=None,
                            exchange: str = "exact",
                            overlap: bool = True):
    """jit(shard_map(full Algorithm-1 loop)) over mesh axis AXIS."""
    local_step = make_distributed_step(program, v_pp, num_parts, schedule,
                                       kernel_on=kernel_on,
                                       frontier=frontier,
                                       prefetch_windows=prefetch_windows,
                                       exchange=exchange, overlap=overlap)
    carry_err = local_step.carries_wire_err

    vspec = P(AXIS)
    espec = P(AXIS)

    def local_loop(vprops, active, out_degree, valid, vids, edges):
        # shard_map slices keep a size-1 leading (part) dim; drop it locally
        sq = lambda t: jax.tree.map(lambda a: a[0], t)
        vprops, active, out_degree, valid, vids, edges = map(
            sq, (vprops, active, out_degree, valid, vids, edges))
        empty = jax.tree.map(jnp.asarray, program.empty_message())
        # vids are precomputed host-side: the ORIGINAL ids under reordering
        vprops = jax.vmap(program.init_vertex)(vids, out_degree, vprops)
        inbox = records.tree_tile(empty, v_pp)
        has_msg = jnp.zeros((v_pp,), bool)
        active = active & valid
        # q8ef error-feedback residual: the allgather/ring schedules ship
        # vertex-property payloads (state over the local vprops record);
        # push ships per-dst-part partial-inbox payloads (state over
        # [P, v_pp] message records)
        werr0 = None
        if carry_err:
            werr0 = wire.init_error_state(
                jax.tree.map(lambda a: jnp.zeros(
                    (num_parts, v_pp) + jnp.shape(a), jnp.asarray(a).dtype),
                    empty)
                if schedule == "push" else vprops)

        def cond(state):
            return (state[0] <= max_iter) & (state[-1] > 0)

        def body(state):
            it, vprops, active, inbox, has_msg = state[:5]
            if carry_err:
                vprops, active, inbox, has_msg, werr, n = local_step(
                    it, vprops, active & valid, inbox, has_msg, edges,
                    state[5])
                return (it + 1, vprops, active & valid, inbox, has_msg,
                        werr, n)
            vprops, active, inbox, has_msg, n = local_step(
                it, vprops, active & valid, inbox, has_msg, edges)
            return (it + 1, vprops, active & valid, inbox, has_msg, n)

        # bootstrap count so iteration 1 always runs
        n0 = jnp.int32(1)
        state = (jnp.int32(1), vprops, active, inbox, has_msg) + (
            (werr0, n0) if carry_err else (n0,))
        state = jax.lax.while_loop(cond, body, state)
        vprops, active = state[1], state[2]
        ex = lambda t: jax.tree.map(lambda a: a[None], t)
        return (ex(vprops), ex(active), state[0][None],
                jnp.asarray(state[-1])[None])

    from repro.distributed.sharding import shard_map
    smapped = shard_map(
        local_loop, mesh=mesh,
        in_specs=(vspec, vspec, vspec, vspec, vspec, espec),
        out_specs=(vspec, vspec, vspec, vspec),
        check_vma=False)
    return jax.jit(smapped)


def make_distributed_chunk_runner(program: vcprog.VCProgram, v_pp: int,
                                  num_parts: int, mesh: Mesh,
                                  schedule: str = "ring",
                                  kernel_on: bool = False,
                                  frontier: str = "dense",
                                  prefetch_windows=None,
                                  exchange: str = "exact",
                                  overlap: bool = True,
                                  guards: bool = False,
                                  faults=()):
    """jit(shard_map(init)) / jit(shard_map(chunk)) pair for the
    resilient path: `chunk(state, valid, edges, limit, fault_on)` runs
    supersteps until `limit` (inclusive), convergence, or a tripped
    guard, over an explicit state DICT {it, vprops, active, inbox,
    has_msg, [werr], n} whose leaves keep the [P, ...] sharded layout —
    the exact carry `run_vcprog_distributed` snapshots at chunk
    boundaries. Scalars (it, n, limit, fault_on) travel as [P]
    replicated arrays so the state stays one uniformly-sharded pytree.
    The superstep sequence is identical to `make_distributed_runner`'s
    monolithic while_loop, so resume is bit-identical."""
    local_step = make_distributed_step(program, v_pp, num_parts, schedule,
                                       kernel_on=kernel_on,
                                       frontier=frontier,
                                       prefetch_windows=prefetch_windows,
                                       exchange=exchange, overlap=overlap,
                                       guards=guards, faults=faults)
    carry_err = local_step.carries_wire_err
    alarmed = local_step.carries_alarms
    vspec = P(AXIS)
    espec = P(AXIS)

    def local_init(vprops, active, out_degree, valid, vids):
        sq = lambda t: jax.tree.map(lambda a: a[0], t)
        vprops, active, out_degree, valid, vids = map(
            sq, (vprops, active, out_degree, valid, vids))
        empty = jax.tree.map(jnp.asarray, program.empty_message())
        vprops = jax.vmap(program.init_vertex)(vids, out_degree, vprops)
        state = {"it": jnp.int32(1),
                 "vprops": vprops,
                 "active": active & valid,
                 "inbox": records.tree_tile(empty, v_pp),
                 "has_msg": jnp.zeros((v_pp,), bool),
                 "n": jnp.int32(1)}  # bootstrap count (iteration 1 runs)
        if carry_err:
            state["werr"] = wire.init_error_state(
                jax.tree.map(lambda a: jnp.zeros(
                    (num_parts, v_pp) + jnp.shape(a), jnp.asarray(a).dtype),
                    empty)
                if schedule == "push" else vprops)
        ex = lambda t: jax.tree.map(lambda a: a[None], t)
        return ex(state)

    def local_chunk(state, valid, edges, limit, fault_on):
        sq = lambda t: jax.tree.map(lambda a: a[0], t)
        state, valid, edges, limit, fault_on = map(
            sq, (state, valid, edges, limit, fault_on))

        def cond(s):
            return ((s["it"] <= limit) & (s["n"] > 0)
                    & (jnp.sum(s["alarms"]) == 0))

        def body(s):
            args = (s["it"], s["vprops"], s["active"] & valid, s["inbox"],
                    s["has_msg"], edges)
            out = local_step(*args,
                             wire_err=(s["werr"] if carry_err else None),
                             fault_on=fault_on)
            vprops, active, inbox, has_msg = out[:4]
            rest = list(out[4:])
            ns = dict(s, it=s["it"] + 1, vprops=vprops,
                      active=active & valid, inbox=inbox, has_msg=has_msg,
                      n=out[-1])
            if carry_err:
                ns["werr"] = rest.pop(0)
            if alarmed:
                ns["alarms"] = s["alarms"] + rest.pop(0)
            return ns

        s0 = dict(state,
                  alarms=jnp.zeros((faults_mod.NUM_ALARMS,), jnp.int32))
        out = jax.lax.while_loop(cond, body, s0)
        alarms = out.pop("alarms")
        ex = lambda t: jax.tree.map(lambda a: a[None], t)
        return ex(out), alarms[None]

    from repro.distributed.sharding import shard_map
    init_m = shard_map(local_init, mesh=mesh,
                       in_specs=(vspec, vspec, vspec, vspec, vspec),
                       out_specs=vspec, check_vma=False)
    chunk_m = shard_map(local_chunk, mesh=mesh,
                        in_specs=(vspec, vspec, espec, vspec, vspec),
                        out_specs=(vspec, vspec), check_vma=False)
    return jax.jit(init_m), jax.jit(chunk_m)


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------

def _exchange_bytes_info(program, sg, schedule: str, frontier: str,
                         exchange: str):
    """Host-side per-superstep wire-byte model of the exchange (bytes per
    device), with the roofline conventions (launch/roofline.py): an
    all-gather counts output bytes, a permute / all_to_all counts
    operand bytes — under all of which every schedule moves P payloads
    per superstep. Derived with jax.eval_shape from the exact templates
    the schedules ship (vertex-property rows for allgather/ring,
    message-record rows for push), so the numbers track the wire
    arrays bit-for-byte. For frontier="auto" the sparse numbers are
    reported (the crossover's intended arm; its dense fallback costs
    `dense_per_superstep`). Every codec's sparse size is included so
    benches and CI gates can compare without extra runs."""
    Pn, v_pp = sg["num_parts"], sg["v_per_part"]
    K = (workset_capacity(v_pp, 1.0) if frontier == "sparse"
         else workset_capacity(v_pp))
    canon = lambda dt: jnp.zeros((), dt).dtype
    SDS = jax.ShapeDtypeStruct
    vp_in = jax.tree.map(
        lambda a: SDS((v_pp,) + np.shape(a)[2:], canon(a.dtype)),
        sg["vprops_in"])
    vp_t = jax.eval_shape(
        lambda i, o, p: jax.vmap(program.init_vertex)(i, o, p),
        SDS((v_pp,), jnp.int32),
        SDS((v_pp,), canon(sg["out_degree"].dtype)), vp_in)
    msg_t = jax.tree.map(
        lambda a: SDS((v_pp,) + jnp.shape(a), jnp.asarray(a).dtype),
        program.empty_message())
    # dense exchange: full-width rows + 1 active/has_msg flag byte each
    tmpl = msg_t if schedule == "push" else vp_t
    dense = Pn * v_pp * (wire.record_row_nbytes(tmpl) + 1)
    sparse = {c: Pn * wire.payload_nbytes(c, K, v_pp, tmpl)
              for c in wire.CODECS}
    return {
        "per_superstep": int(dense if frontier == "dense"
                             else sparse[exchange]),
        "exact_per_superstep": int(dense if frontier == "dense"
                                   else sparse["exact"]),
        "dense_per_superstep": int(dense),
        "sparse_per_superstep": {k: int(v) for k, v in sparse.items()},
        "capacity": int(K),
    }


def run_vcprog_distributed(program: vcprog.VCProgram, graph: PropertyGraph,
                           max_iter: int, mesh: Optional[Mesh] = None,
                           num_parts: Optional[int] = None,
                           schedule: str = "ring",
                           kernel: str | bool = "auto",
                           use_kernel: bool | None = None,
                           reorder: str = "none",
                           frontier: str = "dense",
                           prefetch: str = "auto",
                           batch: int | None = None,
                           exchange: str = "exact",
                           overlap: bool = True,
                           checkpoint_dir: str | None = None,
                           checkpoint_every: int = 0,
                           resume: str = "auto",
                           guards: str | bool = "off",
                           faults=()):
    """Distributed Algorithm-1 entry point (one part per mesh device).

    prefetch ("auto"|"on"|"off"): per-bucket scalar-prefetch window
    tables for the fused bucket planes. "auto" builds and attaches them
    whenever the kernels are on (the unfused paths never consult them);
    "on" forces the build; "off" keeps every bucket vprops-resident.
    Buckets whose required slab pair would be resident-sized keep a
    per-bucket resident fallback (window 0); the result is bit-identical
    in every mode.

    batch / program-sequence: same contract as `run_vcprog` — Q query
    lanes execute as one BatchedProgram over [v_pp, Q] local state, so
    every bucket plane pass AND every delta-exchange hop carries all Q
    lanes at once (the compacted frontier payloads gather whole [Q]-lane
    rows). Result leaves are [V, Q]; `info["batch"] = Q`.

    exchange ("exact"|"fp16"|"q8ef"): the wire codec applied to the
    sparse delta-exchange payloads (repro.distributed.wire) — bit-packed
    u16/u24 local indices plus fp16 or int8-error-feedback float value
    leaves. "exact" (default) is bit-identical; "q8ef" is for
    tolerance-governed operators (PageRank-family) and carries its
    per-vertex residual through the superstep loop. Takes effect with a
    sparse frontier; the dense exchange always ships full-width rows.

    overlap (default True): software-pipeline every schedule so the
    exchange hides behind the bucket plane passes (double-buffered ring
    carry, pipelined allgather decode, per-offset push ppermutes).
    Bit-identical on/off. `info["bytes_exchanged"]` reports the modeled
    per-superstep wire bytes per device (exact vs codec-compressed vs
    dense) for benches and CI gates.

    Resilience (docs/robustness.md): `checkpoint_dir`/`checkpoint_every`
    switch to the chunked runner and snapshot the complete loop carry —
    including batched `_lane_act` masks and q8ef EF residuals — at every
    chunk boundary, stored in the ORIGINAL vertex-id space so
    `resume="auto"` restores elastically onto a different partition
    count (the push+q8ef residual is partition-structured and pins P via
    its fingerprint). `guards="on"` arms wire checksums on every delta
    payload plus the NaN/monotonicity watchdogs; a trip rolls back to
    the last committed chunk and replays, and a deterministic re-trip on
    a lossy codec degrades `exchange` to "exact"
    (`info["degraded_exchange"]`) instead of failing. `faults=` injects
    seeded deterministic faults (repro.distributed.faults) for tests.
    """
    program = vcprog.as_batched(program, batch)
    if mesh is None:
        dev = np.asarray(jax.devices())
        mesh = Mesh(dev.reshape(-1), (AXIS,))
    Pn = num_parts or mesh.devices.size
    assert Pn == mesh.devices.size, "one part per device"
    kernel_on = message_plane.resolve_kernel_arg(kernel, use_kernel)
    frontier = message_plane.resolve_frontier_mode(frontier)
    prefetch = message_plane.resolve_prefetch_mode(prefetch)
    exchange = wire.resolve_exchange_mode(exchange)
    overlap = bool(overlap)

    sg = build_sharded_graph(graph, Pn, reorder=reorder)
    v_pp = sg["v_per_part"]
    if schedule == "push":
        # transpose to the src-part-major view (src ids become local);
        # per-bucket content (and its segment metadata) is unchanged
        for k in ("edge_src_local", "edge_src_global", "edge_dst_global",
                  "edge_src_uid", "edge_dst_uid",
                  "edge_dst_local", "edge_mask", "bucket_last_edge",
                  "bucket_has_edge"):
            sg[k] = np.swapaxes(sg[k], 0, 1)
        sg["eprops"] = {k: np.swapaxes(v, 0, 1)
                        for k, v in sg["eprops"].items()}
        sg["edge_src_local"] = sg["edge_src_global"] % v_pp

    # per-bucket scalar-prefetch tables — built AFTER the push transpose
    # so they describe the exact bucket-local src runs the kernels stream
    pf_blocks, pf_windows = None, None
    if prefetch == "on" or (prefetch == "auto" and kernel_on):
        pf_blocks, pf_windows = build_bucket_prefetch(
            sg["edge_src_local"], sg["edge_mask"], v_pp,
            shared=(schedule == "ring"))
        if not any(pf_windows):
            pf_blocks = pf_windows = None  # every bucket resident

    guards_on = faults_mod.resolve_guards_mode(guards)
    fault_specs = faults_mod.resolve_faults(faults)
    resilient = (bool(checkpoint_dir) or int(checkpoint_every or 0) > 0
                 or guards_on or bool(fault_specs))

    # every [P, ...] array goes host -> its part's device directly, so no
    # device ever holds another part's share (nor the whole graph)
    put = _part_placer(mesh)
    # initial vertex props: the input props (init_vertex runs on device)
    vprops0 = jax.tree.map(put, sg["vprops_in"])
    active0 = put(np.ones((Pn, v_pp), bool))
    edges = {k: put(sg[k]) for k in (
        "edge_src_local", "edge_src_global", "edge_dst_global",
        "edge_src_uid", "edge_dst_uid", "edge_dst_local", "edge_mask",
        "bucket_last_edge", "bucket_has_edge")}
    edges["eprops"] = jax.tree.map(put, sg["eprops"])
    if pf_blocks is not None:
        edges["bucket_pf_blocks"] = put(pf_blocks)
    out_deg_j = put(sg["out_degree"])
    valid_j = put(sg["vertex_valid"])
    vids_j = put(sg["vertex_ids"])

    rinfo = {}
    resumed = None
    if not resilient:
        runner = make_distributed_runner(program, v_pp, Pn, mesh, max_iter,
                                         schedule, kernel_on=kernel_on,
                                         frontier=frontier,
                                         prefetch_windows=pf_windows,
                                         exchange=exchange, overlap=overlap)
        vprops, active, its, _ = runner(vprops0, active0, out_deg_j,
                                        valid_j, vids_j, edges)
        iterations = int(np.asarray(its)[0]) - 1
    else:
        vprops, active, iterations, rinfo, resumed = _run_resilient(
            program, graph, sg, edges, mesh, int(max_iter), schedule,
            kernel_on, frontier, pf_windows, exchange, overlap, guards_on,
            fault_specs, checkpoint_dir, int(checkpoint_every or 0),
            resume, vprops0, active0, out_deg_j, valid_j, vids_j)

    V = sg["num_vertices"]
    part_devices = _part_devices(jax.tree.leaves(vprops)[0])
    host = jax.tree.map(
        lambda a: np.asarray(a).reshape((Pn * v_pp,) + a.shape[2:])[:V],
        vprops)
    if sg["inv_perm"] is not None:
        # un-permute: row old_id of the result lives at new_id=inv_perm[old]
        host = jax.tree.map(lambda a: a[sg["inv_perm"]], host)
    active_end = int(np.sum(np.asarray(active)))
    info = {"engine": "distributed", "schedule": schedule, "num_parts": Pn,
            "kernel_on": kernel_on, "reorder": reorder,
            "frontier": frontier, "prefetch": prefetch,
            "prefetch_windows": pf_windows,
            "exchange": rinfo.get("degraded_exchange") or exchange,
            "overlap": overlap,
            "iterations": iterations,
            "active_at_end": active_end,
            "converged": bool(active_end == 0),
            "part_devices": part_devices,
            "bytes_exchanged": _exchange_bytes_info(
                program, sg, schedule, frontier, exchange)}
    if resilient:
        info.update(rinfo, resumed_from=resumed)
    if not info["converged"]:
        warnings.warn(
            f"run_vcprog_distributed hit max_iter={int(max_iter)} with "
            f"{active_end} vertices still active — the result is "
            "truncated, not converged (info['converged'] is False)",
            faults_mod.NonConvergenceWarning, stacklevel=2)
    if isinstance(program, vcprog.BatchedProgram):
        # un-wrap the lane axis: the user sees the base record with [V, Q]
        # leaves (the `_lane_act` bookkeeping column stays internal)
        host = host["p"]
        info["batch"] = program.num_lanes
    return host, info


def _part_placer(mesh: Mesh):
    """host [P, ...] array -> device array with part p on mesh device p."""
    sharding = NamedSharding(mesh, P(AXIS))
    return lambda a: jax.device_put(np.asarray(a), sharding)


def _part_devices(arr) -> list:
    """Device id holding each part (row block along axis 0) of a
    part-sharded result, in part order."""
    rows = {}
    for shard in arr.addressable_shards:
        start = shard.index[0].start or 0
        rows[start] = shard.device.id
    return [rows[k] for k in sorted(rows)]


def _run_resilient(program, graph, sg, edges, mesh, max_iter, schedule,
                   kernel_on, frontier, pf_windows, exchange, overlap,
                   guards_on, fault_specs, checkpoint_dir, checkpoint_every,
                   resume, vprops0, active0, out_deg_j, valid_j, vids_j):
    """Chunked execution + checkpoint/resume + guard ladder for the
    distributed engine. Returns (vprops [P, v_pp, ...], active, final
    iteration count, resilience info, resumed_from step)."""
    from repro import checkpoint as ckpt
    Pn, v_pp = sg["num_parts"], sg["v_per_part"]
    codec = wire.get_codec(exchange)
    carry_err = codec.error_feedback and frontier != "dense"

    def build(exchange_, faults_):
        return make_distributed_chunk_runner(
            program, v_pp, Pn, mesh, schedule, kernel_on=kernel_on,
            frontier=frontier, prefetch_windows=pf_windows,
            exchange=exchange_, overlap=overlap, guards=guards_on,
            faults=faults_)

    init_j, chunk_j = build(exchange, fault_specs)
    state = init_j(vprops0, active0, out_deg_j, valid_j, vids_j)

    # ---- portable checkpoint form: ORIGINAL vertex-id space ------------
    # [P, v_pp, ...] sharded state globalizes to [V, ...] rows keyed by
    # original ids, so a snapshot restores onto a different partition
    # count or reordering (elastic resume). Pad rows restore as zeros —
    # they are valid-masked inactive and never read by any combine path.
    inv, perm = sg["inv_perm"], sg["vertex_perm"]
    V = sg["num_vertices"]

    def to_global(a):
        a = np.asarray(a)
        g = a.reshape((Pn * v_pp,) + a.shape[2:])[:V]
        return g[inv] if inv is not None else g

    def to_parts(a):
        a = np.asarray(a)
        b = a[perm] if perm is not None else a
        out = np.zeros((Pn * v_pp,) + b.shape[1:], b.dtype)
        out[:V] = b
        return out.reshape((Pn, v_pp) + b.shape[1:])

    def to_portable(st):
        port = {"it": int(np.asarray(st["it"])[0]),
                "n": int(np.asarray(st["n"])[0])}
        for k in ("vprops", "active", "inbox", "has_msg"):
            port[k] = jax.tree.map(to_global, st[k])
        if "werr" in st:
            # push's EF residual is per-(dst-part, local-row) message
            # state — partition-structured, stored raw (the fingerprint
            # pins the layout); allgather/ring residuals are per-vertex
            # property state and globalize like vprops
            port["werr"] = (jax.tree.map(np.asarray, st["werr"])
                            if schedule == "push"
                            else jax.tree.map(to_global, st["werr"]))
        return port

    def from_portable(port):
        st = {"it": jnp.full((Pn,), int(port["it"]), jnp.int32),
              "n": jnp.full((Pn,), int(port["n"]), jnp.int32)}
        for k in ("vprops", "active", "inbox", "has_msg"):
            st[k] = jax.tree.map(lambda a: jnp.asarray(to_parts(a)),
                                 port[k])
        if "werr" in port:
            st["werr"] = (jax.tree.map(jnp.asarray, port["werr"])
                          if schedule == "push"
                          else jax.tree.map(
                              lambda a: jnp.asarray(to_parts(a)),
                              port["werr"]))
        return st

    mgr = save_cb = None
    resumed = None
    if checkpoint_dir:
        # max_iter deliberately NOT fingerprinted: a truncated run may
        # resume with a higher budget. num_parts/reorder are NOT either —
        # the portable form is partition-independent (elastic resume) —
        # EXCEPT when the push schedule carries a partition-structured
        # EF residual, which pins both via `ef_layout`.
        fp = {"graph": ckpt.graph_signature(graph),
              "engine": "distributed", "schedule": schedule,
              "program": ckpt.program_signature(program),
              "frontier": frontier, "exchange": exchange,
              "wire_state": bool(carry_err), "format": 1}
        if carry_err and schedule == "push":
            fp["ef_layout"] = f"push:{Pn}:{sg['v_per_part']}"
        mgr = ckpt.CheckpointManager(checkpoint_dir)
        step0 = ckpt.resume_step(mgr, fp, resume)
        if step0 is not None:
            state = from_portable(mgr.restore(to_portable(state), step0))
            resumed = step0

        def save_cb(st, done):
            mgr.save(done, to_portable(st), metadata={"fingerprint": fp})

    def make_chunk(cj):
        def chunk(st, limit, f_on):
            out, alarms = cj(st, valid_j, edges,
                             jnp.full((Pn,), limit, jnp.int32),
                             jnp.full((Pn,), f_on, jnp.int32))
            return out, np.asarray(alarms)[0]
        return chunk

    def probe(st):
        return (int(np.asarray(st["it"])[0]),
                int(np.asarray(st["n"])[0]) > 0)

    degrade_cb = None
    if not codec.lossless:
        def degrade_cb(st):
            # the degradation rung: deterministic guard trips on a lossy
            # codec fall back to the exact wire — drop the EF residual,
            # drop lossy_only fault specs, keep everything else of the
            # committed state
            _, cj2 = build("exact", faults_mod.drop_lossy_only(fault_specs))
            st2 = {k: v for k, v in st.items() if k != "werr"}
            return make_chunk(cj2), st2, "exact"

    state, rinfo = faults_mod.drive_chunks(
        make_chunk(chunk_j), state, max_iter=max_iter,
        every=checkpoint_every, probe=probe, save=save_cb,
        flush=(mgr.wait if mgr is not None else None),
        guards_on=guards_on, faults=fault_specs, degrade=degrade_cb)
    if mgr is not None:
        mgr.wait()
    iterations = int(np.asarray(state["it"])[0]) - 1
    return state["vprops"], state["active"], iterations, rinfo, resumed
