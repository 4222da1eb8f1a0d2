"""Fused gather–emit–combine Pallas kernel: the message plane in ONE pass.

The unfused pull dataflow makes three full E-sized HBM passes per
iteration:

    src_prop = tree_gather(vprops, src)          # pass 1: gather
    is_emit, msgs = vmap(emit_message)(...)      # pass 2: emit
    inbox = segment_combine(msgs, dst, ...)      # pass 3: combine

This kernel streams dst-sorted edge blocks once: for each edge block it
evaluates the user's (traceable) `emit_message` on the VPU and folds the
messages straight into the per-vertex inbox accumulator — messages never
round-trip through HBM.

Grid: a VISIT TABLE, not a (vertex-block x edge-block) product. Edges are
dst-sorted, so vertex block vb only ever meets the contiguous run of edge
blocks that hold its in-edges. The wrapper lists those (vb, eb) pairs in
order — at most n_vb + n_e of them — and hands the list to the kernel as
a scalar-prefetched table; block index maps read it, so every grid step
DMAs one edge block that overlaps its vertex block. One plane pass thus
streams each edge block about once (the product grid streamed it n_vb
times). Steps past the table's live length repeat the last pair and do
nothing.

Where the src rows come from:
  * resident (default): XLA gathers the src rows of every vertex-property
    leaf (and the frontier flag) into edge-ordered [E] operands, which the
    kernel streams with the edges. VMEM holds O(block) state at any V.
  * scalar-prefetch (`prefetch=(block_idx, window, block_e)`): a window
    table (`core/graph_device.py::compute_prefetch_windows`) names, per
    edge block, a pair of adjacent src slabs that cover its src span; the
    kernel DMAs that pair and gathers in-kernel with a one-hot select
    (exact for every dtype, inf included). Used for windows up to
    MAX_WINDOW rows; wider ones take the resident variant.

Layout contract (the framework's canonical order):
  * `dst` is sorted ascending. Padded edge slots of pre-padded layouts
    (distributed buckets) carry a sentinel dst >= num_segments so
    sortedness survives padding; kernel-padded edges carry dst == V_pad.
  * vertex-property leaves are [V] for the scalar kernel; the PACKED
    variant also accepts [V, D] vertex-property / message / edge-property
    leaves (D consecutive slab rows); anything else falls back to the
    unfused path.
  * `valid` (optional [E] mask) vetoes emissions of padded slots; `src_ids`
    / `dst_ids` (optional [E]) are the endpoint ids handed to `emit_fn`
    when they differ from the gather/combine indices (distributed buckets
    emit with global ids but gather/combine with local ones).

Combine: float sums use a one-hot matmul on the MXU at HIGHEST precision;
integer sums and min/max use a masked select + reduce on the VPU (the MXU
has no int32 path). Integer payloads accumulate in int32 (exact for
sentinel ids like 2^31-1), floats in f32.

Block shapes follow Mosaic's tiling: a 1-D array of >= 1024 elements is
tiled T(1024), so 1-D edge and vertex blocks are multiples of TILE_1D (or
the whole array). The packed variant keeps its slabs lane-major
([W, N]: record columns on sublanes, vertices or edges on lanes), so
narrow records are not padded to 128 lanes in HBM.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32_IDENT = {"sum": 0.0, "min": 3.4e38, "max": -3.4e38}
_NAMED = ("sum", "min", "max")

#: Mosaic tiles a 1-D array of >= 1024 elements as T(1024): every 1-D
#: block is a multiple of this, or the whole array.
TILE_1D = 1024
#: widest src window the scalar-prefetch variant gathers in-kernel. Its
#: one-hot select costs block_e x 2 x max(window, TILE_1D) VPU work per
#: edge block; past this, XLA's gather of the edge-ordered rows is cheaper.
MAX_WINDOW = 2048
#: scalar-memory words the visit / window / block-skip tables may take
#: (SMEM is 1 MiB on v5e; half is left to Mosaic).
SMEM_TABLE_WORDS = 128 * 1024
#: scoped VMEM a kernel may use (v5e has 128 MiB; Mosaic's default scope
#: is 16 MiB, which a packed record's [TILE_1D, BV] selects outgrow)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_HIGHEST = jax.lax.Precision.HIGHEST


def _ceil_to(x: int, m: int) -> int:
    return max(m, ((x + m - 1) // m) * m)


def interpret_mode() -> bool:
    """Pallas kernels compile to Mosaic on a TPU and run in interpret
    mode everywhere else (the CPU test path). Decided from the backend
    alone, so a TPU run can never fall back to the interpreter."""
    return jax.default_backend() != "tpu"


def _ident_for(dtype, monoid: str):
    if jnp.issubdtype(dtype, jnp.integer) or dtype == jnp.bool_:
        # the payload dtype's own bounds (not int32's): the identity must
        # survive the flush cast back to narrow int outputs
        info = jnp.iinfo(jnp.int32 if dtype == jnp.bool_ else dtype)
        return {"sum": 0, "min": int(info.max),
                "max": int(info.min)}[monoid], jnp.int32
    return _F32_IDENT[monoid], jnp.float32


# ---------------------------------------------------------------------------
# Grid planning: block sizes and the visit table
# ---------------------------------------------------------------------------

class _Plan(NamedTuple):
    bv: int      # vertex rows per output block
    be: int      # edge slots per edge block
    n_vb: int
    n_e: int
    shift: int   # visit code = vb << shift | eb

    @property
    def V_pad(self) -> int:
        return self.n_vb * self.bv

    @property
    def E_pad(self) -> int:
        return self.n_e * self.be

    @property
    def n_steps(self) -> int:
        return self.n_vb + self.n_e


def _plan(E: int, V: int, block_v: int, block_e: int,
          words_per_edge_block: int = 0, fixed_be: int = 0) -> _Plan:
    """Block sizes from shapes. The edge block grows (in TILE_1D steps)
    until the scalar tables fit SMEM_TABLE_WORDS; `fixed_be` pins it (a
    window table is built for one block size) and is dropped — returned
    plan has a different be — when the tables would not fit."""
    bv = _ceil_to(V, 128) if V <= TILE_1D else _ceil_to(block_v, TILE_1D)
    n_vb = max(pl.cdiv(V, bv), 1)
    if fixed_be:
        be = fixed_be
    elif E <= TILE_1D and block_e >= E:
        be = _ceil_to(E, 8)
    else:
        be = _ceil_to(block_e, TILE_1D)

    def words(be_):
        n_e_ = max(pl.cdiv(E, be_), 1)
        return n_vb + n_e_ + 1 + words_per_edge_block * n_e_

    while words(be) > SMEM_TABLE_WORDS:
        be = _ceil_to(be * 2, TILE_1D)
    n_e = max(pl.cdiv(E, be), 1)  # E == 0 still needs a flush pass
    shift = max(int(n_e - 1).bit_length(), 1)
    if n_vb > 2 ** (31 - shift):
        raise ValueError(f"visit table cannot encode {n_vb} vertex blocks "
                         f"x {n_e} edge blocks in int32")
    return _Plan(bv=bv, be=be, n_vb=n_vb, n_e=n_e, shift=shift)


def _visit_table(seg_p, p: _Plan):
    """[n_steps + 1] int32: the (vb, eb) pairs the grid visits, in order,
    as ``vb << shift | eb``; the last entry is the live step count. Vertex
    block vb visits edge blocks [lo, hi] — the blocks holding its in-edge
    run (one block, masked to nothing, when it has none) — so every vertex
    block is visited, and initialized and flushed, at least once."""
    starts = jnp.searchsorted(
        seg_p, jnp.arange(p.n_vb + 1, dtype=jnp.int32) * p.bv,
        side="left").astype(jnp.int32)
    lo = jnp.minimum(starts[:-1] // p.be, p.n_e - 1)
    hi = jnp.clip(jnp.maximum(starts[1:] - 1, starts[:-1]) // p.be,
                  lo, p.n_e - 1)
    cnt = hi - lo + 1
    ends = jnp.cumsum(cnt).astype(jnp.int32)
    t = jnp.arange(p.n_steps, dtype=jnp.int32)
    vb = jnp.minimum(jnp.searchsorted(ends, t, side="right"),
                     p.n_vb - 1).astype(jnp.int32)
    eb = jnp.minimum(lo[vb] + t - (ends[vb] - cnt[vb]), hi[vb])
    return jnp.concatenate([(vb << p.shift) | eb, ends[-1:]])


def _step(code_ref, p: _Plan):
    """This grid step's (vb, eb) and its first/last/live flags."""
    t = pl.program_id(0)
    mask = (1 << p.shift) - 1
    code = code_ref[t]
    vb, eb = code >> p.shift, code & mask
    prev = code_ref[jnp.maximum(t - 1, 0)] >> p.shift
    nxt = code_ref[jnp.minimum(t + 1, p.n_steps - 1)] >> p.shift
    first = (t == 0) | (prev != vb)
    last = (t == p.n_steps - 1) | (nxt != vb)
    live = t < code_ref[p.n_steps]
    return vb, eb, first, last, live


def _e_map(p: _Plan):
    mask = (1 << p.shift) - 1
    return lambda t, code, *_: (code[t] & mask,)


def _v_map(p: _Plan):
    return lambda t, code, *_: (code[t] >> p.shift,)


def _block_active(active, src, valid, pad_e, n_e: int, be: int):
    """Per-edge-block frontier bitmap [n_e] int32: does any edge in the
    block have an active src (and a valid slot)? Computed on device each
    superstep — one cheap [E] int gather + a blocked max.

    `active` may carry trailing query-lane axes ([V, Q] per-lane masks
    from a batched run): lanes are OR-reduced first, so the bitmap keeps
    a block live whenever ANY lane still needs it — the union bitmap is
    a superset of every per-lane bitmap, so block-skip never drops a
    block some lane's frontier touches."""
    active = jnp.asarray(active)
    if active.ndim > 1:
        active = active.reshape(active.shape[0], -1).max(axis=1)
    flag = jnp.take(active.astype(jnp.int32), src.astype(jnp.int32), axis=0)
    if valid is not None:
        flag = flag * valid.astype(jnp.int32)
    return pad_e(flag, 0).reshape(n_e, be).max(axis=1)


# ---------------------------------------------------------------------------
# Schema checks
# ---------------------------------------------------------------------------

def _emit_schema(emit_fn, num_edges: int, vprops, eprops):
    """Abstract-trace the vmapped emit: (is_emit_sds, msg_sds pytree)."""
    E = int(num_edges)
    return jax.eval_shape(
        jax.vmap(emit_fn), jax.ShapeDtypeStruct((E,), jnp.int32),
        jax.ShapeDtypeStruct((E,), jnp.int32),
        jax.tree.map(lambda a: jax.ShapeDtypeStruct((E,) + a.shape[1:],
                                                    a.dtype), vprops),
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                     eprops))


def _schema_ok(emit_sds, num_edges, num_vertices, vprops, eprops,
               allow_vector: bool = False) -> bool:
    E, V = int(num_edges), int(num_vertices)

    def ok(shape, n):
        if shape == (n,):
            return True
        return (allow_vector and len(shape) == 2 and shape[0] == n
                and shape[1] >= 1)

    return (all(ok(s.shape, E) for s in jax.tree.leaves(emit_sds[1]))
            and all(ok(a.shape, V) for a in jax.tree.leaves(vprops))
            and all(ok(a.shape, E) for a in jax.tree.leaves(eprops)))


def fusable(emit_fn, monoid, vprops, eprops, num_edges: int,
            num_vertices: int, allow_vector: bool = False) -> bool:
    """THE applicability predicate for the fused kernel — the same schema
    check gather_emit_combine enforces, so a True here can never turn
    into a trace-time ValueError there.

    `monoid` is either one named-monoid string (every leaf combines the
    same way, scalar kernel) or a tuple of per-leaf names in the flattened
    message order (the packed multi-leaf kernel's per-slice table).
    `allow_vector` admits [V, D] vertex-property and [E, D] message leaves
    — legal only for the PACKED variant, where a vector leaf occupies D
    consecutive slab rows.

    An emit that fails to trace raises here: the unfused path would trace
    the same function and fail the same way, so nothing is gained by
    hiding the error."""
    if isinstance(monoid, (tuple, list)):
        if not monoid or any(m not in _NAMED for m in monoid):
            return False
    elif monoid not in _NAMED:
        return False
    if int(num_vertices) == 0:
        return False
    emit_sds = _emit_schema(emit_fn, num_edges, vprops, eprops)
    if isinstance(monoid, (tuple, list)) \
            and len(monoid) != len(jax.tree.leaves(emit_sds[1])):
        return False
    return _schema_ok(emit_sds, num_edges, num_vertices, vprops, eprops,
                      allow_vector=allow_vector)


# ---------------------------------------------------------------------------
# Kernel-body pieces shared by the scalar and packed variants
# ---------------------------------------------------------------------------

def _carrier(dtype):
    """dtype a leaf is streamed, gathered and reduced in: f32 for floats,
    int32 for ints and bools. Every kernel operand is 32-bit, so one
    tiling rule covers them all (bool and 16-bit 1-D blocks tile
    differently in Mosaic); leaves are cast back before emit sees them."""
    return jnp.float32 if jnp.issubdtype(dtype, jnp.floating) else jnp.int32


def _slab_gather(pair, idx, slab: int):
    """Rows idx of the slab pair [base, base + 2*slab) as a [BE] vector,
    by one-hot select + reduce (exactly one row hits; out-of-window idx
    hit none and yield 0 — those edges are invalid anyway)."""
    be = idx.shape[0]
    dt = pair[0].dtype  # a 32-bit carrier (see _carrier)
    out = jnp.zeros((be,), dt)
    for h, ref in enumerate(pair):
        for j in range(0, slab, TILE_1D):
            w = min(TILE_1D, slab - j)
            rows = jax.lax.broadcasted_iota(jnp.int32, (be, w), 1) \
                + (h * slab + j)
            out = out + jnp.sum(
                jnp.where(rows == idx[:, None], ref[j:j + w][None, :],
                          jnp.asarray(0, dt)), axis=1)
    return out


def _emit_valid(emit_fn, vp_def, ep_def, sp_leaves, ep_leaves, sid, did,
                act, eb, block_e: int, num_edges: int, valid_ref):
    """Evaluate the user's emit on one edge block; returns (msg leaves,
    valid [BE] bool). Padded rows run emit on zero-filled eprops and can
    produce non-finite garbage, so validity is settled before any combine
    arithmetic touches a message."""
    be = sid.shape[0]
    src_prop = jax.tree.unflatten(vp_def, sp_leaves)
    edge_prop = jax.tree.unflatten(ep_def, ep_leaves)
    is_emit, msg = jax.vmap(emit_fn)(sid, did, src_prop, edge_prop)
    pos = jax.lax.broadcasted_iota(jnp.int32, (be, 1), 0)[:, 0] \
        + eb * block_e
    valid = is_emit.astype(bool) & (pos < num_edges)
    if act is not None:
        valid &= act
    if valid_ref is not None:
        valid &= valid_ref[...] > 0
    return jax.tree.leaves(msg), valid


def _onehot_chunks(seg, valid, v_lo, block_v: int):
    """Yield (chunk slice, onehot [sub, BV], hit [sub, BV]) over TILE_1D-
    edge chunks of the block, so no intermediate exceeds TILE_1D x BV."""
    be = seg.shape[0]
    sub = min(be, TILE_1D)
    cols = jax.lax.broadcasted_iota(jnp.int32, (sub, block_v), 1) + v_lo
    valid = valid.astype(jnp.int32)  # Mosaic relayouts i32, not i1
    for c0 in range(0, be, sub):
        sl = slice(c0, c0 + sub)
        onehot = seg[sl][:, None] == cols
        yield sl, onehot, onehot & (valid[sl][:, None] > 0)


def _fold_row(acc, rows, vl, onehot, hit, monoid: str, ident):
    """acc [n, BV] <- acc (+)= fold of rows [n, sub] over the edge chunk."""
    adt = acc.dtype
    if monoid == "sum" and adt == jnp.float32:
        m = jnp.where(vl.astype(jnp.int32)[None, :] > 0, rows,
                      jnp.asarray(0, adt))
        acc[...] += jax.lax.dot_general(
            m, onehot.astype(adt),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=adt, precision=_HIGHEST)
        return
    n = rows.shape[0]
    reds = []
    for j in range(n):
        fill = 0 if monoid == "sum" else ident
        sel = jnp.where(hit, rows[j][:, None], jnp.asarray(fill, adt))
        reds.append({"sum": jnp.sum, "min": jnp.min,
                     "max": jnp.max}[monoid](sel, axis=0)[None, :])
    red = reds[0] if n == 1 else jnp.concatenate(reds, axis=0)
    if monoid == "sum":
        acc[...] += red
    else:
        op = jnp.minimum if monoid == "min" else jnp.maximum
        acc[...] = op(acc[...], red)


# ---------------------------------------------------------------------------
# Scalar-record kernel
# ---------------------------------------------------------------------------

def _kernel(*refs, emit_fn, monoid, n_vp, n_ep, n_msg, vp_def, ep_def,
            vp_dtypes, ep_dtypes, idents, p, num_edges, has_act, has_valid,
            has_ids, window, slab, sub_blocks, blockskip):
    code_ref, refs = refs[0], refs[1:]
    if window:
        win_ref, refs = refs[0], refs[1:]
    if blockskip:
        bm_ref, refs = refs[0], refs[1:]
    seg_ref, src_ref = refs[0], refs[1]
    k = 2
    valid_ref = None
    if has_valid:
        valid_ref = refs[k]
        k += 1
    if has_ids:
        sid_ref, did_ref = refs[k], refs[k + 1]
        k += 2
    # window mode: a (lo, hi) slab pair per leaf per window-table block
    n_slab = 2 * sub_blocks if window else 1
    act_refs = refs[k:k + n_slab] if has_act else ()
    k += len(act_refs)
    vp_refs = refs[k:k + n_slab * n_vp]
    ep_refs = refs[k + n_slab * n_vp:k + n_slab * n_vp + n_ep]
    k += n_slab * n_vp + n_ep
    out_refs = refs[k:k + n_msg]
    hm_out = refs[k + n_msg]
    acc_refs = refs[k + n_msg + 1:k + 2 * n_msg + 1]
    hm_acc = refs[k + 2 * n_msg + 1]

    vb, eb, first, last, live = _step(code_ref, p)

    @pl.when(first)
    def _init():
        for a, ident in zip(acc_refs, idents):
            a[...] = jnp.full_like(a, ident)
        hm_acc[...] = jnp.zeros_like(hm_acc)

    run = live
    if blockskip:
        # frontier block-skip: the prefetched per-edge-block any_active
        # bitmap says no src in this block is on the frontier — every
        # emission would be vetoed, so the block contributes only
        # identities and is skipped (bit-identical to running it)
        run &= bm_ref[eb] > 0

    @pl.when(run)
    def _compute():
        seg = seg_ref[...]  # [BE] int32 dst ids, sorted (pads = sentinel)
        src = src_ref[...]
        if window:
            # each window-table block j of this edge block gathers from
            # its own DMA'd slab pair [base_j, base_j + 2*slab)
            tbe = p.be // sub_blocks
            part = jax.lax.broadcasted_iota(jnp.int32, (p.be, 1), 0)[:, 0] \
                // tbe
            idxs, in_win = [], None
            for j in range(sub_blocks):
                base = ((win_ref[eb * sub_blocks + j] * window) // slab) \
                    * slab
                idxs.append(src - base)
                ok = (part == j) & (idxs[j] >= 0) & (idxs[j] < 2 * slab)
                in_win = ok if in_win is None else in_win | ok

            def gather(leaf_refs):  # the leaf's k slab pairs, in order
                out = None
                for j in range(sub_blocks):
                    g = _slab_gather(leaf_refs[2 * j:2 * j + 2], idxs[j],
                                     slab)
                    out = g if out is None else jnp.where(part == j, g, out)
                return out

            sp_leaves = [gather(vp_refs[i * n_slab:(i + 1) * n_slab])
                         for i in range(n_vp)]
            act = gather(act_refs) > 0 if has_act else None
            act = in_win if act is None else act & in_win
        else:
            sp_leaves = [r[...] for r in vp_refs]
            act = act_refs[0][...] > 0 if has_act else None
        sp_leaves = [x.astype(dt) for x, dt in zip(sp_leaves, vp_dtypes)]
        ep_leaves = [r[...].astype(dt) for r, dt in zip(ep_refs, ep_dtypes)]
        msg_leaves, valid = _emit_valid(
            emit_fn, vp_def, ep_def, sp_leaves, ep_leaves,
            sid_ref[...] if has_ids else src,
            did_ref[...] if has_ids else seg, act, eb, p.be, num_edges,
            valid_ref)
        for sl, onehot, hit in _onehot_chunks(seg, valid, vb * p.bv, p.bv):
            for leaf, acc, ident in zip(msg_leaves, acc_refs, idents):
                _fold_row(acc, leaf[sl].astype(acc.dtype)[None, :],
                          valid[sl], onehot, hit, monoid, ident)
            got = jnp.max(hit.astype(jnp.int32), axis=0)[None, :]
            hm_acc[...] = jnp.maximum(hm_acc[...], got)

    @pl.when(last)
    def _flush():
        for o, a in zip(out_refs, acc_refs):
            o[...] = a[0]
        hm_out[...] = hm_acc[0]


def _pallas(body, p: _Plan, scalar_ops, operands, in_specs, out_specs,
            out_shape, scratch, name):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalar_ops), grid=(p.n_steps,),
        in_specs=in_specs, out_specs=tuple(out_specs),
        scratch_shapes=scratch)
    return pl.pallas_call(
        body, grid_spec=grid_spec, out_shape=tuple(out_shape),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret_mode(), name=name,
    )(*scalar_ops, *operands)


def _edge_operands(p: _Plan, src, dst, valid, src_ids, dst_ids, e_spec):
    """The edge-streamed id/mask operands every variant starts with."""
    pad_e = lambda a, fill: jnp.pad(a, (0, p.E_pad - a.shape[0]),
                                    constant_values=fill)
    seg_p = pad_e(dst.astype(jnp.int32), jnp.int32(p.V_pad))  # sentinel
    operands = [seg_p, pad_e(src.astype(jnp.int32), 0)]
    if valid is not None:
        operands.append(pad_e(valid.astype(jnp.int32), 0))
    if src_ids is not None or dst_ids is not None:
        operands += [pad_e((src if src_ids is None else src_ids)
                           .astype(jnp.int32), 0),
                     pad_e((dst if dst_ids is None else dst_ids)
                           .astype(jnp.int32), 0)]
    return seg_p, pad_e, operands, [e_spec] * len(operands)


def gather_emit_combine(emit_fn, monoid: str, src, dst, vprops, eprops,
                        active, num_vertices: int, *, valid=None,
                        src_ids=None, dst_ids=None, prefetch=None,
                        block_skip: bool = False,
                        block_v: int = TILE_1D, block_e: int = TILE_1D):
    """Single-pass message plane over combine-ordered (dst-sorted) edges.

    emit_fn(src, dst, src_prop, edge_prop) -> (is_emit, msg) is the user's
    scalar Phase-3 function (traced into the kernel body — no host
    boundary). Returns (inbox record batch [V], has_msg [V] bool).
    `active` None means every source is on the frontier.

    valid / src_ids / dst_ids: see the module docstring (pre-padded and
    globally-addressed layouts). prefetch=(block_idx, window, table_be)
    selects the scalar-prefetch variant when 0 < window <= MAX_WINDOW and
    2*window < V; `block_e` is then the table's block size. block_skip=True
    prefetches a per-edge-block frontier bitmap and skips whole blocks with
    no active src — bit-identical to the dense pass (skipped blocks
    contribute only identities).
    """
    if monoid not in _NAMED:
        raise ValueError(f"fused kernel needs a named monoid, got {monoid!r}")
    E = int(src.shape[0])
    V = int(num_vertices)
    vp_leaves, vp_def = jax.tree.flatten(vprops)
    ep_leaves, ep_def = jax.tree.flatten(eprops)

    # message schema from an abstract trace of the vmapped emit
    emit_sds = _emit_schema(emit_fn, E, vprops, eprops)
    msg_sds = jax.tree.leaves(emit_sds[1])
    if not _schema_ok(emit_sds, E, V, vprops, eprops):
        raise ValueError("fused kernel needs scalar record leaves")

    window, sub_blocks = 0, 1
    if prefetch is not None:
        win_idx, window, table_be = prefetch
        window, table_be = int(window), int(table_be)
        if not 0 < window <= MAX_WINDOW or 2 * window >= _ceil_to(V, 8) \
                or TILE_1D % table_be:
            window = 0  # resident is no larger than the slab pair
        else:
            sub_blocks = TILE_1D // table_be
    p = _plan(E, V, block_v, block_e,
              int(bool(block_skip)) + sub_blocks * int(bool(window)),
              fixed_be=TILE_1D if window else 0)
    if window and p.be != TILE_1D:
        # the window table no longer fits SMEM beside the visit table
        window, sub_blocks = 0, 1
        p = _plan(E, V, block_v, block_e, int(bool(block_skip)))
    slab = max(window, TILE_1D)

    idents, acc_dtypes = zip(*(_ident_for(s.dtype, monoid) for s in msg_sds))
    e_spec = pl.BlockSpec((p.be,), _e_map(p))
    seg_p, pad_e, operands, in_specs = _edge_operands(
        p, src, dst, valid, src_ids, dst_ids, e_spec)
    scalar_ops = [_visit_table(seg_p, p)]
    has_act = active is not None
    vertex_leaves = [l.astype(_carrier(l.dtype)) for l in
                     ([jnp.asarray(active)] if has_act else []) + vp_leaves]
    if window:
        # window-table block j of edge block eb DMAs the slab PAIR that
        # covers [q·W, (q+2)·W), q = win[eb·k + j], in slabs of
        # max(W, TILE_1D) rows; vertex leaves get one extra slab so the
        # +1 index map is always in bounds
        VW_pad = (pl.cdiv(V, slab) + 1) * slab
        mask = (1 << p.shift) - 1

        def blk(win, code, t, j):
            return (win[(code[t] & mask) * sub_blocks + j] * window) // slab

        v_specs = []
        for j in range(sub_blocks):
            v_specs += [
                pl.BlockSpec((slab,), lambda t, code, win, *_, j=j:
                             (blk(win, code, t, j),)),
                pl.BlockSpec((slab,), lambda t, code, win, *_, j=j:
                             (blk(win, code, t, j) + 1,))]
        scalar_ops.append(jnp.pad(
            win_idx.astype(jnp.int32),
            (0, p.n_e * sub_blocks - int(win_idx.shape[0]))))
        for leaf in vertex_leaves:
            leaf = jnp.pad(leaf, (0, VW_pad - leaf.shape[0]))
            operands += [leaf] * len(v_specs)
            in_specs += v_specs
    else:
        # resident: XLA gathers the src rows into edge order once
        src_c = src.astype(jnp.int32)
        for leaf in vertex_leaves:
            operands.append(pad_e(jnp.take(leaf, src_c, axis=0), 0))
            in_specs.append(e_spec)
    operands += [pad_e(l.astype(_carrier(l.dtype)), 0) for l in ep_leaves]
    in_specs += [e_spec] * len(ep_leaves)
    if block_skip:
        scalar_ops.append(_block_active(
            jnp.ones((V,), bool) if active is None else active, src, valid,
            pad_e, p.n_e, p.be))

    body = functools.partial(
        _kernel, emit_fn=emit_fn, monoid=monoid, n_vp=len(vp_leaves),
        n_ep=len(ep_leaves), n_msg=len(msg_sds), vp_def=vp_def,
        ep_def=ep_def, vp_dtypes=tuple(l.dtype for l in vp_leaves),
        ep_dtypes=tuple(l.dtype for l in ep_leaves), idents=idents, p=p,
        num_edges=E, has_act=has_act,
        has_valid=valid is not None,
        has_ids=src_ids is not None or dst_ids is not None, window=window,
        slab=slab, sub_blocks=sub_blocks, blockskip=bool(block_skip))
    out_spec = pl.BlockSpec((p.bv,), _v_map(p))
    out_shape = ([jax.ShapeDtypeStruct((p.V_pad,), adt)
                  for adt in acc_dtypes]
                 + [jax.ShapeDtypeStruct((p.V_pad,), jnp.int32)])
    scratch = ([pltpu.VMEM((1, p.bv), adt) for adt in acc_dtypes]
               + [pltpu.VMEM((1, p.bv), jnp.int32)])
    name = (f"gather_emit{'_prefetch' if window else ''}"
            f"{'_skip' if block_skip else ''}_{monoid}")
    outs = _pallas(body, p, scalar_ops, operands, in_specs,
                   [out_spec] * (len(msg_sds) + 1), out_shape, scratch, name)
    msg_out, hm = outs[:-1], outs[-1]
    inbox = jax.tree.unflatten(jax.tree.structure(emit_sds[1]),
                               [o[:V].astype(s.dtype)
                                for o, s in zip(msg_out, msg_sds)])
    return inbox, hm[:V] > 0


# ---------------------------------------------------------------------------
# Packed multi-leaf variant: one launch for the WHOLE record
# ---------------------------------------------------------------------------
# The scalar kernel above keeps every record leaf a separate [V] operand
# and a separate [1, BV] accumulator: k leaves mean k operands and k folds
# per edge block — and a per-leaf fallback dispatcher would pay k whole
# launches, re-streaming the same endpoints each time. The packed variant
# groups leaves host-side (PackSpec): vertex-property leaves by dtype into
# [W, V] slabs (ONE gather per slab), message leaves by (dtype, monoid)
# into [W, BV] accumulators. A per-slice monoid table means mixed-monoid
# records (sum and min and max leaves in one message) still run as a
# single launch. Slabs are lane-major: record columns on sublanes,
# vertices/edges on lanes.

#: slab widths are padded to this sublane quantum so the [W, BV]
#: accumulators tile cleanly.
LANE_ALIGN = 8


class PackSlot(NamedTuple):
    leaf: int     # flat leaf index in the record
    offset: int   # first row in the group's slab
    ncols: int = 1  # rows occupied ([E]/[V] scalar leaf = 1, [.., D] = D)
    vector: bool = False  # leaf rank: [N, D] (even D=1) vs plain [N]


class PackGroup(NamedTuple):
    dtype: str    # numpy dtype name shared by every leaf in the group
    monoid: str   # per-slice monoid ("" for vertex-property groups)
    width: int    # lane-aligned slab width (>= total slot columns)
    slots: Tuple[PackSlot, ...]


class PackSpec(NamedTuple):
    """Host-side packing table: which record leaf lives at which slab
    row. Hashable — rides EdgeLayout's static `pack` field and the jit
    cache key."""
    vp_groups: Tuple[PackGroup, ...]
    msg_groups: Tuple[PackGroup, ...]


def _pack_groups(keys, ncols, vectors) -> Tuple[PackGroup, ...]:
    order = {}
    for i, k in enumerate(keys):
        order.setdefault(k, []).append(i)
    out = []
    for (dtype, monoid), idxs in order.items():
        slots, off = [], 0
        for i in idxs:
            slots.append(PackSlot(leaf=i, offset=off, ncols=int(ncols[i]),
                                  vector=bool(vectors[i])))
            off += int(ncols[i])
        out.append(PackGroup(
            dtype=dtype, monoid=monoid, width=_ceil_to(off, LANE_ALIGN),
            slots=tuple(slots)))
    return tuple(out)


def _leaf_cols(sds) -> int:
    """Slab rows a record leaf occupies: 1 for [N], D for [N, D]."""
    return 1 if len(sds.shape) == 1 else int(sds.shape[1])


def make_pack_spec(emit_fn, monoids, vprops, eprops, num_edges: int
                   ) -> PackSpec:
    """Group vertex-property leaves by dtype and message leaves by
    (dtype, monoid); computed host-side once per (program, layout) pair.
    Vector ([N, D]) leaves occupy D consecutive rows of their group's
    slab."""
    vp_sds = jax.tree.leaves(jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), vprops))
    msg_sds = jax.tree.leaves(
        _emit_schema(emit_fn, num_edges, vprops, eprops)[1])
    if len(monoids) != len(msg_sds):
        raise ValueError(
            f"per-leaf monoid table has {len(monoids)} entries for "
            f"{len(msg_sds)} message leaves")
    return PackSpec(
        vp_groups=_pack_groups([(s.dtype.name, "") for s in vp_sds],
                               [_leaf_cols(s) for s in vp_sds],
                               [len(s.shape) > 1 for s in vp_sds]),
        msg_groups=_pack_groups([(s.dtype.name, m)
                                 for s, m in zip(msg_sds, monoids)],
                                [_leaf_cols(s) for s in msg_sds],
                                [len(s.shape) > 1 for s in msg_sds]))


def _rows(leaf):
    """[N] / [N, D] leaf -> its slab rows [1, N] / [D, N]."""
    return leaf[None, :] if leaf.ndim == 1 else leaf.T


def _pack_rows(leaves, group: PackGroup, fill):
    """The group's leaves as one lane-major [width, N] slab in the group
    dtype (slot offsets are contiguous in slot order, then row padding)."""
    dt = jnp.dtype(group.dtype)
    pieces = [_rows(leaves[s.leaf]).astype(dt)
              for s in sorted(group.slots, key=lambda s: s.offset)]
    n = pieces[0].shape[1]
    used = sum(s.ncols for s in group.slots)
    if group.width > used:
        pieces.append(jnp.full((group.width - used, n), fill, dt))
    return jnp.concatenate(pieces, axis=0)


def _unpack_slot(slab, slot: PackSlot):
    """The slot's rows of a [W, N] slab, in the leaf's own layout ([N]
    for scalar leaves; [N, D] for vector ones, [N, 1] included)."""
    if slot.ncols == 1 and not slot.vector:
        return slab[slot.offset]
    return slab[slot.offset:slot.offset + slot.ncols].T


def _packed_kernel(*refs, emit_fn, pack, vp_def, n_ep, ep_def, ep_vector,
                   ep_dtypes, idents, p, num_edges, has_act, has_valid,
                   has_ids, blockskip):
    code_ref, refs = refs[0], refs[1:]
    if blockskip:
        bm_ref, refs = refs[0], refs[1:]
    seg_ref, src_ref = refs[0], refs[1]
    k = 2
    valid_ref = None
    if has_valid:
        valid_ref = refs[k]
        k += 1
    if has_ids:
        sid_ref, did_ref = refs[k], refs[k + 1]
        k += 2
    n_vg, n_mg = len(pack.vp_groups), len(pack.msg_groups)
    act_ref = refs[k] if has_act else None
    k += int(has_act)
    vp_refs = refs[k:k + n_vg]
    ep_refs = refs[k + n_vg:k + n_vg + n_ep]
    k += n_vg + n_ep
    out_refs = refs[k:k + n_mg]
    hm_out = refs[k + n_mg]
    acc_refs = refs[k + n_mg + 1:k + 2 * n_mg + 1]
    hm_acc = refs[k + 2 * n_mg + 1]

    vb, eb, first, last, live = _step(code_ref, p)

    @pl.when(first)
    def _init():
        for a, ident in zip(acc_refs, idents):
            a[...] = jnp.full_like(a, ident)
        hm_acc[...] = jnp.zeros_like(hm_acc)

    run = live
    if blockskip:
        run &= bm_ref[eb] > 0  # see _kernel

    @pl.when(run)
    def _compute():
        seg = seg_ref[...]
        src = src_ref[...]
        # unpack the gathered [W, BE] slabs into the record emit sees
        sp_leaves = [None] * sum(len(g.slots) for g in pack.vp_groups)
        for g, ref in zip(pack.vp_groups, vp_refs):
            slab = ref[...]
            for slot in g.slots:
                sp_leaves[slot.leaf] = _unpack_slot(slab, slot).astype(
                    jnp.dtype(g.dtype))
        ep_leaves = [(r[...].T if vec else r[...]).astype(dt)
                     for r, vec, dt in zip(ep_refs, ep_vector, ep_dtypes)]
        msg_leaves, valid = _emit_valid(
            emit_fn, vp_def, ep_def, sp_leaves, ep_leaves,
            sid_ref[...] if has_ids else src,
            did_ref[...] if has_ids else seg,
            act_ref[...] > 0 if has_act else None, eb, p.be, num_edges,
            valid_ref)
        for sl, onehot, hit in _onehot_chunks(seg, valid, vb * p.bv, p.bv):
            for g, acc, ident in zip(pack.msg_groups, acc_refs, idents):
                for slot in g.slots:
                    rows = _rows(msg_leaves[slot.leaf][sl]).astype(acc.dtype)
                    _fold_row(acc.at[slot.offset:slot.offset + slot.ncols],
                              rows, valid[sl], onehot, hit, g.monoid, ident)
            got = jnp.max(hit.astype(jnp.int32), axis=0)[None, :]
            hm_acc[...] = jnp.maximum(hm_acc[...], got)

    @pl.when(last)
    def _flush():
        for o, a in zip(out_refs, acc_refs):
            o[...] = a[...]
        hm_out[...] = hm_acc[0]


def gather_emit_combine_packed(emit_fn, monoids, src, dst, vprops, eprops,
                               active, num_vertices: int, *, valid=None,
                               src_ids=None, dst_ids=None,
                               pack: PackSpec | None = None,
                               block_skip: bool = False,
                               block_v: int = TILE_1D,
                               block_e: int = TILE_1D):
    """Packed multi-leaf single-pass message plane (combine-ordered edges).

    Like :func:`gather_emit_combine` but for records with several leaves
    and/or per-leaf monoids: `monoids` is the per-slice monoid table (one
    named monoid per flattened message leaf), `pack` the optional
    precomputed :class:`PackSpec` (computed here when absent). Vertex
    properties are packed into per-dtype [W, V] slabs and gathered into
    edge order by XLA (one gather per slab), messages fold into
    per-(dtype, monoid) accumulators, so the whole record costs ONE launch.
    Vector leaves ([V, D] vertex properties / [E, D] messages and edge
    properties) occupy D consecutive slab rows. block_skip: see
    gather_emit_combine.
    """
    monoids = tuple(monoids)
    if any(m not in _NAMED for m in monoids):
        raise ValueError(f"per-leaf monoids must be named, got {monoids!r}")
    E = int(src.shape[0])
    V = int(num_vertices)
    vp_leaves, vp_def = jax.tree.flatten(vprops)
    ep_leaves, ep_def = jax.tree.flatten(eprops)

    emit_sds = _emit_schema(emit_fn, E, vprops, eprops)
    msg_sds = jax.tree.leaves(emit_sds[1])
    msg_def = jax.tree.structure(emit_sds[1])
    if not _schema_ok(emit_sds, E, V, vprops, eprops, allow_vector=True):
        raise ValueError(
            "packed fused kernel needs [N] or [N, D] record leaves")
    if pack is None:
        pack = make_pack_spec(emit_fn, monoids, vprops, eprops, E)
    p = _plan(E, V, block_v, block_e, int(bool(block_skip)))

    # one (identity, acc dtype) pair per msg GROUP (uniform inside a group)
    idents, acc_dtypes = zip(*(
        _ident_for(jnp.dtype(g.dtype), g.monoid) for g in pack.msg_groups))

    e_spec = pl.BlockSpec((p.be,), _e_map(p))
    seg_p, pad_e, operands, in_specs = _edge_operands(
        p, src, dst, valid, src_ids, dst_ids, e_spec)
    scalar_ops = [_visit_table(seg_p, p)]
    pad_cols = lambda a: jnp.pad(a, ((0, 0), (0, p.E_pad - a.shape[1])))
    mask = (1 << p.shift) - 1
    slab_spec = lambda w: pl.BlockSpec(
        (w, p.be), lambda t, code, *_: (0, code[t] & mask))
    src_c = src.astype(jnp.int32)
    has_act = active is not None
    if has_act:
        operands.append(pad_e(jnp.take(
            jnp.asarray(active).astype(jnp.int32), src_c, axis=0), 0))
        in_specs.append(e_spec)
    # XLA gathers each lane-major vertex slab into edge order once
    for g in pack.vp_groups:
        slab = _pack_rows(vp_leaves, g, 0)
        operands.append(pad_cols(jnp.take(
            slab.astype(_carrier(slab.dtype)), src_c, axis=1)))
        in_specs.append(slab_spec(g.width))
    ep_vector = tuple(l.ndim > 1 for l in ep_leaves)
    for l, vec in zip(ep_leaves, ep_vector):
        l = l.astype(_carrier(l.dtype))
        operands.append(pad_cols(l.T) if vec else pad_e(l, 0))
        in_specs.append(slab_spec(l.shape[1]) if vec else e_spec)
    if block_skip:
        scalar_ops.append(_block_active(
            jnp.ones((V,), bool) if active is None else active, src, valid,
            pad_e, p.n_e, p.be))

    body = functools.partial(
        _packed_kernel, emit_fn=emit_fn, pack=pack, vp_def=vp_def,
        n_ep=len(ep_leaves), ep_def=ep_def, ep_vector=ep_vector,
        ep_dtypes=tuple(l.dtype for l in ep_leaves), idents=idents, p=p,
        num_edges=E, has_act=has_act,
        has_valid=valid is not None,
        has_ids=src_ids is not None or dst_ids is not None,
        blockskip=bool(block_skip))
    v_map = _v_map(p)
    out_specs = [pl.BlockSpec((g.width, p.bv),
                              lambda t, code, *_: (0, v_map(t, code)[0]))
                 for g in pack.msg_groups]
    out_specs.append(pl.BlockSpec((p.bv,), v_map))
    out_shape = ([jax.ShapeDtypeStruct((g.width, p.V_pad), adt)
                  for g, adt in zip(pack.msg_groups, acc_dtypes)]
                 + [jax.ShapeDtypeStruct((p.V_pad,), jnp.int32)])
    scratch = ([pltpu.VMEM((g.width, p.bv), adt)
                for g, adt in zip(pack.msg_groups, acc_dtypes)]
               + [pltpu.VMEM((1, p.bv), jnp.int32)])
    name = f"gather_emit_packed{'_skip' if block_skip else ''}"
    outs = _pallas(body, p, scalar_ops, operands, in_specs, out_specs,
                   out_shape, scratch, name)

    slab_out, hm = outs[:-1], outs[-1]
    inbox_leaves = [None] * len(msg_sds)
    for g, slab in zip(pack.msg_groups, slab_out):
        for slot in g.slots:
            inbox_leaves[slot.leaf] = _unpack_slot(
                slab[:, :V].astype(jnp.dtype(g.dtype)), slot)
    inbox = jax.tree.unflatten(msg_def, inbox_leaves)
    return inbox, hm[:V] > 0
