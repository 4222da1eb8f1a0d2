"""Engine-agnostic driver: device graph prep + Algorithm-1 loop runner.

Engines are thin *schedule descriptions*: each one picks which
:class:`~repro.core.graph_device.EdgeLayout` of the
:class:`~repro.core.graph_device.DeviceGraph` to hand the message plane
(and where its operands live), and `core/message_plane.py` does the rest.
"""
from __future__ import annotations

import functools
import warnings
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ... import obs
from .. import message_plane, records, vcprog
from ..graph import PropertyGraph
from ..graph_device import DeviceGraph, build_device_graph


def prepare_device_graph(g: PropertyGraph,
                         reorder: str = "none") -> DeviceGraph:
    """Host→device conversion; see graph_device.build_device_graph.
    `reorder` relabels the vertex space for locality (core/reorder.py);
    the driver below un-permutes results, so it is invisible to users."""
    return build_device_graph(g, reorder=reorder)


def _init_state(program, graph: DeviceGraph, engine, kernel_on: bool):
    """The complete Algorithm-1 loop carry (it, vprops, active, inbox,
    has_msg, extra) — the chunked/checkpointed path snapshots exactly
    this tuple at superstep boundaries."""
    V = graph.num_vertices
    empty = jax.tree.map(jnp.asarray, program.empty_message())
    # reordered graphs: init_vertex sees ORIGINAL ids (vertex_perm)
    vprops0 = vcprog.init_vertices(program, graph.vprops_in,
                                   graph.out_degree, V,
                                   vids=graph.vertex_perm)
    inbox0 = records.tree_tile(empty, V)
    active0 = jnp.ones((V,), bool)
    has_msg0 = jnp.zeros((V,), bool)
    extra0 = engine.init_extra(graph, program, vprops0, kernel_on)
    return (jnp.int32(1), vprops0, active0, inbox0, has_msg0, extra0)


def _make_step(program, graph: DeviceGraph, engine, kernel_on: bool,
               frontier: str, prefetch: str):
    empty = jax.tree.map(jnp.asarray, program.empty_message())
    compute_override = getattr(engine, "compute_phase", None)

    def step(it, vprops, active, inbox, has_msg, extra):
        process = active | has_msg
        if compute_override is not None:
            vprops, active = compute_override(graph, program, vprops, inbox,
                                              process, it)
        else:
            vprops, active = vcprog.compute_phase(program, vprops, inbox,
                                                  process, it)
        # the frontier is first-class from here on: engines consume the
        # mask (push/pull heuristic, the plane's per-edge flags); the
        # distributed engine additionally dispatches on the count. For
        # batched programs `active` is already the OR across lanes (the
        # adapter's scalar is_active), and the per-lane masks ride along
        # so the union-driven dispatch stays inspectable per lane
        lanes = (vprops["_lane_act"] > 0
                 if isinstance(program, vcprog.BatchedProgram) else None)
        front = vcprog.make_frontier(active, lane_mask=lanes)
        inbox, has_msg, extra = engine.emit_and_combine(
            graph, program, vprops, front, extra, empty, kernel_on,
            frontier, prefetch)
        return vprops, active, inbox, has_msg, extra

    return step


def _finish(graph: DeviceGraph, state, engine):
    """(vprops, supersteps, active count, active-edge tally); the tally is
    `()` for engines that keep none (see pushpull.tally_value)."""
    final_it, vprops, active = state[0], state[1], state[2]
    if graph.inv_perm is not None:
        # un-permute: row old_id of the result lives at new_id=inv_perm[old]
        vprops = records.tree_gather(vprops, graph.inv_perm)
    tally = getattr(engine, "active_edge_tally", None)
    return (vprops, final_it - 1, jnp.sum(active),
            () if tally is None else tally(state[5]))


def _run_compiled(program, graph: DeviceGraph, max_iter: int, engine,
                  kernel_on: bool, frontier: str = "dense",
                  prefetch: str = "auto"):
    step = _make_step(program, graph, engine, kernel_on, frontier, prefetch)
    state = vcprog.run_loop(step, _init_state(program, graph, engine,
                                              kernel_on), max_iter)
    return _finish(graph, state, engine)


def _bind_lanes(program, lanes):
    """Rebind a BatchedProgram's per-lane attribute values to the traced
    `lanes` operands inside a jitted runner (no-op for plain programs).
    The values are DATA, not part of the compile key — see _ProgramKey."""
    if isinstance(program, vcprog.BatchedProgram) and lanes:
        return program._with_lane_values(lanes)
    return program


@functools.lru_cache(maxsize=64)
def _jitted_runner(engine_name: str, program_key, max_iter: int,
                   kernel_on: bool, frontier: str = "dense",
                   prefetch: str = "auto"):
    from . import pregel, gas, pushpull, callback  # noqa: F401 (registration)
    engine = ENGINES[engine_name]
    program = program_key.program

    @obs.scope(obs.VERTEX)
    def run(graph: DeviceGraph, lanes=()):
        return _run_compiled(_bind_lanes(program, lanes), graph, max_iter,
                             engine, kernel_on, frontier, prefetch)

    # DeviceGraph's static fields (num_vertices/num_edges/...) live in the
    # pytree structure, so jax.jit keys its own cache on graph shape.
    return jax.jit(run)


def _warm_entry_state(program, graph: DeviceGraph, engine, kernel_on: bool,
                      frontier: str, prefetch: str, vprops0, active0):
    """The Algorithm-1 loop carry entering at superstep 2 from a WARM
    fixpoint: `vprops0` (original-id space, base record leaves — [V, Q]
    trailing lane axis for batched programs) and a seed frontier
    `active0` [V] bool.

    The sequential loop's invariant at the top of step k+1 is "`inbox`
    holds what step k's frontier emitted" — a naive warm entry would
    either hit the programs' it==1 special cases or enter with an empty
    inbox and die instantly. So the warm path performs ONE
    emit_and_combine from the seeded frontier first, then enters the loop
    at it=2 with the delivered inbox (exactly the state an uninterrupted
    run would carry if its step-1 frontier had been the seed)."""
    V = graph.num_vertices
    empty = jax.tree.map(jnp.asarray, program.empty_message())
    active0 = jnp.asarray(active0).astype(bool)
    if graph.vertex_perm is not None:
        # device row new_id holds original id vertex_perm[new_id]
        vprops0 = records.tree_gather(vprops0, graph.vertex_perm)
        active0 = jnp.take(active0, graph.vertex_perm, axis=0)
    lanes = None
    if isinstance(program, vcprog.BatchedProgram):
        # a structural delta touches every lane alike: broadcast the seed
        lane_act = jnp.broadcast_to(
            active0[:, None], (V, program.num_lanes)).astype(jnp.int32)
        vprops0 = {"p": vprops0, "_lane_act": lane_act}
        lanes = lane_act > 0
    extra0 = engine.init_extra(graph, program, vprops0, kernel_on)
    front = vcprog.make_frontier(active0, lane_mask=lanes)
    inbox, has_msg, extra = engine.emit_and_combine(
        graph, program, vprops0, front, extra0, empty, kernel_on,
        frontier, prefetch)
    return (jnp.int32(2), vprops0, active0, inbox, has_msg, extra)


@functools.lru_cache(maxsize=64)
def _jitted_warm_runner(engine_name: str, program_key, max_iter: int,
                        kernel_on: bool, frontier: str = "dense",
                        prefetch: str = "auto"):
    """The warm-start twin of `_jitted_runner`:
    run(graph, lanes, vprops0, active0) re-converges from a cached
    fixpoint through the same step function — the serving tier's
    frontier-incremental recompute entry (O(affected region), and for
    monotone monoid programs bit-identical to a from-scratch run)."""
    from . import pregel, gas, pushpull, callback  # noqa: F401 (registration)
    engine = ENGINES[engine_name]
    program = program_key.program

    @obs.scope(obs.VERTEX)
    def run(graph: DeviceGraph, lanes, vprops0, active0):
        prog = _bind_lanes(program, lanes)
        step = _make_step(prog, graph, engine, kernel_on, frontier, prefetch)
        state = vcprog.run_loop(
            step, _warm_entry_state(prog, graph, engine, kernel_on,
                                    frontier, prefetch, vprops0, active0),
            max_iter)
        return _finish(graph, state, engine)

    return jax.jit(run)


@functools.lru_cache(maxsize=64)
def _chunked_runner(engine_name: str, program_key, kernel_on: bool,
                    frontier: str, prefetch: str, guards_on: bool,
                    fault_specs):
    """(init, chunk, finish) jitted triple for host-level rounds of
    supersteps — the resilient path of `run_vcprog`. `chunk(graph, state,
    limit, fault_on)` runs the same per-superstep body as the monolithic
    runner until superstep `limit` (inclusive), convergence, or a tripped
    guard, and returns (state, [NUM_ALARMS] alarm counts); `limit` and
    `fault_on` are traced operands, so chunk boundaries never retrace.
    The superstep sequence is identical to the monolithic loop, so a
    resumed run is bit-identical to an uninterrupted one."""
    from repro.distributed import faults as faults_mod
    from . import pregel, gas, pushpull, callback  # noqa: F401 (registration)
    engine = ENGINES[engine_name]
    program = program_key.program
    vspecs = faults_mod.vprop_faults(fault_specs)

    @obs.scope(obs.VERTEX)
    def init(graph: DeviceGraph, lanes=()):
        return _init_state(_bind_lanes(program, lanes), graph, engine,
                           kernel_on)

    @obs.scope(obs.VERTEX)
    def chunk(graph: DeviceGraph, lanes, state, limit, fault_on):
        step = _make_step(_bind_lanes(program, lanes), graph, engine,
                          kernel_on, frontier, prefetch)

        def cond(s):
            it, _, active, _, has_msg, _, alarms = s
            return ((it <= limit)
                    & (jnp.sum(active) + jnp.sum(has_msg) > 0)
                    & (jnp.sum(alarms) == 0))

        def body(s):
            it, vprops, active, inbox, has_msg, extra, alarms = s
            prev = vprops
            vprops, active, inbox, has_msg, extra = step(
                it, vprops, active, inbox, has_msg, extra)
            if vspecs:
                vprops = faults_mod.poison_vprops(vprops, program, it,
                                                  fault_on, vspecs)
            if guards_on:
                alarms = alarms + faults_mod.guard_alarms(program, prev,
                                                          vprops)
            return (it + 1, vprops, active, inbox, has_msg, extra, alarms)

        out = jax.lax.while_loop(
            cond, body,
            tuple(state) + (jnp.zeros((faults_mod.NUM_ALARMS,), jnp.int32),))
        return out[:-1], out[-1]

    @obs.scope(obs.VERTEX)
    def finish(graph: DeviceGraph, state):
        return _finish(graph, tuple(state), engine)

    return jax.jit(init), jax.jit(chunk), jax.jit(finish)


class _ProgramKey:
    """Hashable wrapper keying the jit cache on program *semantics*
    (class + constructor attributes), so repeated operator calls — which
    build fresh program objects — reuse the compiled runner instead of
    recompiling (a fresh PageRankProgram per call cost ~0.8 s each).

    For a :class:`~repro.core.vcprog.BatchedProgram` the per-lane
    attribute VALUES (the query sources) are deliberately NOT part of the
    key — they ride into the jitted runner as the `lane_values` operands
    and are rebound inside the trace (`_bind_lanes`), so a new source set
    of the same shape reuses the compiled runner instead of re-tracing
    with new baked constants. This is the compile-cache contract the
    serving tier's "second same-shape request pays zero trace+compile"
    gate rests on."""

    def __init__(self, program):
        self.program = program
        self.lane_values = ()
        if isinstance(program, vcprog.BatchedProgram):
            self.lane_values = program.lane_values
            try:
                sig = program.lane_signature
                hash(sig)
                self._key = ("batched",) + sig
            except TypeError:
                self._key = (type(program), id(program))
            return
        try:
            attrs = tuple(sorted(program.__dict__.items()))
            hash(attrs)
            self._key = (type(program), attrs)
        except TypeError:
            self._key = (type(program), id(program))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _ProgramKey) and other._key == self._key


def local_bytes_info() -> dict:
    """The single-device twin of the distributed engine's
    `info["bytes_exchanged"]` model: same key structure, zero bytes —
    there is no wire. Keeping the SHAPE identical is the info-parity
    contract the serving tier reports through (`cache_hit`/`batch_lane`/
    `queue_wait_ms`/`bytes_exchanged` regardless of engine)."""
    from repro.distributed import wire
    return {"per_superstep": 0, "exact_per_superstep": 0,
            "dense_per_superstep": 0,
            "sparse_per_superstep": {c: 0 for c in wire.CODECS},
            "capacity": 0}


def _run_lane_chunked(program, graph, max_iter, *, engine, kernel,
                      use_kernel, reorder, frontier, prefetch, gdev,
                      exchange, overlap, resume, guards, faults,
                      chunk_width: int, warm_start):
    """Split a wide batch into `chunk_width`-lane sub-batches and run
    each through the (shared) compiled runner of that width — lane
    chunking past the `lane_slab_width` sweet spot. Results concatenate
    on the trailing lane axis, bit-identical to the unchunked run (lanes
    never interact)."""
    if gdev is None and engine != "distributed":
        gdev = prepare_device_graph(graph, reorder=reorder)
    outs, infos, lo = [], [], 0
    for sub in program.split(chunk_width):
        hi = lo + sub.num_lanes
        ws = None
        if warm_start is not None:
            wv, wa = warm_start
            ws = (jax.tree.map(lambda a: a[..., lo:hi], wv), wa)
        v, i = run_vcprog(sub, graph, max_iter, engine=engine, kernel=kernel,
                          use_kernel=use_kernel, reorder=reorder,
                          frontier=frontier, prefetch=prefetch,
                          gdev=None if engine == "distributed" else gdev,
                          exchange=exchange, overlap=overlap, resume=resume,
                          guards=guards, faults=faults, warm_start=ws)
        outs.append(v)
        infos.append(i)
        lo = hi
    vprops = records.tree_concat(outs, axis=-1)
    info = dict(infos[0])
    info["iterations"] = max(i["iterations"] for i in infos)
    info["active_at_end"] = sum(i["active_at_end"] for i in infos)
    info["converged"] = all(i["converged"] for i in infos)
    info["batch"] = program.num_lanes
    info["lane_chunks"] = {"width": int(chunk_width), "chunks": len(infos)}
    for key in ("active_edges", "edge_slots"):
        if key in info:
            info[key] = sum(i[key] for i in infos)
    return vprops, info


def _edge_counts(tally, num_edges: int, supersteps: int) -> dict:
    """`info["active_edges"]` (slots whose source was on the frontier,
    summed over the supersteps) and `info["edge_slots"]` (stored slots x
    supersteps), added to the process's `obs` counters; nothing for
    engines that keep no tally."""
    if isinstance(tally, tuple):
        return {}
    from .pushpull import tally_value
    counts = {"active_edges": tally_value(tally),
              "edge_slots": int(num_edges) * int(supersteps)}
    obs.add(obs.ACTIVE_EDGES, counts["active_edges"])
    obs.add(obs.EDGE_SLOTS, counts["edge_slots"])
    return counts


def _run_chunked(program, graph, gdev, pkey, max_iter: int, *, engine,
                 kernel_on, reorder, frontier, prefetch, checkpoint_dir,
                 checkpoint_every, resume, guards_on, fault_specs):
    """The resilient path of `run_vcprog`: host-level rounds of supersteps
    with snapshots, guards and faults. Returns the runner's output and
    its info keys."""
    from repro import checkpoint as ckpt
    from repro.distributed import faults as faults_mod
    if faults_mod.wire_faults(fault_specs):
        raise ValueError(
            "wire faults (flip_bits/drop_delta) need "
            "engine='distributed' — single-device engines have no "
            "delta exchange to corrupt")
    init_j, chunk_j, finish_j = _chunked_runner(
        engine, pkey, kernel_on, frontier, prefetch,
        guards_on, fault_specs)
    state = init_j(gdev, pkey.lane_values)
    mgr = resumed = save_cb = None
    if checkpoint_dir:
        # max_iter deliberately NOT in the fingerprint: a truncated
        # run may resume with a higher budget (the kill→resume tests)
        fp = {"graph": ckpt.graph_signature(graph), "engine": engine,
              "program": ckpt.program_signature(program),
              "reorder": reorder, "kernel": bool(kernel_on),
              "layout": "device", "format": 2}
        mgr = ckpt.CheckpointManager(checkpoint_dir)
        step0 = ckpt.resume_step(mgr, fp, resume)
        if step0 is not None:
            state = mgr.restore(tuple(state), step0)
            resumed = step0

        def save_cb(st, done):
            mgr.save(done, tuple(st), metadata={"fingerprint": fp})

    def chunk(st, limit, f_on):
        return chunk_j(gdev, pkey.lane_values, tuple(st),
                       jnp.int32(limit), jnp.int32(f_on))

    def probe(st):
        it = int(jax.device_get(st[0]))
        live = (int(jnp.sum(jnp.asarray(st[2]))) +
                int(jnp.sum(jnp.asarray(st[4])))) > 0
        return it, live

    state, rinfo = faults_mod.drive_chunks(
        chunk, state, max_iter=max_iter,
        every=int(checkpoint_every or 0), probe=probe, save=save_cb,
        flush=(mgr.wait if mgr is not None else None),
        guards_on=guards_on, faults=fault_specs, degrade=None)
    if mgr is not None:
        mgr.wait()
    return finish_j(gdev, tuple(state)), {"resumed_from": resumed, **rinfo}


@obs.job()
def run_vcprog(program: vcprog.VCProgram, graph: PropertyGraph, max_iter: int,
               engine: str = "pushpull", kernel: str | bool = "auto",
               use_kernel: bool | None = None, reorder: str = "none",
               frontier: str = "dense", prefetch: str = "auto",
               gdev: DeviceGraph | None = None, batch: int | None = None,
               exchange: str = "exact", overlap: bool = True,
               checkpoint_dir: str | None = None, checkpoint_every: int = 0,
               resume: str = "auto", guards: str | bool = "off",
               faults=(), warm_start=None, lane_chunk=None):
    """Execute a VCProg program (paper Algorithm 1). Returns (vprops, info).

    kernel: "auto" (default) picks the fused/segment Pallas kernels on TPU
    and the XLA segment ops on CPU; "on"/"off" force a path. `use_kernel`
    is the legacy boolean alias and wins when given.

    batch: the multi-query axis. `program` may be a SEQUENCE of same-class
    programs (one query lane each), or `batch=Q` replicates one program
    across Q lanes — either way the lanes execute as ONE
    :class:`~repro.core.vcprog.BatchedProgram` whose record leaves carry a
    trailing [Q] lane axis, so every message-plane pass covers all Q
    queries in one O(E) sweep (the packed fused kernel streams the lanes
    as slab columns). Returned vprops leaves are [V, Q]; per-lane values
    are bit-identical to Q sequential runs and `info["batch"] = Q`.

    reorder: "none" (default) | "rcm" | "degree" | "auto" — host-side
    vertex reordering for gather locality (core/reorder.py). Results are
    un-permuted before returning, so any strategy is semantically
    invisible; `gdev`, when given, wins over `reorder` (it was built with
    its own strategy).

    frontier: "dense" (default) | "auto" | "sparse" — the frontier-sparse
    message plane (message_plane.resolve_frontier_mode). "auto" makes
    per-superstep cost track the frontier (block-skip fused kernels +
    active-edge compaction with a dense fallback); every mode is
    bit-identical to "dense".

    prefetch: "auto" (default) | "on" | "off" — the scalar-prefetch
    fused variant (message_plane.resolve_prefetch_mode). "off" pins the
    vprops-resident kernels; for the distributed engine the knob also
    controls the per-bucket window-table build. Bit-identical either way.

    exchange: "exact" (default) | "fp16" | "q8ef" — the wire codec of
    the distributed delta exchange (repro.distributed.wire): bit-packed
    u16/u24 local indices plus fp16 or int8-error-feedback float value
    leaves on the sparse payloads. "exact" is bit-identical; "q8ef" is
    for tolerance-governed operators (PageRank-family). Single-device
    engines have no exchange — the knob is validated and inert there.

    overlap (default True): software-pipeline the distributed schedules
    so the exchange hides behind the bucket plane passes; bit-identical
    on/off and inert for single-device engines.

    warm_start: optional (vprops, active_mask) pair — re-converge from a
    cached FIXPOINT instead of Phase-0 init (the serving tier's
    frontier-incremental recompute). `vprops` is the full vertex record
    in original id space (with the trailing [Q] lane axis when batched),
    `active_mask` a [V] bool seed frontier — e.g. the endpoints an edge
    delta touched (`vcprog.delta_frontier`). The runner emits once from
    the seed and enters the loop at superstep 2 (so it==1 clauses never
    re-fire); for monotone monoid programs re-converging from a valid
    bound (edge ADDS under min-monoids) the result is bit-identical to a
    from-scratch run at O(affected region) cost. Single-device only, and
    does not compose with checkpointing/guards/faults.

    lane_chunk: None (default) | int | "auto" — split a batched run
    wider than this many lanes into sub-batches of at most that width
    ("auto" = graph_device.LANE_CHUNK_DEFAULT), run each through the
    shared compiled runner of its width, and concatenate on the lane
    axis. Hundreds-of-sources requests stay at the packed plane's
    sweet-spot slab width instead of one over-wide launch; bit-identical
    to the unchunked run (lanes never interact) and
    `info["lane_chunks"]` reports the split.

    Resilience (docs/robustness.md): `checkpoint_dir`/`checkpoint_every`
    restructure the loop into host-level rounds of `checkpoint_every`
    supersteps and snapshot the complete loop carry at every boundary
    through `repro.checkpoint.CheckpointManager`; `resume="auto"` picks
    up the latest fingerprint-matching snapshot and the resumed run is
    bit-identical to an uninterrupted one. `guards="on"` arms the NaN/Inf
    and monotonicity watchdogs (and, on the distributed engine, the wire
    checksums) — a tripped guard rolls back to the last committed
    snapshot and replays. `faults=` takes seeded
    `repro.distributed.faults.Fault` specs for deterministic injection
    (tests/CI); `info["converged"]` is False (with a
    NonConvergenceWarning) when the run hits `max_iter` with a
    non-empty frontier.

    This is the single-device path; `repro.core.engines.distributed` provides
    the shard_map multi-device path with identical semantics.
    """
    from repro.distributed import faults as faults_mod, wire
    from ..graph_device import resolve_lane_chunk
    frontier = message_plane.resolve_frontier_mode(frontier)
    prefetch = message_plane.resolve_prefetch_mode(prefetch)
    exchange = wire.resolve_exchange_mode(exchange)
    program = vcprog.as_batched(program, batch)
    chunk_width = resolve_lane_chunk(lane_chunk)
    if (chunk_width and isinstance(program, vcprog.BatchedProgram)
            and program.num_lanes > chunk_width):
        if checkpoint_dir or int(checkpoint_every or 0) > 0:
            raise ValueError(
                "lane_chunk does not compose with checkpointing — "
                "checkpoint the unchunked run instead")
        return _run_lane_chunked(
            program, graph, max_iter, engine=engine, kernel=kernel,
            use_kernel=use_kernel, reorder=reorder, frontier=frontier,
            prefetch=prefetch, gdev=gdev, exchange=exchange,
            overlap=overlap, resume=resume, guards=guards, faults=faults,
            chunk_width=chunk_width, warm_start=warm_start)
    if engine == "distributed":
        if warm_start is not None:
            raise ValueError(
                "warm_start is single-device only — the distributed engine "
                "re-runs cold (its compiled runners are still cached)")
        from . import distributed
        return distributed.run_vcprog_distributed(
            program, graph, max_iter, kernel=kernel, use_kernel=use_kernel,
            reorder=reorder, frontier=frontier, prefetch=prefetch,
            batch=None, exchange=exchange, overlap=overlap,
            checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
            resume=resume, guards=guards, faults=faults)
    guards_on = faults_mod.resolve_guards_mode(guards)
    fault_specs = faults_mod.resolve_faults(faults)
    if gdev is None:
        gdev = prepare_device_graph(graph, reorder=reorder)
    kernel_on = message_plane.resolve_kernel_arg(kernel, use_kernel)
    resilient = (bool(checkpoint_dir) or int(checkpoint_every or 0) > 0
                 or guards_on or bool(fault_specs))
    pkey = _ProgramKey(program)
    base_info = {"engine": engine, "schedule": None, "num_parts": 1,
                 "kernel_on": kernel_on, "reorder": reorder,
                 "frontier": frontier, "prefetch": prefetch,
                 "prefetch_windows": None, "exchange": exchange,
                 "overlap": bool(overlap),
                 "bytes_exchanged": local_bytes_info()}
    with obs.span(obs.RUN):
        if warm_start is not None:
            if resilient:
                raise ValueError(
                    "warm_start does not compose with checkpointing/guards/"
                    "faults — re-converge cold under those, or warm without")
            wv, wa = warm_start
            runner = _jitted_warm_runner(engine, pkey, int(max_iter),
                                         kernel_on, frontier, prefetch)
            out = runner(gdev, pkey.lane_values, wv, wa)
            run_info = {"warm_start": True}
        elif not resilient:
            runner = _jitted_runner(engine, pkey, int(max_iter),
                                    kernel_on, frontier, prefetch)
            out = runner(gdev, pkey.lane_values)
            run_info = {}
        else:
            out, run_info = _run_chunked(
                program, graph, gdev, pkey, int(max_iter), engine=engine,
                kernel_on=kernel_on, reorder=reorder, frontier=frontier,
                prefetch=prefetch, checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every, resume=resume,
                guards_on=guards_on, fault_specs=fault_specs)
        vprops, iters, num_active, tally = out
        info = {**base_info, "iterations": int(iters),
                "active_at_end": int(num_active),
                "converged": bool(int(num_active) == 0), **run_info,
                **_edge_counts(tally, gdev.num_edges, int(iters))}
    if not info["converged"]:
        warnings.warn(
            f"run_vcprog hit max_iter={int(max_iter)} with "
            f"{info['active_at_end']} vertices still active — the result "
            "is truncated, not converged (info['converged'] is False)",
            faults_mod.NonConvergenceWarning, stacklevel=3)
    if isinstance(program, vcprog.BatchedProgram):
        # un-wrap the lane axis: the user sees the base record with [V, Q]
        # leaves (the `_lane_act` bookkeeping column stays internal)
        vprops = vprops["p"]
        info["batch"] = program.num_lanes
    return vprops, info


def compiled_runner(program, engine: str = "pushpull", max_iter: int = 100,
                    kernel: str | bool = "auto",
                    use_kernel: bool | None = None,
                    frontier: str = "dense", prefetch: str = "auto",
                    warm: bool = False, batch: int | None = None):
    """The serving tier's cache value: the jitted Algorithm-1 runner for
    this (program class, engine, knob) combination, plus the program's
    lane-value operands.

    Returns (runner, lane_values):
      * cold (warm=False):  runner(gdev, lane_values)
      * warm (warm=True):   runner(gdev, lane_values, vprops0, active0)
    both yielding the raw (vprops, final_iterations, num_active, tally)
    tuple (tally: the engine's active-edge count, `()` where it keeps
    none) — batched programs return the WRAPPED record (caller unwraps
    ["p"]).
    The runner is the same object `run_vcprog` would use (one shared
    lru_cache), so holding it in a serving cache and calling it directly
    skips every per-request resolution/dispatch layer while staying
    bit-identical to the full path."""
    program = vcprog.as_batched(program, batch)
    frontier = message_plane.resolve_frontier_mode(frontier)
    prefetch = message_plane.resolve_prefetch_mode(prefetch)
    kernel_on = message_plane.resolve_kernel_arg(kernel, use_kernel)
    pkey = _ProgramKey(program)
    make = _jitted_warm_runner if warm else _jitted_runner
    return (make(engine, pkey, int(max_iter), kernel_on, frontier, prefetch),
            pkey.lane_values)


# Registered by the engine modules at import time (see package __init__).
ENGINES: Dict[str, Any] = {}


def register(name: str):
    def deco(cls):
        ENGINES[name] = cls()
        cls.name = name
        return cls
    return deco
