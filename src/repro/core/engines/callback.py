"""Execution-environment-isolation engine (paper §IV-C analogue).

The paper lets JVM/C++ engines call Python UDFs through an IPC client/server
pair; every UDF invocation crosses a process boundary. The TPU analogue of
that boundary is the host↔device hop: this engine executes the user's
VCProg methods ON THE HOST via `jax.pure_callback`, from inside the
compiled iteration loop. Each iteration pays (a) device→host transfer of
operands, (b) host-side eager execution of the UDF batch, (c) host→device
transfer of results — the cost structure of the paper's IPC mechanism
(batched per phase rather than per call; see DESIGN.md §2).

The whole canonical EdgeLayout — endpoints, edge properties AND the
precomputed SegmentMeta — rides through the `pure_callback` operand list
(EdgeLayout is a registered pytree), so the host-side combine reuses the
static segment structure instead of re-deriving it with `searchsorted`
every iteration, exactly like the compiled engines.

The paper's *zero-copy* optimization corresponds to the other engines,
where the UDFs are traced into XLA and the boundary disappears entirely.
`benchmarks/bench_ipc.py` reproduces Fig. 8d with this pair.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import message_plane, records, vcprog
from .common import register


def _as_shapes(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _lane_operands(program):
    """The per-lane attribute arrays of a bound BatchedProgram, to ride the
    `pure_callback` operand list. Inside a jitted runner these are TRACERS
    (`common._bind_lanes` rebinds lane values to the jit's lane operands);
    the host closure must not capture them — it outlives the trace."""
    if isinstance(program, vcprog.BatchedProgram):
        return program.lane_values
    return ()


def _no_tracer(tree, what: str):
    """Host-side guard for the PR-1 bug class (lint rule UL203): a value
    reaching eager host execution must be concrete. A leaked jit-scope
    tracer here would either crash deep inside numpy with an opaque
    TracerArrayConversionError or silently pin stale constants — fail
    fast with the lint rule's name instead."""
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.core.Tracer):
            raise RuntimeError(
                f"UL203 callback-captures-traced-value: {what} reached "
                f"the host callback as a jit-scope tracer ({leaf!r}). "
                f"Traced values must ride the pure_callback operand "
                f"list and be rebound host-side — run `python -m "
                f"repro.lint` on the program (docs/linting.md#ul203).")


def _on_host_cpu(fn):
    """Run a host callback's eager jax ops on the host CPU device. The
    enclosing executable holds the accelerator while it waits on the
    callback, so eager ops dispatched to the default (accelerator)
    device from inside it would wait on it in turn."""
    @functools.wraps(fn)
    def wrapped(*args):
        with jax.default_device(jax.devices("cpu")[0]):
            return fn(*args)
    return wrapped


def _host_program(program, lane_vals):
    """Rebind the concrete lane values delivered to the host callback."""
    if lane_vals:
        _no_tracer(lane_vals, "a per-lane attribute value")
        return program._with_lane_values(
            tuple(jnp.asarray(v) for v in lane_vals))
    return program


@register("callback")
class CallbackEngine:
    def init_extra(self, graph, program, vprops0, kernel_on):
        return ()

    # Phase 2 on the host --------------------------------------------------
    def compute_phase(self, graph, program, vprops, inbox, process_mask, it):
        # a BatchedProgram bound inside a jitted runner carries TRACED
        # per-lane attribute values (`common._bind_lanes`); the host
        # closure outlives the trace, so those must ride the operand list
        # and be rebound host-side, never captured
        lanes = _lane_operands(program)

        @_on_host_cpu
        def host(vp, ib, mask, it_, *lane_vals):
            prog = _host_program(program, lane_vals)
            new_props, is_active = jax.vmap(
                prog.vertex_compute, in_axes=(0, 0, None))(vp, ib, int(it_))
            vp2 = records.tree_where(jnp.asarray(mask), new_props, vp)
            act = jnp.asarray(mask) & jnp.asarray(is_active).astype(bool)
            return jax.tree.map(np.asarray, (vp2, act))

        out_shapes = (_as_shapes(vprops),
                      jax.ShapeDtypeStruct(process_mask.shape, jnp.bool_))
        vprops, active = jax.pure_callback(
            host, out_shapes, vprops, inbox, process_mask, it, *lanes)
        return vprops, active

    # Phase 3 + Phase 1 on the host ----------------------------------------
    def emit_and_combine(self, graph, program, vprops, active, extra, empty,
                         kernel_on, frontier="dense", prefetch="auto"):
        V = graph.num_vertices
        # strip the nested canonical alias so the operand list stays flat;
        # prefetch metadata goes with it (the host-side eager plane is the
        # paper's IPC analogue, not a kernel path — `prefetch` is resolved
        # for validation but the stripped layout always runs resident)
        message_plane.resolve_prefetch_mode(prefetch)
        layout = dataclasses.replace(graph.canonical, canonical=None,
                                     prefetch_blocks=None, prefetch_window=0)

        lanes = _lane_operands(program)

        @_on_host_cpu
        def host(vp, act, lo, *lane_vals):
            prog = _host_program(program, lane_vals)
            lo = jax.tree.map(jnp.asarray, lo)
            vp = jax.tree.map(jnp.asarray, vp)
            # rebuild the empty record host-side: the traced `empty` closure
            # is a jit-scope tracer and must not leak into eager execution
            empty_h = prog.empty_message()
            _no_tracer(empty_h, "the program's empty_message() record")
            empty_h = jax.tree.map(jnp.asarray, empty_h)
            inbox, has_msg = message_plane.emit_and_combine(
                prog, lo, vp, jnp.asarray(act), empty_h, kernel_on=False,
                frontier=frontier)
            return jax.tree.map(np.asarray, (inbox, has_msg))

        inbox_shape = _as_shapes(records.tree_tile(empty, V))
        out_shapes = (inbox_shape, jax.ShapeDtypeStruct((V,), jnp.bool_))
        inbox, has_msg = jax.pure_callback(
            host, out_shapes, vprops, vcprog.frontier_mask(active), layout,
            *lanes)
        return inbox, has_msg, extra
