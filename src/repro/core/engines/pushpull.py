"""Push-Pull adaptive engine (paper Fig. 4c — Gemini style).

Gemini switches between a sparse *push* mode (iterate out-edges of the
active frontier) and a dense *pull* mode (iterate in-edges of every vertex)
based on frontier density. The dense/sparse duality survives on TPU as a
schedule choice under `lax.cond` over WHICH EdgeLayout the message plane
receives:

  sparse/push: the src-sorted (out-edge) layout — the Pregel dataflow
               (emit in out-edge order, permute, combine)
  dense/pull : the canonical (in-edge) layout —
               "DENSESIGNAL(v, inEdgeIterator)" — no permute; fused-kernel
               eligible.

Heuristic (Gemini): push when `sum(out_degree[active]) < |E| / alpha`.

The loop carry (`extra`) tallies that sum over the supersteps: the edge
slots whose source was on the frontier, i.e. the useful part of the
slots the plane streams (`info["active_edges"]`, counter
`obs.ACTIVE_EDGES`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import message_plane, vcprog
from .common import register

#: the tally is [lo, hi] int32 with count = hi * 2**LOW_BITS + lo, so it
#: stays exact past 2**31 (100 supersteps of 2**31 slots)
LOW_BITS = 30


def _tally(carry, n):
    """carry + n for a non-negative int32 n; lo stays below 2**LOW_BITS,
    so lo + n fits uint32."""
    s = carry[0].astype(jnp.uint32) + n.astype(jnp.uint32)
    lo = (s & ((1 << LOW_BITS) - 1)).astype(jnp.int32)
    return jnp.stack([lo, carry[1] + (s >> LOW_BITS).astype(jnp.int32)])


def tally_value(carry) -> int:
    """The tally as a Python int (syncs with the device)."""
    lo, hi = (int(x) for x in jax.device_get(carry))
    return (hi << LOW_BITS) | lo


@register("pushpull")
class PushPullEngine:
    alpha: float = 20.0

    def init_extra(self, graph, program, vprops0, kernel_on):
        return jnp.zeros((2,), jnp.int32)

    def active_edge_tally(self, extra):
        return extra

    def emit_and_combine(self, graph, program, vprops, active, extra, empty,
                         kernel_on, frontier="dense", prefetch="auto"):
        mask = vcprog.frontier_mask(active)
        active_out_edges = jnp.sum(jnp.where(mask, graph.out_degree, 0))
        use_push = active_out_edges < (graph.num_edges / self.alpha)

        def push(_):
            return message_plane.emit_and_combine(
                program, graph.src_sorted, vprops, active, empty,
                kernel_on=kernel_on, frontier=frontier, prefetch=prefetch)

        def pull(_):
            return message_plane.emit_and_combine(
                program, graph.canonical, vprops, active, empty,
                kernel_on=kernel_on, frontier=frontier, prefetch=prefetch)

        inbox, has_msg = jax.lax.cond(use_push, push, pull, operand=None)
        return inbox, has_msg, _tally(extra, active_out_edges)
