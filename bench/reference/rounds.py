"""Synchronous Bellman-Ford rounds of plain SSSP, in jax.numpy.

Round k relaxes the out-edges of the vertices that changed in round k - 1
(round 1: the root) and folds the messages by a min per target. The
rounds give two things:

* the plane's minimum HBM traffic (`plane_bytes`): per round, the out-edge
  slots of the frontier times (4 B neighbour id + 4 B per edge property +
  4 B source value), plus |V| x state bytes x 2 (read and write the
  state). No arithmetic bound applies: each slot costs one add and one
  min against at least 12 bytes moved.
* the control: SSSP summed in bfloat16, the precision below the
  configuration's float32.

Nothing here imports the system under test.
"""
from __future__ import annotations

import functools

import numpy as np

MAX_ROUNDS = 512


@functools.lru_cache(maxsize=None)
def _rounds_fn(num_vertices: int, dtype: str):
    import jax
    import jax.numpy as jnp

    V = num_vertices
    dt = jnp.dtype(dtype)
    big = jnp.array(jnp.inf, dt)

    def run(src, dst, w, root):
        outdeg = jnp.zeros(V, jnp.int32).at[src].add(1)
        state = jnp.full(V, big, dt).at[root].set(0)
        front = jnp.zeros(V, bool).at[root].set(True)
        w = w.astype(dt)
        slots = jnp.zeros(MAX_ROUNDS, jnp.int32)

        def cond(c):
            _, front, k, _ = c
            return jnp.any(front) & (k < MAX_ROUNDS)

        def body(c):
            state, front, k, slots = c
            slots = slots.at[k].set(jnp.sum(jnp.where(front, outdeg, 0)))
            val = jnp.where(front[src], state[src] + w, big)
            new = jnp.minimum(state, jax.ops.segment_min(val, dst,
                                                         num_segments=V))
            return new, new < state, k + 1, slots

        state, _, k, slots = jax.lax.while_loop(
            cond, body, (state, front, jnp.int32(0), slots))
        return state, k, slots

    return jax.jit(run)


def run_rounds(num_vertices: int, src, dst, w, *, root: int = 0,
               dtype: str = "float32"):
    """(final distances as numpy, rounds with a non-empty frontier,
    [rounds] out-edge slots of each round's frontier). `src, dst, w` are
    the generated (one direction) edges; both directions are relaxed."""
    import jax.numpy as jnp
    s, d = jnp.asarray(src), jnp.asarray(dst)
    both_s, both_d = jnp.concatenate([s, d]), jnp.concatenate([d, s])
    ww = jnp.concatenate([jnp.asarray(w)] * 2)
    out, k, slots = _rounds_fn(int(num_vertices), dtype)(
        both_s, both_d, ww, jnp.int32(root))
    k = int(k)
    return np.asarray(out), k, np.asarray(slots)[:k].astype(np.int64)


def plane_bytes(num_vertices: int, slots_per_round, *, edge_props: int,
                state_bytes: int = 4) -> int:
    """Minimum bytes the rounds move: see the module docstring."""
    per_slot = 4 + 4 * edge_props + 4
    rounds = len(slots_per_round)
    return int(np.sum(slots_per_round) * per_slot
               + rounds * num_vertices * state_bytes * 2)
