"""Graph500 Kronecker (RMAT) edge lists, generated on the device from a seed.

The recurrence is the Graph500 one (and `repro.core.io.rmat_graph`'s): for
each of `scale` bits, an edge picks the lower or upper half of the source
range with probability (a + b) : (c + d), then the lower or upper half of
the target range with a : b in the lower row and c : d in the upper one.
Self loops are dropped, parallel edges kept, weights are float32 uniform in
[weight_min, weight_max) (Graph500 kernel 3: [0, 1)).

A configuration with a `structure_seed` draws the edges and weights from
that fixed seed, and the run's seed draws the Graph500 vertex-label
permutation and the order of the edge list: every seed then gets the
same graph up to its labels, so the same work, in another order. Without
one, the run's seed draws the edges and labels keep the RMAT skew.

The device makes every random number in one jitted call; the host only
drops the self loops. The same seeds give the same edge list on any
backend (threefry is bit-exact across platforms).
"""
from __future__ import annotations

import functools

import numpy as np


def seed_key(seed: int):
    """A PRNG key from a seed of any size (the low and high 32 bits are
    both used, so seeds past 2**32 stay distinct)."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _rmat_fn(scale: int, num_edges: int, a: float, b: float, c: float,
             w_min: float, w_max: float, relabel: bool):
    import jax
    import jax.numpy as jnp

    row_lo = a + b                  # P(source bit = 0)
    col_lo_top = a / (a + b)        # P(target bit = 0 | source bit = 0)
    col_lo_bot = c / (1.0 - a - b)  # P(target bit = 0 | source bit = 1)

    def gen(key, label_key):
        def bit(i, carry):
            src, dst = carry
            u = jax.random.uniform(jax.random.fold_in(key, i),
                                   (2, num_edges), jnp.float32)
            down = u[0] > row_lo
            right = u[1] > jnp.where(down, col_lo_bot, col_lo_top)
            src = src | (down.astype(jnp.int32) << i)
            dst = dst | (right.astype(jnp.int32) << i)
            return src, dst

        zeros = jnp.zeros((num_edges,), jnp.int32)
        src, dst = jax.lax.fori_loop(0, scale, bit, (zeros, zeros))
        w = jax.random.uniform(jax.random.fold_in(key, scale), (num_edges,),
                               jnp.float32, w_min, w_max)
        if relabel:
            labels = jax.random.permutation(jax.random.fold_in(label_key, 0),
                                            1 << scale).astype(jnp.int32)
            order = jax.random.permutation(jax.random.fold_in(label_key, 1),
                                           num_edges)
            src, dst, w = labels[src[order]], labels[dst[order]], w[order]
        return src, dst, w

    return jax.jit(gen)


def rmat_edges(scale: int, edge_factor: int, a: float, b: float, c: float,
               seed: int, w_min: float = 0.0, w_max: float = 1.0,
               label_seed: int | None = None):
    """(src, dst, weight) host arrays of the generated edges, self loops
    dropped: int32, int32, float32 in [w_min, w_max). With `label_seed`,
    vertex labels and edge order are permuted from that seed."""
    if scale > 30:
        raise ValueError(f"scale {scale} does not fit int32 vertex ids")
    fn = _rmat_fn(int(scale), int(edge_factor) << int(scale), float(a),
                  float(b), float(c), float(w_min), float(w_max),
                  label_seed is not None)
    label_key = seed_key(0 if label_seed is None else label_seed)
    src, dst, w = (np.asarray(x) for x in fn(seed_key(seed), label_key))
    keep = src != dst
    return src[keep], dst[keep], w[keep]


def generate(config: dict, seed: int):
    """The edge list a configuration file describes, for a run's seed."""
    if config["generator"] != "rmat":
        raise ValueError(f"unknown generator {config['generator']!r}")
    fixed = config.get("structure_seed")
    return rmat_edges(config["scale"], config["edge_factor"], config["a"],
                      config["b"], config["c"],
                      seed if fixed is None else fixed,
                      config["weight_min"], config["weight_max"],
                      label_seed=None if fixed is None else seed)
