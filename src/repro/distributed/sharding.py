"""Logical-axis sharding rules with divisibility-aware fallback.

Model code names array dims with *logical* axes ("vocab", "mlp", "batch",
…); this module resolves them to mesh PartitionSpecs. Resolution walks the
rule's candidate list and picks the first candidate whose mesh-axis product
divides the dim size — so starcoder2's 36 heads fall back off a 16-way
'model' axis, granite's 49155 vocab falls back off TP, and batch=1
(long_500k) falls back to replicated, all automatically and logged.

Two rule tables: PARAM_RULES (weights; includes the FSDP 'embed'→data rule)
and ACT_RULES (activations / caches / inputs).
"""
from __future__ import annotations

import contextlib
import logging
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

log = logging.getLogger("repro.sharding")


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """`jax.shard_map` with the engines' default of no replication check."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)

Candidate = Tuple[str, ...]          # mesh axes fused for one dim
RuleTable = Dict[str, Sequence[Candidate]]

# Weights. 'embed' on a param is the FSDP axis (gathered at use by SPMD);
# 'mlp'/'heads'/'vocab'/'experts' are the TP/EP axes.
PARAM_RULES: RuleTable = {
    "vocab": [("model",), ()],
    "embed": [("data",), ()],              # FSDP / ZeRO-3
    "heads": [("model",), ()],
    "kv_heads": [("model",), ()],
    "head_dim": [()],
    "mlp": [("model",), ()],
    "experts": [("model",), ()],           # EP
    "expert_mlp": [()],                    # within-expert width under EP
    "rnn": [("model",), ()],
    "conv": [()],
    "layers": [()],                        # scan-stacked dim, never sharded
    None: [()],
}

# Activations / inputs / caches.
ACT_RULES: RuleTable = {
    "batch": [("pod", "data"), ("data",), ()],
    # sequence parallelism over the TP axis: activations shard on seq, and
    # XLA all-gathers k/v per attention layer (Megatron-SP). This is the
    # general fallback that keeps score tensors sharded even when the head
    # count (36, 40, 24…) does not divide the 16-way model axis.
    "seq": [("model",), ()],
    "act_embed": [()],
    "act_heads": [("model",), ()],
    "act_kv_heads": [("model",), ()],
    "act_mlp": [("model",), ()],
    "act_experts": [("model",), ()],
    "cache_seq": [("model",), ()],          # sequence-sharded KV cache
    "act_vocab": [("model",), ()],
    None: [()],
}


# Per-arch activation profiles (§Perf levers):
#   default  sequence parallelism over the TP axis (general fallback)
#   dp       pure data parallelism: batch shards over EVERY mesh axis
#            (1 seq/device at 4k×256), seq unsharded — no per-layer
#            activation collectives. Right for recurrent archs whose
#            time-scans break under a sharded seq axis (xlstm).
def rules_for_profile(profile: str) -> RuleTable:
    if profile == "dp":
        rules = dict(ACT_RULES)
        rules["batch"] = [("pod", "data", "model"), ("data", "model"),
                          ("pod", "data"), ("data",), ()]
        rules["seq"] = [()]
        return rules
    return ACT_RULES


def _mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def resolve_dim(logical: Optional[str], size: int, mesh: Mesh,
                rules: RuleTable, taken: set):
    """First divisible candidate whose axes exist in the mesh and are not
    already used by another dim of the same array."""
    sizes = _mesh_axis_sizes(mesh)
    for cand in rules.get(logical, [()]):
        axes = tuple(a for a in cand if a in sizes)
        if not axes:
            if cand == () or cand is None:
                return None
            continue
        if any(a in taken for a in axes):
            continue
        prod = 1
        for a in axes:
            prod *= sizes[a]
        if size % prod == 0:
            taken.update(axes)
            return axes if len(axes) > 1 else axes[0]
    return None


def spec_for(logical_axes: Sequence[Optional[str]], shape: Sequence[int],
             mesh: Mesh, rules: RuleTable) -> PartitionSpec:
    taken: set = set()
    entries = [resolve_dim(l, s, mesh, rules, taken)
               for l, s in zip(logical_axes, shape)]
    fell_back = [l for l, e in zip(logical_axes, entries)
                 if l is not None and rules.get(l, [()])[0] != () and e is None]
    if fell_back:
        log.debug("sharding fallback to replicated for logical axes %s "
                  "(shape %s)", fell_back, tuple(shape))
    return PartitionSpec(*entries)


def param_spec(logical_axes, shape, mesh) -> PartitionSpec:
    return spec_for(logical_axes, shape, mesh, PARAM_RULES)


def act_spec(logical_axes, shape, mesh) -> PartitionSpec:
    return spec_for(logical_axes, shape, mesh, ACT_RULES)


def tree_param_specs(spec_tree, shape_tree, mesh):
    """Resolve a pytree of logical-axis tuples against a matching pytree of
    shapes -> pytree of PartitionSpec."""
    return jax.tree.map(
        lambda axes, shp: param_spec(axes, shp, mesh),
        spec_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


# ---------------------------------------------------------------------------
# Mesh context: model code calls logical_constraint() without knowing meshes
# ---------------------------------------------------------------------------

_CTX = threading.local()


@contextlib.contextmanager
def mesh_rules(mesh: Mesh, act_rules: RuleTable = None):
    prev = getattr(_CTX, "state", None)
    _CTX.state = (mesh, act_rules or ACT_RULES)
    try:
        yield
    finally:
        _CTX.state = prev


def logical_constraint(x, *logical_axes):
    """with_sharding_constraint by logical names; no-op outside mesh_rules
    (keeps single-device smoke tests mesh-free)."""
    state = getattr(_CTX, "state", None)
    if state is None:
        return x
    mesh, rules = state
    spec = spec_for(logical_axes, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))
