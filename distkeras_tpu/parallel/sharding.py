"""Logical-axis → mesh-axis sharding rules (GSPMD).

One model definition (annotated with ``nn.with_logical_partitioning``,
see :mod:`distkeras_tpu.models.bert`) maps onto any mesh by resolving its
logical axes against these rules — the "pick a mesh, annotate shardings,
let XLA insert collectives" recipe. The reference has no analogue: its only
notion of placement is "which Spark partition" (SURVEY §2 parallelism table:
TP/SP absent from dist-keras; provided here because BASELINE config #5
requires data+model sharding).
"""

from __future__ import annotations

import jax
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "DEFAULT_RULES",
    "kv_pytree_shardings",
    "logical_axis_rules",
    "infer_variable_shardings",
    "replicated",
]

# logical axis -> mesh axis (None = replicate)
DEFAULT_RULES: tuple[tuple[str, str | None], ...] = (
    ("batch", "dp"),
    ("seq", "sp"),
    ("embed", None),     # keep the residual stream replicated
    ("heads", "tp"),
    ("mlp", "tp"),
    ("kv", None),
    ("vocab", "tp"),
    ("expert", "ep"),
)


def logical_axis_rules(mesh: Mesh, overrides=None):
    """Filter DEFAULT_RULES down to axes the mesh actually has."""
    rules = []
    seen = set()
    for logical, phys in tuple(overrides or ()) + DEFAULT_RULES:
        if logical in seen:
            continue
        seen.add(logical)
        rules.append((logical, phys if phys in mesh.axis_names else None))
    return tuple(rules)


def infer_variable_shardings(mesh: Mesh, abstract_variables, overrides=None):
    """Resolve a variables PyTree (possibly containing
    ``nn.Partitioned`` leaves from logical annotations) to NamedShardings.

    Un-annotated leaves are replicated. Returns a PyTree of NamedSharding
    matching the *unboxed* variables structure.
    """
    rules = logical_axis_rules(mesh, overrides)
    logical_specs = nn.get_partition_spec(abstract_variables)
    mesh_specs = nn.logical_to_mesh(logical_specs, rules)
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        mesh_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def kv_pytree_shardings(mesh: Mesh, tree, axis: str = "tp"):
    """Shardings for a decode KV cache/pool pytree: every K/V leaf is
    sharded over its **heads** dimension, everything else replicated.

    The rule is shape-driven because cache variables carry no logical-
    axis annotations (they are created with plain ``self.variable``).
    4-D leaves — dense per-slot caches ``[B, L, H, D]``, single prefill
    rows ``[1, L, H, D]`` and the dense prefix cache's blocks
    ``[C, block_tokens, H, D]`` — keep heads at axis ``-2``. 3-D leaves
    are paged block pools ``[C, block_tokens, H*D]``, heads folded into
    the last axis, where a contiguous split over the mesh axis is the
    same partition of heads. A leaf shards only when that dimension
    divides the mesh axis (the engine refuses a head count its ``tp``
    does not divide before it builds a pool). 1-D index leaves
    (cache/pos counters) and anything else stay replicated host-ish
    metadata, mirroring the serving engine's stance that block tables
    and slot state are replicated while only the KV bytes shard.
    ``tree`` may hold concrete arrays or ``eval_shape`` structs."""
    n = mesh.shape.get(axis, 1)

    def rule(leaf):
        shape = getattr(leaf, "shape", ())
        heads_dim = -1 if len(shape) == 3 else -2
        if (axis in mesh.axis_names and n > 1 and len(shape) >= 3
                and shape[heads_dim] % n == 0):
            spec = [None] * len(shape)
            spec[heads_dim] = axis
            return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    return jax.tree.map(rule, tree)


def unbox(variables):
    """Strip ``nn.Partitioned`` boxes, leaving raw arrays."""
    return nn.meta.unbox(variables)
