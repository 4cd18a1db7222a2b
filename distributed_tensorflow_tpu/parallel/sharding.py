"""Partition-rule machinery: regex path -> PartitionSpec for param pytrees.

The declarative replacement for the reference's ``replica_device_setter``
(reference example.py:133-141): instead of pinning variables to PS tasks, a
rule table maps parameter *paths* to ``PartitionSpec``s over named mesh axes.
One rule set covers every mesh size because absent axes have size 1.

Conventions (scaling-book recipe):
  * ``tensor`` shards hidden/head dims (megatron-style: column-parallel
    first matmul, row-parallel second);
  * ``fsdp`` optionally shards the remaining large dim of each matrix
    (zero-3 style) — applied via ``fsdp_rules``;
  * everything unmatched is replicated (P()).
"""
from __future__ import annotations

import re
from typing import Any, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import BATCH_AXES, data_shards

__all__ = ["PartitionRules", "tree_paths", "shard_pytree",
           "logical_to_mesh", "prune_spec", "constrain_batch"]

Rules = Sequence[Tuple[str, P]]


def prune_spec(spec: P, mesh: Mesh) -> P:
    """Drop axis names the mesh doesn't have (-> replicated on that dim).

    Lets ONE rule table serve every mesh: a spec like
    ``P(None, 'fsdp', 'tensor')`` on a data-only mesh simply degrades to
    ``P(None, None, None)``.
    """
    names = set(mesh.axis_names)

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, str):
            return entry if entry in names else None
        kept = tuple(a for a in entry if a in names)
        return kept if kept else None

    return P(*(keep(e) for e in spec))


def constrain_batch(x, mesh: Optional[Mesh], seq_axis: Optional[str] = None):
    """Pin activation ``x`` ``[batch, seq, ...]`` to batch-sharded over the
    mesh's data-parallel axes, every other dim replicated (``seq`` over
    ``seq_axis`` where the model runs ring attention).

    Parameters stored ``fsdp``-sharded on a contracting dim leave GSPMD two
    consistent layouts for an activation: batch on ``fsdp`` (from the input)
    or hidden on ``fsdp`` (from the weight).  Unpinned it picks the second:
    the batch is replicated, every chip runs all of attention, and matmul
    outputs are all-reduced at whole-batch size.  Pinned, the only
    resolution left is ZeRO-3: all-gather each layer's weights at use,
    reduce-scatter their gradients, each chip computing its own sequences.

    Follows from what it can observe, no knob: axes the mesh lacks or holds
    at size 1 drop out, so without a batch axis > 1 (or without a mesh) this
    is the identity and the traced program is unchanged; a batch the axes do
    not divide (a 2-sequence eval on four chips) is left to propagation.
    """
    if mesh is None:
        return x
    batch = tuple(a for a in BATCH_AXES if mesh.shape.get(a, 1) > 1)
    if not batch or x.shape[0] % data_shards(mesh):
        return x
    seq = None
    if (seq_axis is not None and mesh.shape.get(seq_axis, 1) > 1
            and x.shape[1] % mesh.shape[seq_axis] == 0):
        seq = seq_axis
    spec = P(batch, seq, *([None] * (x.ndim - 2)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def tree_paths(tree) -> List[str]:
    """'/'-joined dict-key paths for every leaf, in flatten order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, _leaf in flat:
        parts = []
        for entry in path:
            if hasattr(entry, "key"):
                parts.append(str(entry.key))
            elif hasattr(entry, "idx"):
                parts.append(str(entry.idx))
            else:
                parts.append(str(entry))
        out.append("/".join(parts))
    return out


class PartitionRules:
    """Ordered (regex, PartitionSpec) table; first match wins."""

    def __init__(self, rules: Rules):
        self.rules = [(re.compile(pat), spec) for pat, spec in rules]

    def spec_for(self, path: str) -> P:
        for pat, spec in self.rules:
            if pat.search(path):
                return spec
        return P()

    def tree_specs(self, params) -> Any:
        """Same-structure pytree of PartitionSpecs."""
        flat, treedef = jax.tree_util.tree_flatten(params)
        paths = tree_paths(params)
        return jax.tree_util.tree_unflatten(
            treedef, [self.spec_for(p) for p in paths])

    def tree_shardings(self, mesh: Mesh, params) -> Any:
        return jax.tree.map(
            lambda spec: NamedSharding(mesh, prune_spec(spec, mesh)),
            self.tree_specs(params),
            is_leaf=lambda v: isinstance(v, P))


def shard_pytree(params, mesh: Mesh, rules: PartitionRules):
    """device_put a param pytree according to the rule table."""
    return jax.device_put(params, rules.tree_shardings(mesh, params))


def logical_to_mesh(specs, mesh: Mesh):
    """PartitionSpec pytree -> NamedSharding pytree on ``mesh``."""
    return jax.tree.map(lambda spec: NamedSharding(mesh, spec), specs,
                        is_leaf=lambda v: isinstance(v, P))
