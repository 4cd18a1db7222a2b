"""Device mesh construction — the declarative replacement for device pinning.

The reference pins variables to PS tasks and compute to the local worker via
``tf.train.replica_device_setter`` (reference example.py:133-141).  On TPU,
placement is a *sharding* over a named ``jax.sharding.Mesh``; XLA inserts the
ICI collectives implied by the shardings (SURVEY.md §7 translation table).

Canonical axis names used across the framework:

  ``data``     data parallelism (batch dim)           — ref's only strategy
  ``fsdp``     parameter-sharded data parallelism
  ``tensor``   tensor/model parallelism (hidden dims)
  ``seq``      sequence/context parallelism (ring attention)
  ``pipe``     pipeline stage axis
  ``expert``   expert (MoE) axis

Axes the caller does not ask for simply have size 1, so a PartitionSpec that
mentions them is still valid — this keeps one set of sharding rules working
from a single chip up to a multi-pod mesh.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["MeshConfig", "make_mesh", "data_parallel_mesh", "AXIS_ORDER",
           "BATCH_AXES", "named_sharding", "replicated", "local_batch_size"]

# Fixed major-to-minor order: pipe outermost (cross-slice / DCN friendly),
# then the data-like axes, with tensor parallelism innermost so it rides the
# fastest ICI links (scaling-book recipe: TP wants the tightest torus links).
AXIS_ORDER: Sequence[str] = ("pipe", "data", "fsdp", "expert", "seq", "tensor")

# The data-parallel axes: both divide the batch.  ``fsdp`` is a BATCH axis
# for activations and a storage axis for parameters (ZeRO-3).
BATCH_AXES: Sequence[str] = ("data", "fsdp")


class MeshConfig(dict):
    """{axis_name: size} with validation against the device count."""

    def total(self) -> int:
        return math.prod(self.values()) if self else 1


def make_mesh(axes: Optional[Dict[str, int]] = None,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a Mesh. Unspecified => all devices on the ``data`` axis.

    ``axes`` may leave exactly one axis as ``-1`` to absorb the remaining
    devices (like a reshape).
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)

    if not axes:
        axes = {"data": n}
    axes = dict(axes)

    wildcard = [k for k, v in axes.items() if v == -1]
    if len(wildcard) > 1:
        raise ValueError("at most one mesh axis may be -1")
    if wildcard:
        known = math.prod(v for v in axes.values() if v != -1)
        if n % known:
            raise ValueError(f"{n} devices not divisible by {known}")
        axes[wildcard[0]] = n // known

    size = math.prod(axes.values())
    if size != n:
        raise ValueError(
            f"mesh axes {axes} require {size} devices, have {n}")

    unknown = set(axes) - set(AXIS_ORDER)
    if unknown:
        raise ValueError(f"unknown mesh axes {unknown}; use {AXIS_ORDER}")

    names = tuple(a for a in AXIS_ORDER if a in axes)
    shape = tuple(axes[a] for a in names)
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, names)


def data_parallel_mesh(devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """All devices on one ``data`` axis — the reference-parity topology."""
    return make_mesh(None, devices)


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    """Shorthand: ``named_sharding(mesh, 'data', None)``."""
    return NamedSharding(mesh, PartitionSpec(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def data_shards(mesh: Mesh, axes: Sequence[str] = BATCH_AXES) -> int:
    """Number of ways the batch dim is split on this mesh."""
    shard = 1
    for a in axes:
        if a in mesh.shape:
            shard *= mesh.shape[a]
    return shard


def round_batch_to_mesh(global_batch: int, mesh: Mesh,
                        axes: Sequence[str] = BATCH_AXES) -> int:
    """Smallest batch >= global_batch divisible by the mesh's data shards.

    The reference's batch of 50 (example.py:13) does not shard over 8 chips;
    callers round up (56) rather than silently dropping devices.
    """
    shard = data_shards(mesh, axes)
    return -(-global_batch // shard) * shard


def local_batch_size(global_batch: int, mesh: Mesh,
                     axes: Sequence[str] = BATCH_AXES) -> int:
    """Per-process batch share for building host-local input pipelines."""
    shard = data_shards(mesh, axes)
    if global_batch % shard:
        raise ValueError(
            f"global batch {global_batch} not divisible by data shards {shard}")
    return global_batch // shard
