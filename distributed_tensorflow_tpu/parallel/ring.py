"""Ring attention: sequence/context parallelism over a mesh axis.

The reference has no sequence dimension at all (fixed 64-bit MLP input,
reference example.py:149); this implements the long-context capability the
framework treats as first-class (SURVEY.md §5 long-context row).

Blockwise attention with an online softmax: each device owns one sequence
shard of Q, K, V.  K/V blocks rotate around the ring with
``lax.ppermute`` (ICI neighbor exchange) while every device accumulates
``softmax(QK^T)V`` against the passing blocks using the numerically-stable
running (max, sum) trick — peak memory is O(block²) instead of O(seq²) and
the sequence can exceed one chip's HBM.

Two entry points:
  * ``ring_attention(q, k, v, axis_name=...)`` — call inside an existing
    ``shard_map``/manual region where ``axis_name`` is bound;
  * ``ring_attention_sharded(q, k, v, mesh, seq_axis)`` — wraps itself in a
    partial-manual ``jax.shard_map`` over only the sequence axis (other mesh
    axes stay on the automatic pjit path), so models can drop it into an
    otherwise auto-sharded step.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

__all__ = ["ring_attention", "ring_attention_sharded"]


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str, causal: bool = False,
                   kv_valid: Optional[jnp.ndarray] = None,
                   scale: Optional[float] = None) -> jnp.ndarray:
    """q,k,v: local shards [batch, seq_local, heads, head_dim].

    Must be traced where ``axis_name`` is a *manual* (shard_map) axis.
    ``causal=True`` masks by global position, reconstructed from the ring
    rotation: after ``i`` steps, the resident K/V block came from device
    ``(my_index - i) mod ring_size``.  ``kv_valid``: optional
    [batch, seq_local] bool/int padding mask (1 = real token) for the local
    key block; it rotates around the ring alongside K/V.
    """
    ring = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    q32 = q.astype(jnp.float32)
    acc = jnp.zeros((b, h, sq, d), jnp.float32)
    row_max = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    row_sum = jnp.zeros((b, h, sq), jnp.float32)

    q_pos = my_idx * sq + jnp.arange(sq)             # global query positions
    valid0 = (jnp.ones((b, k.shape[1]), jnp.bool_) if kv_valid is None
              else kv_valid.astype(jnp.bool_))

    def step(i, carry):
        acc, row_max, row_sum, k_blk, v_blk, valid_blk = carry
        logits = jnp.einsum("bqhd,bkhd->bhqk", q32,
                            k_blk.astype(jnp.float32)) * scale
        logits = jnp.where(valid_blk[:, None, None, :], logits, -jnp.inf)
        if causal:
            src = (my_idx - i) % ring                 # owner of this block
            k_pos = src * sq + jnp.arange(k_blk.shape[1])
            mask = q_pos[:, None] >= k_pos[None, :]   # [sq, sk]
            logits = jnp.where(mask[None, None], logits, -jnp.inf)

        blk_max = jnp.max(logits, axis=-1)
        new_max = jnp.maximum(row_max, blk_max)
        # Fully-masked rows keep -inf; guard the exp shift.
        shift = jnp.where(jnp.isfinite(new_max), new_max, 0.0)
        correction = jnp.exp(jnp.where(jnp.isfinite(row_max),
                                       row_max - shift, -jnp.inf))
        correction = jnp.nan_to_num(correction)
        probs = jnp.exp(logits - shift[..., None])
        probs = jnp.nan_to_num(probs)

        row_sum = row_sum * correction + jnp.sum(probs, axis=-1)
        acc = (acc * correction[..., None] +
               jnp.einsum("bhqk,bkhd->bhqd", probs,
                          v_blk.astype(jnp.float32)))

        perm = [(j, (j + 1) % ring) for j in range(ring)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        valid_blk = lax.ppermute(valid_blk, axis_name, perm)
        return acc, new_max, row_sum, k_blk, v_blk, valid_blk

    acc, row_max, row_sum, _, _, _ = lax.fori_loop(
        0, ring, step, (acc, row_max, row_sum, k, v, valid0))
    out = acc / jnp.maximum(row_sum, 1e-30)[..., None]
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh: Mesh, seq_axis: str = "seq",
                           causal: bool = False, kv_valid=None,
                           scale: Optional[float] = None):
    """Partial-manual wrapper: manual over ``seq_axis`` only, other mesh
    axes (data/tensor/...) remain automatically partitioned by XLA.
    ``kv_valid``: optional [batch, seq] padding mask (1 = real token)."""
    spec = P(None, seq_axis, None, None)
    vspec = P(None, seq_axis)

    def inner(q, k, v, valid):
        return ring_attention(q, k, v, axis_name=seq_axis, causal=causal,
                              kv_valid=valid, scale=scale)

    if kv_valid is None:
        kv_valid = jnp.ones(q.shape[:2], jnp.bool_)
    return shard_map(inner, mesh=mesh,
                         in_specs=(spec, spec, spec, vspec),
                         out_specs=spec,
                         axis_names=frozenset({seq_axis}),
                         check_vma=False)(q, k, v, kv_valid)


# --------------------------------------------------- dtlint graph tier

from ..analysis import graph as _graph_lib  # noqa: E402  (registration)


@_graph_lib.trace_entry("parallel.ring", hbm_budget=8 << 20)
def _graph_entries():
    """Ring attention with q/k/v sharded over ``seq`` — the specs match
    the shard_map's own in_specs, so no DT501 resharding fires and the
    ledger holds exactly the ring traffic: one k/v-block ppermute per
    hop times (seq-1) hops."""
    import jax

    from .mesh import make_mesh

    n = min(8, len(jax.devices()))
    mesh = make_mesh({"seq": n})
    q = jax.ShapeDtypeStruct((2, n * 8, 2, 16), jnp.float32)
    spec = P(None, "seq", None, None)

    def fwd(q, k, v):
        return ring_attention_sharded(q, k, v, mesh=mesh, causal=True)

    return _graph_lib.Target("ring_attention_sharded", fwd, (q, q, q),
                             in_specs=(spec, spec, spec), mesh=mesh)
