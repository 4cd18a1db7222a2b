"""Ring attention composed with the fused Pallas flash kernel.

``parallel.ring`` gives sequence parallelism (each device owns one
sequence shard; K/V blocks rotate over ICI with ``ppermute``) but
computes each block pair with dense XLA attention — materialising
[b, h, sq_local, sk_local] logits per step.  This module runs the SAME
ring schedule with the validated flash kernel per block pair, merging
block outputs by their row logsumexp — i.e. ring-flash attention, the
long-context configuration where both levers stack: O(block) memory
inside each device AND sequence sharding across devices.

Correctness structure (the standard ring-flash derivation):
 * forward: each block call returns (out_i, lse_i) where ``out_i`` is
   softmax-normalised within the block; the running merge
   ``out = Σ_i exp(lse_i - lse_tot) out_i`` reconstructs the global
   softmax exactly.
 * backward: with the GLOBAL ``lse`` (and global D = rowsum(dO·O)), the
   per-block flash backward recovers exactly this block's contribution
   to dq and the block's own dk/dv — so the ring runs again, rotating
   the K/V blocks WITH their gradient accumulators; after a full loop
   each accumulator is home.

Off-TPU the kernels run in interpret mode, so the CPU mesh tests cover
the identical code path (reference: /root/reference has no attention at
all — SURVEY.md §5 long-context row; this is framework-native scope).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from ..ops.pallas.common import use_interpret as _use_interpret
from ..ops.pallas.flash_attention import _flash_backward, _flash_forward

__all__ = ["ring_flash_attention", "ring_flash_attention_sharded"]


def _rel_index(src, my, causal: bool):
    """0 = block fully visible, 1 = diagonal (aligned causal), 2 = skip."""
    if not causal:
        return jnp.int32(0)
    return jnp.where(src < my, jnp.int32(0),
                     jnp.where(src == my, jnp.int32(1), jnp.int32(2)))


def _block_fwd(q, k_blk, v_blk, valid_blk, rel, scale, bq, bk, interpret):
    def full(_):
        return _flash_forward(q, k_blk, v_blk, valid_blk, scale, False,
                              bq, bk, interpret)

    def diag(_):
        return _flash_forward(q, k_blk, v_blk, valid_blk, scale, True,
                              bq, bk, interpret)

    def skip(_):
        b, h, sq, d = q.shape
        return (jnp.zeros((b, h, sq, d), q.dtype),
                jnp.full((b, h, sq), -jnp.inf, jnp.float32))

    return lax.switch(rel, (full, diag, skip), None)


def _block_bwd(q, k_blk, v_blk, valid_blk, out, lse, do, dvec, rel,
               scale, bq, bk, interpret):
    def full(_):
        return _flash_backward(q, k_blk, v_blk, valid_blk, out, lse, do,
                               scale, False, bq, bk, interpret, dvec=dvec)

    def diag(_):
        return _flash_backward(q, k_blk, v_blk, valid_blk, out, lse, do,
                               scale, True, bq, bk, interpret, dvec=dvec)

    def skip(_):
        return (jnp.zeros_like(q), jnp.zeros_like(k_blk),
                jnp.zeros_like(v_blk))

    return lax.switch(rel, (full, diag, skip), None)


def _rotate(x, axis_name, ring):
    perm = [(j, (j + 1) % ring) for j in range(ring)]
    return lax.ppermute(x, axis_name, perm)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _ring_flash(q, k, v, valid, axis_name, causal, scale, block_q,
                block_k, interpret):
    out, _ = _ring_flash_fwd_loop(q, k, v, valid, axis_name, causal,
                                  scale, block_q, block_k, interpret)
    return out.astype(q.dtype)


def _ring_flash_fwd_loop(q, k, v, valid, axis_name, causal, scale,
                         block_q, block_k, interpret):
    ring = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    out = jnp.zeros((b, h, sq, d), jnp.float32)
    lse = jnp.full((b, h, sq), -jnp.inf, jnp.float32)

    def step(i, carry):
        out, lse, k_blk, v_blk, valid_blk = carry
        src = (my - i) % ring
        rel = _rel_index(src, my, causal)
        o_i, lse_i = _block_fwd(q, k_blk, v_blk, valid_blk, rel, scale,
                                block_q, block_k, interpret)
        new_lse = jnp.logaddexp(lse, lse_i)
        # fully-masked rows stay -inf; guard the exp shifts
        w_old = jnp.exp(jnp.where(jnp.isfinite(new_lse), lse - new_lse,
                                  -jnp.inf))
        w_new = jnp.exp(jnp.where(jnp.isfinite(new_lse), lse_i - new_lse,
                                  -jnp.inf))
        out = (out * jnp.nan_to_num(w_old)[..., None]
               + o_i.astype(jnp.float32)
               * jnp.nan_to_num(w_new)[..., None])
        return (out, new_lse, _rotate(k_blk, axis_name, ring),
                _rotate(v_blk, axis_name, ring),
                _rotate(valid_blk, axis_name, ring))

    out, lse, _, _, _ = lax.fori_loop(0, ring, step,
                                      (out, lse, k, v, valid))
    return out, lse


def _ring_flash_fwd(q, k, v, valid, axis_name, causal, scale, block_q,
                    block_k, interpret):
    out, lse = _ring_flash_fwd_loop(q, k, v, valid, axis_name, causal,
                                    scale, block_q, block_k, interpret)
    # residual in the INPUT dtype (the f32 merge accumulator would double
    # this residual's memory for bf16 models — the backward upcasts where
    # it matters: D = rowsum(dO·O) in f32); matches the non-ring flash
    # path, which saves the kernel-dtype out.
    out = out.astype(q.dtype)
    return out, (q, k, v, valid, out, lse)


def _ring_flash_bwd(axis_name, causal, scale, block_q, block_k, interpret,
                    res, g):
    q, k, v, valid, out, lse = res
    ring = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    do = g
    # D = rowsum(dO·O) is identical for every K/V block — compute once,
    # not once per ring step
    dvec = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1)

    dq = jnp.zeros(q.shape, jnp.float32)
    dk_rot = jnp.zeros(k.shape, jnp.float32)
    dv_rot = jnp.zeros(v.shape, jnp.float32)

    def step(i, carry):
        dq, dk_rot, dv_rot, k_blk, v_blk, valid_blk = carry
        src = (my - i) % ring
        rel = _rel_index(src, my, causal)
        dq_i, dk_i, dv_i = _block_bwd(q, k_blk, v_blk, valid_blk, out,
                                      lse, do, dvec, rel, scale, block_q,
                                      block_k, interpret)
        dq = dq + dq_i.astype(jnp.float32)
        dk_rot = dk_rot + dk_i.astype(jnp.float32)
        dv_rot = dv_rot + dv_i.astype(jnp.float32)
        # gradient accumulators travel WITH their k/v blocks: after the
        # full ring both are back at the owning device
        return (dq, _rotate(dk_rot, axis_name, ring),
                _rotate(dv_rot, axis_name, ring),
                _rotate(k_blk, axis_name, ring),
                _rotate(v_blk, axis_name, ring),
                _rotate(valid_blk, axis_name, ring))

    dq, dk_rot, dv_rot, _, _, _ = lax.fori_loop(
        0, ring, step, (dq, dk_rot, dv_rot, k, v, valid))
    return (dq.astype(q.dtype), dk_rot.astype(k.dtype),
            dv_rot.astype(v.dtype), jnp.zeros_like(valid))


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_flash_attention(q, k, v, axis_name: str, causal: bool = False,
                         kv_valid=None, scale: Optional[float] = None,
                         block_q: int = 512, block_k: int = 1024,
                         interpret: Optional[bool] = None):
    """Flash-kernel ring attention over a manual (shard_map) mesh axis.

    q, k, v: local shards [batch, seq_local, heads, head_dim] (the
    framework-wide head layout); ``kv_valid``: optional
    [batch, seq_local] padding mask for the local key block (1 = real),
    rotating with K/V.  Same contract as ``ring.ring_attention``.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = _use_interpret()
    valid = (jnp.ones((k.shape[0], k.shape[1]), jnp.float32)
             if kv_valid is None else kv_valid.astype(jnp.float32))
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _ring_flash(qt, kt, vt, valid, axis_name, bool(causal),
                      float(scale), int(block_q), int(block_k),
                      bool(interpret))
    return jnp.swapaxes(out, 1, 2)


def ring_flash_attention_sharded(q, k, v, mesh: Mesh,
                                 seq_axis: str = "seq",
                                 causal: bool = False, kv_valid=None,
                                 scale: Optional[float] = None,
                                 block_q: int = 512, block_k: int = 1024):
    """Partial-manual wrapper mirroring ``ring.ring_attention_sharded``:
    manual over ``seq_axis`` only; other mesh axes stay on the automatic
    pjit path."""
    spec = P(None, seq_axis, None, None)
    vspec = P(None, seq_axis)

    def inner(q, k, v, valid):
        return ring_flash_attention(q, k, v, axis_name=seq_axis,
                                    causal=causal, kv_valid=valid,
                                    scale=scale, block_q=block_q,
                                    block_k=block_k)

    if kv_valid is None:
        kv_valid = jnp.ones(q.shape[:2], jnp.bool_)
    return shard_map(inner, mesh=mesh,
                         in_specs=(spec, spec, spec, vspec),
                         out_specs=spec,
                         axis_names=frozenset({seq_axis}),
                         check_vma=False)(q, k, v, kv_valid)


# --------------------------------------------------- dtlint graph tier

from ..analysis import graph as _graph_lib  # noqa: E402  (registration)


@_graph_lib.trace_entry("parallel.ring_flash", hbm_budget=8 << 20)
def _graph_entries():
    """The fused-kernel ring: same sharding contract as parallel.ring
    (specs match the shard_map in_specs — no implicit resharding), the
    kernel body opaque to propagation (degrades to unknown, per the
    tier's contract) while the ring ppermutes around it still price."""
    import jax

    from .mesh import make_mesh

    n = min(8, len(jax.devices()))
    mesh = make_mesh({"seq": n})
    q = jax.ShapeDtypeStruct((2, n * 8, 2, 16), jnp.float32)
    spec = P(None, "seq", None, None)

    def fwd(q, k, v):
        return ring_flash_attention_sharded(q, k, v, mesh=mesh,
                                            causal=True, block_q=8,
                                            block_k=8)

    return _graph_lib.Target("ring_flash_attention_sharded", fwd,
                             (q, q, q), in_specs=(spec, spec, spec),
                             mesh=mesh)
