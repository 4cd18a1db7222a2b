"""Fused flash attention (Pallas / Mosaic-TPU): forward AND backward.

Replaces the O(seq²)-memory ``ops.attention.dot_product_attention`` hot path
with a blockwise online-softmax kernel: Q stays resident in VMEM per block
row while K/V blocks stream through, so the full logits matrix never
materialises in HBM.  The MXU sees [block_q, head_dim] x [head_dim, block_k]
matmuls with float32 accumulation; inputs may be bfloat16.

Forward grid: ``(batch, heads, q_blocks, k_blocks)`` with the K dimension
minormost — Pallas executes the grid sequentially on a TPU core, so the
float32 accumulator / running-max / running-sum scratch carried across the
k iterations implements the streaming softmax without HBM round-trips.  The
kernel also emits the row logsumexp (``lse``), which the backward consumes.

Backward (the standard two-kernel flash split, residuals = (q,k,v,out,lse)
— O(seq) extra memory, logits recomputed blockwise):
  * ``dkv`` kernel, grid ``(b, h, k_blocks, q_blocks)`` (q minormost):
    each k block accumulates dK/dV while the q blocks stream through;
  * ``dq`` kernel, grid ``(b, h, q_blocks, k_blocks)`` (k minormost):
    each q block accumulates dQ while the k blocks stream;
  * the row term ``D = rowsum(dO * O)`` is a cheap elementwise reduce done
    in plain XLA before both kernels.

Off-TPU the kernels run in Pallas interpret mode so CPU tests execute the
identical code; NOTE interpret mode has hidden Mosaic tiling violations
before (docs/PERF.md) — hardware validation is required before claiming a
measured win.

Default blocks (512, 1024), clamped to seq, come from the 2026-07-31
hardware sweep (scripts/sweep_flash_blocks.py): the 128x128 blocks the
kernel started with spend ~33us of per-grid-step overhead on thousands of
tiny sequential steps, losing to XLA everywhere; 4x-fatter blocks win
1.6x at seq 2048 and ~3x at 4096 (docs/PERF.md has the full table).

Reference parity note: the reference repo has no attention at all (its model
is an MLP, reference example.py:149-155); this kernel serves the BERT/GPT
model families the driver's baseline configs require.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import use_interpret as _use_interpret

__all__ = ["flash_attention", "make_flash_attention_fn"]

NEG_INF = float("-inf")


def _flash_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, lse_ref,
                  acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool,
                  block_q: int, block_k: int):
    """One (batch, head, q_block, k_block) grid step.

    Refs: q [1,1,bq,d], k/v [1,1,bk,d], valid [1,1,bk] float (1=real key;
    the singleton middle axis keeps the block's trailing-2 shape (1, bk)
    equal-or-tiled against Mosaic's (8, 128) rule), o [1,1,bq,d],
    lse [1,1,bq,1] f32 row logsumexp (backward residual; the trailing
    singleton makes the block's trailing-2 shape (bq, 1) — bq tiles by 8,
    1 equals the array dim — the same Mosaic rule the valid mask needed);
    scratch acc [bq,d] f32, m/l [bq,1] f32.
    """
    # program_id must be read at kernel top level: the HLO interpreter used
    # off-TPU cannot lower it from inside a pl.when body.
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, d]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]

        valid = valid_ref[0, 0, :] > 0.5                # [bk]
        logits = jnp.where(valid[None, :], logits, NEG_INF)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            logits = jnp.where(q_pos >= k_pos, logits, NEG_INF)

        m_prev = m_ref[:, 0]                            # [bq]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
        # Rows with every key masked so far keep m == -inf; shift by 0 there
        # so exp() stays finite and contributes nothing.
        shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        probs = jnp.exp(logits - shift[:, None])        # masked -> exp(-inf)=0
        correction = jnp.where(jnp.isfinite(m_prev),
                               jnp.exp(m_prev - shift), 0.0)

        l_ref[:, 0] = l_ref[:, 0] * correction + jnp.sum(probs, axis=-1)
        acc_ref[:] = (acc_ref[:] * correction[:, None] +
                      jax.lax.dot_general(
                          probs, v_ref[0, 0].astype(jnp.float32),
                          (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))
        m_ref[:, 0] = m_new

    if causal:
        # Blocks strictly above the diagonal contribute nothing: no query
        # row in this block can attend to any key column in it.
        @pl.when((qi + 1) * block_q > ki * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, 0]
        out = acc_ref[:] / jnp.where(l > 0.0, l, 1.0)[:, None]
        o_ref[0, 0] = out.astype(o_ref.dtype)
        # row logsumexp: the running max (shift) + log of the running sum;
        # fully-masked rows (l == 0) get -inf so the backward's
        # exp(s - lse) reproduces their zero probabilities
        m = m_ref[:, 0]
        shift = jnp.where(jnp.isfinite(m), m, 0.0)
        lse = jnp.where(l > 0.0, shift + jnp.log(
            jnp.where(l > 0.0, l, 1.0)), NEG_INF)
        lse_ref[0, 0] = lse[:, None]          # 2-D store: [bq, 1]


def _pad_to(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _flash_forward(q, k, v, valid, scale, causal, block_q, block_k,
                   interpret):
    """q: [b, h, s, d]; k, v: [b, hk, s, d] with h % hk == 0 (GQA/MQA:
    each kv head serves h//hk query heads, selected by block-index
    mapping — the broadcast never materialises); valid: [b, s_k] float32.
    Returns (out [b, h, s, d], lse [b, h, s] f32)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    group = h // k.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, sk)

    q = _pad_to(q, 2, bq)
    k = _pad_to(k, 2, bk)
    v = _pad_to(v, 2, bk)
    valid = _pad_to(valid, 1, bk)          # padded keys arrive masked
    valid = valid[:, None, :]              # [b, 1, sk]: Mosaic-tileable
    sq_p, sk_p = q.shape[2], k.shape[2]
    grid = (b, h, sq_p // bq, sk_p // bk)

    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        out_shape=[jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, sq_p, 1), jnp.float32)],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, bk), lambda ib, ih, iq, ik: (ib, 0, ik)),
        ],
        out_specs=[pl.BlockSpec((1, 1, bq, d),
                                lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
                   pl.BlockSpec((1, 1, bq, 1),
                                lambda ib, ih, iq, ik: (ib, ih, iq, 0))],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="dttpu_flash_fwd",
    )(q, k, v, valid)
    return out[:, :, :sq, :], lse[:, :, :sq, 0]


def _bwd_block_terms(q, k, v, do, lse, dvec, valid, qi, ki, scale, causal,
                     block_q, block_k):
    """Shared per-block backward math: returns (p, ds), both [bq, bk] f32.

    ``p`` re-derives the forward probabilities from the saved row logsumexp
    (exp(s - lse)); ``ds = p * (dp - D) * scale`` is the logits cotangent.
    """
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[None, :] > 0.5, s, NEG_INF)
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    # masked s = -inf -> p = 0; fully-masked rows have lse = -inf, guard the
    # subtraction so exp sees -inf, not (-inf) - (-inf) = nan
    p = jnp.exp(s - jnp.where(jnp.isfinite(lse), lse, 0.0)[:, None])
    p = jnp.where(jnp.isfinite(lse)[:, None], p, 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - dvec[:, None]) * scale
    return p, ds


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, valid_ref,
                      dk_ref, dv_ref, dk_acc, dv_acc, *,
                      scale: float, causal: bool,
                      block_q: int, block_k: int):
    """dK/dV: grid (b, h, k_blocks, q_blocks), q minormost.  Each k block
    holds f32 accumulators while every q block streams through."""
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        p, ds = _bwd_block_terms(
            q, k, v, do, lse_ref[0, 0, :, 0], d_ref[0, 0, :, 0],
            valid_ref[0, 0, :], qi, ki, scale, causal, block_q, block_k)
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # p^T @ dO [bk, d]
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # ds^T @ Q [bk, d]

    if causal:
        # q blocks entirely above the diagonal contribute nothing to this
        # k block
        @pl.when((qi + 1) * block_q > ki * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, valid_ref,
                     dq_ref, dq_acc, *,
                     scale: float, causal: bool,
                     block_q: int, block_k: int):
    """dQ: grid (b, h, q_blocks, k_blocks), k minormost — the forward's
    layout, accumulating dq while k blocks stream."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        _, ds = _bwd_block_terms(
            q, k, v, do, lse_ref[0, 0, :, 0], d_ref[0, 0, :, 0],
            valid_ref[0, 0, :], qi, ki, scale, causal, block_q, block_k)
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # ds @ K [bq, d]

    if causal:
        @pl.when((qi + 1) * block_q > ki * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_backward(q, k, v, valid, out, lse, do, scale, causal,
                    block_q, block_k, interpret, dvec=None):
    """Fused backward: (dq, dk, dv) with logits recomputed blockwise.

    GQA: k/v may have hk < h heads.  The kernels consume them through the
    same ``ih // group`` index mapping as the forward and emit PER-Q-HEAD
    dk/dv ([b, h, sk, d]); the group reduction to [b, hk, sk, d] is one
    cheap XLA sum afterwards (costs group x transient dk/dv memory — still
    O(seq), the kernels' point).

    ``dvec``: optionally the precomputed D = rowsum(dO·O) [b, h, sq] —
    ring-flash calls this once per K/V block with identical q/do/out, so
    it hoists the reduce out of its loop.
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    hk = k.shape[1]
    group = h // hk
    bq = min(block_q, sq)
    bk = min(block_k, sk)

    if dvec is None:
        # D = rowsum(dO * O): cheap elementwise reduce, plain XLA
        dvec = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                       axis=-1)

    q_p = _pad_to(q, 2, bq)
    do_p = _pad_to(do, 2, bq)                 # zero dO rows: no contribution
    # pad lse with 0 (any finite value): padded q rows have dO = 0 and
    # D = 0, so their p never reaches an accumulator.  Both ride with a
    # trailing singleton axis so their blocks' trailing-2 shape (bq, 1)
    # satisfies Mosaic's (8, 128) tiling rule (see _flash_kernel docstring).
    lse_p = _pad_to(lse, 2, bq)[..., None]    # [b, h, sq_p, 1]
    d_p = _pad_to(dvec, 2, bq)[..., None]     # [b, h, sq_p, 1]
    k_p = _pad_to(k, 2, bk)
    v_p = _pad_to(v, 2, bk)
    valid_p = _pad_to(valid, 1, bk)[:, None, :]   # [b, 1, sk_p]
    sq_p, sk_p = q_p.shape[2], k_p.shape[2]

    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        out_shape=[jax.ShapeDtypeStruct((b, h, sk_p, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, sk_p, d), v.dtype)],
        grid=(b, h, sk_p // bk, sq_p // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda ib, ih, ik, iq: (ib, ih, iq, 0)),   # q
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, ik, iq: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, ik, iq: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, bq, d),
                         lambda ib, ih, ik, iq: (ib, ih, iq, 0)),   # do
            pl.BlockSpec((1, 1, bq, 1),
                         lambda ib, ih, ik, iq: (ib, ih, iq, 0)),   # lse
            pl.BlockSpec((1, 1, bq, 1),
                         lambda ib, ih, ik, iq: (ib, ih, iq, 0)),   # D
            pl.BlockSpec((1, 1, bk),
                         lambda ib, ih, ik, iq: (ib, 0, ik)),       # valid
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, ik, iq: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, ik, iq: (ib, ih, ik, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
        name="dttpu_flash_dkv",
    )(q_p, k_p, v_p, do_p, lse_p, d_p, valid_p)

    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
        grid=(b, h, sq_p // bq, sk_p // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),   # q
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, bq, d),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),   # do
            pl.BlockSpec((1, 1, bq, 1),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),   # lse
            pl.BlockSpec((1, 1, bq, 1),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),   # D
            pl.BlockSpec((1, 1, bk),
                         lambda ib, ih, iq, ik: (ib, 0, ik)),       # valid
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="dttpu_flash_dq",
    )(q_p, k_p, v_p, do_p, lse_p, d_p, valid_p)

    if group > 1:
        sk_pad = dk.shape[2]
        dk = dk.astype(jnp.float32).reshape(
            b, hk, group, sk_pad, d).sum(2).astype(k.dtype)
        dv = dv.astype(jnp.float32).reshape(
            b, hk, group, sk_pad, d).sum(2).astype(v.dtype)
    return dq[:, :, :sq, :], dk[:, :, :sk, :], dv[:, :, :sk, :]


def _reference(q, k, v, valid, scale, causal):
    """Pure-XLA parity implementation; also the rematerialised backward."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = jnp.where(valid[:, None, None, :] > 0.5, logits, NEG_INF)
    if causal:
        sq, sk = logits.shape[-2:]
        mask = (jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :])
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    # Fully-masked rows: softmax of all -inf — zero the output instead.
    row_any = jnp.any(logits > NEG_INF, axis=-1, keepdims=True)
    weights = jax.nn.softmax(jnp.where(row_any, logits, 0.0), axis=-1)
    weights = jnp.where(row_any, weights, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", weights,
                      v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, valid, scale, causal, block_q, block_k, interpret):
    out, _ = _flash_forward(q, k, v, valid, scale, causal, block_q, block_k,
                            interpret)
    return out


def _flash_fwd(q, k, v, valid, scale, causal, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, valid, scale, causal, block_q,
                              block_k, interpret)
    return out, (q, k, v, valid, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, g):
    q, k, v, valid, out, lse = res
    dq, dk, dv = _flash_backward(q, k, v, valid, out, lse, g, scale, causal,
                                 block_q, block_k, interpret)
    return dq, dk, dv, jnp.zeros_like(valid)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _mesh_split(mesh, q_shape, k_shape):
    """(mesh axes for the batch dim, mesh axes for the heads dim) of a
    kernel call split over ``mesh``: batch over the data-like axes, heads
    over ``tensor`` — the layout ``partition_rules`` gives activations.  An
    axis group that does not divide its dimension is left out (every device
    then computes that dimension whole, which is what the partitioner would
    have made of a replicated operand)."""
    def fit(axes, *dims):
        axes = tuple(a for a in axes if mesh.shape.get(a, 1) > 1)
        size = math.prod(mesh.shape[a] for a in axes)
        return axes if axes and all(d % size == 0 for d in dims) else None
    return (fit(("data", "fsdp"), q_shape[0]),
            fit(("tensor",), q_shape[2], k_shape[2]))


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    kv_valid: Optional[jnp.ndarray] = None,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 1024,
                    interpret: Optional[bool] = None,
                    mesh=None) -> jnp.ndarray:
    """Fused attention.  q: [batch, seq, heads, head_dim] (the
    framework-wide head layout, see ops.attention); k, v:
    [batch, seq_k, kv_heads, head_dim] where heads % kv_heads == 0 —
    GQA/MQA kv heads are shared across their query group by block-index
    mapping, never materialised; kv_valid: optional [batch, seq_k] mask,
    1 = real key.  Returns [batch, seq, heads, head_dim].

    ``mesh``: the mesh the operands live on (the one the model was built
    with).  XLA cannot partition a Mosaic kernel by itself — a plain
    ``jit`` over more than one device refuses it — so on such a mesh the
    call runs under ``jax.shard_map``: batch split over ``data``/``fsdp``,
    heads over ``tensor``, each device running the kernel on its own
    block.  Attention mixes neither batch rows nor heads, so no
    collective is needed.  Traced inside an enclosing ``shard_map`` (the
    pipeline's) only the axes that are still automatic there are taken.

    Off-TPU the kernel runs in Pallas interpret mode, so CPU tests cover the
    identical kernel code.
    """
    if q.shape[2] % k.shape[2] != 0:
        raise ValueError(
            f"flash_attention requires the q head count to be a multiple "
            f"of the kv head count; got {q.shape[2]} vs {k.shape[2]}")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = _use_interpret()
    if kv_valid is None:
        valid = jnp.ones((k.shape[0], k.shape[1]), jnp.float32)
    else:
        valid = kv_valid.astype(jnp.float32)

    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P
        enclosing = jax.sharding.get_abstract_mesh()
        if enclosing.manual_axes:
            mesh = enclosing    # nested: shard_map wants the context's mesh
        auto = frozenset(mesh.axis_names) - frozenset(enclosing.manual_axes)
        batch, heads = _mesh_split(mesh, q.shape, k.shape)
        qkv_spec = P(batch, None, heads, None)

        def local(q, k, v, valid):
            return flash_attention(q, k, v, kv_valid=valid, causal=causal,
                                   scale=scale, block_q=block_q,
                                   block_k=block_k, interpret=interpret)

        return jax.shard_map(
            local, mesh=mesh, axis_names=auto, check_vma=False,
            in_specs=(qkv_spec, qkv_spec, qkv_spec, P(batch, None)),
            out_specs=qkv_spec)(q, k, v, valid)

    # [b, s, h, d] -> [b, h, s, d] for per-(batch, head) grid blocking.
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _flash(qt, kt, vt, valid, float(scale), bool(causal),
                 int(block_q), int(block_k), bool(interpret))
    return jnp.swapaxes(out, 1, 2)


def make_flash_attention_fn(causal: bool = False, block_q: int = 512,
                            block_k: int = 1024, mesh=None):
    """Adapter matching the ``attention_fn(q, k, v, mask=...)`` slot of
    ``ops.attention.attention_core``.

    Accepts ``mask=None`` or a *padding* mask shaped [b, 1, 1, s_k] (the
    output of ``ops.attention.padding_mask``); arbitrary additive masks
    don't map onto the fused kernel and raise.  ``mesh``: see
    ``flash_attention``.
    """
    def fn(q, k, v, mask=None, scale=None):
        kv_valid = None
        if mask is not None:
            if mask.ndim != 4 or mask.shape[1] != 1 or mask.shape[2] != 1:
                raise ValueError(
                    "flash attention accepts only padding masks "
                    f"[b,1,1,s]; got {mask.shape}")
            kv_valid = (mask[:, 0, 0, :] >= 0.0)
        return flash_attention(q, k, v, kv_valid=kv_valid, causal=causal,
                               scale=scale, block_q=block_q, block_k=block_k,
                               mesh=mesh)
    fn.supports_gqa = True   # attention_core: skip the kv-head broadcast
    return fn
