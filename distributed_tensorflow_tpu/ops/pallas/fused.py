"""Fused elementwise Pallas kernels: Adam update, LayerNorm, RMSNorm.

The reference's optimizer/normalisation math runs as individual C++/Eigen
ops inside TF 1.4 (reference example.py:168-170); here the whole update is
one VMEM-resident kernel per block — one HBM read and one HBM write per
tensor element instead of one per intermediate.

XLA already fuses most elementwise chains; these kernels exist for the two
places fusion boundaries bite on TPU: the optimizer update (param + grad +
two moment buffers = 4 HBM streams XLA sometimes splits across fusions)
and LayerNorm's mean/var reductions feeding an elementwise epilogue.
Off-TPU they run in Pallas interpret mode so CPU tests execute the same
kernel code.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_adam_update", "fused_layernorm", "fused_rmsnorm",
           "resolve_fused_ln"]


def resolve_fused_ln(flag) -> bool:
    """Model-config gate for ``fused_layernorm``: True/False pass through;
    "auto" means the Pallas kernel on TPU only (off-TPU it would run in
    slow interpret mode)."""
    if flag == "auto":
        import jax
        return jax.default_backend() == "tpu"
    return bool(flag)

_LANES = 128
_BLOCK_ROWS = 256        # 256 x 128 f32 = 128 KiB per stream, well under VMEM


from .common import use_interpret as _use_interpret


# ---------------------------------------------------------------------------
# Fused Adam
# ---------------------------------------------------------------------------

def _adam_kernel(scalars_ref, p_ref, g_ref, m_ref, v_ref,
                 po_ref, mo_ref, vo_ref, *, b1, b2, wd, delta):
    """scalars: [1, 3] SMEM = (lr_t, eps_t, lr) with bias correction folded
    into lr_t/eps_t; plain lr drives the decoupled weight-decay term.
    ``delta``: emit (new_p - p) instead of new_p — free in-kernel (p is
    already in VMEM) and lets optimizer wrappers report exact updates."""
    lr_t = scalars_ref[0, 0]
    eps_t = scalars_ref[0, 1]
    lr = scalars_ref[0, 2]
    g = g_ref[:]
    p = p_ref[:]
    m = b1 * m_ref[:] + (1.0 - b1) * g
    v = b2 * v_ref[:] + (1.0 - b2) * g * g
    step_term = -lr_t * (m / (jnp.sqrt(v) + eps_t))
    if wd:
        step_term = step_term - lr * wd * p
    po_ref[:] = step_term if delta else p + step_term
    mo_ref[:] = m
    vo_ref[:] = v


def fused_adam_update(params: jnp.ndarray, grads: jnp.ndarray,
                      m: jnp.ndarray, v: jnp.ndarray, step: jnp.ndarray,
                      lr: float = 1e-3, b1: float = 0.9, b2: float = 0.999,
                      eps: float = 1e-8, weight_decay: float = 0.0,
                      tf14_eps: bool = False,
                      return_delta: bool = False,
                      interpret: Optional[bool] = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One exact Adam(W) step for a single tensor, fused into one kernel.

    ``step`` is the 1-based step count (traced scalar is fine).  Bias
    correction is folded into scalar prefactors outside the kernel:
    ``p -= lr*sqrt(1-b2^t)/(1-b1^t) * m / (sqrt(v) + eps*sqrt(1-b2^t))``,
    algebraically identical to the m_hat/v_hat form.  ``tf14_eps=True``
    instead applies eps UN-scaled (``sqrt(v) + eps`` on raw v) — the TF-1.4
    rule ``optim.adam`` documents; the two differ when eps matters.
    ``return_delta=True`` returns ``new_p - p`` (f32) in slot 0 instead of
    new params, for optimizer wrappers that report updates.  Returns
    ``(new_params_or_delta, new_m, new_v)``.
    """
    if interpret is None:
        interpret = _use_interpret()
    orig_shape, orig_dtype = params.shape, params.dtype
    t = step.astype(jnp.float32)
    bc1 = 1.0 - jnp.power(jnp.float32(b1), t)
    bc2 = 1.0 - jnp.power(jnp.float32(b2), t)
    lr_t = lr * jnp.sqrt(bc2) / bc1
    eps_t = jnp.float32(eps) if tf14_eps else eps * jnp.sqrt(bc2)
    scalars = jnp.stack([lr_t, eps_t, jnp.float32(lr)]
                        ).reshape(1, 3).astype(jnp.float32)

    def flat2d(x):
        x = x.reshape(-1).astype(jnp.float32)
        pad = (-x.shape[0]) % _LANES
        x = jnp.pad(x, (0, pad))
        return x.reshape(-1, _LANES)

    p2, g2, m2, v2 = map(flat2d, (params, grads, m, v))
    rows = p2.shape[0]
    br = min(_BLOCK_ROWS, rows)
    pad_rows = (-rows) % br
    if pad_rows:
        p2, g2, m2, v2 = (jnp.pad(x, ((0, pad_rows), (0, 0)))
                          for x in (p2, g2, m2, v2))
    grid = (p2.shape[0] // br,)

    tensor_spec = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    shape = jax.ShapeDtypeStruct(p2.shape, jnp.float32)
    new_p, new_m, new_v = pl.pallas_call(
        functools.partial(_adam_kernel, b1=b1, b2=b2, wd=weight_decay,
                          delta=return_delta),
        out_shape=(shape, shape, shape),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 3), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            tensor_spec, tensor_spec, tensor_spec, tensor_spec,
        ],
        out_specs=(tensor_spec, tensor_spec, tensor_spec),
        interpret=interpret,
        name="dttpu_fused_adam",
    )(scalars, p2, g2, m2, v2)

    n = math.prod(orig_shape) if orig_shape else 1
    def unflat(x, dtype):
        return x.reshape(-1)[:n].reshape(orig_shape).astype(dtype)
    out_dtype = jnp.float32 if return_delta else orig_dtype
    return (unflat(new_p, out_dtype), unflat(new_m, jnp.float32),
            unflat(new_v, jnp.float32))


# ---------------------------------------------------------------------------
# Fused LayerNorm
# ---------------------------------------------------------------------------

def _layernorm_kernel(x_ref, gamma_ref, beta_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)                       # [br, d]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    centred = x - mean
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    y = centred * inv * gamma_ref[:].astype(jnp.float32) + \
        beta_ref[:].astype(jnp.float32)
    o_ref[:] = y.astype(o_ref.dtype)


def _layernorm_forward(x2, gamma, beta, eps, interpret):
    rows, d = x2.shape
    br = min(_BLOCK_ROWS, rows)
    pad = (-rows) % br
    xp = jnp.pad(x2, ((0, pad), (0, 0))) if pad else x2
    out = pl.pallas_call(
        functools.partial(_layernorm_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct(xp.shape, x2.dtype),
        grid=(xp.shape[0] // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        interpret=interpret,
        name="dttpu_fused_layernorm",
    )(xp, gamma.reshape(1, d), beta.reshape(1, d))
    return out[:rows]


def _layernorm_reference(x, gamma, beta, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) +
            beta.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _layernorm(x2, gamma, beta, eps, interpret):
    return _layernorm_forward(x2, gamma, beta, eps, interpret)


def _layernorm_fwd(x2, gamma, beta, eps, interpret):
    return _layernorm_forward(x2, gamma, beta, eps, interpret), \
        (x2, gamma, beta)


def _layernorm_bwd(eps, interpret, res, g):
    x2, gamma, beta = res
    _, vjp = jax.vjp(
        lambda x_, g_, b_: _layernorm_reference(x_, g_, b_, eps),
        x2, gamma, beta)
    return vjp(g)


_layernorm.defvjp(_layernorm_fwd, _layernorm_bwd)


def fused_layernorm(x: jnp.ndarray, gamma: jnp.ndarray, beta: jnp.ndarray,
                    eps: float = 1e-6,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """LayerNorm over the last axis as a single fused kernel.

    ``x``: [..., d]; ``gamma``/``beta``: [d].  Statistics in float32
    regardless of input dtype; backward rematerialises via the XLA
    reference under ``jax.vjp``.
    """
    if interpret is None:
        interpret = _use_interpret()
    d = x.shape[-1]
    lead = x.shape[:-1]
    out2 = _layernorm(x.reshape(-1, d), gamma, beta, float(eps),
                      bool(interpret))
    return out2.reshape(*lead, d)


# ---------------------------------------------------------------------------
# Fused RMSNorm (the Llama block norm: f32 rms, gamma scale, no centering)
# ---------------------------------------------------------------------------

def _rmsnorm_kernel(x_ref, gamma_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)                       # [br, d]
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    o_ref[:] = (x * inv * gamma_ref[:].astype(jnp.float32)
                ).astype(o_ref.dtype)


def _rmsnorm_forward(x2, gamma, eps, interpret):
    rows, d = x2.shape
    br = min(_BLOCK_ROWS, rows)
    pad = (-rows) % br
    xp = jnp.pad(x2, ((0, pad), (0, 0))) if pad else x2
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct(xp.shape, x2.dtype),
        grid=(xp.shape[0] // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        interpret=interpret,
        name="dttpu_fused_rmsnorm",
    )(xp, gamma.reshape(1, d))
    return out[:rows]


def _rmsnorm_reference(x, gamma, eps):
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv * gamma.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rmsnorm(x2, gamma, eps, interpret):
    return _rmsnorm_forward(x2, gamma, eps, interpret)


def _rmsnorm_fwd(x2, gamma, eps, interpret):
    return _rmsnorm_forward(x2, gamma, eps, interpret), (x2, gamma)


def _rmsnorm_bwd(eps, interpret, res, g):
    x2, gamma = res
    _, vjp = jax.vjp(lambda x_, g_: _rmsnorm_reference(x_, g_, eps),
                     x2, gamma)
    return vjp(g)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def fused_rmsnorm(x: jnp.ndarray, gamma: jnp.ndarray, eps: float = 1e-6,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """RMSNorm over the last axis as a single fused kernel.

    ``x``: [..., d]; ``gamma``: [d].  Same structure as
    ``fused_layernorm`` (f32 statistics, padded row blocks, XLA-reference
    backward under ``jax.vjp``) minus the centering and bias — matches
    the model's HF-LlamaRMSNorm numerics (models/gpt.py ``_norm``).
    """
    if interpret is None:
        interpret = _use_interpret()
    d = x.shape[-1]
    lead = x.shape[:-1]
    out2 = _rmsnorm(x.reshape(-1, d), gamma, float(eps), bool(interpret))
    return out2.reshape(*lead, d)
