"""State-space (Mamba-2 / SSD) operators with carried state.

A selective state-space mixer keeps, per head, a state ``H`` of shape
``[head_dim, d_state]`` that one token updates as

    H_t = exp(dt_t * A) * H_{t-1} + dt_t * x_t (outer) B_t
    y_t = H_t . C_t

with a scalar ``A < 0`` per head, a step ``dt_t > 0`` per head and token,
and ``B_t``/``C_t`` shared by the heads of a group (one group here).  In
front of it sits a short depthwise causal convolution whose last
``width - 1`` inputs are state too.  Three forms of the same arithmetic:

* :func:`ssd_step` — the recurrence itself, one token for every row (the
  serving decode step);
* :func:`ssd_chunked` — the chunked (matrix) form over a block of tokens:
  inside a chunk the contribution of token ``s`` to token ``t`` is
  ``exp(sum_{s<r<=t} dt_r A) * (C_t . B_s) * dt_s x_s``, a masked
  ``[chunk, chunk]`` matmul, and the state crosses chunk boundaries in a
  ``lax.scan`` — incoming state carried in, outgoing state carried out
  (training's full-sequence forward and the serving prefill window);
* :func:`causal_conv1d` — the convolution as shifted multiply-adds over the
  carried inputs followed by the new ones.

Padding: a position whose ``dt`` is 0 leaves ``H`` exactly as it was
(``exp(0) = 1``, ``0 * x = 0``), which is how a right-padded prefill
window and a frozen decode row keep their state; the convolution's state
is cut at ``valid`` for the same reason.  Everything here is float32 with
``precision="highest"``: the state is summed over thousands of tokens and
the blocks are small.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["causal_conv1d", "gated_rms_norm", "softplus_dt", "ssd_chunked",
           "ssd_step"]

F32 = jnp.float32
_HI = lax.Precision.HIGHEST


def causal_conv1d(x, kernel, bias, state=None, valid=None):
    """Depthwise causal convolution with carried inputs.

    ``x`` [b, s, c]; ``kernel`` [width, c] (``kernel[width - 1]`` weighs
    the current position); ``bias`` [c]; ``state`` [b, width - 1, c], the
    inputs just before ``x`` (zeros at a sequence's start; None = zeros).
    Returns ``(y [b, s, c], new_state)`` where ``new_state`` holds the
    ``width - 1`` inputs that end at position ``valid - 1`` (``valid`` a
    traced count of real positions, a scalar or one a row ``[b]``; None =
    ``s``), so right padding never enters the state.
    """
    width = kernel.shape[0]
    b, s, c = x.shape
    if state is None:
        state = jnp.zeros((b, width - 1, c), x.dtype)
    ext = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    with jax.named_scope("ssm_conv"):
        y = bias.astype(F32)
        for j in range(width):
            y = y + kernel[j].astype(F32) * ext[:, j:j + s].astype(F32)
    if valid is None:
        return y, lax.dynamic_slice_in_dim(ext, s, width - 1, axis=1)
    ends = (jnp.broadcast_to(jnp.reshape(valid, (-1, 1)), (b, 1))
            + jnp.arange(width - 1))
    return y, jnp.take_along_axis(ext, ends[:, :, None], axis=1)


def ssd_step(x, dt, a, b_in, c_in, h):
    """One token of the recurrence for every row.

    ``x`` [b, heads, p]; ``dt`` [b, heads] (0 freezes the row); ``a``
    [heads] (negative); ``b_in``/``c_in`` [b, n]; ``h`` [b, heads, p, n]
    float32.  Returns ``(y [b, heads, p] float32, new h)``."""
    with jax.named_scope("ssm_scan"):
        x, dt = x.astype(F32), dt.astype(F32)
        decay = jnp.exp(dt * a.astype(F32))[:, :, None, None]
        dx = (dt[:, :, None] * x)[..., None]
        h = decay * h + dx * b_in.astype(F32)[:, None, None, :]
        y = jnp.sum(h * c_in.astype(F32)[:, None, None, :], axis=-1)
    return y, h


def _chunk(x, dt, a, b_in, c_in, h):
    """One chunk in matrix form: [b, L, ...] operands, ``h`` the state
    before its first token -> (y [b, L, heads, p], state after its
    last)."""
    seg = dt * a                                  # [b, L, heads], <= 0
    cs = jnp.cumsum(seg, axis=1)                  # through t, inclusive
    length = x.shape[1]
    causal = jnp.tril(jnp.ones((length, length), bool))
    # decay[b, heads, t, s] = exp(sum_{s<r<=t} seg_r) for s <= t
    diff = cs[:, :, None, :] - cs[:, None, :, :]  # [b, t, s, heads]
    decay = jnp.exp(jnp.where(causal[None, :, :, None], diff, -jnp.inf))
    gram = jnp.einsum("btn,bsn->bts", c_in, b_in, precision=_HI)
    mix = decay * gram[..., None] * dt[:, None, :, :]       # [b,t,s,heads]
    y = jnp.einsum("btsh,bshp->bthp", mix, x, precision=_HI)
    # the incoming state, decayed to t and read out by C_t
    y = y + jnp.exp(cs)[..., None] * jnp.einsum(
        "btn,bhpn->bthp", c_in, h, precision=_HI)
    last = cs[:, -1:, :]                                     # [b, 1, heads]
    carry = jnp.exp(last - cs) * dt                          # [b, L, heads]
    h = (jnp.exp(last[:, 0])[:, :, None, None] * h
         + jnp.einsum("bsh,bshp,bsn->bhpn", carry, x, b_in, precision=_HI))
    return y, h


def ssd_chunked(x, dt, a, b_in, c_in, h0, chunk: int):
    """The recurrence over a block of tokens, in chunks of ``chunk``.

    ``x`` [b, s, heads, p]; ``dt`` [b, s, heads] (0 on padding); ``a``
    [heads]; ``b_in``/``c_in`` [b, s, n]; ``h0`` [b, heads, p, n] float32
    (the state before the first token).  Returns ``(y [b, s, heads, p]
    float32, state after the last token)``."""
    with jax.named_scope("ssm_scan"):
        b, s = x.shape[:2]
        x, dt = x.astype(F32), dt.astype(F32)
        b_in, c_in, a = b_in.astype(F32), c_in.astype(F32), a.astype(F32)
        if s <= chunk:
            return _chunk(x, dt, a, b_in, c_in, h0)
        pad = -s % chunk

        def chunks(t):
            t = jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
            t = t.reshape((b, (s + pad) // chunk, chunk) + t.shape[2:])
            return jnp.moveaxis(t, 1, 0)

        def body(h, inputs):
            y, h = _chunk(*inputs[:2], a, *inputs[2:], h)
            return h, y

        h, y = lax.scan(body, h0, (chunks(x), chunks(dt), chunks(b_in),
                                   chunks(c_in)))
        y = jnp.moveaxis(y, 0, 1).reshape((b, s + pad) + y.shape[3:])
        return y[:, :s], h


def gated_rms_norm(y, z, gamma, eps: float):
    """``RMSNorm(y * silu(z)) * gamma`` over the last axis (one group: all
    of the mixer's channels), in float32."""
    with jax.named_scope("ssm_gate_norm"):
        g = y.astype(F32) * jax.nn.silu(z.astype(F32))
        g = g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + eps)
        return g * gamma.astype(F32)


def softplus_dt(dt_raw, dt_bias, valid_mask: Optional[jnp.ndarray] = None
                ) -> jnp.ndarray:
    """``softplus(dt_raw + dt_bias)`` in float32, zeroed where
    ``valid_mask`` (broadcastable bool) is False."""
    dt = jax.nn.softplus(dt_raw.astype(F32) + dt_bias.astype(F32))
    if valid_mask is not None:
        dt = jnp.where(valid_mask, dt, 0.0)
    return dt
