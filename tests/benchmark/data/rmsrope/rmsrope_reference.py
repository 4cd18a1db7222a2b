"""The plain reference of the test family ``rmsrope``: float32 ``jax.numpy``
at ``default_matmul_precision("highest")`` on the system's own weights.

Pre-norm blocks: RMSNorm (no centering, gamma only), causal grouped-query
attention with rotary positions in the rotate-half convention (query head
``i`` reads key/value head ``i // (heads / kv_heads)``), a SwiGLU FFN
``w_out(silu(w_gate x) * w_in x)``, a final RMSNorm and an untied head.  No
biases, no position table.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(p, x, eps):
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + eps) * p["gamma"].astype(F32))


def _rotate(x, theta):
    """``[b, s, h, k]``: pair feature ``j`` with ``j + k/2`` and turn the
    pair by ``position * theta ** (-j / (k/2))``."""
    s, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = jnp.arange(s, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _block(p, x, eps, theta):
    p = jax.tree.map(lambda a: a.astype(F32), p)
    a = p["attention"]
    h = _rms_norm(p["ln_1"], x, eps)
    q = _rotate(jnp.einsum("bsd,dhk->bshk", h, a["query"]["kernel"]), theta)
    k = _rotate(jnp.einsum("bsd,dhk->bshk", h, a["key"]["kernel"]), theta)
    v = jnp.einsum("bsd,dhk->bshk", h, a["value"]["kernel"])
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = x.shape[1]
    scores = jnp.einsum("bqhk,bthk->bhqt", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    ctx = jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(scores, axis=-1), v)
    x = x + jnp.einsum("bqhk,hkd->bqd", ctx, a["out"]["kernel"])
    f = p["ffn"]
    h = _rms_norm(p["ln_2"], x, eps)
    h = jax.nn.silu(h @ f["w_gate"]["kernel"]) * (h @ f["w_in"]["kernel"])
    return x + h @ f["w_out"]["kernel"]


def logits(params, input_ids, config):
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    with jax.default_matmul_precision("highest"):
        x = params["embeddings"]["word"].astype(F32)[input_ids]
        x, _ = jax.lax.scan(lambda x, p: (_block(p, x, eps, theta), None), x,
                            params["decoder"])
        x = _rms_norm(params["ln_f"], x, eps)
        return x @ params["lm_head"].astype(F32).T


def token_losses(lg, targets):
    logp = jax.nn.log_softmax(lg.astype(F32), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def tail_logits(params, input_ids, config, count: int):
    import numpy as np
    whole = jax.jit(lambda p, ids: logits(p, ids, config))(params, input_ids)
    return np.asarray(whole)[:, -count:]


def top2(params, input_ids, config):
    import numpy as np

    def both(p, ids):
        values, indices = jax.lax.top_k(logits(p, ids, config), 2)
        return values, indices[..., 0]

    values, best = jax.jit(both)(params, input_ids)
    return np.asarray(values), np.asarray(best)
