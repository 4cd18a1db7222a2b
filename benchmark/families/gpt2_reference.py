"""The plain reference of family ``gpt2``: GPT-2's forward pass and LM loss
in float32 (``families/gpt2.py`` names this file; the drivers reach it through
the family and nowhere else).

Straightforward ``jax.numpy`` following Radford et al. 2019 / the public
``GPT2LMHeadModel``: learned token + position embeddings, pre-LN blocks of
causal multi-head attention and a 4x GELU(tanh) MLP, a final LayerNorm and
a head tied to the token embedding.  No kernels, no cache, no batching
tricks; matmuls at ``jax.default_matmul_precision("highest")`` because a TPU
otherwise runs float32 matmuls in bf16 passes.

It reads the parameter tree of the system under test (``GPT.init``'s layout:
``embeddings/{word,position}``, ``decoder/...`` stacked over layers,
``ln_f``) so that both sides hold the same weights, and casts each layer to
float32 as it is used, so a bf16-served model needs no second f32 copy.
Departure from the plain text: the layer loop is a ``lax.scan`` over the
stacked layer axis (48 unrolled float32 layers take minutes to compile).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _layer_norm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * p["gamma"].astype(F32)
            + p["beta"].astype(F32))


def _block(p, x, eps):
    p = jax.tree.map(lambda a: a.astype(F32), p)
    a = p["attention"]
    h = _layer_norm(p["ln_1"], x, eps)
    q = jnp.einsum("bsd,dhk->bshk", h, a["query"]["kernel"]) + a["query"]["bias"]
    k = jnp.einsum("bsd,dhk->bshk", h, a["key"]["kernel"]) + a["key"]["bias"]
    v = jnp.einsum("bsd,dhk->bshk", h, a["value"]["kernel"]) + a["value"]["bias"]
    s = x.shape[1]
    scores = jnp.einsum("bqhk,bthk->bhqt", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    ctx = jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(scores, axis=-1), v)
    x = x + jnp.einsum("bqhk,hkd->bqd", ctx, a["out"]["kernel"]) + a["out"]["bias"]
    f = p["ffn"]
    h = _layer_norm(p["ln_2"], x, eps)
    h = jax.nn.gelu(h @ f["w_in"]["kernel"] + f["w_in"]["bias"],
                    approximate=True)            # GPT-2's "gelu_new"
    return x + h @ f["w_out"]["kernel"] + f["w_out"]["bias"]


def logits(params, input_ids, config):
    """``[b, s]`` token ids -> ``[b, s, vocab]`` float32 logits.  ``config``
    is the configuration file: of it the reference reads the one number the
    weights do not carry, ``layer_norm_epsilon``."""
    eps = config["layer_norm_epsilon"]
    with jax.default_matmul_precision("highest"):
        emb = params["embeddings"]
        word = emb["word"].astype(F32)
        s = input_ids.shape[1]
        x = word[input_ids] + emb["position"][:s].astype(F32)[None]
        x, _ = jax.lax.scan(lambda x, p: (_block(p, x, eps), None), x,
                            params["decoder"])
        x = _layer_norm(params["ln_f"], x, eps)
        return x @ word.T


def token_losses(lg, targets):
    """``[b, s, vocab]`` logits (anyone's) and ``[b, s]`` targets -> every
    position's cross-entropy ``[b, s]`` in float32.  The trainer's inputs are
    ``ids[:, :-1]`` and its targets ``ids[:, 1:]``."""
    logp = jax.nn.log_softmax(lg.astype(F32), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def tail_logits(params, input_ids, config, count: int):
    """The logits of the last ``count`` positions, as a host array
    ``[b, count, vocab]``: what the serving probe is compared with.  The
    whole ``[b, s, vocab]`` is computed and cut on the host (200 positions
    here); a family with long contexts applies its head to the tail alone."""
    import numpy as np
    whole = jax.jit(lambda p, ids: logits(p, ids, config))(params, input_ids)
    return np.asarray(whole)[:, -count:]


def top2(params, input_ids, config):
    """At every position the two largest logits ``[b, s, 2]`` and the id of
    the largest ``[b, s]``, as host arrays: what the emitted tokens are
    compared with, and how far apart the reference's first two choices
    lie."""
    import numpy as np

    def both(p, ids):
        values, indices = jax.lax.top_k(logits(p, ids, config), 2)
        return values, indices[..., 0]

    values, best = jax.jit(both)(params, input_ids)
    return np.asarray(values), np.asarray(best)
