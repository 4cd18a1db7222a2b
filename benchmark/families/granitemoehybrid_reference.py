"""The plain reference of family ``granitemoehybrid``: a decoder of Mamba-2
and attention layers in float32 (``families/granitemoehybrid.py`` names this
file; the drivers reach it through the family and nowhere else).

Straightforward ``jax.numpy`` from the layer equations of the public
``GraniteMoeHybridForCausalLM`` with ``num_local_experts`` 0:

* ``x0 = embedding_multiplier * E[ids]``; every layer ``x += r * mixer(
  RMSNorm(x))`` then ``x += r * mlp(RMSNorm(x))`` with ``r =
  residual_multiplier``; the MLP ``(silu(a) * b) W_out`` with ``[a, b] = x
  W_in``; a final RMSNorm and the tied head ``x E^T / logits_scaling``.
* attention layers: grouped-query causal softmax of ``attention_multiplier *
  q k^T`` — a full masked softmax, no positional encoding.
* Mamba-2 layers: ``[z, xBC, dt] = u W_in``; the depthwise causal
  convolution as ``mamba_d_conv`` shifted multiply-adds, then silu; ``delta
  = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; **the recurrence token by
  token** — a ``lax.scan`` over positions of ``H = exp(delta A) H + delta x
  (outer) B``, ``y = H C + D x`` — and the gated RMSNorm over all channels
  before the out-projection.

No chunked form, no cache, no kernels; matmuls at
``jax.default_matmul_precision("highest")`` because a TPU otherwise runs
float32 matmuls in bf16 passes.  It shares no code with the program: it
reads the program's parameter tree (``HybridDecoder.init``'s layout:
``segments[k]`` stacked runs of Mamba layers, ``attention[j]``) so that both
sides hold the same weights, and casts each layer to float32 as it is used.
Departures from the plain text, all for memory at 4096 positions beside a
serving engine: a run of layers is a ``lax.scan`` over its stacked axis, the
batch's rows and an attention layer's heads go one at a time (``lax.map``),
and the head is applied to the rows asked for, the vocabulary in slices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _rms_norm(gamma, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * gamma


def _mlp(p, x):
    a, b = jnp.split(x @ p["w_in"]["kernel"], 2, axis=-1)
    return (jax.nn.silu(a) * b) @ p["w_out"]["kernel"]


def _mamba(m, u, config):
    """The Mamba-2 mixer on ``u`` [s, d] (one sequence, zero initial
    state)."""
    heads, p_dim = config["mamba_n_heads"], config["mamba_d_head"]
    n, width = config["mamba_d_state"], config["mamba_d_conv"]
    inner = heads * p_dim
    s = u.shape[0]
    # [z, xBC, dt] = u W_in: the program holds W_in's three column blocks
    # as three matrices
    z, xbc, dt = (u @ m["in_proj"][part]["kernel"]
                  for part in ("z", "xbc", "dt"))
    padded = jnp.pad(xbc, ((width - 1, 0), (0, 0)))
    conv = m["conv"]["bias"]
    for j in range(width):           # kernel[width - 1] weighs position t
        conv = conv + m["conv"]["kernel"][j] * padded[j:j + s]
    act = jax.nn.silu(conv)
    x = act[:, :inner].reshape(s, heads, p_dim)
    b_in, c_in = act[:, inner:inner + n], act[:, inner + n:]
    delta = jax.nn.softplus(dt + m["dt_bias"])              # [s, heads]
    a = -jnp.exp(m["a_log"])

    def token(h, inputs):
        x_t, b_t, c_t, d_t = inputs
        h = (jnp.exp(d_t * a)[:, None, None] * h
             + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return h, jnp.sum(h * c_t[None, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((heads, p_dim, n), F32),
                        (x, b_in, c_in, delta))
    y = y + m["d_skip"][:, None] * x
    gated = y.reshape(s, inner) * jax.nn.silu(z)
    return _rms_norm(m["norm"]["gamma"], gated,
                     config["rms_norm_eps"]) @ m["out_proj"]["kernel"]


def _attention(a, h, config):
    """Grouped-query causal attention on ``h`` [s, d], one head at a time:
    a full [s, s] masked softmax each."""
    s = h.shape[0]
    group = config["num_attention_heads"] // config["num_key_value_heads"]
    q = jnp.einsum("sd,dhk->hsk", h, a["query"]["kernel"])
    k = jnp.einsum("sd,dhk->hsk", h, a["key"]["kernel"])
    v = jnp.einsum("sd,dhk->hsk", h, a["value"]["kernel"])
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(i):
        scores = config["attention_multiplier"] * (q[i] @ k[i // group].T)
        scores = jnp.where(causal, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v[i // group]

    ctx = jax.lax.map(head, jnp.arange(q.shape[0]))          # [h, s, k]
    return jnp.einsum("hsk,hkd->sd", ctx, a["out"]["kernel"])


def _block(p, x, config, mixer):
    p = _f32(p)
    eps, r = config["rms_norm_eps"], config["residual_multiplier"]
    x = x + r * mixer(p["mixer"], _rms_norm(p["ln_1"]["gamma"], x, eps),
                      config)
    return x + r * _mlp(p["ffn"], _rms_norm(p["ln_2"]["gamma"], x, eps))


def _hidden_row(params, ids, config):
    """``[s]`` ids -> ``[s, d]`` after the final norm, the layers in the
    published order."""
    word = params["embeddings"]["word"]
    x = config["embedding_multiplier"] * word[ids].astype(F32)
    segments, attention = iter(params["segments"]), iter(params["attention"])
    kinds = config["layer_types"]
    i = 0
    while i < len(kinds):
        if kinds[i] == "attention":
            x = _block(next(attention), x, config, _attention)
            i += 1
            continue
        run = next(segments)
        x, _ = jax.lax.scan(
            lambda x, p: (_block(p, x, config, _mamba), None), x, run)
        i += run["ln_1"]["gamma"].shape[0]
    return _rms_norm(params["ln_f"]["gamma"].astype(F32), x,
                     config["rms_norm_eps"])


def _hidden(params, input_ids, config):
    return jax.lax.map(lambda ids: _hidden_row(params, ids, config),
                       input_ids)


def _vocab_slices(vocab: int) -> int:
    return next(n for n in (16, 8, 4, 2, 1) if vocab % n == 0)


def _head_slices(params, hidden, config, each):
    """``each(logits of one slice of the vocabulary [.., width], first
    id)`` for every slice, stacked: the word matrix is widened a slice at a
    time."""
    word = params["embeddings"]["word"]
    vocab = word.shape[0]
    n = _vocab_slices(vocab)
    width = vocab // n

    def one(k):
        rows = jax.lax.dynamic_slice_in_dim(word, k * width, width)
        return each(hidden @ rows.astype(F32).T / config["logits_scaling"],
                    k * width)

    return jax.lax.map(one, jnp.arange(n))


def logits(params, input_ids, config):
    """``[b, s]`` token ids -> ``[b, s, vocab]`` float32 logits."""
    with jax.default_matmul_precision("highest"):
        hidden = _hidden(params, input_ids, config)
        out = _head_slices(params, hidden, config, lambda lg, _: lg)
        return jnp.moveaxis(out, 0, -2).reshape(hidden.shape[:-1] + (-1,))


def token_losses(lg, targets):
    """``[b, s, vocab]`` logits (anyone's) and ``[b, s]`` targets -> every
    position's cross-entropy ``[b, s]`` in float32."""
    logp = jax.nn.log_softmax(lg.astype(F32), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def tail_logits(params, input_ids, config, count: int):
    """The logits of the last ``count`` positions, as a host array
    ``[b, count, vocab]``: the head is applied to those rows alone."""
    import numpy as np

    def tail(p, ids):
        with jax.default_matmul_precision("highest"):
            hidden = _hidden(p, ids, config)[:, -count:]
            out = _head_slices(p, hidden, config, lambda lg, _: lg)
            return jnp.moveaxis(out, 0, -2).reshape(
                hidden.shape[:-1] + (-1,))

    return np.asarray(jax.jit(tail)(params, input_ids))


def top2(params, input_ids, config):
    """At every position the two largest logits ``[b, s, 2]`` and the id of
    the largest ``[b, s]``, as host arrays, without ever holding ``[b, s,
    vocab]``: the two largest of every slice of the vocabulary, then of
    those."""
    import numpy as np

    def both(p, ids):
        with jax.default_matmul_precision("highest"):
            hidden = _hidden(p, ids, config)

            def slice_top(lg, first):
                values, indices = jax.lax.top_k(lg, 2)
                return values, indices + first

            values, indices = _head_slices(p, hidden, config, slice_top)
            values = jnp.moveaxis(values, 0, -2).reshape(
                hidden.shape[:-1] + (-1,))
            indices = jnp.moveaxis(indices, 0, -2).reshape(
                hidden.shape[:-1] + (-1,))
            best, where = jax.lax.top_k(values, 2)
            return best, jnp.take_along_axis(indices, where[..., :1],
                                             axis=-1)[..., 0]

    values, best = jax.jit(both)(params, input_ids)
    return np.asarray(values), np.asarray(best)
