"""The plain reference of family ``longcat_flash``: a decoder of
shortcut-connected expert layers over latent attention, in float32
(``families/longcat_flash.py`` names this file; the drivers reach it through
the family and nowhere else).

Straightforward ``jax.numpy`` from the layer equations of the public
``LongcatFlashForCausalLM`` (``transformers`` 4.57,
``models/longcat_flash/modular_longcat_flash.py``), on a residual stream
``x``:

* one layer: ``x += MLA_0(norm(x))``; ``m = norm(x)``; the shortcut ``s =
  MoE(m)``; ``x += MLP_0(m)``; ``x += MLA_1(norm(x))``; ``x += MLP_1(norm(x))
  + s``.  RMSNorm everywhere, SwiGLU FFNs, no biases; a final norm and an
  untied head.
* MLA in the published, EXPANDED form: ``q = W_qb norm(W_qa h)`` split a head
  into a position-free and a rotary part, both times ``sqrt(hidden /
  q_lora_rank)``; the latent ``norm(W_kva h)`` times ``sqrt(hidden /
  kv_lora_rank)`` expanded by ``W_kvb`` to a key and a value a head; one
  rotary key shared by all heads; rotary over interleaved pairs; a full
  masked softmax of ``q k^T / sqrt(qk_head_dim)``.
* the router in float32: ``p = softmax(m W_r)`` over FFN and identity
  experts; the ``moe_topk`` largest of ``p + e_score_correction_bias`` are
  chosen; the weights are the unbiased ``p`` of the chosen times
  ``routed_scaling_factor``, not renormalised; an FFN expert adds ``w
  E_i(m)``, an identity expert ``w m``.

No absorbed attention, no cache, no kernels; matmuls at
``jax.default_matmul_precision("highest")`` because a TPU otherwise runs
float32 matmuls in bf16 passes.  It shares no code with the program: it
reads the program's parameter tree (``LongcatFlash.init``'s layout) so that
both sides hold the same weights, as served.

Departures from the public implementation, each marked ``DEPARTURE`` where
it happens:

1. **The share.**  A cut configuration holds ``n_routed_experts`` of the
   ``cut.published`` FFN experts, from ``cut.expert_offset`` on, and a slice
   of the vocabulary.  The router scores ALL published experts; a pick on an
   FFN expert that is not held adds nothing, here as in the program
   (section 4 of the ``model-configs`` guide).  Uncut, this is the public
   model.
2. **Memory beside a serving engine** (a float32 layer is 5 GB): weights
   are widened one matrix (a dense FFN's: one block of its inner width) at
   a time, the batch's rows and an attention's heads go one at a time
   (``lax.map``), the head is applied to the rows asked for, the vocabulary
   in slices.  The sums are the same sums.
3. **The expert loop** runs every held expert on every token with the
   token's weight for it (zero where it was not picked) where the public
   loop gathers each expert's tokens: the same sum, with static shapes.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
FFN_BLOCKS = 4           # a dense FFN's inner width, widened a block a time


def share(config):
    """``(published FFN experts, held, first held index)``."""
    cut = config.get("cut") or {}
    held = config["n_routed_experts"]
    return (cut.get("published", {}).get("n_routed_experts", held), held,
            cut.get("expert_offset", 0))


def _rms_norm(gamma, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * gamma.astype(F32)


def _swiglu(w_in, w_out, x, blocks: int = 1):
    """``(silu(x W_gate) * (x W_up)) W_out`` with ``w_in = [W_gate, W_up]``
    side by side; DEPARTURE 2: the inner width in ``blocks`` blocks."""
    inner = w_out.shape[0]
    if inner % blocks:
        blocks = 1
    width = inner // blocks
    y = jnp.zeros(x.shape[:-1] + (w_out.shape[1],), F32)
    for j in range(blocks):
        cols = slice(j * width, (j + 1) * width)
        gate = x @ w_in[:, cols].astype(F32)
        up = x @ w_in[:, inner + j * width:inner + (j + 1) * width].astype(F32)
        y = y + (jax.nn.silu(gate) * up) @ w_out[cols].astype(F32)
    return y


def _rotary(x, cos, sin):
    """``apply_rotary_pos_emb_interleave``: the interleaved pairs brought
    side by side (evens, then odds), then rotate-half.  ``x`` [s, r]."""
    r = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (r // 2, 2))
    x = jnp.concatenate([x[..., 0], x[..., 1]], axis=-1)
    turned = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], axis=-1)
    return x * cos + turned * sin


def _mla(a, h, config, cos, sin):
    """Latent attention on ``h`` [s, d], the published form, one head at a
    time: a full [s, s] masked softmax each."""
    s, d = h.shape
    eps = config["rms_norm_eps"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    q_scale = (math.sqrt(d / config["q_lora_rank"])
               if config["mla_scale_q_lora"] else 1.0)
    kv_scale = (math.sqrt(d / config["kv_lora_rank"])
                if config["mla_scale_kv_lora"] else 1.0)
    low = _rms_norm(a["q_norm"]["gamma"], h @ a["q_a"]["kernel"].astype(F32),
                    eps)
    # the program holds W_kva's two column blocks as two matrices
    latent = _rms_norm(a["kv_norm"]["gamma"],
                       h @ a["kv_a"]["kernel"].astype(F32), eps) * kv_scale
    k_rot = _rotary(h @ a["k_rope"]["kernel"].astype(F32), cos, sin)
    causal = jnp.tril(jnp.ones((s, s), bool))
    q_b, kv_b, out = (a[n]["kernel"] for n in ("q_b", "kv_b", "out"))

    def head(i):
        q = (low @ q_b[:, i].astype(F32)) * q_scale           # [s, 192]
        kv = latent @ kv_b[:, i].astype(F32)                  # [s, 256]
        q = jnp.concatenate([q[:, :nope], _rotary(q[:, nope:], cos, sin)],
                            axis=-1)
        k = jnp.concatenate([kv[:, :nope], k_rot], axis=-1)
        scores = (q @ k.T) / math.sqrt(nope + rope)
        scores = jnp.where(causal, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ kv[:, nope:]  # [s, 128]

    # DEPARTURE 2: heads one at a time
    ctx = jax.lax.map(head, jnp.arange(q_b.shape[1]))          # [h, s, 128]
    return jnp.einsum("hsv,hvd->sd", ctx, out.astype(F32))


def _router_logits(moe, m):
    return m.astype(F32) @ moe["router"]["kernel"].astype(F32)


def route(moe, m, config):
    """The router's choice ``[T, k]`` and weights ``[T, k]`` for ``m`` [T,
    d]: LongcatFlashTopkRouter, in float32."""
    p = jax.nn.softmax(_router_logits(moe, m), axis=-1)
    _, choice = jax.lax.top_k(
        p + moe["router"]["choice_bias"].astype(F32), config["moe_topk"])
    return choice, (jnp.take_along_axis(p, choice, axis=-1)
                    * config["routed_scaling_factor"])


def _moe(moe, m, config):
    published, held, offset = share(config)
    choice, weight = route(moe, m, config)
    # an identity expert adds w * m
    y = jnp.sum(jnp.where(choice >= published, weight, 0.0), axis=-1,
                keepdims=True) * m
    w_in, w_out = (moe["experts"][n]["kernel"] for n in ("w_in", "w_out"))
    # DEPARTURE 1: only the held experts; DEPARTURE 3: on every token
    for i in range(held):
        w = jnp.sum(jnp.where(choice == offset + i, weight, 0.0), axis=-1,
                    keepdims=True)
        y = y + w * _swiglu(w_in[i], w_out[i], m)
    return y


def _layer(layer, x, config, cos, sin, routers=None):
    """One layer on the stream ``x`` [s, d]: LongcatFlashDecoderLayer.  Its
    router's logits ``[s, E]`` are appended to ``routers`` if given."""
    eps = config["rms_norm_eps"]
    att, ffn = layer["attention"], layer["ffn"]

    def mlp(f, h):
        return _swiglu(f["w_in"]["kernel"], f["w_out"]["kernel"], h,
                       FFN_BLOCKS)

    x = x + _mla(att[0], _rms_norm(att[0]["ln"]["gamma"], x, eps), config,
                 cos, sin)
    m = _rms_norm(ffn[0]["ln"]["gamma"], x, eps)
    shortcut = _moe(layer["moe"], m, config)
    if routers is not None:
        routers.append(_router_logits(layer["moe"], m))
    x = x + mlp(ffn[0], m)
    x = x + _mla(att[1], _rms_norm(att[1]["ln"]["gamma"], x, eps), config,
                 cos, sin)
    # the shortcut joins after the second FFN
    return x + mlp(ffn[1], _rms_norm(ffn[1]["ln"]["gamma"], x, eps)) \
        + shortcut


def rotary_tables(positions: int, config):
    """cos and sin ``[positions, qk_rope_head_dim]``, the frequencies twice
    over (DeepseekV3RotaryEmbedding, no scaling)."""
    rope = config["qk_rope_head_dim"]
    inv_freq = config["rope_theta"] ** (
        -jnp.arange(0, rope, 2, dtype=F32) / rope)
    angles = jnp.arange(positions, dtype=F32)[:, None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def _hidden_row(params, ids, config):
    """``[s]`` ids -> ``[s, d]`` after the final norm, and every expert
    layer's router logits ``[layers, s, E]``."""
    x = params["embeddings"]["word"][ids].astype(F32)
    cos, sin = rotary_tables(ids.shape[0], config)
    routers = []
    for layer in params["layers"]:
        x = _layer(layer, x, config, cos, sin, routers)
    return (_rms_norm(params["ln_f"]["gamma"], x, config["rms_norm_eps"]),
            jnp.stack(routers))


def _hidden(params, input_ids, config):
    return jax.lax.map(lambda ids: _hidden_row(params, ids, config)[0],
                       input_ids)


def router_logits(params, input_ids, config):
    """``[b, s]`` ids -> every expert layer's router logits ``[b, layers,
    s, E]`` in a full forward: what a load-balancing state is fitted to
    (``families/longcat_flash.py balance_choice_bias``).  Traceable, and at
    the caller's matmul precision: a fit over tens of thousands of tokens
    does not need the comparison's."""
    return jax.lax.map(lambda ids: _hidden_row(params, ids, config)[1],
                       input_ids)


def _vocab_slices(vocab: int) -> int:
    return next(n for n in (16, 8, 4, 2, 1) if vocab % n == 0)


def _head_slices(params, hidden, each):
    """``each(logits of one slice of the vocabulary [.., width], first
    id)`` for every slice, stacked: the head is widened a slice at a
    time."""
    head = params["lm_head"]["kernel"]                        # [d, vocab]
    vocab = head.shape[1]
    n = _vocab_slices(vocab)
    width = vocab // n

    def one(k):
        cols = jax.lax.dynamic_slice_in_dim(head, k * width, width, axis=1)
        return each(hidden @ cols.astype(F32), k * width)

    return jax.lax.map(one, jnp.arange(n))


def _all_logits(params, hidden):
    out = _head_slices(params, hidden, lambda lg, _: lg)
    return jnp.moveaxis(out, 0, -2).reshape(hidden.shape[:-1] + (-1,))


def logits(params, input_ids, config):
    """``[b, s]`` token ids -> ``[b, s, vocab]`` float32 logits."""
    with jax.default_matmul_precision("highest"):
        return _all_logits(params, _hidden(params, input_ids, config))


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
            return _all_logits(p, _hidden(p, ids, config)[:, -count:])

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

            values, indices = _head_slices(p, hidden, slice_top)
            values = jnp.moveaxis(values, 0, -2).reshape(
                hidden.shape[:-1] + (-1,))
            indices = jnp.moveaxis(indices, 0, -2).reshape(
                hidden.shape[:-1] + (-1,))
            best, where = jax.lax.top_k(values, 2)
            return best, jnp.take_along_axis(indices, where[..., :1],
                                             axis=-1)[..., 0]

    values, best = jax.jit(both)(params, input_ids)
    return np.asarray(values), np.asarray(best)
