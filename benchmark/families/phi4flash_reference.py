"""The plain reference of family ``phi4flash``: the SambaY
decoder-hybrid-decoder in float32 (``families/phi4flash.py`` names this
file; the drivers reach it through the family and nowhere else).

Straightforward ``jax.numpy`` from the layer equations of the public
``Phi4FlashForCausalLM`` (arXiv 2507.06607), a Python loop over the
``num_hidden_layers`` layers in order:

* every layer ``x += mixer(LN(x))`` then ``x += W2 (silu(g) * u)`` with
  ``[g, u] = W1 LN(x)``, each LayerNorm with its own gain and bias; a final
  LayerNorm and the tied head;
* layers ``0 .. L/2 - 1``: even ones Mamba-1, odd ones differential
  attention over a sliding window of ``sliding_window`` keys (the query at
  ``t`` sees ``t - sliding_window + 1 .. t``: an explicit mask);
* layer ``L/2``: Mamba-1, whose gated scan output ``M = y * silu(z)`` is
  the memory; layer ``L/2 + 1``: differential attention under an explicit
  causal mask, whose K/V every later attention layer reads;
* then GMU layers ``W_out (silu(W_in LN(x)) * M)`` and cross-attention
  layers (a query and an out-projection, layer ``L/2 + 1``'s K/V, causal)
  in turn.
* Mamba-1: ``[x, z] = u W_in``; the depthwise causal convolution as
  ``d_conv`` shifted multiply-adds plus its bias, then silu; ``[delta, B,
  C] = x W_x``; ``dt = softplus(delta W_dt + b_dt)``; ``A = -exp(A_log)``;
  **the recurrence token by token** — ``h = exp(dt A) h + dt x B``, ``y = h C
  + D x``; ``out = (y * silu(z)) W_out``.
* differential attention, one head at a time: differential head ``i`` has
  query heads ``2i`` and ``2i + 1``, K/V heads ``2j`` and ``2j + 1`` with
  ``j = i // (heads / kv_heads)``; ``o_i = (softmax(q_2i k_2j^T / sqrt(hd))
  - lam softmax(q_2i+1 k_2j+1^T / sqrt(hd))) [v_2j, v_2j+1]``, ``lam =
  exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init``, ``lam_init = 0.8 - 0.6
  exp(-0.3 layer)``; ``o_i`` RMS-normed over its 128 lanes, times the gain
  and ``1 - lam_init``; ``out = W_o concat(o_i) + b_o``.

No cache, no ring, no scan over layers or positions in matrix form, no
kernels; matmuls at ``jax.default_matmul_precision("highest")`` because a TPU
otherwise runs float32 matmuls in bf16 passes.  It shares no code with the
program: it reads the program's parameter tree (``SambaY.init``'s layout:
``self`` and ``cross`` stacked pairs, ``memory``, ``full``; ``A_log`` held
``[d_state, channels]``, the transpose of the published ``[channels,
d_state]``) so that both sides hold the same weights, and casts each layer to
float32 as it is used.  Departures from the plain text, for memory at 8,192
positions beside a serving engine: the batch's rows and the differential
heads go one at a time (``lax.map``), and the head is applied to the rows
asked for, the vocabulary in slices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _layer_norm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _mlp(p, x):
    gate, up = jnp.split(x @ p["w_in"]["kernel"], 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ p["w_out"]["kernel"]


def _mamba(m, u, config):
    """The Mamba-1 mixer on ``u`` [s, d] (one sequence, zero initial state)
    -> (its output [s, d], the memory ``y * silu(z)`` [s, channels])."""
    width, n = config["assumed"]["mamba_d_conv"], \
        config["assumed"]["mamba_d_state"]
    rank = config["assumed"]["mamba_dt_rank"]
    s = u.shape[0]
    x, z = jnp.split(u @ m["in_proj"]["kernel"], 2, axis=-1)
    padded = jnp.pad(x, ((width - 1, 0), (0, 0)))
    conv = m["conv"]["bias"]
    for j in range(width):           # kernel[width - 1] weighs position t
        conv = conv + m["conv"]["kernel"][j] * padded[j:j + s]
    x = jax.nn.silu(conv)
    dbc = x @ m["x_proj"]["kernel"]
    delta, b_in, c_in = (dbc[:, :rank], dbc[:, rank:rank + n],
                         dbc[:, rank + n:])
    dt = jax.nn.softplus(delta @ m["dt_proj"]["kernel"] + m["dt_proj"]["bias"])
    a = -jnp.exp(m["a_log"])                                  # [n, channels]

    def token(h, inputs):
        x_t, dt_t, b_t, c_t = inputs
        h = jnp.exp(dt_t[None, :] * a) * h + (dt_t * x_t)[None, :] \
            * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0)

    _, y = jax.lax.scan(token, jnp.zeros(a.shape, F32), (x, dt, b_in, c_in))
    memory = (y + m["d_skip"] * x) * jax.nn.silu(z)
    return memory @ m["out_proj"]["kernel"], memory


def _differential(m, q, k, v, allowed, layer, config):
    """Differential attention: ``q`` [s, heads * hd], ``k`` / ``v`` [t,
    kv_heads * hd], ``allowed`` [s, t] bool -> [s, d]."""
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    hd = config["hidden_size"] // heads
    group = heads // kv_heads
    q = q.reshape(q.shape[0], heads, hd)
    k = k.reshape(k.shape[0], kv_heads, hd)
    v = v.reshape(v.shape[0], kv_heads, hd)
    lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * layer)
    lv = m["lambda"]
    lam = (jnp.exp(jnp.sum(lv["q1"] * lv["k1"]))
           - jnp.exp(jnp.sum(lv["q2"] * lv["k2"])) + lam_init)

    def softmax(qh, kh):
        scores = jnp.where(allowed, qh @ kh.T / jnp.sqrt(float(hd)), -jnp.inf)
        return jax.nn.softmax(scores, axis=-1)

    def head(i):
        j = i // group
        values = jnp.concatenate([v[:, 2 * j], v[:, 2 * j + 1]], axis=-1)
        o = (softmax(q[:, 2 * i], k[:, 2 * j])
             - lam * softmax(q[:, 2 * i + 1], k[:, 2 * j + 1])) @ values
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                              + config["layer_norm_eps"])
        return o * m["subln"]["scale"] * (1.0 - lam_init)

    o = jax.lax.map(head, jnp.arange(heads // 2))            # [h/2, s, 2hd]
    o = jnp.moveaxis(o, 0, 1).reshape(q.shape[0], heads * hd)
    return o @ m["out"]["kernel"] + m["out"]["bias"]


def _qkv(m, u, config):
    y = u @ m["qkv"]["kernel"] + m["qkv"]["bias"]
    q = config["hidden_size"]
    kv = config["num_key_value_heads"] * (q // config["num_attention_heads"])
    return y[:, :q], y[:, q:q + kv], y[:, q + kv:]


def _hidden_row(params, ids, config):
    """``[s]`` ids -> ``[s, d]`` after the final norm, the layers in the
    published order."""
    eps = config["layer_norm_eps"]
    layers = config["num_hidden_layers"]
    half = layers // 2
    s = ids.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    window = causal & (pos[None, :] > pos[:, None]
                       - config["sliding_window"])
    x = params["embeddings"]["word"][ids].astype(F32)
    memory = shared = None

    def layer_of(stack, kind, index):
        return _f32(jax.tree.map(lambda a: a[index], stack[kind]))

    for layer in range(layers):
        if layer < half:
            p = layer_of(params["self"], ("mamba", "window")[layer % 2],
                         layer // 2)
        elif layer == half:
            p = _f32(params["memory"])
        elif layer == half + 1:
            p = _f32(params["full"])
        else:
            k = layer - half - 2
            p = layer_of(params["cross"], ("gmu", "attention")[k % 2], k // 2)
        m = p["mixer"]
        u = _layer_norm(p["ln_1"], x, eps)
        if "in_proj" in m:                                    # Mamba-1
            out, mem = _mamba(m, u, config)
            if layer == half:
                memory = mem
        elif "w_in" in m:                                     # GMU
            out = (jax.nn.silu(u @ m["w_in"]["kernel"]) * memory) \
                @ m["w_out"]["kernel"]
        elif "qkv" in m:                      # window or full attention
            q, k, v = _qkv(m, u, config)
            if layer == half + 1:
                shared = (k, v)
            out = _differential(m, q, k, v,
                                causal if layer == half + 1 else window,
                                layer, config)
        else:                                 # cross: layer L/2 + 1's K/V
            q = u @ m["query"]["kernel"] + m["query"]["bias"]
            out = _differential(m, q, *shared, causal, layer, config)
        x = x + out
        x = x + _mlp(p["ffn"], _layer_norm(p["ln_2"], x, eps))
    return _layer_norm(_f32(params["ln_f"]), x, eps)


def _hidden(params, input_ids, config):
    return jax.lax.map(lambda ids: _hidden_row(params, ids, config),
                       input_ids)


def _vocab_slices(vocab: int) -> int:
    return next(n for n in (16, 8, 4, 2, 1) if vocab % n == 0)


def _head_slices(params, hidden, each):
    """``each(logits of one slice of the vocabulary [.., width], first
    id)`` for every slice, stacked: the word matrix is widened a slice at a
    time."""
    word = params["embeddings"]["word"]
    vocab = word.shape[0]
    n = _vocab_slices(vocab)
    width = vocab // n

    def one(k):
        rows = jax.lax.dynamic_slice_in_dim(word, k * width, width)
        return each(hidden @ rows.astype(F32).T, k * width)

    return jax.lax.map(one, jnp.arange(n))


def logits(params, input_ids, config):
    """``[b, s]`` token ids -> ``[b, s, vocab]`` float32 logits."""
    with jax.default_matmul_precision("highest"):
        hidden = _hidden(params, input_ids, config)
        out = _head_slices(params, hidden, lambda lg, _: lg)
        return jnp.moveaxis(out, 0, -2).reshape(hidden.shape[:-1] + (-1,))


def token_losses(lg, targets):
    """``[b, s, vocab]`` logits (anyone's) and ``[b, s]`` targets -> every
    position's cross-entropy ``[b, s]`` in float32."""
    logp = jax.nn.log_softmax(lg.astype(F32), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def tail_logits(params, input_ids, config, count: int):
    """The logits of the last ``count`` positions, as a host array
    ``[b, count, vocab]``: the head is applied to those rows alone, a slice
    of the vocabulary at a time."""
    import numpy as np

    def tail(p, ids):
        with jax.default_matmul_precision("highest"):
            hidden = _hidden(p, ids, config)[:, -count:]
            out = _head_slices(p, hidden, lambda lg, _: lg)
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
