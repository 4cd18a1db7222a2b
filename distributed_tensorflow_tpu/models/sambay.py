"""A decoder-hybrid-decoder (SambaY) causal LM with a serving cache.

The first half of the stack is a self-decoder: pairs of a Mamba-1 layer
(``ops/ssm.py``'s selective scan: a state per channel and state element,
each with its own decay) and a layer of sliding-window attention.  The
second half reads what the first left behind:

  * layer ``L/2`` is Mamba-1; its gated scan output ``M = y * silu(z)``
    (before the out-projection) is the **memory**;
  * layer ``L/2 + 1`` is full causal attention; its K/V are the only K/V of
    the second half;
  * then pairs of a gated memory unit (``out = W_out (silu(W_in x) * M)``,
    no state of its own) and a cross-attention layer that has a query and an
    out-projection only and attends layer ``L/2 + 1``'s K/V.

Every attention layer is differential (``ops/attention.py``
``differential_attention``): two softmax maps a head, the second weighed by
a learned ``lambda`` and subtracted, the difference RMS-normed per head and
scaled by ``1 - lambda_init``.  Every block is pre-LayerNorm, its mixer
followed by a gated (SiLU) MLP; no positional encoding; tied head.

  * **Layers of one kind are stacked and scanned.**  The self-decoder's
    pairs are one stacked set under ``params["self"]`` (``"mamba"``,
    ``"window"``), the cross-decoder's under ``params["cross"]`` (``"gmu"``,
    ``"attention"``); layers ``L/2`` and ``L/2 + 1`` are trees of their own
    (``"memory"``, ``"full"``).  No weights are ever sliced out of a stack.
  * **What a slot's cache is made of** (``paged_cache_spec``): K/V pages for
    layer ``L/2 + 1`` alone (``kv_layers`` 1, a token's heads one flat row),
    and per slot a state block: the float32 Mamba state of every Mamba
    layer, the convolutions' last inputs, and each window layer's **ring**
    of ``window + prefill_chunk`` K/V rows, written at ``position % ring``.
    A window layer keeps nothing behind its window, so the ring rides in
    the slot's state row (and in its snapshots, ``serve/pages.py``) and the
    page manager sees one plane.
  * **Serving programs** are the hybrid decoder's (``models/hybrid.py``):
    ``decode_window_paged`` (a batch of prefill windows, state carried in
    from and out to each row's slot, padding rows that write nowhere) and
    ``decode_step_slots_paged`` (one token for every slot; a row that is not
    live keeps its state and its ring exactly).  In both the view of the
    shared plane is gathered ONCE, after layer ``L/2 + 1`` has written its
    tokens, and read by it and every cross-attention layer; see
    ``paged_kernel_ok``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import attention as attn_lib
from ..ops import initializers as init_lib
from ..ops import ssm
from ..parallel.sharding import constrain_batch

__all__ = ["SambaYConfig", "SambaY", "sambay_tiny"]

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class SambaYConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int                       # self-decoder half + cross half
    num_heads: int                        # query heads (two a differential head)
    num_kv_heads: int
    intermediate_size: int                # the gated MLP's inner width
    sliding_window: int                   # the self-decoder's attention
    d_state: int = 16                     # Mamba-1
    d_conv: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None         # None: ceil(hidden_size / 16)
    layer_norm_eps: float = 1e-5
    max_position: int = 8192              # serving's default max_len
    prefill_chunk: int = 32               # the widest prefill window
    dtype: Any = jnp.bfloat16             # compute
    param_dtype: Any = jnp.float32        # what ``init`` makes
    state_dtype: Any = jnp.float32        # the Mamba state
    conv_state_dtype: Any = jnp.bfloat16  # the convolutions' carried inputs
    initializer_range: float = 0.02       # matrices and biases
    lambda_std: float = 0.1               # the differential lambdas

    def __post_init__(self):
        if self.num_layers % 4 or self.num_layers < 8:
            raise ValueError("num_layers must be a multiple of 4, at least "
                             f"8; got {self.num_layers}")
        if (self.hidden_size % self.num_heads or self.num_kv_heads % 2
                or self.num_heads % self.num_kv_heads):
            raise ValueError("num_heads must divide hidden_size, "
                             "num_kv_heads must be even and divide num_heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def kv_width(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def rank(self) -> int:
        return self.dt_rank or -(-self.hidden_size // 16)

    @property
    def ring(self) -> int:
        """Rows of a window layer's ring: a window of ``prefill_chunk``
        queries reaches back ``sliding_window - 1`` keys before its
        first."""
        return self.sliding_window + self.prefill_chunk

    @property
    def self_pairs(self) -> int:
        return self.num_layers // 4

    @property
    def cross_pairs(self) -> int:
        return self.num_layers // 4 - 1

    @property
    def full_layer(self) -> int:
        """Index of the full-attention layer whose K/V the cross layers
        read (the memory layer is the one before it)."""
        return self.num_layers // 2 + 1


def sambay_tiny(**kw) -> "SambaY":
    """Eight layers — Mamba, window, Mamba, window, Mamba-memory, full, GMU,
    cross — at a toy width for tests."""
    base = dict(vocab_size=128, hidden_size=64, num_layers=8, num_heads=4,
                num_kv_heads=2, intermediate_size=96, sliding_window=8,
                max_position=128, dtype=jnp.float32,
                # 1/sqrt(width): activations of order one at a toy width,
                # so that state, window and memory carry weight in the logits
                initializer_range=0.125)
    base.update(kw)
    return SambaY(SambaYConfig(**base))


def _layer_norm(p, x, eps):
    with jax.named_scope("norm"):
        xf = x.astype(F32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        y = (xf - mean) * lax.rsqrt(var + eps)
        return (y * p["scale"].astype(F32)
                + p["bias"].astype(F32)).astype(x.dtype)


class SambaY:
    """Functional decoder: ``init`` -> params, ``apply`` -> hidden states
    ``[b, s, d]`` (after the final norm), ``logits`` -> LM logits."""

    # The Mosaic paged-attention kernel weighs one head's values by one
    # softmax; a differential head weighs a PAIR of value heads by two maps
    # and subtracts them, and the window layers' rows live in the slot's
    # ring, not in pages.  The scheduler takes the gather read path.
    paged_kernel_ok = False

    def __init__(self, config: SambaYConfig, mesh=None):
        self.config = config
        self.mesh = mesh

    @property
    def attention_window(self) -> int:
        """Keys a window layer's query sees (the scheduler counts a decode
        step's window reads by it)."""
        return self.config.sliding_window

    # ---------------------------------------------------------------- init

    def init(self, key) -> Dict[str, Any]:
        c = self.config
        dt = jnp.dtype(c.param_dtype)
        trunc = init_lib.truncated_normal(c.initializer_range)
        d, inner, n = c.hidden_size, c.d_inner, c.d_state

        def draw(k, shape):
            return trunc(k, shape, F32).astype(dt)

        def uniform(k, shape, bound):
            return jax.random.uniform(k, shape, F32, -bound, bound).astype(dt)

        def norm(width=d):
            return {"scale": jnp.ones((width,), dt),
                    "bias": jnp.zeros((width,), dt)}

        def block(k, mixer):
            ks = jax.random.split(k, 2)
            return {"ln_1": norm(), "ln_2": norm(), "mixer": mixer,
                    "ffn": {"w_in": {"kernel": draw(
                                ks[0], (d, 2 * c.intermediate_size))},
                            "w_out": {"kernel": draw(
                                ks[1], (c.intermediate_size, d))}}}

        def mamba(k):
            ks = jax.random.split(k, 9)
            # A uniform in [1, 16] and dt log-uniform in [1e-3, 0.1]
            # through an inverse softplus, as the hybrid's state-space
            # layers draw them: decay neither saturated nor zero
            a = jax.random.uniform(ks[0], (n, inner), F32, 1.0, 16.0)
            step = jnp.exp(jax.random.uniform(
                ks[1], (inner,), F32, math.log(1e-3), math.log(0.1)))
            return block(ks[8], {
                "in_proj": {"kernel": draw(ks[2], (d, 2 * inner))},
                # PyTorch's Conv1d default: +-1/sqrt(fan in), bias too
                "conv": {"kernel": uniform(ks[3], (c.d_conv, inner),
                                           c.d_conv ** -0.5),
                         "bias": uniform(ks[4], (inner,), c.d_conv ** -0.5)},
                "x_proj": {"kernel": draw(ks[5], (inner, c.rank + 2 * n))},
                # Mamba-1's own: +-1/sqrt(dt_rank), the bias dt's inverse
                "dt_proj": {"kernel": uniform(ks[6], (c.rank, inner),
                                              c.rank ** -0.5),
                            "bias": (step + jnp.log(-jnp.expm1(-step))
                                     ).astype(dt)},
                # [d_state, channels]: the state's own layout
                "a_log": jnp.log(a).astype(dt),
                "d_skip": jnp.ones((inner,), dt),
                "out_proj": {"kernel": draw(ks[7], (inner, d))}})

        def differential(k):
            ks = jax.random.split(k, 4)
            return {"lambda": {name: (c.lambda_std * jax.random.normal(
                        kk, (c.head_dim,), F32)).astype(dt)
                        for name, kk in zip(("q1", "k1", "q2", "k2"), ks)},
                    "subln": {"scale": jnp.ones((2 * c.head_dim,), dt)}}

        def projection(k, shape):
            ks = jax.random.split(k, 2)
            return {"kernel": draw(ks[0], shape),
                    "bias": draw(ks[1], shape[-1:])}

        def attention(k, query_only: bool):
            ks = jax.random.split(k, 4)
            q = c.num_heads * c.head_dim
            mixer = {"out": projection(ks[0], (q, d)), **differential(ks[1])}
            if query_only:
                mixer["query"] = projection(ks[2], (d, q))
            else:
                mixer["qkv"] = projection(ks[2], (d, q + 2 * c.kv_width))
            return block(ks[3], mixer)

        def gmu(k):
            ks = jax.random.split(k, 3)
            return block(ks[2], {"w_in": {"kernel": draw(ks[0], (d, inner))},
                                 "w_out": {"kernel": draw(ks[1], (inner, d))}})

        k_emb, k_self, k_mem, k_full, k_cross = jax.random.split(key, 5)
        return {
            "embeddings": {"word": draw(k_emb, (c.vocab_size, d))},
            # a pair at a time (``lax.map``): the float32 draws of a whole
            # stack at once would be twice its weights
            "self": lax.map(lambda k: {
                "mamba": mamba(jax.random.fold_in(k, 0)),
                "window": attention(jax.random.fold_in(k, 1), False)},
                jax.random.split(k_self, c.self_pairs)),
            "memory": mamba(k_mem),
            "full": attention(k_full, False),
            "cross": lax.map(lambda k: {
                "gmu": gmu(jax.random.fold_in(k, 0)),
                "attention": attention(jax.random.fold_in(k, 1), True)},
                jax.random.split(k_cross, c.cross_pairs)),
            "ln_f": norm(),
        }

    # -------------------------------------------------------------- pieces

    def _embed(self, params, ids):
        with jax.named_scope("embed"):
            return jnp.take(params["embeddings"]["word"], ids,
                            axis=0).astype(self.config.dtype)

    def _finish_block(self, p, x, mixed):
        """``x + mixer`` then ``+ mlp(norm(.))``."""
        x = x + mixed.astype(x.dtype)
        h = _layer_norm(p["ln_2"], x, self.config.layer_norm_eps)
        with jax.named_scope("mlp"):
            gate, up = jnp.split(
                h @ p["ffn"]["w_in"]["kernel"].astype(h.dtype), 2, axis=-1)
            out = (jax.nn.silu(gate) * up) @ p["ffn"]["w_out"][
                "kernel"].astype(h.dtype)
        return x + out

    # Mamba-1 -------------------------------------------------------------

    def _mamba_in(self, p, x):
        """The mixer's in-projection of ``norm(x)`` -> (x_in, z)."""
        u = _layer_norm(p["ln_1"], x, self.config.layer_norm_eps)
        xz = u @ p["mixer"]["in_proj"]["kernel"].astype(u.dtype)
        return jnp.split(xz, 2, axis=-1)

    def _mamba_select(self, m, xc, real, dtype):
        """The convolution's output -> (``silu`` of it, step ``dt``, B, C):
        ``[delta, B, C] = x W_x``, ``dt = softplus(delta W_dt + b_dt)``,
        zero where ``real`` (broadcastable bool) is False."""
        c = self.config
        xs = jax.nn.silu(xc.astype(F32))
        dbc = xs.astype(dtype) @ m["x_proj"]["kernel"].astype(dtype)
        delta = dbc[..., :c.rank]
        b_in = dbc[..., c.rank:c.rank + c.d_state]
        c_in = dbc[..., c.rank + c.d_state:]
        dt = ssm.softplus_dt(delta @ m["dt_proj"]["kernel"].astype(dtype),
                             m["dt_proj"]["bias"], real)
        return xs, dt, b_in, c_in

    def _mamba_out(self, p, x, y, xs, z):
        """``+ D x``, the gate -> (x after the block, the memory ``M`` =
        the gated output before the out-projection)."""
        m = p["mixer"]
        y = y + m["d_skip"].astype(F32) * xs
        gated = (y * jax.nn.silu(z.astype(F32))).astype(x.dtype)
        out = gated @ m["out_proj"]["kernel"].astype(x.dtype)
        return self._finish_block(p, x, out), gated

    def _mamba_block(self, p, x, h0, conv0, valid=None):
        """One Mamba-1 layer over a block ``x`` [b, s, d] with its state
        carried in (``h0`` [b, d_state, channels] float32, ``conv0`` [b,
        d_conv - 1, channels]) -> (x, state after the last REAL token,
        convolution inputs ending there, memory [b, s, channels])."""
        m = p["mixer"]
        x_in, z = self._mamba_in(p, x)
        xc, conv = ssm.causal_conv1d(x_in, m["conv"]["kernel"],
                                     m["conv"]["bias"], conv0, valid)
        real = (None if valid is None else
                (jnp.arange(x.shape[1]) < jnp.reshape(valid, (-1, 1))
                 )[:, :, None])
        xs, dt, b_in, c_in = self._mamba_select(m, xc, real, x.dtype)
        y, h = ssm.selective_scan(xs, dt, -jnp.exp(m["a_log"].astype(F32)),
                                  b_in, c_in, h0)
        x, memory = self._mamba_out(p, x, y, xs, z)
        return x, h, conv, memory

    def _mamba_step(self, p, x, h0, conv0, live):
        """One token for every row: ``x`` [b, 1, d]; a row that is not
        ``live`` keeps ``h0`` / ``conv0`` exactly."""
        m = p["mixer"]
        x_in, z = self._mamba_in(p, x)
        xc, conv = ssm.causal_conv1d(x_in, m["conv"]["kernel"],
                                     m["conv"]["bias"], conv0)
        xs, dt, b_in, c_in = self._mamba_select(m, xc[:, 0], live[:, None],
                                                x.dtype)
        y, h = ssm.selective_step(xs, dt, -jnp.exp(m["a_log"].astype(F32)),
                                  b_in, c_in, h0)
        conv = jnp.where(live[:, None, None], conv.astype(conv0.dtype),
                         conv0)
        x, memory = self._mamba_out(p, x, y[:, None], xs[:, None], z)
        return x, h, conv, memory

    # attention -----------------------------------------------------------

    def _qkv(self, p, x):
        """``norm(x)``'s query [b, s, heads, hd] and flat K/V rows [b, s,
        kv_width]."""
        c = self.config
        u = _layer_norm(p["ln_1"], x, c.layer_norm_eps)
        w = p["mixer"]["qkv"]
        y = u @ w["kernel"].astype(u.dtype) + w["bias"].astype(u.dtype)
        q = c.num_heads * c.head_dim
        return (y[..., :q].reshape(x.shape[:2] + (c.num_heads, c.head_dim)),
                y[..., q:q + c.kv_width], y[..., q + c.kv_width:])

    def _query(self, p, x):
        """A cross-attention layer's query of ``norm(x)``."""
        c = self.config
        u = _layer_norm(p["ln_1"], x, c.layer_norm_eps)
        w = p["mixer"]["query"]
        y = u @ w["kernel"].astype(u.dtype) + w["bias"].astype(u.dtype)
        return y.reshape(x.shape[:2] + (c.num_heads, c.head_dim))

    def _attend(self, p, x, q, k_rows, v_rows, mask, layer):
        """Differential attention of ``q`` over the rows under ``mask`` [b,
        sq, t], each head's norm and ``1 - lambda_init``, the out-projection
        and the MLP -> x after the block.  ``layer``: the stack index
        (traced inside a scan), which sets ``lambda_init``."""
        c = self.config
        m = p["mixer"]
        lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, F32))
        lv = {k: v.astype(F32) for k, v in m["lambda"].items()}
        lam = (jnp.exp(jnp.sum(lv["q1"] * lv["k1"]))
               - jnp.exp(jnp.sum(lv["q2"] * lv["k2"])) + lam_init)
        o = attn_lib.differential_attention(q, k_rows, v_rows, lam, mask,
                                            1.0 / math.sqrt(c.head_dim))
        o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + c.layer_norm_eps)
        o = o * m["subln"]["scale"].astype(F32) * (1.0 - lam_init)
        w = m["out"]
        out = (o.reshape(x.shape).astype(x.dtype)
               @ w["kernel"].astype(x.dtype) + w["bias"].astype(x.dtype))
        return self._finish_block(p, x, out)

    def _gmu(self, p, x, memory):
        """A gated memory unit: ``W_out (silu(W_in norm(x)) * M)``."""
        u = _layer_norm(p["ln_1"], x, self.config.layer_norm_eps)
        m = p["mixer"]
        with jax.named_scope("gmu"):
            g = (jax.nn.silu((u @ m["w_in"]["kernel"].astype(u.dtype)
                              ).astype(F32)) * memory.astype(F32))
            out = g.astype(x.dtype) @ m["w_out"]["kernel"].astype(x.dtype)
        return self._finish_block(p, x, out)

    def _cross_layer_index(self, i):
        return self.config.full_layer + 2 + 2 * i

    # ------------------------------------------------------------- forward

    def apply(self, params, input_ids, *, train: bool = False, rng=None):
        """``[b, s]`` ids -> ``[b, s, d]`` hidden states after the final
        norm, every Mamba layer starting from a zero state."""
        del train, rng                      # no dropout in this decoder
        c = self.config
        b, s = input_ids.shape
        x = constrain_batch(self._embed(params, input_ids), self.mesh)
        causal = attn_lib.causal_mask(s)[:, 0]
        pos = jnp.arange(s)[None]
        window = attn_lib.window_mask(pos, pos, c.sliding_window)
        h0 = jnp.zeros((b, c.d_state, c.d_inner), F32)

        def self_pair(x, inputs):
            p, i = inputs
            x = self._mamba_block(p["mamba"], x, h0, None)[0]
            q, k, v = self._qkv(p["window"], x)
            with jax.named_scope("window_attention"):
                x = self._attend(p["window"], x, q, k, v, window, 2 * i + 1)
            return x, None

        x, _ = lax.scan(self_pair, x,
                        (params["self"], jnp.arange(c.self_pairs)))
        x, _, _, memory = self._mamba_block(params["memory"], x, h0, None)
        q, k, v = self._qkv(params["full"], x)
        with jax.named_scope("shared_attention"):
            x = self._attend(params["full"], x, q, k, v, causal,
                             c.full_layer)

        def cross_pair(x, inputs):
            p, i = inputs
            x = self._gmu(p["gmu"], x, memory)
            with jax.named_scope("shared_attention"):
                x = self._attend(p["attention"], x,
                                 self._query(p["attention"], x), k, v,
                                 causal, self._cross_layer_index(i))
            return x, None

        x, _ = lax.scan(cross_pair, x,
                        (params["cross"], jnp.arange(c.cross_pairs)))
        return _layer_norm(params["ln_f"], x, c.layer_norm_eps)

    def logits(self, params, hidden):
        """Tied head -> ``[..., vocab]`` float32 logits."""
        with jax.named_scope("head"):
            word = params["embeddings"]["word"]
            return (hidden @ word.T.astype(hidden.dtype)).astype(F32)

    # --------------------------------------------------------------- cache

    def paged_cache_spec(self) -> Dict[str, Any]:
        """What a slot's cache is made of (``serve/pages.py`` builds the
        pool from it): per-token K/V rows of the one full-attention layer,
        and per slot the Mamba layers' state and convolution inputs and the
        window layers' rings."""
        c = self.config
        mamba = c.self_pairs + 1
        ring = ((c.ring, c.kv_width), jnp.dtype(c.dtype))
        return {
            "kv_layers": 1,
            "kv": {"k": ((c.kv_width,), jnp.dtype(c.dtype)),
                   "v": ((c.kv_width,), jnp.dtype(c.dtype))},
            "state": {
                "ssm": (mamba, (c.d_state, c.d_inner),
                        jnp.dtype(c.state_dtype)),
                "conv": (mamba, (c.d_conv - 1, c.d_inner),
                         jnp.dtype(c.conv_state_dtype)),
                "window_k": (c.self_pairs,) + ring,
                "window_v": (c.self_pairs,) + ring},
        }

    def _run_stack(self, params, x, kv, state, mamba_layer, window_layer,
                   pages, offs, table, mask):
        """The stack over a cache.  ``mamba_layer(p, i, x, state) -> (x,
        state, memory)`` and ``window_layer(p, i, x, state) -> (x, state)``
        on the i-th Mamba / window layer's part of ``state``, which rides
        the self-decoder's scan carry.  Then the full layer's K/V rows
        (row-major over ``x``'s batch and positions) go to pool cells
        ``(pages, offs)``, every row of ``table`` [b, pages_per_row] is
        gathered ONCE into a ``[b, view_len, kv_width]`` view, and the full
        layer and every cross layer attend it under the additive ``mask``
        [b, s, view_len] -> (x, kv, state)."""
        c = self.config

        def self_pair(carry, inputs):
            x, state = carry
            p, i = inputs
            x, state, _ = mamba_layer(p["mamba"], i, x, state)
            return window_layer(p["window"], i, x, state), None

        (x, state), _ = lax.scan(self_pair, (x, state),
                                 (params["self"], jnp.arange(c.self_pairs)))
        x, state, memory = mamba_layer(params["memory"], c.self_pairs, x,
                                       state)
        full = params["full"]
        q, k, v = self._qkv(full, x)
        kv = dict(kv)
        for name, val in (("k", k), ("v", v)):
            kv[name] = kv[name].at[0, pages, offs].set(
                val.reshape(-1, c.kv_width).astype(kv[name].dtype))
        # one gather, held as it is: no reader re-gathers its own copy
        views = lax.optimization_barrier(tuple(
            kv[name][0, table].reshape(x.shape[0], -1, c.kv_width)
            for name in ("k", "v")))
        with jax.named_scope("shared_attention"):
            x = self._attend(full, x, q, *views, mask, c.full_layer)

        def cross_pair(x, inputs):
            p, i = inputs
            x = self._gmu(p["gmu"], x, memory)
            with jax.named_scope("shared_attention"):
                x = self._attend(p["attention"], x,
                                 self._query(p["attention"], x), *views,
                                 mask, self._cross_layer_index(i))
            return x, None

        x, _ = lax.scan(cross_pair, x,
                        (params["cross"], jnp.arange(c.cross_pairs)))
        return x, kv, state

    def decode_window_paged(self, params, kv, token_ids, page_row, pos,
                            head: str = "all", *, state, slot, valid,
                            adapters=None, adapter_rows=None,
                            use_kernel: bool = False):
        """A batch of prefill windows against the paged cache, in the hybrid
        decoder's form (``models/hybrid.py``): row r of ``token_ids`` [n, s]
        is one request's window at positions ``pos[r] ..`` of which the
        first ``valid[r]`` are real, the full layer's K/V written through
        ``page_row[r]``, slot ``slot[r]``'s state read (zero at position 0)
        and written back after the last real token.  A window layer writes
        the row's real tokens into the slot's ring at ``position % ring``
        and reads the ring back, each entry at the position it holds: a
        window of ``s <= prefill_chunk`` tokens never overwrites a key one
        of its queries sees.  A row with ``valid == 0`` is padding: it
        writes no slot's state or ring and only the trash page of the pool.
        Returns ``(logits, kv, state)``: logits ``[n, s, vocab]``, for
        ``head="last"`` ``[n, vocab]`` at each row's last real position,
        None for ``head="none"``."""
        if head not in ("all", "last", "none"):
            raise ValueError(f"head must be all|last|none; got {head!r}")
        if adapters is not None or use_kernel:
            raise ValueError("this decoder has no adapter path and reads "
                             "its pages through the gather path")
        c = self.config
        n, s = token_ids.shape
        if s > c.prefill_chunk:
            raise ValueError(f"a window of {s} tokens overruns the rings of "
                             f"{c.ring} rows (prefill_chunk "
                             f"{c.prefill_chunk})")
        page_size = kv["k"].shape[2]
        win = attn_lib.paged_windows(page_row, pos, valid, n, s, page_size)
        valid = win.valid
        # a padding row names no slot: it reads the last one's state and
        # its writes are dropped
        slots = state["ssm"].shape[1]
        slot = jnp.where(valid > 0, jnp.broadcast_to(
            jnp.asarray(slot, jnp.int32).reshape(-1), (n,)), slots)
        read = jnp.minimum(slot, slots - 1)
        fresh = win.pos == 0
        x = self._embed(params, token_ids)
        ring_col = jnp.where(jnp.arange(s)[None, :] < valid[:, None],
                             win.cols % c.ring, c.ring)
        held = attn_lib.ring_positions(win.pos + valid - 1, c.ring)
        ring_mask = attn_lib.window_mask(win.cols, held, c.sliding_window)

        def mamba_layer(p, i, x, state):
            h0 = jnp.where(fresh[:, None, None], 0.0,
                           state["ssm"][i, read].astype(F32))
            conv0 = state["conv"][i, read]
            conv0 = jnp.where(fresh[:, None, None], jnp.zeros_like(conv0),
                              conv0)
            x, h, conv, memory = self._mamba_block(p, x, h0, conv0, valid)
            return x, dict(
                state,
                ssm=state["ssm"].at[i, slot].set(
                    h.astype(state["ssm"].dtype), mode="drop"),
                conv=state["conv"].at[i, slot].set(
                    conv.astype(state["conv"].dtype), mode="drop")), memory

        def window_layer(p, i, x, state):
            q, k, v = self._qkv(p, x)
            state = dict(state)
            for name, val in (("window_k", k), ("window_v", v)):
                state[name] = state[name].at[i, slot[:, None], ring_col].set(
                    val.astype(state[name].dtype), mode="drop")
            with jax.named_scope("window_attention"):
                x = self._attend(p, x, q, state["window_k"][i, read],
                                 state["window_v"][i, read], ring_mask,
                                 2 * i + 1)
            return x, state

        x, kv, state = self._run_stack(
            params, x, kv, state, mamba_layer, window_layer, win.pages,
            win.offs, win.page_rows,
            attn_lib.paged_window_mask(win, page_size)[:, 0])
        if head == "none":
            return None, kv, state
        if head == "last":
            x = attn_lib.last_real_position(x, valid)
        x = _layer_norm(params["ln_f"], x, c.layer_norm_eps)
        logits = self.logits(params, x)
        return (logits[:, 0] if head == "last" else logits), kv, state

    def decode_step_slots_paged(self, params, kv, token_ids, page_tab,
                                start_col, write_col, positions, *, state,
                                live, adapters=None, adapter_rows=None,
                                use_kernel: bool = False):
        """One token for every slot against the paged cache (the serving
        decode step): row r writes its full-layer K/V at logical column
        ``write_col[r]`` through ``page_tab[r]`` and its window layers' K/V
        into its ring at ``positions[r] % ring``, attends the columns from
        ``start_col[r]`` up to and with its own and the ring's last
        ``sliding_window`` positions, and advances row r of ``state`` —
        unless it is not ``live``: such a row's state, convolution inputs
        and ring come back unchanged (its pool write lands wherever its
        table points: the trash page once retired).  Returns ``(logits [b,
        vocab], kv, state)``."""
        if adapters is not None or use_kernel:
            raise ValueError("this decoder has no adapter path and reads "
                             "its pages through the gather path")
        c = self.config
        rows = jnp.arange(token_ids.shape[0])
        page_size = kv["k"].shape[2]
        cols = jnp.arange(page_tab.shape[1] * page_size)[None, :]
        mask = jnp.where((cols >= start_col[:, None])
                         & (cols <= write_col[:, None]), 0.0,
                         attn_lib.NEG_INF)[:, None, :]
        page_idx = jnp.minimum(write_col // page_size, page_tab.shape[1] - 1)
        w_pages = jnp.take_along_axis(page_tab, page_idx[:, None],
                                      axis=1)[:, 0]
        ring_col = jnp.where(live, positions % c.ring, c.ring)
        ring_mask = attn_lib.window_mask(
            positions[:, None], attn_lib.ring_positions(positions, c.ring),
            c.sliding_window)
        x = self._embed(params, token_ids)[:, None, :]

        def mamba_layer(p, i, x, state):
            h0 = lax.dynamic_index_in_dim(state["ssm"], i, keepdims=False)
            conv0 = lax.dynamic_index_in_dim(state["conv"], i,
                                             keepdims=False)
            x, h, conv, memory = self._mamba_step(p, x, h0.astype(F32),
                                                  conv0, live)
            zero = jnp.zeros((), jnp.int32)
            return x, dict(
                state,
                ssm=lax.dynamic_update_slice(
                    state["ssm"], h.astype(state["ssm"].dtype)[None],
                    (i,) + (zero,) * h.ndim),
                conv=lax.dynamic_update_slice(
                    state["conv"], conv[None], (i,) + (zero,) * conv.ndim)
            ), memory

        def window_layer(p, i, x, state):
            q, k, v = self._qkv(p, x)
            state = dict(state)
            for name, val in (("window_k", k), ("window_v", v)):
                state[name] = state[name].at[i, rows, ring_col].set(
                    val[:, 0].astype(state[name].dtype), mode="drop")
            with jax.named_scope("window_attention"):
                x = self._attend(p, x, q, state["window_k"][i],
                                 state["window_v"][i], ring_mask, 2 * i + 1)
            return x, state

        x, kv, state = self._run_stack(params, x, kv, state, mamba_layer,
                                       window_layer, w_pages,
                                       write_col % page_size, page_tab, mask)
        x = _layer_norm(params["ln_f"], x, c.layer_norm_eps)
        return self.logits(params, x)[:, 0, :], kv, state
