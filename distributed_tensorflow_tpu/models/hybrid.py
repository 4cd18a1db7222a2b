"""A decoder of state-space and attention layers (causal LM, serving cache).

The stack's layers are of two kinds in a configured order
(``HybridConfig.layer_types``): a selective state-space mixer
(``ops/ssm.py``: short depthwise convolution, per-head recurrent state,
gated norm) and grouped-query softmax attention with NO positional encoding
— order comes from the recurrence.  Every layer is pre-RMSNorm, its mixer
followed by a gated (SwiGLU) MLP; four scalar multipliers scale the
embedding, each residual branch, the attention logits and the LM logits.
The head is tied to the embedding.

  * **Layers of one kind are stacked and scanned, the order holds.**  A
    maximal run of state-space layers is one stacked parameter set under
    ``params["segments"][i]`` applied with ``lax.scan``; the attention
    layers between the runs are separate trees under
    ``params["attention"][j]``.  No run's weights are ever sliced out of a
    larger stack (a slice feeding a loop is a copy of the weights).
  * **What a slot's cache is made of** (``paged_cache_spec``): K/V pages for
    the attention layers only, each token's heads flattened to one
    ``kv_heads * head_dim`` row (512 lanes wide here: the page pool tiles
    without padding), and per slot one recurrent-state block — the
    float32 state ``H`` of every state-space layer and the convolution's
    last ``width - 1`` inputs.  ``serve/pages.py`` builds the pool from
    this description.
  * **Serving programs** mirror ``models/gpt.py``: ``decode_window_paged``
    (a batch of requests' chunked-prefill windows, each one's state carried
    in from its slot's row — zero at position 0 — and out after the window's
    last REAL token) and ``decode_step_slots_paged`` (one token for every slot; a row
    that is not live keeps its state and convolution inputs exactly).  The
    attention reads gather the row's pages; see ``paged_kernel_ok``.

Attention over the flat K/V rows keeps them flat: a query head's vector is
placed in the 64 lanes of its K/V head (zeros elsewhere), so scores and
context are plain matmuls against ``[tokens, kv_heads * head_dim]`` and the
gathered pages are never re-laid-out; the matmuls are ``kv_heads`` times
larger than needed and still far under the time the cache read takes.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops import attention as attn_lib
from ..ops import initializers as init_lib
from ..ops import losses as loss_lib
from ..ops import ssm
from ..parallel.sharding import PartitionRules, constrain_batch

__all__ = ["HybridConfig", "HybridDecoder", "hybrid_tiny"]

F32 = jnp.float32
MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int
    hidden_size: int
    layer_types: Tuple[str, ...]          # "mamba" | "attention", in order
    num_heads: int                        # attention query heads
    num_kv_heads: int
    intermediate_size: int                # the gated MLP's inner width
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int = 1
    conv_width: int = 4
    layer_norm_eps: float = 1e-5
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None   # None: 1/sqrt(head_dim)
    logits_scaling: float = 1.0
    max_position: int = 4096              # serving's default max_len
    dtype: Any = jnp.bfloat16             # compute
    param_dtype: Any = jnp.float32        # what ``init`` makes
    state_dtype: Any = jnp.float32        # the recurrent state H
    conv_state_dtype: Any = jnp.bfloat16  # the convolution's carried inputs
    ssm_chunk: int = 64                   # full-sequence forward's chunk
    initializer_range: float = 0.02       # matrices: truncated normal
    dropout_rate: float = 0.0

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = set(self.layer_types) - {MAMBA, ATTENTION}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types holds {MAMBA!r} / {ATTENTION!r}; "
                             f"got {sorted(bad) or 'nothing'}")
        if self.ssm_groups != 1:
            raise ValueError("one B/C group is implemented; got "
                             f"ssm_groups={self.ssm_groups}")
        if self.hidden_size % self.num_heads \
                or self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must divide hidden_size and "
                             "num_kv_heads must divide num_heads")
        if self.dropout_rate:
            raise ValueError("dropout is not implemented in this decoder")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads

    @property
    def kv_width(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def num_mamba(self) -> int:
        return sum(t == MAMBA for t in self.layer_types)

    @property
    def num_attention(self) -> int:
        return self.num_layers - self.num_mamba

    @property
    def plan(self) -> Tuple[Tuple[str, int, int], ...]:
        """The stack in order: ``("mamba", first state-space index, count)``
        for a maximal run (``params["segments"][k]`` for the k-th run) and
        ``("attention", j, 1)`` for the j-th attention layer."""
        out: List[Tuple[str, int, int]] = []
        m = a = 0
        for kind in self.layer_types:
            if kind == MAMBA:
                if out and out[-1][0] == MAMBA:
                    out[-1] = (MAMBA, out[-1][1], out[-1][2] + 1)
                else:
                    out.append((MAMBA, m, 1))
                m += 1
            else:
                out.append((ATTENTION, a, 1))
                a += 1
        return tuple(out)


def hybrid_tiny(**kw) -> "HybridDecoder":
    """A toy of two periods of ``[m, m, a, m]`` for tests."""
    base = dict(vocab_size=128, hidden_size=64,
                layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA) * 2,
                num_heads=4, num_kv_heads=2, intermediate_size=96,
                ssm_heads=4, ssm_head_dim=16, ssm_state=16,
                embedding_multiplier=3.0, residual_multiplier=0.5,
                attention_multiplier=0.2, logits_scaling=2.0,
                max_position=128, dtype=jnp.float32, ssm_chunk=8,
                # 1/sqrt(width): activations of order one at a toy width,
                # so that the recurrent state carries weight in the logits
                initializer_range=0.125)
    base.update(kw)
    return HybridDecoder(HybridConfig(**base))


def _rms_norm(p, x, eps):
    with jax.named_scope("norm"):
        xf = x.astype(F32)
        y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                           + eps)
        return (y * p["gamma"].astype(F32)).astype(x.dtype)


class HybridDecoder:
    """Functional decoder: ``init`` -> params, ``apply`` -> hidden states
    ``[b, s, d]`` (after the final norm), ``logits`` -> LM logits."""

    # The Mosaic paged-attention kernel reads the flat rows this pool has
    # (one layout for every family), but it walks every page of a slot's
    # table, one page a grid step: at 4096-token tables it would spend its
    # time on grid steps over unmapped pages.  The scheduler takes the
    # gather read path for a model that says so.
    paged_kernel_ok = False

    def __init__(self, config: HybridConfig, mesh=None):
        self.config = config
        self.mesh = mesh

    # ---------------------------------------------------------------- init

    def init(self, key) -> Dict[str, Any]:
        c = self.config
        dt = jnp.dtype(c.param_dtype)
        trunc = init_lib.truncated_normal(c.initializer_range)
        d, inner = c.hidden_size, c.intermediate_size

        def draw(k, shape):
            return trunc(k, shape, F32).astype(dt)

        def common(ks):
            return {
                "ln_1": {"gamma": jnp.ones((d,), dt)},
                "ln_2": {"gamma": jnp.ones((d,), dt)},
                "ffn": {"w_in": {"kernel": draw(ks[0], (d, 2 * inner))},
                        "w_out": {"kernel": draw(ks[1], (inner, d))}},
            }

        def mamba_layer(k):
            ks = jax.random.split(k, 7)
            heads = c.ssm_heads
            # the ranges a trained state-space model's A and dt lie in
            # (the public Mamba-2 initialisation): A uniform in [1, 16],
            # dt log-uniform in [1e-3, 0.1] through an inverse softplus,
            # so that decay and step are neither saturated nor zero
            a = jax.random.uniform(ks[4], (heads,), F32, 1.0, 16.0)
            step = jnp.exp(jax.random.uniform(
                ks[5], (heads,), F32, math.log(1e-3), math.log(0.1)))
            layer = common(ks)
            layer["mixer"] = {
                # the in-projection [z, xBC, dt], one matrix a part: the
                # fused width (8512 here) is no multiple of a TPU lane
                # tile, and XLA re-lays a matrix of that width out on
                # every use
                "in_proj": {
                    "z": {"kernel": draw(ks[2], (d, c.d_inner))},
                    "xbc": {"kernel": draw(jax.random.fold_in(ks[2], 1),
                                           (d, c.conv_dim))},
                    "dt": {"kernel": draw(jax.random.fold_in(ks[2], 2),
                                          (d, heads))}},
                "conv": {"kernel": (jax.random.uniform(
                    ks[6], (c.conv_width, c.conv_dim), F32, -1.0, 1.0)
                    / math.sqrt(c.conv_width)).astype(dt),
                    "bias": jnp.zeros((c.conv_dim,), dt)},
                "a_log": jnp.log(a).astype(dt),
                "d_skip": jnp.ones((heads,), dt),
                "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
                "norm": {"gamma": jnp.ones((c.d_inner,), dt)},
                "out_proj": {"kernel": draw(ks[3], (c.d_inner, d))},
            }
            return layer

        def attention_layer(k):
            ks = jax.random.split(k, 6)
            h, kv, hd = c.num_heads, c.num_kv_heads, c.head_dim
            layer = common(ks)
            layer["mixer"] = {
                "query": {"kernel": draw(ks[2], (d, h, hd))},
                "key": {"kernel": draw(ks[3], (d, kv, hd))},
                "value": {"kernel": draw(ks[4], (d, kv, hd))},
                "out": {"kernel": draw(ks[5], (h, hd, d))},
            }
            return layer

        k_emb, k_m, k_a = jax.random.split(key, 3)
        m_keys = jax.random.split(k_m, max(c.num_mamba, 1))
        a_keys = jax.random.split(k_a, max(c.num_attention, 1))
        return {
            "embeddings": {"word": draw(k_emb, (c.vocab_size, d))},
            # a layer at a time (``lax.map``, not ``vmap``): the float32
            # draws of a whole run at once are twice the run's weights
            "segments": [lax.map(mamba_layer, m_keys[first:first + n])
                         for kind, first, n in c.plan if kind == MAMBA],
            "attention": [attention_layer(a_keys[j])
                          for kind, j, _ in c.plan if kind == ATTENTION],
            "ln_f": {"gamma": jnp.ones((d,), dt)},
        }

    # -------------------------------------------------------------- pieces

    def _embed(self, params, ids):
        with jax.named_scope("embed"):
            x = jnp.take(params["embeddings"]["word"], ids, axis=0)
            return (x.astype(F32) * self.config.embedding_multiplier
                    ).astype(self.config.dtype)

    def _mlp(self, p, x):
        with jax.named_scope("mlp"):
            dtype = x.dtype
            both = x @ p["w_in"]["kernel"].astype(dtype)
            gate, up = jnp.split(both, 2, axis=-1)
            return (jax.nn.silu(gate) * up) @ p["w_out"]["kernel"].astype(
                dtype)

    def _finish_block(self, p, x, mixed):
        """``x + r * mixer`` then ``+ r * mlp(norm(.))``."""
        c = self.config
        r = c.residual_multiplier
        x = x + (r * mixed.astype(F32)).astype(x.dtype)
        h = _rms_norm(p["ln_2"], x, c.layer_norm_eps)
        return x + (r * self._mlp(p["ffn"], h).astype(F32)).astype(x.dtype)

    def _ssm_inputs(self, p, u):
        """The mixer's projections of ``u`` [b, s, d]: gate ``z``, the
        convolution's input ``xBC`` and the raw step ``dt``."""
        proj = p["mixer"]["in_proj"]
        return tuple(u @ proj[part]["kernel"].astype(u.dtype)
                     for part in ("z", "xbc", "dt"))

    def _ssm_split(self, xbc_conv):
        """silu, then x [.., heads, p], B [.., n], C [.., n]."""
        c = self.config
        act = jax.nn.silu(xbc_conv)
        x = act[..., :c.d_inner].reshape(
            act.shape[:-1] + (c.ssm_heads, c.ssm_head_dim))
        b_in = act[..., c.d_inner:c.d_inner + c.ssm_state]
        c_in = act[..., c.d_inner + c.ssm_state:]
        return x, b_in, c_in

    def _ssm_out(self, p, y, x, z, dtype):
        """``+ D x``, the gated norm over all channels, the projection."""
        c = self.config
        m = p["mixer"]
        y = y + m["d_skip"].astype(F32)[:, None] * x.astype(F32)
        y = ssm.gated_rms_norm(y.reshape(y.shape[:-2] + (c.d_inner,)), z,
                               m["norm"]["gamma"], c.layer_norm_eps)
        return y.astype(dtype) @ m["out_proj"]["kernel"].astype(dtype)

    def _mamba_block(self, p, x, h0, conv0, valid=None):
        """One state-space layer over a block ``x`` [b, s, d] with the state
        carried in (``h0`` [b, heads, p, n] float32, ``conv0``
        [b, width-1, conv_dim]) -> (x, state after the last REAL token,
        convolution inputs ending there)."""
        c = self.config
        m = p["mixer"]
        u = _rms_norm(p["ln_1"], x, c.layer_norm_eps)
        z, xbc, dt_raw = self._ssm_inputs(p, u)
        xbc_conv, conv = ssm.causal_conv1d(
            xbc, m["conv"]["kernel"], m["conv"]["bias"], conv0, valid)
        xs, b_in, c_in = self._ssm_split(xbc_conv)
        real = (None if valid is None else
                (jnp.arange(x.shape[1]) < jnp.reshape(valid, (-1, 1))
                 )[:, :, None])
        dt = ssm.softplus_dt(dt_raw, m["dt_bias"], real)
        a = -jnp.exp(m["a_log"].astype(F32))
        y, h = ssm.ssd_chunked(xs, dt, a, b_in, c_in, h0, c.ssm_chunk)
        mixed = self._ssm_out(p, y, xs, z, x.dtype)
        return self._finish_block(p, x, mixed), h, conv

    def _mamba_step(self, p, x, h0, conv0, live):
        """One token for every row: ``x`` [b, 1, d]; a row that is not
        ``live`` keeps ``h0`` / ``conv0`` exactly."""
        c = self.config
        m = p["mixer"]
        u = _rms_norm(p["ln_1"], x, c.layer_norm_eps)
        z, xbc, dt_raw = self._ssm_inputs(p, u)
        xbc_conv, conv = ssm.causal_conv1d(
            xbc, m["conv"]["kernel"], m["conv"]["bias"], conv0)
        xs, b_in, c_in = self._ssm_split(xbc_conv[:, 0])
        dt = ssm.softplus_dt(dt_raw[:, 0], m["dt_bias"], live[:, None])
        a = -jnp.exp(m["a_log"].astype(F32))
        y, h = ssm.ssd_step(xs, dt, a, b_in, c_in, h0)
        conv = jnp.where(live[:, None, None], conv.astype(conv0.dtype),
                         conv0)
        mixed = self._ssm_out(p, y[:, None], xs[:, None], z, x.dtype)
        return self._finish_block(p, x, mixed), h, conv

    def _qkv(self, p, x):
        c = self.config
        a = p["mixer"]
        h = _rms_norm(p["ln_1"], x, c.layer_norm_eps)
        dtype = h.dtype
        q = jnp.einsum("bsd,dhk->bshk", h, a["query"]["kernel"].astype(dtype))
        k = jnp.einsum("bsd,dhk->bshk", h, a["key"]["kernel"].astype(dtype))
        v = jnp.einsum("bsd,dhk->bshk", h, a["value"]["kernel"].astype(dtype))
        return q, k, v

    def _attn_scale(self) -> float:
        c = self.config
        return (c.attention_multiplier if c.attention_multiplier is not None
                else 1.0 / math.sqrt(c.head_dim))

    def _attention_out(self, p, x, ctx):
        out = jnp.einsum("bshk,hkd->bsd", ctx,
                         p["mixer"]["out"]["kernel"].astype(ctx.dtype))
        return self._finish_block(p, x, out)

    def _attention_block(self, p, x, mask):
        """Full-sequence causal attention (no positions)."""
        q, k, v = self._qkv(p, x)
        with jax.named_scope("attention"):
            ctx = attn_lib.dot_product_attention(q, k, v, mask=mask,
                                                 scale=self._attn_scale())
        return self._attention_out(p, x, ctx)

    def _attend_flat(self, q, k_view, v_view, mask):
        """``q`` [b, sq, heads, hd] against flat K/V rows ``[b, t, kv_heads
        * hd]`` under an additive ``mask`` [b, 1, sq, t] (module doc)."""
        c = self.config
        b, sq, heads, hd = q.shape
        kvh = c.num_kv_heads
        owner = jax.nn.one_hot(jnp.arange(heads) // (heads // kvh), kvh,
                               dtype=q.dtype)                 # [heads, kvh]
        with jax.named_scope("attention"):
            q_flat = (q[:, :, :, None, :] * owner[None, None, :, :, None]
                      ).reshape(b, sq, heads, kvh * hd)
            scores = jnp.einsum("bqhc,btc->bhqt", q_flat,
                                k_view.astype(q.dtype)).astype(F32)
            scores = scores * self._attn_scale() + mask
            probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
            ctx = jnp.einsum("bhqt,btc->bqhc", probs,
                             v_view.astype(q.dtype))
            ctx = ctx.reshape(b, sq, heads, kvh, hd)
            return jnp.einsum("bqhgd,hg->bqhd", ctx, owner)

    def _cached_attention(self, p, layer, x, kv, pages, offs, table, mask):
        """One attention layer against the page pool: the block's K/V rows
        (flattened ``[tokens, kv_width]``, row-major over ``x``'s batch and
        positions) written to pool cells ``(pages, offs)`` of plane
        ``layer``, then every row of ``table`` [b, pages_per_row] gathered
        into a ``[b, view_len, kv_width]`` view and attended under the
        additive ``mask`` -> (x after the block, kv)."""
        c = self.config
        b = x.shape[0]
        q, k, v = self._qkv(p, x)
        kv = dict(kv)
        for name, val in (("k", k), ("v", v)):
            flat = val.reshape(-1, c.kv_width).astype(kv[name].dtype)
            kv[name] = kv[name].at[layer, pages, offs].set(flat)
        views = [kv[name][layer, table].reshape(b, -1, c.kv_width)
                 for name in ("k", "v")]
        return self._attention_out(
            p, x, self._attend_flat(q, *views, mask)), kv

    # ------------------------------------------------------------- forward

    def apply(self, params, input_ids, *, train: bool = False, rng=None):
        """``[b, s]`` ids -> ``[b, s, d]`` hidden states after the final
        norm, every state-space layer starting from a zero state."""
        del train, rng                      # no dropout in this decoder
        c = self.config
        b, s = input_ids.shape
        x = constrain_batch(self._embed(params, input_ids), self.mesh)
        mask = attn_lib.causal_mask(s)
        h0 = jnp.zeros((b, c.ssm_heads, c.ssm_head_dim, c.ssm_state), F32)

        def mamba(x, p):
            return self._mamba_block(p, x, h0, None)[0], None

        def attention(p, x):
            return self._attention_block(p, x, mask)

        segments = iter(params["segments"])
        for kind, j, _ in c.plan:
            if kind == MAMBA:
                x, _ = lax.scan(mamba, x, next(segments))
            else:
                x = attention(params["attention"][j], x)
        return _rms_norm(params["ln_f"], x, c.layer_norm_eps)

    def logits(self, params, hidden):
        """Tied head -> ``[..., vocab]`` float32 logits, divided by
        ``logits_scaling``."""
        with jax.named_scope("head"):
            word = params["embeddings"]["word"]
            return ((hidden @ word.T.astype(hidden.dtype)).astype(F32)
                    / self.config.logits_scaling)

    def lm_loss_fn(self):
        """``train.make_custom_train_step``'s contract, as ``GPT``'s."""

        def loss_fn(params, model_state, batch, rng, train):
            ids = batch["input_ids"]
            hidden = self.apply(params, ids[:, :-1], train=train, rng=rng)
            targets = ids[:, 1:]
            mask = batch.get("loss_mask")
            with jax.named_scope("head_loss"):
                lg = self.logits(params, hidden)
                loss = loss_lib.softmax_cross_entropy_with_integer_labels(
                    lg, targets, where=mask)
                hits = (jnp.argmax(lg, -1) == targets).astype(F32)
                acc = (jnp.mean(hits) if mask is None else
                       jnp.sum(hits * mask) / jnp.maximum(jnp.sum(mask), 1.0))
            metrics = {"token_accuracy": acc}
            if mask is not None:
                metrics["loss_weight"] = jnp.sum(mask).astype(F32)
            return loss, (metrics, model_state)

        return loss_fn

    # --------------------------------------------------------------- cache

    def paged_cache_spec(self) -> Dict[str, Any]:
        """What a slot's cache is made of (``serve/pages.py`` builds the
        pool from it): per-token K/V rows for the attention layers, and
        per-slot state blocks for the state-space layers."""
        c = self.config
        return {
            "kv_layers": c.num_attention,
            "kv": {"k": ((c.kv_width,), jnp.dtype(c.dtype)),
                   "v": ((c.kv_width,), jnp.dtype(c.dtype))},
            "state": {
                "ssm": (c.num_mamba,
                        (c.ssm_heads, c.ssm_head_dim, c.ssm_state),
                        jnp.dtype(c.state_dtype)),
                "conv": (c.num_mamba, (c.conv_width - 1, c.conv_dim),
                         jnp.dtype(c.conv_state_dtype))},
        }

    def _run_stack(self, params, x, kv, state, mamba_layer, attention_layer):
        """The stack in order over a cache: ``mamba_layer(p, x, h, conv)
        -> (x, h, conv)`` on one state-space layer's state (already cut to
        the rows in play), ``attention_layer(p, j, x, kv) -> (x, kv)``.
        The state arrays ride each run's scan CARRY and are read and
        written one layer slab at a time, in place."""
        c = self.config
        ssm_state, conv_state = state["ssm"], state["conv"]
        segments = iter(params["segments"])
        for kind, first, n in c.plan:
            if kind == ATTENTION:
                x, kv = attention_layer(params["attention"][first], first,
                                        x, kv)
                continue

            def body(carry, inputs):
                x, ssm_state, conv_state = carry
                p, i = inputs
                x, ssm_state, conv_state = mamba_layer(
                    p, i, x, ssm_state, conv_state)
                return (x, ssm_state, conv_state), None

            (x, ssm_state, conv_state), _ = lax.scan(
                body, (x, ssm_state, conv_state),
                (next(segments), first + jnp.arange(n)))
        return x, kv, {"ssm": ssm_state, "conv": conv_state}

    def decode_window_paged(self, params, kv, token_ids, page_row, pos,
                            head: str = "all", *, state, slot, valid,
                            adapters=None, adapter_rows=None,
                            use_kernel: bool = False):
        """A batch of prefill windows against the paged cache: row r of
        ``token_ids`` [n, s] is one request's window, ``s`` tokens at
        positions ``pos[r] .. pos[r] + s - 1`` of which the first
        ``valid[r]`` are real, K/V written through ``page_row[r]`` [n,
        pages_per_row], the recurrent state read from row ``slot[r]`` of
        ``state`` (zero when ``pos[r]`` is 0: a sequence's start) and
        written back after the last real token.  The batch-1 call form (a
        rank-1 ``page_row``, scalar ``pos`` / ``slot`` / ``valid``) is the
        n = 1 case of the same code; the windows of one dispatch read the
        weights once.  ``pos`` need not be a page or window boundary: a
        request resumed from a state snapshot starts wherever the snapshot
        was taken.  Pad columns are written to the reserved trash page; a
        row with ``valid == 0`` is PADDING of the batch: it writes nowhere
        else, and no slot's state or convolution inputs (the write back is
        a scatter by slot that drops such rows).  Returns ``(logits, kv,
        state)``: logits ``[n, s, vocab]``, for ``head="last"`` ``[n,
        vocab]`` at each row's last real position (taken before the head
        matmul), None for ``head="none"``."""
        if head not in ("all", "last", "none"):
            raise ValueError(f"head must be all|last|none; got {head!r}")
        if adapters is not None or use_kernel:
            raise ValueError("this decoder has no adapter path and reads "
                             "its pages through the gather path")
        c = self.config
        n, s = token_ids.shape
        page_size = kv["k"].shape[2]
        win = attn_lib.paged_windows(page_row, pos, valid, n, s, page_size)
        valid = win.valid
        # a padding row names no slot: it reads the last one's state and
        # its write is dropped
        slots = state["ssm"].shape[1]
        slot = jnp.where(valid > 0, jnp.broadcast_to(
            jnp.asarray(slot, jnp.int32).reshape(-1), (n,)), slots)
        read = jnp.minimum(slot, slots - 1)
        x = self._embed(params, token_ids)
        fresh = win.pos == 0
        mask = attn_lib.paged_window_mask(win, page_size)

        def mamba_layer(p, i, x, ssm_state, conv_state):
            h0 = jnp.where(fresh[:, None, None, None], 0.0,
                           ssm_state[i, read].astype(F32))
            conv0 = conv_state[i, read]
            conv0 = jnp.where(fresh[:, None, None], jnp.zeros_like(conv0),
                              conv0)
            x, h, conv = self._mamba_block(p, x, h0, conv0, valid)
            ssm_state = ssm_state.at[i, slot].set(
                h.astype(ssm_state.dtype), mode="drop")
            conv_state = conv_state.at[i, slot].set(
                conv.astype(conv_state.dtype), mode="drop")
            return x, ssm_state, conv_state

        def attention_layer(p, layer, x, kv):
            return self._cached_attention(p, layer, x, kv, win.pages,
                                          win.offs, win.page_rows, mask)

        x, kv, state = self._run_stack(params, x, kv, state, mamba_layer,
                                       attention_layer)
        if head == "none":
            return None, kv, state
        if head == "last":
            x = attn_lib.last_real_position(x, valid)
        x = _rms_norm(params["ln_f"], x, c.layer_norm_eps)
        logits = self.logits(params, x)
        return (logits[:, 0] if head == "last" else logits), kv, state

    def decode_step_slots_paged(self, params, kv, token_ids, page_tab,
                                start_col, write_col, positions, *, state,
                                live, adapters=None, adapter_rows=None,
                                use_kernel: bool = False):
        """One token for every slot against the paged cache (the serving
        decode step): row r writes its K/V at logical column
        ``write_col[r]`` through ``page_tab[r]``, attends the columns from
        ``start_col[r]`` up to and with its own, and advances row r of
        ``state`` — unless it is not ``live``: such a row's state and
        convolution inputs come back
        unchanged (its K/V write lands wherever its table points: the
        trash page once retired).  ``positions`` is unused: the stack has
        no positional encoding.  Returns ``(logits [b, vocab], kv,
        state)``."""
        del positions
        if adapters is not None or use_kernel:
            raise ValueError("this decoder has no adapter path and reads "
                             "its pages through the gather path")
        c = self.config
        page_size = kv["k"].shape[2]
        view_len = page_tab.shape[1] * page_size
        # the slot's own columns, then (below) the one it writes now: in
        # this order the decoder's programs lower to the text they had
        # (tests/test_tpu_compile.py pins it)
        cols = jnp.arange(view_len)[None, :]
        kv_valid = ((cols >= start_col[:, None])
                    & (cols < write_col[:, None]))
        x = self._embed(params, token_ids)[:, None, :]
        valid = kv_valid | (jnp.arange(view_len)[None, :]
                            == write_col[:, None])
        mask = jnp.where(valid, 0.0, attn_lib.NEG_INF)[:, None, None, :]
        page_idx = jnp.minimum(write_col // page_size, page_tab.shape[1] - 1)
        w_pages = jnp.take_along_axis(page_tab, page_idx[:, None],
                                      axis=1)[:, 0]
        offs = write_col % page_size

        def mamba_layer(p, i, x, ssm_state, conv_state):
            h0 = lax.dynamic_index_in_dim(ssm_state, i, keepdims=False)
            conv0 = lax.dynamic_index_in_dim(conv_state, i, keepdims=False)
            x, h, conv = self._mamba_step(p, x, h0.astype(F32), conv0, live)
            zero = jnp.zeros((), jnp.int32)
            ssm_state = lax.dynamic_update_slice(
                ssm_state, h.astype(ssm_state.dtype)[None],
                (i,) + (zero,) * h.ndim)
            conv_state = lax.dynamic_update_slice(
                conv_state, conv[None], (i,) + (zero,) * conv.ndim)
            return x, ssm_state, conv_state

        def attention_layer(p, layer, x, kv):
            return self._cached_attention(p, layer, x, kv, w_pages, offs,
                                          page_tab, mask)

        x, kv, state = self._run_stack(params, x, kv, state, mamba_layer,
                                       attention_layer)
        x = _rms_norm(params["ln_f"], x, c.layer_norm_eps)
        return self.logits(params, x)[:, 0, :], kv, state

    # ------------------------------------------------------------ sharding

    def partition_rules(self, fsdp: bool = False) -> PartitionRules:
        """Megatron-style specs: projections split over ``tensor`` on their
        head / inner axis, the other matrix axis over ``fsdp`` when asked;
        a run's stacked leading layer axis is never sharded.  The
        state-space in-projections feed a convolution and a scan over
        whole channels, so they split over ``fsdp`` only."""
        f = "fsdp" if fsdp else None
        seg, att = r"segments/\d+/", r"attention/\d+/"
        return PartitionRules([
            (r"embeddings/word$", P("tensor", f)),
            (seg + r"mixer/in_proj/(z|xbc|dt)/kernel", P(None, f, None)),
            (seg + r"mixer/out_proj/kernel", P(None, None, f)),
            (seg + r"ffn/w_in/kernel", P(None, f, "tensor")),
            (seg + r"ffn/w_out/kernel", P(None, "tensor", f)),
            (att + r"mixer/query/kernel", P(f, "tensor", None)),
            (att + r"mixer/(key|value)/kernel", P(f, None, None)),
            (att + r"mixer/out/kernel", P("tensor", None, f)),
            (att + r"ffn/w_in/kernel", P(f, "tensor")),
            (att + r"ffn/w_out/kernel", P("tensor", f)),
        ])
