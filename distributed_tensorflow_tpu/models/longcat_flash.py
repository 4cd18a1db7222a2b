"""A decoder of shortcut-connected expert layers over latent attention
(causal LM, serving cache).

One layer of the stack holds TWO attention sublayers, TWO dense gated FFNs
and ONE routed expert layer whose result skips a sublayer (the public
``LongcatFlashForCausalLM``; the config fields below are its keys):

    x += MLA_0(norm(x));  m = norm(x);  s = MoE(m);  x += MLP_0(m)
    x += MLA_1(norm(x));  x += MLP_1(norm(x)) + s

  * **The shortcut.**  ``s`` is computed from the first sublayer's FFN input
    and joins the stream after the SECOND FFN: one attention and one FFN
    lie between where its input left the stream and where its result joins
    it (in a deployment the expert exchange overlaps them).
  * **Latent attention (MLA).**  Queries go through a low-rank pair with a
    norm between (``W_qb norm(W_qa h)``); each head is 128 lanes without and
    64 with rotary position, both scaled by ``sqrt(hidden / q_lora_rank)``.
    Keys and values come from ONE 512-wide latent a token, RMS-normed and
    scaled by ``sqrt(hidden / kv_lora_rank)``, that ``W_kvb`` expands to 128
    key + 128 value lanes a head, plus ONE 64-wide rotary key all heads
    share.  Rotary is over interleaved pairs.  ``apply`` computes the
    published (expanded) form.  The serving programs keep the latent and the
    rotated key as the cache and use the ABSORBED form: ``W_kvb``'s key half
    is folded into the query (a 512-wide query a head against the latents),
    its value half applied after the weighted sum over latents — the same
    sums in another order.  Expanding 4096 cached latents for every slot
    and sublayer would be ~20 TFLOP a decode step.  A prefill window is
    absorbed too: by count it is cheaper below ~170 query rows, and a
    window has 32.
  * **The expert layer** (``ops/moe.py apply_routed_experts``) routes in
    float32 over ``n_routed_experts_published`` FFN experts + ``zero_expert_num``
    identity experts; this model holds ``experts_held`` of the FFN experts
    from ``expert_offset`` on — one chip's share under expert parallelism —
    computes their part and the identity part, and leaves out what the
    absent ones would add.  With all of them held it is the whole model.  A
    decode step and a prefill window alike skip the held experts none of
    their rows picked (their weights are not read).
  * **What a slot's cache is made of** (``paged_cache_spec``): for each of
    the ``2 * num_layers`` attention sublayers ONE row a token: its
    normed-and-scaled latent (512 lanes), its rotated shared key (64 lanes)
    and zeros up to a whole number of 128-lane tiles (640).  A 576-lane leaf
    and a 512 + 64 pair both tile to 640 lanes a token
    (``pages.kv_pool_bytes``): the padding is there either way, and naming
    it is what keeps the pool in place — the TPU compiler gives a pool leaf
    whose lanes are no multiple of a tile (64 and 576 were both tried) a
    layout of its own, and every program then re-lays the whole leaf out on
    entry, between sublayers and on exit (pool-sized copies: 8 % of the
    device's time in the first chip run of PR 36, 2.7 GB of temporaries in
    the rehearsal).  One scatter and one gather a sublayer; the scores
    contract the whole row against ``[q absorbed, q rotary, 0]`` and the
    weighted sum's first 512 lanes are the context.  No per-slot state:
    radix prefix reuse works as for a K/V-only model.  ``counters`` are the
    router's statistics, added up on the device and read with the fetches
    the scheduler makes anyway.

The layers are separate trees under ``params["layers"][i]`` (a bank of
experts is closed over whole by the branch that reads one of them: no
program slices a layer's weights out of a stack).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import attention as attn_lib
from ..ops import losses as loss_lib
from ..ops import moe as moe_lib
from ..parallel.sharding import PartitionRules, constrain_batch

__all__ = ["LongcatFlashConfig", "LongcatFlash", "longcat_flash_tiny"]

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig:
    vocab_size: int
    hidden_size: int
    ffn_hidden_size: int                  # the dense FFNs' inner width
    expert_ffn_hidden_size: int
    num_layers: int                       # each: two attentions, two FFNs
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts_published: int       # FFN experts the router scores
    zero_expert_num: int                  # identity experts, scored after
    moe_topk: int
    routed_scaling_factor: float
    experts_held: Optional[int] = None    # None: all of them
    expert_offset: int = 0                # the first held one's index
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    max_position: int = 4096              # serving's default max_len
    dtype: Any = jnp.bfloat16             # compute
    param_dtype: Any = jnp.float32        # what ``init`` makes
    initializer_range: float = 0.02       # matrices: normal
    choice_bias_range: float = 0.0        # e_score_correction_bias: uniform
    dropout_rate: float = 0.0

    def __post_init__(self):
        if self.experts_held is None:
            object.__setattr__(self, "experts_held",
                               self.n_routed_experts_published)
        if not (0 <= self.expert_offset and 0 < self.experts_held
                and self.expert_offset + self.experts_held
                <= self.n_routed_experts_published):
            raise ValueError(
                f"experts {self.expert_offset}..+{self.experts_held} are "
                f"not among {self.n_routed_experts_published}")
        if self.moe_topk > self.n_routed_experts_published \
                + self.zero_expert_num:
            raise ValueError("moe_topk exceeds the router's outputs")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")
        if self.dropout_rate:
            raise ValueError("dropout is not implemented in this decoder")

    @property
    def router_outputs(self) -> int:
        return self.n_routed_experts_published + self.zero_expert_num

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_row_width(self) -> int:
        """A token's cached row: latent, shared key, zeros to a lane tile."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def q_scale(self) -> float:
        return (math.sqrt(self.hidden_size / self.q_lora_rank)
                if self.mla_scale_q_lora else 1.0)

    @property
    def kv_scale(self) -> float:
        return (math.sqrt(self.hidden_size / self.kv_lora_rank)
                if self.mla_scale_kv_lora else 1.0)


def longcat_flash_tiny(**kw) -> "LongcatFlash":
    """A toy for tests: two layers, 8 of 8 FFN experts + 4 identity."""
    base = dict(vocab_size=128, hidden_size=64, ffn_hidden_size=96,
                expert_ffn_hidden_size=32, num_layers=2,
                num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                n_routed_experts_published=8, zero_expert_num=4, moe_topk=3,
                routed_scaling_factor=2.0, max_position=128,
                dtype=jnp.float32,
                # 1/sqrt(width): activations of order one at a toy width
                initializer_range=0.125, choice_bias_range=0.05)
    base.update(kw)
    return LongcatFlash(LongcatFlashConfig(**base))


def _rms_norm(p, x, eps):
    with jax.named_scope("norm"):
        xf = x.astype(F32)
        y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1,
                                        keepdims=True) + eps)
        return (y * p["gamma"].astype(F32)).astype(x.dtype)


def _rope_interleaved(x, cos, sin):
    """Rotary over INTERLEAVED pairs ``(x[2j], x[2j+1])`` of ``x`` [b, s,
    h, r]: the pairs are brought side by side (evens, then odds) and turned
    as halves — the public implementation's order, which queries and keys
    share."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    halves = jnp.concatenate([pairs[..., 0], pairs[..., 1]], axis=-1)
    return attn_lib.apply_rope(halves, cos, sin)


class LongcatFlash:
    """Functional decoder: ``init`` -> params, ``apply`` -> hidden states
    ``[b, s, d]`` (after the final norm), ``logits`` -> LM logits."""

    # The Mosaic paged-attention kernel reads K and V rows of ``kv_heads *
    # head_dim`` lanes; this cache holds one latent and one rotary key that
    # all heads share.  The scheduler takes the gather read path.
    paged_kernel_ok = False

    def __init__(self, config: LongcatFlashConfig, mesh=None):
        self.config = config
        self.mesh = mesh

    # ---------------------------------------------------------------- init

    def init(self, key) -> Dict[str, Any]:
        c = self.config
        dt = jnp.dtype(c.param_dtype)
        d, h = c.hidden_size, c.num_attention_heads

        def draw(k, shape):
            return (c.initializer_range
                    * jax.random.normal(k, shape, F32)).astype(dt)

        def gain(n):
            return {"gamma": jnp.ones((n,), dt)}

        def attention(k):
            ks = jax.random.split(k, 6)
            return {
                "ln": gain(d),
                "q_a": {"kernel": draw(ks[0], (d, c.q_lora_rank))},
                "q_norm": gain(c.q_lora_rank),
                "q_b": {"kernel": draw(ks[1], (c.q_lora_rank, h,
                                               c.qk_head_dim))},
                # W_kva's two column blocks, one matrix each: the fused
                # width (576) is no multiple of a TPU lane tile
                "kv_a": {"kernel": draw(ks[2], (d, c.kv_lora_rank))},
                "k_rope": {"kernel": draw(ks[3], (d, c.qk_rope_head_dim))},
                "kv_norm": gain(c.kv_lora_rank),
                "kv_b": {"kernel": draw(ks[4], (
                    c.kv_lora_rank, h, c.qk_nope_head_dim + c.v_head_dim))},
                "out": {"kernel": draw(ks[5], (h, c.v_head_dim, d))},
            }

        def ffn(k, inner):
            k_in, k_out = jax.random.split(k)
            return {"w_in": {"kernel": draw(k_in, (d, 2 * inner))},
                    "w_out": {"kernel": draw(k_out, (inner, d))}}

        def layer(k):
            ks = jax.random.split(k, 7)
            inner = c.expert_ffn_hidden_size
            # an expert at a time (``lax.map``, not ``vmap``): the float32
            # draws of a whole bank at once are twice the bank's weights
            experts = jax.lax.map(lambda ek: ffn(ek, inner),
                                  jax.random.split(ks[5], c.experts_held))
            bias = jax.random.uniform(
                ks[6], (c.router_outputs,), F32, -1.0, 1.0)
            return {
                "attention": [attention(ks[0]), attention(ks[1])],
                "ffn": [dict(ffn(ks[2], c.ffn_hidden_size), ln=gain(d)),
                        dict(ffn(ks[3], c.ffn_hidden_size), ln=gain(d))],
                "moe": {
                    "router": {
                        "kernel": draw(ks[4], (d, c.router_outputs)),
                        # float32 whatever the weights' type: it is added
                        # to probabilities of order 1/router_outputs
                        "choice_bias": c.choice_bias_range * bias},
                    "experts": experts},
            }

        k_emb, k_head, k_layers = jax.random.split(key, 3)
        return {
            "embeddings": {"word": draw(k_emb, (c.vocab_size, d))},
            "layers": [layer(k) for k in
                       jax.random.split(k_layers, c.num_layers)],
            "ln_f": gain(d),
            "lm_head": {"kernel": draw(k_head, (d, c.vocab_size))},
        }

    # -------------------------------------------------------------- pieces

    def _embed(self, params, ids):
        with jax.named_scope("embed"):
            return jnp.take(params["embeddings"]["word"], ids,
                            axis=0).astype(self.config.dtype)

    def _mlp(self, p, x):
        with jax.named_scope("mlp"):
            dtype = x.dtype
            gate, up = jnp.split(x @ p["w_in"]["kernel"].astype(dtype), 2,
                                 axis=-1)
            return (jax.nn.silu(gate) * up) @ p["w_out"]["kernel"].astype(
                dtype)

    def _moe(self, p, m, valid=None):
        """The routed layer on ``m`` [b, s, d] -> (s [b, s, d], counts)."""
        c = self.config
        rows = m.reshape(-1, m.shape[-1])
        y, counts = moe_lib.apply_routed_experts(
            p, rows, top_k=c.moe_topk, scale=c.routed_scaling_factor,
            num_ffn_experts=c.n_routed_experts_published,
            expert_offset=c.expert_offset,
            valid=None if valid is None else valid.reshape(-1))
        return y.reshape(m.shape), counts

    def _queries(self, p, h, cos, sin):
        """``h`` [b, s, d] -> the heads' position-free part [b, s, heads,
        nope] and rotated part [b, s, heads, rope], both scaled."""
        c = self.config
        with jax.named_scope("mla_q"):
            dtype = h.dtype
            low = _rms_norm(p["q_norm"], h @ p["q_a"]["kernel"].astype(dtype),
                            c.rms_norm_eps)
            q = jnp.einsum("bsr,rhk->bshk", low,
                           p["q_b"]["kernel"].astype(dtype))
            q = (q.astype(F32) * c.q_scale).astype(dtype)
            q_nope = q[..., :c.qk_nope_head_dim]
            q_rot = _rope_interleaved(q[..., c.qk_nope_head_dim:], cos, sin)
            return q_nope, q_rot

    def _latents(self, p, h, cos, sin):
        """``h`` [b, s, d] -> what the cache keeps of a token: the normed and
        scaled latent [b, s, kv_lora_rank] and the rotated key all heads
        share [b, s, rope]."""
        c = self.config
        with jax.named_scope("mla_kv"):
            dtype = h.dtype
            latent = _rms_norm(p["kv_norm"],
                               h @ p["kv_a"]["kernel"].astype(dtype),
                               c.rms_norm_eps)
            latent = (latent.astype(F32) * c.kv_scale).astype(dtype)
            key = (h @ p["k_rope"]["kernel"].astype(dtype))[:, :, None, :]
            return latent, _rope_interleaved(key, cos, sin)[:, :, 0, :]

    def _attention_out(self, p, ctx):
        return jnp.einsum("bshv,hvd->bsd", ctx,
                          p["out"]["kernel"].astype(ctx.dtype))

    def _attend_expanded(self, p, h, cos, sin, mask):
        """The published form on a whole sequence: every token's latent
        expanded to keys and values for every head."""
        c = self.config
        q_nope, q_rot = self._queries(p, h, cos, sin)
        latent, k_rot = self._latents(p, h, cos, sin)
        with jax.named_scope("mla_attend"):
            dtype = h.dtype
            kv = jnp.einsum("btc,chk->bthk", latent,
                            p["kv_b"]["kernel"].astype(dtype))
            k_nope, v = kv[..., :c.qk_nope_head_dim], \
                kv[..., c.qk_nope_head_dim:]
            scores = (jnp.einsum("bshk,bthk->bhst", q_nope, k_nope,
                                 preferred_element_type=F32)
                      + jnp.einsum("bshr,btr->bhst", q_rot, k_rot,
                                   preferred_element_type=F32))
            scores = scores / math.sqrt(c.qk_head_dim) + mask
            probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
            ctx = jnp.einsum("bhst,bthv->bshv", probs, v)
        return self._attention_out(p, ctx)

    def _attend_absorbed(self, p, q_nope, q_rot, rows, mask):
        """Queries against cached rows ``[b, t, cache_row_width]`` (a
        token's latent, its shared rotated key, zeros) under an additive
        ``mask`` [b, 1, s, t], ``W_kvb`` absorbed (module doc)."""
        c = self.config
        with jax.named_scope("mla_attend"):
            dtype = q_nope.dtype
            w_kvb = p["kv_b"]["kernel"].astype(dtype)
            w_key = w_kvb[..., :c.qk_nope_head_dim]
            w_value = w_kvb[..., c.qk_nope_head_dim:]
            q_lat = jnp.einsum("bshk,chk->bshc", q_nope, w_key)
            q_row = jnp.concatenate([q_lat, q_rot], axis=-1)
            q_row = jnp.pad(q_row, ((0, 0),) * 3 + (
                (0, rows.shape[-1] - q_row.shape[-1]),))
            rows = rows.astype(dtype)
            # float32 out of the accumulator: a score of magnitude 64 keeps
            # 0.25 of error as a bf16
            scores = jnp.einsum("bshc,btc->bhst", q_row, rows,
                                preferred_element_type=F32)
            scores = scores / math.sqrt(c.qk_head_dim) + mask
            probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
            # over the whole row (the key's and the padding's lanes ride
            # along, a quarter more work) and the small result cut: no lane
            # slice of the gathered view
            ctx_row = jnp.einsum("bhst,btc->bshc", probs, rows)
            ctx = jnp.einsum("bshc,chv->bshv",
                             ctx_row[..., :c.kv_lora_rank], w_value)
        return self._attention_out(p, ctx)

    def _layer(self, p, x, attend, valid=None):
        """One layer: ``attend(p_attention, sublayer, h) -> [b, s, d]``.
        Returns (x, the expert layer's counts)."""
        c = self.config
        eps = c.rms_norm_eps
        att, ffn = p["attention"], p["ffn"]
        x = x + attend(att[0], 0, _rms_norm(att[0]["ln"], x, eps))
        m = _rms_norm(ffn[0]["ln"], x, eps)
        shortcut, counts = self._moe(p["moe"], m, valid)
        x = x + self._mlp(ffn[0], m)
        x = x + attend(att[1], 1, _rms_norm(att[1]["ln"], x, eps))
        x = x + self._mlp(ffn[1], _rms_norm(ffn[1]["ln"], x, eps))
        with jax.named_scope("shortcut_join"):
            x = x + shortcut
        return x, counts

    # ------------------------------------------------------------- forward

    def apply(self, params, input_ids, *, train: bool = False, rng=None):
        """``[b, s]`` ids -> ``[b, s, d]`` hidden states after the final
        norm."""
        del train, rng                      # no dropout in this decoder
        c = self.config
        s = input_ids.shape[1]
        x = constrain_batch(self._embed(params, input_ids), self.mesh)
        cos, sin = attn_lib.rope_tables(jnp.arange(s), c.qk_rope_head_dim,
                                        c.rope_theta)
        mask = attn_lib.causal_mask(s)
        for p in params["layers"]:
            x, _ = self._layer(
                p, x, lambda pa, _, h: self._attend_expanded(pa, h, cos, sin,
                                                             mask))
        return _rms_norm(params["ln_f"], x, c.rms_norm_eps)

    def logits(self, params, hidden):
        """Untied head -> ``[..., vocab]`` float32 logits."""
        with jax.named_scope("head"):
            return (hidden @ params["lm_head"]["kernel"].astype(
                hidden.dtype)).astype(F32)

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
        pool from it): per token and attention sublayer one row (latent,
        shared rotated key, zeros to a lane tile); no per-slot state; and
        the router's counters,
        which every serving program adds to: ``router`` ``[layers, held +
        2]`` (tokens received by each held expert, picks on identity
        experts, picks on absent experts) and ``touched`` ``[2]`` (held
        experts that received a token, over layers and DECODE steps; how
        many they could have been)."""
        c = self.config
        return {
            "kv_layers": 2 * c.num_layers,
            "kv": {"latent_key": ((c.cache_row_width,), jnp.dtype(c.dtype))},
            "state": {},
            "counters": {
                "router": ((c.num_layers, c.experts_held + 2), jnp.int32),
                "touched": ((2,), jnp.int32)},
        }

    def _run_cached(self, params, x, kv, cos, sin, pages, offs, table, mask,
                    valid):
        """The stack over the page pool: each sublayer writes the block's
        rows (latent, key, zeros; row-major over ``x``'s batch and
        positions) to pool cells ``(pages, offs)`` of its plane, gathers
        every row of ``table`` [b, pages_per_row] and attends under
        ``mask`` -> (x, kv,
        router counts [layers, held + 2])."""
        b = x.shape[0]
        pool = kv["latent_key"]
        counts = []
        for i, p in enumerate(params["layers"]):

            def attend(pa, sub, h, i=i):
                nonlocal pool
                plane = 2 * i + sub
                q_nope, q_rot = self._queries(pa, h, cos, sin)
                row = jnp.concatenate(self._latents(pa, h, cos, sin), axis=-1)
                flat = row.reshape(-1, row.shape[-1]).astype(pool.dtype)
                flat = jnp.pad(flat, ((0, 0), (
                    0, pool.shape[-1] - flat.shape[-1])))
                pool = pool.at[plane, pages, offs].set(flat)
                view = pool[plane, table].reshape(b, -1, flat.shape[-1])
                return self._attend_absorbed(pa, q_nope, q_rot, view, mask)

            x, n = self._layer(p, x, attend, valid)
            counts.append(n)
        return x, {"latent_key": pool}, jnp.stack(counts)

    def decode_window_paged(self, params, kv, token_ids, page_row, pos,
                            head: str = "all", *, valid, counters,
                            adapters=None, adapter_rows=None,
                            use_kernel: bool = False):
        """A batch of prefill windows against the paged cache: row r of
        ``token_ids`` [n, s] is one request's window, ``s`` tokens at
        positions ``pos[r] .. pos[r] + s - 1`` of which the first
        ``valid[r]`` are real, latents and keys written through
        ``page_row[r]`` [n, pages_per_row].  The batch-1 call form (a rank-1
        ``page_row``, scalar ``pos`` / ``valid``) is the n = 1 case of the
        same code; the windows of one dispatch read the weights — and each
        expert they touch — once.  Pad columns go to the reserved trash
        page and are neither routed to an expert nor counted; a row with
        ``valid == 0`` is PADDING of the batch and is all pad columns.
        ``pos`` need not be a page or window boundary.  Returns ``(logits,
        kv, counters)``: logits ``[n, s, vocab]``, for ``head="last"`` ``[n,
        vocab]`` at each row's last real position (taken before the head
        matmul), None for ``head="none"``; ``counters`` (the cache's, module
        doc) with these windows' router counts added."""
        if head not in ("all", "last", "none"):
            raise ValueError(f"head must be all|last|none; got {head!r}")
        if adapters is not None or use_kernel:
            raise ValueError("this decoder has no adapter path and reads "
                             "its pages through the gather path")
        c = self.config
        n, s = token_ids.shape
        page_size = kv["latent_key"].shape[2]
        win = attn_lib.paged_windows(page_row, pos, valid, n, s, page_size)
        x = self._embed(params, token_ids)
        cos, sin = attn_lib.rope_tables(win.cols, c.qk_rope_head_dim,
                                        c.rope_theta)
        x, kv, counts = self._run_cached(
            params, x, kv, cos, sin, win.pages, win.offs, win.page_rows,
            attn_lib.paged_window_mask(win, page_size),
            jnp.arange(s) < win.valid[:, None])
        counters = dict(counters, router=counters["router"] + counts)
        if head == "none":
            return None, kv, counters
        if head == "last":
            x = attn_lib.last_real_position(x, win.valid)
        x = _rms_norm(params["ln_f"], x, c.rms_norm_eps)
        logits = self.logits(params, x)
        return (logits[:, 0] if head == "last" else logits), kv, counters

    def decode_step_slots_paged(self, params, kv, token_ids, page_tab,
                                start_col, write_col, positions, *,
                                live, counters, adapters=None,
                                adapter_rows=None, use_kernel: bool = False):
        """One token for every slot against the paged cache (the serving
        decode step): row r writes its latent and key at logical column
        ``write_col[r]`` through ``page_tab[r]``, rotated for position
        ``positions[r]``, and attends the columns from ``start_col[r]`` up
        to and with its own.  A row that is not ``live`` computes too (its
        write lands wherever its table points: the trash page once
        retired) but is neither routed to an expert nor counted.  Returns
        ``(logits [b, vocab], kv, counters)``."""
        if adapters is not None or use_kernel:
            raise ValueError("this decoder has no adapter path and reads "
                             "its pages through the gather path")
        c = self.config
        page_size = kv["latent_key"].shape[2]
        view_len = page_tab.shape[1] * page_size
        cols = jnp.arange(view_len)[None, :]
        seen = (cols >= start_col[:, None]) & (cols <= write_col[:, None])
        mask = jnp.where(seen, 0.0, attn_lib.NEG_INF)[:, None, None, :]
        page_idx = jnp.minimum(write_col // page_size, page_tab.shape[1] - 1)
        w_pages = jnp.take_along_axis(page_tab, page_idx[:, None],
                                      axis=1)[:, 0]
        offs = write_col % page_size
        cos, sin = attn_lib.rope_tables(positions[:, None],
                                        c.qk_rope_head_dim, c.rope_theta)
        x = self._embed(params, token_ids)[:, None, :]
        x, kv, counts = self._run_cached(
            params, x, kv, cos, sin, w_pages, offs, page_tab, mask,
            live[:, None])
        held = counts[:, :c.experts_held]
        counters = {
            "router": counters["router"] + counts,
            "touched": counters["touched"] + jnp.stack(
                [jnp.sum(held > 0, dtype=jnp.int32),
                 jnp.int32(held.size)])}
        x = _rms_norm(params["ln_f"], x, c.rms_norm_eps)
        return self.logits(params, x)[:, 0, :], kv, counters

    # ------------------------------------------------------------ sharding

    def partition_rules(self, fsdp: bool = False) -> PartitionRules:
        """Megatron-style specs: head and inner axes over ``tensor``, the
        other matrix axis over ``fsdp`` when asked; a bank of experts over
        ``expert`` on its leading axis; the low-rank down-projections and
        the router replicated."""
        f = "fsdp" if fsdp else None
        lay = r"layers/\d+/"
        return PartitionRules([
            (r"embeddings/word$", P("tensor", f)),
            (r"lm_head/kernel$", P(f, "tensor")),
            (lay + r"attention/\d+/(q_b|kv_b)/kernel", P(f, "tensor", None)),
            (lay + r"attention/\d+/out/kernel", P("tensor", None, f)),
            (lay + r"ffn/\d+/w_in/kernel", P(f, "tensor")),
            (lay + r"ffn/\d+/w_out/kernel", P("tensor", f)),
            (lay + r"moe/experts/w_in/kernel", P("expert", f, "tensor")),
            (lay + r"moe/experts/w_out/kernel", P("expert", "tensor", f)),
        ])
