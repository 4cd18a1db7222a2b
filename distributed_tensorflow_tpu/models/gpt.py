"""GPT decoder family (causal LM + KV-cache generation).

The reference has no transformer at all (3-layer MLP, reference
example.py:149-155); the decoder family completes the model zoo beside the
BERT encoder (models/bert.py) with the same TPU-first machinery:

  * **Scanned layer stack**: L pre-LN decoder blocks as ONE stacked
    parameter set applied with ``lax.scan`` — O(1) compile time in depth;
    optional ``remat`` for long-context HBM headroom.
  * **Causal attention** through the shared kernel swap: full softmax by
    default, Pallas flash attention (``use_flash``) on TPU, ring attention
    over a ``seq`` mesh axis (``seq_axis``) for context parallelism.
  * **KV-cache decode**: ``init_cache`` + ``decode_step`` run one token
    through the stack against a static-shape cache (``dynamic_update_slice``
    writes, position-masked reads) so ``generate`` is a ``lax.scan`` with no
    recompilation per token.
  * **Tied embeddings**: the LM head is the word-embedding transpose —
    megatron-style ``tensor`` sharding applies to both at once
    (``partition_rules``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops import attention as attn_lib
from ..ops import initializers as init_lib
from ..ops import losses as loss_lib
from ..ops.moe import apply_moe, init_moe, moe_partition_rules
from ..parallel.sharding import PartitionRules, constrain_batch
from .bert import _dropout, _layer_norm

__all__ = ["GPTConfig", "GPT", "gpt_small", "gpt_tiny"]


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 1024
    dropout_rate: float = 0.1
    layer_norm_eps: float = 1e-5
    dtype: Any = jnp.float32
    remat: bool = False
    # With remat=True, what the per-layer checkpoint SAVES: "full"
    # (nothing — recompute the whole block, max memory savings),
    # "dots" (all matmul outputs — recompute only elementwise chains,
    # much cheaper backward at higher memory), "dots_no_batch"
    # (weight-only dots).  Measured on hardware via
    # scripts/mfu_ablation.py before changing any default.
    remat_policy: str = "full"
    seq_axis: Optional[str] = None    # mesh axis for ring attention (SP)
    # True / False / "auto": auto dispatches the fused Pallas kernel on TPU
    # at seq >= the measured crossover (ops.attention.resolve_use_flash).
    # Hardware-validated + measured 2026-07-31 (docs/PERF.md): ties XLA at
    # seq <= 1024, wins 1.3-1.7x at 2048, ~3x at 4096 — "auto" is safe.
    use_flash: Any = "auto"
    # True / False / "auto": block norms via the fused Pallas kernel —
    # ops.pallas.fused_layernorm for norm="layernorm",
    # ops.pallas.fused_rmsnorm for norm="rmsnorm"; auto = TPU only.
    # Default False until the end-to-end win is measured on hardware.
    fused_layernorm: Any = False
    # >0: compute the LM loss ``loss_seq_chunk`` tokens at a time (head
    # projection + log-softmax reduced per chunk under jax.checkpoint) so
    # the [tokens, vocab] logits tensor is never fully materialised —
    # GPT-2-small at bench shapes pays ~2.5 GB of f32 logits otherwise.
    # 0 = off (single full-width projection).
    loss_seq_chunk: int = 0
    # "learned" absolute positions (GPT-2) or "rope" rotary embeddings
    # (relative; extrapolates past trained length, no position table)
    position_embedding: str = "learned"
    # RoPE frequency base (10000 = Su et al. / Llama-2; Llama-3 ships
    # 500000 for its 8k context)
    rope_base: float = 10000.0
    # Block normalization: "layernorm" (GPT-2) or "rmsnorm" (Llama — gamma
    # only, no centering/beta)
    norm: str = "layernorm"
    # FFN body: "gelu" (w_in -> gelu -> w_out) or "swiglu" (Llama:
    # w_out(silu(w_gate(x)) * w_in(x)) — w_in is HF's up_proj)
    ffn_activation: str = "gelu"
    # False (Llama): no bias params anywhere in attention/FFN projections
    use_bias: bool = True
    # False (Llama): separate lm_head matrix instead of the tied
    # word-embedding transpose
    tied_head: bool = True
    # Grouped-query attention: number of key/value heads (None = num_heads
    # i.e. plain MHA; 1 = MQA).  Shrinks the KV cache num_heads/num_kv_heads
    # fold — the serving-memory lever for long-context decode.
    num_kv_heads: Optional[int] = None
    # Sparse (MoE) FFN: 0 = dense.  With experts > 0 every block's FFN is a
    # grouped top-k MoE bank (ops.moe) shardable over the ``expert`` axis;
    # the router aux losses are folded into lm_loss_fn automatically.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 1e-2
    moe_z_weight: float = 1e-3
    # Pipeline parallelism (parallel.pipeline): split the L decoder blocks
    # into ``pipeline_stages`` same-shape stages of L/S blocks over the
    # ``pipe_axis`` mesh axis.  Embedding and LM head run pipe-REPLICATED
    # (they are O(vocab*d) beside L blocks; dedicating stages to them would
    # stretch the bubble instead).  0/1 = off.  Requires a mesh at
    # construction (``GPT(config, mesh=...)``).
    pipeline_stages: int = 0
    pipe_axis: str = "pipe"
    # microbatches per step; 0 -> pipeline_stages (the GPipe minimum for
    # full utilization)
    pipeline_microbatches: int = 0
    # KV-cache storage dtype at decode: None = the model dtype; "int8"
    # stores symmetric per-(token, head) int8 with f32 scales — cache
    # reads rival the weight reads at serving batch sizes, so this is
    # the decode HBM-bandwidth lever (2x smaller cache traffic AND 2x
    # the cache capacity per chip at bf16 models).  Dequantize happens
    # at the attention operand, where XLA fuses the widen+scale (same
    # scheme as ops.quant's weight-only path).
    kv_cache_dtype: Optional[str] = None

    def __post_init__(self):
        if self.kv_cache_dtype not in (None, "int8"):
            raise ValueError(f"kv_cache_dtype must be None or 'int8'; "
                             f"got {self.kv_cache_dtype!r}")
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"norm must be 'layernorm' or 'rmsnorm'; "
                             f"got {self.norm!r}")
        if self.loss_seq_chunk < 0:
            raise ValueError(f"loss_seq_chunk must be >= 0; "
                             f"got {self.loss_seq_chunk}")
        if self.ffn_activation not in ("gelu", "swiglu"):
            raise ValueError(f"ffn_activation must be 'gelu' or 'swiglu'; "
                             f"got {self.ffn_activation!r}")
        if self.ffn_activation == "swiglu" and self.moe_experts > 0:
            raise ValueError("moe_experts with ffn_activation='swiglu' is "
                             "unsupported: ops.moe's expert bank is the "
                             "two-matrix gelu FFN")
        if self.pipeline_stages > 1:
            if self.num_layers % self.pipeline_stages:
                raise ValueError(
                    f"num_layers {self.num_layers} not divisible by "
                    f"pipeline_stages {self.pipeline_stages}")
            if self.moe_experts > 0:
                raise ValueError(
                    "pipeline_stages with MoE is unsupported: the router "
                    "aux-loss scalar cannot cross the same-shape pipeline "
                    "stage contract (parallel/pipeline.py)")
            if self.seq_axis is not None:
                raise ValueError(
                    "pipeline_stages with seq_axis (ring attention) is "
                    "unsupported: ring's shard_map cannot nest inside the "
                    "pipe-manual region")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def kv_heads(self) -> int:
        # explicit None check: 0 must be rejected (at init), not silently
        # fall back to full MHA
        return (self.num_heads if self.num_kv_heads is None
                else self.num_kv_heads)


def gpt_small(**kw) -> "GPT":
    return GPT(GPTConfig(**kw))


def _remat_policy(name: str):
    """Map the config string to a jax.checkpoint save policy (None =
    save nothing, the classic full-block remat)."""
    if name == "full":
        return None
    if name == "dots":
        return jax.checkpoint_policies.dots_saveable
    if name == "dots_no_batch":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    raise ValueError(f"remat_policy must be 'full', 'dots', or "
                     f"'dots_no_batch'; got {name!r}")


def gpt_tiny(**kw) -> "GPT":
    kw.setdefault("hidden_size", 128)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 2)
    kw.setdefault("intermediate_size", 512)
    kw.setdefault("vocab_size", 512)
    kw.setdefault("max_position", 128)
    return GPT(GPTConfig(**kw))


class GPT:
    """Functional decoder: ``init(key) -> params``,
    ``apply(params, input_ids, ...) -> [b, s, hidden]``."""

    def __init__(self, config: GPTConfig, mesh=None):
        self.config = config
        # The mesh the params/batch are sharded over.  Needed by the paths
        # that run manual collectives or kernels: ring attention (seq_axis),
        # the pipeline, and — on any mesh of more than one device — the
        # flash kernel, which XLA cannot partition by itself.
        self.mesh = mesh

    # -- init -------------------------------------------------------------
    def init(self, key) -> Dict[str, Any]:
        c = self.config
        trunc = init_lib.truncated_normal(0.02)
        k_emb, k_layers = jax.random.split(key)
        ke = jax.random.split(k_emb, 2)

        def ln():
            p = {"gamma": jnp.ones((c.hidden_size,), jnp.float32)}
            if c.norm == "layernorm":
                p["beta"] = jnp.zeros((c.hidden_size,), jnp.float32)
            return p

        def maybe_bias(shape):
            return {"bias": jnp.zeros(shape, jnp.float32)} if c.use_bias \
                else {}

        h, hd, d, i = c.num_heads, c.head_dim, c.hidden_size, \
            c.intermediate_size
        kv = c.kv_heads
        if kv < 1 or h % kv:
            raise ValueError(f"num_kv_heads must be a positive divisor of "
                             f"num_heads {h}; got {kv}")

        def one_layer(k):
            ks = jax.random.split(k, 7)
            layer = {
                "ln_1": ln(),
                "attention": {
                    "query": {"kernel": trunc(ks[0], (d, h, hd)),
                              **maybe_bias((h, hd))},
                    "key": {"kernel": trunc(ks[1], (d, kv, hd)),
                            **maybe_bias((kv, hd))},
                    "value": {"kernel": trunc(ks[2], (d, kv, hd)),
                              **maybe_bias((kv, hd))},
                    "out": {"kernel": trunc(ks[3], (h, hd, d)),
                            **maybe_bias((d,))},
                },
                "ln_2": ln(),
            }
            if c.moe_experts > 0:
                layer["moe"] = init_moe(ks[4], d, i, c.moe_experts)
            else:
                layer["ffn"] = {
                    "w_in": {"kernel": trunc(ks[4], (d, i)),
                             **maybe_bias((i,))},
                    "w_out": {"kernel": trunc(ks[5], (i, d)),
                              **maybe_bias((d,))},
                }
                if c.ffn_activation == "swiglu":
                    layer["ffn"]["w_gate"] = {
                        "kernel": trunc(ks[6], (d, i)),
                        **maybe_bias((i,))}
            return layer

        embeddings = {"word": trunc(ke[0], (c.vocab_size, c.hidden_size))}
        if c.position_embedding == "learned":
            embeddings["position"] = trunc(
                ke[1], (c.max_position, c.hidden_size))
        elif c.position_embedding != "rope":
            raise ValueError("position_embedding must be 'learned' or "
                             f"'rope'; got {c.position_embedding!r}")
        params = {
            "embeddings": embeddings,
            "decoder": jax.vmap(one_layer)(
                jax.random.split(k_layers, c.num_layers)),
            "ln_f": ln(),
        }
        if not c.tied_head:
            # HF lm_head layout [vocab, d] so logits() shares the tied
            # `hidden @ W.T` projection
            params["lm_head"] = trunc(jax.random.split(ke[1])[0],
                                      (c.vocab_size, c.hidden_size))
        return params

    # -- blocks -----------------------------------------------------------
    def _pin(self, x):
        """The ``[b, s, d]`` stream of the full-sequence forward, pinned
        batch-sharded over the mesh's data-parallel axes
        (``parallel.sharding.constrain_batch``): ``fsdp`` divides the batch,
        and the weights it stores are gathered at use.  Identity without a
        mesh; pipeline stage bodies run under ``shard_map`` and are left
        alone."""
        if self.config.pipeline_stages > 1:
            return x
        return constrain_batch(x, self.mesh, seq_axis=self.config.seq_axis)

    def _norm(self, p, x):
        """Config-dispatched block norm: LayerNorm (GPT-2) or RMSNorm
        (Llama: f32 rms, gamma scale, no centering — matches HF
        LlamaRMSNorm numerics)."""
        c = self.config
        from ..ops.pallas import resolve_fused_ln
        with jax.named_scope("norm"):
            if c.norm == "rmsnorm":
                if resolve_fused_ln(c.fused_layernorm):
                    from ..ops.pallas import fused_rmsnorm
                    return fused_rmsnorm(x, p["gamma"], c.layer_norm_eps)
                xf = x.astype(jnp.float32)
                y = xf * jax.lax.rsqrt(
                    jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                    + c.layer_norm_eps)
                return (y * p["gamma"]).astype(x.dtype)
            return _layer_norm(p, x, c.layer_norm_eps,
                               fused=resolve_fused_ln(c.fused_layernorm))

    def _rope_transform(self, local_seq_len: int):
        """qk_transform for this forward, or None.  Built ONCE per forward
        (apply hoists it out of the layer scan — cos/sin tables are
        identical across layers).  Under the in-shard_map ring path the
        local shard restarts at 0, so positions get the shard's global
        offset from its axis index."""
        c = self.config
        if c.position_embedding != "rope":
            return None
        positions = jnp.arange(local_seq_len)
        if c.seq_axis is not None and self.mesh is None:
            # traced inside an existing shard_map over seq_axis
            positions = (jax.lax.axis_index(c.seq_axis) * local_seq_len
                         + positions)
        cos, sin = attn_lib.rope_tables(positions, c.head_dim,
                                        base=c.rope_base)
        return lambda q, k: (attn_lib.apply_rope(q, cos, sin),
                             attn_lib.apply_rope(k, cos, sin))

    def _attention(self, p, x, mask, rng, train, qk_transform=None):
        c = self.config
        if c.seq_axis is not None and self.mesh is not None:
            # flash-vs-XLA crossover applies to the kernel's PER-CALL
            # sequence: inside the ring each call sees one shard, so the
            # gate uses the local shard length, not the global seq
            local = x.shape[1] // self.mesh.shape[c.seq_axis]
            if attn_lib.resolve_use_flash(c.use_flash, local):
                # SP x flash: ring schedule with the fused kernel per
                # block pair (parallel.ring_flash)
                from ..parallel.ring_flash import ring_flash_attention_sharded
                attention_fn = lambda q, k, v, mask=None: \
                    ring_flash_attention_sharded(
                        q, k, v, self.mesh, seq_axis=c.seq_axis,
                        causal=True)
                attention_fn.supports_gqa = True
            else:
                from ..parallel.ring import ring_attention_sharded
                attention_fn = lambda q, k, v, mask=None: \
                    ring_attention_sharded(
                        q, k, v, self.mesh, seq_axis=c.seq_axis,
                        causal=True)
        elif c.seq_axis is not None:
            # traced inside a caller's shard_map: x is already the local
            # shard, so x.shape[1] IS the per-call sequence
            if attn_lib.resolve_use_flash(c.use_flash, x.shape[1]):
                from ..parallel.ring_flash import ring_flash_attention
                attention_fn = lambda q, k, v, mask=None: \
                    ring_flash_attention(q, k, v, axis_name=c.seq_axis,
                                         causal=True)
                attention_fn.supports_gqa = True
            else:
                from ..parallel.ring import ring_attention
                attention_fn = lambda q, k, v, mask=None: ring_attention(
                    q, k, v, axis_name=c.seq_axis, causal=True)
        elif attn_lib.resolve_use_flash(c.use_flash, x.shape[1]):
            # GQA configs run natively: the kernel maps kv blocks by
            # q_head // group, so no broadcast materialises
            from ..ops.pallas.flash_attention import make_flash_attention_fn
            attention_fn = make_flash_attention_fn(causal=True,
                                                   mesh=self.mesh)
        else:
            attention_fn = attn_lib.dot_product_attention
        with jax.named_scope("attention"):
            return attn_lib.attention_core(
                p, x, mask=mask, dropout_rate=c.dropout_rate, rng=rng,
                train=train, attention_fn=attention_fn,
                qk_transform=qk_transform)

    def _ffn(self, p, x, rng=None, train=False):
        """Pre-LN FFN (dense or MoE): shared by the full-sequence and
        KV-cache paths so the math can never diverge between them.

        Returns ``(out, aux)`` — ``aux`` is the weighted router loss scalar
        (0 for the dense path).  Note: at KV-cache decode the MoE routes one
        token per group, so capacity never drops; full-sequence outputs
        match decode exactly only when the configured capacity drops no
        tokens (use a generous ``moe_capacity_factor`` at eval).
        """
        c = self.config
        h = self._norm(p["ln_2"], x)
        with jax.named_scope("mlp"):
            if "moe" in p:
                y, m = apply_moe(p["moe"], h, k=c.moe_top_k,
                                 capacity_factor=c.moe_capacity_factor,
                                 train=train, rng=rng)
                aux = (c.moe_aux_weight * m["aux_loss"]
                       + c.moe_z_weight * m["router_z_loss"])
                return y, aux
            if c.ffn_activation == "swiglu":
                return (attn_lib.ffn_swiglu_core(p["ffn"], h),
                        jnp.zeros((), jnp.float32))
            return (attn_lib.ffn_core(p["ffn"], h),
                    jnp.zeros((), jnp.float32))

    def _block(self, p, x, mask, rng, train, qk_transform=None):
        c = self.config
        x = self._pin(x)    # inside the remat: the recomputed forward too
        r_attn, r_res, r_moe, r_drop = jax.random.split(rng, 4)
        attn_out = self._attention(
            p["attention"], self._norm(p["ln_1"], x),
            mask, r_attn, train, qk_transform=qk_transform)
        x = x + _dropout(attn_out, c.dropout_rate, r_res, train)
        ffn_out, aux = self._ffn(p, x, rng=r_moe, train=train)
        return x + _dropout(ffn_out, c.dropout_rate, r_drop, train), aux

    def _embed(self, emb, input_ids, r_emb, train):
        """Word (+ learned position) embedding, dropout, compute-dtype
        cast — ONE implementation for the plain forward and the 1F1B path
        (the gradient parity between them depends on bit-identity here)."""
        c = self.config
        s = input_ids.shape[1]
        with jax.named_scope("embed"):
            x = jnp.take(emb["word"], input_ids, axis=0)
            if c.position_embedding == "learned":
                x = x + emb["position"][None, :s, :]
            return _dropout(x, c.dropout_rate, r_emb, train).astype(c.dtype)

    def _make_layer_fn(self, seq_len: int):
        """Decoder block fn with the RoPE transform bound and optional
        remat — shared by apply() and the 1F1B path.  The transform is
        bound via partial (not a call argument): it's a callable, which
        jax.checkpoint can't accept as a traced arg."""
        from functools import partial
        layer_fn = partial(self._block,
                           qk_transform=self._rope_transform(seq_len))
        if self.config.remat:
            layer_fn = jax.checkpoint(
                layer_fn, static_argnums=(4,),
                policy=_remat_policy(self.config.remat_policy))
        return layer_fn

    # -- full-sequence forward -------------------------------------------
    def apply(self, params, input_ids, *, train: bool = False, rng=None,
              return_aux: bool = False):
        """-> hidden [b, s, d]; with ``return_aux`` also the summed router
        aux-loss scalar (nonzero only for MoE configs)."""
        c = self.config
        if rng is None:
            if train:
                raise ValueError("GPT.apply(train=True) requires rng")
            rng = jax.random.PRNGKey(0)
        s = input_ids.shape[1]
        r_emb, r_layers = jax.random.split(rng)
        x = self._pin(
            self._embed(params["embeddings"], input_ids, r_emb, train))
        layer_fn = self._make_layer_fn(s)
        layer_keys = jax.random.split(r_layers, c.num_layers)
        if c.pipeline_stages > 1:
            # the stage_fn builds its own mask (shard_map bodies cannot
            # capture traced values) — don't materialize one here
            x = self._pipeline_blocks(params, x, layer_keys, train, layer_fn)
            aux_total = jnp.zeros((), jnp.float32)   # MoE rejected at config
        else:
            # Ring / flash paths mask internally (causal=True); the dense
            # path gets an explicit causal mask.
            mask = (None if (c.seq_axis is not None
                             or attn_lib.resolve_use_flash(c.use_flash, s))
                    else attn_lib.causal_mask(s))

            def body(carry, inputs):
                layer_params, layer_key = inputs
                new_x, aux = layer_fn(layer_params, carry, mask, layer_key,
                                      train)
                return new_x, aux

            x, aux_per_layer = lax.scan(body, x,
                                        (params["decoder"], layer_keys))
            aux_total = jnp.sum(aux_per_layer)
        hidden = self._pin(self._norm(params["ln_f"], x))
        if return_aux:
            return hidden, aux_total
        return hidden

    def _pipeline_stage_bits(self, params, layer_keys, train, layer_fn):
        """(stage_params, stage_fn) for the pipelined decoder stack.

        The scanned [L, ...] decoder stack reshapes to [S, L/S, ...] stage
        params (a local view when the store shards the leading layer dim
        ``P(pipe_axis)`` — ``partition_rules``); per-layer dropout keys ride
        along inside the stage params so every block keeps its own key.
        Note: under pp each layer key is reused for every microbatch of the
        step, so dropout masks repeat across microbatches (still random
        per layer/step); the non-pp path draws one mask over the full batch.
        The causal mask is rebuilt from the microbatch shape inside the
        stage (a closure-free constant — shard_map bodies cannot capture
        traced values).
        """
        c = self.config
        if self.mesh is None:
            raise ValueError("pipeline_stages requires GPT(config, mesh=...)")
        s_count = c.pipeline_stages
        per = c.num_layers // s_count
        stage_params = {
            "layers": jax.tree.map(
                lambda p: p.reshape(s_count, per, *p.shape[1:]),
                params["decoder"]),
            "keys": layer_keys.reshape(s_count, per, *layer_keys.shape[1:]),
        }

        def stage_fn(sp, acts):
            mask = (None if attn_lib.resolve_use_flash(c.use_flash,
                                                       acts.shape[1])
                    else attn_lib.causal_mask(acts.shape[1]))

            def body(carry, inputs):
                lp, lk = inputs
                new_x, _ = layer_fn(lp, carry, mask, lk, train)
                return new_x, None

            acts, _ = lax.scan(body, acts, (sp["layers"], sp["keys"]))
            return acts

        return stage_params, stage_fn

    def _pipeline_blocks(self, params, x, layer_keys, train, layer_fn):
        """Decoder blocks as a GPipe pipeline over ``config.pipe_axis``
        (see ``_pipeline_stage_bits`` for the stage construction)."""
        from ..parallel.pipeline import pipeline_apply
        c = self.config
        stage_params, stage_fn = self._pipeline_stage_bits(
            params, layer_keys, train, layer_fn)
        return pipeline_apply(
            stage_fn, stage_params, x, self.mesh,
            c.pipeline_microbatches or c.pipeline_stages, axis=c.pipe_axis)

    def _logits_from_word(self, word, hidden):
        """Tied-head projection against an explicit word matrix — ONE
        implementation for logits() and the 1F1B head loss (their
        gradient parity depends on bit-identity)."""
        with jax.named_scope("head"):
            return (hidden @ word.T.astype(hidden.dtype)
                    ).astype(jnp.float32)

    def _head_word(self, params):
        """The LM head's [vocab, d] matrix: the tied word embedding, or
        the separate ``lm_head`` for ``tied_head=False`` configs.  One
        resolver for logits(), the chunked loss, and the 1F1B head."""
        return (params["embeddings"]["word"] if self.config.tied_head
                else params["lm_head"])

    def logits(self, params, hidden):
        """LM head -> [b, s, vocab] f32 logits."""
        return self._logits_from_word(self._head_word(params), hidden)

    # -- training ---------------------------------------------------------
    def _chunked_lm_stats(self, word, hidden, targets, mask, chunk):
        """(nll_sum, hit_sum) over all tokens, computed ``chunk`` tokens at
        a time so the full ``[tokens, vocab]`` logits tensor is never live:
        each scan step projects one chunk against the head and reduces it,
        with ``jax.checkpoint`` recomputing the chunk's logits in backward.
        At GPT-2 bench shapes the unchunked f32 logits are ~2.5 GB of the
        step's peak (batch 48 x seq 256 x vocab 50257) — this caps the
        live slice at ``chunk x vocab`` and unlocks bigger batches."""
        d = hidden.shape[-1]
        h2 = hidden.reshape(-1, d)
        y2 = targets.reshape(-1)
        m2 = (jnp.ones(y2.shape, jnp.float32) if mask is None
              else mask.reshape(-1).astype(jnp.float32))
        t = h2.shape[0]
        # an over-large chunk would PAD tokens up to it and allocate a
        # bigger logits block than the unchunked path — clamp, don't cliff
        chunk = min(chunk, t)
        pad = (-t) % chunk
        if pad:
            h2 = jnp.concatenate(
                [h2, jnp.zeros((pad, d), h2.dtype)])
            y2 = jnp.concatenate([y2, jnp.zeros((pad,), y2.dtype)])
            m2 = jnp.concatenate([m2, jnp.zeros((pad,), m2.dtype)])
        n = h2.shape[0] // chunk

        @jax.checkpoint
        def stats(h_c, y_c, m_c):
            logits = self._logits_from_word(word, h_c)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, y_c[:, None], axis=-1)[:, 0]
            hits = (jnp.argmax(logits, -1) == y_c).astype(jnp.float32)
            return jnp.sum(nll * m_c), jnp.sum(hits * m_c)

        def body(carry, xs):
            nll_c, hit_c = stats(*xs)
            return (carry[0] + nll_c, carry[1] + hit_c), None

        (nll_sum, hit_sum), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (h2.reshape(n, chunk, d), y2.reshape(n, chunk),
             m2.reshape(n, chunk)))
        return nll_sum, hit_sum

    def lm_loss_fn(self):
        """Contract for ``train.make_custom_train_step``: batch dict with
        ``input_ids`` [b, s] and optional ``loss_mask`` [b, s-1]; next-token
        targets are the shifted inputs."""

        def loss_fn(params, model_state, batch, rng, train):
            c = self.config
            ids = batch["input_ids"]
            hidden, aux = self.apply(params, ids[:, :-1], train=train,
                                     rng=rng, return_aux=True)
            targets = ids[:, 1:]
            mask = batch.get("loss_mask")
            with jax.named_scope("head_loss"):
                if c.loss_seq_chunk:
                    nll_sum, hit_sum = self._chunked_lm_stats(
                        self._head_word(params), hidden, targets, mask,
                        c.loss_seq_chunk)
                    if mask is None:
                        count = jnp.asarray(targets.size, jnp.float32)
                        loss = nll_sum / count
                        acc = hit_sum / count
                    else:
                        w = jnp.sum(mask.astype(jnp.float32))
                        loss = nll_sum / jnp.maximum(w, 1e-9)
                        acc = hit_sum / jnp.maximum(w, 1.0)
                else:
                    logits = self.logits(params, hidden)
                    loss = \
                        loss_lib.softmax_cross_entropy_with_integer_labels(
                            logits, targets, where=mask)
                    hits = (jnp.argmax(logits, -1) == targets
                            ).astype(jnp.float32)
                    if mask is not None:
                        acc = (jnp.sum(hits * mask)
                               / jnp.maximum(jnp.sum(mask), 1.0))
                    else:
                        acc = jnp.mean(hits)
            metrics = {"token_accuracy": acc}
            if mask is not None:
                # normalizer for exact gradient accumulation (train.step)
                metrics["loss_weight"] = jnp.sum(mask).astype(jnp.float32)
            if self.config.moe_experts > 0:
                metrics["moe_aux"] = aux
            return loss + aux, (metrics, model_state)

        return loss_fn

    def lm_1f1b_value_and_grad(self, params, batch, rng=None,
                               train: bool = True):
        """Full-model causal-LM training pass under the hand-scheduled
        **1F1B** pipeline -> ``(loss, grads)`` with ``grads`` matching the
        ``params`` tree (what ``jax.value_and_grad(lm_loss_fn)`` returns on
        the GPipe path, at O(stages) activation memory instead of
        O(microbatches)).

        Composition: embeddings run pipe-replicated under an explicit
        ``jax.vjp`` whose cotangent is the pipeline's ``dx``; the decoder
        stages run ``parallel.pipeline.pipeline_value_and_grad``; final-LN
        + tied LM head + softmax-CE are the pipeline's ``loss_fn`` with
        ``aux_params`` (their grads come back pipe-replicated).  The tied
        word embedding accumulates BOTH paths: embed-side lookup grads +
        head-side logit grads.
        """
        c = self.config
        if c.pipeline_stages <= 1:
            raise ValueError("lm_1f1b_value_and_grad requires "
                             "pipeline_stages > 1")
        if c.loss_seq_chunk:
            import warnings
            warnings.warn(
                "loss_seq_chunk is not applied on the 1F1B path: head_loss "
                "builds full-width logits per microbatch (already 1/N of "
                "the batch).  Use the GPipe path (the normal train step) "
                "for chunked-loss memory savings.", stacklevel=2)
        from ..parallel.pipeline import pipeline_value_and_grad
        if rng is None:
            if train:
                raise ValueError("train=True requires rng")
            rng = jax.random.PRNGKey(0)
        ids = batch["input_ids"]
        inputs, targets = ids[:, :-1], ids[:, 1:]
        mask = batch.get("loss_mask")
        r_emb, r_layers = jax.random.split(rng)

        x_emb, vjp_embed = jax.vjp(
            lambda emb: self._embed(emb, inputs, r_emb, train),
            params["embeddings"])

        layer_fn = self._make_layer_fn(inputs.shape[1])
        layer_keys = jax.random.split(r_layers, c.num_layers)
        stage_params, stage_fn = self._pipeline_stage_bits(
            params, layer_keys, train, layer_fn)

        aux = {"ln_f": params["ln_f"], "word": self._head_word(params)}

        def head_loss(a, out_mb, y_mb):
            h = self._norm(a["ln_f"], out_mb)
            logits = self._logits_from_word(a["word"], h)
            return loss_lib.softmax_cross_entropy_with_integer_labels(
                logits, y_mb["t"], where=y_mb.get("m"))

        n_micro = c.pipeline_microbatches or c.pipeline_stages
        y = {"t": targets}
        weights = None
        if mask is not None:
            # masked-mean loss: each microbatch's masked mean weighs in by
            # its share of the global mask count (uniform weights would be
            # wrong whenever microbatch mask counts differ)
            y["m"] = mask
            per_mb = mask.reshape(n_micro, -1).sum(axis=1).astype(
                jnp.float32)
            # 1e-9 floor, same as ops.losses: a 1.0 floor would silently
            # shrink fractional-weight batches relative to the GPipe path
            weights = per_mb / jnp.maximum(per_mb.sum(), 1e-9)

        loss, stage_grads, aux_grads, dx = pipeline_value_and_grad(
            stage_fn, head_loss, stage_params, x_emb, y, self.mesh,
            n_micro, axis=c.pipe_axis, aux_params=aux, with_dx=True,
            microbatch_weights=weights)

        (emb_grads,) = vjp_embed(dx)
        emb_grads = dict(emb_grads)
        grads = {
            "embeddings": emb_grads,
            "decoder": jax.tree.map(
                lambda g, p: g.reshape(p.shape),
                stage_grads["layers"], params["decoder"]),
            "ln_f": aux_grads["ln_f"],
        }
        if c.tied_head:
            # tied embedding: head-side grads add to the lookup-side grads
            emb_grads["word"] = (emb_grads["word"]
                                 + aux_grads["word"].astype(
                                     emb_grads["word"].dtype))
        else:
            grads["lm_head"] = aux_grads["word"]
        return loss, grads

    # -- LoRA adapters ----------------------------------------------------
    # Low-rank per-request adapters for the serving tier (serve/ +
    # fleet/): many fine-tuned variants of one base serve from ONE set of
    # base weights.  An adapter adds rank-r deltas to the four attention
    # projections — q/k/v get x @ a @ b added to the projection output
    # BEFORE RoPE (both are linear, so this equals projecting with the
    # merged kernel W + a@b, pinned by ``merge_lora`` parity tests); the
    # out projection gets attn @ a @ b.  Adapters live in a fixed-
    # capacity STACKED table ([T, L, ...] leaves) indexed by a traced
    # per-row slot -> table-row vector, so loading, evicting, and
    # swapping adapters never changes any compiled executable
    # (serve.adapters.AdapterTable is the host-side manager).  Row 0 is
    # reserved all-zero: ``adapter_id=None`` requests resolve to it and
    # their delta is an exact zero — output tokens identical to an
    # adapter-free engine.

    _LORA_TARGETS = ("query", "key", "value", "out")

    def lora_shapes(self, rank: int) -> Dict[str, Any]:
        """{target: (a_shape, b_shape)} for ONE layer of a rank-``rank``
        adapter (the per-adapter leaves prepend [num_layers], the table
        leaves [capacity, num_layers])."""
        c = self.config
        h, hd, d = c.num_heads, c.head_dim, c.hidden_size
        kv = c.kv_heads
        return {
            "query": ((d, rank), (rank, h, hd)),
            "key": ((d, rank), (rank, kv, hd)),
            "value": ((d, rank), (rank, kv, hd)),
            "out": ((h, hd, rank), (rank, d)),
        }

    def init_lora(self, key, rank: int, scale: float = 1.0):
        """One adapter: {target: {a, b}} with [L, ...] leaves.  Standard
        LoRA init — ``a`` ~ N(0, 0.02) truncated, ``b`` zeros, so a fresh
        adapter is a no-op until trained/loaded; bake any alpha/r scaling
        into ``b`` (``scale`` multiplies ``a`` for synthetic tests)."""
        if rank < 1:
            raise ValueError(f"rank must be >= 1; got {rank}")
        c = self.config
        trunc = init_lib.truncated_normal(0.02)
        keys = jax.random.split(key, len(self._LORA_TARGETS))
        adapter = {}
        for k_t, (name, (a_shape, b_shape)) in zip(
                keys, self.lora_shapes(rank).items()):
            adapter[name] = {
                "a": trunc(k_t, (c.num_layers,) + a_shape) * scale,
                "b": jnp.zeros((c.num_layers,) + b_shape, jnp.float32),
            }
        return adapter

    def init_lora_table(self, capacity: int, rank: int):
        """All-zero stacked adapter table: {target: {a, b}} with
        [capacity, L, ...] leaves.  Row 0 is the reserved zero adapter
        (``adapter_id=None``) — never write it."""
        if capacity < 2:
            raise ValueError(f"capacity must be >= 2 (row 0 is the "
                             f"reserved zero adapter); got {capacity}")
        c = self.config
        return {name: {"a": jnp.zeros((capacity, c.num_layers) + a_shape,
                                      jnp.float32),
                       "b": jnp.zeros((capacity, c.num_layers) + b_shape,
                                      jnp.float32)}
                for name, (a_shape, b_shape)
                in self.lora_shapes(rank).items()}

    @staticmethod
    def lora_insert_row(table, row, adapter):
        """Splice one adapter into table row ``row`` (traced index —
        ONE executable loads every row; jit with the table donated)."""
        def splice(buf, leaf):
            starts = (jnp.asarray(row, jnp.int32),) \
                + (jnp.int32(0),) * leaf.ndim
            return lax.dynamic_update_slice(
                buf, leaf[None].astype(buf.dtype), starts)
        return jax.tree.map(splice, table, adapter)

    def merge_lora(self, params, adapter):
        """Base params with the adapter's deltas MERGED into the four
        attention projection kernels — the exactness oracle: running the
        merged params adapter-free must match running the base params
        with the adapter applied per-request."""
        merged = jax.tree.map(lambda x: x, params)   # shallow-ish copy
        dec_p = dict(merged["decoder"])
        for name in self._LORA_TARGETS:
            a, b = adapter[name]["a"], adapter[name]["b"]
            if name == "out":
                delta = jnp.einsum("lhkr,lrd->lhkd", a, b)
            else:
                delta = jnp.einsum("ldr,lrhk->ldhk", a, b)
            attn = dict(dec_p["attention"])
            attn[name] = dict(attn[name],
                              kernel=attn[name]["kernel"] + delta)
            dec_p["attention"] = attn
        merged["decoder"] = dec_p
        return merged

    def _lora_deltas(self, adapters, adapter_rows, i, dtype):
        """Per-row rank-r projection deltas for layer ``i``:
        {target: fn(x) -> delta}.  ``adapters``: stacked [T, L, ...]
        table leaves; ``adapter_rows`` [b]: each batch row's table row.
        The gathers are [b, ...] slices of a tiny table — the einsum
        chain is O(b·s·d·r), negligible beside the dense projection."""
        def gathered(name):
            a = lax.dynamic_index_in_dim(adapters[name]["a"], i, 1,
                                         keepdims=False)     # [T, ...]
            b = lax.dynamic_index_in_dim(adapters[name]["b"], i, 1,
                                         keepdims=False)
            return (jnp.take(a, adapter_rows, axis=0).astype(dtype),
                    jnp.take(b, adapter_rows, axis=0).astype(dtype))

        def qkv_delta(name):
            a, b = gathered(name)                 # [b,d,r], [b,r,h,hd]
            def fn(x):                            # x: [b, s, d]
                t = jnp.einsum("bsd,bdr->bsr", x, a)
                return jnp.einsum("bsr,brhk->bshk", t, b)
            return fn

        def out_delta():
            a, b = gathered("out")                # [b,h,hd,r], [b,r,d]
            def fn(attn):                         # attn: [b, s, h, hd]
                t = jnp.einsum("bshk,bhkr->bsr", attn, a)
                return jnp.einsum("bsr,brd->bsd", t, b)
            return fn

        return {"query": qkv_delta("query"), "key": qkv_delta("key"),
                "value": qkv_delta("value"), "out": out_delta()}

    # -- KV-cache decode --------------------------------------------------
    def init_cache(self, batch_size: int, max_len: Optional[int] = None):
        c = self.config
        max_len = max_len or c.max_position
        # kv_heads, not num_heads: GQA's cache is the whole point
        shape = (c.num_layers, batch_size, max_len, c.kv_heads, c.head_dim)
        if c.kv_cache_dtype == "int8":
            sshape = shape[:-1] + (1,)   # per-(token, head) scale
            return {"k": jnp.zeros(shape, jnp.int8),
                    "v": jnp.zeros(shape, jnp.int8),
                    "k_scale": jnp.zeros(sshape, jnp.float32),
                    "v_scale": jnp.zeros(sshape, jnp.float32),
                    "pos": jnp.zeros((), jnp.int32)}
        return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype),
                "pos": jnp.zeros((), jnp.int32)}

    def paged_cache_spec(self) -> Dict[str, Any]:
        """What a slot's paged cache is made of (``serve/pages.py`` builds
        the pool from it): K/V for every layer, int8 scale planes
        included, and no recurrent state.  A token's heads are one FLAT
        row of ``kv_heads * head_dim`` (a scale plane: ``kv_heads``), as
        ``HybridDecoder.paged_cache_spec`` has them, so the pool is
        ``[L, num_pages, page_size, kv_heads * head_dim]``: a page is
        ``page_size`` sublanes by whole lane tiles and tiles on the TPU
        with almost no padding (``[.., 25, 64]`` minor dimensions pad
        2.56 x)."""
        c = self.config
        token = (c.kv_heads * c.head_dim,)
        if c.kv_cache_dtype == "int8":
            scale = ((c.kv_heads,), jnp.dtype(jnp.float32))
            kv = {"k": (token, jnp.dtype(jnp.int8)),
                  "v": (token, jnp.dtype(jnp.int8)),
                  "k_scale": scale, "v_scale": scale}
        else:
            kv = {"k": (token, jnp.dtype(c.dtype)),
                  "v": (token, jnp.dtype(c.dtype))}
        return {"kv_layers": c.num_layers, "kv": kv, "state": {}}

    @staticmethod
    def _cache_kv(cache):
        """The scan-carried K/V subtree of a cache dict (everything but
        the position pointer)."""
        return {k: v for k, v in cache.items() if k != "pos"}

    def _dequant_layer_kv(self, kv, i):
        """Layer ``i``'s (k, v) read from the carried cache subtree, in
        the compute dtype — dequantizing int8 entries at the operand
        (XLA fuses the widen+scale into the attention einsum)."""
        k_all = lax.dynamic_index_in_dim(kv["k"], i, keepdims=False)
        v_all = lax.dynamic_index_in_dim(kv["v"], i, keepdims=False)
        if "k_scale" not in kv:
            return k_all, v_all
        from ..ops import quant
        dtype = self.config.dtype
        ks = lax.dynamic_index_in_dim(kv["k_scale"], i, keepdims=False)
        vs = lax.dynamic_index_in_dim(kv["v_scale"], i, keepdims=False)
        return (quant.dequantize_tensor(quant.QTensor(k_all, ks), dtype),
                quant.dequantize_tensor(quant.QTensor(v_all, vs), dtype))

    def _paged_layer_kv(self, kv, i, page_tab):
        """Layer ``i``'s (k, v) read from a PAGE POOL through per-row
        page tables, in the compute dtype.

        ``kv``: pool subtree with ``[L, num_pages, page_size, kv_heads *
        head_dim]`` leaves (scale planes ``[..., kv_heads]``;
        serve/pages.py); ``page_tab`` [b, pages_per_row]
        int32: row r's logical page j lives at pool page
        ``page_tab[r, j]``.  One gather on the pool picks the rows'
        pages of layer ``i``, and a reshape of the gathered rows gives
        the same ``[b, view_len, kv_heads, head_dim]`` operand
        ``decode_step`` reads from an ``init_cache`` row (``view_len =
        pages_per_row * page_size``), so downstream attention math —
        int8 dequant at the operand included — is IDENTICAL to the
        ``generate()`` path's; the indirection swaps per-slot worst-case
        stripes for pay-as-you-go pages without touching the compiled
        attention."""
        kv_heads = self.config.kv_heads

        def view(name):
            g = kv[name][i, page_tab]               # [b, mp, pg, width]
            return g.reshape(g.shape[0], g.shape[1] * g.shape[2],
                             kv_heads, -1)
        k_all, v_all = view("k"), view("v")
        if "k_scale" not in kv:
            return k_all, v_all
        from ..ops import quant
        dtype = self.config.dtype
        return (quant.dequantize_tensor(
                    quant.QTensor(k_all, view("k_scale")), dtype),
                quant.dequantize_tensor(
                    quant.QTensor(v_all, view("v_scale")), dtype))

    def decode_step(self, params, cache, token_ids, kv_valid=None,
                    positions=None):
        """One token through the stack against the cache.

        token_ids: [b] int32 — the token at position ``cache['pos']``.
        Returns (logits [b, vocab] f32, new cache).  Static shapes: cache
        reads are masked by position, writes are ``dynamic_update_slice``.

        Ragged-prompt serving (``generate(prompt_valid=...)``): ``kv_valid``
        [b, max_len] additionally masks per-row cache positions (left-pad
        slots), and ``positions`` [b] supplies per-row position indices
        (cache position minus the row's pad length) so learned/RoPE
        embeddings see each row's REAL token positions.
        """
        c = self.config
        b = token_ids.shape[0]
        pos = cache["pos"]
        emb = params["embeddings"]
        x = jnp.take(emb["word"], token_ids, axis=0)[:, None, :]   # [b,1,d]
        if c.position_embedding == "learned":
            if positions is not None:
                x = x + jnp.take(emb["position"], positions,
                                 axis=0)[:, None, :]
            else:
                x = x + lax.dynamic_slice_in_dim(emb["position"], pos,
                                                 1)[None]
        x = x.astype(c.dtype)

        max_len = cache["k"].shape[2]
        # keys at positions > pos are zeros/garbage — mask them out
        # (additive 0/-inf convention of ops.attention)
        kv_mask = jnp.where(jnp.arange(max_len) <= pos, 0.0,
                            attn_lib.NEG_INF)[None, None, None, :]
        if kv_valid is not None:
            kv_mask = kv_mask + jnp.where(kv_valid, 0.0, attn_lib.NEG_INF
                                          )[:, None, None, :]

        # rope tables built ONCE per call, not once per layer (cos/sin are
        # identical across the layer scan — same hoist as _rope_transform)
        rope_cs = None
        if c.position_embedding == "rope":
            # rotate q and THIS k at its own position; cached keys were
            # rotated when written, matching the full-sequence path
            pos1 = (positions[:, None] if positions is not None
                    else jnp.full((1,), pos))
            rope_cs = attn_lib.rope_tables(pos1, c.head_dim,
                                           base=c.rope_base)

        def attention(q, k_blk, v_blk, kv, i):
            del k_blk, v_blk   # single token: read back through the cache
            k_cache, v_cache = self._dequant_layer_kv(kv, i)
            # GQA handled natively by the dense kernel (grouped einsum
            # against the unrepeated cache — no full-head materialization)
            return attn_lib.dot_product_attention(q, k_cache, v_cache,
                                                  mask=kv_mask)

        def body(carry, inputs):
            x, kv = carry
            p, i = inputs
            return self._cache_layer(p, x, kv, i,
                                     write_pos=pos, rope_cs=rope_cs,
                                     attention=attention), None

        (x, new_kv), _ = lax.scan(
            body, (x, self._cache_kv(cache)),
            (params["decoder"], jnp.arange(c.num_layers)))
        x = self._norm(params["ln_f"], x)
        logits = self.logits(params, x)[:, 0, :]
        return logits, dict(new_kv, pos=pos + 1)

    def decode_step_slots_paged(self, params, kv, token_ids, page_tab,
                                start_col, write_col, positions,
                                adapters=None, adapter_rows=None,
                                use_kernel: bool = False):
        """One token per row against a PAGED slot cache (continuous
        batching): the serving tier's hot step (serve/).

        The batch dimension is a bank of SLOTS, each an independent
        request, and per-row state replaces ``decode_step``'s scalar
        ``pos``: row r's token writes at its logical column
        ``write_col[r]``, attends the columns ``start_col[r]`` up to and
        with its own (an empty run when ``start_col[r] > write_col[r]``:
        how the caller marks a row that is not live), and embeds at
        ``positions[r]`` (the row's token count).  The
        K/V live in a shared page pool (``kv``: ``[L, num_pages,
        page_size, ...]`` leaves) indexed by the per-row ``page_tab``
        [b, pages_per_row]: reads gather each row's pages
        into the usual ``[b, view_len, ...]`` operand
        (``_paged_layer_kv``), the write scatters into pool cell
        ``(page_tab[r, write_col[r] // page_size], write_col[r] %
        page_size)``.  Both the table and the column state are traced,
        so page allocation, shared-prefix mapping, and slot retirement
        never change the compiled step (serve/pages.py owns the host
        bookkeeping).  Rows whose table maps the reserved trash page 0
        are retired: their writes land where no validity mask looks.

        Returns (logits [b, vocab] f32, new kv pool).  Per row the math
        is exactly ``decode_step`` at ``pos = write_col[r]`` on the
        gathered view, and every op is row-independent, so admitting or
        retiring one slot cannot change another slot's logits
        (tests/test_pages.py).  State advancement — bumping
        write_col/positions — is the caller's job
        (serve.pages.decode_paged_step), because only the scheduler
        knows which rows are live.

        ``adapters`` / ``adapter_rows`` [b]: per-row LoRA deltas from a
        stacked adapter table (see the LoRA section above) — row r runs
        table row ``adapter_rows[r]``'s adapter; row 0 of the table is
        the zero adapter, so mixing adapter and non-adapter requests in
        one tick costs one gather, never a recompile.

        ``use_kernel`` (STATIC, resolved by the caller through
        ``attn_lib.resolve_use_paged_kernel``): read the pool through
        the fused Pallas kernel (ops/pallas/paged_attention.py) — the
        page walk happens inside the attention loop, over the table
        entries a row's run holds and no others, and neither the
        gathered ``[b, view_len, ...]`` operand nor a ``[b, view_len]``
        mask materializes.  The write path is the same either way;
        tests pin kernel == gather token streams bit-for-bit.
        """
        c = self.config
        emb = params["embeddings"]
        x = jnp.take(emb["word"], token_ids, axis=0)[:, None, :]  # [b,1,d]
        if c.position_embedding == "learned":
            x = x + jnp.take(emb["position"], positions,
                             axis=0)[:, None, :]
        x = x.astype(c.dtype)

        page_size = kv["k"].shape[2]

        rope_cs = None
        if c.position_embedding == "rope":
            rope_cs = attn_lib.rope_tables(positions[:, None], c.head_dim,
                                           base=c.rope_base)

        # write cell per row, from the traced table (clamped index: a
        # full slot's frozen write head cannot run off its table row)
        page_idx = jnp.minimum(write_col // page_size,
                               page_tab.shape[1] - 1)
        w_pages = jnp.take_along_axis(page_tab, page_idx[:, None],
                                      axis=1)[:, 0]
        paged = (w_pages, write_col % page_size)

        if use_kernel:
            from ..ops.pallas import paged_attention as paged_lib
            # the same pages for every layer: walked once, out here
            walk = paged_lib.page_walk(kv, page_tab, start_col,
                                       write_col + 1)

            def attention(q, k_blk, v_blk, kv, i):
                del k_blk, v_blk   # one token: read back through the pool
                return paged_lib.paged_decode_attention(q, kv, i, walk)
        else:
            cols = jnp.arange(page_tab.shape[1] * page_size)[None, :]
            kv_mask = jnp.where(
                (cols >= start_col[:, None]) & (cols <= write_col[:, None]),
                0.0, attn_lib.NEG_INF)[:, None, None, :]

            def attention(q, k_blk, v_blk, kv, i):
                del k_blk, v_blk
                k_cache, v_cache = self._paged_layer_kv(kv, i, page_tab)
                return attn_lib.dot_product_attention(q, k_cache, v_cache,
                                                      mask=kv_mask)

        def body(carry, inputs):
            x, kv = carry
            p, i = inputs
            return self._cache_layer(p, x, kv, i,
                                     write_pos=None, rope_cs=rope_cs,
                                     attention=attention,
                                     adapters=adapters,
                                     adapter_rows=adapter_rows,
                                     paged=paged), None

        (x, new_kv), _ = lax.scan(
            body, (x, dict(kv)),
            (params["decoder"], jnp.arange(c.num_layers)))
        x = self._norm(params["ln_f"], x)
        return self.logits(params, x)[:, 0, :], new_kv

    def _cache_layer(self, p, x, kv, i, *, write_pos, rope_cs,
                     attention, adapters=None, adapter_rows=None,
                     paged=None):
        """ONE decoder layer of the KV-cache path — shared by decode_step
        (s=1 against the cache) and decode_block (whole-prompt prefill)
        so the layer math can never diverge between them.  The cache
        subtree ``kv`` ({k, v[, k_scale, v_scale]}) rides the scan
        CARRY, not the scanned ys: as ys each layer would write its FULL
        [b, max_len, h, d] cache back out every call when only
        ``write_pos`` onward changes; as carry the updates are in-place
        slice writes.  When scale entries are present the write
        quantizes to symmetric per-(token, head) int8 (the
        ``kv_cache_dtype="int8"`` decode-bandwidth lever).

        ``attention(q, k_blk, v_blk, kv, i)`` supplies the step/block-
        specific attention read; ``rope_cs``: (cos, sin) tables hoisted
        out of the layer scan.

        ``adapters``/``adapter_rows``: per-row LoRA projection deltas
        (see the LoRA section) — q/k/v deltas add BEFORE RoPE so the
        result equals projecting with the merged kernel.

        The write has two cases.  ``write_pos``, a scalar: one column
        for the whole batch (decode_step, decode_block, decode_window —
        the generate/beam/speculative path).

        ``paged``: (page_ids [N], offs [N]) with N = b*s — the cache is
        a PAGE POOL ([L, num_pages, page_size, kv_heads * head_dim]
        leaves, serve/pages.py) and token t of the flattened (b, s)
        window writes its flat row at pool cell ``(i, page_ids[t],
        offs[t])`` instead of a column of a per-row stripe.  The traced
        indices come from a per-slot page table, so every (slot, page)
        assignment runs the SAME executable; ``write_pos`` is ignored
        for the write (reads still go through the table in
        ``attention``).
        """
        h = self._norm(p["ln_1"], x)
        a = p["attention"]
        dtype = h.dtype
        lora = (self._lora_deltas(adapters, adapter_rows, i, dtype)
                if adapters is not None else None)

        def proj(name):
            pp = a[name]
            y = jnp.einsum("bsd,dhk->bshk", h,
                           pp["kernel"].astype(dtype))
            if lora is not None:
                y = y + lora[name](h)
            if "bias" in pp:
                y = y + pp["bias"].astype(dtype)
            return y

        q, k, v = proj("query"), proj("key"), proj("value")
        if rope_cs is not None:
            q = attn_lib.apply_rope(q, *rope_cs)
            k = attn_lib.apply_rope(k, *rope_cs)
        zero = jnp.zeros((), jnp.int32)

        def page_write(name, val):
            """Pool-cell scatter, in place on the carried pool: the
            flattened (b, s) tokens' flat rows land at ``(i,
            page_ids[t], offs[t])`` — N rows written, and no layer of
            the pool sliced out or written back (on the chip that moved
            a 67 MB padded layer twice per leaf to place 8 rows).
            Live slots always map disjoint write cells (a slot's write
            page is private — serve/pages.py); retired rows map the
            reserved trash page 0, whose cells no validity mask ever
            admits, so their frozen writes are dead weight, not state."""
            flat = val.reshape(val.shape[0] * val.shape[1], -1)
            kv[name] = kv[name].at[(i,) + paged].set(
                flat.astype(kv[name].dtype))

        def write(name, val):
            if "k_scale" in kv:
                # ONE quantization scheme repo-wide: ops.quant's
                # symmetric int8 with a per-(token, head) scale (the
                # last axis is the reduced one)
                from ..ops import quant
                qt = quant.quantize_tensor(val, reduce_axes=(-1,))
                if paged is not None:
                    page_write(name, qt.q)
                    page_write(name + "_scale", qt.scale)
                else:
                    kv[name] = lax.dynamic_update_slice(
                        kv[name], qt.q[None],
                        (i, zero, write_pos, zero, zero))
                    kv[name + "_scale"] = lax.dynamic_update_slice(
                        kv[name + "_scale"], qt.scale[None],
                        (i, zero, write_pos, zero, zero))
            elif paged is not None:
                page_write(name, val)
            else:
                kv[name] = lax.dynamic_update_slice(
                    kv[name], val[None].astype(kv[name].dtype),
                    (i, zero, write_pos, zero, zero))

        kv = dict(kv)
        write("k", k)
        write("v", v)
        attn = attention(q, k, v, kv, i)
        attn_out = jnp.einsum("bshk,hkd->bsd", attn,
                              a["out"]["kernel"].astype(dtype))
        if lora is not None:
            attn_out = attn_out + lora["out"](attn)
        if "bias" in a["out"]:
            attn_out = attn_out + a["out"]["bias"].astype(dtype)
        x = x + attn_out
        ffn_out, _ = self._ffn(p, x)   # aux unused at decode
        return x + ffn_out, kv

    def decode_block(self, params, cache, token_ids, kv_valid=None,
                     positions=None):
        """Prefill: push a WHOLE [b, s] prompt block through the stack
        into an EMPTY cache in one forward — one batched matmul pass per
        layer instead of ``s`` sequential ``decode_step`` calls, which is
        the difference between 1 dispatch and ``s`` dependent MXU-starved
        steps for long prompts (time-to-first-token).

        Requires ``cache['pos'] == 0`` (the generate/beam_search prefill
        call sites — the in-block causal mask assumes the cache holds
        nothing before the block).  ``kv_valid`` [b, s]: per-row validity
        of the block columns (left-padded ragged prompts); ``positions``
        [b, s]: per-row position indices for learned/RoPE embeddings.
        Returns (logits [b, vocab] f32 at the LAST block position, cache
        with pos advanced by ``s``).
        """
        c = self.config
        b, s = token_ids.shape
        emb = params["embeddings"]
        x = jnp.take(emb["word"], token_ids, axis=0)            # [b,s,d]
        if c.position_embedding == "learned":
            pos_idx = (positions if positions is not None
                       else jnp.arange(s))
            x = x + jnp.take(emb["position"], pos_idx, axis=0)
        x = x.astype(c.dtype)

        # The cache beyond the block is empty, so attention reads the
        # block's own keys — s x s scores, never s x max_len.  Past the
        # measured crossover the causal no-padding case dispatches the
        # fused flash kernel exactly like the full forward; ragged
        # prompts need the per-row pad mask, which the dense path takes
        # additively.
        if kv_valid is None and attn_lib.resolve_use_flash(c.use_flash, s):
            from ..ops.pallas.flash_attention import make_flash_attention_fn
            flash_fn = make_flash_attention_fn(causal=True, mesh=self.mesh)

            def block_attn(q, k_blk, v_blk, kv, i):
                del kv, i
                return flash_fn(q, k_blk, v_blk)
        else:
            mask = attn_lib.causal_mask(s)
            if kv_valid is not None:
                mask = mask + attn_lib.padding_mask(kv_valid)

            def block_attn(q, k_blk, v_blk, kv, i):
                del kv, i
                return attn_lib.dot_product_attention(q, k_blk, v_blk,
                                                      mask=mask)

        rope_cs = None
        if c.position_embedding == "rope":
            rope_pos = (positions if positions is not None
                        else jnp.arange(s))
            rope_cs = attn_lib.rope_tables(rope_pos, c.head_dim,
                                           base=c.rope_base)

        def body(carry, inputs):
            x, kv = carry
            p, i = inputs
            return self._cache_layer(p, x, kv, i,
                                     write_pos=jnp.zeros((), jnp.int32),
                                     rope_cs=rope_cs,
                                     attention=block_attn), None

        (x, new_kv), _ = lax.scan(
            body, (x, self._cache_kv(cache)),
            (params["decoder"], jnp.arange(c.num_layers)))
        # head on the last position only — [b, s, vocab] never materializes
        x = self._norm(params["ln_f"], x[:, -1:, :])
        logits = self.logits(params, x)[:, 0, :]
        return logits, dict(new_kv, pos=cache["pos"] + s)

    def decode_window(self, params, cache, token_ids, head: str = "all",
                      adapters=None, adapter_rows=None):
        """``s`` tokens against a NON-empty cache in one forward.

        The generalization of ``decode_block`` to ``cache['pos'] > 0``:
        row ``j`` of the window attends every cache column ``<= pos + j``
        (prefix plus in-window causal), K/V are written at columns
        ``pos..pos+s-1``.  This is the verification step of speculative
        decoding (models/speculative.py): the target model scores all
        draft tokens in ONE dispatch instead of s sequential
        decode_steps.  Rollback is the caller's job: setting ``pos`` back
        masks (and later overwrites) any rejected columns.

        ``head``: what the LM head computes — ``"all"`` ([b, s, vocab]
        f32, the verification shape), ``"last"`` ([b, vocab], prefill's
        next-token shape), ``"none"`` (logits is None — intermediate
        chunked-prefill windows only feed the cache, and the [b, s,
        vocab] tensor must not materialize for them).

        ``adapters``/``adapter_rows`` [b]: per-row LoRA deltas (see the
        LoRA section) — the serve tier prefills each request under its
        own adapter through this path.
        """
        if head not in ("all", "last", "none"):
            raise ValueError(f"head must be all|last|none; got {head!r}")
        c = self.config
        b, s = token_ids.shape
        pos = cache["pos"]
        emb = params["embeddings"]
        x = jnp.take(emb["word"], token_ids, axis=0)            # [b,s,d]
        win_pos = pos + jnp.arange(s)
        if c.position_embedding == "learned":
            x = x + jnp.take(emb["position"], win_pos, axis=0)
        x = x.astype(c.dtype)

        max_len = cache["k"].shape[2]
        # col visible to window row j iff col <= pos + j
        col = jnp.arange(max_len)[None, None, None, :]
        row = win_pos[None, None, :, None]
        kv_mask = jnp.where(col <= row, 0.0, attn_lib.NEG_INF)

        rope_cs = None
        if c.position_embedding == "rope":
            rope_cs = attn_lib.rope_tables(win_pos, c.head_dim,
                                           base=c.rope_base)

        def window_attn(q, k_blk, v_blk, kv, i):
            del k_blk, v_blk   # read back through the cache (prefix + win)
            k_cache, v_cache = self._dequant_layer_kv(kv, i)
            return attn_lib.dot_product_attention(q, k_cache, v_cache,
                                                  mask=kv_mask)

        def body(carry, inputs):
            x, kv = carry
            p, i = inputs
            return self._cache_layer(p, x, kv, i,
                                     write_pos=pos, rope_cs=rope_cs,
                                     attention=window_attn,
                                     adapters=adapters,
                                     adapter_rows=adapter_rows), None

        (x, new_kv), _ = lax.scan(
            body, (x, self._cache_kv(cache)),
            (params["decoder"], jnp.arange(c.num_layers)))
        new_cache = dict(new_kv, pos=pos + s)
        if head == "none":
            return None, new_cache
        if head == "last":
            x = self._norm(params["ln_f"], x[:, -1:, :])
            return self.logits(params, x)[:, 0, :], new_cache
        x = self._norm(params["ln_f"], x)
        return self.logits(params, x), new_cache

    def decode_window_paged(self, params, kv, token_ids, page_row, pos,
                            head: str = "all", adapters=None,
                            adapter_rows=None, use_kernel: bool = False,
                            valid=None):
        """``decode_window`` against a PAGED cache, for a BATCH of
        windows: row r of ``token_ids`` [n, s] is one request's window at
        positions ``pos[r] .. pos[r] + s - 1``, reading and writing the
        shared page pool through its own table row ``page_row[r]``
        [n, pages_per_row] int32.  The batch-1 call form — a rank-1
        ``page_row`` and a scalar ``pos`` (and ``valid``) — is the n = 1
        case of the same code.

        The serve tier's chunked-prefill step under paging
        (serve/pages.py): ``pos`` is TRACED, so a request that maps shared
        prefix pages simply starts its first window at ``pos = skip`` —
        the skipped windows are never dispatched, yet row j of a window
        still attends every cache column ``<= pos + j`` (shared pages
        included).  The windows of one dispatch read the weights once.

        Structure: ``decode_window``'s (embed at ``pos + j``, RoPE at the
        window positions, write-then-attend per layer), but the cache is
        the POOL: K/V land on their pool cells ``(page_row[c //
        page_size], c % page_size)`` via ``_cache_layer``'s page-write,
        and each row reads its own pages back — gathered into the usual
        ``[n, view_len, ...]`` operand under the ``col <= pos + j`` mask
        (``_paged_layer_kv``), or, with ``use_kernel`` (STATIC), walked
        inside the fused Pallas kernel
        (``ops.pallas.paged_window_attention``), which computes the same
        mask from each row's run and fetches no page past the window's
        last column.

        ``valid`` [n] (None: every column of every row): how many of a
        row's tokens are real.  The columns past them are written to the
        reserved trash page 0 — dead weight, not state — and a row with
        ``valid == 0`` is PADDING of the batch: it writes nothing else and
        its kernel walk is empty.

        ``head`` as in ``decode_window``, with ``"last"`` the logits at
        each row's last REAL position, taken before the head matmul:
        ``[n, vocab]``.  Returns (logits, new kv pool) — the pool subtree
        carries no ``pos``; the caller owns positions (serve/scheduler
        tracks them host-side).
        """
        if head not in ("all", "last", "none"):
            raise ValueError(f"head must be all|last|none; got {head!r}")
        c = self.config
        n, s = token_ids.shape
        page_size = kv["k"].shape[2]
        win = attn_lib.paged_windows(page_row, pos, valid, n, s, page_size)
        emb = params["embeddings"]
        x = jnp.take(emb["word"], token_ids, axis=0)            # [n,s,d]
        if c.position_embedding == "learned":
            x = x + jnp.take(emb["position"], win.cols, axis=0)
        x = x.astype(c.dtype)

        rope_cs = None
        if c.position_embedding == "rope":
            rope_cs = attn_lib.rope_tables(win.cols, c.head_dim,
                                           base=c.rope_base)
        paged = (win.pages, win.offs)

        if use_kernel:
            from ..ops.pallas import paged_attention as paged_lib
            # prefix + window: the columns up to the window's last, on
            # the same pages for every layer; a padding row has none
            walk = paged_lib.page_walk(
                kv, win.page_rows, jnp.zeros((n,), jnp.int32),
                jnp.where(win.valid > 0, win.pos + s, 0))

            def window_attn(q, k_blk, v_blk, kv, i):
                del k_blk, v_blk   # read back through the pool
                return paged_lib.paged_window_attention(q, kv, i, walk)
        else:
            kv_mask = attn_lib.paged_window_mask(win, page_size)

            def window_attn(q, k_blk, v_blk, kv, i):
                del k_blk, v_blk   # read back through the pool
                k_cache, v_cache = self._paged_layer_kv(kv, i,
                                                        win.page_rows)
                return attn_lib.dot_product_attention(q, k_cache, v_cache,
                                                      mask=kv_mask)

        def body(carry, inputs):
            x, kv = carry
            p, i = inputs
            return self._cache_layer(p, x, kv, i,
                                     write_pos=None, rope_cs=rope_cs,
                                     attention=window_attn,
                                     adapters=adapters,
                                     adapter_rows=adapter_rows,
                                     paged=paged), None

        (x, new_kv), _ = lax.scan(
            body, (x, dict(kv)),
            (params["decoder"], jnp.arange(c.num_layers)))
        if head == "none":
            return None, new_kv
        if head == "last":
            x = self._norm(params["ln_f"],
                           attn_lib.last_real_position(x, win.valid))
            return self.logits(params, x)[:, 0, :], new_kv
        x = self._norm(params["ln_f"], x)
        return self.logits(params, x), new_kv

    def prefill_cache(self, params, cache, token_ids,
                      chunk: Optional[int] = None):
        """Prompt ingestion into an empty cache, optionally CHUNKED.

        ``chunk=None``: one ``decode_block`` forward (s x s attention —
        the fast path while the whole prompt's attention fits).
        ``chunk=W``: the prompt streams through ``decode_window`` W
        tokens at a time, each window attending the cached prefix plus
        itself — live attention memory is bounded by W x max_len
        instead of s x s, the long-context serving shape (a 32k prompt
        prefills at the memory of its window).  Exact parity with the
        one-block path (tests/test_gpt.py::test_chunked_prefill_*) —
        except under ``kv_cache_dtype="int8"``, where each window reads
        its own K/V back through the quantized cache (one rounding step
        the single-block path's in-block attention doesn't take), so
        chunked-prefill logits agree to quantization tolerance rather
        than exactly.

        Returns (last-position logits [b, vocab] f32, advanced cache).
        Requires an EMPTY cache (``pos == 0``, the decode_block
        precondition) — validated when ``pos`` is concrete; under jit
        the caller owns it.
        """
        b, s = token_ids.shape
        if s == 0:
            raise ValueError("prefill_cache needs a non-empty prompt")
        if chunk is not None and chunk < 1:
            raise ValueError(f"chunk must be >= 1; got {chunk}")
        if not isinstance(cache["pos"], jax.core.Tracer) \
                and int(cache["pos"]) != 0:
            raise ValueError(
                f"prefill_cache needs an empty cache (pos == 0); got pos="
                f"{int(cache['pos'])} — append to a live cache with "
                "decode_window instead")
        if chunk is None or chunk >= s:
            return self.decode_block(params, cache, token_ids)
        logits = None
        for lo in range(0, s, chunk):
            window = token_ids[:, lo:lo + chunk]
            last = lo + chunk >= s
            logits, cache = self.decode_window(
                params, cache, window, head="last" if last else "none")
        return logits, cache

    def generate(self, params, prompt_ids, max_new_tokens: int,
                 temperature: float = 0.0, rng=None,
                 max_len: Optional[int] = None,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 eos_id: Optional[int] = None,
                 pad_id: Optional[int] = None,
                 prompt_valid=None,
                 prefill_chunk: Optional[int] = None) -> jnp.ndarray:
        """Autoregressive sampling with the KV cache.

        ``prefill_chunk``: stream the prompt into the cache W tokens at
        a time (``prefill_cache``) instead of one block — bounds prefill
        attention memory for very long prompts; not supported together
        with ``prompt_valid``.

        prompt_ids: [b, p] int32.  temperature 0 = greedy; ``top_k`` /
        ``top_p`` filter the sampled distribution (ops.decoding).  Returns
        [b, p + max_new_tokens].  Without ``eos_id`` the whole loop is one
        ``lax.scan`` (prompt positions are teacher-forced), so generation
        jits with no per-token recompilation.

        ``eos_id``: rows that sample EOS (after the prompt) are finished —
        they emit ``pad_id`` (default: ``eos_id``) from then on, and the
        loop becomes a ``lax.while_loop`` that EXITS EARLY once every row
        has finished: a batch whose longest answer is 10 tokens pays for
        10 decode steps, not ``max_new_tokens``.  Output shape stays
        static ([b, p + max_new_tokens], padded).

        ``prompt_valid`` [b, p]: ragged prompts, LEFT-padded so every row's
        last prompt token sits at column p-1 (1 = real token).  Pad slots
        are masked out of attention and each row's position indices are
        shifted by its pad length, so learned and RoPE models both see the
        row's true positions — batch serving for unequal prompt lengths.
        The left-padding contract is only VALIDATED on concrete masks:
        under jit the check cannot run, and a right-padded mask silently
        yields wrong positions/attention — callers tracing this must
        guarantee left-padding themselves.
        """
        from ..ops import decoding as dec
        c = self.config
        if prefill_chunk is not None and prompt_valid is not None:
            # validated up front so the combination fails the same way
            # regardless of prompt length / max_new_tokens
            raise ValueError("prefill_chunk does not compose with "
                             "prompt_valid (ragged prompts prefill as "
                             "one block)")
        pad = dec.resolve_pad(eos_id, pad_id)
        b, plen = prompt_ids.shape
        total = plen + max_new_tokens
        max_len = max_len or max(total, 1)
        self._check_gen_lengths(plen, max_new_tokens, max_len)
        if rng is None:
            rng = jax.random.PRNGKey(0)
        cache = self.init_cache(b, max_len)
        tokens = (jnp.zeros((b, total), jnp.int32) if eos_id is None
                  else jnp.full((b, total), pad, jnp.int32))
        tokens = tokens.at[:, :plen].set(prompt_ids)

        if prompt_valid is not None:
            pad_len, kv_valid = dec.ragged_prompt_masks(
                prompt_valid, (b, plen), max_len)
        else:
            pad_len = kv_valid = None

        def advance(tokens, cache, rng, finished, i):
            tok = lax.dynamic_slice_in_dim(tokens, i, 1, axis=1)[:, 0]
            if prompt_valid is not None:
                logits, cache = self.decode_step(
                    params, cache, tok, kv_valid=kv_valid,
                    positions=jnp.maximum(i - pad_len, 0))
            else:
                logits, cache = self.decode_step(params, cache, tok)
            rng, sub = jax.random.split(rng)
            nxt = dec.sample_logits(sub, logits, temperature,
                                    top_k=top_k, top_p=top_p)
            # Teacher-force while still inside the prompt.
            inside = i + 1 < plen
            target = lax.dynamic_slice_in_dim(
                tokens, jnp.minimum(i + 1, total - 1), 1, axis=1)[:, 0]
            nxt = jnp.where(inside, target, nxt)  # sample_logits returns int32
            if eos_id is not None:
                nxt, finished = dec.finish_step(nxt, finished, eos_id, pad,
                                                eligible=~inside)
            tokens = lax.dynamic_update_slice_in_dim(
                tokens, nxt[:, None], i + 1, axis=1)
            return tokens, cache, rng, finished

        no_finish = jnp.zeros((b,), bool)
        finished = no_finish
        start = 0
        if plen > 1 and max_new_tokens > 0:
            # Batched prefill: the whole prompt in ONE forward (decode_
            # block) instead of plen sequential teacher-forced decode
            # steps, then sample the first new token from its logits.
            # Greedy output is identical to the sequential path (parity-
            # tested); sampling paths draw from the same distributions
            # but consume fewer rng splits.
            if prompt_valid is not None:
                logits, cache = self.decode_block(
                    params, cache, prompt_ids,
                    kv_valid=kv_valid[:, :plen],
                    positions=jnp.maximum(
                        jnp.arange(plen)[None, :] - pad_len[:, None], 0))
            else:
                logits, cache = self.prefill_cache(params, cache,
                                                   prompt_ids,
                                                   chunk=prefill_chunk)
            rng, sub = jax.random.split(rng)
            nxt = dec.sample_logits(sub, logits, temperature,
                                    top_k=top_k, top_p=top_p)
            if eos_id is not None:
                nxt, finished = dec.finish_step(nxt, no_finish, eos_id,
                                                pad)
            tokens = lax.dynamic_update_slice_in_dim(
                tokens, nxt[:, None], plen, axis=1)
            start = plen

        if eos_id is None:
            def step(carry, i):
                tokens, cache, rng = carry
                tokens, cache, rng, _ = advance(tokens, cache, rng,
                                                no_finish, i)
                return (tokens, cache, rng), None

            (tokens, _, _), _ = lax.scan(step, (tokens, cache, rng),
                                         jnp.arange(start, total - 1))
            return tokens

        (tokens, _, _, _), _ = dec.decode_loop(
            lambda carry, i: advance(*carry, i),
            (tokens, cache, rng, finished), total - 1, start=start)
        return tokens

    def _check_gen_lengths(self, plen: int, max_new_tokens: int,
                           max_len: int) -> None:
        """Shared generate/beam_search length rules."""
        c = self.config
        if max_len > c.max_position and c.position_embedding == "learned":
            # only the learned table runs out of rows; RoPE extrapolates
            raise ValueError(f"generation length {max_len} exceeds "
                             f"max_position {c.max_position}")
        if plen + max_new_tokens > max_len:
            # dynamic_update_slice would silently clamp cache writes at
            # max_len and corrupt every later token — refuse instead.
            raise ValueError(f"prompt ({plen}) + max_new_tokens "
                             f"({max_new_tokens}) = {plen + max_new_tokens} "
                             f"exceeds max_len {max_len}")

    def beam_search(self, params, prompt_ids, max_new_tokens: int,
                    beam_size: int = 4, eos_id: Optional[int] = None,
                    length_penalty: float = 0.6,
                    max_len: Optional[int] = None,
                    prompt_valid=None,
                    prefill_chunk: Optional[int] = None) -> jnp.ndarray:
        """Jittable beam search over the KV cache.

        Two phases, each one ``lax.scan``: the prompt prefills the cache at
        batch ``b`` (no beam-fold waste), then the cache rows are repeated
        ``beam_size``-fold and every expansion REORDERS them by gather (the
        standard KV-cache beam trick).  Shared bookkeeping lives in
        ``ops.decoding``.  Returns the best row per batch element,
        [b, plen + max_new_tokens].

        ``prefill_chunk``: stream the prompt prefill W tokens at a time
        (``prefill_cache``) — bounds long-prompt prefill memory; not
        supported with ``prompt_valid``, and under
        ``kv_cache_dtype="int8"`` it matches the one-block prefill to
        quantization tolerance only (see ``prefill_cache``).

        ``prompt_valid``: LEFT-padded ragged prompts, same contract as
        ``generate`` — pad slots masked from attention, per-row position
        shift through prefill and expansion.  As there, the left-padding
        check only runs on concrete masks; under jit the caller owns it.
        """
        from ..ops import decoding as dec

        c = self.config
        if prefill_chunk is not None and prompt_valid is not None:
            # same up-front refusal (and precedence) as generate: the
            # combination fails identically regardless of prompt length
            raise ValueError("prefill_chunk does not compose with "
                             "prompt_valid (ragged prompts prefill as "
                             "one block)")
        b, plen = prompt_ids.shape
        k = beam_size
        total = plen + max_new_tokens
        max_len = max_len or max(total, 1)
        self._check_gen_lengths(plen, max_new_tokens, max_len)

        if prompt_valid is not None:
            pad_len, kv_valid = dec.ragged_prompt_masks(
                prompt_valid, (b, plen), max_len)
            # loop-invariant beam folds, hoisted out of the expansion loop
            # (lax.while_loop gives no hoisting guarantee)
            kv_valid_folded = jnp.repeat(kv_valid, k, axis=0)
            pad_len_folded = jnp.repeat(pad_len, k, axis=0)
        else:
            pad_len = kv_valid = None

        def step_kwargs(i):
            """decode_step kwargs for position i with the cache rows
            beam-folded k-fold (the only decode_step caller left since
            the prefill became one decode_block forward)."""
            if prompt_valid is None:
                return {}
            return dict(kv_valid=kv_valid_folded,
                        positions=jnp.maximum(i - pad_len_folded, 0))

        # phase 1 — prefill positions 0..plen-2 at batch b, as ONE
        # decode_block forward (phase 2's first expansion reads the token
        # at plen-1, so the block stops one short); prefill_chunk streams
        # it W tokens at a time instead (long-prompt memory bound)
        cache = self.init_cache(b, max_len)
        if plen > 1:
            if prompt_valid is not None:
                _, cache = self.decode_block(
                    params, cache, prompt_ids[:, :-1],
                    kv_valid=kv_valid[:, :plen - 1],
                    positions=jnp.maximum(
                        jnp.arange(plen - 1)[None, :]
                        - pad_len[:, None], 0))
            else:
                _, cache = self.prefill_cache(params, cache,
                                              prompt_ids[:, :-1],
                                              chunk=prefill_chunk)
        # fold beams into the batch dim: row r of batch i -> i*k + r
        # (tree-mapped over every cache entry but pos, so int8 caches'
        # scale arrays fold with their values)
        cache = dict(jax.tree.map(lambda a: jnp.repeat(a, k, axis=1),
                                  self._cache_kv(cache)),
                     pos=cache["pos"])

        tokens = jnp.zeros((b, k, total), jnp.int32)
        tokens = tokens.at[:, :, :plen].set(prompt_ids[:, None, :])
        scores = dec.init_beam_scores(b, k)
        finished = jnp.zeros((b, k), bool)
        batch_base = jnp.arange(b)[:, None] * k            # [b, 1]

        def advance(carry, i):
            tokens, cache, scores, finished = carry
            tok = lax.dynamic_slice_in_dim(
                tokens.reshape(b * k, total), i, 1, axis=1)[:, 0]
            logits, cache = self.decode_step(params, cache, tok,
                                             **step_kwargs(i))
            logp = jax.nn.log_softmax(logits, -1).reshape(b, k, -1)
            logp = dec.freeze_finished(logp, finished, eos_id)
            scores, beam, nxt = dec.expand_beams(scores, logp)
            tokens = jnp.take_along_axis(tokens, beam[:, :, None], axis=1)
            tokens = lax.dynamic_update_slice_in_dim(
                tokens, nxt[:, :, None], i + 1, axis=2)
            finished = jnp.take_along_axis(finished, beam, axis=1)
            if eos_id is not None:
                finished = finished | (nxt == eos_id)
            flat = (batch_base + beam).reshape(-1)
            cache = dict(jax.tree.map(lambda a: jnp.take(a, flat, axis=1),
                                      self._cache_kv(cache)),
                         pos=cache["pos"])
            return (tokens, cache, scores, finished)

        # phase 2 — beam expansion from position plen-1 onward
        carry0 = (tokens, cache, scores, finished)
        if eos_id is None:
            (tokens, _, scores, finished), _ = lax.scan(
                lambda carry, i: (advance(carry, i), None), carry0,
                jnp.arange(plen - 1, total - 1))
        else:
            # early exit once every beam of every row finished; unwritten
            # tail positions get EOS — exactly what the full run writes
            # (frozen beams only ever extend with EOS, dec.freeze_finished)
            (tokens, _, scores, finished), steps = dec.decode_loop(
                lambda carry, j: advance(carry, plen - 1 + j),
                carry0, max_new_tokens)
            pos = jnp.arange(total)[None, None, :]
            tokens = jnp.where(pos > plen - 1 + steps, eos_id, tokens)
        best = dec.rank_beams(scores, tokens[:, :, plen:], eos_id,
                              max_new_tokens, length_penalty)
        return jnp.take_along_axis(tokens, best[:, None, None],
                                   axis=1)[:, 0, :]

    # -- sharding ---------------------------------------------------------
    def partition_rules(self, fsdp: bool = False,
                        shard_kv: Optional[bool] = None) -> PartitionRules:
        """Megatron-style TP specs; tied head sharding comes free with the
        word embedding (vocab on ``tensor``).

        GQA/MQA: the kv head axis can be smaller than the TP degree, so
        by default key/value projections follow the standard MQA recipe —
        queries shard over heads, keys/values replicate across the tensor
        axis.  Pass ``shard_kv=True`` when the tensor degree divides
        kv_heads (e.g. GQA 4 kv heads on tensor=2) to shard them too;
        the table is mesh-agnostic so it cannot decide this itself.
        """
        f = "fsdp" if fsdp else None
        # With pipeline_stages the scanned leading LAYER dim shards over the
        # pipe axis — each stage's devices hold exactly their L/S blocks;
        # apply()'s [L,...]->[S,L/S,...] reshape is then a local view.
        lead = (self.config.pipe_axis if self.config.pipeline_stages > 1
                else None)
        kv_on_tensor = (shard_kv if shard_kv is not None
                        else self.config.kv_heads == self.config.num_heads)
        kv_spec = (P(lead, f, "tensor", None) if kv_on_tensor
                   else P(lead, f, None, None))
        kv_bias = (P(lead, "tensor", None) if kv_on_tensor
                   else P(lead, None, None))
        return PartitionRules([
            (r"embeddings/word$", P("tensor", f)),
            (r"lm_head$", P("tensor", f)),      # untied head: same split
            (r"embeddings/position$", P(None, None)),
            (r"decoder/attention/query/kernel", P(lead, f, "tensor", None)),
            (r"decoder/attention/query/bias", P(lead, "tensor", None)),
            (r"decoder/attention/(key|value)/kernel", kv_spec),
            (r"decoder/attention/(key|value)/bias", kv_bias),
            (r"decoder/attention/out/kernel", P(lead, "tensor", None, f)),
            (r"decoder/ffn/w_(in|gate)/kernel", P(lead, f, "tensor")),
            (r"decoder/ffn/w_(in|gate)/bias", P(lead, "tensor")),
            (r"decoder/ffn/w_out/kernel", P(lead, "tensor", f)),
            (r"decoder/ffn/w_out/bias", P(lead, None)),
            (r"decoder/attention/out/bias", P(lead, None)),
            (r"decoder/ln_[12]/(gamma|beta)", P(lead, None)),
            # MoE rows derive from the canonical ops.moe table (its patterns
            # are suffix-matching), with the scanned leading layer dim
            # prepended to each spec — one source of truth.  (MoE cannot
            # combine with pipeline — rejected at config — so lead=None.)
        ] + [(pat, P(None, *spec)) for pat, spec in moe_partition_rules()])


# --------------------------------------------------- dtlint graph tier

from ..analysis import graph as _graph_lib  # noqa: E402  (registration)


@_graph_lib.trace_entry("gpt", hbm_budget=1 << 20)
def _graph_entries():
    """Registry-scale decode/prefill paths for the DT4xx pack: the
    chunked-prefill window (``decode_window``) and the single-token
    decode step traced abstractly on a tiny config.  DT401 watches for
    weights silently closed over instead of passed as ``params``;
    DT402 for a decode path whose matmuls get upcast to f32."""
    import jax

    model = gpt_tiny(vocab_size=64, hidden_size=32, num_heads=2,
                     intermediate_size=64, max_position=32,
                     dropout_rate=0.0)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(tuple(x.shape), x.dtype),
        jax.eval_shape(lambda: model.init_cache(1, 32)))
    window = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    token = jax.ShapeDtypeStruct((1,), jnp.int32)
    return [
        _graph_lib.Target(
            "prefill_window",
            lambda p, c, w: model.decode_window(p, c, w, head="last"),
            (params, cache, window)),
        _graph_lib.Target(
            "decode_step",
            lambda p, c, t: model.decode_step(p, c, t),
            (params, cache, token)),
    ]
