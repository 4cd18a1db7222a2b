"""BERT encoder family (MLM pre-train / fine-tune) — the flagship model.

The reference has no transformer (its model is a 3-layer MLP, reference
example.py:149-155); BERT-base MLM is the driver's largest baseline config
(BASELINE.md #5: pjit data+model parallel on v5p-128).  TPU-first design:

  * **Scanned layer stack**: the L encoder layers are ONE set of parameter
    arrays with a leading ``[L, ...]`` stacking dim, applied with
    ``lax.scan`` — compile time is O(1) in depth and XLA pipelines the
    layers.  Optional ``remat`` wraps the scan body in ``jax.checkpoint``
    to trade recompute for HBM (long-context requirement).
  * **4D mesh-ready sharding**: ``partition_rules()`` ships megatron-style
    specs — attention heads and FFN hidden on ``tensor`` (column-parallel
    in, row-parallel out), optional ``fsdp`` on the complementary dim,
    embeddings sharded on vocab — one rule table from 1 chip to a pod.
  * **Sequence parallelism**: ``apply`` takes the activations in
    ``[batch, seq, hidden]``; with ``seq_axis`` set, attention runs as ring
    attention over the ``seq`` mesh axis (parallel.ring) so sequences can
    exceed one chip's HBM.
  * bf16 activations / f32 master params via the shared layer conventions.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import attention as attn_lib
from ..ops import initializers as init_lib
from ..ops import losses as loss_lib
from ..parallel.sharding import PartitionRules

__all__ = ["BertConfig", "Bert", "bert_base", "bert_tiny"]


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 512
    type_vocab_size: int = 2
    dropout_rate: float = 0.1
    layer_norm_eps: float = 1e-12
    dtype: Any = jnp.float32          # activation/compute dtype
    remat: bool = False               # checkpoint each encoder layer
    # with remat=True: "full" (save nothing), "dots" (save matmul
    # outputs, recompute elementwise only), "dots_no_batch" — see
    # GPTConfig.remat_policy
    remat_policy: str = "full"
    seq_axis: Optional[str] = None    # mesh axis for ring attention (SP)
    # True / False / "auto": auto dispatches the fused Pallas kernel on TPU
    # at seq >= the measured crossover (ops.attention.resolve_use_flash).
    # Hardware-validated + measured 2026-07-31 (docs/PERF.md): ties XLA at
    # seq <= 1024, wins 1.3-1.7x at 2048, ~3x at 4096 — "auto" is safe.
    use_flash: Any = "auto"
    # True / False / "auto": LayerNorms via the fused Pallas kernel
    # (ops.pallas.fused_layernorm, one HBM pass); auto = TPU only.
    # Default False until the end-to-end win is measured on hardware.
    fused_layernorm: Any = False
    # >0: the original BERT ``max_predictions_per_seq`` design — gather at
    # most N masked positions per sequence BEFORE the MLM head, so the
    # transform/LN/vocab projection (2*d*V FLOPs/token, V=30522) runs on
    # ~15% of tokens instead of all of them and the [b, s, V] logits are
    # never built.  Exact vs the full path while every row has <= N masked
    # positions; overflow drops extra positions from the loss (reported in
    # the ``mlm_overflow`` metric).  0 = project every position.
    mlm_predictions_per_seq: int = 0
    # FFN / MLM-transform activation: "gelu_approx" (tanh, the GPT-2/zoo
    # default) or "gelu" (exact erf — what HF BERT checkpoints were
    # trained with; models/convert.py sets this)
    hidden_act: str = "gelu_approx"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def act_fn(self):
        from ..ops.attention import resolve_activation
        return resolve_activation(self.hidden_act)


def bert_base(**kw) -> "Bert":
    return Bert(BertConfig(**kw))


def bert_tiny(**kw) -> "Bert":
    kw.setdefault("hidden_size", 128)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 2)
    kw.setdefault("intermediate_size", 512)
    kw.setdefault("vocab_size", 1000)
    kw.setdefault("max_position", 128)
    return Bert(BertConfig(**kw))


def _layer_norm(params, x, eps, fused=False):
    if fused:
        from ..ops.pallas import fused_layernorm
        return fused_layernorm(x, params["gamma"], params["beta"], eps=eps)
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * params["gamma"] + params["beta"]).astype(x.dtype)


def _resolve_fused_ln(flag) -> bool:
    from ..ops.pallas import resolve_fused_ln
    return resolve_fused_ln(flag)


def mlm_gather_flops_correction(config, seq: int) -> float:
    """Training FLOPs/token the gathered MLM head SKIPS vs projecting
    every position: transform d^2 + vocab projection d*V, 6x each (fwd
    2x + bwd 4x), on the non-gathered fraction.  One accounting shared
    by bench.py and scripts/mfu_ablation.py so their MFU columns stay
    comparable.  0 when gathering is off."""
    n = config.mlm_predictions_per_seq
    if not n:
        return 0.0
    d, v = config.hidden_size, config.vocab_size
    return (1.0 - n / seq) * 6.0 * (d * d + d * v)


def _dropout(x, rate, rng, train):
    if not train or rate == 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


class Bert:
    """Functional BERT: ``init(key) -> params``, ``apply(params, batch, ...)``."""

    def __init__(self, config: BertConfig, mesh=None):
        self.config = config
        # The mesh the params/batch are sharded over.  With ``seq_axis``
        # set, attention runs as a partial-manual ring over that axis
        # inside the otherwise-auto pjit program; without it, on any mesh
        # of more than one device, the flash kernel runs under shard_map
        # (XLA cannot partition a Mosaic kernel by itself).
        self.mesh = mesh

    # -- init -------------------------------------------------------------
    def init(self, key) -> Dict[str, Any]:
        c = self.config
        trunc = init_lib.truncated_normal(0.02)
        k_emb, k_layers, k_head = jax.random.split(key, 3)
        ke = jax.random.split(k_emb, 3)

        def ln():
            return {"gamma": jnp.ones((c.hidden_size,), jnp.float32),
                    "beta": jnp.zeros((c.hidden_size,), jnp.float32)}

        params: Dict[str, Any] = {
            "embeddings": {
                "word": trunc(ke[0], (c.vocab_size, c.hidden_size)),
                "position": trunc(ke[1], (c.max_position, c.hidden_size)),
                "type": trunc(ke[2], (c.type_vocab_size, c.hidden_size)),
                "ln": ln(),
            },
        }

        h, hd, d, i = c.num_heads, c.head_dim, c.hidden_size, c.intermediate_size

        def one_layer(k):
            ks = jax.random.split(k, 6)
            return {
                "attention": {
                    "query": {"kernel": trunc(ks[0], (d, h, hd)),
                              "bias": jnp.zeros((h, hd), jnp.float32)},
                    "key": {"kernel": trunc(ks[1], (d, h, hd)),
                            "bias": jnp.zeros((h, hd), jnp.float32)},
                    "value": {"kernel": trunc(ks[2], (d, h, hd)),
                              "bias": jnp.zeros((h, hd), jnp.float32)},
                    "out": {"kernel": trunc(ks[3], (h, hd, d)),
                            "bias": jnp.zeros((d,), jnp.float32)},
                    "ln": ln(),
                },
                "ffn": {
                    "w_in": {"kernel": trunc(ks[4], (d, i)),
                             "bias": jnp.zeros((i,), jnp.float32)},
                    "w_out": {"kernel": trunc(ks[5], (i, d)),
                              "bias": jnp.zeros((d,), jnp.float32)},
                    "ln": ln(),
                },
            }

        # Stacked layers: vmap init over per-layer keys -> leading [L, ...].
        params["encoder"] = jax.vmap(one_layer)(
            jax.random.split(k_layers, c.num_layers))

        kh = jax.random.split(k_head, 2)
        params["mlm"] = {
            "transform": {"kernel": trunc(kh[0], (d, d)),
                          "bias": jnp.zeros((d,), jnp.float32)},
            "ln": ln(),
            "output_bias": jnp.zeros((c.vocab_size,), jnp.float32),
        }
        params["pooler"] = {"kernel": trunc(kh[1], (d, d)),
                            "bias": jnp.zeros((d,), jnp.float32)}
        return params

    # -- encoder ----------------------------------------------------------
    def _attention(self, p, x, mask, valid, rng, train):
        c = self.config

        if c.seq_axis is not None and self.mesh is not None:
            # the flash crossover applies to the kernel's PER-CALL seq:
            # inside the ring each call sees one shard, so gate on the
            # local shard length, not the global sequence
            local = x.shape[1] // self.mesh.shape[c.seq_axis]
            if attn_lib.resolve_use_flash(c.use_flash, local):
                # SP x flash: the ring schedule with the fused kernel per
                # block pair (parallel.ring_flash) — both long-context
                # levers stacked
                from ..parallel.ring_flash import ring_flash_attention_sharded
                attention_fn = lambda q, k, v, mask=None: \
                    ring_flash_attention_sharded(
                        q, k, v, self.mesh, seq_axis=c.seq_axis,
                        kv_valid=valid)
            else:
                from ..parallel.ring import ring_attention_sharded
                attention_fn = lambda q, k, v, mask=None: \
                    ring_attention_sharded(
                        q, k, v, self.mesh, seq_axis=c.seq_axis,
                        kv_valid=valid)
        elif c.seq_axis is not None:
            # traced inside a caller's shard_map: x is the local shard
            if attn_lib.resolve_use_flash(c.use_flash, x.shape[1]):
                from ..parallel.ring_flash import ring_flash_attention
                attention_fn = lambda q, k, v, mask=None: \
                    ring_flash_attention(q, k, v, axis_name=c.seq_axis,
                                         kv_valid=valid)
            else:
                from ..parallel.ring import ring_attention
                attention_fn = lambda q, k, v, mask=None: ring_attention(
                    q, k, v, axis_name=c.seq_axis, kv_valid=valid)
        elif attn_lib.resolve_use_flash(c.use_flash, x.shape[1]):
            from ..ops.pallas import flash_attention
            attention_fn = lambda q, k, v, mask=None: flash_attention(
                q, k, v, kv_valid=valid, mesh=self.mesh)
        else:
            attention_fn = attn_lib.dot_product_attention
        return attn_lib.attention_core(
            p, x, mask=mask, dropout_rate=c.dropout_rate, rng=rng,
            train=train, attention_fn=attention_fn)

    def _encoder_layer(self, p, x, mask, valid, rng, train):
        c = self.config
        fused = _resolve_fused_ln(c.fused_layernorm)
        r1, r2, r3 = jax.random.split(rng, 3)
        attn_out = self._attention(p["attention"], x, mask, valid, r1, train)
        x = _layer_norm(p["attention"]["ln"],
                        x + _dropout(attn_out, c.dropout_rate, r2, train),
                        c.layer_norm_eps, fused=fused)
        ffn_out = attn_lib.ffn_core(p["ffn"], x, activation=c.act_fn)
        return _layer_norm(p["ffn"]["ln"],
                           x + _dropout(ffn_out, c.dropout_rate, r3, train),
                           c.layer_norm_eps, fused=fused)

    def apply(self, params, input_ids, *, token_type_ids=None,
              attention_mask=None, train: bool = False, rng=None):
        """-> sequence output [batch, seq, hidden] in config.dtype."""
        c = self.config
        if rng is None:
            if train:
                raise ValueError(
                    "Bert.apply(train=True) requires an rng key (dropout); "
                    "use make_custom_train_step or pass rng explicitly")
            rng = jax.random.PRNGKey(0)   # eval: dropout is a no-op
        b, s = input_ids.shape
        emb = params["embeddings"]
        x = jnp.take(emb["word"], input_ids, axis=0)
        x = x + emb["position"][None, :s, :]
        if token_type_ids is not None:
            x = x + jnp.take(emb["type"], token_type_ids, axis=0)
        else:
            x = x + emb["type"][0][None, None, :]
        x = _layer_norm(emb["ln"], x, c.layer_norm_eps,
                        fused=_resolve_fused_ln(c.fused_layernorm))
        r_emb, r_layers = jax.random.split(rng)
        x = _dropout(x, c.dropout_rate, r_emb, train).astype(c.dtype)

        mask = (attn_lib.padding_mask(attention_mask)
                if attention_mask is not None else None)
        valid = attention_mask  # raw [b, s] form for the ring path

        layer_fn = self._encoder_layer
        if c.remat:
            from .gpt import _remat_policy
            layer_fn = jax.checkpoint(layer_fn, static_argnums=(5,),
                                      policy=_remat_policy(c.remat_policy))

        def body(carry, inputs):
            layer_params, layer_key = inputs
            return layer_fn(layer_params, carry, mask, valid, layer_key,
                            train), None

        layer_keys = jax.random.split(r_layers, c.num_layers)
        x, _ = jax.lax.scan(body, x, (params["encoder"], layer_keys))
        return x

    # -- heads ------------------------------------------------------------
    def mlm_logits(self, params, sequence_output):
        """Tied-embedding MLM head -> [batch, seq, vocab] (f32 logits)."""
        c = self.config
        p = params["mlm"]
        dtype = sequence_output.dtype
        h = c.act_fn(sequence_output @ p["transform"]["kernel"].astype(dtype)
                     + p["transform"]["bias"].astype(dtype))
        h = _layer_norm(p["ln"], h, c.layer_norm_eps,
                        fused=_resolve_fused_ln(c.fused_layernorm))
        logits = h @ params["embeddings"]["word"].T.astype(dtype)
        return logits.astype(jnp.float32) + p["output_bias"]

    def pooled(self, params, sequence_output):
        """[CLS] pooler -> [batch, hidden] (classification fine-tune)."""
        p = params["pooler"]
        first = sequence_output[:, 0, :]
        return jnp.tanh(first @ p["kernel"].astype(first.dtype)
                        + p["bias"].astype(first.dtype))

    # -- losses -----------------------------------------------------------
    def mlm_loss_fn(self):
        """Contract for ``train.make_custom_train_step``: batch dict with
        input_ids / labels / mlm mask (-100 or mask array) / attention_mask."""

        def loss_fn(params, model_state, batch, rng, train):
            seq = self.apply(params, batch["input_ids"],
                             token_type_ids=batch.get("token_type_ids"),
                             attention_mask=batch.get("attention_mask"),
                             train=train, rng=rng)
            mask = batch["mlm_mask"]
            labels = batch["labels"]
            n_pred = self.config.mlm_predictions_per_seq
            extra = {}
            if n_pred:
                # top_k on the 0/1 mask sorts the masked positions first;
                # the gathered mask values double as the loss weights, so
                # rows with fewer than n_pred masked positions pad with
                # weight 0 and rows with more drop the overflow.
                w, idx = jax.lax.top_k(mask.astype(jnp.float32), n_pred)
                seq = jnp.take_along_axis(seq, idx[..., None], axis=1)
                labels = jnp.take_along_axis(labels, idx, axis=1)
                full = jnp.sum(mask.astype(jnp.float32))
                mask = w
                extra["mlm_overflow"] = full - jnp.sum(w)
            logits = self.mlm_logits(params, seq)
            loss = loss_lib.softmax_cross_entropy_with_integer_labels(
                logits, labels, where=mask)
            acc_hits = (jnp.argmax(logits, -1) == labels).astype(
                jnp.float32) * mask
            accuracy = jnp.sum(acc_hits) / jnp.maximum(jnp.sum(mask), 1.0)
            # loss_weight: the masked-mean normalizer, consumed by
            # train.step gradient accumulation for exact full-batch grads.
            return loss, ({"mlm_accuracy": accuracy,
                           "loss_weight": jnp.sum(mask).astype(jnp.float32),
                           **extra},
                          model_state)

        return loss_fn

    # -- sharding ---------------------------------------------------------
    def partition_rules(self, fsdp: bool = False) -> PartitionRules:
        """Megatron-style TP specs (+ optional fsdp on the complementary
        dim).  Paths include the scanned leading layer dim, which is never
        sharded (each chip holds all L slices of its shard)."""
        f = "fsdp" if fsdp else None
        return PartitionRules([
            # embeddings: vocab on tensor (row-parallel gather + tied head)
            (r"embeddings/word$", P("tensor", f)),
            (r"embeddings/(position|type)$", P(None, None)),
            # attention projections [L, d, h, hd]: heads on tensor
            (r"encoder/attention/(query|key|value)/kernel", P(None, f, "tensor", None)),
            (r"encoder/attention/(query|key|value)/bias", P(None, "tensor", None)),
            # out projection [L, h, hd, d]: heads on tensor (row-parallel)
            (r"encoder/attention/out/kernel", P(None, "tensor", None, f)),
            # FFN [L, d, i] / [L, i, d]: hidden i on tensor
            (r"encoder/ffn/w_in/kernel", P(None, f, "tensor")),
            (r"encoder/ffn/w_in/bias", P(None, "tensor")),
            (r"encoder/ffn/w_out/kernel", P(None, "tensor", f)),
            (r"mlm/transform/kernel", P(f, "tensor")),
            (r"pooler/kernel", P(f, "tensor")),
            (r"mlm/output_bias", P("tensor")),
        ])
