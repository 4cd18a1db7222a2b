"""Single-run MFU ablation for the LM bench rows, on real TPU.

Round-3 verdict: BERT 0.112 / Llama 0.140 / GPT 0.169 MFU got remat and
batch tuning only — the repo's own fused kernels were never in a bench
config, and nobody profiled where the step time actually goes.  This
script measures every candidate lever in ONE process on one chip so the
arms are comparable (docs/PERF.md methodology: donated-state step chain
closed by a value fetch; compare only within one run):

gpt arms:   base(remat,b48,s256) / fused_adam / fused_ln / both /
            vocab_pad(50304: lm head + embed padded to a 128-multiple
            lane width) / batch96 / batch192 / seq512_b24
bert arms:  base(s128,b64) / seq256 / fused_adam / fused_ln / batch128

Usage: python scripts/mfu_ablation.py [gpt|bert] [arm ...]
CPU wiring check (env JAX_PLATFORMS=cpu DTTPU_ABLATION_SMOKE=1): same command.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# DTTPU_ABLATION_SMOKE=1: shrink every arm to a 2-layer toy so the script's
# wiring can be validated on CPU in seconds; numbers are meaningless there.
# ("0"/"false"/empty = off — same parse as decode_ladder.py).
SMOKE = os.environ.get("DTTPU_ABLATION_SMOKE", "").lower() \
    not in ("", "0", "false")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

PEAK = {"v5e": 197e12, "v5 lite": 197e12, "v5p": 459e12,
        "v6e": 918e12, "v4": 275e12}


def peak_flops():
    """Peak bf16 FLOP/s of this device; None off the accelerator.  A TPU
    whose kind is not in the table is an error, not a missing MFU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    kind = dev.device_kind.lower()
    for k, v in PEAK.items():
        if k in kind:
            return v
    raise RuntimeError(f"no peak FLOP/s for TPU device_kind "
                       f"{dev.device_kind!r}: add it to PEAK")


def time_step(step, state, batch, warmup=3, steps=10):
    for _ in range(warmup):
        state, metrics = step(state, batch)
    float(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch)
    loss = float(metrics["loss"])  # value fetch closes the window
    return (time.perf_counter() - t0) / steps, loss


def run_gpt(arms):
    from distributed_tensorflow_tpu import optim, parallel, train
    from distributed_tensorflow_tpu.models.gpt import GPT, GPTConfig

    mesh = parallel.data_parallel_mesh()
    bsh = NamedSharding(mesh, P("data"))
    rng = np.random.default_rng(0)
    peak = peak_flops()

    MATRIX = {
        "base":       dict(),
        "fused_adam": dict(fused_adam=True),
        "fused_ln":   dict(fused_layernorm=True),
        "both":       dict(fused_adam=True, fused_layernorm=True),
        "vocab_pad":  dict(vocab=50304),
        "batch96":    dict(batch=96),
        "batch192":   dict(batch=192),
        "seq512_b24": dict(seq=512, batch=24),
        # chunked LM loss: the [tokens, vocab] logits never materialise,
        # so the batch ladder can climb past the logits memory wall
        "loss_chunk":      dict(loss_chunk=512),
        "loss_chunk_b96":  dict(loss_chunk=512, batch=96),
        "loss_chunk_b192": dict(loss_chunk=512, batch=192),
        "loss_chunk_b384": dict(loss_chunk=512, batch=384),
        # remat policy: save matmul outputs instead of nothing — less
        # backward recompute, more memory (OOM rungs are data)
        "remat_dots":          dict(remat_policy="dots"),
        "remat_dots_chunk":    dict(remat_policy="dots", loss_chunk=512),
        "remat_dots_chunk_b96": dict(remat_policy="dots", loss_chunk=512,
                                     batch=96),
    }
    for arm in arms or MATRIX:
        a = MATRIX[arm]
        seq, batch = a.get("seq", 256), a.get("batch", 48)
        vocab = a.get("vocab", 50257)
        if SMOKE:
            # smoke batch stays tiny but must divide over the data mesh
            seq, batch = min(seq, 64), max(min(batch, 4),
                                           len(jax.devices()))
        config = GPTConfig(vocab_size=vocab, hidden_size=64 if SMOKE else 768,
                           num_layers=2 if SMOKE else 12,
                           num_heads=2 if SMOKE else 12,
                           intermediate_size=128 if SMOKE else 3072,
                           max_position=seq, dtype=jnp.bfloat16,
                           dropout_rate=0.0, remat=True,
                           remat_policy=a.get("remat_policy", "full"),
                           fused_layernorm=a.get("fused_layernorm", False),
                           loss_seq_chunk=min(a.get("loss_chunk", 0),
                                              64 if SMOKE else 1 << 30))
        model = GPT(config, mesh=mesh)
        optimizer = optim.adamw(1e-4, fused=a.get("fused_adam", False))
        step = train.make_custom_train_step(model.lm_loss_fn(), optimizer,
                                            grad_clip_norm=1.0)
        try:
            params = model.init(jax.random.PRNGKey(0))
            n_params = sum(int(x.size) for x in jax.tree.leaves(params))
            state = train.TrainState.create(params, optimizer.init(params))
            state = jax.device_put(state, NamedSharding(mesh, P()))
            # targets stay < 50257 so vocab_pad's tail rows get no gradient
            # traffic beyond the matmul itself — same work, aligned shapes
            tokens = rng.integers(0, 50257, (batch, seq + 1)).astype(np.int32)
            bb = jax.device_put({"input_ids": tokens}, bsh)
            dt, loss = time_step(step, state, bb)
            toks = batch * seq / dt
            f_tok = 6.0 * n_params + 12.0 * 12 * 768 * seq
            out = {"model": "gpt", "arm": arm, "batch": batch, "seq": seq,
                   "backend": jax.devices()[0].platform, "smoke": SMOKE,
                   "tokens_per_sec": round(toks, 1),
                   "ms_per_step": round(dt * 1e3, 2), "loss": round(loss, 3)}
            if peak:
                out["mfu"] = round(toks * f_tok / peak, 4)
            print(json.dumps(out), flush=True)
        except Exception as e:  # noqa: BLE001 - OOM arms are data
            print(json.dumps({"model": "gpt", "arm": arm,
                              "error": str(e)[:160]}), flush=True)


def run_bert(arms):
    from distributed_tensorflow_tpu import optim, parallel, train
    from distributed_tensorflow_tpu.models.bert import Bert, BertConfig

    mesh = parallel.data_parallel_mesh()
    bsh = NamedSharding(mesh, P("data"))
    rng = np.random.default_rng(0)
    peak = peak_flops()

    MATRIX = {
        "base":       dict(),
        "seq256":     dict(seq=256, batch=32),
        "fused_adam": dict(fused_adam=True),
        "fused_ln":   dict(fused_layernorm=True),
        "batch128":   dict(batch=128),
        # original-BERT max_predictions_per_seq: MLM head on ~15% of
        # tokens instead of all of them (cap = 20% of seq)
        "mlm_gather":      dict(mlm_gather=True),
        "mlm_gather_b128": dict(mlm_gather=True, batch=128),
        "mlm_gather_b256": dict(mlm_gather=True, batch=256),
        "remat_dots":        dict(remat_policy="dots"),
        "remat_dots_gather": dict(remat_policy="dots", mlm_gather=True,
                                  batch=128),
        # fused_ln measured +6.4% pure (08-01) but its composition with
        # the winning remat_dots_gather arm is UNMEASURED (a custom-vjp
        # Pallas LN inside a remat region changes what gets saved) —
        # this arm decides whether the fused-LN lever joins the default
        "remat_dots_gather_ln": dict(remat_policy="dots", mlm_gather=True,
                                     batch=128, fused_layernorm=True),
    }
    for arm in arms or MATRIX:
        a = MATRIX[arm]
        seq, batch = a.get("seq", 128), a.get("batch", 64)
        if SMOKE:
            # smoke batch stays tiny but must divide over the data mesh
            seq, batch = min(seq, 64), max(min(batch, 4),
                                           len(jax.devices()))
        kw = (dict(vocab_size=512, hidden_size=64, num_layers=2,
                   num_heads=2, intermediate_size=128) if SMOKE else {})
        config = BertConfig(max_position=seq, dtype=jnp.bfloat16,
                            dropout_rate=0.0, remat=True,
                            remat_policy=a.get("remat_policy", "full"),
                            fused_layernorm=a.get("fused_layernorm", False),
                            mlm_predictions_per_seq=(
                                seq // 5 if a.get("mlm_gather") else 0),
                            **kw)
        model = Bert(config, mesh=mesh)
        optimizer = optim.adamw(1e-4, fused=a.get("fused_adam", False))
        step = train.make_custom_train_step(model.mlm_loss_fn(), optimizer,
                                            grad_clip_norm=1.0)
        try:
            params = model.init(jax.random.PRNGKey(0))
            n_params = sum(int(x.size) for x in jax.tree.leaves(params))
            state = train.TrainState.create(params, optimizer.init(params))
            state = jax.device_put(state, NamedSharding(mesh, P()))
            ids = rng.integers(0, config.vocab_size,
                               (batch, seq)).astype(np.int32)
            batch_d = jax.device_put(
                {"input_ids": ids,
                 "labels": ids,
                 "mlm_mask": (rng.random((batch, seq)) < 0.15
                              ).astype(np.float32),
                 "attention_mask": np.ones((batch, seq), np.int32)}, bsh)
            dt, loss = time_step(step, state, batch_d)
            toks = batch * seq / dt
            # gather arms execute fewer head FLOPs — count only what ran
            # (shared accounting with bench_bert)
            from distributed_tensorflow_tpu.models.bert import \
                mlm_gather_flops_correction
            f_tok = (6.0 * n_params + 12.0 * 12 * 768 * seq
                     - mlm_gather_flops_correction(config, seq))
            out = {"model": "bert", "arm": arm, "batch": batch, "seq": seq,
                   "backend": jax.devices()[0].platform, "smoke": SMOKE,
                   "tokens_per_sec": round(toks, 1),
                   "ms_per_step": round(dt * 1e3, 2), "loss": round(loss, 3)}
            if peak:
                out["mfu"] = round(toks * f_tok / peak, 4)
            print(json.dumps(out), flush=True)
        except Exception as e:  # noqa: BLE001
            print(json.dumps({"model": "bert", "arm": arm,
                              "error": str(e)[:160]}), flush=True)


def run_llama(arms):
    """The bench_llama model (rmsnorm/swiglu/rope/GQA 12q/4kv, ~160M
    params) through the same arm harness: the 08-01 window covered only
    gpt/bert, so the llama row's levers are unmeasured — in particular
    whether remat_dots helps (it did for BERT +12%, it HURT for GPT -4%)
    and whether the fused rmsnorm kernel (ops.pallas.fused_rmsnorm —
    added after the window, parity-tested, Mosaic-unproven) wins."""
    from distributed_tensorflow_tpu import optim, parallel, train
    from distributed_tensorflow_tpu.models.gpt import GPT
    from distributed_tensorflow_tpu.models.llama import llama_config

    mesh = parallel.data_parallel_mesh()
    bsh = NamedSharding(mesh, P("data"))
    rng = np.random.default_rng(0)
    peak = peak_flops()

    MATRIX = {
        "base":       dict(),                      # remat full, b48 s256
        "remat_dots": dict(remat_policy="dots"),
        "fused_ln":   dict(fused_layernorm=True),  # fused_rmsnorm kernel
        "batch96":    dict(batch=96),
    }
    for arm in arms or MATRIX:
        a = MATRIX[arm]
        seq, batch = a.get("seq", 256), a.get("batch", 48)
        if SMOKE:
            # smoke batch stays tiny but must divide over the data mesh
            seq, batch = min(seq, 64), max(min(batch, 4),
                                           len(jax.devices()))
        kw = (dict(vocab_size=512, hidden_size=128, num_layers=2,
                   num_heads=4, num_kv_heads=2, intermediate_size=384)
              if SMOKE else
              dict(vocab_size=32000, hidden_size=768, num_layers=12,
                   num_heads=12, num_kv_heads=4, intermediate_size=2048))
        config = llama_config(max_position=seq, dtype=jnp.bfloat16,
                              remat=True,
                              remat_policy=a.get("remat_policy", "full"),
                              fused_layernorm=a.get("fused_layernorm",
                                                    False), **kw)
        model = GPT(config, mesh=mesh)
        optimizer = optim.adamw(1e-4)
        step = train.make_custom_train_step(model.lm_loss_fn(), optimizer,
                                            grad_clip_norm=1.0)
        try:
            params = model.init(jax.random.PRNGKey(0))
            n_params = sum(int(x.size) for x in jax.tree.leaves(params))
            state = train.TrainState.create(params, optimizer.init(params))
            state = jax.device_put(state, NamedSharding(mesh, P()))
            tokens = rng.integers(0, config.vocab_size,
                                  (batch, seq + 1)).astype(np.int32)
            bb = jax.device_put({"input_ids": tokens}, bsh)
            dt, loss = time_step(step, state, bb)
            toks = batch * seq / dt
            f_tok = (6.0 * n_params
                     + 12.0 * config.num_layers * config.hidden_size * seq)
            out = {"model": "llama", "arm": arm, "batch": batch, "seq": seq,
                   "backend": jax.devices()[0].platform, "smoke": SMOKE,
                   "tokens_per_sec": round(toks, 1),
                   "ms_per_step": round(dt * 1e3, 2), "loss": round(loss, 3)}
            if peak:
                out["mfu"] = round(toks * f_tok / peak, 4)
            print(json.dumps(out), flush=True)
        except Exception as e:  # noqa: BLE001
            print(json.dumps({"model": "llama", "arm": arm,
                              "error": str(e)[:160]}), flush=True)


def main():
    dev = jax.devices()[0]
    print(f"backend: {dev.platform} ({getattr(dev, 'device_kind', '?')})",
          file=sys.stderr)
    which = sys.argv[1] if len(sys.argv) > 1 else "gpt"
    arms = sys.argv[2:]
    if which in ("gpt", "all"):
        run_gpt(arms if which == "gpt" else None)
    if which in ("bert", "all"):
        run_bert(arms if which == "bert" else None)
    if which in ("llama", "all"):
        run_llama(arms if which == "llama" else None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
