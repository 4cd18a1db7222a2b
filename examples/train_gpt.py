"""End-to-end distributed GPT training — the full subsystem stack in one
script.

The transformer-family analogue of ``example.py``: causal-LM training on a
deterministic synthetic corpus (no downloads), exercising

  * mesh construction with data+fsdp axes and ZeRO state placement,
  * mixed bf16 compute over an f32 master copy (``policy``),
  * EMA parameter averaging riding in opt_state,
  * TrainSession with stop/checkpoint/summary/logging hooks and sharded
    per-process checkpoints,
  * KV-cache generation from the trained weights at the end.

Run (CPU mesh): ``XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
python examples/train_gpt.py --device=cpu --steps=60``
Run (TPU): ``python examples/train_gpt.py``
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_tensorflow_tpu.utils import flags as flags_lib

flags_lib.DEFINE_string("device", "", "cpu|tpu override (config-level)")
flags_lib.DEFINE_integer("steps", 200, "training steps")
flags_lib.DEFINE_integer("batch_size", 32, "global batch size")
flags_lib.DEFINE_integer("seq_len", 64, "sequence length")
flags_lib.DEFINE_string("log_dir", "/tmp/dttpu_gpt", "checkpoints + events")
flags_lib.DEFINE_integer("seed", 0, "data/init seed")
flags_lib.DEFINE_integer("num_layers", 2, "decoder blocks")
flags_lib.DEFINE_integer("pipeline_stages", 0,
                         "split the decoder over a 'pipe' mesh axis "
                         "(0 = off; must divide --num_layers AND the "
                         "device count; replaces the fsdp axis)")
flags_lib.DEFINE_string("pp_schedule", "gpipe",
                        "pipeline schedule: gpipe (autodiff backward) | "
                        "1f1b (hand-scheduled, O(stages) activation memory)")
flags_lib.DEFINE_string("family", "gpt2",
                        "decoder recipe: gpt2 (layernorm/gelu/learned "
                        "positions) | llama (rmsnorm/swiglu/rope/GQA, "
                        "models/llama.py)")
flags_lib.DEFINE_integer("loss_seq_chunk", 0,
                         "chunked LM loss: compute the head projection + "
                         "log-softmax N tokens at a time (the full "
                         "[tokens, vocab] logits never materialise; "
                         "0 = off)")
flags_lib.DEFINE_string("remat_policy", "full",
                        "with remat: full (save nothing) | dots (save "
                        "matmul outputs) | dots_no_batch")
flags_lib.DEFINE_bool("remat", False, "checkpoint each decoder layer "
                      "(recompute in backward; unlocks bigger batches)")
FLAGS = flags_lib.FLAGS


def main() -> int:
    if FLAGS.device:
        import jax
        jax.config.update("jax_platforms", FLAGS.device)
    from distributed_tensorflow_tpu.utils import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_tpu import data, optim, parallel, summary, train
    from distributed_tensorflow_tpu.data.datasets import (lm_sequences,
                                                          synthetic_lm_corpus)
    from distributed_tensorflow_tpu.models.gpt import GPT, GPTConfig

    n = len(jax.devices())
    pp = FLAGS.pipeline_stages
    if pp > 1:
        if n % pp:
            raise SystemExit(f"--pipeline_stages={pp} does not divide the "
                             f"device count {n}")
        if FLAGS.num_layers % pp:
            raise SystemExit(f"--pipeline_stages={pp} does not divide "
                             f"--num_layers={FLAGS.num_layers}")
        fsdp = 1
        mesh = parallel.make_mesh({"pipe": pp, "data": n // pp})
    else:
        fsdp = 2 if n % 2 == 0 and n > 1 else 1
        mesh = parallel.make_mesh({"data": n // fsdp, "fsdp": fsdp})
    print(f"devices: {n} ({jax.devices()[0].platform}), "
          f"mesh={dict(mesh.shape)}", file=sys.stderr)

    # XLA:CPU miscompiles scan+ppermute pipeline programs with bf16
    # activations ("Invalid binary instruction opcode copy" check failure
    # in both the GPipe transpose and the jitted pipelined forward) — on
    # the CPU backend the pp path trains in f32.  TPU keeps bf16.
    pp_cpu = pp > 1 and jax.devices()[0].platform == "cpu"
    if pp_cpu:
        print("pp on XLA:CPU: falling back to f32 activations (bf16 "
              "pipeline programs trip an XLA:CPU compiler bug)",
              file=sys.stderr)
    dims = dict(vocab_size=256, num_layers=FLAGS.num_layers, num_heads=4,
                hidden_size=128, max_position=FLAGS.seq_len,
                dtype=jnp.float32 if pp_cpu else jnp.bfloat16,
                pipeline_stages=pp if pp > 1 else 0,
                remat=FLAGS.remat, remat_policy=FLAGS.remat_policy,
                loss_seq_chunk=FLAGS.loss_seq_chunk)
    if FLAGS.family == "llama":
        from distributed_tensorflow_tpu.models.llama import llama_config
        config = llama_config(num_kv_heads=2, **dims)
    elif FLAGS.family == "gpt2":
        config = GPTConfig(**dims)
    else:
        raise SystemExit(f"--family={FLAGS.family!r}: gpt2|llama")
    model = GPT(config, mesh=mesh)
    optimizer = optim.with_ema(optim.adamw(3e-3), decay=0.99)

    params = model.init(jax.random.PRNGKey(FLAGS.seed))
    state = train.TrainState.create(params, optimizer.init(params))
    state = train.shard_train_state(state, mesh,
                                    model.partition_rules(fsdp=fsdp > 1))

    if pp > 1 and FLAGS.pp_schedule == "1f1b":
        # hand-scheduled 1F1B: full-model grads at O(stages) memory
        step = train.make_1f1b_train_step(model, optimizer,
                                          grad_clip_norm=1.0)
    else:
        # non-pp, or GPipe: apply() routes the decoder through the
        # pipeline and autodiff transposes it into the backward schedule.
        # The bf16 policy is skipped under pp: config.dtype already casts
        # the compute path, and the param-cast composed with the pipeline
        # shard_map trips an XLA:CPU check failure.
        step = train.make_custom_train_step(
            model.lm_loss_fn(), optimizer, grad_clip_norm=1.0,
            policy=None if pp > 1 else "mixed_bfloat16")

    # order-1 (bigram) chain: strongly learnable, so short runs show a real
    # drop below the uniform baseline
    rows = lm_sequences(synthetic_lm_corpus(config.vocab_size, 200_000,
                                            seed=FLAGS.seed, order=1),
                        FLAGS.seq_len)
    batch = parallel.round_batch_to_mesh(FLAGS.batch_size, mesh)
    if pp > 1 and batch % pp:
        # the pipeline also needs batch % microbatches == 0 (= stages
        # here); round up to the lcm of the data-shard and stage counts
        import math
        quantum = math.lcm(parallel.data_shards(mesh), pp)
        batch = -(-FLAGS.batch_size // quantum) * quantum
        print(f"batch_size -> {batch} (divisible by {quantum}: data shards"
              f" x pipeline stages)", file=sys.stderr)
    ds = data.Dataset([rows], batch, seed=FLAGS.seed)
    bsh = NamedSharding(mesh, P(("data", "fsdp")) if fsdp > 1 else P("data"))

    writer = summary.SummaryWriter(FLAGS.log_dir) if parallel.is_chief() \
        else None
    hooks = [train.StopAtStepHook(FLAGS.steps),
             train.LoggingHook(every_steps=20),
             train.NaNHook(every_steps=20)]
    if writer is not None:
        hooks.append(train.SummaryHook(writer, every_steps=10))

    sync_every = 1 if jax.devices()[0].platform == "cpu" else 20
    with train.TrainSession(state, step, checkpoint_dir=FLAGS.log_dir,
                            hooks=hooks, sharded_checkpoint=True) as sess:
        it = 0
        while not sess.should_stop():
            for (b,) in ds:
                if sess.should_stop():
                    break
                m = sess.run_step({"input_ids": jax.device_put(b, bsh)})
                it += 1
                if it % sync_every == 0:
                    float(m["loss"])   # CPU collectives need a shallow queue
        final = sess.state
    if writer is not None:
        writer.close()

    # Evaluate both live and EMA weights on held-out rows; generate a sample.
    eval_rows = rows[-64:]
    loss_fn = model.lm_loss_fn()

    # jit the eval: the pipelined apply (shard_map manual over 'pipe' only)
    # requires a jit context on a multi-axis mesh
    @jax.jit
    def _eval(params, rows_):
        return loss_fn(params, (), {"input_ids": rows_}, None, False)

    def eval_loss(params):
        loss, (metrics, _) = _eval(params, jnp.asarray(eval_rows))
        return float(loss), float(metrics["token_accuracy"])
    live = eval_loss(final.params)
    ema = eval_loss(optim.ema_params(final.opt_state))
    uniform = float(np.log(config.vocab_size))
    print(f"eval loss: live={live[0]:.3f} ema={ema[0]:.3f} "
          f"(uniform={uniform:.3f}); token acc live={live[1]:.3f}")

    prompt = jnp.asarray(eval_rows[:2, :8])
    out = model.generate(final.params, prompt, max_new_tokens=16)
    print(f"generated: {np.asarray(out)[0].tolist()}")
    if live[0] >= uniform:
        print("WARNING: did not beat the uniform baseline", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
