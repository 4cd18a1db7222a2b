"""BERT classification fine-tune — the pooled-head workflow end to end.

The BASELINE "BERT-base MLM fine-tune" config's little sibling, runnable
anywhere: a tiny BERT encoder + [CLS] pooler + classification head trained
on a deterministic synthetic task (does the token sequence contain the
"trigger" token?), exercising

  * the ``Bert.apply`` + ``pooled`` fine-tune head composition,
  * ``make_custom_train_step`` with a dict batch and grad clipping,
  * megatron TP partition rules on a data+tensor mesh,
  * eval accuracy reporting.

Run (CPU mesh): ``XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
python examples/finetune_bert.py --device=cpu --steps=60``
Run (TPU): ``python examples/finetune_bert.py``
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_tensorflow_tpu.utils import flags as flags_lib

flags_lib.DEFINE_string("device", "", "cpu|tpu override (config-level)")
flags_lib.DEFINE_integer("steps", 150, "training steps")
flags_lib.DEFINE_integer("batch_size", 32, "global batch size")
flags_lib.DEFINE_integer("seq_len", 32, "sequence length")
flags_lib.DEFINE_integer("seed", 0, "data/init seed")
flags_lib.DEFINE_integer("mlm_steps", 0,
                         "MLM pretrain steps before the classifier "
                         "fine-tune (the standard BERT recipe order)")
flags_lib.DEFINE_integer("mlm_predictions_per_seq", 0,
                         "gather at most N masked positions before the "
                         "MLM head (BertConfig.mlm_predictions_per_seq; "
                         "0 = project every position)")
flags_lib.DEFINE_bool("fused_layernorm", False,
                         "LayerNorm via the fused Pallas kernel")
flags_lib.DEFINE_bool("remat", False, "checkpoint each encoder layer")
flags_lib.DEFINE_string("remat_policy", "full",
                        "remat policy: full | dots | dots_no_batch")
FLAGS = flags_lib.FLAGS

TRIGGER = 7          # class 1 iff this token id appears in the sequence
NUM_CLASSES = 2


def make_batch(rng, vocab, batch, seq):
    ids = rng.integers(8, vocab, (batch, seq)).astype("int32")
    labels = rng.integers(0, NUM_CLASSES, batch).astype("int32")
    pos = rng.integers(0, seq, batch)
    rows = labels == 1
    ids[rows, pos[rows]] = TRIGGER
    return ids, labels


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

    from distributed_tensorflow_tpu import optim, parallel, train
    from distributed_tensorflow_tpu.models.bert import Bert, BertConfig
    from distributed_tensorflow_tpu.ops import losses

    n = len(jax.devices())
    tp = 2 if n % 2 == 0 and n > 1 else 1
    mesh = parallel.make_mesh({"data": n // tp, "tensor": tp})
    print(f"devices: {n} ({jax.devices()[0].platform}), "
          f"mesh={dict(mesh.shape)}", file=sys.stderr)

    config = BertConfig(vocab_size=64, hidden_size=128, num_layers=2,
                        num_heads=4, intermediate_size=256,
                        max_position=FLAGS.seq_len, dropout_rate=0.1,
                        dtype=jnp.bfloat16,
                        mlm_predictions_per_seq=FLAGS.mlm_predictions_per_seq,
                        fused_layernorm=FLAGS.fused_layernorm,
                        remat=FLAGS.remat, remat_policy=FLAGS.remat_policy)
    model = Bert(config, mesh=mesh)
    params = model.init(jax.random.PRNGKey(FLAGS.seed))
    # fine-tune head: fresh [hidden, classes] on top of the pooler
    params["classifier"] = {
        "kernel": jnp.zeros((config.hidden_size, NUM_CLASSES), jnp.float32),
        "bias": jnp.zeros((NUM_CLASSES,), jnp.float32)}

    def loss_fn(p, model_state, batch, rng, train_flag):
        seq_out = model.apply(p, batch["input_ids"], train=train_flag,
                              rng=rng)
        pooled = model.pooled(p, seq_out)
        logits = (pooled @ p["classifier"]["kernel"].astype(pooled.dtype)
                  + p["classifier"]["bias"].astype(pooled.dtype)
                  ).astype(jnp.float32)
        loss = losses.softmax_cross_entropy_with_integer_labels(
            logits, batch["labels"])
        acc = jnp.mean((jnp.argmax(logits, -1) == batch["labels"]
                        ).astype(jnp.float32))
        return loss, ({"accuracy": acc}, model_state)

    optimizer = optim.adamw(5e-4)
    state = train.TrainState.create(params, optimizer.init(params))
    if tp > 1:
        rules = model.partition_rules()
        state = train.shard_train_state(state, mesh, rules)
    else:
        state = jax.device_put(state, NamedSharding(mesh, P()))
    step = train.make_custom_train_step(loss_fn, optimizer,
                                        grad_clip_norm=1.0)

    rng = np.random.default_rng(FLAGS.seed)
    bsh = NamedSharding(mesh, P("data"))
    batch = parallel.round_batch_to_mesh(FLAGS.batch_size, mesh)

    # Optional MLM warm-up (the standard BERT recipe order: pretrain the
    # encoder with the MLM head, then fine-tune the classifier).  This is
    # the phase where ``mlm_predictions_per_seq`` actually executes: the
    # masked-position gather before the vocab projection.
    if FLAGS.mlm_steps:
        MASK_ID = 1   # reserved: data tokens are drawn from [8, vocab)
        mlm_step = train.make_custom_train_step(model.mlm_loss_fn(),
                                                optimizer,
                                                grad_clip_norm=1.0)
        for i in range(FLAGS.mlm_steps):
            ids = rng.integers(8, config.vocab_size,
                               (batch, FLAGS.seq_len)).astype(np.int32)
            mask = (rng.random((batch, FLAGS.seq_len)) < 0.15
                    ).astype(np.float32)
            # BERT's corruption rule at the masked positions — 80%
            # [MASK], 10% random token, 10% keep — applied HOST-side:
            # mlm_loss_fn forwards input_ids as-is, so without this the
            # "MLM" phase would be a readable-identity task.
            inp = ids.copy()
            r = rng.random((batch, FLAGS.seq_len))
            m = mask == 1.0
            inp[m & (r < 0.8)] = MASK_ID
            rand_rows = m & (r >= 0.8) & (r < 0.9)
            inp[rand_rows] = rng.integers(
                8, config.vocab_size, int(rand_rows.sum())).astype(np.int32)
            mb = jax.device_put(
                {"input_ids": inp, "labels": ids,
                 "mlm_mask": mask,
                 "attention_mask": np.ones_like(ids)}, bsh)
            state, mlm_m = mlm_step(state, mb)
            if (i + 1) % 25 == 0 or i + 1 == FLAGS.mlm_steps:
                print(f"mlm step {i + 1}: "
                      f"loss={float(mlm_m['loss']):.4f} "
                      f"acc={float(mlm_m['mlm_accuracy']):.3f}",
                      flush=True)

    metrics = {}
    for i in range(FLAGS.steps):
        ids, labels = make_batch(rng, config.vocab_size, batch,
                                 FLAGS.seq_len)
        b = jax.device_put({"input_ids": ids, "labels": labels}, bsh)
        state, metrics = step(state, b)
        if (i + 1) % 25 == 0:
            print(f"step {i + 1}: loss={float(metrics['loss']):.4f} "
                  f"acc={float(metrics['accuracy']):.3f}", flush=True)

    # held-out eval
    eval_step = jax.jit(lambda p, b: loss_fn(p, {}, b,
                                             jax.random.PRNGKey(0), False))
    ids, labels = make_batch(np.random.default_rng(FLAGS.seed + 1),
                             config.vocab_size, 256, FLAGS.seq_len)
    _, (m, _) = eval_step(state.params,
                          {"input_ids": jnp.asarray(ids),
                           "labels": jnp.asarray(labels)})
    print(f"eval accuracy: {float(m['accuracy']):.3f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
