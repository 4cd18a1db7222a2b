"""Fine-tune a Hugging Face GPT-2 checkpoint under this framework.

The interop walkthrough: take a ``transformers`` GPT-2 (here random-init
tiny for a no-download demo; point ``--hf_dir`` at a real downloaded
checkpoint directory to use trained weights + its tokenizer), convert the
weights (``models.convert.gpt2_from_hf``), fine-tune with the framework's
compiled train step on a data-parallel mesh, and generate through the
KV cache — ids stay exactly the checkpoint's
(``data.GPT2BPETokenizer``).

Run (CPU mesh): ``XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
python examples/finetune_gpt2_hf.py --device=cpu --steps=30``
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_tensorflow_tpu.utils import flags as flags_lib

flags_lib.DEFINE_string("device", "", "cpu|tpu override (config-level)")
flags_lib.DEFINE_string("hf_dir", "", "local HF checkpoint dir (config + "
                        "weights + vocab.json/merges.txt); empty = "
                        "random-init tiny demo model")
flags_lib.DEFINE_integer("steps", 50, "fine-tune steps")
flags_lib.DEFINE_integer("batch_size", 16, "global batch size")
flags_lib.DEFINE_integer("seq_len", 32, "training sequence length")
FLAGS = flags_lib.FLAGS


def main() -> int:
    if FLAGS.device:
        import jax
        jax.config.update("jax_platforms", FLAGS.device)
    from distributed_tensorflow_tpu.utils import enable_compile_cache
    enable_compile_cache()
    import jax
    import numpy as np
    import torch
    import transformers
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_tpu import optim, parallel, train
    from distributed_tensorflow_tpu.models.convert import gpt2_from_hf

    if FLAGS.hf_dir:
        hf = transformers.GPT2LMHeadModel.from_pretrained(FLAGS.hf_dir)
        from distributed_tensorflow_tpu.data import GPT2BPETokenizer
        tok = GPT2BPETokenizer.load(
            os.path.join(FLAGS.hf_dir, "vocab.json"),
            os.path.join(FLAGS.hf_dir, "merges.txt"))
        encode = tok.encode
        decode = tok.decode
    else:
        torch.manual_seed(0)
        hf = transformers.GPT2LMHeadModel(transformers.GPT2Config(
            vocab_size=256, n_positions=64, n_embd=64, n_layer=2, n_head=2,
            resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0))
        # demo tokenizer: the framework's byte-level base (ids < 256
        # land inside the tiny vocab)
        from distributed_tensorflow_tpu.data import ByteTokenizer
        tok = ByteTokenizer()
        encode, decode = tok.encode, tok.decode

    mesh = parallel.data_parallel_mesh()
    model, params = gpt2_from_hf(hf.eval(), mesh=mesh)
    print(f"converted GPT-2: {model.config.num_layers} layers, "
          f"hidden {model.config.hidden_size}, "
          f"vocab {model.config.vocab_size}", file=sys.stderr)

    corpus = ("the quick brown fox jumps over the lazy dog. " * 64)
    ids = np.asarray(encode(corpus))
    seq = FLAGS.seq_len
    n = (len(ids) - 1) // seq
    if n == 0:
        raise SystemExit(
            f"--seq_len={seq} exceeds the tokenized corpus "
            f"({len(ids)} ids) — no training rows")
    rows = np.stack([ids[i * seq:i * seq + seq + 1] for i in range(n)])

    optimizer = optim.adamw(3e-4)
    step = train.make_custom_train_step(model.lm_loss_fn(), optimizer,
                                        grad_clip_norm=1.0)
    state = train.TrainState.create(params, optimizer.init(params))
    batch = parallel.round_batch_to_mesh(FLAGS.batch_size, mesh)
    bsh = NamedSharding(mesh, P("data"))
    rng = np.random.default_rng(0)
    for it in range(FLAGS.steps):
        pick = rng.integers(0, len(rows), batch)
        state, m = step(state, {"input_ids": jax.device_put(
            rows[pick].astype(np.int32), bsh)})
        if it % 10 == 0 or it == FLAGS.steps - 1:
            print(f"step {it}: loss={float(m['loss']):.4f}",
                  file=sys.stderr)

    prompt = encode("the quick brown")[None].astype(np.int32)
    out = model.generate(state.params, prompt, max_new_tokens=12,
                         temperature=0.0)
    print("generated:", repr(decode(np.asarray(out)[0].tolist())))
    return 0


if __name__ == "__main__":
    sys.exit(main())
