"""ViT image classification — the vision-transformer training workflow.

Trains a tiny ViT on a deterministic synthetic image task (which quadrant
holds the bright patch), exercising

  * patchify-by-conv + pre-LN scanned encoder (``models.vit``),
  * data-parallel mesh training via ``make_custom_train_step``,
  * warmup-cosine LR schedule + grad clipping,
  * eval accuracy as the convergence oracle.

Run (CPU mesh): ``XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
python examples/train_vit.py --device=cpu --steps=300``
Run (TPU): ``python examples/train_vit.py``
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_tensorflow_tpu.utils import flags as flags_lib

flags_lib.DEFINE_string("device", "", "cpu|tpu override (config-level)")
flags_lib.DEFINE_integer("steps", 300, "training steps")
flags_lib.DEFINE_integer("batch_size", 64, "global batch size")
flags_lib.DEFINE_integer("seed", 0, "data/init seed")
FLAGS = flags_lib.FLAGS

SIZE = 32
CLASSES = 4


def make_batch(rng, batch):
    """Class = quadrant of a bright 8x8 patch on a noisy background."""
    x = rng.normal(0.0, 0.2, (batch, SIZE, SIZE, 3)).astype("float32")
    y = rng.integers(0, CLASSES, batch).astype("int32")
    half = SIZE // 2
    for i in range(batch):
        r = (y[i] // 2) * half + rng.integers(0, half - 8)
        c = (y[i] % 2) * half + rng.integers(0, half - 8)
        x[i, r:r + 8, c:c + 8] += 1.0
    return x, y


def main() -> int:
    if FLAGS.device:
        import jax
        jax.config.update("jax_platforms", FLAGS.device)
    from distributed_tensorflow_tpu.utils import enable_compile_cache
    enable_compile_cache()
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_tpu import optim, parallel, train
    from distributed_tensorflow_tpu.models.vit import ViT, ViTConfig

    n = len(jax.devices())
    mesh = parallel.make_mesh({"data": n})
    print(f"devices: {n} ({jax.devices()[0].platform}), "
          f"mesh={dict(mesh.shape)}", file=sys.stderr)

    model = ViT(ViTConfig(image_size=SIZE, patch_size=8, channels=3,
                          num_classes=CLASSES, hidden_size=64, num_layers=4,
                          num_heads=4, intermediate_size=128,
                          dropout_rate=0.1))
    params = model.init(jax.random.PRNGKey(FLAGS.seed))
    optimizer = optim.adamw(optim.schedules.warmup_cosine_decay(
        3e-3, 20, FLAGS.steps))
    state = train.TrainState.create(params, optimizer.init(params))
    state = jax.device_put(state, NamedSharding(mesh, P()))
    step = train.make_custom_train_step(model.loss_fn(), optimizer,
                                        grad_clip_norm=1.0)

    rng = np.random.default_rng(FLAGS.seed)
    bsh = NamedSharding(mesh, P("data"))
    batch = parallel.round_batch_to_mesh(FLAGS.batch_size, mesh)
    for i in range(FLAGS.steps):
        x, y = make_batch(rng, batch)
        b = jax.device_put((x, y), bsh)
        state, metrics = step(state, b)
        if (i + 1) % 20 == 0:
            print(f"step {i + 1}: loss={float(metrics['loss']):.4f} "
                  f"acc={float(metrics['accuracy']):.3f}", flush=True)

    x, y = make_batch(np.random.default_rng(FLAGS.seed + 1), 256)
    import jax.numpy as jnp
    logits = jax.jit(lambda p, xb: model.apply(p, xb))(state.params,
                                                       jnp.asarray(x))
    acc = float(np.mean(np.argmax(np.asarray(logits), -1) == y))
    print(f"eval accuracy: {acc:.3f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
