"""CIFAR-10 CNN entrypoint (high-level tier) — BASELINE config #3.

The reference ships this file EMPTY (0 bytes, SURVEY.md §2a #16); per the
driver's north star it becomes the Keras-style CNN entrypoint:
``Sequential``/``compile``/``fit`` over the small conv net, data-parallel
across all chips via the mesh argument to ``compile`` — the high-level user
never touches a collective.

Run: python outline_keras.py [--device=tpu] [--epochs=N] [--data_dir=...]
Real CIFAR-10 files in --data_dir are used when present; otherwise the
learnable synthetic stand-in (zero-egress default).
"""
import os
import sys
from time import time

from distributed_tensorflow_tpu.utils import flags as flags_lib
from distributed_tensorflow_tpu.utils.flags import FLAGS

flags_lib.DEFINE_string("device", "", "Force a JAX platform; empty = default")
flags_lib.DEFINE_string("data_dir", os.environ.get("DATA_DIR", ""),
                        "Directory with CIFAR-10 files")
flags_lib.DEFINE_string("log_dir",
                        os.environ.get("LOG_DIR",
                                       os.path.join("logs", "cifar_{}")),
                        "TensorBoard directory; '{}' gets a timestamp")
flags_lib.DEFINE_integer("epochs", 10, "Training epochs")
flags_lib.DEFINE_integer("batch_size", 256, "Global batch size")
flags_lib.DEFINE_integer("steps_per_execution", 1,
                         "Optimizer updates per compiled dispatch (K>1 "
                         "amortizes host->device latency for small models)")
flags_lib.DEFINE_integer("seed", 0, "PRNG seed")


def main() -> int:
    FLAGS.parse()
    if FLAGS.device:
        import jax
        jax.config.update("jax_platforms", FLAGS.device)
    from distributed_tensorflow_tpu.utils import enable_compile_cache
    enable_compile_cache()

    from distributed_tensorflow_tpu.parallel import cluster
    cluster.initialize()

    import jax

    from distributed_tensorflow_tpu import data, models, parallel

    mesh = parallel.data_parallel_mesh()
    print(f"devices: {len(jax.devices())} ({jax.devices()[0].platform}), "
          f"mesh={dict(mesh.shape)}", file=sys.stderr)

    (x_train, y_train), (x_val, y_val) = data.cifar10(FLAGS.data_dir or None,
                                                      seed=FLAGS.seed)

    model = models.Sequential(models.cifar_cnn().layers, name="cifar_cnn")
    model.compile(loss="sparse_categorical_crossentropy", optimizer="adam",
                  metrics=["accuracy"], mesh=mesh, seed=FLAGS.seed,
                  steps_per_execution=FLAGS.steps_per_execution)

    tensorboard = models.TensorBoard(log_dir=FLAGS.log_dir.format(time()))
    # Standard CIFAR recipe: pad-reflect crop + horizontal flip, host-side,
    # overlapped with device compute by the prefetch queue.
    train_augment = data.augment.compose(data.augment.random_crop(4),
                                         data.augment.random_flip_lr())
    model.fit(x_train, y_train, epochs=FLAGS.epochs,
              batch_size=FLAGS.batch_size,
              validation_data=(x_val[:4096], y_val[:4096]),
              callbacks=[tensorboard], seed=FLAGS.seed,
              augment=train_augment)

    final = model.evaluate(x_val, y_val, batch_size=FLAGS.batch_size,
                           verbose=0)
    print(f"Final validation accuracy: {final['accuracy']:.4f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
