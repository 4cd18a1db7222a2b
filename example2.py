"""High-level (Sequential/compile/fit) distributed training entrypoint.

Capability-parity rebuild of reference example2.py (cited lines refer to
/root/reference/example2.py): the same XOR task and MLP expressed as a
``Sequential`` container (ref :151-156), ``compile(loss='mean_squared_error',
optimizer='adam', metrics=['accuracy'])`` (ref :165), and
``model.fit(..., validation_data=..., callbacks=[TensorBoard])``
(ref :197-200) — with the same cluster bootstrap as example.py.

Divergences from the reference, on purpose (SURVEY.md §7):
  * No ``K.set_session`` bridge (ref :194-195): fit drives the framework's
    own jitted step directly; distribution is a ``mesh=`` argument to
    ``compile``.
  * Checkpointing is NOT silently disabled (the reference comments it out,
    ref :187,191-192) — pass --log_dir and the TensorBoard callback writes
    there; epochs defaults to the module constant instead of the reference's
    hard-coded ``epochs=20`` drift (ref :20,200).
  * The broken ``xor_metric`` (ref :158-163, no return statement) maps to
    the working ``bitwise_accuracy`` metric.
"""
import os
import sys
from time import time

from distributed_tensorflow_tpu.utils import flags as flags_lib
from distributed_tensorflow_tpu.utils.flags import FLAGS

# Hyperparameters (parity with ref :14-21)
bits = 32
train_batch_size = 50
train_set_size = 30000
val_set_size = 1000
epochs = 50

flags_lib.DEFINE_string("job_name", flags_lib.env_default("JOB_NAME", None),
                        "Legacy role name ('ps' is refused)")
flags_lib.DEFINE_integer("task_index",
                         flags_lib.env_default("TASK_INDEX", 0, int),
                         "Process index; 0 is chief")
flags_lib.DEFINE_string("log_dir",
                        os.environ.get("LOG_DIR",
                                       os.path.join("logs", "xor2_{}")),
                        "TensorBoard/checkpoint dir; '{}' gets a timestamp "
                        "(parity with ref :197)")
flags_lib.DEFINE_string("device", "",
                        "Force a JAX platform ('tpu', 'cpu'); empty = default")
flags_lib.DEFINE_integer("epochs", epochs, "Training epochs")
flags_lib.DEFINE_integer("batch_size", train_batch_size, "Global batch size")
flags_lib.DEFINE_integer("seed", 0, "PRNG seed")


def main() -> int:
    FLAGS.parse()
    if FLAGS.device:
        import jax
        jax.config.update("jax_platforms", FLAGS.device)
    from distributed_tensorflow_tpu.utils import enable_compile_cache
    enable_compile_cache()

    from distributed_tensorflow_tpu.parallel import cluster
    config = cluster.cluster_from_env()
    if FLAGS.job_name == "ps" or config.is_legacy_ps:
        print("JOB_NAME=ps: no parameter-server role on TPU. Exiting.")
        if os.environ.get("DTTPU_LAUNCHER"):
            # under a supervisor, exit 0 would read as "completed" —
            # refuse loudly instead (fleet/launcher.py names the reason)
            return cluster.LEGACY_PS_EXIT_CODE
        return 0
    if not config.distributed:
        print("Running single-machine training")
    cluster.initialize(config)

    import jax

    from distributed_tensorflow_tpu import data, models, ops, parallel

    mesh = parallel.data_parallel_mesh()
    print(f"devices: {len(jax.devices())} ({jax.devices()[0].platform}), "
          f"mesh={dict(mesh.shape)}")

    # Sequential model (parity with ref :151-156).
    model = models.Sequential(name="xor_mlp")
    model.add(ops.Dense(128, activation="relu"))
    model.add(ops.Dropout(0.3))
    model.add(ops.Dense(128, activation="relu"))
    model.add(ops.Dropout(0.3))
    model.add(ops.Dense(bits, activation="sigmoid"))

    # compile (parity with ref :165; 'accuracy' on sigmoid bits = the
    # reference's rounded elementwise accuracy graph).
    model.compile(loss="mean_squared_error", optimizer="adam",
                  metrics=["bitwise_accuracy"], mesh=mesh, seed=FLAGS.seed)

    (x_train, y_train), (x_val, y_val) = data.xor_data(
        train_set_size, val_set_size, seed=FLAGS.seed)

    log_dir = FLAGS.log_dir.format(time())
    tensorboard = models.TensorBoard(log_dir=log_dir)   # ref :197

    # fit (parity with ref :200).
    model.fit(x_train, y_train, epochs=FLAGS.epochs,
              batch_size=FLAGS.batch_size,
              validation_data=(x_val, y_val),
              callbacks=[tensorboard], seed=FLAGS.seed)

    final = model.evaluate(x_val, y_val, batch_size=FLAGS.batch_size,
                           verbose=0)
    print(f"Final validation accuracy: {final['bitwise_accuracy']:.4f}",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
