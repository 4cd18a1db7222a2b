"""MNIST MLP entrypoint (low-level tier) — BASELINE configs #1/#2.

The reference ships this file EMPTY (0 bytes, see SURVEY.md §2a #16); the
driver's north star repurposes the outlines as real ``--device=tpu``
entrypoints.  This one is the low-level-API MNIST run: the 2-layer MLP
data-parallel over all chips (pmap+psum capability expressed as pjit over a
``data`` mesh), with the same monitored-session machinery as example.py.

Run: python outline_tensorflow.py [--device=tpu] [--epochs=N] [--data_dir=...]
Real MNIST IDX/npz files in --data_dir are used when present; otherwise a
learnable synthetic stand-in with identical shapes (zero-egress default).
"""
import os
import sys

from distributed_tensorflow_tpu.utils import flags as flags_lib
from distributed_tensorflow_tpu.utils.flags import FLAGS

flags_lib.DEFINE_string("device", "", "Force a JAX platform; empty = default")
flags_lib.DEFINE_string("data_dir", os.environ.get("DATA_DIR", ""),
                        "Directory with MNIST files (IDX or mnist.npz)")
flags_lib.DEFINE_string("log_dir",
                        os.environ.get("LOG_DIR", os.path.join("logs", "mnist")),
                        "Checkpoint/summary directory")
flags_lib.DEFINE_integer("epochs", 5, "Training epochs")
flags_lib.DEFINE_integer("batch_size", 1024, "Global batch size")
flags_lib.DEFINE_float("learning_rate", 1e-3, "Adam learning rate")
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
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_tpu import data, models, optim, parallel, train
    from distributed_tensorflow_tpu.summary import SummaryWriter

    mesh = parallel.data_parallel_mesh()
    is_chief = cluster.is_chief()
    print(f"devices: {len(jax.devices())} ({jax.devices()[0].platform}), "
          f"mesh={dict(mesh.shape)}", file=sys.stderr)

    (x_train, y_train), (x_val, y_val) = data.mnist(
        FLAGS.data_dir or None, flatten=True, seed=FLAGS.seed)

    model = models.mnist_mlp()
    optimizer = optim.adam(FLAGS.learning_rate)
    metric_fns = {"accuracy": "accuracy"}
    train_step = train.make_train_step(
        model, "sparse_categorical_crossentropy", optimizer,
        metric_fns=metric_fns, mesh=mesh, seed=FLAGS.seed)
    eval_step = train.make_eval_step(
        model, "sparse_categorical_crossentropy", metric_fns=metric_fns)

    batch_size = parallel.round_batch_to_mesh(FLAGS.batch_size, mesh)
    local_batch = batch_size // jax.process_count()
    dataset = data.Dataset([x_train, y_train], local_batch, seed=FLAGS.seed,
                           process_index=jax.process_index(),
                           process_count=jax.process_count())
    state = train.init_train_state(model, optimizer,
                                   jax.random.PRNGKey(FLAGS.seed), (784,))
    state = jax.device_put(state, NamedSharding(mesh, P()))
    batch_sharding = NamedSharding(mesh, P("data"))

    writer = SummaryWriter(FLAGS.log_dir) if is_chief else None
    hooks = [train.StopAtStepHook(last_step=FLAGS.epochs * len(dataset)),
             train.CheckpointHook(every_secs=120.0),
             train.LoggingHook(every_steps=max(10, len(dataset) // 2)),
             train.PreemptionHook()]
    if writer is not None:
        hooks.append(train.SummaryHook(writer, every_steps=10))

    with train.TrainSession(state, train_step, checkpoint_dir=FLAGS.log_dir,
                            hooks=hooks, is_chief=is_chief) as sess:
        while not sess.should_stop():
            for batch in data.prefetch_to_device(iter(dataset),
                                                 sharding=batch_sharding):
                if sess.should_stop():
                    break
                sess.run_step(batch)
        val = eval_step(sess.state, (x_val[:4096], y_val[:4096]))
        print(f"Final step {sess.step}: val loss {float(val['loss']):.4f}  "
              f"val accuracy {float(val['accuracy']):.4f}", flush=True)
    if writer is not None:
        writer.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
