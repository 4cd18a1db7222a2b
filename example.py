"""Low-level distributed training entrypoint — TPU-native.

Capability-parity rebuild of reference example.py (all cited lines refer to
/root/reference/example.py): the 64-bit XOR task (ref :24-48), the
128-128-32 MLP with dropout (ref :149-155), MSE + bitwise accuracy
(ref :157-164), Adam + global step (ref :168-170), monitored training with
chief election / checkpointing / StopAtStepHook (ref :187-192), TB summaries
at fractional-epoch steps (ref :172-174,219), per-5-epoch validation prints
(ref :222-226), and env-var cluster bootstrap with a single-machine fallback
(ref :59-68,108-143).

What is different — by design, not accident (SURVEY.md §7):
  * No parameter server, no gRPC: every process runs this same SPMD program;
    gradient sync is a compiled all-reduce over ICI implied by sharding the
    batch over the mesh's ``data`` axis.  ``JOB_NAME=ps`` processes are
    politely refused.
  * Synchronous data parallelism (the reference's async PS updates train on
    stale weights); one step = one global update.
  * The whole train step (fwd+bwd+Adam+metrics) is ONE XLA program; batches
    are prefetched to device, not fed per step over feed_dict.

Run:  python example.py [--device=tpu] [--log_dir=...] [--epochs=N]
Cluster topology comes from COORDINATOR_ADDRESS/NUM_PROCESSES/PROCESS_ID or
the reference's legacy JOB_NAME/TASK_INDEX/WORKER_HOSTS env vars; with none
set this runs single-machine, exactly like the reference.
"""
import os
import sys

from distributed_tensorflow_tpu import utils
from distributed_tensorflow_tpu.utils import flags as flags_lib
from distributed_tensorflow_tpu.utils.flags import FLAGS

# ---------------------------------------------------------------------------
# Hyperparameters (parity with ref :12-19)
# ---------------------------------------------------------------------------
bits = 32                  # half the input width; label width
train_batch_size = 50      # global batch size
train_set_size = 30000
val_set_size = 1000
epochs = 50
print_rate = 5             # epochs between validation prints

# ---------------------------------------------------------------------------
# Env-var bootstrap -> flags (parity with ref :59-105, minus the str/int
# chief-election bug and the swapped data_dir/log_dir help strings)
# ---------------------------------------------------------------------------
flags_lib.DEFINE_string(
    "job_name", flags_lib.env_default("JOB_NAME", None),
    "Legacy role name ('worker'; 'ps' is refused — there is no parameter "
    "server on TPU)")
flags_lib.DEFINE_integer(
    "task_index",
    flags_lib.env_default("PROCESS_ID",
                          flags_lib.env_default("TASK_INDEX", 0, int), int),
    "Process index within the job; index 0 is chief (does checkpoint and "
    "summary writes)")
flags_lib.DEFINE_string(
    "coordinator", flags_lib.env_default("COORDINATOR_ADDRESS", None),
    "host:port of process 0 for multi-host runs")
flags_lib.DEFINE_integer(
    "num_processes", flags_lib.env_default("NUM_PROCESSES", 0, int),
    "Number of participating host processes (0 = infer from env)")
flags_lib.DEFINE_string(
    "worker_hosts", flags_lib.env_default("WORKER_HOSTS", None),
    "Legacy comma-separated worker list; first host becomes coordinator")
# Local-vs-cloud defaults via the clusterone-helper analogue (reference
# example.py:83-102): DTTPU_DATA_ROOT / DTTPU_LOGS_ROOT switch to managed
# roots, else the local fallback.
flags_lib.DEFINE_string(
    "data_dir", os.environ.get("DATA_DIR") or utils.get_data_path(
        "xor", local_root=os.path.join("logs", "data"), local_repo="xor"),
    "Directory containing/receiving training data")
flags_lib.DEFINE_string(
    "log_dir", os.environ.get("LOG_DIR") or utils.get_logs_path(
        os.path.join("logs", "xor")),
    "Directory for checkpoints and TensorBoard event files")
flags_lib.DEFINE_string(
    "device", "", "Force a JAX platform ('tpu', 'cpu'); empty = default")
flags_lib.DEFINE_integer("epochs", epochs, "Training epochs")
flags_lib.DEFINE_integer(
    "accum_steps", 1,
    "Gradient-accumulation microbatches per update (1 = off)")
flags_lib.DEFINE_bool(
    "async_checkpoint", False,
    "Write checkpoints on a background thread (never stalls the step)")
flags_lib.DEFINE_integer("batch_size", train_batch_size, "Global batch size")
flags_lib.DEFINE_integer("seed", 0, "PRNG seed")


def main() -> int:
    FLAGS.parse()
    if FLAGS.device:
        import jax
        jax.config.update("jax_platforms", FLAGS.device)
    from distributed_tensorflow_tpu.utils import enable_compile_cache
    enable_compile_cache()

    # Cluster bootstrap (replaces ClusterSpec/Server/replica_device_setter,
    # ref :108-143).  CLI flags overlay the environment so
    # ``--coordinator/--num_processes/--task_index`` work without env vars.
    from distributed_tensorflow_tpu.parallel import cluster
    env = dict(os.environ)
    if FLAGS.coordinator:
        env["COORDINATOR_ADDRESS"] = FLAGS.coordinator
    if FLAGS.num_processes:
        env["NUM_PROCESSES"] = str(FLAGS.num_processes)
    if FLAGS.worker_hosts:
        env["WORKER_HOSTS"] = FLAGS.worker_hosts
    if FLAGS.job_name:
        env["JOB_NAME"] = FLAGS.job_name
    env["PROCESS_ID"] = str(FLAGS.task_index)
    config = cluster.cluster_from_env(environ=env)
    if FLAGS.job_name == "ps" or config.is_legacy_ps:
        print("JOB_NAME=ps: no parameter-server role exists on TPU; "
              "gradient sync is an ICI all-reduce. Exiting.")
        if os.environ.get("DTTPU_LAUNCHER"):
            # under a supervisor, exit 0 would read as "completed" —
            # refuse loudly instead (fleet/launcher.py names the reason)
            return cluster.LEGACY_PS_EXIT_CODE
        return 0
    if not config.distributed:
        print("Running single-machine training")   # parity with ref :112
    cluster.initialize(config)

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_tpu import data, ops, optim, parallel, train
    from distributed_tensorflow_tpu.summary import SummaryWriter

    # Device mesh: all chips on one 'data' axis (the pjit generalization of
    # pmap+psum sync-DP; placement is sharding, not device pinning).
    mesh = parallel.data_parallel_mesh()
    is_chief = cluster.is_chief()
    print(f"devices: {len(jax.devices())} ({jax.devices()[0].platform}), "
          f"mesh={dict(mesh.shape)}, chief={is_chief}")

    # Model graph (parity with ref :149-155).
    model = ops.serial(
        ops.Dense(128, activation="relu"),
        ops.Dropout(0.3),
        ops.Dense(128, activation="relu"),
        ops.Dropout(0.3),
        ops.Dense(bits, activation="sigmoid"),
    )

    # Optimizer + global step (ref :168-170); step lives in TrainState.
    optimizer = optim.adam()   # TF 1.4 defaults

    # Data (ref :24-48,184) — vectorized, reshuffled per epoch, sharded per
    # process for multi-host.
    (x_train, y_train), (x_val, y_val) = data.xor_data(
        train_set_size, val_set_size, seed=FLAGS.seed)
    batch_size = parallel.round_batch_to_mesh(FLAGS.batch_size, mesh)
    if batch_size != FLAGS.batch_size:
        print(f"batch_size {FLAGS.batch_size} -> {batch_size} "
              f"(divisible by {parallel.data_shards(mesh)} data shards)")
    # Each process feeds its 1/P share of the *global* batch; the prefetcher
    # assembles the global sharded array (batch_size is divisible by the
    # device count, hence by the process count).
    local_batch = batch_size // jax.process_count()
    dataset = data.Dataset(
        [x_train, y_train], local_batch, seed=FLAGS.seed,
        process_index=jax.process_index(), process_count=jax.process_count())
    total_batch = len(dataset)   # == global steps per epoch

    # Compiled train/eval steps: fwd+bwd+Adam+metrics in one XLA program,
    # batch sharded over 'data' (replaces the sess.run hot loop, ref
    # :207-213).
    metric_fns = {"accuracy": "bitwise_accuracy"}
    train_step = train.make_train_step(model, "mse", optimizer,
                                       metric_fns=metric_fns, mesh=mesh,
                                       seed=FLAGS.seed,
                                       accum_steps=FLAGS.accum_steps)
    eval_step = train.make_eval_step(model, "mse", metric_fns=metric_fns,
                                     mesh=mesh)

    state = train.init_train_state(model, optimizer,
                                   jax.random.PRNGKey(FLAGS.seed), (2 * bits,))
    state = jax.device_put(state, NamedSharding(mesh, P()))
    batch_sharding = NamedSharding(mesh, P("data"))

    # Monitored session (parity with ref :187-192,219): StopAtStepHook at
    # epochs*steps_per_epoch global steps, chief-only checkpoints, TB
    # summaries on the reference's fractional-epoch x-axis.
    last_step = FLAGS.epochs * total_batch
    writer = SummaryWriter(FLAGS.log_dir) if is_chief else None
    if writer is not None:
        # model topology -> TB graph tab (parity with ref :195 add_graph)
        writer.add_graph(model)
    hooks = [train.StopAtStepHook(last_step=last_step),
             train.CheckpointHook(every_secs=60.0),
             train.PreemptionHook()]
    if writer is not None:
        hooks.append(train.SummaryHook(
            writer, every_steps=max(1, total_batch // 60),
            step_fn=lambda s: s / total_batch))

    val_batch = jax.device_put((x_val, y_val), batch_sharding)

    with train.TrainSession(state, train_step, checkpoint_dir=FLAGS.log_dir,
                            hooks=hooks, is_chief=is_chief,
                            async_checkpoint=FLAGS.async_checkpoint) as sess:
        start_epoch = sess.step // total_batch
        for epoch in range(start_epoch, FLAGS.epochs):
            if sess.should_stop():
                break
            # Epoch averages (parity with ref :216-217,226: the reference
            # prints loss/accuracy averaged over the epoch's 600 batches).
            # Sums accumulate ON DEVICE — one tiny add per step, a single
            # host fetch per epoch — so the async dispatch queue never
            # stalls on a per-step device->host sync.
            loss_sum = acc_sum = None
            n_batches = 0
            for batch in data.prefetch_to_device(iter(dataset),
                                                 sharding=batch_sharding):
                if sess.should_stop():
                    break
                m = sess.run_step(batch)
                loss_sum = (m["loss"] if loss_sum is None
                            else loss_sum + m["loss"])
                acc_sum = (m["accuracy"] if acc_sum is None
                           else acc_sum + m["accuracy"])
                n_batches += 1
            # Per-print_rate validation (parity with ref :222-226).
            if epoch % print_rate == 0 or epoch == FLAGS.epochs - 1:
                val = eval_step(sess.state, val_batch)
                avg_loss = (float(loss_sum) / n_batches) if n_batches else 0.0
                avg_acc = (float(acc_sum) / n_batches) if n_batches else 0.0
                print(f"Epoch: {epoch:4d}  loss: {avg_loss:.6f}  "
                      f"train acc: {avg_acc:.4f}  "
                      f"val acc: {float(val['accuracy']):.4f}", flush=True)
                if writer is not None:
                    writer.add_scalars(
                        {"val/accuracy": float(val["accuracy"]),
                         "val/loss": float(val["loss"])}, epoch)
    if writer is not None:
        writer.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
