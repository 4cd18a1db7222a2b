"""Batch/remat operating-point tuner for the GPT bench row, on real TPU.

The 2026-07-31 sweep showed bench_gpt's ladder landing at batch 24: the
layer-scan saves every activation for backward, and GPT-2-small at
seq 256 already OOMs a 16G chip at batch 48.  ``GPTConfig(remat=True)``
(checkpoint each decoder layer, recompute in backward) trades those saved
activations for recompute FLOPs — this script measures whether the bigger
batch it unlocks nets out faster, to pick the bench default.

Timing: warmup dispatches then a timed window of chained donated-state
steps closed by a value fetch (docs/PERF.md methodology).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def main():
    from distributed_tensorflow_tpu import optim, parallel, train
    from distributed_tensorflow_tpu.models.gpt import GPT, GPTConfig

    dev = jax.devices()[0]
    print(f"backend: {dev.platform} ({dev.device_kind})", file=sys.stderr)
    if dev.platform != "tpu":
        print("NOT a TPU — operating-point decisions need hardware",
              file=sys.stderr)
        return 2

    seq = int(os.environ.get("DTTPU_BENCH_SEQ", "256"))
    mesh = parallel.data_parallel_mesh()
    bsh = NamedSharding(mesh, P("data"))
    rng = np.random.default_rng(0)

    for remat in (False, True):
        config = GPTConfig(vocab_size=50257, hidden_size=768, num_layers=12,
                           num_heads=12, intermediate_size=3072,
                           max_position=seq, dtype=jnp.bfloat16,
                           dropout_rate=0.0, remat=remat)
        model = GPT(config, mesh=mesh)
        # host copy: the donated train-step state aliases the live params
        # buffers, so each rung rebuilds device state from host
        params_host = jax.device_get(model.init(jax.random.PRNGKey(0)))
        optimizer = optim.adamw(1e-4)
        step = train.make_custom_train_step(model.lm_loss_fn(), optimizer,
                                            grad_clip_norm=1.0)
        for batch in (24, 48, 96, 192, 384):
            try:
                params = jax.device_put(params_host)
                state = train.TrainState.create(params,
                                                optimizer.init(params))
                state = jax.device_put(state, NamedSharding(mesh, P()))
                tokens = rng.integers(0, config.vocab_size,
                                      (batch, seq + 1)).astype(np.int32)
                bb = jax.device_put({"input_ids": tokens}, bsh)
                for _ in range(3):                       # compile + warmup
                    state, metrics = step(state, bb)
                float(metrics["loss"])
                n = 10
                t0 = time.perf_counter()
                for _ in range(n):
                    state, metrics = step(state, bb)
                loss = float(metrics["loss"])            # closes the window
                dt = (time.perf_counter() - t0) / n
                print(json.dumps({
                    "remat": remat, "batch": batch,
                    "tokens_per_sec": round(batch * seq / dt, 1),
                    "ms_per_step": round(dt * 1e3, 2),
                    "loss": round(loss, 3)}), flush=True)
            except Exception as e:  # noqa: BLE001 - OOM rungs are data
                print(json.dumps({"remat": remat, "batch": batch,
                                  "error": str(e)[:120]}), flush=True)
                break    # bigger batches only OOM harder
    return 0


if __name__ == "__main__":
    sys.exit(main())
