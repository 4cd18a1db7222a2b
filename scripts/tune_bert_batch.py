"""Batch/remat operating-point tuner for the BERT bench row, on real TPU.

Same question tune_gpt_batch.py answered for the decoder (where remat won
+14-20%): does per-layer rematerialisation beat the activation spill for
BERT-base MLM at seq 128, and does the batch it unlocks net out faster?
Decides whether bench_bert flips ``remat=True`` and extends its ladder.
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
    from distributed_tensorflow_tpu.models.bert import Bert, BertConfig

    dev = jax.devices()[0]
    print(f"backend: {dev.platform} ({dev.device_kind})", file=sys.stderr)
    if dev.platform != "tpu":
        print("NOT a TPU — operating-point decisions need hardware",
              file=sys.stderr)
        return 2

    seq = 128
    mesh = parallel.data_parallel_mesh()
    bsh = NamedSharding(mesh, P("data"))
    rng = np.random.default_rng(0)
    for remat in (False, True):
        config = BertConfig(max_position=seq, dtype=jnp.bfloat16,
                            remat=remat)
        model = Bert(config, mesh=mesh)
        params_host = jax.device_get(model.init(jax.random.PRNGKey(0)))
        optimizer = optim.adamw(1e-4)
        step = train.make_custom_train_step(model.mlm_loss_fn(), optimizer,
                                            grad_clip_norm=1.0)
        for batch in (96, 192, 384):
            try:
                params = jax.device_put(params_host)
                state = train.TrainState.create(params,
                                                optimizer.init(params))
                state = jax.device_put(state, NamedSharding(mesh, P()))
                bb = jax.device_put({
                    "input_ids": rng.integers(
                        0, config.vocab_size, (batch, seq)).astype(np.int32),
                    "labels": rng.integers(
                        0, config.vocab_size, (batch, seq)).astype(np.int32),
                    "mlm_mask": (rng.random((batch, seq)) < 0.15
                                 ).astype(np.float32),
                    "attention_mask": np.ones((batch, seq), np.int32)}, bsh)
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
                del state, bb
            except Exception as e:  # noqa: BLE001 - OOM rungs are data
                print(json.dumps({"remat": remat, "batch": batch,
                                  "error": str(e)[:120]}), flush=True)
                break    # bigger batches only OOM harder
    return 0


if __name__ == "__main__":
    sys.exit(main())
