"""Profile one LM train step on TPU and name the top time sinks.

Round-3 verdict: the LM MFU rows (GPT 0.169, BERT 0.112) were tuned
blind — remat/batch ladders but no per-op attribution.  This captures a
``jax.profiler`` trace of a few steps and post-processes the XPlane
protobuf with ``tensorboard_plugin_profile`` (installed here alongside
TF 2.21) into a self-time-ranked op table, i.e. the ResNet-quality
"where does the step actually go" evidence PERF.md is missing for LMs.

Usage: python scripts/profile_gpt_step.py [gpt|bert] [trace_dir]
"""
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


SMOKE = bool(os.environ.get("DTTPU_PROFILE_SMOKE"))


def build(which):
    from distributed_tensorflow_tpu import optim, parallel, train

    mesh = parallel.data_parallel_mesh()
    bsh = NamedSharding(mesh, P("data"))
    rng = np.random.default_rng(0)
    if which == "gpt":
        from distributed_tensorflow_tpu.models.gpt import GPT, GPTConfig
        config = (GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                            num_heads=2, intermediate_size=128,
                            max_position=64, dtype=jnp.bfloat16,
                            dropout_rate=0.0, remat=True) if SMOKE else
                  GPTConfig(vocab_size=50257, hidden_size=768, num_layers=12,
                            num_heads=12, intermediate_size=3072,
                            max_position=256, dtype=jnp.bfloat16,
                            dropout_rate=0.0, remat=True))
        model = GPT(config, mesh=mesh)
        loss_fn = model.lm_loss_fn()
        b, s = (4, 64) if SMOKE else (48, 256)
        tokens = rng.integers(0, config.vocab_size,
                              (b, s + 1)).astype(np.int32)
        batch = jax.device_put({"input_ids": tokens}, bsh)
    else:
        from distributed_tensorflow_tpu.models.bert import Bert, BertConfig
        config = (BertConfig(vocab_size=512, hidden_size=64, num_layers=2,
                             num_heads=2, intermediate_size=128,
                             max_position=64, dtype=jnp.bfloat16,
                             dropout_rate=0.0, remat=True) if SMOKE else
                  BertConfig(max_position=128, dtype=jnp.bfloat16,
                             dropout_rate=0.0, remat=True))
        model = Bert(config, mesh=mesh)
        loss_fn = model.mlm_loss_fn()
        b, s = (4, 64) if SMOKE else (64, 128)
        ids = rng.integers(0, config.vocab_size, (b, s)).astype(np.int32)
        batch = jax.device_put(
            {"input_ids": ids, "labels": ids,
             "mlm_mask": (rng.random((b, s)) < 0.15).astype(np.float32),
             "attention_mask": np.ones((b, s), np.int32)}, bsh)
    optimizer = optim.adamw(1e-4)
    step = train.make_custom_train_step(loss_fn, optimizer,
                                        grad_clip_norm=1.0)
    params = model.init(jax.random.PRNGKey(0))
    state = train.TrainState.create(params, optimizer.init(params))
    state = jax.device_put(state, NamedSharding(mesh, P()))
    return step, state, batch


def top_ops_from_trace(trace_dir, k=25):
    """Aggregate device-plane event durations from the captured XPlane,
    grouped by op name.  Parses the protobuf directly with TF's xplane
    schema (the installed tensorboard_plugin_profile converter wants a
    pywrap symbol this TF build doesn't ship)."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:  # older/newer TF layouts
        from tensorflow.core.profiler.protobuf import xplane_pb2

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no xplane.pb under {trace_dir}")
    xs = xplane_pb2.XSpace()
    with open(paths[-1], "rb") as f:
        xs.ParseFromString(f.read())
    device = [p for p in xs.planes if "/device:" in p.name.lower()]
    rows = []
    for plane in device or xs.planes:
        meta = plane.event_metadata
        agg = {}
        for line in plane.lines:
            for ev in line.events:
                name = meta[ev.metadata_id].name
                d, n = agg.get(name, (0, 0))
                agg[name] = (d + ev.duration_ps, n + 1)
        total = sum(d for d, _ in agg.values()) or 1
        top = sorted(agg.items(), key=lambda kv: -kv[1][0])[:k]
        rows.append({
            "plane": plane.name,
            "total_us": round(total / 1e6, 1),
            "top_ops": [
                {"op": name, "us": round(d / 1e6, 1), "calls": n,
                 "pct": round(100.0 * d / total, 1)}
                for name, (d, n) in top],
        })
    return rows


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "gpt"
    trace_dir = sys.argv[2] if len(sys.argv) > 2 else f"/tmp/prof_{which}"
    dev = jax.devices()[0]
    print(f"backend: {dev.platform} ({getattr(dev, 'device_kind', '?')})",
          file=sys.stderr)

    step, state, batch = build(which)
    for _ in range(3):  # compile + warmup outside the trace
        state, m = step(state, batch)
    float(m["loss"])

    with jax.profiler.trace(trace_dir):
        for _ in range(5):
            state, m = step(state, batch)
        float(m["loss"])
    print(f"trace captured under {trace_dir}", file=sys.stderr)

    try:
        k = int(os.environ.get("DTTPU_PROFILE_TOPK", "25"))
        planes = top_ops_from_trace(trace_dir, k=k)
        out_path = os.path.join(trace_dir, f"op_stats_{which}.json")
        with open(out_path, "w") as f:
            json.dump(planes, f, indent=1)
        print(f"op stats written to {out_path}", file=sys.stderr)
        for plane in planes:
            print(json.dumps({"plane": plane["plane"],
                              "total_us": plane["total_us"]}))
            for row in plane["top_ops"][:10]:
                print(json.dumps(row))
    except Exception as e:  # noqa: BLE001 - parsing is best-effort
        print(f"xplane parse failed ({e}); raw trace kept at {trace_dir}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
