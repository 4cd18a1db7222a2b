"""Decode operating-point ladder: tokens/s/chip across batch x length.

One decode number (7,017 tok/s at batch 64 / seq 256) says nothing about
where it sits on the throughput curve.  This sweep
measures greedy KV-cache generate on the gpt bench model over a
batch ladder at two sequence lengths, printing a table plus one JSON
line per cell — so the record shows the achievable ceiling (decode is
HBM-bandwidth-bound: throughput should rise with batch until the cache
traffic saturates, then flatten).

Run on TPU:        python scripts/decode_ladder.py
Full-int8 cells (int8 weights + int8 KV cache — the serving ceiling):
                   python scripts/decode_ladder.py int8
CPU wiring check:  same commands under
                   JAX_PLATFORMS=cpu DTTPU_ABLATION_SMOKE=1
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# "0"/"false"/empty = off — same parse as mfu_ablation.py
SMOKE = os.environ.get("DTTPU_ABLATION_SMOKE", "").lower() \
    not in ("", "0", "false")


def main() -> int:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from distributed_tensorflow_tpu.models.gpt import GPT, GPTConfig

    # "int8" argv: the FULL-int8 serving point (int8 weights in HBM +
    # int8 KV cache) over the same cells — decode is bandwidth-bound, so
    # this is the achievable serving ceiling the fp ladder can't show.
    # Unknown args fail FAST: a typo must not burn an 1800s queue slot
    # re-measuring the fp ladder mislabeled.
    extra = [a for a in sys.argv[1:] if a != "int8"]
    if extra:
        print(f"unknown argument(s) {extra}; only 'int8' is accepted",
              file=sys.stderr)
        return 1
    int8 = "int8" in sys.argv[1:]
    dev = jax.devices()[0]
    print(f"device: {dev.platform} ({dev.device_kind})"
          + (" [full-int8]" if int8 else ""), file=sys.stderr)

    # the bench.py gpt model (GPT-2-small) so cells are comparable to the
    # recorded gpt_decode row; SMOKE shrinks like bench.py's smoke config
    if SMOKE:
        cfgs = {64: GPTConfig(vocab_size=512, hidden_size=128,
                              num_layers=2, num_heads=2,
                              intermediate_size=512, max_position=64,
                              dtype=jnp.bfloat16, dropout_rate=0.0)}
        batches = [2, 4]
    else:
        cfgs = {seq: GPTConfig(vocab_size=50257, hidden_size=768,
                               num_layers=12, num_heads=12,
                               intermediate_size=3072, max_position=seq,
                               dtype=jnp.bfloat16, dropout_rate=0.0)
                for seq in (256, 1024)}
        batches = [1, 8, 16, 32, 64, 128, 256]

    prompt_len = 8
    rng = np.random.default_rng(0)
    rows = []
    if int8:
        from distributed_tensorflow_tpu.ops import quant
        prep = quant.dequantize_tree          # runs INSIDE the jit
    else:
        prep = lambda t: t  # noqa: E731 - identity for the fp cells
    for seq, config in cfgs.items():
        if int8:
            config = dataclasses.replace(config, kv_cache_dtype="int8")
        model = GPT(config)
        params = model.init(jax.random.PRNGKey(0))
        if int8:
            params = quant.quantize_tree(params)
        new_tokens = (16 if SMOKE else seq - prompt_len)
        # One wrapper per config (DT105 fix: was rebuilt per batch rung,
        # discarding the compile cache); each batch shape still traces
        # once, but inside the SAME cache.  The per-config construction
        # that remains is inherent — model/new_tokens change the program.
        gen = jax.jit(lambda p, ids, m=model, nt=new_tokens, s=seq:  # dtlint: disable=DT105
                      m.generate(prep(p), ids, max_new_tokens=nt,
                                 temperature=0.0, max_len=s))
        for batch in batches:
            prompt = rng.integers(0, config.vocab_size,
                                  (batch, prompt_len)).astype(np.int32)
            try:
                np.asarray(gen(params, prompt))      # compile + warmup
                dt = None
                for _ in range(3):                   # best-of-3 windows
                    t0 = time.perf_counter()
                    out = gen(params, prompt)
                    np.asarray(out)                  # value fetch
                    w = time.perf_counter() - t0
                    dt = w if dt is None else min(dt, w)
            except Exception as e:                   # OOM rung: report, go on
                msg = str(e).splitlines()[0][:100]
                print(f"seq {seq} batch {batch}: FAILED ({msg})",
                      flush=True)
                continue
            rate = batch * new_tokens / dt
            rows.append(dict(seq_len=seq, batch=batch,
                             new_tokens=new_tokens,
                             tokens_per_sec_per_chip=round(rate, 1),
                             ms_per_token=round(dt * 1e3 / new_tokens, 3)))
            print(f"seq {seq} batch {batch:4d}: {rate:10,.0f} tok/s/chip "
                  f"({dt * 1e3 / new_tokens:7.3f} ms/token)", flush=True)

    name = "gpt_decode_ladder_int8" if int8 else "gpt_decode_ladder"
    for r in rows:
        print(json.dumps({"metric": name, **r}))
    if not rows:
        # every rung failed: say so loudly AND fail the queue step — a
        # silent rc 0 here would let the watcher log QUEUE-COMPLETE with
        # the ladder evidence missing
        print(json.dumps({"metric": name + "_FAILED", "value": 0.0}))
        return 1
    best = max(rows, key=lambda r: r["tokens_per_sec_per_chip"])
    print(json.dumps({"metric": name + "_best", **best}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
