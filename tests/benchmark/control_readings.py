#!/usr/bin/env python3
"""The control of ``correct``: the plain reference put in the program's
place at the next lower precision, read by the drivers' own comparisons.

    chiprun --chips 1 -- python3 tests/benchmark/control_readings.py \\
        gpt2-xl gpt2-medium --seeds 2147489201 1202 2147489203

The configurations state bf16 compute; the step below it is 8 bits.  The
control is the family's reference with every matrix of the weights rounded
to 8 bits and back, one scale per layer and output channel as 8-bit serving
does — ``int8`` (symmetric, 255 levels) or ``fp8`` (4 exponent and 3 mantissa
bits, the channel's largest weight scaled to 240) — and everything else as
the reference computes it.  Against the unrounded
reference on the same weights and ids it reads the numbers the drivers
compare: the serving probe's ``logit_max_abs_err`` (the last
``check_decode_positions + 1`` positions of a ``check_context_tokens``-long
context), the share of positions whose first choice agrees and the clear
positions that do not (over 2 x ``max_len`` ids), and training's
``loss_abs_diff`` and ``token_loss_max_abs_err`` (2 sequences of ``seq + 1``
ids).  A limit should lie under what the control reads; PERF.md has the
readings.  ``test_control.py`` keeps the same arithmetic at a toy size.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

MATRICES = ("kernel", "word", "position", "lm_head")


def rounded(params, how: str):
    """``params`` with every matrix rounded to 8 bits and back, in its own
    dtype.  Norm gains and biases stay as they are, as 8-bit paths keep
    them."""
    import jax
    import jax.numpy as jnp

    def one(path, leaf):
        if getattr(path[-1], "key", None) not in MATRICES:
            return leaf
        w = leaf.astype(jnp.float32)
        # one scale per output channel (and per layer of a stacked leaf)
        axes = tuple(range(1 if w.ndim >= 3 else 0, w.ndim - 1))
        top = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
        scale = jnp.where(top > 0, top, 1.0) / (240.0 if how == "fp8"
                                                 else 127.0)
        # ``reduce_precision`` and not a cast to a float8 type and back: the
        # TPU compiler folds that pair of converts away (seen on the v5e: the
        # cast "control" read 0.0 everywhere)
        q = (jax.lax.reduce_precision(w / scale, exponent_bits=4,
                                      mantissa_bits=3)
             if how == "fp8" else jnp.round(w / scale))
        return (q * scale).astype(leaf.dtype)

    return jax.jit(lambda p: jax.tree_util.tree_map_with_path(one, p))(params)


def readings(reference, config, params, control, probe_ids, turn_ids,
             train_ids, logit_tol: float, tail: int) -> dict:
    """The drivers' numbers with ``control`` in the program's place."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    got = reference.tail_logits(control, probe_ids, config, tail)
    want = reference.tail_logits(params, probe_ids, config, tail)
    values, best = reference.top2(params, turn_ids, config)
    _, chosen = reference.top2(control, turn_ids, config)
    clear = values[..., 0] - values[..., 1] > 2 * logit_tol

    def losses(p, ids):
        return reference.token_losses(
            reference.logits(p, ids[:, :-1], config), ids[:, 1:])

    both = jax.jit(lambda p, c, ids: (losses(p, ids), losses(c, ids)))
    ref_l, ctl_l = both(params, control, train_ids)
    return {
        "logit_max_abs_err": float(np.max(np.abs(got - want))),
        "token_agreement_share": float(np.mean(chosen == best)),
        "token_positions_clear": int(np.sum(clear)),
        "token_positions_clear_wrong": int(np.sum(clear & (chosen != best))),
        "loss_abs_diff": float(jnp.abs(jnp.mean(ctl_l) - jnp.mean(ref_l))),
        "token_loss_max_abs_err": float(jnp.max(jnp.abs(ctl_l - ref_l))),
    }


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import common, spec

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("configs", nargs="+")
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--seq", type=int, default=1024)
    args = parser.parse_args()
    if jax.devices()[0].platform == "cpu":
        print("no accelerator: nothing read", file=sys.stderr)
        return 3
    bench = spec.Benchmark(ROOT)
    for name in args.configs:
        cell = next(w["name"] for w in bench.doc["workloads"]
                    if w["config"] == name)
        cell = bench.cell(cell)
        family, config = bench.family(cell), cell.config
        serve = config.get("serve", {})
        context = serve.get("check_context_tokens", 200)
        tail = serve.get("check_decode_positions", 8) + 1
        model = family.build_model(config)
        vocab = family.vocab_size(config)
        for seed in args.seeds:
            # held as served (bf16); the reference widens each layer itself
            params = jax.jit(lambda key: jax.tree.map(
                lambda x: x.astype(jnp.bfloat16), model.init(key)))(
                    common.prng_key(seed))
            rng = np.random.default_rng(seed)
            probe = rng.integers(0, vocab, (1, context + tail - 1),
                                 dtype=np.int32)
            turns = rng.integers(0, vocab, (2, args.seq), dtype=np.int32)
            train = rng.integers(0, vocab, (2, args.seq + 1), dtype=np.int32)
            for how in ("int8", "fp8"):
                out = readings(family.reference, config, params,
                               rounded(params, how), probe, turns, train,
                               family.TOLERANCES["logit"], tail)
                print(json.dumps({"config": name, "seed": seed,
                                  "control": how, **out,
                                  "limits": family.TOLERANCES,
                                  "device": jax.devices()[0].device_kind}),
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
