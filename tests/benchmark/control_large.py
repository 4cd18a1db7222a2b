#!/usr/bin/env python3
"""``control_readings.py`` for a serving configuration whose weights fill
most of a chip: the same control (every matrix rounded to 8 bits and back,
``control_readings.rounded``), read by the same comparisons, holding ONE copy
of the weights at a time.

    chiprun --chips 1 -- python3 tests/benchmark/control_large.py \\
        longcat-flash-chat --seeds 2147489201 1202 2147489203

``control_readings.py`` keeps the served weights and their rounded copy side
by side (20.7 GB for 5.17 B parameters in bf16).  Here the reference reads
the unrounded weights first, the rounding then DONATES them (the copy takes
their place), and the weights are made again from the seed for the second
control.  Serving's numbers only: ``logit_max_abs_err`` over the last
``check_decode_positions + 1`` positions of a ``check_context_tokens``-long
context, the share of positions whose first choice agrees and the clear
positions that do not (2 x ``--seq`` ids).

With ``--picks`` it also reads, for a family whose reference has a ``route``
(an expert layer), the share of (position, expert layer) pairs at which the
PROGRAM's full forward in the served precision picks another set of experts
than the float32 reference does, and the share of single picks that differ:
what a router pick that flips on rounding is, which the logit limit has to
carry.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "benchmark"),
           os.path.dirname(os.path.abspath(__file__))):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def pick_differences(model, family, config, params, ids):
    """(pairs of position and expert layer, pairs whose pick SETS differ,
    single picks of the program's that the reference did not make)."""
    import jax
    import numpy as np
    from distributed_tensorflow_tpu.ops import moe as moe_lib

    reference = family.reference
    seen = {"program": [], "reference": []}
    real_route, real_ref = moe_lib.route_top_k, reference.route

    def program_route(*args, **kw):
        choice, weight = real_route(*args, **kw)
        seen["program"].append(choice)
        return choice, weight

    def reference_route(*args, **kw):
        choice, weight = real_ref(*args, **kw)
        seen["reference"].append(choice)
        return choice, weight

    def both(p, row):
        moe_lib.route_top_k, reference.route = program_route, reference_route
        try:
            model.apply(p, row[None])
            with jax.default_matmul_precision("highest"):
                reference._hidden_row(p, row, config)
        finally:
            moe_lib.route_top_k, reference.route = real_route, real_ref
        return list(seen["program"]), list(seen["reference"])

    got, want = jax.jit(both)(params, ids)
    pairs = differ = picks = wrong = 0
    for mine, theirs in zip(got, want):
        mine, theirs = np.sort(np.asarray(mine)), np.sort(np.asarray(theirs))
        pairs += mine.shape[0]
        differ += int(np.sum(np.any(mine != theirs, axis=-1)))
        picks += mine.size
        wrong += sum(len(set(a) - set(b)) for a, b in zip(mine, theirs))
    return pairs, differ, picks, wrong


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import control_readings
    from harness import common, spec

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("config")
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--seq", type=int, default=1024)
    parser.add_argument("--picks", action="store_true")
    args = parser.parse_args()
    if jax.devices()[0].platform == "cpu":
        print("no accelerator: nothing read", file=sys.stderr)
        return 3
    bench = spec.Benchmark(ROOT)
    cell = bench.cell(next(w["name"] for w in bench.doc["workloads"]
                           if w["config"] == args.config))
    family, config = bench.family(cell), cell.config
    reference = family.reference
    serve = config["serve"]
    tail = serve["check_decode_positions"] + 1
    model = family.build_model(config)
    vocab = family.vocab_size(config)
    weight_dtype = jnp.dtype(serve["weight_dtype"])
    make = jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype(weight_dtype), model.init(key)))
    tol = family.TOLERANCES["logit"]
    for seed in args.seeds:
        rng = np.random.default_rng(seed)
        probe = rng.integers(0, vocab, (1, serve["check_context_tokens"]
                                        + tail - 1), dtype=np.int32)
        turns = rng.integers(0, vocab, (2, args.seq), dtype=np.int32)
        params = make(common.prng_key(seed))
        want = reference.tail_logits(params, probe, config, tail)
        values, best = reference.top2(params, turns, config)
        clear = values[..., 0] - values[..., 1] > 2 * tol
        line = {"config": args.config, "seed": seed,
                "device": jax.devices()[0].device_kind}
        if args.picks and hasattr(reference, "route"):
            pairs, differ, picks, wrong = pick_differences(
                model, family, config, params, probe[0])
            print(json.dumps(dict(
                line, pick_pairs=pairs, pick_sets_that_differ=differ,
                pick_sets_that_differ_share=differ / pairs,
                single_picks=picks, single_picks_that_differ=wrong,
                single_picks_that_differ_share=wrong / picks)), flush=True)
        for how in ("int8", "fp8"):
            # the rounded copy takes the weights' place
            control = _rounded_in_place(control_readings, params, how)
            del params
            got = reference.tail_logits(control, probe, config, tail)
            _, chosen = reference.top2(control, turns, config)
            del control
            print(json.dumps(dict(
                line, control=how,
                logit_max_abs_err=float(np.max(np.abs(got - want))),
                token_agreement_share=float(np.mean(chosen == best)),
                token_positions_clear=int(np.sum(clear)),
                token_positions_clear_wrong=int(np.sum(
                    clear & (chosen != best))),
                limits=family.TOLERANCES)), flush=True)
            if how == "int8":
                params = make(common.prng_key(seed))
    return 0


_ROUNDERS: dict = {}


def _rounded_in_place(control_readings, params, how: str):
    """``control_readings.rounded``'s arithmetic leaf by leaf, each leaf
    donated to its rounded copy.  ``rounded`` decides by a leaf's last key
    alone whether it is a matrix, so a leaf goes in under that key; one
    jitted function a key and control, so that leaves of one shape share a
    program."""
    import jax

    def leaf(path, x):
        key = getattr(path[-1], "key", "leaf")
        if (key, how) not in _ROUNDERS:
            _ROUNDERS[key, how] = jax.jit(
                lambda a: control_readings.rounded({key: a}, how)[key],
                donate_argnums=0)
        return _ROUNDERS[key, how](x)

    return jax.tree_util.tree_map_with_path(leaf, params)


if __name__ == "__main__":
    sys.exit(main())
