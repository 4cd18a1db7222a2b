"""Drive a training cell: the trainer's own path, measured in readings.

``data.pipeline.Dataset`` -> ``prefetch_to_device`` -> ``TrainSession.run_step``
on the step ``train.make_custom_train_step(model.lm_loss_fn(), adamw)`` builds,
with the state placed by ``train.shard_train_state`` under the rules
``GPT.partition_rules`` gives for the configuration's mesh.  No checkpoint
directory: a save every N steps is a later cell's own metric.

A *reading* dispatches ``steps_per_reading`` steps and then fetches the loss
of the step that closed the reading BEFORE it (a fetch is what says the
device has finished a step).  So the device's queue never drains: it holds
one to two readings' steps, as in any loop that logs every N steps, and a
host that is late by less than a reading costs the trainer nothing.  A
reading thus runs from the instant one reading's last step was known complete
to the instant the next one's was: ``steps_per_reading`` steps of device
time.  Readings follow one another without a gap, warm-up into window.  The
window opens when the last warm-up reading ends and closes with the first
reading that ends past ``--seconds``; ``train_tokens_per_s`` is every token of
the steps completed in it over all of its time, stalls included.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

from . import common
from . import device as device_lib
from . import readings as readings_lib

# Sequences of the batch on which the program's loss and every token's loss
# are compared with the family's plain reference; the tolerances, with the
# measurements they were set from, are the family's.
CHECK_SEQUENCES = 2


def _state_shardings(abstract_state, mesh, rules):
    """Where ``train.shard_train_state`` will put each leaf, from shapes
    alone, so the state can be MADE sharded: a 1.5 B-parameter state is
    25 GB and cannot first exist on one 16 GB chip.  Whatever this gets
    wrong, ``shard_train_state`` moves afterwards; it is the authority."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    params = abstract_state.params
    params_sh = rules.tree_shardings(mesh, params)
    params_def = jax.tree_util.tree_structure(params)
    replicated = NamedSharding(mesh, P())

    def place(sub):
        if jax.tree_util.tree_structure(sub) == params_def:
            return jax.tree.map(
                lambda leaf, sh, p: sh if leaf.shape == p.shape else replicated,
                sub, params_sh, params)
        if isinstance(sub, dict):
            return {k: place(v) for k, v in sub.items()}
        if isinstance(sub, tuple) and hasattr(sub, "_fields"):
            return type(sub)(*(place(v) for v in sub))
        if isinstance(sub, (tuple, list)):
            return type(sub)(place(v) for v in sub)
        return jax.tree.map(lambda _: replicated, sub)

    return place(abstract_state)


def run(run: common.Run, cell, generator, family) -> common.Outcome:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_tpu import data, optim, parallel, train

    config, params_t = cell.config, cell.traffic["params"]
    deployment = config["train"]
    tol, reference = family.TOLERANCES, family.reference
    mesh = parallel.make_mesh(dict(deployment["mesh"]), devices=run.devices)
    fsdp = mesh.shape.get("fsdp", 1) > 1
    model = family.build_model(config, mesh=mesh)
    optimizer = optim.adamw(deployment["learning_rate"])
    rules = model.partition_rules(fsdp=fsdp)
    batch, seq = params_t["global_batch"], params_t["seq_len"]
    steps_per_reading = params_t["steps_per_reading"]
    tokens_per_step = batch * seq

    # ---- weights and optimizer state: one jitted call from the seed, made
    # in place on the mesh
    def make_state(key):
        p = model.init(key)
        return train.TrainState.create(p, optimizer.init(p))

    key = common.prng_key(run.seed)
    shardings = _state_shardings(jax.eval_shape(make_state, key), mesh, rules)
    state = jax.jit(make_state, out_shardings=shardings)(key)
    state = train.shard_train_state(state, mesh, rules)
    jax.block_until_ready(state)
    setup_marks = [("start_to_state", run.now())]
    step = train.make_custom_train_step(
        model.lm_loss_fn(), optimizer,
        grad_clip_norm=deployment["grad_clip_norm"])

    # ---- inputs: a host array from the seed, through the input pipeline
    tokens = generator.generate(params_t, run.seed,
                                family.vocab_size(config))
    dataset = data.Dataset([tokens], batch_size=batch, shuffle=True,
                           seed=run.seed % (2 ** 32))
    batch_sharding = NamedSharding(
        mesh, P(("data", "fsdp")) if fsdp else P("data"))
    batches = data.prefetch_to_device(
        ({"input_ids": x} for (x,) in dataset.epochs(10 ** 9)),
        size=2, sharding=batch_sharding)

    # ---- correct, part 1: the program's loss against the plain reference
    # on the cell's own weights, before the first step donates them
    sample = tokens[:CHECK_SEQUENCES]
    loss_fn = model.lm_loss_fn()

    def check(p, ids):
        system = loss_fn(p, (), {"input_ids": ids}, None, False)[0]
        system_tokens = reference.token_losses(
            family.forward_logits(model, p, ids[:, :-1]), ids[:, 1:])
        reference_tokens = reference.token_losses(
            reference.logits(p, ids[:, :-1], config), ids[:, 1:])
        return (system, jnp.mean(reference_tokens),
                jnp.max(jnp.abs(system_tokens - reference_tokens)))

    system_loss, reference_loss, token_loss_err = (
        float(x) for x in jax.jit(check)(state.params, sample))
    kernel = family.shard_witness(state.params)
    shard_devices = len({s.device for s in kernel.addressable_shards})
    shard_elems = math.prod(kernel.addressable_shards[0].data.shape)
    sharded_ok = (shard_devices == len(run.devices)
                  and (len(run.devices) == 1
                       or shard_elems < math.prod(kernel.shape)))
    setup_marks.append(("reference_check", run.now()))

    # ---- the loop: warm-up, window, traced segment all run this
    all_readings: List[readings_lib.Reading] = []
    losses: List[float] = []
    spans = run.spans
    try:
        with train.TrainSession(state, step) as session:
            def reading() -> None:
                nonlocal unfetched
                start = all_readings[-1].end if all_readings else run.now()
                for _ in range(steps_per_reading):
                    with spans.span("next_batch"):
                        device_batch = next(batches)
                    with spans.span("dispatch"):
                        metrics = session.run_step(device_batch)
                with spans.span("fetch"):
                    losses.append(float(unfetched["loss"]))
                unfetched = metrics
                all_readings.append(readings_lib.Reading(
                    start, run.now(), steps_per_reading * tokens_per_step))

            with spans.span("dispatch"):
                unfetched = session.run_step(next(batches))     # compiles
            for _ in range(params_t["warmup_readings"]):
                reading()
            warmup = len(all_readings)

            window_start = all_readings[-1].end
            window_end = window_start + run.seconds
            compiles_before = run.compiles.count
            setup_marks.append(("compile_and_warmup", window_start))
            while all_readings[-1].end < window_end:
                reading()
            compiles_in_window = run.compiles.count - compiles_before
            memory = device_lib.memory_report(run.devices)
            measured = all_readings[warmup:]
            window_losses = losses[-len(measured):]

            reduced = None
            if run.trace:
                reduced = common.traced_segment(
                    run, lambda: [reading()
                                  for _ in range(params_t["trace_readings"])])
    finally:
        batches.close()

    # ---- reduce
    rate = readings_lib.wall_rate(measured)
    reading_s = [r.seconds for r in measured]
    measured_end = measured[-1].end
    span_s = spans.seconds_by_name(window_start, measured_end)
    run.emit({
        "readings": readings_lib.summary(reading_s),
        "reading_seconds": reading_s,
        "reading_tokens_per_s": readings_lib.summary(
            [r.rate for r in measured]),
        "tokens_over_wall_tokens_per_s": rate,
        "median_of_readings_tokens_per_s": readings_lib.median_rate(measured),
        "slow_readings": readings_lib.slow(
            reading_s, readings_lib.SLOW_READING_FACTOR),
        "window_seconds": measured_end - window_start,
        "span_seconds_in_window": span_s,
        "first_loss": losses[0], "last_loss": losses[-1],
        "setup_parts_s": common.parts(run.t0, setup_marks),
    })

    run.emit({"memory": memory})

    checks = {
        "loss_matches_reference": (abs(system_loss - reference_loss)
                                   <= tol["loss"]),
        "token_losses_match_reference": token_loss_err <= tol["token_loss"],
        "window_losses_finite": bool(np.all(np.isfinite(window_losses))),
        "every_device_holds_a_param_shard": sharded_ok,
    }
    run.emit({"checks": checks, "system_loss": system_loss,
              "reference_loss": reference_loss, "loss_tol": tol["loss"],
              "token_loss_max_abs_err": token_loss_err,
              "token_loss_tol": tol["token_loss"],
              "param_shard_devices": shard_devices})

    record: Dict[str, Any] = {
        "kind": "train", "chips": len(run.devices),
        "platform": run.devices[0].platform,
        "device_kind": run.devices[0].device_kind,
        "compiles_in_window": compiles_in_window,
        "reading_seconds": reading_s,
        "steps_per_reading": steps_per_reading,
        "span_seconds": span_s, "window_s": measured_end - window_start,
        "tokens_per_s": rate,
        "flops_per_token": family.train_flops_per_token(config, seq),
        "traced_steps": (params_t["trace_readings"] * steps_per_reading
                         if run.trace else 0),
        "memory": memory,
    }
    failed = sum(1 for x in window_losses if not math.isfinite(x))
    compared = {
        "loss_abs_diff": {"value": abs(system_loss - reference_loss),
                          "limit": tol["loss"], "holds": "<="},
        "token_loss_max_abs_err": {"value": token_loss_err,
                                   "limit": tol["token_loss"], "holds": "<="},
        "window_losses_not_finite": {"value": failed, "limit": 0,
                                     "holds": "<="},
        "param_shard_devices": {"value": shard_devices,
                                "limit": len(run.devices), "holds": ">="},
    }
    return common.Outcome(
        correct=all(checks.values()),
        attempted=len(measured) * steps_per_reading,
        failed=failed,
        end_to_end={"train_tokens_per_s": rate,
                    "setup_s": window_start - run.t0},
        record=record, memory=memory, compared=compared, reduced=reduced)
