"""Drive a serving cell: ``serve.Engine`` under a closed loop of clients.

The benchmark builds the engine with what defines the deployment
(``num_slots``, ``max_len``, weight type) and none of its tuning knobs, pumps
``Engine.step()`` itself on one thread, stamps token arrival in ``on_token``,
and submits a client's next turn when its reply is complete.  Set-up runs the
sessions until every client has finished a turn, so the window opens on a
steady mix of short and long histories; nothing is drained after it.

The window opens at the instant a tick returned and closes at the instant
the first tick past ``--seconds`` did.  ``serve_tokens_per_s`` is every token
stamped in it over all of its time; ``ttft_p95_ms`` is over turns whose first
token arrived in it and ``tpot_p50_ms`` over those whose last did.  The
tick-aligned readings of at least ``reading_seconds`` go to an earlier line.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import Any, Dict, List, Optional

from . import common
from . import device as device_lib
from . import readings as readings_lib
from . import reference

KERNEL_MARK = "tpu_custom_call"      # how a Mosaic kernel shows in HLO text
# bf16 weights and activations through 48 layers against float32: logits
# are O(1) (sigma ~0.8 with 0.02-normal weights at width 1600).  Measured at
# GPT-2-XL on the v5e: 0.050-0.065 max-abs over 9 positions x 50,257 logits
# in 17 runs (my chip runs, PR 24); PR 22 measured 0.0013 for the paged
# kernel alone at GPT-2-small.  2.3x the largest measured; a wrong position,
# mask or page mapping moves logits by O(1).
LOGIT_TOL = 0.15
# Share of ALL emitted tokens that must equal the reference's argmax: with
# random weights the top two logits are often closer than bf16 resolves (PR 22
# measured 0.91 agreement at GPT-2-small); a wrong engine agrees 1 in 50,257.
MIN_AGREEMENT = 0.6
DECODE_POSITIONS = 8
# The checked context is cut to one length for every seed: a length that
# moved with the seed would compile the reference anew in every run.
CHECK_PROMPT_TOKENS = 200


@dataclasses.dataclass
class TurnRecord:
    client: int
    prompt: Any
    budget: int
    submitted: float
    handle: Any = None
    first: Optional[float] = None
    last: Optional[float] = None
    tokens: int = 0
    rejected: bool = False


def _logit_check(model, params, sched_page_size, sched_chunk, use_kernel,
                 context, eps):
    """Prefill ``context`` through a paged cache in the scheduler's windows,
    decode ``DECODE_POSITIONS`` more tokens one at a time, by the ``GPT``
    methods the scheduler calls; compare the logits at the last prompt
    position and at every decoded one with the reference's full forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_tpu.serve import pages as pages_lib

    max_len = model.config.max_position
    pps = max_len // sched_page_size
    cache = pages_lib.init_paged_cache(model, 1, pps + 1, sched_page_size)
    row = jnp.arange(1, pps + 1, dtype=jnp.int32)
    w = sched_chunk
    plen = len(context) - DECODE_POSITIONS
    n_win = -(-plen // w)
    padded = np.zeros((n_win * w,), np.int32)
    padded[:plen] = context[:plen]

    window = jax.jit(
        lambda p, kv, toks, pos, head: model.decode_window_paged(
            p, kv, toks, row, pos, head=head, use_kernel=use_kernel),
        static_argnums=4)
    step = jax.jit(lambda p, c, tok: pages_lib.decode_paged_step(
        model, p, c, row[None], tok, jnp.ones((1,), bool),
        use_kernel=use_kernel))

    kv = cache["kv"]
    for i in range(n_win - 1):
        _, kv = window(params, kv, padded[None, i * w:(i + 1) * w],
                       np.int32(i * w), "none")
    logits, kv = window(params, kv, padded[None, (n_win - 1) * w:],
                        np.int32((n_win - 1) * w), "all")
    got = [np.asarray(logits[0, plen - 1 - (n_win - 1) * w], np.float32)]
    cache = {"kv": kv, "start_col": jnp.zeros((1,), jnp.int32),
             "write_col": jnp.full((1,), plen, jnp.int32),
             "positions": jnp.full((1,), plen, jnp.int32)}
    for j in range(DECODE_POSITIONS):
        lg, cache = step(params, cache,
                         jnp.asarray(context[plen + j:plen + j + 1]))
        got.append(np.asarray(lg[0], np.float32))
    want = np.asarray(jax.jit(
        lambda p, ids: reference.logits(p, ids, eps))(
            params, np.asarray(context)[None]))[0, plen - 1:]
    return float(np.max(np.abs(np.stack(got) - want)))


def _token_check(params, turns: List[TurnRecord], max_len, eps):
    """The tokens the engine emitted for ``turns`` against the reference's
    argmax -> (positions, positions that agree, clear positions, clear
    positions that disagree).  A position is clear where the reference's top
    two logits differ by more than twice the logit tolerance: closer than
    that, bf16 may pick the other."""
    import jax
    import numpy as np

    ids = np.zeros((len(turns), max_len), np.int32)
    spans = []
    for i, t in enumerate(turns):
        out = np.asarray(t.handle.tokens, np.int32)
        full = np.concatenate([t.prompt, out])[:max_len]
        ids[i, :len(full)] = full
        spans.append((len(t.prompt), out))

    def top2(p, ids):
        values, indices = jax.lax.top_k(reference.logits(p, ids, eps), 2)
        return values, indices[..., 0]

    values, best = (np.asarray(a) for a in jax.jit(top2)(params, ids))
    positions = agree = clear = clear_wrong = 0
    for i, (plen, out) in enumerate(spans):
        for j, token in enumerate(out):
            pos = plen - 1 + j
            same = int(best[i, pos] == token)
            positions += 1
            agree += same
            if values[i, pos, 0] - values[i, pos, 1] > 2 * LOGIT_TOL:
                clear += 1
                clear_wrong += 1 - same
    return positions, agree, clear, clear_wrong


def run(run: common.Run, cell, generator) -> common.Outcome:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_tpu import serve
    from distributed_tensorflow_tpu.models.gpt import GPT

    config, params_t = cell.config, cell.traffic["params"]
    deployment = config["serve"]
    eps = config["layer_norm_epsilon"]
    model = GPT(common.gpt_config(config))
    weight_dtype = jnp.dtype(deployment["weight_dtype"])
    params = jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype(weight_dtype), model.init(key)))(
            common.prng_key(run.seed))
    jax.block_until_ready(params)
    setup_marks = [("start_to_weights", run.now())]
    traffic = generator.make(params_t, run.seed, config["vocab_size"])
    first_turns = [c.next_turn(None) for c in traffic.clients]

    engine = serve.Engine(model, params, num_slots=deployment["num_slots"],
                          max_len=deployment["max_len"])
    sched = engine.scheduler
    num_slots = deployment["num_slots"]

    # ---- correct, part 1: logits through the paged cache vs the reference
    context = np.concatenate([
        first_turns[0].prompt[:CHECK_PROMPT_TOKENS],
        np.random.default_rng(run.seed).integers(
            0, config["vocab_size"], DECODE_POSITIONS, dtype=np.int32)])
    logit_err = _logit_check(model, params, sched.page_size,
                             sched.prefill_chunk, sched.use_paged_kernel,
                             context, eps)

    setup_marks.append(("engine_and_logit_check", run.now()))

    # ---- the closed loop
    spans = run.spans
    records: List[TurnRecord] = []
    current: List[Optional[TurnRecord]] = [None] * len(traffic.clients)
    finished_turns = [0] * len(traffic.clients)
    tick_tokens = [0]

    def submit(client: int, turn) -> None:
        rec = TurnRecord(client, turn.prompt, turn.max_new_tokens, run.now())

        def on_token(tokens, rec=rec):
            now = run.now()
            if rec.first is None:
                rec.first = now
            rec.last = now
            rec.tokens += len(tokens)
            tick_tokens[0] += len(tokens)

        try:
            with spans.span("submit"):
                rec.handle = engine.submit(turn.prompt, turn.max_new_tokens,
                                           on_token=on_token)
        except (serve.QueueFullError, ValueError):
            rec.rejected = True
        records.append(rec)
        current[client] = rec

    tick_ends: List[float] = []
    tick_counts: List[int] = []
    tick_seconds: List[float] = []
    occupancy: List[float] = []

    def tick() -> None:
        before = run.now()
        tick_tokens[0] = 0
        with spans.span("engine_step"):
            engine.step()
        now = run.now()
        tick_ends.append(now)
        tick_counts.append(tick_tokens[0])
        tick_seconds.append(now - before)
        occupancy.append(engine.stats().active / num_slots)
        for client, rec in enumerate(current):
            if rec.rejected or rec.handle.done:
                finished_turns[client] += 1
                reply = None if rec.rejected else rec.handle.tokens
                submit(client, traffic.clients[client].next_turn(reply))

    for client, turn in enumerate(first_turns):
        submit(client, turn)
    while min(finished_turns) < 1:          # fill: compiles, then steadies
        tick()

    compiles_before = run.compiles.count
    stats_before = engine.stats()
    window_start = tick_ends[-1]
    setup_marks.append(("compile_and_fill", window_start))
    first_window_tick = len(tick_ends) - 1
    while tick_ends[-1] < window_start + run.seconds:
        tick()
    window_end = tick_ends[-1]
    memory = device_lib.memory_report(run.devices)
    stats_after = engine.stats()
    last_window_tick = len(tick_ends)
    compiles_in_window = run.compiles.count - compiles_before
    window_records = list(records)

    reduced = None
    if run.trace:
        def traced():
            stop = run.now() + params_t["trace_seconds"]
            while run.now() < stop:
                tick()
        reduced = common.traced_segment(run, traced)

    # ---- reduce
    ends = tick_ends[first_window_tick:last_window_tick]
    counts = tick_counts[first_window_tick:last_window_tick]
    readings = readings_lib.tick_aligned(ends, counts,
                                         params_t["reading_seconds"])
    window_tokens = sum(counts[1:])     # of the ticks that ended in it
    rate = window_tokens / (window_end - window_start)

    def in_window(t: Optional[float]) -> bool:
        return t is not None and window_start <= t <= window_end

    ttft_ms = [1e3 * (r.first - r.submitted) for r in window_records
               if in_window(r.first)]
    tpot_ms = [1e3 * (r.last - r.first) / (r.tokens - 1)
               for r in window_records
               if r.handle is not None and r.handle.done and r.tokens > 1
               and in_window(r.last)]
    submitted = [r for r in window_records if in_window(r.submitted)]
    failed = [r for r in submitted
              if r.rejected or (r.handle.done and r.handle.status != "ok")]
    window_ticks = tick_seconds[first_window_tick + 1:last_window_tick]
    window_occupancy = occupancy[first_window_tick + 1:last_window_tick]
    prompt_tokens = sum(len(r.prompt) for r in submitted if not r.rejected)
    run.emit({
        "readings": readings_lib.summary([r.seconds for r in readings]),
        "reading_tokens_per_s": [r.rate for r in readings],
        "tokens_over_wall_tokens_per_s": rate,
        "median_of_readings_tokens_per_s": readings_lib.median_rate(readings),
        "window_seconds": window_end - window_start,
        "window_tokens": window_tokens,
        "ttft_ms": readings_lib.summary(ttft_ms),
        "tpot_ms": readings_lib.summary(tpot_ms),
        "tick_ms": readings_lib.summary([1e3 * s for s in window_ticks]),
        "slow_ticks_ms": [1e3 * s for s in readings_lib.slow(
            window_ticks, readings_lib.SLOW_TICK_FACTOR)],
        "turns_submitted": len(submitted), "turns_failed": len(failed),
        "prompt_tokens_submitted": prompt_tokens,
        "fill_ticks": first_window_tick + 1,
        "setup_parts_s": common.parts(run.t0, setup_marks),
    })

    # ---- correct, part 2: emitted tokens of two finished turns
    done = [r for r in records
            if r.handle is not None and r.handle.done
            and r.handle.status == "ok"][:2]
    positions, agree, clear, clear_wrong = _token_check(
        params, done, deployment["max_len"], eps)

    # ---- the three hot programs (the scheduler's own jitted callables at
    # the shapes of its call sites): each was dispatched (where jit says how
    # many programs it holds), what the chip's compiler made of it holds the
    # kernel, and its temporaries (printed beside the allocator's
    # ``bytes_reserved``, which is where the chip holds them).  Whether a
    # kernel is expected comes from the configuration, never from the
    # scheduler: a scheduler that fell back to the gather path is a failure.
    analysis_start = run.now()
    kernel_expected = (bool(deployment["paged_attention_kernel"])
                       and run.devices[0].platform == "tpu")
    kernel_in, dispatched, temp = {}, {}, 0
    for target in sched.graph_targets():
        compiled = target.fn.lower(*target.args).compile()
        kernel_in[target.name] = KERNEL_MARK in compiled.as_text()
        programs_held = getattr(target.fn, "_cache_size", None)
        dispatched[target.name] = programs_held is None or programs_held() >= 1
        temp = max(temp, device_lib.temp_bytes(compiled) or 0)
    run.emit({"memory": memory, "largest_hot_program_temp_bytes": temp,
              "program_analysis_seconds": run.now() - analysis_start})

    checks = {
        "logits_match_reference": logit_err <= LOGIT_TOL,
        "emitted_tokens_match_reference_argmax": (
            clear_wrong == 0 and positions > 0
            and agree >= MIN_AGREEMENT * positions),
        "hot_programs_were_dispatched": all(dispatched.values()),
        "kernel_in_every_hot_program_as_configured": (
            bool(sched.use_paged_kernel) == kernel_expected
            and all(present == kernel_expected
                    for present in kernel_in.values())),
        "no_turn_failed": not failed,
        "enough_readings": len(readings) >= 1 and bool(ttft_ms)
        and bool(tpot_ms),
    }
    run.emit({"checks": checks, "logit_max_abs_err": logit_err,
              "logit_tol": LOGIT_TOL, "token_positions": positions,
              "token_positions_agree": agree, "token_positions_clear": clear,
              "token_positions_clear_wrong": clear_wrong,
              "use_paged_kernel": bool(sched.use_paged_kernel),
              "kernel_expected": kernel_expected,
              "kernel_in_program": kernel_in,
              "page_size": sched.page_size,
              "prefill_chunk": sched.prefill_chunk,
              "tick_steps": sched.tick_steps})

    record: Dict[str, Any] = {
        "kind": "serve", "chips": len(run.devices),
        "platform": run.devices[0].platform,
        "device_kind": run.devices[0].device_kind,
        "compiles_in_window": compiles_in_window,
        "tick_seconds": window_ticks,
        "occupancy": window_occupancy,
        "prefix_tokens_reused": (stats_after.prefix_tokens_reused_total
                                 - stats_before.prefix_tokens_reused_total),
        "prompt_tokens_submitted": prompt_tokens,
        "ttft_ms": ttft_ms,
        "memory": memory,
    }
    return common.Outcome(
        correct=all(checks.values()),
        attempted=len(submitted), failed=len(failed),
        end_to_end={
            "serve_tokens_per_s": rate,
            "ttft_p95_ms": readings_lib.nearest_rank(ttft_ms, 95),
            "tpot_p50_ms": (statistics.median(tpot_ms) if tpot_ms else None),
            "setup_s": window_start - run.t0},
        record=record, memory=memory, reduced=reduced)
