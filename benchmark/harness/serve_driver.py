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

KERNEL_MARK = "tpu_custom_call"      # how a Mosaic kernel shows in HLO text


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


def _token_check(family, config, params, turns: List[TurnRecord], max_len,
                 logit_tol: float):
    """The tokens the engine emitted for ``turns`` against the reference's
    argmax -> (positions, positions that agree, clear positions, clear
    positions that disagree).  A position is clear where the reference's top
    two logits differ by more than twice the logit tolerance: closer than
    that, bf16 may pick the other."""
    import numpy as np

    ids = np.zeros((len(turns), max_len), np.int32)
    spans = []
    for i, t in enumerate(turns):
        out = np.asarray(t.handle.tokens, np.int32)
        full = np.concatenate([t.prompt, out])[:max_len]
        ids[i, :len(full)] = full
        spans.append((len(t.prompt), out))

    values, best = family.reference.top2(params, ids, config)
    positions = agree = clear = clear_wrong = 0
    for i, (plen, out) in enumerate(spans):
        for j, token in enumerate(out):
            pos = plen - 1 + j
            same = int(best[i, pos] == token)
            positions += 1
            agree += same
            if values[i, pos, 0] - values[i, pos, 1] > 2 * logit_tol:
                clear += 1
                clear_wrong += 1 - same
    return positions, agree, clear, clear_wrong


def _computed_work(family, config, turns: List[TurnRecord],
                   prompt_tokens: int, reused: int, window_tokens: int,
                   first_tokens: int) -> Dict[str, float]:
    """What the engine computed in the window, for ``serve_mfu_pct``: the
    prompt tokens it prefilled (submitted less those the prefix cache
    served), the tokens its decode steps produced (delivered less each
    turn's first, which the prefill's last position yields), how many
    positions one of each attends over on average, and the family's
    operations for such a token.  The driver knows the reuse only summed
    over turns and takes each turn's as that share of its prompt, at its
    front; a turn of prompt ``p``, reuse ``r`` and ``n`` tokens prefills
    ``p - r`` tokens attending ``(r + p + 1) / 2`` positions on average and
    decodes ``n - 1`` attending ``p + n / 2``."""
    share = reused / prompt_tokens if prompt_tokens else 0.0
    pre_n = pre_ctx = dec_n = dec_ctx = 0.0
    for t in turns:
        p = len(t.prompt)
        r = share * p
        pre_n += p - r
        pre_ctx += (p - r) * (r + p + 1) / 2
        if t.tokens > 1:
            dec_n += t.tokens - 1
            dec_ctx += (t.tokens - 1) * (p + t.tokens / 2)
    prefill_context = pre_ctx / pre_n if pre_n else 0.0
    decode_context = dec_ctx / dec_n if dec_n else 0.0
    bare = family.serve_flops_per_token(config, 0, head=False)
    return {
        "prefill_tokens": prompt_tokens - reused,
        "decode_tokens": window_tokens - first_tokens,
        "first_tokens": first_tokens,
        "prefill_context": prefill_context,
        "decode_context": decode_context,
        "flops_per_prefill_token": family.serve_flops_per_token(
            config, prefill_context, head=False),
        "flops_per_decode_token": family.serve_flops_per_token(
            config, decode_context, head=True),
        "flops_per_head": family.serve_flops_per_token(
            config, 0, head=True) - bare,
    }


def run(run: common.Run, cell, generator, family) -> common.Outcome:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_tpu import serve

    config, params_t = cell.config, cell.traffic["params"]
    deployment = config["serve"]
    tol = family.TOLERANCES
    vocab = family.vocab_size(config)
    model = family.build_model(config)
    weight_dtype = jnp.dtype(deployment["weight_dtype"])
    params = jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype(weight_dtype), model.init(key)))(
            common.prng_key(run.seed))
    jax.block_until_ready(params)
    setup_marks = [("start_to_weights", run.now())]
    traffic = generator.make(params_t, run.seed, vocab)
    first_turns = [c.next_turn(None) for c in traffic.clients]

    engine = serve.Engine(model, params, num_slots=deployment["num_slots"],
                          max_len=deployment["max_len"])
    sched = engine.scheduler
    num_slots = deployment["num_slots"]

    # ---- correct, part 1: logits through the paged cache (the family's
    # probe, by the methods the scheduler calls) vs the reference.  The
    # checked context has one length for every seed, the configuration's: a
    # length that moved with the seed would compile the reference anew in
    # every run.
    decode_positions = deployment["check_decode_positions"]
    context = np.concatenate([
        first_turns[0].prompt[:deployment["check_context_tokens"]],
        np.random.default_rng(run.seed).integers(
            0, vocab, decode_positions, dtype=np.int32)])
    logit_err = float(np.max(np.abs(
        family.serve_probe(model, params, sched, context, decode_positions)
        - family.reference.tail_logits(params, context[None], config,
                                       decode_positions + 1)[0])))

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
        family, config, params, done, deployment["max_len"], tol["logit"])

    # ---- the three hot programs (the scheduler's own jitted callables at
    # the shapes of its call sites): each was dispatched (where jit says how
    # many programs it holds), what the chip's compiler made of it holds the
    # kernel, and its temporaries (printed beside the allocator's
    # ``bytes_reserved``, which is where the chip holds them).  Whether a
    # kernel is expected comes from the configuration, never from the
    # scheduler: a scheduler that fell back to the gather path is a failure.
    analysis_start = run.now()
    on_tpu = run.devices[0].platform == "tpu"
    kernel_in, kernel_expected, dispatched, temp = {}, {}, {}, 0
    for target in sched.graph_targets():
        compiled = target.fn.lower(*target.args).compile()
        kernel_in[target.name] = KERNEL_MARK in compiled.as_text()
        kernel_expected[target.name] = on_tpu and bool(
            family.kernel_expected(config, target.name))
        programs_held = getattr(target.fn, "_cache_size", None)
        dispatched[target.name] = programs_held is None or programs_held() >= 1
        temp = max(temp, device_lib.temp_bytes(compiled) or 0)
    run.emit({"memory": memory, "largest_hot_program_temp_bytes": temp,
              "program_analysis_seconds": run.now() - analysis_start})

    checks = {
        "logits_match_reference": logit_err <= tol["logit"],
        "emitted_tokens_match_reference_argmax": (
            clear_wrong == 0 and positions > 0
            and agree >= tol["min_agreement"] * positions),
        "hot_programs_were_dispatched": all(dispatched.values()),
        "kernel_in_every_hot_program_as_configured": (
            bool(sched.use_paged_kernel) == any(kernel_expected.values())
            and kernel_in == kernel_expected),
        "no_turn_failed": not failed,
        "enough_readings": len(readings) >= 1 and bool(ttft_ms)
        and bool(tpot_ms),
    }
    run.emit({"checks": checks, "logit_max_abs_err": logit_err,
              "logit_tol": tol["logit"], "token_positions": positions,
              "token_positions_agree": agree, "token_positions_clear": clear,
              "token_positions_clear_wrong": clear_wrong,
              "use_paged_kernel": bool(sched.use_paged_kernel),
              "kernel_expected": kernel_expected,
              "kernel_in_program": kernel_in,
              "page_size": sched.page_size,
              "prefill_chunk": sched.prefill_chunk,
              "tick_steps": sched.tick_steps})

    reused = (stats_after.prefix_tokens_reused_total
              - stats_before.prefix_tokens_reused_total)
    record: Dict[str, Any] = {
        "kind": "serve", "chips": len(run.devices),
        "platform": run.devices[0].platform,
        "device_kind": run.devices[0].device_kind,
        "compiles_in_window": compiles_in_window,
        "tick_seconds": window_ticks,
        "occupancy": window_occupancy,
        "prefix_tokens_reused": reused,
        "prompt_tokens_submitted": prompt_tokens,
        "ttft_ms": ttft_ms,
        "window_s": window_end - window_start,
        "computed": _computed_work(
            family, config, [r for r in submitted if not r.rejected],
            prompt_tokens, reused, window_tokens, len(ttft_ms)),
        "memory": memory,
    }
    compared = {
        "logit_max_abs_err": {"value": logit_err, "limit": tol["logit"],
                              "holds": "<="},
        "token_agreement_share": {
            "value": agree / positions if positions else 0.0,
            "limit": tol["min_agreement"], "holds": ">="},
        "token_positions_clear_wrong": {"value": clear_wrong, "limit": 0,
                                        "holds": "<="},
        "turns_failed": {"value": len(failed), "limit": 0, "holds": "<="},
    }
    return common.Outcome(
        correct=all(checks.values()),
        attempted=len(submitted), failed=len(failed),
        end_to_end={
            "serve_tokens_per_s": rate,
            "ttft_p95_ms": readings_lib.nearest_rank(ttft_ms, 95),
            "tpot_p50_ms": (statistics.median(tpot_ms) if tpot_ms else None),
            "setup_s": window_start - run.t0},
        record=record, memory=memory, compared=compared, reduced=reduced)
