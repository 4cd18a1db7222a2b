#!/usr/bin/env python3
"""The device queue's account (``obs/trace.py``) on the machine it runs on:
what its polls cost, and whether ``device.ready_wait`` shares the device
trace's clock.

    python scripts/device_queue_check.py [--out device_queue_capture]
    JAX_PLATFORMS=cpu python scripts/device_queue_check.py --tiny   # wiring

Part 1 times loops of a few thousand calls: the null span and
``dispatched()`` with no tracer; a recorded span with no dispatch
registered, while the newest dispatch still runs (each enter and exit asks
its output ``is_ready()``) and once it has finished; ``dispatched()`` with a
tracer; ``is_ready()`` alone; a generation-0 collection with and without the
collector's callback.

Part 2 serves a GPT-2-small-width engine (8 slots) with a tracer active
under a profiler capture, keeps the capture under ``--out``, and for every
``dttpu:device.ready_wait`` annotation finds the program it waited for (the
decode program under a ``serve.decode_fetch``, the admitting window program
under a ``serve.first_token_read``) on the device's ``XLA Modules`` line:
the last such program to end before the annotation did.  The difference of
the two ends says whether the spans and the device share one clock.

One JSON line on stdout.  Off the TPU the numbers describe the CPU.
"""
from __future__ import annotations

import argparse
import gc
import glob
import itertools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

N = 5000


def _per_call_us(fn, n: int = N) -> float:
    calls = itertools.repeat(None, n)
    t0 = time.perf_counter()
    for _ in calls:
        fn()
    return (time.perf_counter() - t0) / n * 1e6


def poll_costs(tiny: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.obs import trace as trace_lib

    width, steps = (256, 400) if tiny else (2048, 4000)
    w = jnp.ones((width, width), jnp.bfloat16)

    @jax.jit
    def long_program(x):
        return jax.lax.fori_loop(0, steps, lambda i, y: (y @ w) * 0.5, x)

    long_program(w).block_until_ready()            # compiled
    out: dict = {}

    def span():
        with trace_lib.span("probe"):
            pass

    trace_lib.deactivate()
    ready = jnp.zeros(()) + 1
    ready.block_until_ready()
    out["null_span_us"] = _per_call_us(span)
    out["dispatched_no_tracer_us"] = _per_call_us(
        lambda: trace_lib.dispatched("probe", ready))
    tracer = trace_lib.activate(trace_lib.Tracer())
    try:
        out["recorded_span_no_dispatch_us"] = _per_call_us(span)
        busy = long_program(w)
        trace_lib.dispatched("long", busy)
        out["recorded_span_newest_busy_us"] = _per_call_us(span)
        out["is_ready_busy_us"] = _per_call_us(busy.is_ready)
        still_busy = not busy.is_ready()
        busy.block_until_ready()
        out["recorded_span_newest_ready_us"] = _per_call_us(span)
        out["is_ready_ready_us"] = _per_call_us(busy.is_ready)
        out["dispatched_tracer_us"] = _per_call_us(
            lambda: trace_lib.dispatched("probe", ready))
        out["busy_for_the_whole_loop"] = still_busy
        junk = [[] for _ in range(20000)]          # a gen-0 worth collecting
        out["gc0_with_callback_us"] = _per_call_us(
            lambda: gc.collect(0), 500)
    finally:
        trace_lib.deactivate(tracer)
    out["gc0_without_callback_us"] = _per_call_us(lambda: gc.collect(0), 500)
    del junk
    out["is_ready_per_poll_us"] = out["is_ready_busy_us"]
    out["poll_cost_us"] = (out["recorded_span_newest_busy_us"]
                           - out["recorded_span_no_dispatch_us"]) / 2
    return out


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return []


def clock_check(out_dir: str, tiny: bool) -> dict:
    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from distributed_tensorflow_tpu import serve
    from distributed_tensorflow_tpu.models.gpt import GPT, GPTConfig
    from distributed_tensorflow_tpu.obs import trace as trace_lib

    config = (GPTConfig(dropout_rate=0.0, hidden_size=128, num_layers=2,
                        num_heads=2, intermediate_size=512, vocab_size=512,
                        max_position=256) if tiny
              else GPTConfig(dropout_rate=0.0))
    model = GPT(config)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    longest = config.max_position // 3
    tracer = trace_lib.activate(trace_lib.Tracer())
    try:
        engine = serve.Engine(model, params, num_slots=8,
                              max_len=config.max_position)

        def wave(n):
            for _ in range(n):
                engine.submit(rng.integers(0, config.vocab_size, int(
                    rng.integers(8, longest))).astype(np.int32),
                    int(rng.integers(16, 64)))
            engine.drain()

        wave(8)                                    # compiles, warms
        os.makedirs(out_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(out_dir, profiler_options=options)
        try:
            wave(16)
        finally:
            jax.profiler.stop_trace()
        queue = dict(tracer.device_queue)
    finally:
        trace_lib.deactivate(tracer)
    (path,) = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                        recursive=True)
    profile = ProfileData.from_file(path)
    host, modules = [], []
    for plane in profile.planes:
        if plane.name == "/host:CPU":
            host += [e for line in plane.lines for e in (
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                for ev in line.events) if e[0].startswith("dttpu:")]
        elif plane.name.startswith("/device:") and plane.name[
                len("/device:"):].split(":")[-1].isdigit():
            modules += _events(plane, "XLA Modules")
    reads = [e for e in host if e[0] in ("dttpu:serve.decode_fetch",
                                         "dttpu:serve.first_token_read")]
    diffs_us, unmatched = [], 0
    for name, start, end in host:
        if name != "dttpu:" + trace_lib.READY_WAIT:
            continue
        parent = next((r for r in reads if r[1] <= start and end <= r[2]),
                      None)
        want = ("paged_tick" if parent and "decode_fetch" in parent[0]
                else "paged_last_admit")
        ended = [m for m in modules if want in m[0] and m[2] <= end + 1e6]
        if not ended:
            unmatched += 1
            continue
        diffs_us.append((end - max(m[2] for m in ended)) / 1e3)
    return {
        "capture": os.path.relpath(path, ROOT),
        "ready_waits": len(diffs_us), "unmatched": unmatched,
        "device_programs": len(modules),
        "end_after_program_end_us": {
            "min": min(diffs_us, default=None),
            "median": statistics.median(diffs_us) if diffs_us else None,
            "max": max(diffs_us, default=None)},
        "within_1ms": sum(abs(d) <= 1000 for d in diffs_us),
        "device_queue": queue}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--out", default=os.path.join(
        ROOT, "device_queue_capture"),
                        help="where the profiler capture is kept")
    parser.add_argument("--tiny", action="store_true",
                        help="small programs and model: a CPU wiring check")
    args = parser.parse_args(argv)
    import jax
    device = jax.devices()[0]
    line = {"platform": device.platform, "device_kind": device.device_kind,
            "poll": poll_costs(args.tiny),
            "clock": clock_check(args.out, args.tiny)}
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
