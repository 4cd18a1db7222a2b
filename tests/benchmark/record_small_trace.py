#!/usr/bin/env python3
"""Record the small trace kept beside the tests (run on the chip, by hand).

    chiprun --chips 1 -- python3 tests/benchmark/record_small_trace.py
    chiprun --chips 1 -- python3 tests/benchmark/record_small_trace.py \
        dttpu_small_double

A few calls of a tiny program — matmuls inside a ``fori_loop`` (so the trace
has a ``while`` that contains its body's operations) and one Pallas kernel (a
custom call) — under the harness's own spans and profiler settings.  Writes
``chiprun_out/small_trace/small_trace.xplane.pb`` (tens of KB) and prints
what the reducer makes of it; the numbers pinned in
``test_benchmark_trace.py`` are that output.  With a kernel name as its
argument the Pallas kernel carries that name, as the program's kernels carry
theirs (``dttpu_*``), and the file is ``named_kernel_trace.xplane.pb``: the
trace on which ``kernel_s`` has something to sum.
"""
import glob
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
from jax.experimental import pallas as pl               # noqa: E402

from harness import spans as spans_lib                  # noqa: E402
from harness import trace as trace_lib                  # noqa: E402


def _double_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


KERNEL_NAME = sys.argv[1] if len(sys.argv) > 1 else None
FILE_NAME = ("named_kernel_trace" if KERNEL_NAME else "small_trace") \
    + ".xplane.pb"


@jax.jit
def program(x):
    y = jax.lax.fori_loop(0, 4, lambda _, a: jnp.tanh(a @ a) * 0.5, x)
    return pl.pallas_call(
        _double_kernel, out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        name=KERNEL_NAME)(y)


def main() -> int:
    if jax.devices()[0].platform == "cpu":
        print("no accelerator: nothing recorded", file=sys.stderr)
        return 3
    out_dir = os.path.join(ROOT, "chiprun_out", "small_trace")
    work = os.path.join(out_dir, "work")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(work)
    x = jnp.ones((512, 512), jnp.bfloat16)
    program(x).block_until_ready()
    spans = spans_lib.Spans(annotate=True)
    trace_lib.start(work)
    with spans.span(trace_lib.WINDOW_SPAN):
        for _ in range(3):
            with spans.span("dispatch"):
                y = program(x)
            with spans.span("fetch"):
                float(y[0, 0])
    trace_lib.stop()
    src = glob.glob(os.path.join(work, "**", "*.xplane.pb"), recursive=True)[0]
    dst = os.path.join(out_dir, FILE_NAME)
    shutil.copy(src, dst)
    shutil.rmtree(work)
    trace = trace_lib.load(out_dir)
    reduced = trace_lib.reduce(trace)
    print(json.dumps({
        "bytes": os.path.getsize(dst),
        "devices": sorted(trace.devices),
        "ops": {d: len(ops) for d, ops in trace.devices.items()},
        "host_spans": sorted({s[0] for s in trace.host_spans}),
        "window_s": reduced.window_s, "busy_s": reduced.busy_s,
        "collective_s": reduced.collective_s,
        "custom_call_s": reduced.custom_call_s,
        "kernel_s": reduced.kernel_s, "kernel_calls": reduced.kernel_calls,
        "device_ops": reduced.device_ops, "idle_gaps": reduced.idle_gaps}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
