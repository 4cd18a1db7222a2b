#!/usr/bin/env python3
"""Run one cell of the benchmark and print the contract's line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about the cell is data: ``BENCHMARK.json`` names the cell's
configuration and traffic mix and the metrics it reports, the configuration
file names its model family, and the files under ``benchmark/`` are found by
those names (``harness/spec.py``).  One process
runs the cell, on the machine it is started on; JAX is imported here and
nowhere before.  Human text and the program's own output go to stderr;
stdout carries JSON lines only, the last of which is the result.  Without an
accelerator, or with fewer chips than the cell asks for, the exit code is 3
and no result is printed.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()          # process start, for setup_s

import argparse                    # noqa: E402
import importlib                   # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import shutil                      # noqa: E402
import sys                         # noqa: E402
import traceback                   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import common, device, spans, spec   # noqa: E402

EXIT_NO_ACCELERATOR = 3
DRIVERS = {"train": "train_driver", "serve": "serve_driver"}


def claim_stdout():
    """Keep the real stdout for the JSON lines and point fd 1 at stderr, so
    no library print or C++ log line can land among or after them."""
    sys.stdout.flush()
    out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    return out


def log(msg: str) -> None:
    print(f"[benchmark] {msg}", file=sys.stderr, flush=True)


def result_line(cell, outcome: common.Outcome, devices, trace: bool) -> dict:
    """The contract's last line.  ``--trace 0``: the cell's end-to-end
    metrics; ``--trace 1``: its per-layer metrics, the device's busy time
    and the breakdown.  ``compared`` comes last: every number that decided
    ``correct`` beside its limit."""
    described = device.describe(devices, outcome.memory["memory_peak_bytes"])
    line = {"correct": bool(outcome.correct),
            "attempted": int(outcome.attempted),
            "failed": int(outcome.failed)}
    if not trace:
        line["metrics"] = spec.select(cell.end_to_end, outcome.end_to_end)
        line["device"] = described
        line["compared"] = outcome.compared
        return line
    line["metrics"] = outcome.per_layer
    reduced = outcome.reduced
    described["busy_s"] = reduced.busy_s
    described["window_s"] = reduced.window_s
    line["device"] = described
    line["breakdown"] = {
        "device_ops": [[n, s] for n, s in reduced.device_ops],
        "idle_gaps": [[n, s] for n, s in reduced.idle_gaps]}
    line["compared"] = outcome.compared
    return line


def main(argv, out, *, root: str = ROOT, rehearse_on_cpu: bool = False,
         t0: float = T0) -> int:
    """Run the cell ``argv`` names; JSON lines go to ``out``.  ``root`` and
    ``rehearse_on_cpu`` are for the tests (a temporary benchmark on the CPU
    at a tiny size); the command has no such option."""
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)

    bench = spec.Benchmark(root)
    cell = bench.cell(args.workload)
    kind = cell.traffic["kind"]
    generator = bench.generator(cell)
    family = bench.family(cell)
    readers = {m["name"]: bench.layer_reader(m["name"])
               for m in cell.per_layer} if args.trace else {}

    if not rehearse_on_cpu:
        from distributed_tensorflow_tpu.utils import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
    try:
        devices = device.require(cell.chips, rehearse_on_cpu)
    except device.NoAccelerator as e:
        log(f"no result: {e}")
        return EXIT_NO_ACCELERATOR
    trace_dir = os.path.join(root, ".bench_trace", cell.name)
    shutil.rmtree(trace_dir, ignore_errors=True)

    def emit(obj: dict) -> None:
        print(json.dumps(obj, allow_nan=False), file=out, flush=True)

    driver = importlib.import_module(f"harness.{DRIVERS[kind]}")
    with device.CompileCounter() as compiles:
        run = common.Run(
            t0=t0, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), devices=devices,
            spans=spans.Spans(annotate=bool(args.trace)),
            compiles=compiles, emit=emit, trace_dir=trace_dir)
        emit({"workload": cell.name, "seed": args.seed,
              "seconds": args.seconds, "trace": args.trace,
              "family": family.name,
              "device_kind": devices[0].device_kind,
              "devices": len(devices)})
        outcome = driver.run(run, cell, generator, family)
    shutil.rmtree(trace_dir, ignore_errors=True)

    if args.trace:
        if outcome.reduced is None or not outcome.reduced.devices:
            raise RuntimeError("the traced segment recorded no device plane")
        emit({"kernel_s": outcome.reduced.kernel_s,
              "kernel_calls": outcome.reduced.kernel_calls})
        values = {name: read(outcome.record, outcome.reduced)
                  for name, read in readers.items()}
        outcome.per_layer = spec.select(cell.per_layer, values)
    emit(result_line(cell, outcome, devices, bool(args.trace)))
    for name, c in outcome.compared.items():      # stderr's last lines
        log(f"compared {name}: {c['value']!r} {c['holds']} {c['limit']!r}")
    return 0


if __name__ == "__main__":
    _out = claim_stdout()
    try:
        _code = main(sys.argv[1:], _out)
    except BaseException:          # noqa: BLE001 - no result line, exit != 0
        traceback.print_exc(file=sys.stderr)
        _code = 1
    _out.close()               # the result was the last thing written
    sys.exit(_code)
