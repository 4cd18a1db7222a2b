"""``benchmark/run.py`` end to end on the CPU at a tiny size.

The tiny benchmark (``tiny_bench.py``) lives in a temporary directory and is
made of NEW files and entries only beside a link to the real ``benchmark/``:
that a later PR can add a cell — and a model family — without editing a file
is what these runs show.
"""
import io
import json
import os
import subprocess
import sys

import pytest

import bench_paths
import run as bench_run
import tiny_bench
from harness import trace as trace_lib

LAST_LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                  "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny_bench.build(
        tmp_path_factory.mktemp("tinybench_root"), "readings_count",
        "def read(record, trace):\n"
        "    return float(len(record['reading_seconds']))\n",
        {"unit": "count", "better": "higher", "source": "program_counter",
         "layer": "train loop", "moves": "train_tokens_per_s",
         "workloads": ["tiny.train", "rr.train"]})


def _run(root, workload, trace, seconds, monkeypatch):
    if not trace:
        def no_profiler(*a, **k):
            raise AssertionError("an untraced run started the profiler")
        monkeypatch.setattr(trace_lib, "start", no_profiler)
    out = io.StringIO()
    code = bench_run.main(
        ["--workload", workload, "--seed", str(2 ** 31 + 11),
         "--seconds", str(seconds), "--trace", str(trace)],
        out, root=root, rehearse_on_cpu=True)
    lines = [json.loads(line) for line in out.getvalue().splitlines()]
    assert code == 0
    return lines


CASES = [("tiny.train", 0, 2), ("tiny.train", 1, 1),
         ("tiny.chat", 0, 3), ("tiny.chat", 1, 2), ("rr.chat", 1, 2)]


@pytest.mark.parametrize("workload,trace,seconds", CASES)
def test_last_line_has_exactly_the_contracts_keys(tiny_root, monkeypatch,
                                                  workload, trace, seconds):
    lines = _run(tiny_root, workload, trace, seconds, monkeypatch)
    last = lines[-1]
    assert set(last) == LAST_LINE_KEYS | ({"breakdown"} if trace else set())
    assert list(last)[-1] == "compared" and last["compared"]
    for number in last["compared"].values():
        assert set(number) == {"value", "limit", "holds"}
    assert last["correct"] is True, lines[-2]
    assert last["attempted"] > 0 and last["failed"] == 0
    device = last["device"]
    assert set(device) == DEVICE_KEYS | (
        {"busy_s", "window_s"} if trace else set())
    assert device["platform"] == "cpu"
    train = workload.endswith(".train")
    assert device["count"] == (4 if train else 1)
    for value in last["metrics"].values():
        assert set(value) == {"value", "unit"}
    # the earlier line with every reading is printed in every run
    readings = next(line for line in lines if "readings" in line)
    assert readings["readings"]["count"] >= 3
    assert readings["median_of_readings_tokens_per_s"] > 0
    assert readings["tokens_over_wall_tokens_per_s"] > 0
    assert seconds <= readings["window_seconds"] < seconds + 1.0

    names = set(last["metrics"])
    if not trace:
        expected = ({"train_tokens_per_s", "setup_s"} if train else
                    {"serve_tokens_per_s", "ttft_p95_ms", "tpot_p50_ms",
                     "setup_s"})
        assert names == expected
        assert all(v["value"] > 0 for v in last["metrics"].values())
        # the end-to-end rate is all tokens over all time of the window
        rate = next(v for k, v in last["metrics"].items()
                    if k.endswith("tokens_per_s"))
        assert rate["value"] == readings["tokens_over_wall_tokens_per_s"]
        return
    assert 0 < device["busy_s"] <= device["window_s"]
    assert len(last["breakdown"]["device_ops"]) <= 10
    assert last["breakdown"]["device_ops"] and last["breakdown"]["idle_gaps"]
    kernels = next(line for line in lines if "kernel_s" in line)
    assert kernels["kernel_s"] == kernels["kernel_calls"] == {}   # on a CPU
    if train:
        # a CPU has no published peak and reports no memory: those two
        # readers find nothing to read and their metrics are left out
        assert names == {"compiles_in_window.train", "step_ms_p50",
                         "data_stall_pct", "slow_readings",
                         "collective_dev_pct",
                         "mosaic_dev_pct.train", "idle_pct.train",
                         "readings_count"}
        assert last["metrics"]["readings_count"]["value"] >= 3
        assert last["metrics"]["compiles_in_window.train"]["value"] == 0
    else:
        assert names == {"compiles_in_window.serve", "tick_ms_p50",
                         "slow_ticks", "prefix_hit_pct", "slot_occupancy_pct",
                         "mosaic_dev_pct.serve", "idle_pct.serve"}
        assert last["metrics"]["compiles_in_window.serve"]["value"] == 0
        assert 20 < last["metrics"]["prefix_hit_pct"]["value"] < 100


@pytest.mark.parametrize("workload,seconds", [("rr.train", 2),
                                              ("rr.chat", 3)])
def test_a_family_added_as_files_only_runs_to_correct(tiny_root, monkeypatch,
                                                      workload, seconds):
    """Family ``rmsrope`` (another block recipe, another parameter tree, a
    configuration with none of GPT-2's keys) exists as files under the
    temporary root alone: its family file, its plain reference, its
    configuration and its ``BENCHMARK.json`` entries."""
    lines = _run(tiny_root, workload, 0, seconds, monkeypatch)
    assert lines[0]["family"] == "rmsrope"
    last = lines[-1]
    assert last["correct"] is True, lines[-2]
    assert last["attempted"] > 0 and last["failed"] == 0
    checks = next(line for line in lines if "checks" in line)
    assert all(checks["checks"].values()), checks
    # held to the family's own tolerances, by its own reference
    limits = {k: v["limit"] for k, v in last["compared"].items()}
    if workload == "rr.train":
        assert (limits["loss_abs_diff"], limits["token_loss_max_abs_err"]) \
            == (1e-4, 1e-3)
        assert checks["param_shard_devices"] == 4
        assert checks["token_loss_max_abs_err"] < 1e-4
    else:
        assert (limits["logit_max_abs_err"],
                limits["token_agreement_share"]) == (1e-3, 0.9)
        assert checks["logit_max_abs_err"] < 1e-4
        assert checks["token_positions"] > 0
    # no file of the benchmark knows the family: it came as files
    assert not os.path.exists(
        os.path.join(bench_paths.BENCH_DIR, "families", "rmsrope.py"))
    for folder, _, names in os.walk(bench_paths.BENCH_DIR):
        for name in names:
            if name.endswith((".py", ".json")):
                with open(os.path.join(folder, name)) as f:
                    assert "rmsrope" not in f.read(), (folder, name)
    for name in ("families/rmsrope.py", "families/rmsrope_reference.py",
                 "configs/rmsrope-tiny.json"):
        assert os.path.isfile(os.path.join(tiny_root, "tiny", name))


def test_a_token_altered_where_it_is_produced_reads_not_correct(
        tiny_root, monkeypatch):
    """The rest of a run with the timed path broken underneath: every decode
    step of the scheduler's own program names the neighbouring id.  The run
    ends, prints its line, and the line says ``correct: false`` with the
    numbers that failed beside their limits."""
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.serve import pages
    real = pages.decode_paged_step

    def altered(*args, **kwargs):
        logits, cache = real(*args, **kwargs)
        return jnp.roll(logits, 1, axis=-1), cache

    monkeypatch.setattr(pages, "decode_paged_step", altered)
    lines = _run(tiny_root, "tiny.chat", 0, 2, monkeypatch)
    last = lines[-1]
    assert last["correct"] is False
    checks = next(line for line in lines if "checks" in line)["checks"]
    assert not checks["logits_match_reference"]
    assert not checks["emitted_tokens_match_reference_argmax"]
    assert checks["no_turn_failed"] and checks["hot_programs_were_dispatched"]
    compared = last["compared"]
    assert compared["logit_max_abs_err"]["value"] > \
        compared["logit_max_abs_err"]["limit"]
    assert compared["token_agreement_share"]["value"] < \
        compared["token_agreement_share"]["limit"]


def test_no_accelerator_means_no_result_and_a_nonzero_exit():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(bench_paths.BENCH_DIR, "run.py"),
         "--workload", "gpt2-medium.train_1k", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=bench_paths.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr
