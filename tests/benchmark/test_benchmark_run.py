"""``benchmark/run.py`` end to end on the CPU at a tiny size.

The tiny benchmark lives in a temporary directory and is made of NEW files
and entries only — a configuration, two traffic mixes and a per-layer metric
— beside a link to the real ``benchmark/``: that a later PR can add a cell
without editing a file is what these runs show.  The train cell runs on four
virtual devices (data 1 x fsdp 4), the serve cell on one.
"""
import io
import json
import os
import subprocess
import sys

import pytest

import bench_paths
import run as bench_run
from harness import serve_driver
from harness import trace as trace_lib

LAST_LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _load(name):
    with open(os.path.join(bench_paths.BENCH_DIR, name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tinybench_root")
    os.symlink(bench_paths.BENCH_DIR, root / "benchmark")
    for sub in ("configs", "traffic", "layer_metrics"):
        (root / "tiny" / sub).mkdir(parents=True)

    config = _load("configs/gpt2-xl.json")
    config.update(n_embd=64, n_head=2, n_layer=2, n_positions=128, n_ctx=128,
                  vocab_size=512)
    config["serve"].update(num_slots=4, max_len=128)
    (root / "tiny/configs/tiny.json").write_text(json.dumps(config))

    train = _load("traffic/train_fsdp_16x1k.json")
    train["params"].update(global_batch=8, seq_len=32, pool_batches=4,
                           trace_readings=2)
    (root / "tiny/traffic/tiny_train.json").write_text(json.dumps(train))

    chat = _load("traffic/chat_sessions.json")
    chat["params"].update(
        clients=4, system_prompt_tokens=24, session_token_limit=120,
        user_message_tokens={"dist": "lognormal", "median": 8, "sigma": 0.5,
                             "min": 4, "max": 16, "points": 8},
        output_tokens={"dist": "lognormal", "median": 8, "sigma": 0.4,
                       "min": 6, "max": 12, "points": 8},
        reading_seconds=0.3, trace_seconds=0.5)
    (root / "tiny/traffic/tiny_chat.json").write_text(json.dumps(chat))

    (root / "tiny/layer_metrics/readings_count.py").write_text(
        "def read(record, trace):\n"
        "    return float(len(record['reading_seconds']))\n")

    doc = json.load(open(os.path.join(bench_paths.ROOT, "BENCHMARK.json")))
    doc["paths"] = ["tiny", "benchmark"]
    doc["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                       "file": "tiny/configs/tiny.json", "why": "test"}]
    doc["workloads"] = [
        {"name": "tiny.train", "config": "tiny", "traffic": "tiny_train",
         "chips": 4, "why": "test"},
        {"name": "tiny.chat", "config": "tiny", "traffic": "tiny_chat",
         "chips": 1, "why": "test"}]
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = sorted({
                "tiny.train" if "train" in cell else "tiny.chat"
                for cell in metric["workloads"]})
    doc["per_layer"].append({
        "name": "readings_count", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "train loop",
        "moves": "train_tokens_per_s", "workloads": ["tiny.train"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return str(root)


def _run(root, workload, trace, seconds, monkeypatch):
    # tiny random weights put the top two logits ~0.01 apart, far inside
    # the full-size tolerance; float32-sized here (measured 0.003)
    monkeypatch.setattr(serve_driver, "LOGIT_TOL", 0.01)
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
         ("tiny.chat", 0, 3), ("tiny.chat", 1, 2)]


@pytest.mark.parametrize("workload,trace,seconds", CASES)
def test_last_line_has_exactly_the_contracts_keys(tiny_root, monkeypatch,
                                                  workload, trace, seconds):
    lines = _run(tiny_root, workload, trace, seconds, monkeypatch)
    last = lines[-1]
    assert set(last) == LAST_LINE_KEYS | ({"breakdown"} if trace else set())
    assert last["correct"] is True, lines[-2]
    assert last["attempted"] > 0 and last["failed"] == 0
    device = last["device"]
    assert set(device) == DEVICE_KEYS | (
        {"busy_s", "window_s"} if trace else set())
    assert device["platform"] == "cpu"
    assert device["count"] == (4 if workload == "tiny.train" else 1)
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
        expected = ({"train_tokens_per_s", "setup_s"}
                    if workload == "tiny.train" else
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
    if workload == "tiny.train":
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
