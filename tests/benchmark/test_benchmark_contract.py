"""``BENCHMARK.json`` against the contract's limits, and every file a cell
needs found by the names in it."""
import json
import os

import pytest

import bench_paths
from harness import flops, peaks, spec

DOC = json.load(open(os.path.join(bench_paths.ROOT, "BENCHMARK.json")))
BENCH = spec.Benchmark(bench_paths.ROOT)
CELLS = [w["name"] for w in DOC["workloads"]]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_are_exactly_the_contracts():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert DOC["command"] == ["python3", "benchmark/run.py"]
    assert DOC["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= DOC["run_seconds"] <= 51
    # every later check: 2 + 14 x 24 runs must fit 43200 s with 24 cells
    runs = 2 + 14 * 24
    assert (runs * (DOC["run_seconds"] + 60) + 24 * 2 * 90 + 1200) <= 43200


@pytest.mark.parametrize("section,keys", [
    ("configs", {"name", "source", "file", "reduced", "why"}),
    ("workloads", {"name", "config", "traffic", "chips", "why"}),
    ("end_to_end", {"name", "unit", "better", "bound", "source"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
])
def test_entries_have_just_the_contracts_keys(section, keys):
    names = [e["name"] for e in DOC[section]]
    assert len(names) == len(set(names))
    for entry in DOC[section]:
        assert set(entry) - {"workloads"} == keys, entry["name"]
        assert spec.NAME_RE.match(entry["name"]), entry["name"]
        for key in ("why", "layer", "source"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]
        if "workloads" in entry:
            assert section in ("end_to_end", "per_layer")
            assert entry["workloads"] and set(entry["workloads"]) <= set(CELLS)


def test_metric_names_units_sources_and_bounds():
    for metric in DOC["end_to_end"] + DOC["per_layer"]:
        assert spec.UNIT_RE.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    for metric in DOC["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    assert {"train_tokens_per_s", "serve_tokens_per_s", "ttft_p95_ms",
            "tpot_p50_ms", "setup_s"} == {m["name"] for m in DOC["end_to_end"]}
    setup = next(m for m in DOC["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup


def test_four_chip_cells_are_at_most_a_quarter_or_one():
    four = [w for w in DOC["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in DOC["workloads"])
    assert len(four) <= max(1, len(DOC["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in DOC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_config_is_used_and_its_file_is_under_paths():
    used = {w["config"] for w in DOC["workloads"]}
    files = [c["file"] for c in DOC["configs"]]
    assert len(files) == len(set(files))
    for config in DOC["configs"]:
        assert config["name"] in used
        assert any(config["file"].startswith(p + "/") for p in DOC["paths"])
        body = json.load(open(os.path.join(bench_paths.ROOT, config["file"])))
        assert body["source"] == config["source"]
        assert body["reduced"] == config["reduced"] == []
        assert config["source"].startswith("https://")


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_are_found_by_name(name):
    cell = BENCH.cell(name)
    assert cell.traffic["kind"] in ("train", "serve")
    assert hasattr(BENCH.generator(cell),
                   "generate" if cell.traffic["kind"] == "train" else "make")
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1
    reported = {m["name"] for m in cell.end_to_end}
    for metric in cell.per_layer:
        assert callable(BENCH.layer_reader(metric["name"]))
        assert metric["moves"] in reported, (metric["name"], name)
    deployment = cell.config[cell.traffic["kind"]]
    if cell.traffic["kind"] == "train":
        chips = 1
        for size in deployment["mesh"].values():
            chips *= size
        assert chips == cell.chips


def test_layers_of_per_layer_metrics_are_perf_mds():
    text = open(os.path.join(bench_paths.ROOT, "PERF.md")).read()
    for layer in {m["layer"] for m in DOC["per_layer"]}:
        assert f"**{layer}**" in text, layer


def test_unknown_device_kind_raises_and_v5e_is_published():
    assert peaks.peak_for("TPU v5 lite").bf16_flops == 197e12
    assert peaks.peak_for("TPU v5 lite").hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError):
        peaks.peak_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peak_for("cpu")


@pytest.mark.parametrize("config,params,flop_per_token", [
    ("gpt2-medium", 354.8e6, 2.43e9), ("gpt2-xl", 1557.6e6, 1.029e10)])
def test_flop_count_from_the_config_file(config, params, flop_per_token):
    entry = next(c for c in DOC["configs"] if c["name"] == config)
    body = json.load(open(os.path.join(bench_paths.ROOT, entry["file"])))
    assert flops.total_params(body) == pytest.approx(params, rel=1e-3)
    assert flops.train_flops_per_token(body, 1024) == pytest.approx(
        flop_per_token, rel=5e-3)
