"""``BENCHMARK.json`` against the contract's limits, and every file a cell
needs found by the names in it."""
import json
import os

import pytest

import bench_paths
from harness import peaks, spec

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


def _config_file(name):
    entry = next(c for c in DOC["configs"] if c["name"] == name)
    return json.load(open(os.path.join(bench_paths.ROOT, entry["file"])))


def _family_of(name):
    cell = next(w["name"] for w in DOC["workloads"] if w["config"] == name)
    return BENCH.family(BENCH.cell(cell))


@pytest.mark.parametrize("config,params,flop_per_token", [
    ("gpt2-medium", 354.8e6, 2.43e9), ("gpt2-xl", 1557.6e6, 1.029e10)])
def test_flop_count_from_the_config_file(config, params, flop_per_token):
    body, family = _config_file(config), _family_of(config)
    assert family.total_params(body) == pytest.approx(params, rel=1e-3)
    assert family.train_flops_per_token(body, 1024) == pytest.approx(
        flop_per_token, rel=5e-3)
    # serving: 2 x the parameters a token passes through + 4 L h context;
    # a third of training's count at the same context, and the head apart
    assert 3 * family.serve_flops_per_token(body, 1024) == pytest.approx(
        family.train_flops_per_token(body, 1024), rel=1e-12)
    head = (family.serve_flops_per_token(body, 0)
            - family.serve_flops_per_token(body, 0, head=False))
    assert head == 2 * body["vocab_size"] * body["n_embd"]
    assert (family.serve_flops_per_token(body, 600)
            - family.serve_flops_per_token(body, 100)
            == 4 * body["n_layer"] * body["n_embd"] * 500)


@pytest.mark.parametrize("config,hidden,layers,heads", [
    ("gpt2-medium", 1024, 24, 16), ("gpt2-xl", 1600, 48, 25)])
def test_family_gpt2_builds_the_model_the_drivers_built(config, hidden,
                                                        layers, heads):
    """The family gives what ``common.gpt_config`` and the drivers' module
    constants gave: the same ``GPTConfig`` and the same four tolerances."""
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.models.gpt import GPT, GPTConfig
    body, family = _config_file(config), _family_of(config)
    assert body["family"] == family.name == "gpt2"
    want = GPTConfig(
        vocab_size=50257, hidden_size=hidden, num_layers=layers,
        num_heads=heads, intermediate_size=4 * hidden, max_position=1024,
        layer_norm_eps=1e-5, dtype=jnp.dtype("bfloat16"), dropout_rate=0.0,
        remat=True)
    assert family.model_config(body) == want
    model = family.build_model(body)
    assert isinstance(model, GPT) and model.config == want
    assert model.mesh is None and family.vocab_size(body) == 50257
    assert family.TOLERANCES == {"logit": 0.15, "min_agreement": 0.6,
                                 "loss": 2e-3, "token_loss": 0.1}
    assert family.REFERENCE == "gpt2_reference"
    assert family.reference.__file__ == os.path.join(
        bench_paths.BENCH_DIR, "families", "gpt2_reference.py")
    assert family.kernel_expected(_config_file("gpt2-xl"), "any") is True


def test_serve_check_sizes_are_the_configurations():
    serve = _config_file("gpt2-xl")["serve"]
    assert (serve["check_context_tokens"],
            serve["check_decode_positions"]) == (200, 8)


def test_a_family_without_a_file_is_a_spec_error_naming_the_file(tmp_path):
    doc = dict(DOC, paths=["benchmark"])
    os.symlink(bench_paths.BENCH_DIR, tmp_path / "benchmark")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    bench = spec.Benchmark(str(tmp_path))
    cell = bench.cell("gpt2-xl.chat_sessions")
    assert bench.family(cell).name == "gpt2"
    cell.config["family"] = "no_such_family"
    with pytest.raises(spec.SpecError, match=r"families/no_such_family\.py"):
        bench.family(cell)
    del cell.config["family"]
    with pytest.raises(spec.SpecError, match="names no \"family\""):
        bench.family(cell)
    # a family file that lacks a name the drivers use is refused by name
    os.unlink(tmp_path / "benchmark")
    (tmp_path / "benchmark" / "families").mkdir(parents=True)
    (tmp_path / "benchmark" / "families" / "half.py").write_text(
        "REFERENCE = 'half_reference'\nTOLERANCES = {}\n")
    cell.config["family"] = "half"
    with pytest.raises(spec.SpecError, match="half.py lacks build_model"):
        spec.Benchmark(str(tmp_path)).family(cell)


def test_serve_mfu_pct_on_a_hand_made_record():
    read = BENCH.layer_reader("serve_mfu_pct")
    record = {
        "platform": "tpu", "device_kind": "TPU v5 lite", "chips": 1,
        "window_s": 50.0,
        "computed": {"prefill_tokens": 6000, "decode_tokens": 2000,
                     "first_tokens": 50, "prefill_context": 500.0,
                     "decode_context": 600.0,
                     "flops_per_prefill_token": 3.0e9,
                     "flops_per_decode_token": 3.2e9,
                     "flops_per_head": 1.6e8}}
    flops = 6000 * 3.0e9 + 2000 * 3.2e9 + 50 * 1.6e8
    assert read(record, None) == pytest.approx(
        100 * flops / (50.0 * 197e12), rel=1e-12)
    assert read(dict(record, chips=4), None) == pytest.approx(
        25 * flops / (50.0 * 197e12), rel=1e-12)
    assert read(dict(record, platform="cpu"), None) is None
    assert read({k: v for k, v in record.items() if k != "computed"},
                None) is None
    with pytest.raises(KeyError):
        read(dict(record, device_kind="TPU v9 imaginary"), None)
    entry = next(m for m in DOC["per_layer"] if m["name"] == "serve_mfu_pct")
    assert entry["moves"] == "serve_tokens_per_s" and entry["unit"] == "%"
    assert entry["layer"] == "model"
    assert entry["workloads"] == ["gpt2-xl.chat_sessions"]


def test_computed_work_of_a_serving_window_on_hand_made_turns():
    """What ``serve_mfu_pct`` is made of: half of each prompt reused at its
    front, the rest prefilled; every token after a turn's first decoded."""
    from types import SimpleNamespace

    import numpy as np
    from harness import serve_driver
    body, family = _config_file("gpt2-xl"), _family_of("gpt2-xl")
    turns = [SimpleNamespace(prompt=np.zeros(100, np.int32), tokens=11),
             SimpleNamespace(prompt=np.zeros(200, np.int32), tokens=1)]
    work = serve_driver._computed_work(family, body, turns, prompt_tokens=300,
                                       reused=150, window_tokens=12,
                                       first_tokens=2)
    assert (work["prefill_tokens"], work["decode_tokens"],
            work["first_tokens"]) == (150, 10, 2)
    # 50 tokens attending (50 + 100 + 1) / 2 and 100 attending 150.5
    assert work["prefill_context"] == pytest.approx(
        (50 * 75.5 + 100 * 150.5) / 150)
    assert work["decode_context"] == pytest.approx(100 + 11 / 2)
    assert work["flops_per_prefill_token"] == family.serve_flops_per_token(
        body, work["prefill_context"], head=False)
    assert work["flops_per_decode_token"] == family.serve_flops_per_token(
        body, work["decode_context"], head=True)
    assert work["flops_per_head"] == 2 * 50257 * 1600
    idle = serve_driver._computed_work(family, body, [], 0, 0, 0, 0)
    assert idle["prefill_tokens"] == idle["decode_tokens"] == 0


def test_the_drivers_know_no_model():
    """What ISSUE 28's grep asks: the entry point and the drivers import no
    model and read no GPT-2 key; all of that is the family's.  (As words:
    the earlier line's ``token_positions`` holds the letters of
    ``n_positions`` and is not one.)"""
    import re
    banned = re.compile(r"models\.gpt|gpt_config|n_embd|n_layer|n_head|"
                        r"\bn_positions|layer_norm_epsilon|gelu_new|"
                        r"harness\.reference|harness\.flops|"
                        r"import reference|import flops")
    for name in ("run.py", "harness/common.py", "harness/serve_driver.py",
                 "harness/train_driver.py"):
        text = open(os.path.join(bench_paths.BENCH_DIR, name)).read()
        assert not banned.search(text), (name, banned.search(text).group(0))
    for gone in ("harness/reference.py", "harness/flops.py"):
        assert not os.path.exists(os.path.join(bench_paths.BENCH_DIR, gone))
