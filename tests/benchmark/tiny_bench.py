"""A tiny benchmark in a temporary directory, for the rehearsals on the CPU.

It is made of NEW files and entries only, beside a link to the real
``benchmark/``: configurations, traffic mixes, a per-layer metric and model
families.  That a later PR can add a cell, and a model family, without
editing a file is what the runs over it show.  Two configurations: ``tiny``
(GPT-2 keys at a toy width, family ``gpt2_tiny``: the real ``gpt2`` family
with a logit tolerance a toy model's logits can be held to) and ``rr`` (the
fixture family ``rmsrope`` under ``data/rmsrope/``, whose configuration has
none of GPT-2's keys).  Train cells run on four virtual devices (data 1 x
fsdp 4), serve cells on one.
"""
import json
import os
import shutil

import bench_paths

FIXTURE_FAMILY = os.path.join(bench_paths.DATA_DIR, "rmsrope")

# tiny random weights put the top two logits ~0.01 apart, far inside the
# full-size tolerance; float32-sized here (measured 0.003)
GPT2_TINY_FAMILY = '''"""The real gpt2 family, held to a toy model's tolerance."""
import importlib.util

_spec = importlib.util.spec_from_file_location("_real_gpt2", {path!r})
_real = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_real)
globals().update({{k: v for k, v in vars(_real).items()
                  if not k.startswith("__")}})
TOLERANCES = dict(_real.TOLERANCES, logit=0.01)
'''


def _load(name):
    with open(os.path.join(bench_paths.BENCH_DIR, name)) as f:
        return json.load(f)


def build(root, metric_name: str, metric_source: str, metric_entry: dict):
    """Fill ``root`` (a ``pathlib.Path``) and return it as a string.  The
    one per-layer metric the caller brings is read by ``metric_source`` and
    entered as ``metric_entry``."""
    os.symlink(bench_paths.BENCH_DIR, root / "benchmark")
    for sub in ("configs", "traffic", "layer_metrics", "families"):
        (root / "tiny" / sub).mkdir(parents=True)

    config = _load("configs/gpt2-xl.json")
    config.update(n_embd=64, n_head=2, n_layer=2, n_positions=128, n_ctx=128,
                  vocab_size=512, family="gpt2_tiny")
    config["serve"].update(num_slots=4, max_len=128)
    (root / "tiny/configs/tiny.json").write_text(json.dumps(config))
    (root / "tiny/families/gpt2_tiny.py").write_text(GPT2_TINY_FAMILY.format(
        path=os.path.join(bench_paths.BENCH_DIR, "families", "gpt2.py")))

    shutil.copy(os.path.join(FIXTURE_FAMILY, "rmsrope-tiny.json"),
                root / "tiny/configs/rmsrope-tiny.json")
    for name in ("rmsrope.py", "rmsrope_reference.py"):
        shutil.copy(os.path.join(FIXTURE_FAMILY, name),
                    root / "tiny/families" / name)

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

    (root / f"tiny/layer_metrics/{metric_name}.py").write_text(metric_source)

    doc = json.load(open(os.path.join(bench_paths.ROOT, "BENCHMARK.json")))
    doc["paths"] = ["tiny", "benchmark"]
    doc["configs"] = [
        {"name": "tiny", "source": "test", "reduced": [],
         "file": "tiny/configs/tiny.json", "why": "test"},
        {"name": "rr", "source": "test", "reduced": [],
         "file": "tiny/configs/rmsrope-tiny.json", "why": "test"}]
    doc["workloads"] = [
        {"name": f"{config}.{kind}", "config": config,
         "traffic": f"tiny_{kind}", "chips": 4 if kind == "train" else 1,
         "why": "test"}
        for config in ("tiny", "rr") for kind in ("train", "chat")]
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in metric:
            kinds = {"train" if "train" in cell else "chat"
                     for cell in metric["workloads"]}
            metric["workloads"] = sorted(
                f"{config}.{kind}" for config in ("tiny", "rr")
                for kind in kinds)
    doc["per_layer"].append(dict(metric_entry, name=metric_name))
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return str(root)
