"""A tiny benchmark of the state-space / attention family in a temporary
directory, for the rehearsals on the CPU: the real family file held to a toy
model's tolerances, the real configuration cut to toy widths (two periods of
``[m, m, a, m]``, hidden 64, state 16), the real traffic file cut to four
sessions.  New files and entries only, beside a link to the real
``benchmark/``, as ``tiny_bench.py``."""
import json
import os

import bench_paths

CELL = "hy.agent"

# float32 compute on both sides at a toy size: logits agree to ~1e-5
# (this sandbox's CPU: 2e-6 measured); a state dropped at every window boundary
# moves them by 1.5e-3, so the limit stands at 1e-4 between the two
TINY_FAMILY = '''"""The real granitemoehybrid family, held to a toy's tolerance."""
import importlib.util

_spec = importlib.util.spec_from_file_location("_real_hybrid", {path!r})
_real = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_real)
globals().update({{k: v for k, v in vars(_real).items()
                  if not k.startswith("__")}})
TOLERANCES = dict(_real.TOLERANCES, logit=1e-4, min_agreement=0.9)
'''


def _load(name):
    with open(os.path.join(bench_paths.BENCH_DIR, name)) as f:
        return json.load(f)


def build(root):
    """Fill ``root`` (a ``pathlib.Path``) and return it as a string."""
    os.symlink(bench_paths.BENCH_DIR, root / "benchmark")
    for sub in ("configs", "traffic", "families"):
        (root / "tiny" / sub).mkdir(parents=True)

    config = _load("configs/granite-4.0-h-micro.json")
    config.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        shared_intermediate_size=96, intermediate_size=96, vocab_size=512,
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
        layer_types=["mamba", "mamba", "attention", "mamba"] * 2,
        num_hidden_layers=8, attention_multiplier=0.2,
        family="hybrid_tiny")
    # 1/sqrt(width) weights: activations of order one at a toy width, so
    # that the recurrent state carries weight in the logits as it does at
    # the published one
    config["assumed"].update(compute_dtype="float32",
                             conv_state_dtype="float32",
                             initializer_range=0.125)
    config["serve"].update(num_slots=4, max_len=256,
                           check_context_tokens=70)
    (root / "tiny/configs/hy.json").write_text(json.dumps(config))
    (root / "tiny/families/hybrid_tiny.py").write_text(TINY_FAMILY.format(
        path=os.path.join(bench_paths.BENCH_DIR, "families",
                          "granitemoehybrid.py")))

    agent = _load("traffic/agent_sessions.json")
    agent["params"].update(
        clients=4, system_prompt_tokens=40, session_token_limit=250,
        user_message_tokens={"dist": "lognormal", "median": 12, "sigma": 0.5,
                             "min": 5, "max": 30, "points": 8},
        output_tokens={"dist": "lognormal", "median": 8, "sigma": 0.4,
                       "min": 6, "max": 12, "points": 8},
        reading_seconds=0.3, trace_seconds=0.5)
    (root / "tiny/traffic/tiny_agent.json").write_text(json.dumps(agent))

    doc = json.load(open(os.path.join(bench_paths.ROOT, "BENCHMARK.json")))
    real = doc["workloads"][-1]["name"]
    doc["paths"] = ["tiny", "benchmark"]
    doc["configs"] = [{"name": "hy", "source": "test", "reduced": [],
                       "file": "tiny/configs/hy.json", "why": "test"}]
    doc["workloads"] = [{"name": CELL, "config": "hy",
                         "traffic": "tiny_agent", "chips": 1, "why": "test"}]
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [CELL] if real in metric["workloads"] \
                else []
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return str(root)
