"""A tiny benchmark of the decoder-hybrid-decoder family in a temporary
directory, for the rehearsal on the CPU: the real family file held to a toy
model's tolerances, the real configuration cut to toy widths (eight layers:
Mamba, window, Mamba, window, Mamba-memory, full, GMU, cross; hidden 64; a
window of 8 keys in rings of 40 rows), the real traffic file cut to four
sessions.  The probe's context (70 tokens) lies past the window and wraps
the rings, and no seed can cut it short: it is at most the system prompt and
the shortest message.  New files and entries only, beside a link to the real
``benchmark/``, as ``hybrid_bench.py``."""
import json
import os

import bench_paths
import config_rules

CELL = "pf.reasoning"
REAL_CELL = "phi-4-mini-flash-reasoning.reasoning_sessions"

# float32 compute on both sides at a toy size: logits agree to ~1e-5; rings
# that forget their window move them by far more, so the limit stands at
# 1e-4 between the two
TINY_FAMILY = '''"""The real phi4flash family, held to a toy's tolerance."""
import importlib.util

_spec = importlib.util.spec_from_file_location("_real_phi4flash", {path!r})
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

    config = _load("configs/phi-4-mini-flash-reasoning.json")
    config.update(hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=2, intermediate_size=96,
                  num_hidden_layers=8, vocab_size=512, sliding_window=8,
                  family="phi4flash_tiny")
    # 1/sqrt(width) weights: activations of order one at a toy width, so
    # that state, rings and memory carry weight in the logits
    config["assumed"].update(compute_dtype="float32",
                             conv_state_dtype="float32",
                             initializer_range=0.125, mamba_dt_rank=4)
    config["serve"].update(num_slots=4, max_len=256, check_context_tokens=70)
    (root / "tiny/configs/pf.json").write_text(json.dumps(config))
    (root / "tiny/families/phi4flash_tiny.py").write_text(TINY_FAMILY.format(
        path=os.path.join(bench_paths.BENCH_DIR, "families",
                          "phi4flash.py")))

    traffic = _load("traffic/reasoning_sessions.json")
    traffic["params"].update(
        clients=4, system_prompt_tokens=64, session_token_limit=250,
        user_message_tokens={"dist": "lognormal", "median": 12, "sigma": 0.5,
                             "min": 8, "max": 30, "points": 8},
        output_tokens={"dist": "lognormal", "median": 8, "sigma": 0.4,
                       "min": 6, "max": 12, "points": 8},
        reading_seconds=0.3, trace_seconds=0.5)
    (root / "tiny/traffic/tiny_reasoning.json").write_text(
        json.dumps(traffic))

    doc = json.load(open(os.path.join(bench_paths.ROOT, "BENCHMARK.json")))
    doc["paths"] = ["tiny", "benchmark"]
    doc["configs"] = [{"name": "pf", "source": "test", "reduced": [],
                       "file": "tiny/configs/pf.json", "why": "test"}]
    doc["workloads"] = [{"name": CELL, "config": "pf",
                         "traffic": "tiny_reasoning", "chips": 1,
                         "why": "test"}]
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [CELL] if REAL_CELL in metric["workloads"] \
                else []
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    config_rules.check(str(root))
    return str(root)
