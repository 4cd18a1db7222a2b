"""A tiny benchmark of the shortcut-expert / latent-attention family in a
temporary directory, for the rehearsal on the CPU: the real family file held
to a toy model's tolerances, a toy configuration that is CUT as the real one
is (``data/longcat/longcat-toy.json``: experts 2-5 of 8, an eighth of the
vocabulary, 2 of 4 layers, with its ``cut`` block), the real traffic file cut
to four sessions.  New files and entries only, beside a link to the real
``benchmark/``, as ``hybrid_bench.py``."""
import json
import os
import shutil

import bench_paths
import config_rules

CELL = "lc.agent"
REAL_CELL = "longcat-flash-chat.agent_sessions_64"
TOY_CONFIG = os.path.join(bench_paths.DATA_DIR, "longcat",
                          "longcat-toy.json")

# float32 compute on both sides at a toy size: logits agree to ~1e-5; a
# router that weighs by the biased score moves them by ~1e-2, so the limit
# stands at 1e-4 between the two
TINY_FAMILY = '''"""The real longcat_flash family, held to a toy's tolerance."""
import importlib.util

_spec = importlib.util.spec_from_file_location("_real_longcat", {path!r})
_real = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_real)
globals().update({{k: v for k, v in vars(_real).items()
                  if not k.startswith("__")}})
TOLERANCES = dict(_real.TOLERANCES, logit=1e-4, min_agreement=0.9)
'''


def build(root):
    """Fill ``root`` (a ``pathlib.Path``) and return it as a string."""
    os.symlink(bench_paths.BENCH_DIR, root / "benchmark")
    for sub in ("configs", "traffic", "families"):
        (root / "tiny" / sub).mkdir(parents=True)
    shutil.copy(TOY_CONFIG, root / "tiny/configs/lc.json")
    (root / "tiny/families/longcat_toy.py").write_text(TINY_FAMILY.format(
        path=os.path.join(bench_paths.BENCH_DIR, "families",
                          "longcat_flash.py")))

    with open(os.path.join(bench_paths.BENCH_DIR, "traffic",
                           "agent_sessions_64.json")) as f:
        agent = json.load(f)
    agent["params"].update(
        clients=4, system_prompt_tokens=40, session_token_limit=250,
        user_message_tokens={"dist": "lognormal", "median": 12, "sigma": 0.5,
                             "min": 5, "max": 30, "points": 8},
        output_tokens={"dist": "lognormal", "median": 8, "sigma": 0.4,
                       "min": 6, "max": 12, "points": 8},
        reading_seconds=0.3, trace_seconds=0.5)
    (root / "tiny/traffic/tiny_agent_64.json").write_text(json.dumps(agent))

    with open(os.path.join(bench_paths.ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(TOY_CONFIG) as f:
        reduced = json.load(f)["reduced"]
    doc["paths"] = ["tiny", "benchmark"]
    doc["configs"] = [{"name": "lc", "source": "test", "reduced": reduced,
                       "file": "tiny/configs/lc.json", "why": "test"}]
    doc["workloads"] = [{"name": CELL, "config": "lc",
                         "traffic": "tiny_agent_64", "chips": 1,
                         "why": "test"}]
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = ([CELL] if REAL_CELL in metric["workloads"]
                                   else [])
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    config_rules.check(str(root))
    return str(root)
