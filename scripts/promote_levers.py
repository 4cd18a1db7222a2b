"""Promote MFU-ablation winners into the bench defaults.

Reads ``scripts/mfu_ablation.py`` output (JSON lines; file paths as argv,
or stdin), picks the best GPT and BERT arms by measured tokens/sec, and
writes ``docs/PROMOTED.json`` mapping the winning levers onto the bench
env knobs that bench.py reads as *defaults* (explicit env still wins):

  GPT : loss_chunk  -> DTTPU_BENCH_LOSS_CHUNK
        remat_policy-> DTTPU_BENCH_REMAT_POLICY
  BERT: mlm_gather  -> DTTPU_BENCH_MLM_GATHER
        remat_dots  -> DTTPU_BENCH_BERT_REMAT

A lever is promoted only when its arm beats the model's ``base`` arm by
>= MIN_WIN (2%) — a tie is noise, and the base path keeps one fewer
moving part.  Arms whose levers have no bench env knob (fused_adam,
batch ladder positions) are reported in the evidence block but cannot be
promoted here; bench configs own those defaults in code.

Pipeline: ``mfu_ablation.py`` prints the arms, this script reads them
from stdin, and the gpt/bert bench rows re-run with the promoted
defaults — all in one session on one chip, so the arms are comparable.
"""
from __future__ import annotations

import datetime
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "docs", "PROMOTED.json")
MIN_WIN = 1.02

# arm name -> env assignment, per model (mirrors mfu_ablation MATRIX)
GPT_LEVERS = {
    "loss_chunk": {"DTTPU_BENCH_LOSS_CHUNK": "512"},
    "remat_dots": {"DTTPU_BENCH_REMAT_POLICY": "dots"},
}
BERT_LEVERS = {
    "mlm_gather": {"DTTPU_BENCH_MLM_GATHER": "1"},
    # Provenance caveat: mfu_ablation's BERT arms ALL run remat=True
    # (base = policy "full"), while bench_bert's default is remat OFF —
    # so this mapping's 1.02x gate compares dots-vs-full, and flipping
    # the bench row to dots additionally rests on the arm-level
    # composite win over the measured no-remat bench row (168,819 vs
    # 134,995 tok/s/chip, 08-01 window).  bench_bert's ladder only
    # attempts b128 when remat is on.
    "remat_dots": {"DTTPU_BENCH_BERT_REMAT": "dots"},
}


def parse(lines, allow_any=False):
    """Only REAL hardware rows may drive a promotion: smoke rows and
    non-TPU backends are wiring checks, and a default promoted from them
    would encode noise.  ``allow_any`` (tests) lifts the guard."""
    rows = []
    for line in lines:
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            row = json.loads(line)
        except json.JSONDecodeError:
            continue
        if "arm" not in row or "tokens_per_sec" not in row:
            continue
        if not allow_any and (row.get("smoke")
                              or row.get("backend") != "tpu"):
            continue
        rows.append(row)
    return rows


def promote(rows):
    """-> (env dict, evidence list)."""
    env, evidence = {}, []
    for model, levers in (("gpt", GPT_LEVERS), ("bert", BERT_LEVERS)):
        mrows = [r for r in rows if r.get("model") == model]
        if not mrows:
            continue
        base = next((r for r in mrows if r["arm"] == "base"), None)
        best = max(mrows, key=lambda r: r["tokens_per_sec"])
        evidence.append({"model": model, "base": base, "best": best})
        if base is None:
            continue
        # promote each lever whose PURE arm (the lever alone at base
        # batch/seq) beats base — composite arms (e.g. loss_chunk_b192)
        # mix levers with batch moves the env can't express
        for arm_prefix, assignment in levers.items():
            arm = next((r for r in mrows if r["arm"] == arm_prefix), None)
            if arm and (arm["tokens_per_sec"]
                        >= MIN_WIN * base["tokens_per_sec"]):
                env.update(assignment)
    return env, evidence


def main() -> int:
    lines = []
    for path in sys.argv[1:]:
        with open(path) as f:
            lines.extend(f.readlines())
    if not sys.argv[1:]:
        lines = sys.stdin.readlines()
    allow_any = os.environ.get("DTTPU_PROMOTE_ALLOW_ANY") == "1"
    rows = parse(lines, allow_any=allow_any)
    if not rows:
        print("promote_levers: no REAL-hardware ablation rows found "
              "(smoke/cpu rows never promote) — nothing written",
              file=sys.stderr)
        return 1
    env, evidence = promote(rows)
    payload = {
        "env": env,
        "evidence": evidence,
        "written_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "rule": f"pure lever arm >= {MIN_WIN}x base tokens/sec",
    }
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    detail = env if env else "{} (no lever beat base — base stays default)"
    print(f"promote_levers: wrote {OUT} env={detail}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
