"""Model FLOP/s utilisation of a serving window: the operations the tokens
the engine computed in it need, over window time x chips x the published
bf16 peak.  The whole step's share of the chip, beside the kernels' shares:
a later PR that takes a kernel off the path leaves that kernel's metric
silent and can claim a gain only while this one still bounds it.

Computed tokens are the prompt tokens submitted less those the prefix cache
served (``prefix_tokens_reused``), at the family's ``serve_flops_per_token``
without the head, plus the tokens decode steps produced, with it, plus the
head once for each turn's first token (the prefill's last position yields
it); each at the mean number of positions such a token attended over
(``serve_driver._computed_work``).  What the engine computes beyond that is
not counted, as MFU counts what the algorithm needs: prefill windows are
padded to 32 tokens and the padding is not counted, nor is the head the last
window applies to all 32 of its positions, nor a decode step past a turn's
budget.  A CPU has no row in the table of peaks and reports nothing.  What
the value was made of goes to stderr."""
import json
import sys

from harness import peaks


def read(record, trace):
    work = record.get("computed")
    if record["platform"] == "cpu" or not work or not record["window_s"]:
        return None
    flops = (work["prefill_tokens"] * work["flops_per_prefill_token"]
             + work["decode_tokens"] * work["flops_per_decode_token"]
             + work["first_tokens"] * work["flops_per_head"])
    peak = peaks.peak_for(record["device_kind"]).bf16_flops
    value = 100.0 * flops / (record["window_s"] * record["chips"] * peak)
    print("[benchmark] serve_mfu_pct: " + json.dumps(
        {"value": value, "flops": flops, "window_s": record["window_s"],
         "chips": record["chips"], "peak_bf16_flops": peak, **work}),
        file=sys.stderr, flush=True)
    return value
