"""Model FLOP/s utilisation: ``train_tokens_per_s`` x the family's
``train_flops_per_token`` (gpt2: 6N + 12 L h s) over chips x the published
bf16 peak.  Recomputation is not counted.  A CPU has
no row in the table of peaks and reports nothing; an accelerator that is
not in the table is an error."""
from harness import peaks


def read(record, trace):
    if record["platform"] == "cpu" or record["tokens_per_s"] is None:
        return None
    peak = peaks.peak_for(record["device_kind"]).bf16_flops
    return (100.0 * record["tokens_per_s"] * record["flops_per_token"]
            / (record["chips"] * peak))
