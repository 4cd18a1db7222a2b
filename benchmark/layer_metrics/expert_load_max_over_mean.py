"""The busiest held expert's tokens over the mean held expert's, across the
window: the increments of the program's ``expert_tokens_total`` (by expert
layer and held expert, ``Engine.stats()``), which the spans that read a
program's tokens carry as ``expert_tokens`` (``identity_picks_pct`` says
which spans).

1.0 is a perfectly even load; the expert with the most tokens is the one a
grouped matmul or an exchange waits for.  With seeded random weights and
uniform ids it reads what a random router's spread is at this traffic.  A
program whose spans carry no such count reports nothing."""
from harness import program_spans
from layer_metrics import identity_picks_pct as _picks

NAME = "expert_load_max_over_mean"


def read(record, trace):
    window = program_spans.window(record, trace)
    if window is None:
        return None
    total = None
    for span in _picks.counting_spans(window):
        rows = span.args["expert_tokens"]
        total = rows if total is None else [
            [a + b for a, b in zip(have, new)]
            for have, new in zip(total, rows)]
    loads = [n for row in total or () for n in row]
    if not loads or not sum(loads):
        return None
    mean = sum(loads) / len(loads)
    return program_spans.report(
        NAME, max(loads) / mean, expert_tokens=sum(loads),
        held_experts=len(loads), max_tokens=max(loads),
        min_tokens=min(loads),
        max_over_mean_by_layer=[max(row) * len(row) / sum(row)
                                for row in total if sum(row)])
