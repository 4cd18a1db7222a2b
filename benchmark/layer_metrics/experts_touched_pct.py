"""Held experts that received at least one token in a decode step, over the
held experts there were: ``experts_touched`` / ``experts_held_steps`` summed
over the window's ``serve.decode_fetch`` spans (held experts x expert layers
x steps of each dispatch; the device counts them, the scheduler reads the
counts with the dispatch's tokens).

An expert nobody picked is skipped and its weights are not read, so this is
the share of the held experts' bytes a decode step moves.  It is what checks
the ``expert_weights`` term of the family's ``decode_step_bytes``, which
MODELS the share as ``1 - (1 - moe_topk / router outputs) ** live`` under
uniform routing: both are on the stderr line.  A program whose spans carry
no such count (a parent commit, a model without experts) reports nothing."""
from harness import program_spans, spec

NAME = "experts_touched_pct"
FETCH = "serve.decode_fetch"


def fetch_spans(window):
    """The window's ticks' ``serve.decode_fetch`` spans."""
    return [window.spans[c] for tick in window.units
            for c in window.children.get(tick, ())
            if window.spans[c].name == FETCH]


def read(record, trace):
    window = program_spans.window(record, trace)
    if window is None:
        return None
    touched = could = live_steps = steps = 0
    for span in fetch_spans(window):
        if "experts_held_steps" in span.args:
            touched += int(span.args["experts_touched"])
            could += int(span.args["experts_held_steps"])
            live_steps += int(span.args["live_steps"])
            steps += 1
    if not could:
        return None
    cell, family = spec.cell_of(record)
    extra = {}
    if hasattr(family.module, "decode_step_bytes"):
        # the family's model of the same share, at the window's mean live
        # slots a step (``tick_steps`` steps a dispatch)
        layers = cell.config["num_layers"]
        held = cell.config["n_routed_experts"]
        per_dispatch = could // (steps * layers * held)
        live = live_steps / (steps * per_dispatch)
        modelled = family.decode_step_bytes(cell.config, live, 0.0)
        whole = family.decode_step_bytes(cell.config, 1e9, 0.0)
        extra = {"mean_live_slots": live, "modelled_pct": 100.0
                 * modelled["expert_weights"] / whole["expert_weights"]}
    return program_spans.report(
        NAME, 100.0 * touched / could, experts_touched=touched,
        experts_held_steps=could, decode_dispatches=steps, **extra)
