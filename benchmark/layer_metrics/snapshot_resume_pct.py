"""Admitted turns of the window whose prefill resumed from a state snapshot,
over all admitted turns: of the program's ``serve.admit`` spans that ended
``ok`` inside the window's ticks, those marked ``resumed`` (each holds a
``serve.state_restore`` span: the device copy of the snapshot).
With ``prefix_hit_pct`` it says whether prefix reuse works for a model whose
prefix is a state.  A program without those spans reports nothing."""
from harness import program_spans


def read(record, trace):
    window = program_spans.window(record, trace)
    if window is None:
        return None
    admits = restores = 0
    seen_restore_span = False
    for s in window.spans:
        if s.end_us is None or not (window.start_us <= s.start_us
                                    <= window.end_us):
            continue
        if s.name == "serve.admit" and s.args.get("outcome") == "ok":
            admits += 1
            seen_restore_span |= "resumed" in s.args
            restores += bool(s.args.get("resumed"))
    if not admits or not seen_restore_span:
        return None
    return program_spans.report(
        "snapshot_resume_pct", 100.0 * restores / admits,
        admitted_turns=admits, resumed_from_snapshot=restores)
