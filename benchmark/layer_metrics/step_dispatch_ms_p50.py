"""Median host time of one ``train.dispatch`` span in the window: the call of
the compiled step, which returns before the device has run it.  The host's
cost of a step: a step shorter than this leaves the device waiting."""
from harness import program_spans


def read(record, trace):
    window = program_spans.window(record, trace)
    if window is None:
        return None
    dispatch_s = program_spans.child_durations_s(
        window, program_spans.DISPATCH_STEP)
    step_s = [(window.spans[u].end_us - window.spans[u].start_us) / 1e6
              for u in window.units]
    if not dispatch_s:
        return None
    return program_spans.report(
        "step_dispatch_ms_p50", 1e3 * program_spans.median(dispatch_s),
        steps=len(dispatch_s), max_ms=1e3 * max(dispatch_s),
        run_step_ms_p50=1e3 * program_spans.median(step_s))
