"""Prefill windows dispatched per tick of the window: the program's
``prefill_windows_total`` over ``ticks_completed`` as differences across the
window's ticks, which each ``serve.tick`` span carries in its args."""
from harness import program_spans


def read(record, trace):
    window = program_spans.window(record, trace)
    if window is None:
        return None
    per_tick = program_spans.tick_args(window, "windows")
    if len(per_tick) != len(window.units):
        return None
    tick_ms = {}                 # what a window costs: ticks by their count
    for t, windows in zip(window.units, per_tick):
        span = window.spans[t]
        tick_ms.setdefault(int(windows), []).append(
            (span.end_us - span.start_us) / 1e3)
    return program_spans.report(
        "prefill_windows_per_tick", sum(per_tick) / len(per_tick),
        ticks=len(per_tick), windows=sum(per_tick),
        tick_ms_p50_by_windows={k: program_spans.median(v)
                                for k, v in sorted(tick_ms.items())},
        ticks_by_windows={k: len(v) for k, v in sorted(tick_ms.items())})
