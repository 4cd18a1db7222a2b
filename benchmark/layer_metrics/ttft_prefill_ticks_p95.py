"""95th percentile (nearest rank) of the ticks a turn spent prefilling, from
the tick that admitted it to the tick of its first token, both counted: the
scheduler's ``prefill_ticks`` on each request's ``reqtrace`` record, over
turns whose first token fell in the window."""
from harness import program_spans, readings


def read(record, trace):
    window = program_spans.window(record, trace)
    if window is None:
        return None
    ticks = program_spans.turn_counts(window, "prefill_ticks")
    if not ticks:
        return None
    windows = program_spans.turn_counts(window, "prefill_windows")
    return program_spans.report(
        "ttft_prefill_ticks_p95", readings.nearest_rank(ticks, 95),
        turns=len(ticks), prefill_ticks=sorted(ticks),
        prefill_windows=sorted(windows))
