"""Share of the window the consumer of ``data.prefetch_to_device`` spent
blocked on the handoff: the program's ``data.prefetch_wait`` spans (the
goodput ``data_stall`` site) clipped to the window, over window time."""
from harness import program_spans


def read(record, trace):
    window = program_spans.window(record, trace)
    if window is None or record["window_s"] <= 0:
        return None
    waited = program_spans.seconds_in_window(
        window, program_spans.PREFETCH_WAIT)
    return program_spans.report(
        "prefetch_wait_pct", 100.0 * waited / record["window_s"],
        wait_s=waited, window_s=record["window_s"])
