"""95th percentile (nearest rank) of a turn's wait from submit to the
admission that started its prefill: the scheduler's ``queue_wait_s`` on each
request's ``reqtrace`` record (the interval ``critpath``'s ``queue_wait`` and
``backpressure_requeue`` phases time), over turns whose first token fell in
the window."""
from harness import program_spans, readings


def read(record, trace):
    window = program_spans.window(record, trace)
    if window is None:
        return None
    waits_ms = [1e3 * s for s in
                program_spans.turn_counts(window, "queue_wait_s")]
    if not waits_ms:
        return None
    return program_spans.report(
        "queue_wait_ms_p95", readings.nearest_rank(waits_ms, 95),
        turns=len(waits_ms), median_ms=program_spans.median(waits_ms),
        max_ms=max(waits_ms))
