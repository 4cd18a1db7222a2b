"""Share of the window in which the device had nothing queued while the
host worked: the sum of the program's ``device.wait_host`` spans, clipped to
the window, over the window (``harness/host_waits.py``).  A lower bound of
the device's idle time that the host caused, over all the window's ticks.

Stderr carries the upper bound (each wait from the last poll that found the
device busy to the next program's queueing), the same from the drain (the
lower bound with the dispatch calls in it), the count of waits, ``unknown``
(outputs found donated by a dispatch the program did not register, over the
run), the time split by the innermost program span that covered it
(``host.gc`` first, ``between_ticks`` where none did) and by the pair of
programs around each wait, and, over the traced segment's ticks, the
account's lower and upper seconds beside the device trace's idle time.  A
program whose ticks carry no ``waited_host_us`` (a parent commit) reports
nothing."""
from harness import host_waits, program_spans


def read(record, trace):
    window = program_spans.window(record, trace)
    if window is None or not host_waits.has_account(window):
        return None
    spans, lo, hi = window.spans, window.start_us, window.end_us
    inside = host_waits.waits(spans, lo, hi)
    lower_us = sum(b - a for _, a, b in inside)
    return program_spans.report(
        "device_waits_host_pct", 100.0 * lower_us / (hi - lo),
        window_s=(hi - lo) / 1e6, lower_s=lower_us / 1e6,
        drained_to_queued_s=host_waits.bracket_us(spans, lo, hi, True) / 1e6,
        upper_s=host_waits.bracket_us(spans, lo, hi) / 1e6,
        waits=sum(b > a for _, a, b in inside),
        unknown=host_waits.unknown(),
        by_span=host_waits.split_by_span(spans, inside),
        by_pair=host_waits.split_by_pair(spans, inside),
        traced_segment=host_waits.segment_check(window, trace))
