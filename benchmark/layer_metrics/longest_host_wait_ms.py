"""The longest single wait of the device on the host in the window: the
longest ``device.wait_host`` span, clipped to the window
(``harness/host_waits.py``).  Stderr lists each slow tick (over twice the
window's median tick, the ``slow_ticks`` rule) with its time split four
ways: the device's waits on the host that fell in it, the reads' waits for
the device (``device.ready_wait``), the reads' copy to the host (their own
time past that wait) and the rest.  A program whose ticks carry no
``waited_host_us`` (a parent commit) reports nothing."""
from harness import host_waits, program_spans, readings


def read(record, trace):
    window = program_spans.window(record, trace)
    if window is None or not host_waits.has_account(window):
        return None
    spans = window.spans
    inside = host_waits.waits(spans, window.start_us, window.end_us)
    longest = max(inside, key=lambda w: w[2] - w[1], default=None)
    slow = [dict(tick=spans[t].args.get("tick"),
                 **host_waits.tick_split(window, t))
            for t in host_waits.slow_ticks(window,
                                           readings.SLOW_TICK_FACTOR)]
    return program_spans.report(
        "longest_host_wait_ms",
        0.0 if longest is None else (longest[2] - longest[1]) / 1e3,
        longest=None if longest is None else dict(
            spans[longest[0]].args, start_us=longest[1]),
        longest_by_span=None if longest is None
        else host_waits.split_by_span(spans, [longest]),
        slow_ticks=slow)
