"""Share of the window's tick time in which the device had nothing dispatched
and unfetched: tick start -> the first dispatch's start, each fetch's return
-> the next dispatch's start, the last fetch -> tick end.  From the program's
``serve.tick`` spans and their children over the whole window
(``harness/program_spans.py``); stderr carries the split by span name."""
from harness import program_spans


def read(record, trace):
    window = program_spans.window(record, trace)
    if window is None:
        return None
    account = program_spans.tick_exposure(window)
    if account["tick_s"] <= 0:
        return None
    tick_ms = [(window.spans[t].end_us - window.spans[t].start_us) / 1e3
               for t in window.units]
    return program_spans.report(
        "tick_host_exposed_pct",
        100.0 * account["exposed_s"] / account["tick_s"],
        program_tick_ms_p50=program_spans.median(tick_ms), **account)
