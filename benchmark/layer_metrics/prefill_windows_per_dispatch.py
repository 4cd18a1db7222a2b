"""Prefill windows a window program holds, over the window: the sum of
``real`` (the windows in a dispatch) over the count of
``serve.prefill_dispatch`` spans, found anywhere under the window's
``serve.tick`` spans (``Engine.stats()`` has the same two, cumulative, as
``prefill_windows_total`` / ``prefill_dispatches_total``).

How often batching engages: 1 is a program a window, each re-reading every
weight for its 32 tokens; the windows a tick dispatches together read them
once, up to the ladder's largest row count a program.  Each span also
carries ``rows`` (the rung its group was padded to), so the padding's share
is on the stderr line.  A program whose spans carry no ``real`` (a parent
commit: a dispatch there is one window) reports nothing."""
from harness import program_spans

NAME = "prefill_windows_per_dispatch"
DISPATCH = "serve.prefill_dispatch"


def dispatches(window):
    """Every window dispatch under the window's ticks that says what it
    held."""
    out, todo = [], list(window.units)
    while todo:
        i = todo.pop()
        todo.extend(window.children.get(i, ()))
        span = window.spans[i]
        if span.name == DISPATCH and "real" in span.args:
            out.append(span)
    return out


def read(record, trace):
    window = program_spans.window(record, trace)
    if window is None:
        return None
    spans = dispatches(window)
    if not spans:
        return None
    real = sum(int(s.args["real"]) for s in spans)
    rows = sum(int(s.args["rows"]) for s in spans)
    by_rows = {}
    for s in spans:
        by_rows[int(s.args["rows"])] = by_rows.get(int(s.args["rows"]), 0) + 1
    return program_spans.report(
        NAME, real / len(spans), dispatches=len(spans), windows=real,
        rows=rows, padding_rows_pct=100.0 * (rows - real) / rows,
        dispatches_by_rows=dict(sorted(by_rows.items())),
        admitting_dispatches=sum(bool(s.args.get("last")) for s in spans))
