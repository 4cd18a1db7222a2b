"""Page-table entries the window's decode steps read, over the entries
their tables hold (steps x slots x pages a slot): the program's own count,
which each ``serve.decode_dispatch`` span carries as ``pages_walked`` and
``pages_table`` (``Engine.stats()`` has the same two, cumulative, as
``decode_pages_walked_total`` / ``decode_pages_table_total``).

100 is a read of every slot's whole table at every step, whatever the slots
hold: the gather read, and the page-walk kernel before it walked only the
pages a live slot's tokens lie on.  It says what share of the table the
traffic fills, so how much a walk of held pages alone can save; it is no
time.  A program whose spans carry no such count (a parent commit) reports
nothing."""
from harness import program_spans

NAME = "decode_pages_walked_pct"
DISPATCH = "serve.decode_dispatch"


def read(record, trace):
    window = program_spans.window(record, trace)
    if window is None:
        return None
    walked = table = dispatches = 0
    for tick in window.units:
        for child in window.children.get(tick, ()):
            span = window.spans[child]
            if span.name == DISPATCH and "pages_table" in span.args:
                dispatches += 1
                walked += int(span.args["pages_walked"])
                table += int(span.args["pages_table"])
    if not table:
        return None
    return program_spans.report(
        NAME, 100.0 * walked / table, decode_dispatches=dispatches,
        pages_walked=walked, pages_table=table)
