"""Router picks that fell on identity (zero-compute) experts, over all the
router's picks for real tokens, prefill and decode: the window's increments
of the program's ``router_picks_identity_total`` / ``router_picks_total``
(``Engine.stats()``), which the spans that read a program's tokens carry as
``router_picks_identity`` / ``router_picks`` (``serve.decode_fetch``, and
``serve.first_token_read`` for the prefill windows before it).

The zero-compute mechanism's share of the routing: a pick on an identity
expert costs no FFN.  Under uniform routing it is ``zero_expert_num / router
outputs`` (a third here).  A program whose spans carry no such count (a
parent commit, a model without experts) reports nothing."""
from harness import program_spans

NAME = "identity_picks_pct"
READS = ("serve.decode_fetch", "serve.first_token_read")


def counting_spans(window):
    """Every span under the window's ticks that read a program's tokens and
    carries the router's counts with them."""
    out, todo = [], list(window.units)
    while todo:
        i = todo.pop()
        todo.extend(window.children.get(i, ()))
        span = window.spans[i]
        if span.name in READS and "router_picks" in span.args:
            out.append(span)
    return out


def read(record, trace):
    window = program_spans.window(record, trace)
    if window is None:
        return None
    spans = counting_spans(window)
    picks = sum(int(s.args["router_picks"]) for s in spans)
    if not picks:
        return None
    identity = sum(int(s.args["router_picks_identity"]) for s in spans)
    held = sum(int(s.args["router_picks_held"]) for s in spans)
    return program_spans.report(
        NAME, 100.0 * identity / picks, router_picks=picks,
        router_picks_identity=identity, router_picks_held=held,
        held_picks_pct=100.0 * held / picks, reads=len(spans))
