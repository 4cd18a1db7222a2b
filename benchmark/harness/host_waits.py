"""The program's own account of its device's waits on the host, read over
the benchmark's window (``harness/program_spans.py`` finds the window).

The program (``obs/trace.py``) records a ``device.wait_host`` span each time
the newest program it dispatched had finished before the next was
dispatched: from the first poll that found that program's output ready
(``drained_us``) to the start of the span that holds the next dispatch.
Each span also carries ``earliest_us`` (the last poll that found it busy)
and ``dispatched_us`` (the next program queued), so over a stretch of time
the device waited on the host at least the spans' length (``lower``) and
at most the sum of ``dispatched_us - earliest_us`` (``upper``).  Each
``serve.tick`` carries ``waited_host_us``, the waits that ended inside it:
a program without the account (a parent commit) has no such argument and
every reader here returns None.

What the host was doing while the device waited is the program span that
covered the time: a ``host.gc`` span (the garbage collector) first, else
the innermost span, else ``between_ticks`` (the benchmark's own loop).
"""
from __future__ import annotations

import bisect
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from distributed_tensorflow_tpu.obs import trace as trace_lib

from . import program_spans

WAIT, READY, GC = "device.wait_host", "device.ready_wait", "host.gc"
TICK_ARG = "waited_host_us"
BETWEEN = "between_ticks"
# the reads whose own time, past their ``device.ready_wait``, is the copy
READS = ("serve.decode_fetch", "serve.first_token_read")


def has_account(win) -> bool:
    """Whether the window's ticks come from a program that keeps it."""
    return any(TICK_ARG in win.spans[t].args for t in win.units)


def unknown() -> Optional[int]:
    """Markers found donated by a dispatch the program did not register,
    over the whole run (no wait is recorded there)."""
    tracer = trace_lib.active_tracer()
    queue = getattr(tracer, "device_queue", None)
    return None if queue is None else int(queue["unknown"])


def waits(spans: Sequence, lo: float, hi: float) -> List[Tuple[int, float,
                                                                float]]:
    """``(index, start, end)`` of every wait, clipped to ``[lo, hi]``, in
    time order; waits of zero length are kept (their upper bound is not)."""
    out = []
    for i, s in enumerate(spans):
        if s.name == WAIT and s.end_us is not None:
            a, b = max(s.start_us, lo), min(s.end_us, hi)
            if b >= a and s.end_us >= lo and s.start_us <= hi:
                out.append((i, a, b))
    out.sort(key=lambda w: w[1])
    return out


def bracket_us(spans: Sequence, lo: float, hi: float,
               from_drain: bool = False) -> float:
    """The waits in ``[lo, hi]``, each from the last poll that saw the
    device busy (``earliest_us``: the most it can have waited) or, with
    ``from_drain``, from its own start, to the next program's queueing
    (``dispatched_us``).  A read that blocks on the newest program cannot
    tell when in its wait that program ended, so the whole read is in the
    first; the second is the lower bound with the dispatch calls added."""
    total = 0.0
    for s in spans:
        if s.name == WAIT and s.end_us is not None:
            a = s.start_us if from_drain else float(s.args["earliest_us"])
            b = float(s.args["dispatched_us"])
            total += max(0.0, min(b, hi) - max(a, lo))
    return total


def _depths(spans: Sequence) -> List[int]:
    depth = [0] * len(spans)
    for i, s in enumerate(spans):
        if s.parent is not None:
            depth[i] = depth[s.parent] + 1
    return depth


def split_by_span(spans: Sequence, intervals: Sequence[Tuple[int, float,
                                                             float]]
                  ) -> Dict[str, float]:
    """Seconds of ``intervals`` by what covered each part: ``host.gc`` over
    any other span, else the innermost program span (deepest, then latest
    started), else ``between_ticks``."""
    ordered = sorted((a, b) for _, a, b in intervals if b > a)
    starts = [a for a, _ in ordered]
    covers: List[List[int]] = [[] for _ in ordered]
    for i, s in enumerate(spans):
        if s.name == WAIT or s.end_us is None:
            continue
        k = bisect.bisect_left(starts, s.end_us) - 1
        while k >= 0 and ordered[k][1] > s.start_us:
            covers[k].append(i)
            k -= 1
    depth = _depths(spans)
    out: Dict[str, float] = {}
    for (a, b), mine in zip(ordered, covers):
        edges = sorted({a, b} | {min(max(t, a), b) for i in mine
                                 for t in (spans[i].start_us,
                                           spans[i].end_us)})
        for lo, hi in zip(edges, edges[1:]):
            over = [i for i in mine
                    if spans[i].start_us <= lo and spans[i].end_us >= hi]
            gc = [i for i in over if spans[i].name == GC]
            if gc:
                name = GC
            elif over:
                name = spans[max(over, key=lambda i: (
                    depth[i], spans[i].start_us))].name
            else:
                name = BETWEEN
            out[name] = out.get(name, 0.0) + (hi - lo) / 1e6
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def split_by_pair(spans: Sequence, intervals) -> Dict[str, float]:
    """Seconds by the program that drained and the dispatch that ended the
    wait: ``"after -> before"``."""
    out: Dict[str, float] = {}
    for i, a, b in intervals:
        args = spans[i].args
        key = f"{args.get('after')} -> {args.get('before')}"
        out[key] = out.get(key, 0.0) + (b - a) / 1e6
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def segment_check(win, trace) -> Optional[Dict[str, object]]:
    """The account over the traced segment's ticks (those after the
    window's last one) beside the device trace's idle time there."""
    spans = win.spans
    ticks = [i for i in program_spans._closed(spans, program_spans.TICK)
             if i > win.units[-1]]
    if not ticks or trace is None:
        return None
    lo, hi = spans[ticks[0]].start_us, spans[ticks[-1]].end_us
    inside = waits(spans, lo, hi)
    gaps = dict(trace.idle_gaps)
    idle_s = (trace.idle_share or 0.0) * trace.window_s
    return {
        "ticks": len(ticks), "ticks_s": (hi - lo) / 1e6,
        "lower_s": sum(b - a for _, a, b in inside) / 1e6,
        "drained_to_queued_s": bracket_us(spans, lo, hi, True) / 1e6,
        "upper_s": bracket_us(spans, lo, hi) / 1e6,
        "trace_window_s": trace.window_s, "trace_idle_s": idle_s,
        "trace_idle_outside_short_gaps_s": idle_s - gaps.get(
            "between_ops_under_20us", 0.0),
        "trace_idle_gaps": [[n, s] for n, s in trace.idle_gaps],
        "by_span": split_by_span(spans, inside)}


def _overlap_us(xs: Sequence[Tuple[float, float]],
                ys: Sequence[Tuple[float, float]]) -> float:
    return sum(max(0.0, min(b, d) - max(a, c)) for a, b in xs for c, d in ys)


def tick_split(win, tick: int) -> Dict[str, float]:
    """One tick's time in ms, four ways that add up to it: the reads'
    waits for the device (``device.ready_wait``), the reads' own time past
    those (the copy to the host), the device's waits on the host that fell
    in the tick outside that copy, and the rest; also the copy's share of
    those waits, the collector's time and where the tick's own time went
    by span."""
    spans = win.spans
    t = spans[tick]
    lo, hi = t.start_us, t.end_us
    ready_us = 0.0
    copies: List[Tuple[float, float]] = []
    todo = list(win.children.get(tick, ()))
    while todo:
        i = todo.pop()
        todo.extend(win.children.get(i, ()))
        s = spans[i]
        if s.name == READY:
            ready_us += s.end_us - s.start_us
        elif s.name in READS:
            at = s.start_us
            for c in sorted((c for c in win.children.get(i, ())
                             if spans[c].name == READY),
                            key=lambda c: spans[c].start_us):
                copies.append((at, spans[c].start_us))
                at = spans[c].end_us
            copies.append((at, s.end_us))
    waited = [(a, b) for _, a, b in waits(spans, lo, hi)]
    copy_us = sum(b - a for a, b in copies)
    in_copy_us = _overlap_us(waited, copies)
    wait_us = sum(b - a for a, b in waited) - in_copy_us
    tick_us = hi - lo
    return {"tick_ms": tick_us / 1e3, "wait_host_ms": wait_us / 1e3,
            "ready_wait_ms": ready_us / 1e3, "transfer_ms": copy_us / 1e3,
            "rest_ms": (tick_us - wait_us - ready_us - copy_us) / 1e3,
            "wait_host_in_transfer_ms": in_copy_us / 1e3,
            "gc_ms": sum(s.end_us - s.start_us for s in spans
                         if s.name == GC and s.end_us is not None
                         and lo <= s.start_us < hi) / 1e3,
            "self_ms_by_span": _self_ms(win, tick)}


def _self_ms(win, tick: int, top: int = 5) -> Dict[str, float]:
    """The tick's subtree by the span whose own time it is (ms), the
    largest ``top``: where a slow tick's rest went."""
    own = trace_lib.self_times_us(win.spans)
    out: Dict[str, float] = {}
    todo = [tick]
    while todo:
        i = todo.pop()
        todo.extend(win.children.get(i, ()))
        name = win.spans[i].name
        out[name] = out.get(name, 0.0) + own[i] / 1e3
    return dict(sorted(out.items(), key=lambda kv: -kv[1])[:top])


def slow_ticks(win, factor: float) -> List[int]:
    """The window's ticks over ``factor`` x the median tick (the
    ``slow_ticks`` rule, on the program's own tick spans)."""
    spans = win.spans
    lengths = [spans[t].end_us - spans[t].start_us for t in win.units]
    if not lengths:
        return []
    limit = factor * statistics.median(lengths)
    return [t for t, n in zip(win.units, lengths) if n > limit]
