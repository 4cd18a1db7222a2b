"""The program's own spans and counts, read in its process after the run.

``run.py`` imports a cell's readers only under ``--trace 1`` and before the
driver builds anything, so importing this module is the on-switch: it makes
an ``obs.trace.Tracer`` the program's active tracer (if none is), and from
then on every ``obs.trace.span`` in the serve tick and the train step is
recorded, on ``time.perf_counter``'s clock, and every request carries a
``reqtrace`` record.  A ``--trace 0`` run never loads this file and measures
with tracing off.

The driver's ``record`` carries durations, not instants, so the window is
found by its shape: the ``len(record["tick_seconds"])`` consecutive
``serve.tick`` spans before the traced segment's ticks (the trailing ticks
whose extent fits ``trace.window_s``) — or, training, the readings' worth of
``train.step`` spans before the ``record["traced_steps"]`` steps the driver
dispatched in the traced segment — and then VERIFIED one by
one against the driver's own list: the program's tick lies inside the
benchmark's, shorter by under 2 ms; a reading's first step starts where the
reading before it ended.  Ticks differ 2.8-fold by what they hold, so a
shifted choice cannot pass.  If the guess fails, its neighbours are tried the
same way; if none passes, every reader here returns None and stderr says why:
a metric that is missing shows, one over the wrong ticks would not.

On a program without the span spine (a parent commit) and on a CPU rehearsal
(whose ticks are not the chip's) the readers return None and raise nothing.
"""
from __future__ import annotations

import dataclasses
import json
import statistics
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

from distributed_tensorflow_tpu.obs import reqtrace
from distributed_tensorflow_tpu.obs import trace as trace_lib

TOLERANCE_S = 0.002          # program interval inside the benchmark's
RING = 4096                  # completed-request records kept: a window's turns
TICK, STEP, DISPATCH_STEP = "serve.tick", "train.step", "train.dispatch"
PREFETCH_WAIT = "data.prefetch_wait"
# in a tick, the device has work from a dispatch's start to the return of
# the next fetch (one in-order stream: a fetch returns after all before it)
DISPATCHES = ("serve.prefill_dispatch", "serve.decode_dispatch")
FETCHES = ("serve.first_token_fetch", "serve.decode_fetch")

HAS_SPINE = hasattr(trace_lib, "to_perf_counter_s")
ACTIVATED = None             # the tracer this import switched on, if it did
if HAS_SPINE:
    if trace_lib.active_tracer() is None:
        ACTIVATED = trace_lib.activate(trace_lib.Tracer())
    reqtrace.configure(ring=RING)


def _say(message: str) -> None:
    print(f"[benchmark] program_spans: {message}", file=sys.stderr,
          flush=True)


@dataclasses.dataclass
class Window:
    spans: list                  # every SpanRecord of the tracer
    units: List[int]             # indices of the window's ticks / steps
    start_us: float
    end_us: float
    children: Dict[int, List[int]]


def _closed(spans: Sequence, name: str) -> List[int]:
    return [i for i, s in enumerate(spans)
            if s.name == name and s.end_us is not None]


def _children(spans: Sequence) -> Dict[int, List[int]]:
    out: Dict[int, List[int]] = {}
    for i, s in enumerate(spans):
        if s.parent is not None and s.end_us is not None:
            out.setdefault(s.parent, []).append(i)
    return out


def _trailing(spans: Sequence, units: Sequence[int], fit_us: float) -> int:
    """How many of the last ``units`` lie within ``fit_us`` of the last
    one's end: the traced segment's."""
    if not units:
        return 0
    end = spans[units[-1]].end_us
    count = 0
    while (count < len(units)
           and end - spans[units[-1 - count]].start_us <= fit_us):
        count += 1
    return count


def _search(total: int, need: int, guess: int, check) -> Tuple[
        Optional[int], str]:
    """The number of trailing units to skip so that ``check(skip)`` (None =
    verified, else why not) passes: the guess first, then its neighbours."""
    if need <= 0 or total < need:
        return None, f"{total} spans, {need} needed"
    most = total - need
    first_why = ""
    order = sorted(range(most + 1), key=lambda k: (abs(k - guess), k))
    for skip in order:
        why = check(skip)
        if why is None:
            if skip != guess:
                _say(f"the guess (skip {guess} trailing) failed [{first_why}]"
                     f"; skip {skip} verified instead")
            return skip, ""
        first_why = first_why or why
    return None, first_why


def select_ticks(spans: Sequence, tick_seconds: Sequence[float],
                 traced_window_s: float) -> Tuple[Optional[List[int]], str]:
    """Indices of the ``serve.tick`` spans of the benchmark's window, or
    (None, why).  Each program tick must lie inside its benchmark tick:
    ``0 <= benchmark - program < TOLERANCE_S``."""
    ticks = _closed(spans, TICK)
    need = len(tick_seconds)

    def check(skip: int) -> Optional[str]:
        chosen = ticks[len(ticks) - skip - need:len(ticks) - skip]
        for k, (i, outside) in enumerate(zip(chosen, tick_seconds)):
            inside = (spans[i].end_us - spans[i].start_us) / 1e6
            if not -1e-6 <= outside - inside < TOLERANCE_S:
                return (f"tick {k} of {need}: program {inside:.6f} s, "
                        f"benchmark {outside:.6f} s")
        return None

    skip, why = _search(len(ticks), need,
                        _trailing(spans, ticks, traced_window_s * 1e6), check)
    if skip is None:
        return None, why
    return ticks[len(ticks) - skip - need:len(ticks) - skip], ""


def _reading_anchor(spans: Sequence, waits: Sequence[int], step: int,
                    previous_end_us: float) -> float:
    """Where the reading that ``step`` opens began: the start of the
    prefetch wait just before it (``next(batches)`` is the reading's first
    act), else the step's own start."""
    start = spans[step].start_us
    best = start
    for w in waits:
        if previous_end_us <= spans[w].start_us <= start:
            best = min(best, spans[w].start_us)
    return best


def select_steps(spans: Sequence, reading_seconds: Sequence[float],
                 steps_per_reading: int, traced_steps: int
                 ) -> Tuple[Optional[List[int]], str]:
    """Indices of the ``train.step`` spans of the benchmark's window, or
    (None, why).  Readings follow one another without a gap, so reading
    k+1's first step starts ``reading_seconds[k]`` after reading k's did
    (to TOLERANCE_S), and the traced segment's first step no earlier than
    that after the last one's.

    The first choice skips the ``traced_steps`` steps the driver says it
    dispatched after the window.  It was the trailing steps whose extent
    fits the traced window; but that window ends with the fetch of a step
    dispatched a reading earlier, so it reaches a reading's length past the
    last dispatch, and counted back from there it holds the window's last
    reading too unless the profiler took longer to start than the steps are
    apart: true on the chip (by twice that start-up), not of a cell whose
    steps last milliseconds.  Steady readings are alike to under TOLERANCE_S,
    so the verification cannot tell a choice one reading early from the
    right one; the first choice has to be right, and a count is."""
    steps = _closed(spans, STEP)
    waits = _closed(spans, PREFETCH_WAIT)
    per = int(steps_per_reading)
    need = len(reading_seconds) * per

    def anchors(chosen: Sequence[int], after: Optional[int]) -> List[float]:
        firsts = list(chosen[::per]) + ([after] if after is not None else [])
        out = []
        for first in firsts:
            before = steps.index(first) - 1
            previous_end = spans[steps[before]].end_us if before >= 0 else 0.0
            out.append(_reading_anchor(spans, waits, first, previous_end))
        return out

    def check(skip: int) -> Optional[str]:
        lo = len(steps) - skip - need
        chosen = steps[lo:lo + need]
        after = steps[lo + need] if skip else None
        starts = anchors(chosen, after)
        for k, outside in enumerate(reading_seconds):
            if k + 1 >= len(starts):
                break
            inside = (starts[k + 1] - starts[k]) / 1e6
            last = k == len(reading_seconds) - 1
            ok = (inside >= outside - TOLERANCE_S if last
                  else abs(inside - outside) < TOLERANCE_S)
            if not ok:
                return (f"reading {k} of {len(reading_seconds)}: program "
                        f"{inside:.6f} s, benchmark {outside:.6f} s")
        return None

    skip, why = _search(len(steps), need, int(traced_steps), check)
    if skip is None:
        return None, why
    lo = len(steps) - skip - need
    return steps[lo:lo + need], ""


# ------------------------------------------------------- what readers call

_LAST: list = [None, None]       # the record last looked at, and its window


def _program_spans() -> Optional[list]:
    tracer = trace_lib.active_tracer()
    if tracer is None or not hasattr(tracer, "spans"):
        _say("no active tracer with spans")
        return None
    return tracer.spans()


def window(record: Dict[str, Any], trace) -> Optional[Window]:
    """The benchmark's window among the program's spans, verified; None
    (and one stderr line) when it cannot be, on a program from before the
    span spine, and on a CPU rehearsal."""
    if _LAST[0] is record:       # a cell's readers share one record
        return _LAST[1]
    found = spans = None
    if record.get("platform") == "cpu":
        _say("a CPU rehearsal: its ticks are not the chip's, nothing read")
    elif HAS_SPINE and trace is not None:
        spans = _program_spans()
    if spans is not None:
        if record["kind"] == "serve":
            units, why = select_ticks(spans, record["tick_seconds"],
                                      trace.window_s)
        else:
            units, why = select_steps(spans, record["reading_seconds"],
                                      record["steps_per_reading"],
                                      record["traced_steps"])
        if units is None:
            _say(f"window NOT verified, no metric reported: {why}")
        else:
            found = make_window(spans, units, record)
            _say(f"window verified: {len(units)} {record['kind']} spans")
    _LAST[:] = [record, found]
    return found


def make_window(spans: Sequence, units: List[int],
                record: Dict[str, Any]) -> Window:
    if record["kind"] == "serve":
        start, end = spans[units[0]].start_us, spans[units[-1]].end_us
    else:
        steps = _closed(spans, STEP)
        before = steps.index(units[0]) - 1
        start = _reading_anchor(
            spans, _closed(spans, PREFETCH_WAIT), units[0],
            spans[steps[before]].end_us if before >= 0 else 0.0)
        end = start + record["window_s"] * 1e6
    return Window(list(spans), units, start, end, _children(spans))


# ------------------------------------------------------------ serving

def tick_exposure(win: Window) -> Dict[str, Any]:
    """Over the window's ticks: seconds of tick time, seconds of it with
    nothing dispatched and unfetched (tick start -> first dispatch's start,
    a fetch's return -> next dispatch's start, last fetch -> tick end), and
    both split by the span whose self time it is."""
    spans, children = win.spans, win.children
    self_us: Dict[str, float] = {}
    exposed_us: Dict[str, float] = {}
    state = {"inflight": False}

    def account(name: str, a: float, b: float) -> None:
        if b > a:
            self_us[name] = self_us.get(name, 0.0) + (b - a)
            if not state["inflight"]:
                exposed_us[name] = exposed_us.get(name, 0.0) + (b - a)

    def walk(i: int) -> None:
        s = spans[i]
        if s.name in DISPATCHES:
            state["inflight"] = True
        cursor = s.start_us
        for c in sorted(children.get(i, ()),
                        key=lambda c: spans[c].start_us):
            account(s.name, cursor, spans[c].start_us)
            walk(c)
            cursor = spans[c].end_us
        account(s.name, cursor, s.end_us)
        if s.name in FETCHES:
            state["inflight"] = False

    for t in win.units:
        walk(t)
    tick_us = sum(spans[t].end_us - spans[t].start_us for t in win.units)
    return {"tick_s": tick_us / 1e6,
            "exposed_s": sum(exposed_us.values()) / 1e6,
            "self_s_by_span": {k: v / 1e6 for k, v in sorted(self_us.items())},
            "exposed_s_by_span": {k: v / 1e6
                                  for k, v in sorted(exposed_us.items())},
            # the program's own helper, as a check: a tick's subtree of
            # self times is the tick
            "self_times_sum_to_ticks_s": _subtree_self_s(win)}


def _subtree_self_s(win: Window) -> float:
    own = trace_lib.self_times_us(win.spans)
    total, todo = 0.0, list(win.units)
    while todo:
        i = todo.pop()
        total += own[i]
        todo.extend(win.children.get(i, ()))
    return total / 1e6


def tick_args(win: Window, key: str) -> List[float]:
    return [float(win.spans[t].args[key]) for t in win.units
            if key in win.spans[t].args]


def turn_counts(win: Window, key: str) -> List[float]:
    """``counts[key]`` of every request record, finished or live, whose
    ``first_token`` mark fell in the window."""
    records = reqtrace.completed() + [
        r for r in map(reqtrace.lookup, reqtrace.live_ids()) if r]
    out = []
    for r in records:
        first = [e["ts"] for e in r["events"] if e["name"] == "first_token"]
        if (first and win.start_us <= first[0] <= win.end_us
                and key in r.get("counts", {})):
            out.append(float(r["counts"][key]))
    return out


# ------------------------------------------------------------ training

def seconds_in_window(win: Window, name: str) -> float:
    total = 0.0
    for s in win.spans:
        if s.name == name and s.end_us is not None:
            lo, hi = max(s.start_us, win.start_us), min(s.end_us, win.end_us)
            if hi > lo:
                total += hi - lo
    return total / 1e6


def child_durations_s(win: Window, name: str) -> List[float]:
    """Durations of the window's steps' direct children named ``name``."""
    return [(win.spans[c].end_us - win.spans[c].start_us) / 1e6
            for u in win.units for c in win.children.get(u, ())
            if win.spans[c].name == name]


def report(name: str, value: Optional[float], **extra: Any) -> Optional[float]:
    """One stderr line per metric with what it was made of (the driver's
    JSON lines are run.py's; a reader has no handle on them)."""
    _say(json.dumps({"metric": name, "value": value, **extra}))
    return value


def median(values: Sequence[float]) -> Optional[float]:
    return statistics.median(values) if values else None
