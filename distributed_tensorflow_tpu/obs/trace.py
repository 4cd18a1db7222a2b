"""Host-timeline tracing: a Chrome-trace-event / Perfetto JSON recorder.

The reference's observability story ends at ``tf.summary`` scalars; a
production run needs to answer "where did the step time go" without a
debugger.  This module records *host-side* spans — ``span("data_load")``,
``span("dispatch")``, ``span("checkpoint")`` — and instant events (jit
compiles/retraces, session lifecycle marks) into the Chrome trace-event
JSON format, so one step of a training run opens in ``chrome://tracing``
or https://ui.perfetto.dev as a timeline.

Spans time the HOST, which is exactly the honest thing to time under
async dispatch (a span around a jitted call measures dispatch; the
completion barrier is wherever the caller fetches a value — see dtlint
rule DT107 for the anti-pattern this prevents).  No span adds a host
sync.  ``span(name, **args)`` is the ONE way the program times a host
interval: a span records its name, start, end, the span that caused it
(the enclosing span on the same thread) and its args, in memory, on
``perf_counter_ns`` (``to_perf_counter_s`` converts to
``time.perf_counter()`` seconds); ``Tracer.spans()`` is the reader-side
view and ``self_times_us`` what each span did not hand to a child.
While a tracer records it, a span is also a
``jax.profiler.TraceAnnotation("dttpu:" + name)``, so any profiler
capture shows the program's spans on ``/host:CPU`` beside the device
operations, on the profiler's clock.  JAX is imported lazily, by the
first recorded span: this module stays importable without it, and with
no tracer active ``span()`` returns a cached no-op context manager (one
global read, no allocation, no annotation, no import).  ``timed()`` is
for the few intervals whose length the program itself consumes (the
pump heartbeat, a critical-path phase, a goodput bucket): it always
measures, and records only when a tracer is active — one clock-read
pair feeds every consumer.

Multi-host: every process writes its own file, but events carry the
JAX process index as the Chrome ``pid`` (plus a ``process_name``
metadata record naming the host and OS pid), so concatenating the
per-host ``traceEvents`` lists — or loading the files together in
Perfetto — merges the hosts into one timeline with one row group per
host.

Module-level *active tracer*: ``activate(tracer)`` makes a tracer the
process-wide sink for code that cannot thread a handle through its API
(``analysis.sanitizer.RetraceGuard`` emits retrace instants this way).
``instant(...)``/``span(...)`` module functions route to it and no-op
when nothing is active.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

__all__ = ["Tracer", "SpanRecord", "activate", "activated", "deactivate",
           "active_tracer", "span", "timed", "instant", "now_us",
           "to_perf_counter_s", "self_times_us", "ANNOTATION_PREFIX"]

ANNOTATION_PREFIX = "dttpu:"      # the spans' names in a profiler capture

# perf_counter_ns is monotonic but has an arbitrary epoch; anchor it once
# so ts values are comparable across tracers in one process.
_EPOCH_NS = time.perf_counter_ns()


class _NullSpan:
    """Cached no-op context manager for the disabled-tracer fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args: Any) -> None:
        pass


_NULL_SPAN = _NullSpan()


def now_us() -> float:
    """Microseconds on the tracer clock (monotonic, process-anchored) —
    for callers recording retroactive spans via ``Tracer.add_span``."""
    return (time.perf_counter_ns() - _EPOCH_NS) / 1e3


def to_perf_counter_s(ts_us: float) -> float:
    """A tracer timestamp as ``time.perf_counter()`` seconds (the
    clock heartbeats, request stamps and the benchmark read)."""
    return (ts_us * 1e3 + _EPOCH_NS) / 1e9


class SpanRecord(NamedTuple):
    """One span as a reader sees it.  ``parent`` indexes the list
    ``Tracer.spans()`` returned (None for a root); ``end_us`` is None
    while the span is open."""
    name: str
    start_us: float
    end_us: Optional[float]
    parent: Optional[int]
    args: Dict[str, Any]
    tid: int


def self_times_us(spans: Sequence[SpanRecord]) -> List[float]:
    """Per span, its duration minus what its child spans cover (children
    of one thread's span follow one another, so their durations add).
    A span and all below it therefore sum to the span's own duration.
    Open spans, and children of other lists' spans, count as nothing."""
    out = [0.0 if s.end_us is None else s.end_us - s.start_us
           for s in spans]
    for s in spans:
        if s.parent is not None and s.end_us is not None:
            out[s.parent] -= s.end_us - s.start_us
    return out


# jax.profiler.TraceAnnotation, resolved by the first recorded span:
# False = not looked for yet, None = JAX is not installed.
_ANNOTATION: Any = False


def _annotation_cls():
    global _ANNOTATION
    if _ANNOTATION is False:
        try:
            from jax.profiler import TraceAnnotation
            _ANNOTATION = TraceAnnotation
        except Exception:              # no JAX: spans stay in memory only
            _ANNOTATION = None
    return _ANNOTATION


class Tracer:
    """Collects Chrome trace events in memory; ``save()`` writes JSON.

    Args:
      enabled: a disabled tracer's record methods are no-ops (cheap to
        leave wired in).
      pid: the Chrome "process" lane — conventionally the multi-host
        process index so per-host files merge into one timeline.
      host: human label for the process lane ("host0"); defaults to
        ``host{pid}``.
    """

    def __init__(self, enabled: bool = True, pid: int = 0,
                 host: Optional[str] = None):
        self.enabled = enabled
        self.pid = int(pid)
        self.host = host or f"host{self.pid}"
        self._lock = threading.Lock()
        self._events: List[Dict[str, Any]] = []
        # span rows [name, start_us, end_us, parent, args, tid], appended
        # when a span OPENS so a child can name its parent's index
        self._spans: List[list] = []
        self._tls = threading.local()
        self.instant_counts: Dict[str, int] = {}
        self._add_metadata()

    # ------------------------------------------------------------ record

    def _add_metadata(self) -> None:
        # ph "M" metadata records name the process lane; the OS pid rides
        # along so a merged multi-host timeline still identifies processes.
        self._events.append({
            "name": "process_name", "ph": "M", "pid": self.pid, "tid": 0,
            "args": {"name": f"{self.host} (os pid {os.getpid()})"}})

    _now_us = staticmethod(now_us)

    def span(self, name: str, **args: Any):
        """Context manager recording a span around its body: a complete
        ("X") event on the timeline, a row of ``spans()``."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args)

    def add_span(self, name: str, start_us: float, end_us: float,
                 **args: Any) -> None:
        """Record an already-measured span (retroactive: no parent, no
        profiler annotation)."""
        if not self.enabled:
            return
        row = [name, start_us, max(start_us, end_us), None, args,
               threading.get_ident() & 0xFFFFFFFF]
        with self._lock:
            self._spans.append(row)

    def _open(self, name: str, start_us: float, args: Dict[str, Any]
              ) -> int:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        row = [name, start_us, None, stack[-1] if stack else None, args,
               threading.get_ident() & 0xFFFFFFFF]
        with self._lock:
            index = len(self._spans)
            self._spans.append(row)
        stack.append(index)
        return index

    def _close(self, index: int, end_us: float) -> None:
        stack = getattr(self._tls, "stack", ())
        # tolerate a misnested exit (a generator's span closed out of
        # order): drop what was opened above it
        while stack and stack.pop() != index:
            pass
        self._spans[index][2] = end_us

    def add_event(self, event: Dict[str, Any]) -> None:
        """Record a raw Chrome trace event (async ``b``/``n``/``e``
        lifecycle phases, flow ``s``/``f`` arrows — shapes the typed
        helpers above don't cover; ``obs.reqtrace`` is the producer).
        The caller supplies ``ts``/``ph``/``cat``/``id``; ``pid`` and
        ``tid`` default to this tracer's lane and the calling thread."""
        if not self.enabled:
            return
        event.setdefault("pid", self.pid)
        event.setdefault("tid", threading.get_ident() & 0xFFFFFFFF)
        with self._lock:
            self._events.append(event)

    def instant(self, name: str, **args: Any) -> None:
        """Record an instant ("i") event — compiles, retraces, marks."""
        if not self.enabled:
            return
        event = {"name": name, "ph": "i", "s": "p", "ts": self._now_us(),
                 "pid": self.pid,
                 "tid": threading.get_ident() & 0xFFFFFFFF, "cat": "host"}
        if args:
            event["args"] = args
        with self._lock:
            self._events.append(event)
            self.instant_counts[name] = self.instant_counts.get(name, 0) + 1

    # ------------------------------------------------------------ output

    def spans(self) -> List[SpanRecord]:
        """Every span so far, in the order they opened."""
        with self._lock:
            return [SpanRecord(*row) for row in self._spans]

    def events(self) -> List[Dict[str, Any]]:
        """The Chrome trace events: metadata, instants and raw events as
        recorded, then one complete ("X") event per closed span."""
        with self._lock:
            out = list(self._events)
            rows = [tuple(row) for row in self._spans]
        for name, start, end, _, args, tid in rows:
            if end is None:
                continue
            event = {"name": name, "ph": "X", "ts": start,
                     "dur": end - start, "pid": self.pid, "tid": tid,
                     "cat": "host"}
            if args:
                event["args"] = dict(args)
            out.append(event)
        return out

    def to_dict(self) -> Dict[str, Any]:
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.to_dict(), f)
        return path


class _Span:
    """One measured interval.  With a tracer it is recorded (a row, an
    "X" event, a profiler annotation that encloses the two clock reads);
    without one (``timed()`` with tracing off) it is a stopwatch.  After
    the block: ``start_s`` / ``end_s`` (``time.perf_counter()`` seconds)
    and ``duration_s``.  ``set()`` adds args known only at the end."""

    __slots__ = ("_tracer", "_name", "_args", "_index", "_annotation",
                 "_t0", "_t1")

    def __init__(self, tracer: Optional[Tracer], name: str,
                 args: Dict[str, Any]):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._annotation = None
        self._t0 = self._t1 = 0

    def __enter__(self) -> "_Span":
        tracer = self._tracer
        if tracer is not None:
            cls = _annotation_cls()
            if cls is not None:
                self._annotation = cls(ANNOTATION_PREFIX + self._name)
                self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        if tracer is not None:
            self._index = tracer._open(
                self._name, (self._t0 - _EPOCH_NS) / 1e3, self._args)
        return self

    def __exit__(self, *exc) -> bool:
        self._t1 = time.perf_counter_ns()
        if self._tracer is not None:
            self._tracer._close(self._index, (self._t1 - _EPOCH_NS) / 1e3)
            if self._annotation is not None:
                self._annotation.__exit__(*exc)
        return False

    def set(self, **args: Any) -> None:
        self._args.update(args)

    @property
    def start_s(self) -> float:
        return self._t0 / 1e9

    @property
    def end_s(self) -> float:
        return self._t1 / 1e9

    @property
    def duration_s(self) -> float:
        return (self._t1 - self._t0) / 1e9


# ---------------------------------------------------------------------------
# Active tracer: the process-wide sink for code without a handle.

_ACTIVE: Optional[Tracer] = None
_ACTIVE_LOCK = threading.Lock()


def activate(tracer: Tracer) -> Tracer:
    global _ACTIVE
    with _ACTIVE_LOCK:
        _ACTIVE = tracer
    return tracer


def deactivate(tracer: Optional[Tracer] = None) -> None:
    """Clear the active tracer (only if it is ``tracer``, when given)."""
    global _ACTIVE
    with _ACTIVE_LOCK:
        if tracer is None or _ACTIVE is tracer:
            _ACTIVE = None


def active_tracer() -> Optional[Tracer]:
    return _ACTIVE


def instant(name: str, **args: Any) -> None:
    t = _ACTIVE
    if t is not None:
        t.instant(name, **args)


def span(name: str, **args: Any):
    t = _ACTIVE
    if t is None:
        return _NULL_SPAN
    return t.span(name, **args)


def timed(name: str, **args: Any) -> _Span:
    """A span that always measures — for an interval whose length the
    program itself uses (``.start_s`` / ``.end_s`` / ``.duration_s``
    after the block).  Recorded like ``span()`` when a tracer is active,
    a bare stopwatch when none is."""
    t = _ACTIVE
    return _Span(t if t is not None and t.enabled else None, name, args)


@contextlib.contextmanager
def activated(tracer: Tracer):
    """Scoped activation (tests, bench): restores the previous tracer."""
    global _ACTIVE
    with _ACTIVE_LOCK:
        prev, _ACTIVE = _ACTIVE, tracer
    try:
        yield tracer
    finally:
        with _ACTIVE_LOCK:
            _ACTIVE = prev
