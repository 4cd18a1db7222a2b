"""Goodput accounting: where every second of a supervised run went.

The reference paper's throughput story totals wall-clock; a distributed
run's wall-clock is only credible *decomposed* — how much was productive
step time vs compile, checkpoint traffic, restart backoff, and the input
pipeline starving the device.  ``GoodputAccountant`` attributes run time
into named buckets at the sites the repo already hooks (the session's
dispatch/checkpoint spans, the supervisor's backoff sleep, the prefetch
handoff, RetraceGuard's trace events) and renders the split three ways:

* ``dttpu_goodput_seconds_total{bucket=...}`` counters on the metrics
  registry (scrape ``rate()`` for a live goodput fraction),
* a Chrome-trace **counter lane** (``ph: "C"``) on the active tracer, so
  the Perfetto timeline shows the cumulative split as a stacked area
  next to the spans it summarizes,
* a per-run :meth:`report` — wall seconds, per-bucket seconds,
  ``goodput_pct = step / wall`` — that bench rows and chaos tests
  assert against.

**Exclusive time.**  Buckets nest (a retrace fires *inside* a step; a
checkpoint restore happens *inside* fault recovery) and naive interval
sums would double-count.  Accounting is a per-thread stack: entering a
nested bucket pauses the enclosing frame's accrual, so each wall-clock
second lands in exactly one bucket and the measured buckets plus the
derived ``other`` remainder sum to wall by construction.

Pure stdlib, same contract as ``obs.trace``: a module-level *active
accountant* (``activate``/``deactivate``/``account``) serves code that
cannot thread a handle through its API (the prefetch generator, the
RetraceGuard patch); with nothing active, ``account()`` returns a cached
no-op context manager — one module-global ``None`` check on the hot
path.  Measured overhead of an active frame is two ``perf_counter``
reads and one lock acquire (~1 µs; docs/OBSERVABILITY.md §Goodput).

**One ``with`` per boundary.**  A frame IS the site's span: it opens
``obs.trace``'s span of the bucket's name (``SPAN_NAMES``) and accrues
that span's own clock reads, so ``run_step``, the prefetch wait and
``save`` each keep one context manager and one measurement.  With no
accountant active ``account()`` hands back the span alone (the cached
null span when no tracer is active either).
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict, Optional

from . import trace as trace_lib

__all__ = ["BUCKETS", "SPAN_NAMES", "GoodputAccountant", "activate",
           "deactivate", "active", "activated", "account"]

# The attribution vocabulary.  "other" is derived (wall minus the
# measured buckets), never accrued directly — it is where untracked time
# (hook bodies, host-side glue, Python overhead) shows up, which keeps
# the split honest instead of silently inflating a named bucket.
BUCKETS = ("step", "compile", "checkpoint_save", "checkpoint_restore",
           "restart_backoff", "data_stall", "fault_recovery", "other")

_MEASURED = tuple(b for b in BUCKETS if b != "other")

# The span each bucket's frame opens (docs/OBSERVABILITY.md span table);
# a bucket not named here is "goodput.<bucket>".
SPAN_NAMES = {"step": "train.dispatch", "data_stall": "data.prefetch_wait",
              "checkpoint_save": "checkpoint"}


class GoodputAccountant:
    """Attributes wall-clock into exclusive named buckets.

    Args:
      registry: an ``obs.metrics.Registry`` to export
        ``dttpu_goodput_seconds_total{bucket=}`` counters into
        (``None`` = in-process report only).
      trace_counters: mirror every accrual onto the *active* tracer as a
        Chrome ``"C"`` counter event (no-op when no tracer is active).
      clock: injectable monotonic clock (tests).
    """

    def __init__(self, registry=None, trace_counters: bool = True,
                 clock=time.perf_counter):
        self._clock = clock
        self._lock = threading.Lock()
        self._totals: Dict[str, float] = {b: 0.0 for b in _MEASURED}
        self._tls = threading.local()
        self._started_at: Optional[float] = None
        self._stopped_at: Optional[float] = None
        self.trace_counters = trace_counters
        self._counters = None
        if registry is not None:
            self._counters = {
                b: registry.counter(
                    "dttpu_goodput_seconds_total",
                    "Wall-clock seconds attributed to each goodput "
                    "bucket (exclusive; see docs/OBSERVABILITY.md "
                    "Goodput section).", labels={"bucket": b})
                for b in _MEASURED}

    # ------------------------------------------------------------ lifecycle

    def start(self) -> "GoodputAccountant":
        """Stamp the wall-clock origin (idempotent)."""
        if self._started_at is None:
            self._started_at = self._clock()
        return self

    def stop(self) -> "GoodputAccountant":
        """Stamp the wall-clock end; frames still open keep accruing into
        their buckets but the report's wall stops here."""
        if self._stopped_at is None:
            self._stopped_at = self._clock()
        return self

    def __enter__(self) -> "GoodputAccountant":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False

    # ------------------------------------------------------------ accrual

    def _stack(self):
        s = getattr(self._tls, "stack", None)
        if s is None:
            s = self._tls.stack = []
        return s

    def _read(self, span_s: float) -> float:
        """A frame's clock read: its span's own (``perf_counter``
        seconds), unless a test injected another clock."""
        return span_s if self._clock is time.perf_counter else self._clock()

    def _accrue(self, bucket: str, seconds: float) -> None:
        if seconds <= 0.0:
            return
        with self._lock:
            self._totals[bucket] += seconds
            if self.trace_counters:
                lane = dict(self._totals)
            else:
                lane = None
        if self._counters is not None:
            self._counters[bucket].inc(seconds)
        if lane is not None:
            tracer = trace_lib.active_tracer()
            if tracer is not None and tracer.enabled:
                tracer.add_event({"name": "goodput_seconds", "ph": "C",
                                  "ts": trace_lib.now_us(),
                                  "cat": "goodput", "args": lane})

    def account(self, bucket: str, **span_args: Any):
        """Context manager attributing its body's wall time to ``bucket``
        (exclusively: an enclosing frame is paused for the duration) and
        recording it as the bucket's span, ``span_args`` included."""
        if bucket not in _MEASURED:
            raise ValueError(f"unknown goodput bucket {bucket!r}; "
                             f"choices: {_MEASURED}")
        return _Frame(self, bucket, span_args)

    def accrue(self, bucket: str, seconds: float) -> None:
        """Attribute an already-measured duration (no pause semantics —
        for durations measured outside any frame)."""
        if bucket not in _MEASURED:
            raise ValueError(f"unknown goodput bucket {bucket!r}; "
                             f"choices: {_MEASURED}")
        self._accrue(bucket, float(seconds))

    # ------------------------------------------------------------ report

    def wall_seconds(self) -> float:
        if self._started_at is None:
            return 0.0
        end = self._stopped_at if self._stopped_at is not None \
            else self._clock()
        return max(0.0, end - self._started_at)

    def snapshot(self) -> Dict[str, float]:
        """Per-bucket seconds including the derived ``other`` remainder.
        Open frames' in-flight time is NOT included (it accrues on frame
        exit) — call between frames, or after :meth:`stop`."""
        with self._lock:
            out = dict(self._totals)
        wall = self.wall_seconds()
        attributed = sum(out.values())
        out["other"] = max(0.0, wall - attributed)
        return out

    def report(self) -> Dict[str, Any]:
        """The per-run goodput document bench rows embed: wall seconds,
        the bucket split, ``goodput_pct`` (= step/wall), and
        ``coverage_pct`` (measured buckets / wall — how much of the run
        the instrumentation saw; the chaos acceptance asserts the split
        sums to wall within 1%, which holds by construction because
        ``other`` is the remainder)."""
        buckets = self.snapshot()
        wall = self.wall_seconds()
        attributed = sum(v for b, v in buckets.items() if b != "other")
        return {
            "wall_s": round(wall, 6),
            "buckets_s": {b: round(buckets[b], 6) for b in BUCKETS},
            "goodput_pct": round(100.0 * buckets["step"] / wall, 3)
            if wall > 0 else 0.0,
            "coverage_pct": round(100.0 * min(attributed, wall) / wall, 3)
            if wall > 0 else 0.0,
        }


def _span_name(bucket: str) -> str:
    return SPAN_NAMES.get(bucket) or "goodput." + bucket


class _Frame:
    """One accounting frame: pauses the enclosing frame on entry, accrues
    its own exclusive time on exit, resumes the parent.  The frame's
    clock reads are its span's (an injected test clock reads its own)."""

    __slots__ = ("_acct", "_bucket", "_t0", "_span")

    def __init__(self, acct: GoodputAccountant, bucket: str,
                 span_args: Dict[str, Any]):
        self._acct = acct
        self._bucket = bucket
        self._t0 = 0.0
        self._span = trace_lib.timed(_span_name(bucket), **span_args)

    @property
    def duration_s(self) -> float:
        return self._span.duration_s

    def __enter__(self) -> "_Frame":
        acct = self._acct
        self._span.__enter__()
        now = acct._read(self._span.start_s)
        stack = acct._stack()
        if stack:
            parent = stack[-1]
            acct._accrue(parent._bucket, now - parent._t0)
        stack.append(self)
        self._t0 = now
        return self

    def __exit__(self, *exc) -> bool:
        acct = self._acct
        self._span.__exit__(*exc)
        now = acct._read(self._span.end_s)
        stack = acct._stack()
        acct._accrue(self._bucket, now - self._t0)
        # tolerate misnested exits (a generator frame GC'd out of order):
        # drop everything above this frame rather than corrupt the stack
        while stack and stack[-1] is not self:
            stack.pop()
        if stack:
            stack.pop()
        if stack:
            stack[-1]._t0 = now          # resume the parent's accrual
        return False


# ---------------------------------------------------------------------------
# Active accountant: the process-wide sink for code without a handle
# (data/pipeline.py's prefetch wait, RetraceGuard's trace-time hook).

_ACTIVE: Optional[GoodputAccountant] = None
_ACTIVE_LOCK = threading.Lock()


def activate(acct: GoodputAccountant) -> GoodputAccountant:
    global _ACTIVE
    with _ACTIVE_LOCK:
        _ACTIVE = acct
    return acct


def deactivate(acct: Optional[GoodputAccountant] = None) -> None:
    """Clear the active accountant (only if it is ``acct``, when given)."""
    global _ACTIVE
    with _ACTIVE_LOCK:
        if acct is None or _ACTIVE is acct:
            _ACTIVE = None


def active() -> Optional[GoodputAccountant]:
    return _ACTIVE


def account(bucket: str, measure: bool = False, **span_args: Any):
    """Module-level frame: routes to the active accountant.  With none
    active the site is still its span: ``obs.trace.span`` (the cached
    no-op when no tracer is active either: two global reads on the
    disabled path), or ``obs.trace.timed`` when the caller reads
    ``.duration_s`` afterwards (``measure=True``)."""
    a = _ACTIVE
    if a is not None:
        return a.account(bucket, **span_args)
    if measure:
        return trace_lib.timed(_span_name(bucket), **span_args)
    return trace_lib.span(_span_name(bucket), **span_args)


@contextlib.contextmanager
def activated(acct: GoodputAccountant):
    """Scoped activation (tests, bench): starts/stops the accountant and
    restores the previously active one."""
    global _ACTIVE
    with _ACTIVE_LOCK:
        prev, _ACTIVE = _ACTIVE, acct
    acct.start()
    try:
        yield acct
    finally:
        acct.stop()
        with _ACTIVE_LOCK:
            _ACTIVE = prev
