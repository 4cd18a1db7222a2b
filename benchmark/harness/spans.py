"""The benchmark's own host spans, around its calls into each layer.

Kept in memory as ``(name, start, end)`` on ``time.perf_counter``.  In a
traced run each span is also a ``jax.profiler.TraceAnnotation`` named
``bench:<name>``, which puts it on the profiler's clock beside the device
operations, so an idle gap on the device can be named by what the host was
doing in it.  Spans inside the program are a later (``tracing``) change.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Tuple

PREFIX = "bench:"


class Spans:
    def __init__(self, annotate: bool = False):
        self.items: List[Tuple[str, float, float]] = []
        self._annotate = annotate

    @contextlib.contextmanager
    def span(self, name: str):
        if self._annotate:
            import jax
            annotation = jax.profiler.TraceAnnotation(PREFIX + name)
        else:
            annotation = contextlib.nullcontext()
        with annotation:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.items.append((name, t0, time.perf_counter()))

    def seconds_by_name(self, start: float, end: float) -> Dict[str, float]:
        """Seconds of each span name clipped to ``[start, end]``."""
        out: Dict[str, float] = {}
        for name, t0, t1 in self.items:
            lo, hi = max(t0, start), min(t1, end)
            if hi > lo:
                out[name] = out.get(name, 0.0) + (hi - lo)
        return out
