"""What every cell's driver is handed, and what it hands back."""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

from . import device as device_lib
from . import spans as spans_lib
from . import trace as trace_lib


@dataclasses.dataclass
class Run:
    """One run of one cell: the arguments, the devices and the recorders."""
    t0: float                      # host clock at process start
    seed: int
    seconds: float
    trace: bool
    devices: List[Any]
    spans: spans_lib.Spans
    compiles: device_lib.CompileCounter
    emit: Callable[[Dict[str, Any]], None]   # one earlier JSON line
    trace_dir: str

    def now(self) -> float:
        return time.perf_counter()


@dataclasses.dataclass
class Outcome:
    """What a driver returns: the end-to-end values it took itself, the
    record the per-layer readers read, and the facts of the last line.
    ``compared`` is every number that decided ``correct`` beside its limit:
    ``{name: {"value": v, "limit": l, "holds": "<=" or ">="}}``."""
    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, Optional[float]]
    record: Dict[str, Any]
    memory: Dict[str, Any]
    compared: Dict[str, Dict[str, float]]
    reduced: Optional[trace_lib.Reduced] = None
    per_layer: Dict[str, Any] = dataclasses.field(default_factory=dict)


def prng_key(seed: int):
    """A JAX key from any whole number up to 2**62: ``PRNGKey`` takes 32
    bits, the driver's seeds pass 2**31."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed >> 31),
                              seed & 0x7FFFFFFF)


def parts(t0: float, marks) -> Dict[str, float]:
    """``[(name, instant), ...]`` in order -> seconds each part took."""
    out, last = {}, t0
    for name, instant in marks:
        out[name], last = instant - last, instant
    return out


def traced_segment(run: Run, body: Callable[[], None]) -> trace_lib.Reduced:
    """Run ``body`` under the profiler inside one ``trace_window`` span and
    reduce what it recorded."""
    trace_lib.start(run.trace_dir)
    try:
        with run.spans.span(trace_lib.WINDOW_SPAN):
            body()
    finally:
        trace_lib.stop()
    return trace_lib.reduce(trace_lib.load(run.trace_dir))
