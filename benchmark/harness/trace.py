"""From a profiler trace to the device's account of a window.

Two halves, kept apart so the arithmetic can be checked on hand-made lists:

* ``reduce(trace)`` — pure arithmetic on a ``Trace``: per device a list of
  operations ``(name, start_ns, duration_ns)``, the benchmark's host spans on
  the same clock, and the window.  It gives busy time (the union of the
  operations' intervals, so nested and overlapping events count once), the
  idle share, the time in collectives and in custom calls (Mosaic kernels),
  the time and the event count of EVERY operation the program named as one
  of its kernels (``dttpu_*``, by kernel name), the operations that took
  most time (self time: a ``while`` that contains
  its body's operations is charged only what they leave), and the idle gaps
  bucketed by the host span that covers most of each.
* ``from_profile(profile)`` — the adapter from ``jax.profiler.ProfileData``:
  device planes are ``/device:TPU:<n>`` and their operations the line
  ``XLA Ops``; host spans are the ``bench:*`` ``TraceAnnotation`` events of
  ``/host:CPU``.  On the CPU (rehearsals only) there is no device plane and
  the events that carry an ``hlo_op`` stat stand in as device 0.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

from . import spans as spans_lib

Op = Tuple[str, float, float]            # name, start_ns, duration_ns
Span = Tuple[str, float, float]          # name, start_ns, end_ns

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
CUSTOM_CALL = "custom-call"
KERNEL_PREFIX = "dttpu_"      # the program's kernels carry their names (PR 26)
SHORT_GAP_NS = 20_000.0
SHORT_GAP_BUCKET = "between_ops_under_20us"
WINDOW_SPAN = "trace_window"
OPS_LINE = "XLA Ops"
TOP_N = 10


@dataclasses.dataclass
class Trace:
    devices: Dict[int, List[Op]]
    host_spans: List[Span]
    window: Tuple[float, float]          # start_ns, end_ns


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                        # mean over devices
    collective_s: float                  # mean over devices
    custom_call_s: float                 # mean over devices
    device_ops: List[Tuple[str, float]]  # name, seconds (mean over devices)
    idle_gaps: List[Tuple[str, float]]   # bucket, seconds (mean over devices)
    devices: int
    # every ``dttpu_*`` kernel, not only those among ``device_ops``: self
    # seconds and events in the window by kernel name, mean over devices
    kernel_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    kernel_calls: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s


def is_collective(name: str) -> bool:
    low = name.lower()
    return any(c in low for c in COLLECTIVES)


def is_custom_call(name: str) -> bool:
    return CUSTOM_CALL in name.lower()


_INSTANCE = re.compile(r"\.\d+$")


def kernel_name(name: str) -> Optional[str]:
    """``dttpu_paged_decode.4 custom-call bf16[8,1,25,64]`` ->
    ``dttpu_paged_decode``: the kernel an operation is an instance of, or
    None for an operation that is not one of the program's kernels."""
    if not name.startswith(KERNEL_PREFIX):
        return None
    return _INSTANCE.sub("", name.split(" ", 1)[0])


def _clip(ops: Sequence[Op], lo: float, hi: float) -> List[Op]:
    out = []
    for name, start, dur in ops:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def merged_intervals(ops: Sequence[Op]) -> List[Tuple[float, float]]:
    """The union of the operations' intervals, as disjoint sorted pairs."""
    out: List[List[float]] = []
    for start, end in sorted((s, s + d) for _, s, d in ops):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def self_times(ops: Sequence[Op]) -> List[Tuple[str, float]]:
    """``(name, self_ns)`` per operation: its duration less what the
    operations nested inside it cover."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    self_ns = [ops[i][2] for i in range(len(ops))]
    stack: List[int] = []
    for i in order:
        _, start, dur = ops[i]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            parent = stack[-1]
            overlap = min(start + dur,
                          ops[parent][1] + ops[parent][2]) - start
            self_ns[parent] = max(0.0, self_ns[parent] - overlap)
        stack.append(i)
    return [(ops[i][0], self_ns[i]) for i in range(len(ops))]


def _gap_bucket(lo: float, hi: float, host_spans: Sequence[Span]) -> str:
    if hi - lo < SHORT_GAP_NS:
        return SHORT_GAP_BUCKET
    best, best_overlap = "unattributed", 0.0
    for name, s, e in host_spans:
        overlap = min(e, hi) - max(s, lo)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def reduce(trace: Trace) -> Reduced:
    lo, hi = trace.window
    n = max(1, len(trace.devices))
    busy = coll = custom = 0.0
    by_name: Dict[str, float] = {}
    kernel_ns: Dict[str, float] = {}
    kernel_events: Dict[str, int] = {}
    gaps: Dict[str, float] = {}
    host = [s for s in trace.host_spans if s[0] != WINDOW_SPAN]
    for ops in trace.devices.values():
        ops = _clip(ops, lo, hi)
        merged = merged_intervals(ops)
        busy += sum(b - a for a, b in merged)
        for name, ns in self_times(ops):
            by_name[name] = by_name.get(name, 0.0) + ns
            if is_collective(name):
                coll += ns
            elif is_custom_call(name):
                custom += ns
            kernel = kernel_name(name)
            if kernel is not None:
                kernel_ns[kernel] = kernel_ns.get(kernel, 0.0) + ns
                kernel_events[kernel] = kernel_events.get(kernel, 0) + 1
        edge = lo
        for a, b in merged + [(hi, hi)]:
            if a > edge:
                bucket = _gap_bucket(edge, a, host)
                gaps[bucket] = gaps.get(bucket, 0.0) + (a - edge)
            edge = max(edge, b)

    def top(table):
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:TOP_N]
        return [(k, v / n / 1e9) for k, v in rows]

    return Reduced(window_s=(hi - lo) / 1e9, busy_s=busy / n / 1e9,
                   collective_s=coll / n / 1e9,
                   custom_call_s=custom / n / 1e9,
                   device_ops=top(by_name), idle_gaps=top(gaps),
                   devices=len(trace.devices),
                   kernel_s={k: v / n / 1e9
                             for k, v in sorted(kernel_ns.items())},
                   kernel_calls={k: v / n
                                 for k, v in sorted(kernel_events.items())})


# ------------------------------------------------------------- the adapter

_HLO_NAME = re.compile(r"^%?([\w.\-]+) = ")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_ARRAY = re.compile(r"[a-z][a-z0-9]*\[[^\]]*\]")


def short_name(raw: str) -> str:
    """A device event is named by its whole HLO instruction.  Keep the
    instruction's name, its opcode where the name does not already say it
    (a Mosaic kernel is ``%<kernel> = ... custom-call(...)``) and its first
    array type: ``%fusion.4 = bf16[24,16,1024,1024]{...} fusion(...)`` ->
    ``fusion.4 bf16[24,16,1024,1024]``.  Anything else is cut to 96 chars."""
    m = _HLO_NAME.match(raw)
    if not m:
        return raw[:96]
    name, rest = m.group(1), _LAYOUT.sub("", raw[m.end():])
    if rest.startswith("("):             # a tuple type: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        type_text, rest = rest[:i + 1], rest[i + 1:]
    else:
        type_text, _, rest = rest.partition(" ")
    opcode = rest.strip().split("(", 1)[0]
    array = _ARRAY.search(type_text)
    parts = [name]
    if opcode and opcode not in name:
        parts.append(opcode)
    if array:
        parts.append(array.group(0))
    return " ".join(parts)


def _cpu_stand_in(profile) -> List[Op]:
    """Rehearsals on the CPU only: the host-plane events that carry an
    ``hlo_op`` stat are XLA:CPU's operations."""
    return [(short_name(e.name), float(e.start_ns), float(e.duration_ns))
            for plane in profile.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if any(k == "hlo_op" for k, _ in e.stats)]


def from_profile(profile) -> Trace:
    devices: Dict[int, List[Op]] = {}
    host_spans: List[Span] = []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            tail = plane.name[len("/device:TPU:"):]
            if not tail.isdigit():
                continue                 # a sub-unit's plane, not the core's
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                raise ValueError(f"{plane.name} has no {OPS_LINE!r} line: "
                                 f"{sorted(lines)}")
            devices[int(tail)] = [
                (short_name(e.name), float(e.start_ns), float(e.duration_ns))
                for e in lines[OPS_LINE].events]
        elif plane.name == "/host:CPU":
            host_spans.extend(
                (e.name[len(spans_lib.PREFIX):], float(e.start_ns),
                 float(e.start_ns + e.duration_ns))
                for line in plane.lines for e in line.events
                if e.name.startswith(spans_lib.PREFIX))
    if not devices:
        stand_in = _cpu_stand_in(profile)
        if stand_in:
            devices[0] = stand_in
    windows = [s for s in host_spans if s[0] == WINDOW_SPAN]
    if windows:
        window = (windows[0][1], windows[0][2])
    else:
        every = [o for ops in devices.values() for o in ops]
        window = ((min(o[1] for o in every), max(o[1] + o[2] for o in every))
                  if every else (0.0, 0.0))
    return Trace(devices=devices, host_spans=host_spans, window=window)


def load(trace_dir: str) -> Trace:
    """The newest ``*.xplane.pb`` under ``trace_dir``, reduced to a Trace."""
    import glob
    import os

    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(files[-1]))


def start(trace_dir: str) -> None:
    """Start the profiler with the Python tracer off: it slows the host
    loop it is there to observe and fills the trace with frames."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()
