"""The device a run is on: found, required, described, and its memory read.

A measurement path that finds no accelerator fails; it never falls back to
the CPU.  ``rehearse_on_cpu`` exists for the tests under ``tests/benchmark``
only, is a Python argument and not an option of the command, and a line
printed under it names the platform ``cpu`` like any other.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional


class NoAccelerator(RuntimeError):
    pass


class CompileCounter:
    """Counts XLA backend compiles through ``jax.monitoring`` (a program
    served from the persistent cache still counts: it was not in memory, so
    the window would have waited for it)."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def _on_duration(self, name, secs, **_):
        if name == self._EVENT:
            self.count += 1
            self.seconds += secs

    def __enter__(self):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc):
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._on_duration)


def require(chips: int, rehearse_on_cpu: bool = False) -> List[Any]:
    """The ``chips`` devices the cell runs on, or ``NoAccelerator``."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu" and not rehearse_on_cpu:
        raise NoAccelerator("JAX found no accelerator (platform cpu)")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX found "
                            f"{len(devices)}")
    return list(devices[:chips])


def memory_report(devices) -> Dict[str, Any]:
    """Memory on the fullest device, from the allocator's own counters and
    nothing else; call it when the window closes.

    ``memory_peak_bytes`` is the larger of ``peak_bytes_in_use`` and
    ``bytes_in_use + bytes_reserved`` as they stand now.  On this backend
    ``peak_bytes_in_use`` counts live buffers only; what a loaded program's
    temporaries take is ``bytes_reserved`` (PERF.md: 6,459,637,760 B
    reserved against 6,460,035,584 B of temporaries that
    ``compiled.memory_analysis()`` gives for the serving engine's largest
    program), so a training step whose state is 4.3 GB read 25.5 % by the
    first counter alone while the chip held 13.3 GB.  The two peaks are not
    added: they need not fall at one instant.
    """
    def held(stats):
        return max(stats.get("peak_bytes_in_use", 0),
                   stats.get("bytes_in_use", 0)
                   + stats.get("bytes_reserved", 0))

    stats = max((d.memory_stats() or {} for d in devices), key=held)
    return {"memory_peak_bytes": held(stats),
            "bytes_limit": stats.get("bytes_limit", 0),
            "allocator_stats": {k: v for k, v in sorted(stats.items())
                                if isinstance(v, (int, float))}}


def describe(devices, memory_peak_bytes: int) -> Dict[str, Any]:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(memory_peak_bytes)}


def temp_bytes(compiled) -> Optional[int]:
    """Temporaries of one compiled program on one device, or None where the
    backend gives no analysis."""
    try:
        analysis = compiled.memory_analysis()
    except Exception:   # noqa: BLE001 - optional backend feature
        return None
    if analysis is None:
        return None
    return int(getattr(analysis, "temp_size_in_bytes", 0))
