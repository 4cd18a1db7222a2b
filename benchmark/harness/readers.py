"""Arithmetic shared by per-layer readers whose metric is split by cell kind
(``x.train`` moves a training metric, ``x.serve`` a serving one).

A reader is ``read(record, trace) -> float | None``: ``record`` is the
driver's dict of host-side measurements and counts, ``trace`` the reduced
device trace (None in an untraced run).  A reader with nothing to read
returns None and the harness leaves its metric out of the line.
"""
from __future__ import annotations

from typing import Any, Dict, Optional


def compiles_in_window(record: Dict[str, Any], trace) -> Optional[float]:
    return float(record["compiles_in_window"])


def idle_pct(record, trace) -> Optional[float]:
    if trace is None or trace.idle_share is None or not trace.devices:
        return None
    return 100.0 * trace.idle_share


def mosaic_dev_pct(record, trace) -> Optional[float]:
    """Device time of custom-call (Mosaic) events over device busy time."""
    if trace is None or trace.busy_s <= 0:
        return None
    return 100.0 * trace.custom_call_s / trace.busy_s


def hbm_peak_pct(record, trace) -> Optional[float]:
    """The last line's ``memory_peak_bytes`` (``device.memory_report``) over
    the allocator's ``bytes_limit``."""
    memory = record["memory"]
    if not memory.get("bytes_limit"):
        return None
    return 100.0 * memory["memory_peak_bytes"] / memory["bytes_limit"]
