"""Perf ledger: an append-only, versioned JSONL history of bench rows.

The repo's performance record used to be loose per-round JSON blobs
compared by filename convention.  The ledger replaces that with a
durable, queryable file: every ``bench.py`` config appends exactly one
schema-checked row carrying its identity (``run_id``, ``git_sha``, the
backend/mesh fingerprint), the config knobs it ran under, the measured
numbers, the matching ``analytical_*`` statics from the DT4xx cost
model, and the goodput split — so "did tokens/s regress since the sharding
change" is a two-row :func:`delta`, not archaeology.  The committed
``ledger/baseline.jsonl`` carries the CPU-smoke reference points the CI
perf gate (``scripts/perf_gate.py`` + ``obs.sentinel``) checks fresh
rows against.

Durability contract (what the race-harness tests pin):

* **append** is a single ``os.write`` of one complete ``\\n``-terminated
  line on an ``O_APPEND`` fd — concurrent appenders from threads or
  processes never interleave bytes mid-row, so every row parses whole;
* **load** tolerates a torn/corrupt trailing line (a crash mid-append on
  a non-O_APPEND copy, a truncated download): it is skipped with a loud
  warning, never a crash;
* **schema skew** (a row written by a different ``SCHEMA_VERSION``) is
  skipped loudly too — old ledgers stay readable forever, unknown future
  rows never crash an old reader.

Pure stdlib, like everything in ``obs``.
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["SCHEMA_VERSION", "LedgerSchemaError", "PerfLedger",
           "row_from_bench", "row_field"]

log = logging.getLogger(__name__)

SCHEMA_VERSION = 1

# Required row fields and their types — the append-side contract.  The
# nested dicts (fingerprint / measured / analytical / knobs / goodput)
# stay open-schema: configs measure different things, and the sentinel
# classifies fields by name instead of a closed list.
_REQUIRED = {
    "schema_version": int,
    "run_id": str,
    "git_sha": str,
    "config": str,
    "timestamp": float,
    "fingerprint": dict,
    "measured": dict,
}
_OPTIONAL_DICTS = ("analytical", "knobs", "goodput")


class LedgerSchemaError(ValueError):
    """An append was handed a row that violates the schema."""


def validate_row(row: Dict[str, Any]) -> None:
    """Raise :class:`LedgerSchemaError` if ``row`` is not appendable."""
    if not isinstance(row, dict):
        raise LedgerSchemaError(f"row must be a dict, got {type(row)}")
    for key, typ in _REQUIRED.items():
        if key not in row:
            raise LedgerSchemaError(f"row missing required field {key!r}")
        val = row[key]
        if typ is float and isinstance(val, int):
            continue      # ints are fine where floats are expected
        if not isinstance(val, typ):
            raise LedgerSchemaError(
                f"row field {key!r} must be {typ.__name__}, "
                f"got {type(val).__name__}")
    for key in _OPTIONAL_DICTS:
        if key in row and row[key] is not None \
                and not isinstance(row[key], dict):
            raise LedgerSchemaError(f"row field {key!r} must be a dict "
                                    "when present")
    for key, val in row["measured"].items():
        if isinstance(val, bool) or not isinstance(val, (int, float)):
            raise LedgerSchemaError(
                f"measured[{key!r}] must be a number, "
                f"got {type(val).__name__}")


def row_field(row: Dict[str, Any], field: str) -> Optional[float]:
    """Resolve a numeric field by name: ``measured`` first, then
    ``analytical``, then ``goodput`` (where ``goodput.buckets_s`` keys
    are reachable as ``goodput_<bucket>_s``), then the row top level.
    Returns ``None`` when absent or non-numeric."""
    for section in ("measured", "analytical"):
        d = row.get(section) or {}
        if field in d:
            return _num(d[field])
    gp = row.get("goodput") or {}
    if field in gp:
        return _num(gp[field])
    if field.startswith("goodput_") and field.endswith("_s"):
        bucket = field[len("goodput_"):-len("_s")]
        buckets = gp.get("buckets_s") or {}
        if bucket in buckets:
            return _num(buckets[bucket])
    if field in row:
        return _num(row[field])
    return None


def _num(v: Any) -> Optional[float]:
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return None
    return float(v)


class PerfLedger:
    """One JSONL ledger file with atomic appends and tolerant loads."""

    def __init__(self, path: str):
        self.path = str(path)
        self._lock = threading.Lock()
        self.skipped_lines = 0       # load-side diagnostics, last rows()
        self.skipped_versions = 0

    # ------------------------------------------------------------ append

    def append(self, row: Dict[str, Any]) -> Dict[str, Any]:
        """Validate and append one row; returns the row as written
        (with ``schema_version``/``timestamp`` stamped if absent).

        One ``os.write`` on an ``O_APPEND`` fd: POSIX serializes the
        offset update with the write, so concurrent appenders (threads
        or processes) produce whole interleaved LINES, never interleaved
        bytes — the property the race-harness test pins."""
        if not isinstance(row, dict):
            raise LedgerSchemaError(f"row must be a dict, got {type(row)}")
        row = dict(row)
        row.setdefault("schema_version", SCHEMA_VERSION)
        row.setdefault("timestamp", time.time())
        validate_row(row)
        data = (json.dumps(row, sort_keys=True,
                           separators=(",", ":")) + "\n").encode("utf-8")
        if "\n" in data[:-1].decode("utf-8"):
            raise LedgerSchemaError("row serialized to multiple lines")
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        fd = os.open(self.path,
                     os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        try:
            os.write(fd, data)
        finally:
            os.close(fd)
        return row

    # -------------------------------------------------------------- load

    def rows(self) -> List[Dict[str, Any]]:
        """All readable rows of this reader's schema version, oldest
        first.  Corrupt lines (torn trailing write, truncation) and rows
        from a different ``schema_version`` are skipped with a warning —
        loudly, never a crash (counts land in ``skipped_lines`` /
        ``skipped_versions``)."""
        skipped_lines = skipped_versions = 0
        out: List[Dict[str, Any]] = []
        if os.path.exists(self.path):
            with open(self.path, "r", encoding="utf-8",
                      errors="replace") as f:
                for lineno, line in enumerate(f, start=1):
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        row = json.loads(line)
                        if not isinstance(row, dict):
                            raise ValueError("row is not an object")
                    except ValueError as e:
                        skipped_lines += 1
                        log.warning("ledger %s:%d: skipping corrupt line "
                                    "(%s)", self.path, lineno, e)
                        continue
                    if row.get("schema_version") != SCHEMA_VERSION:
                        skipped_versions += 1
                        log.warning(
                            "ledger %s:%d: skipping row with schema_"
                            "version=%r (this reader speaks %d)",
                            self.path, lineno,
                            row.get("schema_version"), SCHEMA_VERSION)
                        continue
                    out.append(row)
        with self._lock:
            self.skipped_lines = skipped_lines
            self.skipped_versions = skipped_versions
        return out

    # ------------------------------------------------------------- query

    def latest(self, config: str,
               backend: Optional[str] = None) -> Optional[Dict[str, Any]]:
        """Newest row for ``config`` (and ``backend``, when given —
        matched against ``fingerprint.backend``)."""
        best: Optional[Dict[str, Any]] = None
        for row in self.rows():
            if row.get("config") != config:
                continue
            if backend is not None and \
                    (row.get("fingerprint") or {}).get("backend") != backend:
                continue
            if best is None or row.get("timestamp", 0) >= \
                    best.get("timestamp", 0):
                best = row
        return best

    def series(self, field: str, config: Optional[str] = None,
               backend: Optional[str] = None
               ) -> List[Tuple[float, float]]:
        """``(timestamp, value)`` points for one field across history —
        the trajectory plot ROADMAP item 3's autotuner reads."""
        out: List[Tuple[float, float]] = []
        for row in self.rows():
            if config is not None and row.get("config") != config:
                continue
            if backend is not None and \
                    (row.get("fingerprint") or {}).get("backend") != backend:
                continue
            v = row_field(row, field)
            if v is not None:
                out.append((float(row.get("timestamp", 0.0)), v))
        out.sort(key=lambda tv: tv[0])
        return out

    @staticmethod
    def delta(row: Dict[str, Any], baseline: Dict[str, Any]
              ) -> Dict[str, Dict[str, float]]:
        """Per-field comparison of two rows over their shared measured
        fields: ``{field: {"measured", "baseline", "ratio"}}`` (ratio
        measured/baseline; baseline 0 yields ``inf``/``nan`` honestly)."""
        out: Dict[str, Dict[str, float]] = {}
        m = row.get("measured") or {}
        for fieldname in sorted(m):
            a = _num(m[fieldname])
            b = row_field(baseline, fieldname)
            if a is None or b is None:
                continue
            ratio = a / b if b else (float("inf") if a > 0 else
                                     float("nan"))
            out[fieldname] = {"measured": a, "baseline": b,
                              "ratio": ratio}
        return out


# ---------------------------------------------------------------------------
# bench.py integration: one stamped result line -> one ledger row.

# bench result fields that are identity/bookkeeping, not measurements
_NON_MEASURED = {"schema_version", "run_id", "git_sha", "timestamp",
                 "config", "fingerprint", "goodput"}


def row_from_bench(result: Dict[str, Any],
                   knobs: Optional[Dict[str, str]] = None
                   ) -> Dict[str, Any]:
    """Build a ledger row from a stamped ``bench.py`` result line:
    numeric fields split into ``measured`` vs ``analytical_*`` statics,
    identity fields lifted to the top level, ``DTTPU_*`` env knobs
    recorded (captured from the environment when not given)."""
    measured: Dict[str, float] = {}
    analytical: Dict[str, float] = {}
    for key, val in result.items():
        if key in _NON_MEASURED:
            continue
        n = _num(val)
        if n is None:
            continue
        (analytical if key.startswith("analytical_") else
         measured)[key] = n
    if knobs is None:
        knobs = {k: v for k, v in sorted(os.environ.items())
                 if k.startswith("DTTPU_")}
    return {
        "schema_version": int(result.get("schema_version",
                                         SCHEMA_VERSION)),
        "run_id": str(result.get("run_id", "")),
        "git_sha": str(result.get("git_sha", "")),
        "config": str(result.get("config", result.get("metric", ""))),
        "timestamp": float(result.get("timestamp", time.time())),
        "fingerprint": dict(result.get("fingerprint") or {}),
        "measured": measured,
        "analytical": analytical,
        "knobs": dict(knobs),
        "goodput": result.get("goodput"),
    }
