"""Fault-tolerant cross-host KV-page wire: ship pages, not FLOPs.

Live migration (serve/scheduler.py ``export``/``import_snapshot``)
moves a request's HOST state and re-prefills its KV cache on the
destination — correct, but it burns prefill windows recomputing K/V
the source already holds.  ``PageWire`` is the transport that ships
those pages instead: the radix-cached device pages behind a
``RequestSnapshot``'s shipped-pages manifest (the chain hashes its
export handed off, serve/pages.py) travel device -> host -> wire ->
device in CRC-checked chunks, and the receiver splices them straight
into its ``PagePool`` through the same ``begin``/``handoff`` seam
every request uses — so the resumed request's prefill radix-matches
and skips the shipped windows.

Failure is the common case, so the transfer state machine is built
around it:

* every chunk frame carries a CRC32C (``summary.crc32c`` — the
  TFRecord checksum, reused) over its records AND each record's chain
  hash; a corrupt frame is NAKed by the receiver and re-sent;
* bounded retries with seeded exponential backoff + a per-chunk
  timeout: a dropped frame costs one timeout, a late (stalled) frame
  is re-sent and the receiver dedups by chain key — re-send is
  idempotent end to end because the splice itself is (a chain already
  in the destination's radix tree is matched, not rewritten);
* **graceful degradation**: any unrecoverable failure (link down —
  the host died mid-transfer) raises ``WireError`` and the caller
  (``fleet.Router._place``) falls back to today's re-prefill
  migration.  Correctness NEVER depends on the wire; it only saves
  destination prefill windows.

The chaos kinds ``drop_chunk``/``corrupt_chunk``/``stall_wire``/
``kill_host`` (resilience/faults.py) act inside ``InProcessLink`` —
the loopback link the in-process fleet uses — so every failure mode
above is directly injectable and tested (tests/test_pagewire.py).

Series: ``dttpu_wire_*`` (docs/OBSERVABILITY.md §Page wire).
"""
from __future__ import annotations

import dataclasses
import logging
import struct
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import metrics as metrics_lib
from ..resilience import faults as faults_lib
from ..summary.crc32c import crc32c

log = logging.getLogger(__name__)

__all__ = ["InProcessLink", "PageRecord", "PageWire", "WireError",
           "WireFrameError", "frame_chunk", "parse_frame"]

_MAGIC = b"DTPW"
_VERSION = 1
# chain key type tags: the serve tier keys by blake2b chain hash
# (bytes), the fleet sim by prefix id (int) — frames carry either
_KEY_BYTES = 0
_KEY_INT = 1


class WireError(RuntimeError):
    """Unrecoverable transfer failure (link down, retries exhausted).
    The caller degrades to re-prefill migration — never fatal to the
    request."""


class WireFrameError(WireError):
    """A frame that failed parse or CRC verification — the receiver's
    NAK.  Retryable: the sender re-frames and re-sends."""


@dataclasses.dataclass
class PageRecord:
    """One shipped page: the ``index``-th full chunk of the migrated
    request's context, its radix chain key, the tokens the chain
    covers through this chunk, and the host copy of the device page
    (one ``[L, page_size, ...]`` array per pool leaf; int8 pools ship
    their scale planes as ordinary leaves).  The fleet sim ships
    payload-free records — its "page" is a fingerprint entry."""
    index: int
    chain: Any                           # bytes (serve) | int (sim)
    tokens: int
    payload: Dict[str, np.ndarray]


def _wire_dtype(dt: np.dtype) -> bytes:
    """Dtype -> wire string.  Extension dtypes (bfloat16, float8_*)
    stringify to an opaque void under ``.str`` — the one thing that
    must NOT go on the wire, since ``np.dtype("<V2")`` parses back as
    raw void and the receiver's dtype check would refuse the splice.
    Their registered NAME round-trips instead (via ml_dtypes)."""
    return (dt.name if dt.kind == "V" else dt.str).encode()


def _resolve_dtype(s: str) -> np.dtype:
    """Wire string -> dtype; NAKs (``WireFrameError``) on a dtype this
    host cannot represent rather than splicing mistyped pages."""
    try:
        dt = np.dtype(s)
    except TypeError:
        dt = None
    if dt is not None and dt.kind != "V":
        return dt
    try:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, s))
    except (ImportError, AttributeError, TypeError) as e:
        raise WireFrameError(f"unknown wire dtype {s!r}") from e


def _pack_key(chain: Any) -> Tuple[int, bytes]:
    if isinstance(chain, bytes):
        return _KEY_BYTES, chain
    return _KEY_INT, struct.pack(">q", int(chain))


def _unpack_key(tag: int, raw: bytes) -> Any:
    if tag == _KEY_BYTES:
        return raw
    return struct.unpack(">q", raw)[0]


def frame_chunk(seq: int, records: Sequence[PageRecord]) -> bytes:
    """Serialize one wire chunk: header, each record's (index, chain
    key, tokens, payload leaves with dtype/shape), CRC32C trailer over
    everything before it.  Real bytes on purpose: corruption has a
    byte to flip and the CRC has bytes to cover, and the framing cost
    is what the bench's chunk-size sweep measures."""
    parts = [_MAGIC, struct.pack(">BIH", _VERSION, seq, len(records))]
    for r in records:
        tag, key = _pack_key(r.chain)
        parts.append(struct.pack(">IBB", int(r.index), tag, len(key)))
        parts.append(key)
        parts.append(struct.pack(">IB", int(r.tokens), len(r.payload)))
        for name in sorted(r.payload):
            leaf = np.ascontiguousarray(r.payload[name])
            dt = _wire_dtype(leaf.dtype)
            parts.append(struct.pack(">B", len(name)))
            parts.append(name.encode())
            parts.append(struct.pack(">B", len(dt)))
            parts.append(dt)
            parts.append(struct.pack(">B", leaf.ndim))
            parts.append(struct.pack(f">{leaf.ndim}I", *leaf.shape))
            raw = leaf.tobytes()
            parts.append(struct.pack(">Q", len(raw)))
            parts.append(raw)
    body = b"".join(parts)
    return body + struct.pack(">I", crc32c(body))


def parse_frame(frame: bytes) -> Tuple[int, List[PageRecord]]:
    """Decode one chunk frame, verifying magic, version, and the
    CRC32C trailer.  Raises ``WireFrameError`` on any mismatch — the
    receiver NAKs instead of splicing corrupt pages."""
    if len(frame) < len(_MAGIC) + 7 + 4:
        raise WireFrameError(f"short frame ({len(frame)} bytes)")
    body, (crc,) = frame[:-4], struct.unpack(">I", frame[-4:])
    if crc32c(body) != crc:
        raise WireFrameError("CRC32C mismatch")
    if body[:4] != _MAGIC:
        raise WireFrameError(f"bad magic {body[:4]!r}")
    ver, seq, n = struct.unpack(">BIH", body[4:11])
    if ver != _VERSION:
        raise WireFrameError(f"wire version {ver} != {_VERSION}")
    off = 11
    out: List[PageRecord] = []
    try:
        for _ in range(n):
            index, tag, klen = struct.unpack(">IBB", body[off:off + 6])
            off += 6
            chain = _unpack_key(tag, body[off:off + klen])
            off += klen
            tokens, nleaves = struct.unpack(">IB", body[off:off + 5])
            off += 5
            payload: Dict[str, np.ndarray] = {}
            for _ in range(nleaves):
                (ln,) = struct.unpack(">B", body[off:off + 1])
                name = body[off + 1:off + 1 + ln].decode()
                off += 1 + ln
                (ln,) = struct.unpack(">B", body[off:off + 1])
                dt = _resolve_dtype(body[off + 1:off + 1 + ln].decode())
                off += 1 + ln
                (ndim,) = struct.unpack(">B", body[off:off + 1])
                shape = struct.unpack(f">{ndim}I",
                                      body[off + 1:off + 1 + 4 * ndim])
                off += 1 + 4 * ndim
                (nraw,) = struct.unpack(">Q", body[off:off + 8])
                raw = body[off + 8:off + 8 + nraw]
                off += 8 + nraw
                payload[name] = np.frombuffer(
                    raw, dt).reshape(shape).copy()
            out.append(PageRecord(index=index, chain=chain,
                                  tokens=tokens, payload=payload))
    except (struct.error, ValueError) as e:
        raise WireFrameError(f"truncated frame: {e}") from e
    return seq, out


class InProcessLink:
    """Loopback wire link for the in-process fleet: delivery returns
    the frame as the receiver would see it.  ``latency_s`` is paid
    once per ``deliver`` call (a flight of frames amortizes it — the
    overlap knob's physical meaning), and the active ``FaultPlan``'s
    wire site acts per frame: ``drop_chunk`` vanishes it (None — the
    sender sees a per-chunk timeout), ``corrupt_chunk`` flips one
    payload byte (the receiver's CRC NAKs), ``stall_wire`` sleeps the
    fault's ``seconds`` in-line (a late frame), ``kill_host`` raises
    ``ConnectionError`` (the host died mid-transfer; unrecoverable).

    A real deployment would substitute a socket-backed link with the
    same ``deliver`` contract; everything above it — framing, CRC,
    retry, dedup, degradation — is transport-agnostic."""

    def __init__(self, wire_id: int = 0, latency_s: float = 0.0,
                 sleep=time.sleep):
        self.wire_id = int(wire_id)
        self.latency_s = float(latency_s)
        self.sleep = sleep

    def deliver(self, frames: Sequence[bytes]
                ) -> List[Optional[bytes]]:
        if self.latency_s:
            self.sleep(self.latency_s)
        plan = faults_lib.active()
        out: List[Optional[bytes]] = []
        for frame in frames:
            action = (plan.on_wire_chunk(self.wire_id)
                      if plan is not None else None)
            if action == "drop":
                out.append(None)
            elif action == "corrupt":
                bad = bytearray(frame)
                bad[len(bad) // 2] ^= 0xFF
                out.append(bytes(bad))
            else:
                out.append(bytes(frame))
        return out


class PageWire:
    """The sender-side transfer state machine (module docstring).

    ``chunk_pages`` records per frame and ``overlap`` frames per
    flight are the two wire-shaping knobs ``bench.py --config=fleet``
    sweeps.  ``timeout_s`` is the per-flight delivery budget: a flight
    that exceeds it is re-sent even if frames arrived (late == lost to
    the sender; the receiver's chain-key dedup makes the duplicate
    harmless).  Retries back off exponentially with seeded jitter
    (``resilience.Supervisor``'s discipline) so a congested link is
    not hammered in lockstep.  ``sleep`` is injectable for tests."""

    def __init__(self, *, chunk_pages: int = 2, overlap: int = 1,
                 max_retries: int = 4, timeout_s: float = 0.5,
                 backoff_base_s: float = 0.002,
                 backoff_factor: float = 2.0,
                 backoff_max_s: float = 0.05, jitter: float = 0.5,
                 seed: int = 0, link: Optional[InProcessLink] = None,
                 registry: Optional[metrics_lib.Registry] = None,
                 sleep=time.sleep):
        if chunk_pages < 1:
            raise ValueError(f"chunk_pages must be >= 1; "
                             f"got {chunk_pages}")
        if overlap < 1:
            raise ValueError(f"overlap must be >= 1; got {overlap}")
        self.chunk_pages = int(chunk_pages)
        self.overlap = int(overlap)
        self.max_retries = int(max_retries)
        self.timeout_s = float(timeout_s)
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_factor = float(backoff_factor)
        self.backoff_max_s = float(backoff_max_s)
        self.jitter = float(jitter)
        self.sleep = sleep
        self.link = link if link is not None else InProcessLink()
        self._rng = np.random.default_rng(seed)
        reg = registry if registry is not None else metrics_lib.REGISTRY
        self._m_transfers = reg.counter(
            "dttpu_wire_transfers_total",
            "Completed page-wire transfers (pages adopted by the "
            "destination pool).")
        self._m_failures = reg.counter(
            "dttpu_wire_failures_total",
            "Unrecoverable page-wire transfers (link down or chunk "
            "retries exhausted) — each degraded to re-prefill "
            "migration.")
        self._m_chunks = reg.counter(
            "dttpu_wire_chunks_total",
            "Chunk frames sent over the page wire (re-sends "
            "included).")
        self._m_retries = reg.counter(
            "dttpu_wire_chunk_retries_total",
            "Chunk frames re-sent after a drop, CRC NAK, or per-chunk "
            "timeout.")
        self._m_bytes = reg.counter(
            "dttpu_wire_bytes_total",
            "Framed bytes sent over the page wire (re-sends "
            "included).")
        self._m_pages = reg.counter(
            "dttpu_wire_pages_shipped_total",
            "KV pages adopted by destination pools via the wire.")
        self._m_seconds = reg.histogram(
            "dttpu_wire_transfer_seconds",
            "Wall clock of one completed page-wire transfer (device "
            "read to destination splice).")

    # ------------------------------------------------------------ ship

    def _delay(self, attempt: int) -> float:
        base = min(self.backoff_max_s,
                   self.backoff_base_s
                   * self.backoff_factor ** (attempt - 1))
        return base * (1.0 + self.jitter * float(self._rng.random()))

    def ship(self, records: Sequence[Tuple[int, Any, dict]],
             dest, snap) -> int:
        """Transfer ``records`` — ``(chunk_index, chain key, payload)``
        from the source engine's ``export_wire_pages`` — to ``dest``
        (anything with ``import_wire_pages(snap, records)``); returns
        pages the destination adopted (0 = nothing usable shipped: the
        import re-prefills those windows).

        Raises ``WireError`` on unrecoverable failure; the caller MUST
        treat that as "migrate by re-prefill", never as request
        failure."""
        if not records:
            return 0
        if not hasattr(dest, "import_wire_pages"):
            return 0                      # no page pool: degrade
        t0 = time.perf_counter()
        # tokens covered comes from the snapshot manifest when present
        # (authoritative), else from chunk order x page size
        manifest = dict(getattr(snap, "shipped_pages", None) or ())
        pg = int(getattr(snap, "page_size", 0) or 0)
        recs = [PageRecord(index=int(i), chain=c,
                           tokens=int(manifest.get(c, (int(i) + 1) * pg)),
                           payload=dict(p))
                for i, c, p in records]
        frames = [frame_chunk(seq, recs[seq * self.chunk_pages:
                                        (seq + 1) * self.chunk_pages])
                  for seq in range(-(-len(recs) // self.chunk_pages))]
        accepted: Dict[Any, PageRecord] = {}
        try:
            for base in range(0, len(frames), self.overlap):
                self._send_flight(
                    list(enumerate(frames))[base:base + self.overlap],
                    accepted)
        except WireError:
            self._m_failures.inc()
            raise
        # splice the contiguous prefix (chunk 0..n-1): a gap means a
        # chain the source no longer held — everything past it must
        # re-prefill anyway
        ordered = sorted(accepted.values(), key=lambda r: r.index)
        take: List[PageRecord] = []
        for j, r in enumerate(ordered):
            if r.index != j:
                break
            take.append(r)
        if not take:
            return 0
        try:
            adopted = int(dest.import_wire_pages(snap, take))
        except Exception as e:
            # a refusing destination (pool exhausted, incompatible
            # layout) is degradation, not transfer failure
            log.warning("page-wire splice refused by destination: %r", e)
            self._m_failures.inc()
            return 0
        if adopted:
            self._m_pages.inc(adopted)
            self._m_transfers.inc()
            self._m_seconds.observe(time.perf_counter() - t0)
        return adopted

    def _send_flight(self, flight: List[Tuple[int, bytes]],
                     accepted: Dict[Any, PageRecord]) -> None:
        """Deliver one flight of frames with bounded per-chunk retry.
        A frame is settled when it parses and CRC-verifies within the
        per-flight timeout; drops, NAKs, and timeouts re-send only the
        unsettled frames."""
        pending = list(flight)
        for attempt in range(self.max_retries + 1):
            self._m_chunks.inc(len(pending))
            for _, frame in pending:
                self._m_bytes.inc(len(frame))
            sent = time.perf_counter()
            try:
                outs = self.link.deliver([f for _, f in pending])
            except ConnectionError as e:
                raise WireError(f"page-wire link down: {e}") from e
            late = (time.perf_counter() - sent) > self.timeout_s
            failed: List[Tuple[int, bytes]] = []
            for (seq, frame), out in zip(pending, outs):
                ok = False
                if out is not None:
                    try:
                        _, recs = parse_frame(out)
                    except WireFrameError:
                        recs = None       # receiver NAK
                    if recs is not None:
                        # idempotent re-send: dedup by chain key — a
                        # late duplicate lands here as a no-op
                        for r in recs:
                            accepted.setdefault(r.chain, r)
                        ok = not late
                if not ok:
                    failed.append((seq, frame))
            if not failed:
                return
            if attempt >= self.max_retries:
                raise WireError(
                    f"chunk retries exhausted "
                    f"(seqs {[s for s, _ in failed]} after "
                    f"{self.max_retries} retries)")
            self._m_retries.inc(len(failed))
            self.sleep(self._delay(attempt + 1))
            pending = failed
