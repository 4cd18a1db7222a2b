"""CRC32-C (Castagnoli) + TFRecord masking.

Needed for the TensorBoard event-file record framing (each record's length
and payload carry a masked crc32c), the checkpoint leaf checksums and the
KV-page wire.  The native slice-by-8 implementation
(``native/dttpu_native.cpp``, byte-identical output) does the bulk work; the
table-driven pure-Python version below is the always-available fallback and
the cross-check oracle in tests.

When the native library is built: never from an import, and not for a small
record — but a payload of ``_BUILD_NATIVE_AT`` bytes or more (a checkpoint
leaf) is worth the one-time ~3 s ``make``.  The Python byte loop runs at a
few MB/s: on a fresh checkout (no ``.so``; it is not committed) it turned
one save + verified restore of a 1.5 GB GPT-2-small TrainState into ~7
minutes on the TPU host (PERF.md, PR 22).
"""
from __future__ import annotations

__all__ = ["crc32c", "masked_crc32c", "py_crc32c", "py_masked_crc32c"]

_POLY = 0x82F63B78
_TABLE = []
for _i in range(256):
    _c = _i
    for _ in range(8):
        _c = (_c >> 1) ^ _POLY if _c & 1 else _c >> 1
    _TABLE.append(_c)


def py_crc32c(data: bytes, crc: int = 0) -> int:
    crc ^= 0xFFFFFFFF
    for b in data:
        crc = _TABLE[(crc ^ b) & 0xFF] ^ (crc >> 8)
    return crc ^ 0xFFFFFFFF


def py_masked_crc32c(data: bytes) -> int:
    """The TFRecord mask: rotate right 15 and add a constant."""
    crc = py_crc32c(data)
    return (((crc >> 15) | (crc << 17)) + 0xA282EAD8) & 0xFFFFFFFF


_BUILD_NATIVE_AT = 1 << 20


def _native_for(nbytes: int):
    """utils.native when its library is loaded — or, for a bulk payload,
    can be built now; else None (``DTTPU_NO_NATIVE``, no toolchain)."""
    from ..utils import native
    ok = native.native_available(build=nbytes >= _BUILD_NATIVE_AT)
    return native if ok else None


def crc32c(data: bytes, crc: int = 0) -> int:
    native = _native_for(len(data))
    return native.crc32c(data, crc) if native else py_crc32c(data, crc)


def masked_crc32c(data: bytes) -> int:
    native = _native_for(len(data))
    return (native.masked_crc32c(data) if native
            else py_masked_crc32c(data))
