"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip.  JAX reports
that chip as ``TPU v5 lite``.  A device that is not in the table is an
error, never a default, and nothing in the environment overrides a row
(copied from ``bench.py`` ``_PEAK_BF16`` / ``_PEAK_HBM_BW`` without the
``DTTPU_PEAK_*`` overrides; see PERF.md, Open questions).
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float        # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float


PEAKS = {
    "TPU v5 lite": Peak(197e12, 819e9, 16e9),
    "TPU v5e": Peak(197e12, 819e9, 16e9),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}: add a row "
            "with its source to benchmark/harness/peaks.py") from None
