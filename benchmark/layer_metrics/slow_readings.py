"""Readings of the window that took over 1.2 x its median reading, which
``step_ms_p50`` does not see: a device that stalled, which
``train_tokens_per_s`` pays for, or a fetch that came back late while the
device worked on, which it does not (the next reading is as much shorter)."""
from harness import readings


def read(record, trace):
    if not record["reading_seconds"]:
        return None
    return float(len(readings.slow(record["reading_seconds"],
                                   readings.SLOW_READING_FACTOR)))
