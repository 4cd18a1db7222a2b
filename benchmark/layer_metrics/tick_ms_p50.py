"""Median host-clock time of one ``Engine.step()`` in the window (each tick
ends in a token fetch, so it includes the device's work)."""
import statistics


def read(record, trace):
    if not record["tick_seconds"]:
        return None
    return 1e3 * statistics.median(record["tick_seconds"])
