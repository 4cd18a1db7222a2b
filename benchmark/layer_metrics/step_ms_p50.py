"""Median time of one training step: median reading time over the steps in a
reading, in ms."""
import statistics


def read(record, trace):
    if not record["reading_seconds"]:
        return None
    return (1e3 * statistics.median(record["reading_seconds"])
            / record["steps_per_reading"])
