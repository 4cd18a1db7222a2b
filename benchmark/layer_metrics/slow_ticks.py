"""``Engine.step()`` calls of the window that took over 2 x its median tick:
the stalls that ``serve_tokens_per_s`` pays for and ``tick_ms_p50`` does not
see."""
from harness import readings


def read(record, trace):
    if not record["tick_seconds"]:
        return None
    return float(len(readings.slow(record["tick_seconds"],
                                   readings.SLOW_TICK_FACTOR)))
