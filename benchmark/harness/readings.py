"""The arithmetic that turns a window's readings into the numbers printed.

A *reading* is a span of whole units of work (train steps closed by a loss
fetch; serve ticks) with the tokens it produced.  Readings follow one another
without a gap from the instant the window opens.  An end-to-end rate is ALL
the tokens of the window over ALL its time (``wall_rate``): a stall inside
the window costs the user its whole length, so the rate has to move by it.
The median of per-reading rates, which one slow reading cannot move, is
printed beside it on an earlier line with the distribution and every slow
reading, and feeds per-layer metrics only (``step_ms_p50``, ``tick_ms_p50``).
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List, Optional, Sequence


# What the per-layer counts of stalls call slow: a train reading over 1.2 x
# the window's median reading (steps of one program differ by < 0.1 %), a
# serve tick over 2 x the median tick (ticks differ 2.8-fold by what they
# hold: 271-768 ms at GPT-2-XL, my chip runs, PR 24).
SLOW_READING_FACTOR = 1.2
SLOW_TICK_FACTOR = 2.0


@dataclasses.dataclass(frozen=True)
class Reading:
    start: float      # host clock, seconds
    end: float
    tokens: int

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def rate(self) -> float:
        return self.tokens / self.seconds


def median_rate(readings: Sequence[Reading]) -> Optional[float]:
    if not readings:
        return None
    return statistics.median(r.rate for r in readings)


def wall_rate(readings: Sequence[Reading]) -> Optional[float]:
    """All tokens over all the time from the first reading's start (the
    instant the window opened) to the last one's end: the end-to-end rate."""
    if not readings:
        return None
    return (sum(r.tokens for r in readings)
            / (readings[-1].end - readings[0].start))


def slow(values: Sequence[float], factor: float) -> List[float]:
    """The values over ``factor`` times their median (reading or tick
    seconds): what a per-layer count of stalls counts."""
    if not values:
        return []
    limit = factor * statistics.median(values)
    return [v for v in values if v > limit]


def nearest_rank(values: Sequence[float], pct: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest value with at least ``pct``
    percent of the sample at or below it."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(pct / 100.0 * len(ordered)))
    return ordered[rank - 1]


def summary(values: Sequence[float]) -> Dict[str, float]:
    """count, min, quartiles, max — the earlier line's view of a list."""
    values = list(values)
    if not values:
        return {"count": 0}
    if len(values) >= 2:
        q1, q2, q3 = statistics.quantiles(values, n=4)
    else:
        q1 = q2 = q3 = values[0]
    return {"count": len(values), "min": min(values), "q1": q1,
            "median": q2, "q3": q3, "max": max(values)}


def tick_aligned(tick_ends: Sequence[float], tokens_at: Sequence[int],
                 min_seconds: float) -> List[Reading]:
    """Cut a run of ticks into readings of at least ``min_seconds`` each.

    ``tick_ends[i]`` is the instant tick ``i`` returned and ``tokens_at[i]``
    the tokens it delivered.  A reading runs from the instant one tick
    returned to the instant a later one did — the shortest such run that
    lasts ``min_seconds`` — so no reading is cut through a tick (a tick
    hands out several tokens per slot at once; a fixed slice of wall time
    would hold now three ticks, now five).  The tokens of a reading are
    those of the ticks that ENDED in it.
    """
    out: List[Reading] = []
    if not tick_ends:
        return out
    start, tokens = tick_ends[0], 0
    for end, n in zip(tick_ends[1:], tokens_at[1:]):
        tokens += n
        if end - start >= min_seconds:
            out.append(Reading(start, end, tokens))
            start, tokens = end, 0
    return out
