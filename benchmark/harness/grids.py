"""Quantile grids: a distribution as a fixed multiset of values, dealt in a
fixed order.

A traffic file gives a length distribution as ``{"dist": ..., "points": n}``.
The grid is its ``n`` quantiles at ``(i + 0.5) / n``, rounded to whole
tokens and clipped.  A ``Deal`` hands the grid out to a fixed number of
hands (client sequences): in every pass each value is dealt exactly once, in
one fixed order — never one drawn from the run's seed.  So every run,
whatever its ``--seed``, offers the same lengths
in the same schedule; the seed decides only which client plays which hand
and which token ids fill the lengths.  Runs with different seeds then differ
no more than two runs of one seed.
"""
from __future__ import annotations

import math
import statistics
from typing import Any, Dict, List

import numpy as np


def quantile_grid(spec: Dict[str, Any]) -> List[int]:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown dist {spec['dist']!r}")
    n = int(spec["points"])
    normal = statistics.NormalDist()
    raw = [spec["median"] * math.exp(
        spec["sigma"] * normal.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return [int(min(spec["max"], max(spec["min"], round(v)))) for v in raw]


class Deal:
    """``value(hand, k)``: the ``k``-th value dealt to ``hand``.  The
    number of hands has to divide the grid, so that a pass deals every value
    once.  ``tag`` tells the grids of one traffic mix apart (message
    lengths, output budgets), so that they are not dealt in step."""

    def __init__(self, values: List[int], hands: int, tag: int):
        if len(values) % hands:
            raise ValueError(f"{hands} hands do not divide a grid of "
                             f"{len(values)} points")
        self._values = values
        self._per_hand = len(values) // hands
        self._tag = tag
        self._orders: Dict[int, np.ndarray] = {}

    def value(self, hand: int, k: int) -> int:
        deal_pass, i = divmod(k, self._per_hand)
        if deal_pass not in self._orders:
            self._orders[deal_pass] = np.random.default_rng(
                (0, self._tag, deal_pass)).permutation(len(self._values))
        order = self._orders[deal_pass]
        return int(self._values[
            order[(hand * self._per_hand + i) % len(self._values)]])
