"""Percentiles as the benchmark reports them."""

from __future__ import annotations

import math
from typing import Sequence


def pct(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least a share
    `q` of the values at or below it."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    k = max(0, min(len(s) - 1, math.ceil(q * len(s)) - 1))
    return float(s[k])
