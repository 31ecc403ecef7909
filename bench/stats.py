"""Percentiles as the benchmark reports them."""
from __future__ import annotations

import math
from typing import Sequence


def pct(xs: Sequence[float], q: float) -> float:
    """q-th percentile (0-100), linear between order statistics; +inf
    entries (requests that never produced a token) sort last, and a
    percentile that reaches one is +inf."""
    if not xs:
        return math.nan
    s = sorted(xs)
    r = (len(s) - 1) * q / 100.0
    lo, hi = math.floor(r), math.ceil(r)
    if math.isinf(s[hi]):
        return math.inf
    return s[lo] + (s[hi] - s[lo]) * (r - lo)
