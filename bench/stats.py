"""Percentiles and the small arithmetic the metric readers share."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100), linear between order statistics
    (numpy's default rule).  Raises on an empty sample."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttft_samples(rec) -> list:
    """Time to first token of every request sent, from its due time; a
    request that never got one counts with the time it waited before it
    was given up (a miss)."""
    return [o.ttft_s if len(o.tokens) else o.finish_s
            for o in rec.outs.values()]


def tokens_out(rec) -> int:
    return int(sum(len(o.tokens) for o in rec.outs.values()))
