"""Histogram registry with p50/p95/p99 summaries.

:func:`summarize` is the workhorse: it turns a flat sample list into
the ``{count, mean, min, max, p50, p95, p99}`` dict that
``ServeMetrics.to_dict`` embeds for TTFT and inter-token latency (the
real distributions the flat aggregate used to hide).
:class:`Histogram` (a bounded deterministic reservoir) and
:class:`MetricsRegistry` are what ``obsview`` accumulates span
durations in.

Percentiles use linear interpolation between order statistics (the
numpy ``linear`` method), computed in pure Python so the hot path never
pays an array conversion for a handful of samples.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence

SUMMARY_QUANTILES = (50.0, 95.0, 99.0)


def percentile(values: Sequence[float], q: float) -> float:
    """q-th percentile (0..100) with linear interpolation; ``values``
    need not be sorted.  Returns 0.0 on empty input (the zero-traffic
    edge case must not crash a metrics dump)."""
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"q must be in [0, 100], got {q}")
    n = len(values)
    if n == 0:
        return 0.0
    vs = sorted(values)
    if n == 1:
        return float(vs[0])
    pos = (q / 100.0) * (n - 1)
    lo = int(pos)
    hi = min(lo + 1, n - 1)
    frac = pos - lo
    return float(vs[lo] * (1.0 - frac) + vs[hi] * frac)


def summarize(values: Sequence[float],
              quantiles: Iterable[float] = SUMMARY_QUANTILES) -> dict:
    """``{count, mean, min, max, p50, p95, p99}`` for a sample list;
    all-zero (count 0) on empty input."""
    n = len(values)
    out = {
        "count": n,
        "mean": (sum(values) / n) if n else 0.0,
        "min": float(min(values)) if n else 0.0,
        "max": float(max(values)) if n else 0.0,
    }
    vs = sorted(values)
    for q in quantiles:
        key = f"p{q:g}".replace(".", "_")
        out[key] = percentile(vs, q) if n else 0.0
    return out


class Histogram:
    """Bounded-memory distribution with exact count/mean/min/max and
    reservoir-sampled percentiles.

    Up to ``capacity`` observations are kept verbatim (percentiles are
    then exact); past that, each new observation replaces a
    deterministically chosen slot with probability ``capacity/seen``
    (Vitter's algorithm R, driven by a fixed linear-congruential stream
    so two runs over the same sample order summarize identically —
    CI-comparable without a numpy dependency in the hot path).
    """

    __slots__ = ("capacity", "count", "total", "vmin", "vmax",
                 "_values", "_lcg")

    def __init__(self, capacity: int = 8192) -> None:
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.count = 0
        self.total = 0.0
        self.vmin = float("inf")
        self.vmax = float("-inf")
        self._values: List[float] = []
        self._lcg = 0x9E3779B9

    def observe(self, v: float) -> None:
        v = float(v)
        self.count += 1
        self.total += v
        if v < self.vmin:
            self.vmin = v
        if v > self.vmax:
            self.vmax = v
        if len(self._values) < self.capacity:
            self._values.append(v)
            return
        # reservoir: replace index (rand % count) when it lands in range
        self._lcg = (self._lcg * 1103515245 + 12345) & 0x7FFFFFFF
        idx = self._lcg % self.count
        if idx < self.capacity:
            self._values[idx] = v

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def percentile(self, q: float) -> float:
        return percentile(self._values, q)

    def summary(self) -> dict:
        s = summarize(self._values)
        # exact moments override the reservoir's view of them
        s["count"] = self.count
        s["mean"] = self.mean
        s["min"] = self.vmin if self.count else 0.0
        s["max"] = self.vmax if self.count else 0.0
        return s


@dataclasses.dataclass
class MetricsRegistry:
    """Name-keyed get-or-create registry of histograms; ``to_dict``
    snapshots them JSON-serializably."""

    histograms: Dict[str, Histogram] = dataclasses.field(
        default_factory=dict)
    histogram_capacity: int = 8192

    def histogram(self, name: str,
                  capacity: Optional[int] = None) -> Histogram:
        h = self.histograms.get(name)
        if h is None:
            h = self.histograms[name] = Histogram(
                capacity or self.histogram_capacity)
        return h

    def to_dict(self) -> dict:
        return {
            "histograms": {k: h.summary()
                           for k, h in sorted(self.histograms.items())},
        }
