"""Open-loop arrivals and the statistics taken over them.

Arrivals are drawn up front. Every seed gets the same multiset of gaps
between arrivals (the ``n`` quantiles of an exponential distribution with
the mix's rate, scaled so that ``n`` arrivals fall inside the window) in
an order the seed shuffles: the same work and the same burst sizes in
another order, so the seed does not change how much load a run offers.

A request's latency runs from when it was due, not from when the
generator got round to submitting it, so a stalled generator or a
blocking submit is counted against the system, and how late the
generator ran is reported on its own. Percentiles are exact order
statistics (nearest rank) over every request due in the window; a
request that failed or never answered counts as infinitely late.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence

import numpy as np


def arrivals(rate_per_s: float, seconds: float, seed: int) -> np.ndarray:
    """Due times, in seconds from the window's start, of
    ``round(rate_per_s * seconds)`` requests (at least one), ascending
    and all inside ``[0, seconds)``."""
    n = max(1, int(round(rate_per_s * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate_per_s
    gaps *= seconds / (gaps.sum() + gaps.mean())
    np.random.default_rng(seed).shuffle(gaps)
    return np.cumsum(gaps)


def percentile(values: Sequence[float], q: float) -> float:
    """The nearest-rank ``q``-th percentile: the smallest value with at
    least ``q`` percent of the values at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[k - 1])


def lateness_summary(late_s: Sequence[float]) -> Dict[str, float]:
    """How far behind its schedule the generator submitted, in ms."""
    return {"p50_ms": percentile(late_s, 50) * 1e3,
            "p95_ms": percentile(late_s, 95) * 1e3,
            "max_ms": max(late_s) * 1e3}
