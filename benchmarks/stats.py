"""Percentile and arrival arithmetic of the load generators (copied in
spirit from ``tools/load_bench.py``'s ``_pct`` / ``_arrival_gaps``, which a
later PR may delete in favour of this file)."""

from __future__ import annotations

import math
from typing import List, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``% of
    the sample at or below it. ``inf`` entries (failed requests) sort last."""
    if not values:
        raise ValueError("percentile of an empty sample")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def arrival_gaps(rng: np.random.Generator, n: int, rate: float,
                 burst: int = 1) -> List[float]:
    """Gaps (seconds) before each of ``n`` requests of an open loop at
    ``rate`` per second: exponential gaps (Poisson arrivals); with
    ``burst`` > 1, requests come ``burst`` at a time with the gaps between
    bursts stretched so that the mean rate is unchanged."""
    if rate <= 0:
        raise ValueError("rate must be positive")
    if burst <= 1:
        return rng.exponential(1.0 / rate, size=n).tolist()
    gaps = []
    for i in range(n):
        gaps.append(float(rng.exponential(burst / rate)) if i % burst == 0 else 0.0)
    return gaps
