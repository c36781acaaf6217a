"""Percentiles as the benchmark reports them."""

from __future__ import annotations

import math
from collections import Counter


def percentile(values, q: float) -> float:
    """The q-quantile (0..1) by the nearest-rank rule on sorted values."""
    vals = sorted(values)
    if not vals:
        raise ValueError("no samples")
    return vals[min(len(vals) - 1, max(0, math.ceil(q * len(vals)) - 1))]


def supported(n: int, q: float) -> bool:
    """A percentile is reported only with ten samples or more beyond it."""
    return n * (1.0 - q) >= 10


def median(values) -> float:
    return percentile(values, 0.5)


def tally(items) -> dict:
    """How often each item occurs, keyed by its string (for a JSON line)."""
    return {str(k): v for k, v in Counter(items).items()}
