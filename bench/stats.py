"""Percentile and rate arithmetic of the benchmark (its own copy).

Percentiles interpolate linearly between order statistics, as
``numpy.percentile`` does by default; an empty sample has none.
"""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    arr = np.asarray(list(values), np.float64)
    return float(np.percentile(arr, q)) if arr.size else None


def rate(count: int, seconds: float) -> float | None:
    return count / seconds if count and seconds > 0 else None


def mean(values) -> float | None:
    arr = np.asarray(list(values), np.float64)
    return float(arr.mean()) if arr.size else None
