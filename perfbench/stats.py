"""Order statistics over raw samples: exact percentiles and quartile spread."""

from __future__ import annotations

import statistics

import numpy as np


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) of the raw samples, by linear
    interpolation between closest ranks (numpy's default method): exact
    over the samples given, no binning."""
    arr = np.asarray(values, np.float64)
    if arr.size == 0:
        raise ValueError("percentile of no samples")
    return float(np.percentile(arr, q))


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
