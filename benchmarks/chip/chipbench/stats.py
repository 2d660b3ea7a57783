"""Order statistics the metrics share.

A percentile is taken over every sample given (all requests, all gaps),
never over a subsample; a spread is the interquartile distance of
`statistics.quantiles(values, n=4)` as a share of the median.
"""

from __future__ import annotations

import statistics
from typing import Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (linear interpolation) of all values; None when
    there are none."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def spread(values: Sequence[float]) -> float:
    """(Q3 - Q1) / median, with Python's default quartile method."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
