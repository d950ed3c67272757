"""Order statistics the benchmark reports."""
from __future__ import annotations

import math

import numpy as np


def nearest_rank(values, q: float) -> float:
    """The ``q``-quantile (0 < q <= 1) by nearest rank: the smallest value
    with at least ``q`` of the values at or below it.  A missing value
    (``inf``) counts as larger than every other."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return float("nan")
    return float(v[max(0, math.ceil(q * v.size) - 1)])
