"""Order statistics the end-to-end metrics use."""
from __future__ import annotations

import math


def percentile(xs, p: float) -> float:
    """Nearest-rank percentile: the smallest observed value with at least
    ``p`` percent of the sample at or below it. Always a sample, never an
    interpolation. Raises on an empty sample."""
    xs = sorted(float(x) for x in xs)
    if not xs:
        raise ValueError("percentile of an empty sample")
    rank = math.ceil(p / 100.0 * len(xs))            # 1-based nearest rank
    return xs[min(max(rank, 1), len(xs)) - 1]
