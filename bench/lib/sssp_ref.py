"""Plain NumPy shortest paths, the reference for the kernel-3 distances.

Bellman-Ford over the CSR's slot arrays in float64, one key at a time:
each round relaxes every slot (``d[col] + w``) and takes each row's
minimum, until a round changes nothing. Written apart from the program
and importing nothing of it. Parallel slots and zero weights are taken
as they are, one relaxation per slot, which is what the graph means (a
sparse-matrix shortest-path routine would merge repeated entries and drop
explicit zeros).
"""
from __future__ import annotations

import numpy as np


def shortest_paths(row_ptr: np.ndarray, col_idx: np.ndarray,
                   weights: np.ndarray, keys) -> np.ndarray:
    """float64[n, K] distances from each key (inf unreached)."""
    row_ptr = np.asarray(row_ptr, np.int64)
    col_idx = np.asarray(col_idx, np.int64)
    weights = np.asarray(weights, np.float64)
    keys = np.asarray(keys, np.int64).reshape(-1)
    n = len(row_ptr) - 1
    rows = np.flatnonzero(np.diff(row_ptr) > 0)
    starts = row_ptr[rows]
    out = np.empty((n, keys.size))
    # a contiguous vector per key: a gather of one float64 a slot is
    # several times cheaper than a gather of a row of K
    for k, key in enumerate(keys):
        dist = np.full(n, np.inf)
        dist[key] = 0.0
        while True:
            via = np.minimum.reduceat(np.take(dist, col_idx) + weights,
                                      starts)
            better = via < dist[rows]
            if not better.any():
                break
            dist[rows[better]] = via[better]
        out[:, k] = dist
    return out


def mismatch(got: np.ndarray, want: np.ndarray, tol: float) -> np.ndarray:
    """bool[n, K]: reachability differs, or the distance by more than
    ``tol``."""
    got = np.asarray(got, np.float64)
    seen = np.isfinite(got)
    differ = seen != np.isfinite(want)
    both = seen & ~differ
    far = np.zeros_like(differ)
    far[both] = np.abs(got[both] - want[both]) > tol
    return differ | far
