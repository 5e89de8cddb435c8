"""Host-side NumPy oracles for the weighted traversal subsystem."""
from __future__ import annotations

import heapq

import numpy as np


def dijkstra_reference(row_ptr: np.ndarray, col_idx: np.ndarray,
                       weights: np.ndarray, root: int) -> np.ndarray:
    """Textbook binary-heap Dijkstra over a host CSR copy — the oracle the
    delta-stepping engine is property-tested against. Returns float64[n]
    distances with inf unreached; handles parallel edges, zero weights and
    disconnected graphs (non-negative weights assumed, as enforced by
    ``from_weighted_edges``). Sums are float64; the loop runs over Python
    lists, which index faster than NumPy scalars."""
    row_ptr = np.asarray(row_ptr).tolist()
    col_idx = np.asarray(col_idx).tolist()
    weights = np.asarray(weights, np.float64).tolist()
    dist = [np.inf] * (len(row_ptr) - 1)
    dist[root] = 0.0
    heap = [(0.0, root)]
    while heap:
        d, u = heapq.heappop(heap)
        if d > dist[u]:
            continue                   # stale entry
        lo, hi = row_ptr[u], row_ptr[u + 1]
        for v, w in zip(col_idx[lo:hi], weights[lo:hi]):
            nd = d + w
            if nd < dist[v]:
                dist[v] = nd
                heapq.heappush(heap, (nd, v))
    return np.asarray(dist, np.float64)


def to_numpy_weighted(wg) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host copies of (row_ptr, col_idx, weights) for oracle use."""
    return (np.asarray(wg.row_ptr), np.asarray(wg.col_idx),
            np.asarray(wg.weights))
