"""The least bytes any implementation must move for one batched
Graph 500 sweep (kernel 2 with parents), whatever its algorithm.

Every search key has to look at each vertex's adjacency at least once,
but keys that share a sweep can share that read: the lower bound reads
the CSR once (``row_ptr`` int32[n+1] and ``col_idx`` int32[m]) and writes,
for every key, one int32 depth and one int32 parent per vertex. Nothing a
later change does to the traversal can make this count stale, so the
share of the memory roofline it gives can never pass 100%.
"""
from __future__ import annotations

INT32 = 4


def sweep_min_bytes(n: int, m: int, keys: int) -> int:
    """Bytes read and written at least once by a ``keys``-key sweep over a
    CSR of ``n`` vertices and ``m`` edge slots."""
    read = (n + 1) * INT32 + m * INT32
    written = 2 * n * keys * INT32
    return read + written
