"""The least bytes any implementation must move for one batched Graph 500
kernel-3 sweep (SSSP with parents), whatever its algorithm.

Every key has to look at each vertex's weighted adjacency at least once,
and keys that share a sweep can share that read: the lower bound reads
the CSR and its weights once (``row_ptr`` int32[n+1], ``col_idx`` int32[m],
``weights`` float32[m]) and writes, for every key, one float32 distance
and one int32 parent per vertex. Nothing a later change does to the
traversal can make this count stale, so the share of the memory roofline
it gives can never pass 100%.
"""
from __future__ import annotations

WORD = 4


def sssp_min_bytes(n: int, m: int, keys: int) -> int:
    """Bytes read and written at least once by a ``keys``-key SSSP sweep
    over a weighted CSR of ``n`` vertices and ``m`` edge slots."""
    read = (n + 1) * WORD + 2 * m * WORD
    written = 2 * n * keys * WORD
    return read + written
