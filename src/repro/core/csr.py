"""CSR graph container and builders.

Layout (all static shapes, jit-friendly):
  row_ptr : int32[n+1]   start offset of each vertex's adjacency slice
  col_idx : int32[m]     neighbour ids, sorted within each row
  src_idx : int32[m]     CSR row expansion (owner of edge slot e) — enables
                         the edge-parallel top-down / fallback formulations
  slot_pos: int32[m]     place of slot e in its row (``e - row_ptr[src]``),
                         -1 on slots at or past ``row_ptr[-1]`` (the pad of
                         a distributed edge slab). A constant of the graph:
                         every builder fills it once (``with_slot_pos`` for
                         a graph built by hand), so no traversal step
                         gathers ``row_ptr`` at m indices to rebuild it

``col_idx`` entries are always valid vertex ids (no padding inside rows);
edge-parallel code masks by frontier/visited state instead.

``WeightedCSRGraph`` extends the layout with one float32 weight per edge
slot (``weights[e]`` belongs to edge ``src_idx[e] -> col_idx[e]``) — the
substrate of the semiring traversal subsystem (``repro.traversal``):
boolean traversal ignores the weights, tropical (min-plus) traversal
relaxes over them. Symmetrized edges carry the SAME weight both ways, so
undirected shortest paths match an undirected Dijkstra oracle.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class CSRGraph(NamedTuple):
    row_ptr: jnp.ndarray  # int32[n+1]
    col_idx: jnp.ndarray  # int32[m]
    src_idx: jnp.ndarray  # int32[m]
    slot_pos: jnp.ndarray | None = None  # int32[m], -1 past row_ptr[-1]

    @property
    def n(self) -> int:
        return self.row_ptr.shape[0] - 1

    @property
    def m(self) -> int:
        return self.col_idx.shape[0]

    @property
    def deg(self) -> jnp.ndarray:
        return self.row_ptr[1:] - self.row_ptr[:-1]


class WeightedCSRGraph(NamedTuple):
    row_ptr: jnp.ndarray  # int32[n+1]
    col_idx: jnp.ndarray  # int32[m]
    src_idx: jnp.ndarray  # int32[m]
    weights: jnp.ndarray  # float32[m] — weight of edge src_idx[e]->col_idx[e]
    slot_pos: jnp.ndarray | None = None  # int32[m], -1 past row_ptr[-1]

    @property
    def n(self) -> int:
        return self.row_ptr.shape[0] - 1

    @property
    def m(self) -> int:
        return self.col_idx.shape[0]

    @property
    def deg(self) -> jnp.ndarray:
        return self.row_ptr[1:] - self.row_ptr[:-1]

    @property
    def csr(self) -> CSRGraph:
        """The unweighted view — every boolean-semiring consumer (the
        MS-BFS engines, the analytics sweeps) takes this; the weights ride
        alongside for the tropical/numeric semirings only."""
        return CSRGraph(row_ptr=self.row_ptr, col_idx=self.col_idx,
                        src_idx=self.src_idx, slot_pos=self.slot_pos)


@jax.jit
def build_slot_pos(row_ptr: jnp.ndarray, src_idx: jnp.ndarray) -> jnp.ndarray:
    """The ``slot_pos`` table from the slot-to-row table ``src_idx``: one
    gather of ``row_ptr`` at m indices; -1 from ``row_ptr[-1]`` on."""
    e = jnp.arange(src_idx.shape[0], dtype=jnp.int32)
    return jnp.where(e < row_ptr[-1], e - row_ptr[src_idx], -1)


def with_slot_pos(g):
    """``g`` (a ``CSRGraph`` or ``WeightedCSRGraph``) with its
    ``slot_pos`` table: ``g`` itself when it has one, else ``g`` with the
    table built on the device by one gather of ``row_ptr``. Idempotent;
    every jitted engine entry point calls it at its top, outside every
    loop and conditional."""
    if g.slot_pos is not None:
        return g
    return g._replace(slot_pos=build_slot_pos(g.row_ptr, g.src_idx))


def _build_csr(src: np.ndarray, dst: np.ndarray, w, n: int,
               symmetrize: bool, drop_self_loops: bool, dedup: bool):
    """Shared sort/symmetrize/dedup pipeline; ``w`` is None (unweighted)
    or float64[len(src)] weights carried through every permutation."""
    if len(src) * (2 if symmetrize else 1) >= 2 ** 31:
        # row_ptr/col_idx are int32 and every BFS counter (edges_traversed,
        # trace_ef/eu) sums degrees in int32 — refuse graphs that would
        # silently overflow rather than produce wrong TEPS. Checked before
        # any copy/symmetrization so absurd inputs fail fast; conservative
        # w.r.t. self-loop/dup removal.
        raise ValueError(
            f"edge count {len(src)} overflows the int32 CSR/counter layout")
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    if w is not None:
        w = np.asarray(w, dtype=np.float64)
        if w.shape != src.shape:
            raise ValueError(f"weights shape {w.shape} != edge count "
                             f"{src.shape}")
    if symmetrize:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
        if w is not None:
            w = np.concatenate([w, w])   # reverse edge keeps the SAME weight
    if drop_self_loops:
        keep = src != dst
        src, dst = src[keep], dst[keep]
        if w is not None:
            w = w[keep]
    key = src * n + dst
    if w is None:
        order = np.argsort(key, kind="stable")
    else:
        # secondary sort by weight: dedup's keep-first rule then keeps the
        # MINIMUM-weight parallel edge — the one shortest paths would use
        order = np.lexsort((w, key))
    src, dst = src[order], dst[order]
    if w is not None:
        w = w[order]
    if dedup and len(src):
        keep = np.ones(len(src), dtype=bool)
        keep[1:] = (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])
        src, dst = src[keep], dst[keep]
        if w is not None:
            w = w[keep]
    counts = np.bincount(src, minlength=n)
    row_ptr = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(counts, out=row_ptr[1:])
    return row_ptr, dst, src, w


def from_edges(src: np.ndarray, dst: np.ndarray, n: int,
               symmetrize: bool = True, drop_self_loops: bool = True,
               dedup: bool = False) -> CSRGraph:
    """Build a CSR graph from a directed edge list (host-side, numpy).

    Graph500 graphs are undirected: ``symmetrize`` adds the reverse edges.
    """
    row_ptr, dst, src, _ = _build_csr(src, dst, None, n, symmetrize,
                                      drop_self_loops, dedup)
    return with_slot_pos(CSRGraph(
        row_ptr=jnp.asarray(row_ptr, dtype=jnp.int32),
        col_idx=jnp.asarray(dst, dtype=jnp.int32),
        src_idx=jnp.asarray(src, dtype=jnp.int32),
    ))


def from_weighted_edges(src: np.ndarray, dst: np.ndarray, w: np.ndarray,
                        n: int, symmetrize: bool = True,
                        drop_self_loops: bool = True,
                        dedup: bool = False) -> WeightedCSRGraph:
    """``from_edges`` with one non-negative weight per directed input edge.

    ``symmetrize`` gives the reverse edge the same weight (undirected
    semantics); ``dedup`` keeps the minimum-weight copy of parallel edges
    (the only one shortest paths can use). Negative weights are rejected —
    the delta-stepping engine (and Dijkstra) require w >= 0.
    """
    w = np.asarray(w, dtype=np.float64)
    # finiteness AND sign checked explicitly: a `min() < 0` guard lets
    # NaN through (fails both orderings), `>= 0` alone lets +inf through
    # (which turns default_delta into inf and silently degrades the
    # bucketed engine to pure Bellman-Ford) — both must raise here
    ok = np.isfinite(w) & (w >= 0)
    if len(w) and not ok.all():
        bad = w[~ok][0]
        raise ValueError(
            f"invalid edge weight {bad} — tropical traversal "
            f"(delta-stepping / Dijkstra) requires finite non-negative "
            f"weights")
    row_ptr, dst, src, w = _build_csr(src, dst, w, n, symmetrize,
                                      drop_self_loops, dedup)
    return with_slot_pos(WeightedCSRGraph(
        row_ptr=jnp.asarray(row_ptr, dtype=jnp.int32),
        col_idx=jnp.asarray(dst, dtype=jnp.int32),
        src_idx=jnp.asarray(src, dtype=jnp.int32),
        weights=jnp.asarray(w, dtype=jnp.float32),
    ))


def to_numpy_adj(g: CSRGraph) -> tuple[np.ndarray, np.ndarray]:
    """Host copies of (row_ptr, col_idx) for oracle/validator use."""
    return np.asarray(g.row_ptr), np.asarray(g.col_idx)


def relabel(g: CSRGraph, perm: np.ndarray) -> CSRGraph:
    """Relabel vertices: new id of old vertex v is ``perm[v]``.

    Used for BFS locality reordering (beyond-paper optimisation): vertices
    visited consecutively get consecutive ids, improving gather locality of
    both the probe kernel and GNN SpMM.
    """
    row_ptr, col_idx = to_numpy_adj(g)
    n = g.n
    perm = np.asarray(perm)
    src = perm[np.asarray(g.src_idx)]
    dst = perm[col_idx]
    return from_edges(src, dst, n, symmetrize=False, drop_self_loops=False)


def ell_pad(g: CSRGraph, k_max: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Restructure CSR rows into an ELL slab: int32[n, k_max] neighbour ids
    (padded with n) + bool[n, k_max] validity. The paper's core insight —
    restructure irregular data into a vector-friendly layout — applied to
    message passing. Rows longer than k_max are truncated (caller handles
    the residue via the edge-parallel path, mirroring MAX_POS + fallback).
    """
    n, rp, ci = g.n, g.row_ptr, g.col_idx
    pos = jnp.arange(k_max, dtype=jnp.int32)[None, :]
    starts = rp[:-1][:, None]
    valid = pos < g.deg[:, None]
    idx = jnp.clip(starts + pos, 0, g.m - 1)
    neigh = jnp.where(valid, ci[idx], n)
    return neigh.astype(jnp.int32), valid
