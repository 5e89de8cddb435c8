"""Semiring abstraction over the lane-batched traversal step.

Every traversal this repo runs is one masked multi-lane semiring SpMV
(Buluc & Madduri's linear-algebra BFS, SlimSell's semiring generalization):

    out[v, l] = ADD_{e in row v} ( vals[col_idx[e], l]  MUL  w[e] )

The packed MS-BFS engines are the *boolean* instantiation — ADD = OR,
MUL = AND, with 32/64 lanes packed per machine word and the adjacency
weight identically ``one`` (``packed.segment_or`` is this module's
``segment_reduce`` specialised to bitwise words). This module carries the
same step shape over *numeric* semirings:

* ``TROPICAL``  (min, +,  zero=inf, one=0)  — shortest paths: one relax
  round of delta-stepping / Bellman-Ford per SpMV (``repro.traversal.sssp``
  runs the bucketed engine on top);
* ``PLUS_TIMES`` (+, *, zero=0, one=1)     — weighted aggregation /
  PageRank-style iteration;
* ``BOOLEAN``    (|, &, zero=0, one=1 over uint lane words) — the packed
  engines' own algebra, here in dense per-lane form so the generic path
  can be cross-checked bit-for-bit against ``packed.topdown_packed_step``.

Two execution strategies mirror the packed TD/BU split:

* ``segment_reduce`` — edge-parallel segmented scan over CSR rows (the
  generalized ``segment_or``): O(m * L * log(max degree)), covers any
  degree; and
* the MAX_POS-style *gather-relax* for the tropical semiring
  (``repro.kernels.semiring_relax``): each vertex gathers its first
  ``max_pos`` neighbours' lane values (+ edge weight, min-accumulate),
  with rows deeper than ``max_pos`` falling back to the segmented scan —
  the same probe + cond-skipped fallback structure as
  ``packed.bottomup_packed_step``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.csr import CSRGraph
from repro.core.packed import (gather_lanes, segment_scan_rows,
                               slot_rows)

__all__ = ["BOOLEAN", "PLUS_TIMES", "SEMIRINGS", "Semiring", "TROPICAL",
           "segment_reduce", "semiring_spmv", "tropical_relax"]

INF = jnp.float32(jnp.inf)


@dataclass(frozen=True)
class Semiring:
    """(ADD, MUL, zero, one) with ADD associative+commutative, ``zero``
    the ADD identity (and MUL annihilator), ``one`` the MUL identity.
    ``dtype`` is the lane-value element type the ops run in."""
    name: str
    add: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]
    mul: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]
    zero: float
    one: float
    dtype: jnp.dtype

    def zeros(self, shape) -> jnp.ndarray:
        return jnp.full(shape, self.zero, self.dtype)


TROPICAL = Semiring("tropical", jnp.minimum, jnp.add,
                    zero=float("inf"), one=0.0, dtype=jnp.float32)
PLUS_TIMES = Semiring("plus_times", jnp.add, jnp.multiply,
                      zero=0.0, one=1.0, dtype=jnp.float32)
# dense boolean lanes as uint8 0/1 (bitwise ops ARE or/and there); the
# packed engines implement the same algebra 32/64 lanes per word
BOOLEAN = Semiring("boolean", jnp.bitwise_or, jnp.bitwise_and,
                   zero=0, one=1, dtype=jnp.uint8)

SEMIRINGS = {sr.name: sr for sr in (BOOLEAN, TROPICAL, PLUS_TIMES)}


def segment_reduce(vals: jnp.ndarray, row_ptr: jnp.ndarray,
                   sr: Semiring) -> jnp.ndarray:
    """Per-CSR-row semiring ADD of edge-lane values [m, L] -> [n, L] —
    ``packed.segment_scan_rows`` in row-major layout. Empty rows produce
    ``sr.zero``; slots past ``row_ptr[-1]`` only extend the last segment
    beyond every read-out point."""
    return segment_scan_rows(vals.T, row_ptr,
                             slot_rows(row_ptr, vals.shape[0]), sr.add,
                             sr.zero).T


def semiring_spmv(g: CSRGraph, vals: jnp.ndarray, weights, sr: Semiring,
                  ) -> jnp.ndarray:
    """One lane-batched semiring SpMV: ``out[v, l] = ADD_e vals[col_e, l]
    MUL w_e`` over row v's edge slots. ``vals`` is [nf, L] with nf >= n
    (the distributed local-block shape: rows are LOCAL, ``col_idx`` holds
    global ids into ``vals``); ``weights`` is float-like [m] or None for
    the adjacency pattern (every edge weighs ``sr.one``).

    Boolean instantiation: with 0/1 lanes and weights None this is
    exactly the unpacked top-down frontier expansion
    (``packed.topdown_packed_step`` modulo the visited mask) — the
    cross-check pinning the generic path to the packed engines.
    """
    col = jnp.clip(g.col_idx, 0, vals.shape[0] - 1)
    contrib = gather_lanes(vals, col)                             # [L, m]
    if weights is not None:
        contrib = sr.mul(contrib, weights.astype(vals.dtype))
    return segment_scan_rows(contrib, g.row_ptr, g.src_idx, sr.add,
                             sr.zero).T


def _relax_fallback(g: CSRGraph, weights: jnp.ndarray, vals: jnp.ndarray,
                    max_pos: int) -> jnp.ndarray:
    """Segmented-min over edge slots at position >= ``max_pos`` of rows
    deeper than ``max_pos`` — the residue the gather-relax probe skipped.
    Inert slots contribute inf; pad slots (distributed edge slabs) sit
    past every read-out point, same argument as ``segment_or``."""
    pos_e = jnp.arange(g.m, dtype=jnp.int32) - g.row_ptr[g.src_idx]
    act = (pos_e >= max_pos) & (pos_e < g.deg[g.src_idx])
    cand = gather_lanes(vals, jnp.clip(g.col_idx, 0, vals.shape[0] - 1)) \
        + weights.astype(vals.dtype)                              # [L, m]
    cand = jnp.where(act, cand, INF)
    return segment_scan_rows(cand, g.row_ptr, g.src_idx, TROPICAL.add,
                             INF).T


def tropical_relax(g: CSRGraph, weights: jnp.ndarray, vals: jnp.ndarray,
                   max_pos: int = 8, impl: str = "xla") -> jnp.ndarray:
    """Masked min-plus gather-relax: ``out[v, l] = min_e vals[col_e, l] +
    w_e`` (inf where nothing relaxes). Masking is by value: callers encode
    inactive source vertices as ``vals == inf`` and phase-excluded edges
    as ``w == inf`` — both vanish under min-plus, so ONE contract serves
    every delta-stepping phase.

    ``impl='xla'`` runs the edge-parallel segmented scan over all edges;
    ``impl='pallas'`` runs the ``semiring_relax`` kernel over each row's
    first ``max_pos`` neighbours (the MAX_POS gather shape) with the
    deeper-row residue cond-skipped into the segmented scan — the same
    probe + fallback structure as the packed bottom-up step.
    """
    if g.m == 0:   # edgeless: nothing relaxes
        return jnp.full((g.n, vals.shape[1]), jnp.inf, vals.dtype)
    if impl == "pallas":
        from repro.kernels import semiring_relax
        acc = semiring_relax(g.row_ptr, g.col_idx, weights, vals,
                             max_pos=max_pos)
        residue = jnp.any(g.deg > max_pos)
        return jax.lax.cond(
            residue,
            lambda a: jnp.minimum(
                a, _relax_fallback(g, weights, vals, max_pos)),
            lambda a: a, acc)
    return semiring_spmv(g, vals, weights, TROPICAL)
