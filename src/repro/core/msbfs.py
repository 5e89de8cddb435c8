"""Bit-packed multi-source BFS (MS-BFS) — batched traversal subsystem.

The paper vectorises ONE frontier across SIMD lanes; this module lifts the
same insight one level up (Then et al., "The More the Merrier"; SlimSell):
independent BFS traversals run concurrently by packing per-root state into
uint32 *lane words* — bit ``r & 31`` of word ``r >> 5`` at row ``v`` means
"root r's traversal has reached v".

Two engines share the packed step formulations:

* ``msbfs`` — one batch of R <= ``MAX_LANES`` roots, a single
  ``lax.while_loop`` sweep (PR 1).
* the *pipelined* engine (``msbfs_pipelined`` and the
  ``msbfs_engine_*`` stepping API) — arbitrary root counts streamed
  through a fixed pool of ``lanes`` bit-lanes. Roots live in a pending
  queue; the moment a lane's traversal finishes (frontier empty or the
  MAX_TRACE cap), its per-root results are flushed to the output slot and
  the lane is *immediately refilled* from the queue — no barrier between
  word-batches, so deep lanes never stall shallow ones. ``W`` (lane words
  per vertex) derives from the active lane pool, not a hard-coded
  ``MAX_LANES // 32``. New roots may be enqueued mid-sweep
  (``msbfs_engine_enqueue``) — the serving entry point
  ``repro.launch.serve_bfs`` drives exactly that loop.

State layout (all static shapes, jit-friendly):
  frontier : uint32[n, W]   W = ceil(num_roots / 32) lane words per vertex
  visited  : uint32[n, W]
  depth    : int32[n, R]    per-lane depth, -1 unreached

Both traversal directions become pure bitwise word ops:
  * top-down   — every edge lane contributes ``frontier[col] & td_sel``;
    per-row OR via a segmented scan (CSR rows are contiguous, so
    segment-OR is ``packed.segment_scan_rows`` over lane-major words).
  * bottom-up  — the paper's MAX_POS probe, word-packed: each vertex
    gathers the lane words of its first MAX_POS neighbours and ORs them
    (``repro.kernels.msbfs_probe`` is the Pallas analog); rows with
    deg > MAX_POS and unserved lanes fall back to the segmented scan,
    lax.cond-skipped when the probe retired everything.

Direction is chosen *per lane* each layer with the same alpha/beta rule as
the scalar controller (``repro.core.hybrid.switch_direction``): lanes in
top-down mode are selected by ``td_sel`` words, bottom-up lanes by
``bu_sel``, and the two partial frontiers are OR-merged.

Parent selection: parents are derived once at the end from the depth
arrays (min-id neighbour one level up), so they are *valid* Graph500
parents; serial ``bfs`` picks the min frontier-neighbour per layer, which
coincides for the min-parent rule — tests assert exact parent equality on
top of validator-level equivalence. Up to 2**24 vertices the rule is one
per-row minimum of the key ``(depth + 1) << 24 | id`` over reached row
entries, exact because every step pulls a row from its entries: no
reached entry of a reached vertex lies more than one level above it.

The packed step formulations themselves (lane packing, the segmented-OR
scan, the word-packed probe, per-lane direction dispatch) live in
``repro.core.packed`` — ONE implementation shared with the sharded engine
``repro.core.dist_msbfs`` (re-exported here for compatibility).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.csr import CSRGraph
from repro.core.hybrid import ALPHA_DEFAULT, BETA_DEFAULT, MAX_TRACE
from repro.core.packed import (LANE_WORD_BITS, MODES, _dispatch_packed_step,
                               adaptive_lane_pool, depth_slice_words,
                               dispatch_packed_step, lane_counters,
                               num_lane_words, pack_lanes, queue_claims,
                               segment_or, segment_scan_rows,
                               select_direction, unpack_lanes, word_dtype)

__all__ = [
    "LANE_WORD_BITS", "LayerReadout", "MAX_LANES", "MODES", "MSBFSResult",
    "adaptive_lane_pool", "depth_slice_words", "msbfs",
    "msbfs_engine_drain", "msbfs_engine_enqueue", "msbfs_engine_idle",
    "msbfs_engine_init", "msbfs_engine_readout", "msbfs_engine_result",
    "msbfs_engine_retire", "msbfs_engine_step", "msbfs_engine_stream",
    "msbfs_pipelined", "num_lane_words", "pack_lanes", "segment_or",
    "unpack_lanes",
]

MAX_LANES = 64          # two uint32 words of roots per batch


class MSBFSResult(NamedTuple):
    parent: jnp.ndarray          # int32[n, R], -1 unreached, parent[root_r, r]=root_r
    depth: jnp.ndarray           # int32[n, R], -1 unreached
    num_layers: jnp.ndarray      # int32[R] — layers until lane r's frontier emptied
    edges_traversed: jnp.ndarray  # int32[R] — 2x undirected component edges per lane
    trace_dir: jnp.ndarray       # int32[MAX_TRACE, R]: 0 TD, 1 BU, -1 lane idle
    trace_vf: jnp.ndarray        # int32[MAX_TRACE, R]
    trace_ef: jnp.ndarray        # int32[MAX_TRACE, R]
    trace_eu: jnp.ndarray        # int32[MAX_TRACE, R]
    # int32 scalar on the device: steps whose bottom-up fallback scan ran
    # (the pipelined engine; None from engines that do not count them)
    bu_fallback_passes: jnp.ndarray | None = None

    def reached_words(self, max_depth=None, min_depth=0) -> jnp.ndarray:
        """Packed lane words over the depth band [min_depth, max_depth] —
        the engines' own bit layout, recovered from the result. With the
        defaults this is each lane's full reached set; ``max_depth=k``
        slices the k-hop neighbourhood (``repro.analytics.khop`` rides
        this), ``min_depth=max_depth=d`` reconstructs the layer-d
        frontier."""
        if max_depth is None:
            max_depth = jnp.iinfo(jnp.int32).max
        return depth_slice_words(self.depth, max_depth, min_depth)


class _State(NamedTuple):
    frontier: jnp.ndarray        # uint32[n, W]
    visited: jnp.ndarray         # uint32[n, W]
    depth: jnp.ndarray           # int32[n, R]
    topdown: jnp.ndarray         # bool[R]
    layer: jnp.ndarray           # int32 scalar
    trace_dir: jnp.ndarray
    trace_vf: jnp.ndarray
    trace_ef: jnp.ndarray
    trace_eu: jnp.ndarray


# lanes whose depths share one uint32 word in _derive_parents: a depth + 1
# takes one byte, since no lane runs past MAX_TRACE layers
_PARENT_LANES_PER_WORD = 4
assert MAX_TRACE < 255
# the keyed rule's (depth + 1, id) key: the byte on top, the id below it
_KEY_ID_BITS = 24
_NO_KEY = 0xFFFFFFFF


def _chunk_parents_keyed(g: CSRGraph, w: jnp.ndarray,
                         shifts: jnp.ndarray) -> jnp.ndarray:
    """Parents of the lanes packed in ``w`` (uint32[n], one ``depth + 1``
    byte per lane at ``shifts``) from one gather of m words, ``w[col]``.

    Per lane and row, the least key ``byte(u) << 24 | u`` over the reached
    entries u gives their least depth and, at that depth, the least id.
    Needs ``g.n <= 2**24``.
    """
    col = g.col_idx.astype(jnp.uint32)
    at_col = (w[g.col_idx][None, :] >> shifts[:, None]) & 0xFF  # [k, m]
    key = jnp.where(at_col != 0, (at_col << _KEY_ID_BITS) | col[None, :],
                    jnp.uint32(_NO_KEY))
    best = segment_scan_rows(key, g.row_ptr, g.src_idx, jnp.minimum,
                             _NO_KEY)                              # [k, n]
    own = (w[None, :] >> shifts[:, None]) & 0xFF   # the row's byte, no gather
    # the empty key's top byte (255) is above any byte + 1, so never matches
    ok = (best >> _KEY_ID_BITS) + 1 == own
    return jnp.where(ok, (best & ((1 << _KEY_ID_BITS) - 1)).astype(jnp.int32),
                     -1)


def _chunk_parents_pair(g: CSRGraph, w: jnp.ndarray,
                        shifts: jnp.ndarray) -> jnp.ndarray:
    """``_chunk_parents_keyed``'s result from two gathers of m words,
    ``w[col]`` and ``w[src]``, for ids of any width."""
    n, src, col = g.n, g.src_idx, g.col_idx
    at_col = (w[col][None, :] >> shifts[:, None]) & 0xFF    # [k, m]
    at_src = (w[src][None, :] >> shifts[:, None]) & 0xFF
    ok = (at_col != 0) & (at_col + 1 == at_src)
    cand = jnp.where(ok, col[None, :], n).astype(jnp.int32)
    best = segment_scan_rows(cand, g.row_ptr, src, jnp.minimum, n)
    return jnp.where(best < n, best, -1)                    # [k, n]


@jax.jit
def _derive_parents(g: CSRGraph, depth: jnp.ndarray,
                    roots: jnp.ndarray) -> jnp.ndarray:
    """parent[v, r] = min-id neighbour of v one level up in lane r.

    Four lanes at a time (``lax.map``): their ``depth + 1`` bytes share one
    uint32 word per vertex. A gather on a TPU costs per index, not per
    byte, so four lanes cost what one does. With ``g.n <= 2**24`` a chunk
    gathers m words once (``_chunk_parents_keyed``): the per-row minimum
    of ``(depth + 1) << 24 | id`` over reached entries is exact. Every
    step pulls a row from its entries, so no reached entry of a reached v
    lies more than one level above v; the least depth among them is one
    above v's exactly when v has a parent, and its least id is that
    parent. Larger graphs gather at ``col`` and at ``src``
    (``_chunk_parents_pair``). The min-id rule matches the serial steps'
    deterministic scatter-min parent choice.
    """
    n = g.n
    num_roots = roots.shape[0]
    if num_roots == 0:
        return jnp.zeros((n, 0), jnp.int32)
    k = _PARENT_LANES_PER_WORD
    chunks = -(-num_roots // k)
    biased = jnp.pad(depth + 1, ((0, 0), (0, chunks * k - num_roots)))
    shifts = 8 * jnp.arange(k, dtype=jnp.uint32)
    words = (biased.astype(jnp.uint32).reshape(n, chunks, k)
             << shifts).sum(axis=-1, dtype=jnp.uint32)         # [n, chunks]
    rule = (_chunk_parents_keyed if n <= 1 << _KEY_ID_BITS
            else _chunk_parents_pair)
    parent = jax.lax.map(lambda w: rule(g, w, shifts), words.T)  # [chunks, k, n]
    parent = parent.reshape(chunks * k, n)[:num_roots].T
    lane = jnp.arange(num_roots)
    return parent.at[roots, lane].set(roots.astype(jnp.int32))


@partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def msbfs(g: CSRGraph, roots: jnp.ndarray, mode: str = "hybrid",
          alpha: float = ALPHA_DEFAULT, beta: float = BETA_DEFAULT,
          max_pos: int = 8, probe_impl: str = "xla") -> MSBFSResult:
    """Run up to MAX_LANES BFS traversals concurrently, one bit-lane each.

    Args:
      roots: int[R] root vertex per lane, R <= 64. Compiles once per
        (graph shape, R, mode) — the Graph500 batched harness answers all
        64 roots with a single executable sweep.
      mode: "hybrid" (per-lane alpha/beta switching), "topdown", "bottomup".
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    n = g.n
    roots = roots.astype(jnp.int32)
    num_roots = roots.shape[0]
    if num_roots > MAX_LANES:
        raise ValueError(f"at most {MAX_LANES} roots per batch, "
                         f"got {num_roots} — use msbfs_pipelined for "
                         f"arbitrary root counts")
    w = num_lane_words(num_roots)
    root_onehot = roots[None, :] == jnp.arange(n, dtype=jnp.int32)[:, None]
    frontier0 = pack_lanes(root_onehot)                      # uint32[n, W]
    lane_mask = pack_lanes(jnp.ones((num_roots,), jnp.bool_))  # uint32[W]

    def cond_fn(s: _State):
        return jnp.any(s.frontier != 0) & (s.layer < MAX_TRACE)

    def body_fn(s: _State):
        frontier_b = unpack_lanes(s.frontier, num_roots)
        visited_b = unpack_lanes(s.visited, num_roots)
        e_f, v_f, e_u = lane_counters(g, frontier_b, visited_b)
        topdown = select_direction(mode, s.topdown, e_f, v_f, e_u, n,
                                   alpha, beta, num_roots)

        # dead lanes (empty frontier) leave BOTH selectors: the switch rule
        # flips them to TD (v_f = 0 < n/beta), which would otherwise keep
        # td_sel nonzero forever and defeat the cond-skip in the dispatch
        live = v_f > 0
        td_sel = pack_lanes(topdown & live) & lane_mask      # uint32[W]
        bu_sel = pack_lanes(~topdown & live) & lane_mask
        new = dispatch_packed_step(g, s.frontier, s.visited, td_sel,
                                   bu_sel, mode, max_pos, probe_impl)

        depth2 = jnp.where(unpack_lanes(new, num_roots), s.layer + 1, s.depth)
        i = s.layer
        # dead lanes record nothing (-1 dir, zero counters) — the rows a
        # finished lane never ran must read identically to the serial
        # trace and to the pipelined engine, which retires the lane
        return _State(
            frontier=new, visited=s.visited | new, depth=depth2,
            topdown=topdown, layer=i + 1,
            trace_dir=s.trace_dir.at[i].set(
                jnp.where(live, jnp.where(topdown, 0, 1),
                          -1).astype(jnp.int32)),
            trace_vf=s.trace_vf.at[i].set(jnp.where(live, v_f, 0)),
            trace_ef=s.trace_ef.at[i].set(jnp.where(live, e_f, 0)),
            trace_eu=s.trace_eu.at[i].set(jnp.where(live, e_u, 0)),
        )

    init = _State(
        frontier=frontier0, visited=frontier0,
        depth=jnp.where(root_onehot, 0, -1).astype(jnp.int32),
        topdown=jnp.full((num_roots,), mode != "bottomup"),
        layer=jnp.int32(0),
        trace_dir=jnp.full((MAX_TRACE, num_roots), -1, jnp.int32),
        trace_vf=jnp.zeros((MAX_TRACE, num_roots), jnp.int32),
        trace_ef=jnp.zeros((MAX_TRACE, num_roots), jnp.int32),
        trace_eu=jnp.zeros((MAX_TRACE, num_roots), jnp.int32),
    )
    s = jax.lax.while_loop(cond_fn, body_fn, init)

    visited_b = unpack_lanes(s.visited, num_roots)
    deg = g.deg.astype(jnp.int32)[:, None]
    edges = jnp.sum(jnp.where(visited_b, deg, 0), axis=0,
                    dtype=jnp.int32)
    # a cap-terminated lane ran exactly MAX_TRACE layers (the serial
    # controller's loop bound and the pipelined engine's flush agree)
    num_layers = jnp.minimum(jnp.max(s.depth, axis=0) + 1, MAX_TRACE)
    parent = _derive_parents(g, s.depth, roots)
    return MSBFSResult(parent=parent, depth=s.depth, num_layers=num_layers,
                       edges_traversed=edges, trace_dir=s.trace_dir,
                       trace_vf=s.trace_vf, trace_eu=s.trace_eu,
                       trace_ef=s.trace_ef)


# ---------------------------------------------------------------------------
# Pipelined engine: arbitrary root counts through a fixed bit-lane pool.
#
# State invariants (maintained by _refill / the step body):
#   * lane_qidx[l] < capacity  <=>  lane l is serving queue slot lane_qidx[l];
#     idle lanes hold lane_qidx == capacity and have all-zero frontier /
#     visited bits and an all -1 depth column.
#   * queue[:queued] holds enqueued roots; queue slots [next_root, queued)
#     are pending. Every claimed slot is served by exactly one lane until
#     its traversal finishes, then flushed to out_* column lane_qidx[l].
#   * out_layers[q] > 0  <=>  query q has been answered (flushed).
# Output arrays carry one trailing *trash* column (index == capacity) that
# absorbs the per-layer scatter of non-finished lanes, keeping the flush a
# single static-shape write.
# ---------------------------------------------------------------------------


class PipelineState(NamedTuple):
    frontier: jnp.ndarray        # uint32[n, W]  packed lane frontiers
    visited: jnp.ndarray         # uint32[n, W]
    depth: jnp.ndarray           # int32[n, L]   active-lane depths (-1 unreached)
    lane_layer: jnp.ndarray      # int32[L]      steps run for the lane's root
    lane_qidx: jnp.ndarray       # int32[L]      queue slot served; capacity = idle
    topdown: jnp.ndarray         # bool[L]
    queue: jnp.ndarray           # int32[capacity] enqueued root ids
    queued: jnp.ndarray          # int32 scalar  number of roots enqueued
    next_root: jnp.ndarray       # int32 scalar  next queue slot to claim
    sweep_layers: jnp.ndarray    # int32 scalar  total engine steps run
    bu_fallback_passes: jnp.ndarray  # int32 scalar  BU fallback passes run
    out_depth: jnp.ndarray       # int32[n, capacity+1]
    out_edges: jnp.ndarray       # int32[capacity+1]
    out_layers: jnp.ndarray      # int32[capacity+1]  0 = unanswered
    trace_dir: jnp.ndarray       # int32[MAX_TRACE, capacity+1]
    trace_vf: jnp.ndarray
    trace_ef: jnp.ndarray
    trace_eu: jnp.ndarray

    @property
    def num_lanes(self) -> int:
        return self.lane_qidx.shape[0]

    @property
    def capacity(self) -> int:
        return self.queue.shape[0]


def msbfs_engine_init(g: CSRGraph, capacity: int,
                      lanes: int = MAX_LANES) -> PipelineState:
    """Fresh engine: all lanes idle, empty root queue of ``capacity`` slots.

    ``lanes`` is the concurrency (bit-lane pool size); ``W`` lane words per
    vertex derive from it. A capacity larger than ``lanes`` is the whole
    point: excess roots wait in the queue and stream into lanes as they
    free up.
    """
    if capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    if lanes < 1:
        raise ValueError(f"lanes must be >= 1, got {lanes}")
    n = g.n
    w = num_lane_words(lanes)
    cap = capacity
    return PipelineState(
        frontier=jnp.zeros((n, w), word_dtype()),
        visited=jnp.zeros((n, w), word_dtype()),
        depth=jnp.full((n, lanes), -1, jnp.int32),
        lane_layer=jnp.zeros((lanes,), jnp.int32),
        lane_qidx=jnp.full((lanes,), cap, jnp.int32),
        topdown=jnp.ones((lanes,), jnp.bool_),
        queue=jnp.zeros((cap,), jnp.int32),
        queued=jnp.int32(0),
        next_root=jnp.int32(0),
        sweep_layers=jnp.int32(0),
        bu_fallback_passes=jnp.int32(0),
        out_depth=jnp.full((n, cap + 1), -1, jnp.int32),
        out_edges=jnp.zeros((cap + 1,), jnp.int32),
        out_layers=jnp.zeros((cap + 1,), jnp.int32),
        trace_dir=jnp.full((MAX_TRACE, cap + 1), -1, jnp.int32),
        trace_vf=jnp.zeros((MAX_TRACE, cap + 1), jnp.int32),
        trace_ef=jnp.zeros((MAX_TRACE, cap + 1), jnp.int32),
        trace_eu=jnp.zeros((MAX_TRACE, cap + 1), jnp.int32),
    )


def msbfs_engine_enqueue(state: PipelineState,
                         roots: jnp.ndarray) -> PipelineState:
    """Append roots to the pending queue (host helper, mid-sweep safe).

    The roots land in idle lanes on the next ``msbfs_engine_step`` — the
    streaming-root path: a sweep in flight keeps absorbing new queries.
    """
    roots = jnp.asarray(roots, jnp.int32).reshape(-1)
    k = roots.shape[0]
    queued = int(state.queued)
    if queued + k > state.capacity:
        raise ValueError(
            f"queue overflow: {queued} queued + {k} new > capacity "
            f"{state.capacity}")
    queue = jax.lax.dynamic_update_slice(state.queue, roots,
                                         (state.queued,))
    return state._replace(queue=queue, queued=state.queued + jnp.int32(k))


def msbfs_engine_idle(state: PipelineState) -> bool:
    """True when no lane is active and no enqueued root is pending."""
    return (int(state.next_root) >= int(state.queued)
            and not bool(jnp.any(state.lane_qidx < state.capacity)))


def _refill(g: CSRGraph, s: PipelineState, topdown_init: bool) -> PipelineState:
    """Claim pending queue slots for idle lanes and seat their roots.

    The O(n * lanes) seat-building is lax.cond-skipped in the steady state
    (no idle lane or no pending root — e.g. the whole deep tail of a
    sweep), the same pattern as the TD/BU dispatch."""
    n = g.n
    cap = s.capacity

    def do_refill(s: PipelineState) -> PipelineState:
        claim, cand, root = queue_claims(s.lane_qidx, s.next_root,
                                         s.queued, s.queue)
        onehot = claim[None, :] & (root[None, :]
                                   == jnp.arange(n, dtype=jnp.int32)[:, None])
        fresh = pack_lanes(onehot)                            # uint32[n, W]
        return s._replace(
            frontier=s.frontier | fresh,
            visited=s.visited | fresh,
            depth=jnp.where(claim[None, :],
                            jnp.where(onehot, 0, -1), s.depth),
            lane_layer=jnp.where(claim, 0, s.lane_layer),
            lane_qidx=jnp.where(claim, cand, s.lane_qidx),
            topdown=jnp.where(claim, topdown_init, s.topdown),
            next_root=s.next_root + jnp.sum(claim, dtype=jnp.int32),
        )

    needed = jnp.any(s.lane_qidx >= cap) & (s.next_root < s.queued)
    return jax.lax.cond(needed, do_refill, lambda s: s, s)


def _pipeline_body(g: CSRGraph, s: PipelineState, mode: str, alpha: float,
                   beta: float, max_pos: int,
                   probe_impl: str) -> PipelineState:
    """One engine step: refill idle lanes, advance one layer, flush finished
    lanes to their output slots. Every operation but the bottom-up
    dispatch conditional runs under one of ``packed.STEP_SCOPES``."""
    n = g.n
    lanes = s.lane_qidx.shape[0]
    cap = s.queue.shape[0]
    with jax.named_scope("bfs_refill"):
        s = _refill(g, s, mode != "bottomup")

    with jax.named_scope("bfs_direction"):
        active = s.lane_qidx < cap
        frontier_b = unpack_lanes(s.frontier, lanes)
        visited_b = unpack_lanes(s.visited, lanes)
        e_f, v_f, e_u = lane_counters(g, frontier_b, visited_b)
        topdown = select_direction(mode, s.topdown, e_f, v_f, e_u, n,
                                   alpha, beta, lanes)

        live = active & (v_f > 0)
        td_sel = pack_lanes(topdown & live)                   # uint32[W]
        bu_sel = pack_lanes(~topdown & live)

    # per-root trace rows are indexed by the lane's OWN layer counter and
    # its queue slot, so a root's trace replays its serial run regardless
    # of which lane served it or when it was claimed
    with jax.named_scope("bfs_trace"):
        tr_row = jnp.clip(s.lane_layer, 0, MAX_TRACE - 1)
        tr_col = jnp.where(active, s.lane_qidx, cap)
        # int32 up front: under x64 a weak-int64 scatter value into the
        # int32 trace will become an error in future jax
        dir_vals = jnp.where(live, jnp.where(topdown, 0, 1),
                             -1).astype(jnp.int32)
        trace_dir = s.trace_dir.at[tr_row, tr_col].set(dir_vals)
        trace_vf = s.trace_vf.at[tr_row, tr_col].set(v_f)
        trace_ef = s.trace_ef.at[tr_row, tr_col].set(e_f)
        trace_eu = s.trace_eu.at[tr_row, tr_col].set(e_u)

    new, fell_back = _dispatch_packed_step(g, s.frontier, s.visited, td_sel,
                                           bu_sel, mode, max_pos, probe_impl)

    with jax.named_scope("bfs_flush"):
        new_b = unpack_lanes(new, lanes)
        visited2 = s.visited | new
        visited2_b = visited_b | new_b
        lane_layer2 = s.lane_layer + active.astype(jnp.int32)
        depth2 = jnp.where(new_b, lane_layer2[None, :], s.depth)

        # finish = frontier drained OR per-lane layer cap (mirrors the
        # serial while-loop bound, and guarantees the drain loop ends)
        finished = active & (~new_b.any(axis=0) | (lane_layer2 >= MAX_TRACE))

        deg = g.deg.astype(jnp.int32)[:, None]
        edges_l = jnp.sum(jnp.where(visited2_b, deg, 0), axis=0,
                          dtype=jnp.int32)
        fcol = jnp.where(finished, s.lane_qidx, cap)
        out_depth = s.out_depth.at[:, fcol].set(depth2)
        out_edges = s.out_edges.at[fcol].set(edges_l)
        out_layers = s.out_layers.at[fcol].set(lane_layer2)

        # retire finished lanes: zero their packed bits so _refill can seat
        # a fresh root into the slot on the very next step
        clear = pack_lanes(finished)                          # uint32[W]
        return s._replace(
            frontier=new & ~clear,
            visited=visited2 & ~clear,
            depth=jnp.where(finished[None, :], -1, depth2),
            lane_layer=jnp.where(finished, 0, lane_layer2),
            lane_qidx=jnp.where(finished, cap, s.lane_qidx),
            topdown=topdown,
            sweep_layers=s.sweep_layers + 1,
            bu_fallback_passes=(s.bu_fallback_passes
                                + fell_back.astype(jnp.int32)),
            out_depth=out_depth, out_edges=out_edges, out_layers=out_layers,
            trace_dir=trace_dir, trace_vf=trace_vf, trace_ef=trace_ef,
            trace_eu=trace_eu,
        )


@partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def msbfs_engine_step(g: CSRGraph, state: PipelineState, mode: str = "hybrid",
                      alpha: float = ALPHA_DEFAULT, beta: float = BETA_DEFAULT,
                      max_pos: int = 8,
                      probe_impl: str = "xla") -> PipelineState:
    """Advance the pipelined engine by one traversal layer (streaming API).

    Compiles once per (graph shape, lanes, capacity, mode); the serving loop
    interleaves ``msbfs_engine_enqueue`` calls between steps to feed idle
    lanes mid-sweep.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    return _pipeline_body(g, state, mode, alpha, beta, max_pos, probe_impl)


@partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _drain(g: CSRGraph, state: PipelineState, mode: str, alpha: float,
           beta: float, max_pos: int, probe_impl: str) -> PipelineState:
    cap = state.queue.shape[0]

    def cond_fn(s: PipelineState):
        return (s.next_root < s.queued) | jnp.any(s.lane_qidx < cap)

    def body_fn(s: PipelineState):
        return _pipeline_body(g, s, mode, alpha, beta, max_pos, probe_impl)

    return jax.lax.while_loop(cond_fn, body_fn, state)


def msbfs_engine_drain(g: CSRGraph, state: PipelineState,
                       mode: str = "hybrid", alpha: float = ALPHA_DEFAULT,
                       beta: float = BETA_DEFAULT, max_pos: int = 8,
                       probe_impl: str = "xla") -> PipelineState:
    """Step the engine until every enqueued root has been answered."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    return _drain(g, state, mode, alpha, beta, max_pos, probe_impl)


def msbfs_engine_result(g: CSRGraph, state: PipelineState,
                        derive_parents: bool = True) -> MSBFSResult:
    """Assemble an ``MSBFSResult`` over the answered queue slots.

    Columns of unanswered slots (``out_layers == 0``) hold init values
    (-1 depths); callers normally drain first. ``derive_parents=False``
    skips the O(m)-per-lane-chunk parent scatter and returns a
    zero-width ``parent`` — the depth-only contract the analytics
    workloads consume.
    """
    r = int(state.queued)
    depth = state.out_depth[:, :r]
    roots = state.queue[:r]
    parent = (_derive_parents(g, depth, roots) if derive_parents
              else jnp.zeros((g.n, 0), jnp.int32))
    return MSBFSResult(
        parent=parent, depth=depth, num_layers=state.out_layers[:r],
        edges_traversed=state.out_edges[:r],
        trace_dir=state.trace_dir[:, :r], trace_vf=state.trace_vf[:, :r],
        trace_ef=state.trace_ef[:, :r], trace_eu=state.trace_eu[:, :r],
        bu_fallback_passes=state.bu_fallback_passes)


# ---------------------------------------------------------------------------
# Mid-sweep read-out: the per-layer streaming surface.
#
# BFS depth finality: once a lane has run t layers, every depth value
# <= t in its column is FINAL (level-synchronous traversal never revisits
# a vertex). So a depth-k query (khop band, reach hit) is answerable the
# moment its lane's layer counter passes k — layers before the lane would
# naturally flush. ``LayerReadout`` is that surface; ``msbfs_engine_retire``
# is the matching unlock: flush an answered lane's partial column to its
# output slot NOW and hand the lane back to the pool.
# ---------------------------------------------------------------------------


class LayerReadout(NamedTuple):
    """Host-side snapshot of the engine's per-lane depth surface after a
    step — everything a streaming consumer needs to answer depth-bounded
    queries mid-sweep (``repro.serving`` drives this each layer)."""
    layer: int                   # total engine steps run (sweep clock)
    capacity: int                # queue capacity (lane_qidx == capacity = idle)
    lane_qidx: np.ndarray        # int32[L] queue slot served per lane
    lane_layer: np.ndarray       # int32[L] layers run for the lane's root
    depth: np.ndarray            # int32[n, L] live per-lane depths
    out_depth: np.ndarray        # int32[n, capacity+1] flushed columns
    out_layers: np.ndarray       # int32[capacity+1]  0 = unanswered

    def active(self) -> np.ndarray:
        """bool[L] — lane currently serving a queue slot."""
        return self.lane_qidx < self.capacity

    def band_final(self, k: int) -> np.ndarray:
        """bool[L] — active lane whose ``depth <= k`` band is final (it
        has run at least ``k`` layers; depths are never rewritten)."""
        return self.active() & (self.lane_layer >= k)

    def lane_of_slot(self, q: int) -> int:
        """Lane currently serving queue slot ``q`` (-1 if none)."""
        hit = np.flatnonzero(self.lane_qidx == q)
        return int(hit[0]) if hit.size else -1

    def slot_depth(self, q: int) -> np.ndarray | None:
        """Depth column for queue slot ``q``: the flushed output column
        once answered, the live lane column while in flight, None before
        the root is seated."""
        if self.out_layers[q] > 0:
            return self.out_depth[:, q]
        lane = self.lane_of_slot(q)
        return self.depth[:, lane] if lane >= 0 else None

    def slice_words(self, max_depth: int, min_depth: int = 0) -> np.ndarray:
        """``packed.depth_slice_words`` over the LIVE lane depths — the
        engines' own packed bit layout, mid-sweep."""
        return np.asarray(depth_slice_words(self.depth, max_depth,
                                            min_depth))


def msbfs_engine_readout(state: PipelineState) -> LayerReadout:
    """Snapshot the streaming read-out surface of the host engine."""
    return LayerReadout(
        layer=int(state.sweep_layers), capacity=state.capacity,
        lane_qidx=np.asarray(state.lane_qidx),
        lane_layer=np.asarray(state.lane_layer),
        depth=np.asarray(state.depth),
        out_depth=np.asarray(state.out_depth),
        out_layers=np.asarray(state.out_layers))


def msbfs_engine_stream(g: CSRGraph, state: PipelineState,
                        mode: str = "hybrid", alpha: float = ALPHA_DEFAULT,
                        beta: float = BETA_DEFAULT, max_pos: int = 8,
                        probe_impl: str = "xla"):
    """Iterate the engine to idleness, yielding ``(state, LayerReadout)``
    after every layer — the streaming-callback form of
    ``msbfs_engine_drain``. The caller may enqueue new roots or retire
    answered lanes between yields; the loop re-checks idleness against
    the state it yielded, so keep stepping the LAST yielded state."""
    while not msbfs_engine_idle(state):
        state = msbfs_engine_step(g, state, mode, alpha, beta, max_pos,
                                  probe_impl)
        yield state, msbfs_engine_readout(state)


@jax.jit
def _retire(g: CSRGraph, state: PipelineState,
            lane_mask: jnp.ndarray) -> PipelineState:
    cap = state.capacity
    mask = lane_mask & (state.lane_qidx < cap)
    visited_b = unpack_lanes(state.visited, state.num_lanes)
    deg = g.deg.astype(jnp.int32)[:, None]
    edges_l = jnp.sum(jnp.where(visited_b, deg, 0), axis=0, dtype=jnp.int32)
    # the flush pattern of _pipeline_body: masked lanes write their queue
    # slot, everyone else the trailing trash column
    fcol = jnp.where(mask, state.lane_qidx, cap)
    out_depth = state.out_depth.at[:, fcol].set(state.depth)
    out_edges = state.out_edges.at[fcol].set(edges_l)
    # out_layers > 0 is the answered flag; a lane retired before its
    # first step (k = 0 band) still counts one layer
    out_layers = state.out_layers.at[fcol].set(
        jnp.maximum(state.lane_layer, 1))
    clear = pack_lanes(mask)
    return state._replace(
        frontier=state.frontier & ~clear,
        visited=state.visited & ~clear,
        depth=jnp.where(mask, -1, state.depth),
        lane_layer=jnp.where(mask, 0, state.lane_layer),
        lane_qidx=jnp.where(mask, cap, state.lane_qidx),
        out_depth=out_depth, out_edges=out_edges, out_layers=out_layers)


def msbfs_engine_retire(g: CSRGraph, state: PipelineState,
                        lane_mask) -> PipelineState:
    """Retire the masked ACTIVE lanes early: flush their depth columns to
    their output slots as-is and free the lanes for the pending queue.

    The streaming unlock behind depth-k serving: once ``LayerReadout
    .band_final(k)`` says a khop/reach lane's band is final, the answer
    no longer needs the lane — retiring it mid-sweep returns its bit
    lane to the pool layers before the traversal would drain. A retired
    slot's output column is PARTIAL past the retirement layer (exactly
    the band the caller declared final); ``out_layers`` records the
    layers actually run. Idle lanes in the mask are ignored."""
    lane_mask = jnp.asarray(lane_mask, jnp.bool_).reshape(-1)
    if lane_mask.shape[0] != state.num_lanes:
        raise ValueError(
            f"lane_mask has {lane_mask.shape[0]} lanes, engine has "
            f"{state.num_lanes}")
    return _retire(g, state, lane_mask)


def msbfs_pipelined(g: CSRGraph, roots: jnp.ndarray, mode: str = "hybrid",
                    alpha: float = ALPHA_DEFAULT, beta: float = BETA_DEFAULT,
                    max_pos: int = 8, probe_impl: str = "xla",
                    lanes: int = MAX_LANES, derive_parents: bool = True,
                    recorder=None) -> MSBFSResult:
    """Answer an arbitrary number of roots in ONE pipelined engine sweep.

    Splits R > ``lanes`` roots across bit-lane word-batches WITHOUT batch
    barriers: each finished lane refills from the pending-root queue on the
    next layer, so the sweep's critical path is set by total traversal
    work, not by the deepest root of each 64-root batch. With R <= lanes
    the lane pool shrinks to ``ceil32(R)`` lanes and this reduces to the
    single-batch ``msbfs`` sweep (same packed steps, same results).

    ``recorder`` (a ``repro.obs.SweepRecorder``) switches the fused drain
    for a host step-loop that records a ``LayerRecord`` per layer — the
    step and the drain share ``_pipeline_body``, so results and traces
    are bit-identical either way; with ``recorder=None`` (the default)
    nothing from ``repro.obs`` is imported or executed.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    roots = jnp.asarray(roots, jnp.int32).reshape(-1)
    num_roots = roots.shape[0]
    if num_roots < 1:
        raise ValueError("need at least one root")
    # W derives from the ACTIVE batch: small R never pays for idle words
    lanes = max(1, min(lanes, LANE_WORD_BITS * num_lane_words(num_roots)))
    state = msbfs_engine_init(g, capacity=num_roots, lanes=lanes)
    state = msbfs_engine_enqueue(state, roots)
    if recorder is None:
        state = msbfs_engine_drain(g, state, mode, alpha, beta, max_pos,
                                   probe_impl)
    else:
        from repro.obs.sweeplog import drive_recorded
        state = drive_recorded(
            recorder, state,
            lambda s: msbfs_engine_step(g, s, mode, alpha, beta, max_pos,
                                        probe_impl),
            msbfs_engine_idle, kind="bfs")
    return msbfs_engine_result(g, state, derive_parents=derive_parents)
