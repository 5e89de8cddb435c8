"""Distributed delta-stepping SSSP: the tropical lane engine sharded.

The weighted sibling of ``dist_msbfs``/``dist2d``: float lane values fold
under ``min`` across partitions exactly as packed words fold under OR
(Buluc-Madduri's decomposition and SlimSell's semiring-BFS formulation
generalized past the boolean algebra), so both partition shapes reuse the
shared exchange layer (``repro.core.exchange``) through its MIN-monoid
surface — ``allreduce_min`` / ``gather_values`` / ``exchange_reduce_min``
— and the same density-switched sparse wire format: a relaxation
candidate is ``inf`` everywhere a relaxation did not fire this step, so
compressed layers cost bytes proportional to the ACTIVE frontier, not the
graph.

**1-D engine** (``dist_sssp_*``): device d owns a contiguous row block of
the weighted CSR (``partition_weighted_graph`` — the ``dist_bfs``
partition plus an inf-padded weight slab). Lane distances, the ``relaxed``
request flags, and all bucket control are REPLICATED; per step each device
runs the host engine's masked ``tropical_relax`` phases over its local
block against the full replicated values, places its row-block candidates
onto an inf background, and the per-step exchange is one
``exchange_reduce_min`` over the mesh (the ``allreduce_or`` analog, with
optional value compression + byte metering). Bucket control replays the
host engine from collectively-merged counters: per-block light-pending
counts ``psum`` to the global request-set population, per-block unsettled
minima ``pmin`` to the global bucket advance — int32 sums and float32
mins are exact, so every control decision (and therefore every distance,
step count, truncation flag, and bucket/phase trace) is bit-identical to
single-host ``sssp_pipelined``.

**2-D engine** (``dist2d_sssp_*``): the ``pr x pc`` grid of ``dist2d``
with no replicated ``[n, L]`` value state. Device ``(i, j)`` holds row
block ``i``'s distances (replicated along "col") and the weighted
adjacency block ``(i, j)``. Per step: slice the own chunk, all-gather it
along "row" (``exchange_expand_values``) into the column block's value
slice, run the masked relax phases over the local block, MIN-fold the
row-block partials along "col" (``exchange_reduce_min``). The two phases
of a lane are mutually exclusive, so ONE masked source array ships per
step — each device recovers the light/heavy operands from the replicated
per-lane phase flags after the gather, keeping the wire as sparse as the
union of both request sets. Partial row minima over column blocks compose
exactly to the full row minimum, so the grid replays the host engine
bit-for-bit too (``tests/test_dist_sssp.py`` pins the whole matrix).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core.csr import CSRGraph, WeightedCSRGraph
from repro.core.dist2d import (DistGraph2D, _check_partition_2d, mesh2d,
                               partition_graph_2d)
from repro.core.dist_bfs import _flat_axis_index, partition_graph
from repro.core.dist_msbfs import host_mesh
from repro.core.exchange import (allreduce_min, exchange_expand_values,
                                 exchange_reduce_min)
from repro.core.packed import queue_claims
from repro.traversal.semiring import INF, tropical_relax
from repro.traversal.sssp import (DEFAULT_LANES, MAX_SSSP_STEPS,
                                  MAX_SSSP_TRACE, SSSPResult, _check_delta,
                                  _delta_lanes, sssp_engine_enqueue,
                                  sssp_engine_idle)

__all__ = [
    "DistSSSPState", "DistWeightedGraph", "DistWeightedGraph2D",
    "allreduce_min", "default_delta_dist", "dist2d_sssp",
    "dist2d_sssp_engine_drain", "dist2d_sssp_engine_enqueue",
    "dist2d_sssp_engine_idle", "dist2d_sssp_engine_init",
    "dist2d_sssp_engine_result", "dist2d_sssp_engine_step", "dist_sssp",
    "dist_sssp_engine_drain", "dist_sssp_engine_enqueue",
    "dist_sssp_engine_idle", "dist_sssp_engine_init",
    "dist_sssp_engine_result", "dist_sssp_engine_step", "host_mesh",
    "mesh2d", "partition_weighted_graph", "partition_weighted_graph_2d",
]


# ---------------------------------------------------------------------------
# Weighted partitions: the unweighted structure + an inf-padded weight slab.
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DistWeightedGraph:
    """1-D ``DistGraph`` plus the matching per-device weight slabs. Edge
    slab d is row block d's edges in ORIGINAL adjacency order, so the
    weight slab is the same contiguous cut of ``wg.weights``; pad slots
    carry ``inf`` (the min-plus annihilator — a consumed pad could only
    produce an inf candidate, which the fold ignores)."""
    row_ptr: jnp.ndarray   # int32[ndev, n_loc+1]
    col_idx: jnp.ndarray   # int32[ndev, m_loc] — global neighbour ids
    src_loc: jnp.ndarray   # int32[ndev, m_loc]
    slot_pos: jnp.ndarray  # int32[ndev, m_loc] — place in row, -1 pads
    deg: jnp.ndarray       # int32[ndev, n_loc]
    weights: jnp.ndarray   # float32[ndev, m_loc] — inf pads
    n: int                 # padded global vertex count
    n_orig: int            # original vertex count
    m_loc: int             # uniform per-device edge-slab size


def partition_weighted_graph(wg: WeightedCSRGraph,
                             ndev: int) -> DistWeightedGraph:
    """1-D partition of a weighted CSR: ``dist_bfs.partition_graph`` on
    the structure, plus the per-block weight slabs it implies."""
    dg = partition_graph(wg.csr, ndev)
    rp = np.asarray(wg.row_ptr)
    w = np.asarray(wg.weights)
    block = dg.n // ndev
    w_l = np.full((ndev, dg.m_loc), np.inf, np.float32)
    for d in range(ndev):
        lo_v, hi_v = d * block, min((d + 1) * block, wg.n)
        if lo_v < wg.n:
            slab = w[rp[lo_v]:rp[hi_v]]
            w_l[d, :len(slab)] = slab
    return DistWeightedGraph(
        row_ptr=dg.row_ptr, col_idx=dg.col_idx, src_loc=dg.src_loc,
        slot_pos=dg.slot_pos, deg=dg.deg, weights=jnp.asarray(w_l), n=dg.n,
        n_orig=dg.n_orig, m_loc=dg.m_loc)


@dataclass(frozen=True)
class DistWeightedGraph2D:
    """2-D ``DistGraph2D`` plus per-block weight slabs (inf pads). The
    structure partition is ``dist2d.partition_graph_2d`` verbatim; the
    weights replay the same per-block edge selection."""
    g2: DistGraph2D
    weights: jnp.ndarray   # float32[G, m_loc] — inf pads

    @property
    def n(self) -> int:
        return self.g2.n

    @property
    def n_orig(self) -> int:
        return self.g2.n_orig


def partition_weighted_graph_2d(wg: WeightedCSRGraph, pr: int,
                                pc: int) -> DistWeightedGraph2D:
    """2-D partition of a weighted CSR: structure from
    ``partition_graph_2d``, weight slabs by replaying its per-block edge
    selection (same row-block cut, same per-column-block destination
    filter, same order)."""
    g2 = partition_graph_2d(wg.csr, pr, pc)
    rp = np.asarray(wg.row_ptr)
    ci = np.asarray(wg.col_idx)
    w = np.asarray(wg.weights)
    chunk, n_loc_r = g2.chunk, g2.n_loc_r
    w_l = np.full((pr * pc, g2.m_loc), np.inf, np.float32)
    rp_check = np.asarray(g2.row_ptr)
    for i in range(pr):
        lo_v, hi_v = i * n_loc_r, min((i + 1) * n_loc_r, wg.n)
        if lo_v < wg.n:
            dst = ci[rp[lo_v]:rp[hi_v]]
            wrow = w[rp[lo_v]:rp[hi_v]]
        else:
            dst = np.zeros(0, np.int32)
            wrow = np.zeros(0, np.float32)
        dst_chunk = dst // chunk
        for j in range(pc):
            sel = dst_chunk % pc == j
            d = i * pc + j
            k = int(sel.sum())
            if k != int(rp_check[d, -1]):
                raise AssertionError(
                    f"weight slab {d} selected {k} edges but the structure "
                    f"partition holds {int(rp_check[d, -1])}")
            w_l[d, :k] = wrow[sel]
    return DistWeightedGraph2D(g2=g2, weights=jnp.asarray(w_l))


def default_delta_dist(dwg) -> float:
    """``sssp.default_delta`` recomputed from a partitioned weighted graph
    — same max-weight / average-degree rule over the REAL edges (pads are
    inf and excluded), bit-identical to the host value so a distributed
    run with ``delta=None`` replays the host engine exactly."""
    w = np.asarray(dwg.weights)
    fin = np.isfinite(w)
    m = int(fin.sum())
    if m == 0:
        return 1.0
    w_max = float(w[fin].max())
    avg_deg = m / max(dwg.n_orig, 1)
    delta = w_max / max(avg_deg, 1.0)
    return delta if delta > 0 else 1.0


# ---------------------------------------------------------------------------
# Shared engine state (both partition shapes).
# ---------------------------------------------------------------------------

class DistSSSPState(NamedTuple):
    """Sharded-engine state. Mirrors ``sssp.SSSPState`` field-for-field
    (so the host enqueue/idle helpers are shared) plus the exchange byte
    meters. On the 1-D partition EVERY field is replicated (the graph is
    what's sharded — value state stays replicated like the 1-D MS-BFS
    frontier); on the 2-D grid the row-indexed arrays are row-block
    slices with a leading stacked device dim."""
    dist: jnp.ndarray          # float32[..., L]  lane distances
    relaxed: jnp.ndarray       # bool[..., L]     light request flags
    lane_bucket: jnp.ndarray   # int32[L]
    lane_steps: jnp.ndarray    # int32[L]
    lane_qidx: jnp.ndarray     # int32[L]   queue slot served; cap = idle
    queue: jnp.ndarray         # int32[capacity]
    queued: jnp.ndarray        # int32 scalar
    next_root: jnp.ndarray     # int32 scalar
    sweep_steps: jnp.ndarray   # int32 scalar
    out_dist: jnp.ndarray      # float32[..., capacity+1]
    out_steps: jnp.ndarray     # int32[capacity+1]  0 = unanswered
    out_truncated: jnp.ndarray  # bool[capacity+1]
    trace_bucket: jnp.ndarray  # int32[MAX_SSSP_TRACE, capacity+1]
    trace_phase: jnp.ndarray   # int32[MAX_SSSP_TRACE, capacity+1]
    exch_bytes: jnp.ndarray    # int32 scalar — mesh-total wire bytes
    exch_log: jnp.ndarray      # int32[MAX_SSSP_TRACE] — bytes per step

    @property
    def num_lanes(self) -> int:
        return self.lane_qidx.shape[0]

    @property
    def capacity(self) -> int:
        return self.queue.shape[0]


def _masked_relax_groups(g_loc: CSRGraph, w_loc: jnp.ndarray, vals_from,
                         delta, lanes: int, iterating, settling,
                         max_pos: int, relax_impl: str) -> jnp.ndarray:
    """The host engine's per-delta-group light/heavy relax pair over a
    LOCAL adjacency block: ``vals_from(phase_sel)`` supplies the masked
    [nf, L] source values for a per-lane selector (inf outside it), the
    block's candidates min-fold across groups. Same group structure as
    ``sssp._sssp_body``, so scalar deltas run the exact single-width
    relaxations."""
    n_loc = g_loc.n
    cand = jnp.full((n_loc, lanes), jnp.inf, jnp.float32)
    widths = (sorted(set(delta)) if isinstance(delta, tuple)
              else [float(delta)])
    lane_widths = (delta if isinstance(delta, tuple)
                   else (float(delta),) * lanes)

    def relax_phase(vals, phase_w):
        def run(vals):
            return tropical_relax(g_loc, phase_w, vals, max_pos, relax_impl)
        return jax.lax.cond(
            jnp.any(jnp.isfinite(vals)), run,
            lambda vals: jnp.full((n_loc, lanes), jnp.inf, jnp.float32),
            vals)

    for dv in widths:
        gsel = jnp.asarray([lw == dv for lw in lane_widths], jnp.bool_)
        dv32 = jnp.float32(dv)
        light_w = jnp.where(w_loc <= dv32, w_loc, INF)
        heavy_w = jnp.where(w_loc > dv32, w_loc, INF)
        cand = jnp.minimum(
            cand, relax_phase(vals_from(iterating & gsel), light_w))
        cand = jnp.minimum(
            cand, relax_phase(vals_from(settling & gsel), heavy_w))
    return cand


def _bucket_control(s: DistSSSPState, d32, min_unsettled, iterating,
                    max_steps: int):
    """Replicated post-relax control shared by both engines: request-flag
    update is the caller's (it needs the local ``changed``); this covers
    bucket advance, the step/truncation bookkeeping, and the trace writes
    — exactly ``sssp._sssp_body``'s tail, computed from globally-merged
    ``min_unsettled``."""
    cap = s.capacity
    active = s.lane_qidx < cap
    settling = active & ~iterating
    exhausted = settling & ~jnp.isfinite(min_unsettled)
    next_bucket = jnp.where(
        settling & jnp.isfinite(min_unsettled),
        jnp.maximum(jnp.floor(min_unsettled / d32).astype(jnp.int32),
                    s.lane_bucket + 1),
        s.lane_bucket)
    lane_steps2 = s.lane_steps + active.astype(jnp.int32)
    capped = active & (lane_steps2 >= max_steps) & ~exhausted
    finished = exhausted | capped

    tr_row = jnp.clip(s.lane_steps, 0, MAX_SSSP_TRACE - 1)
    tr_col = jnp.where(active, s.lane_qidx, cap)
    trace_bucket = s.trace_bucket.at[tr_row, tr_col].set(
        jnp.where(active, s.lane_bucket, -1))
    trace_phase = s.trace_phase.at[tr_row, tr_col].set(
        jnp.where(active, jnp.where(iterating, 0, 1), -1).astype(jnp.int32))
    return (next_bucket, lane_steps2, capped, finished, trace_bucket,
            trace_phase)


# ---------------------------------------------------------------------------
# 1-D engine: replicated values, sharded graph, allreduce-MIN exchange.
# ---------------------------------------------------------------------------

def _state_specs_1d() -> DistSSSPState:
    rep = P()
    return DistSSSPState(*([rep] * len(DistSSSPState._fields)))


def _check_partition_1d(dwg: DistWeightedGraph, mesh: Mesh) -> int:
    ndev = int(np.prod(mesh.devices.shape))
    if dwg.row_ptr.shape[0] != ndev:
        raise ValueError(
            f"DistWeightedGraph partitioned for {dwg.row_ptr.shape[0]} "
            f"devices but mesh has {ndev} — repartition with "
            f"partition_weighted_graph(wg, {ndev})")
    return ndev


def dist_sssp_engine_init(dwg: DistWeightedGraph, mesh: Mesh, capacity: int,
                          lanes: int = DEFAULT_LANES) -> DistSSSPState:
    """Fresh sharded SSSP engine: all lanes idle, empty source queue,
    byte meters zero."""
    if capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    if lanes < 1:
        raise ValueError(f"lanes must be >= 1, got {lanes}")
    _check_partition_1d(dwg, mesh)
    n = dwg.n
    cap = capacity
    return DistSSSPState(
        dist=jnp.full((n, lanes), jnp.inf, jnp.float32),
        relaxed=jnp.zeros((n, lanes), jnp.bool_),
        lane_bucket=jnp.zeros((lanes,), jnp.int32),
        lane_steps=jnp.zeros((lanes,), jnp.int32),
        lane_qidx=jnp.full((lanes,), cap, jnp.int32),
        queue=jnp.zeros((cap,), jnp.int32),
        queued=jnp.int32(0),
        next_root=jnp.int32(0),
        sweep_steps=jnp.int32(0),
        out_dist=jnp.full((n, cap + 1), jnp.inf, jnp.float32),
        out_steps=jnp.zeros((cap + 1,), jnp.int32),
        out_truncated=jnp.zeros((cap + 1,), jnp.bool_),
        trace_bucket=jnp.full((MAX_SSSP_TRACE, cap + 1), -1, jnp.int32),
        trace_phase=jnp.full((MAX_SSSP_TRACE, cap + 1), -1, jnp.int32),
        exch_bytes=jnp.int32(0),
        exch_log=jnp.zeros((MAX_SSSP_TRACE,), jnp.int32),
    )


def dist_sssp_engine_enqueue(state: DistSSSPState, roots) -> DistSSSPState:
    """Append sources to the (replicated) pending queue — the host helper
    verbatim, as in the MS-BFS engines."""
    return sssp_engine_enqueue(state, roots)


def dist_sssp_engine_idle(state: DistSSSPState) -> bool:
    """True when no lane is active and no enqueued source is pending."""
    return sssp_engine_idle(state)


def _queue_refill(s: DistSSSPState, n: int):
    """Replicated refill — ``sssp._refill`` on the engine's own state
    width (both engines' control state is replicated, so the claim logic
    is the host one verbatim)."""
    def do_refill(s: DistSSSPState) -> DistSSSPState:
        claim, cand, root = queue_claims(s.lane_qidx, s.next_root,
                                         s.queued, s.queue)
        onehot = claim[None, :] & (root[None, :]
                                   == jnp.arange(n, dtype=jnp.int32)[:, None])
        return s._replace(
            dist=jnp.where(claim[None, :],
                           jnp.where(onehot, jnp.float32(0), INF), s.dist),
            relaxed=jnp.where(claim[None, :], False, s.relaxed),
            lane_bucket=jnp.where(claim, 0, s.lane_bucket),
            lane_steps=jnp.where(claim, 0, s.lane_steps),
            lane_qidx=jnp.where(claim, cand, s.lane_qidx),
            next_root=s.next_root + jnp.sum(claim, dtype=jnp.int32),
        )

    needed = jnp.any(s.lane_qidx >= s.capacity) & (s.next_root < s.queued)
    return jax.lax.cond(needed, do_refill, lambda s: s, s)


def _dist_sssp_body(gw_loc, base, s: DistSSSPState, delta, max_pos: int,
                    relax_impl: str, max_steps: int, n: int, n_loc: int,
                    axes, compress: bool) -> DistSSSPState:
    """One engine step, per-device view: refill idle lanes (replicated),
    run the masked relax phases over the local row block, MIN-exchange
    the placed candidates, advance buckets from psum/pmin-merged
    counters, flush finished lanes."""
    g_loc, w_loc = gw_loc
    cap = s.capacity
    lanes = s.num_lanes
    col0 = jnp.zeros((), jnp.asarray(base).dtype)
    s = _queue_refill(s, n)

    d32 = _delta_lanes(delta, lanes)                          # [L]
    active = s.lane_qidx < cap
    b_hi = (s.lane_bucket.astype(jnp.float32) + 1) * d32      # [L]
    in_bucket = active[None, :] & (s.dist < b_hi[None, :])    # [n, L]
    light_pending = in_bucket & ~s.relaxed

    # request-set population via psum of per-block counts: each device
    # counts its OWN rows, the int32 sum is exact, so the phase decision
    # replays the host's global any() bit-for-bit
    lp_loc = jax.lax.dynamic_slice(light_pending, (base, col0),
                                   (n_loc, lanes))
    req_count = jax.lax.psum(
        jnp.sum(lp_loc, axis=0, dtype=jnp.int32), axes)       # [L]
    iterating = req_count > 0
    settling = active & ~iterating

    def vals_from(phase_sel):
        # light lanes mask by the request set, settling lanes by bucket
        # membership — phase_sel already carries the lane split
        mask = jnp.where(iterating[None, :], light_pending, in_bucket)
        return jnp.where(mask & phase_sel[None, :], s.dist, INF)

    cand_loc = _masked_relax_groups(g_loc, w_loc, vals_from, delta, lanes,
                                    iterating, settling, max_pos,
                                    relax_impl)               # [n_loc, L]

    # --- candidate exchange: place the row block, MIN-fold the mesh -----
    placed = jax.lax.dynamic_update_slice(
        jnp.full((n, lanes), jnp.inf, jnp.float32), cand_loc, (base, col0))
    cand_full, step_bytes = exchange_reduce_min(placed, axes, compress)

    new_dist = jnp.minimum(s.dist, cand_full)
    changed = new_dist < s.dist
    relaxed2 = (s.relaxed | (light_pending & iterating[None, :])) & ~changed

    # bucket advance from pmin-merged per-block unsettled minima (float32
    # min is exactly associative: same bits as the host's global min)
    unsettled = jnp.where(new_dist >= b_hi[None, :], new_dist, INF)
    uns_loc = jax.lax.dynamic_slice(unsettled, (base, col0), (n_loc, lanes))
    min_unsettled = jax.lax.pmin(jnp.min(uns_loc, axis=0), axes)  # [L]

    (next_bucket, lane_steps2, capped, finished, trace_bucket,
     trace_phase) = _bucket_control(s, d32, min_unsettled, iterating,
                                    max_steps)

    fcol = jnp.where(finished, s.lane_qidx, cap)
    out_dist = s.out_dist.at[:, fcol].set(new_dist)
    out_steps = s.out_steps.at[fcol].set(lane_steps2)
    out_truncated = s.out_truncated.at[fcol].set(capped)

    log_row = jnp.clip(s.sweep_steps, 0, MAX_SSSP_TRACE - 1)
    return s._replace(
        dist=jnp.where(finished[None, :], INF, new_dist),
        relaxed=relaxed2 & ~finished[None, :],
        lane_bucket=jnp.where(finished, 0, next_bucket),
        lane_steps=jnp.where(finished, 0, lane_steps2),
        lane_qidx=jnp.where(finished, cap, s.lane_qidx),
        sweep_steps=s.sweep_steps + 1,
        out_dist=out_dist, out_steps=out_steps,
        out_truncated=out_truncated,
        trace_bucket=trace_bucket, trace_phase=trace_phase,
        exch_bytes=s.exch_bytes + step_bytes,
        exch_log=s.exch_log.at[log_row].add(step_bytes),
    )


@partial(jax.jit, static_argnames=("mesh", "delta", "max_pos", "relax_impl",
                                   "max_steps", "n", "n_loc", "compress",
                                   "drain"))
def _dist_sssp_run(row_ptr_s, col_s, srcloc_s, pos_s, w_s,
                   state: DistSSSPState, *, mesh: Mesh, delta, max_pos: int,
                   relax_impl: str, max_steps: int, n: int, n_loc: int,
                   compress: bool, drain: bool) -> DistSSSPState:
    axes = tuple(mesh.axis_names)
    cap = state.queue.shape[0]

    def body(row_ptr, col, src_loc, pos, w, s: DistSSSPState):
        g_loc = CSRGraph(row_ptr=row_ptr[0], col_idx=col[0],
                         src_idx=src_loc[0], slot_pos=pos[0])
        base = _flat_axis_index(axes, dict(mesh.shape)) * n_loc
        step = partial(_dist_sssp_body, (g_loc, w[0]), base, delta=delta,
                       max_pos=max_pos, relax_impl=relax_impl,
                       max_steps=max_steps, n=n, n_loc=n_loc, axes=axes,
                       compress=compress)
        if drain:
            s = jax.lax.while_loop(
                lambda s: (s.next_root < s.queued)
                | jnp.any(s.lane_qidx < cap),
                lambda s: step(s), s)
        else:
            s = step(s)
        return s

    spec_dev = P(axes)
    specs = _state_specs_1d()
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_dev, spec_dev, spec_dev, spec_dev, spec_dev, specs),
        out_specs=specs, check_vma=False,
    )(row_ptr_s, col_s, srcloc_s, pos_s, w_s, state)


def dist_sssp_engine_step(dwg: DistWeightedGraph, state: DistSSSPState,
                          mesh: Mesh, delta, max_pos: int = 8,
                          relax_impl: str = "xla",
                          max_steps: int = MAX_SSSP_STEPS,
                          compress: bool = False) -> DistSSSPState:
    """Advance the sharded SSSP engine by one phase step (streaming API).
    ``delta`` is a scalar or per-lane tuple, static like the host's."""
    _check_delta(delta)
    ndev = _check_partition_1d(dwg, mesh)
    return _dist_sssp_run(
        dwg.row_ptr, dwg.col_idx, dwg.src_loc, dwg.slot_pos, dwg.weights,
        state, mesh=mesh, delta=delta, max_pos=max_pos, relax_impl=relax_impl,
        max_steps=max_steps, n=dwg.n, n_loc=dwg.n // ndev,
        compress=compress, drain=False)


def dist_sssp_engine_drain(dwg: DistWeightedGraph, state: DistSSSPState,
                           mesh: Mesh, delta, max_pos: int = 8,
                           relax_impl: str = "xla",
                           max_steps: int = MAX_SSSP_STEPS,
                           compress: bool = False) -> DistSSSPState:
    """Step the sharded engine until every enqueued source is answered."""
    _check_delta(delta)
    ndev = _check_partition_1d(dwg, mesh)
    return _dist_sssp_run(
        dwg.row_ptr, dwg.col_idx, dwg.src_loc, dwg.slot_pos, dwg.weights,
        state, mesh=mesh, delta=delta, max_pos=max_pos, relax_impl=relax_impl,
        max_steps=max_steps, n=dwg.n, n_loc=dwg.n // ndev,
        compress=compress, drain=True)


def dist_sssp_engine_result(dwg: DistWeightedGraph,
                            state: DistSSSPState) -> SSSPResult:
    """Assemble an ``SSSPResult`` over the answered queue slots, trimmed
    to the original (pre-padding) vertex count."""
    r = int(state.queued)
    return SSSPResult(sources=state.queue[:r],
                      dist=state.out_dist[:dwg.n_orig, :r],
                      steps=state.out_steps[:r],
                      truncated=state.out_truncated[:r],
                      trace_bucket=state.trace_bucket[:, :r],
                      trace_phase=state.trace_phase[:, :r])


def dist_sssp(dwg: DistWeightedGraph, roots, mesh: Mesh, delta=None,
              lanes: int = DEFAULT_LANES, max_pos: int = 8,
              relax_impl: str = "xla", max_steps: int = MAX_SSSP_STEPS,
              compress: bool = False, recorder=None) -> SSSPResult:
    """Answer an arbitrary number of SSSP sources with ONE sharded sweep.
    ``delta=None`` picks the host's ``default_delta`` value (recomputed
    from the partition, bit-identical); distances/steps/truncation/traces
    replay ``sssp_pipelined`` exactly on every partition shape.
    ``recorder`` (a ``repro.obs.SweepRecorder``) steps the engine
    recording a ``LayerRecord`` (incl. the per-step ``exch_bytes`` delta)
    each phase — bit-identical to the drain; None touches nothing."""
    roots = jnp.asarray(roots, jnp.int32).reshape(-1)
    num_roots = roots.shape[0]
    if num_roots < 1:
        raise ValueError("need at least one source")
    if delta is None:
        delta = default_delta_dist(dwg)
    lanes = max(1, min(lanes, num_roots))
    delta = delta if isinstance(delta, tuple) else float(delta)
    state = dist_sssp_engine_init(dwg, mesh, capacity=num_roots, lanes=lanes)
    state = dist_sssp_engine_enqueue(state, roots)
    if recorder is None:
        state = dist_sssp_engine_drain(dwg, state, mesh, delta, max_pos,
                                       relax_impl, max_steps, compress)
    else:
        from repro.obs.sweeplog import drive_recorded
        state = drive_recorded(
            recorder, state,
            lambda s: dist_sssp_engine_step(dwg, s, mesh, delta, max_pos,
                                            relax_impl, max_steps,
                                            compress),
            dist_sssp_engine_idle, kind="sssp",
            exch_format="compressed" if compress else "dense")
    return dist_sssp_engine_result(dwg, state)


# ---------------------------------------------------------------------------
# 2-D engine: row-block values, expand/fold grid exchanges, MIN monoid.
# ---------------------------------------------------------------------------

def _state_specs_2d() -> DistSSSPState:
    row = P("row")
    rep = P()
    return DistSSSPState(
        dist=row, relaxed=row, lane_bucket=rep, lane_steps=rep,
        lane_qidx=rep, queue=rep, queued=rep, next_root=rep,
        sweep_steps=rep, out_dist=row, out_steps=rep, out_truncated=rep,
        trace_bucket=rep, trace_phase=rep, exch_bytes=rep, exch_log=rep)


def dist2d_sssp_engine_init(dwg2: DistWeightedGraph2D, mesh: Mesh,
                            capacity: int,
                            lanes: int = DEFAULT_LANES) -> DistSSSPState:
    """Fresh 2-D SSSP engine: row-block value state, byte meters zero."""
    if capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    if lanes < 1:
        raise ValueError(f"lanes must be >= 1, got {lanes}")
    g2 = dwg2.g2
    _check_partition_2d(g2, mesh)
    n_loc_r = g2.n_loc_r
    cap = capacity
    return DistSSSPState(
        dist=jnp.full((g2.pr, n_loc_r, lanes), jnp.inf, jnp.float32),
        relaxed=jnp.zeros((g2.pr, n_loc_r, lanes), jnp.bool_),
        lane_bucket=jnp.zeros((lanes,), jnp.int32),
        lane_steps=jnp.zeros((lanes,), jnp.int32),
        lane_qidx=jnp.full((lanes,), cap, jnp.int32),
        queue=jnp.zeros((cap,), jnp.int32),
        queued=jnp.int32(0),
        next_root=jnp.int32(0),
        sweep_steps=jnp.int32(0),
        out_dist=jnp.full((g2.pr, n_loc_r, cap + 1), jnp.inf, jnp.float32),
        out_steps=jnp.zeros((cap + 1,), jnp.int32),
        out_truncated=jnp.zeros((cap + 1,), jnp.bool_),
        trace_bucket=jnp.full((MAX_SSSP_TRACE, cap + 1), -1, jnp.int32),
        trace_phase=jnp.full((MAX_SSSP_TRACE, cap + 1), -1, jnp.int32),
        exch_bytes=jnp.int32(0),
        exch_log=jnp.zeros((MAX_SSSP_TRACE,), jnp.int32),
    )


def dist2d_sssp_engine_enqueue(state: DistSSSPState,
                               roots) -> DistSSSPState:
    """Append sources to the (replicated) pending queue."""
    return sssp_engine_enqueue(state, roots)


def dist2d_sssp_engine_idle(state: DistSSSPState) -> bool:
    """True when no lane is active and no enqueued source is pending."""
    return sssp_engine_idle(state)


def _dist2d_sssp_body(gw_loc, base_r, chunk_base, s: DistSSSPState, delta,
                      max_pos: int, relax_impl: str, max_steps: int, n: int,
                      n_loc_r: int, chunk: int,
                      compress: bool) -> DistSSSPState:
    """One engine step, per-device view on the grid: refill (replicated
    control, row-block seat writes), expand the own chunk's masked values
    along "row", relax over the local weighted block, MIN-fold the
    partials along "col", advance buckets from globally-merged counters,
    flush finished lanes."""
    g_loc, w_loc = gw_loc
    cap = s.capacity
    lanes = s.num_lanes
    col0 = jnp.zeros((), jnp.asarray(base_r).dtype)

    # --- refill: replicated claim logic, row-block seat writes ----------
    def do_refill(s: DistSSSPState) -> DistSSSPState:
        claim, cand, root = queue_claims(s.lane_qidx, s.next_root,
                                         s.queued, s.queue)
        onehot = claim[None, :] & (root[None, :]
                                   == jnp.arange(n, dtype=jnp.int32)[:, None])
        onehot_loc = jax.lax.dynamic_slice(onehot, (base_r, col0),
                                           (n_loc_r, lanes))
        return s._replace(
            dist=jnp.where(claim[None, :],
                           jnp.where(onehot_loc, jnp.float32(0), INF),
                           s.dist),
            relaxed=jnp.where(claim[None, :], False, s.relaxed),
            lane_bucket=jnp.where(claim, 0, s.lane_bucket),
            lane_steps=jnp.where(claim, 0, s.lane_steps),
            lane_qidx=jnp.where(claim, cand, s.lane_qidx),
            next_root=s.next_root + jnp.sum(claim, dtype=jnp.int32),
        )

    needed = jnp.any(s.lane_qidx >= cap) & (s.next_root < s.queued)
    s = jax.lax.cond(needed, do_refill, lambda s: s, s)

    d32 = _delta_lanes(delta, lanes)                          # [L]
    active = s.lane_qidx < cap
    b_hi = (s.lane_bucket.astype(jnp.float32) + 1) * d32      # [L]
    in_bucket = active[None, :] & (s.dist < b_hi[None, :])    # [n_loc_r, L]
    light_pending = in_bucket & ~s.relaxed

    # phase decision from psum'd per-row-block request counts ("row" only:
    # row-block state is replicated along "col" — both axes would count
    # it pc times)
    req_count = jax.lax.psum(
        jnp.sum(light_pending, axis=0, dtype=jnp.int32), "row")
    iterating = req_count > 0
    settling = active & ~iterating

    # ONE masked source array per step: a lane is in exactly one phase,
    # so the union mask ships once and each device recovers the per-phase
    # operands from the replicated lane flags after the gather — the wire
    # stays as sparse as the union of the request sets
    masked_src = jnp.where(
        jnp.where(iterating[None, :], light_pending, in_bucket),
        s.dist, INF)

    # --- expand: assemble this column block's value slice x_j -----------
    f_own = jax.lax.dynamic_slice(masked_src, (chunk_base, col0),
                                  (chunk, lanes))
    x_j, bytes_expand = exchange_expand_values(f_own, "row", compress)

    def vals_from(phase_sel):
        return jnp.where(phase_sel[None, :], x_j, INF)

    partial_cand = _masked_relax_groups(g_loc, w_loc, vals_from, delta,
                                        lanes, iterating, settling,
                                        max_pos, relax_impl)  # [n_loc_r, L]

    # --- fold: complete the row block's candidates along "col" ----------
    cand, bytes_fold = exchange_reduce_min(partial_cand, "col", compress)

    new_dist = jnp.minimum(s.dist, cand)
    changed = new_dist < s.dist
    relaxed2 = (s.relaxed | (light_pending & iterating[None, :])) & ~changed

    unsettled = jnp.where(new_dist >= b_hi[None, :], new_dist, INF)
    min_unsettled = jax.lax.pmin(jnp.min(unsettled, axis=0), "row")  # [L]

    (next_bucket, lane_steps2, capped, finished, trace_bucket,
     trace_phase) = _bucket_control(s, d32, min_unsettled, iterating,
                                    max_steps)

    fcol = jnp.where(finished, s.lane_qidx, cap)
    out_dist = s.out_dist.at[:, fcol].set(new_dist)
    out_steps = s.out_steps.at[fcol].set(lane_steps2)
    out_truncated = s.out_truncated.at[fcol].set(capped)

    # mesh-total wire bytes this step: each "row" gather group (a grid
    # column) reports its expand total, each "col" group (a grid row) its
    # fold total — summing each along the OTHER axis covers the mesh once
    step_bytes = (jax.lax.psum(bytes_expand, "col")
                  + jax.lax.psum(bytes_fold, "row")).astype(jnp.int32)
    log_row = jnp.clip(s.sweep_steps, 0, MAX_SSSP_TRACE - 1)

    return s._replace(
        dist=jnp.where(finished[None, :], INF, new_dist),
        relaxed=relaxed2 & ~finished[None, :],
        lane_bucket=jnp.where(finished, 0, next_bucket),
        lane_steps=jnp.where(finished, 0, lane_steps2),
        lane_qidx=jnp.where(finished, cap, s.lane_qidx),
        sweep_steps=s.sweep_steps + 1,
        out_dist=out_dist, out_steps=out_steps,
        out_truncated=out_truncated,
        trace_bucket=trace_bucket, trace_phase=trace_phase,
        exch_bytes=s.exch_bytes + step_bytes,
        exch_log=s.exch_log.at[log_row].add(step_bytes),
    )


@partial(jax.jit, static_argnames=("mesh", "delta", "max_pos", "relax_impl",
                                   "max_steps", "n", "n_loc_r", "chunk",
                                   "compress", "drain"))
def _dist2d_sssp_run(row_ptr_s, colloc_s, srcloc_s, pos_s, w_s,
                     state: DistSSSPState, *, mesh: Mesh, delta,
                     max_pos: int, relax_impl: str, max_steps: int, n: int,
                     n_loc_r: int, chunk: int, compress: bool,
                     drain: bool) -> DistSSSPState:
    cap = state.queue.shape[0]

    def body(row_ptr, col_loc, src_loc, pos, w, s: DistSSSPState):
        g_loc = CSRGraph(row_ptr=row_ptr[0], col_idx=col_loc[0],
                         src_idx=src_loc[0], slot_pos=pos[0])
        i = jax.lax.axis_index("row")
        j = jax.lax.axis_index("col")
        base_r = (i * n_loc_r).astype(jnp.int32)     # row block start
        chunk_base = (j * chunk).astype(jnp.int32)   # own chunk, in-block
        s = s._replace(dist=s.dist[0], relaxed=s.relaxed[0],
                       out_dist=s.out_dist[0])

        step = partial(_dist2d_sssp_body, (g_loc, w[0]), base_r, chunk_base,
                       delta=delta, max_pos=max_pos, relax_impl=relax_impl,
                       max_steps=max_steps, n=n, n_loc_r=n_loc_r,
                       chunk=chunk, compress=compress)
        if drain:
            s = jax.lax.while_loop(
                lambda s: (s.next_root < s.queued)
                | jnp.any(s.lane_qidx < cap),
                lambda s: step(s), s)
        else:
            s = step(s)
        return s._replace(dist=s.dist[None], relaxed=s.relaxed[None],
                          out_dist=s.out_dist[None])

    spec_dev = P(("row", "col"))
    specs = _state_specs_2d()
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_dev, spec_dev, spec_dev, spec_dev, spec_dev, specs),
        out_specs=specs, check_vma=False,
    )(row_ptr_s, colloc_s, srcloc_s, pos_s, w_s, state)


def dist2d_sssp_engine_step(dwg2: DistWeightedGraph2D, state: DistSSSPState,
                            mesh: Mesh, delta, max_pos: int = 8,
                            relax_impl: str = "xla",
                            max_steps: int = MAX_SSSP_STEPS,
                            compress: bool = False) -> DistSSSPState:
    """Advance the 2-D SSSP engine by one phase step (streaming API)."""
    _check_delta(delta)
    g2 = dwg2.g2
    _check_partition_2d(g2, mesh)
    return _dist2d_sssp_run(
        g2.row_ptr, g2.col_loc, g2.src_loc, g2.slot_pos, dwg2.weights,
        state, mesh=mesh, delta=delta, max_pos=max_pos, relax_impl=relax_impl,
        max_steps=max_steps, n=g2.n, n_loc_r=g2.n_loc_r, chunk=g2.chunk,
        compress=compress, drain=False)


def dist2d_sssp_engine_drain(dwg2: DistWeightedGraph2D, state: DistSSSPState,
                             mesh: Mesh, delta, max_pos: int = 8,
                             relax_impl: str = "xla",
                             max_steps: int = MAX_SSSP_STEPS,
                             compress: bool = False) -> DistSSSPState:
    """Step the 2-D engine until every enqueued source is answered."""
    _check_delta(delta)
    g2 = dwg2.g2
    _check_partition_2d(g2, mesh)
    return _dist2d_sssp_run(
        g2.row_ptr, g2.col_loc, g2.src_loc, g2.slot_pos, dwg2.weights,
        state, mesh=mesh, delta=delta, max_pos=max_pos, relax_impl=relax_impl,
        max_steps=max_steps, n=g2.n, n_loc_r=g2.n_loc_r, chunk=g2.chunk,
        compress=compress, drain=True)


def dist2d_sssp_engine_result(dwg2: DistWeightedGraph2D,
                              state: DistSSSPState) -> SSSPResult:
    """Assemble an ``SSSPResult`` (row blocks are contiguous, so the
    stacked ``out_dist`` reshapes straight into global row order), trimmed
    to the original vertex count."""
    g2 = dwg2.g2
    r = int(state.queued)
    cap = state.capacity
    dist = jnp.reshape(state.out_dist, (g2.n, cap + 1))[:g2.n_orig, :r]
    return SSSPResult(sources=state.queue[:r],
                      dist=dist,
                      steps=state.out_steps[:r],
                      truncated=state.out_truncated[:r],
                      trace_bucket=state.trace_bucket[:, :r],
                      trace_phase=state.trace_phase[:, :r])


def dist2d_sssp(dwg2: DistWeightedGraph2D, roots, mesh: Mesh, delta=None,
                lanes: int = DEFAULT_LANES, max_pos: int = 8,
                relax_impl: str = "xla", max_steps: int = MAX_SSSP_STEPS,
                compress: bool = False, recorder=None) -> SSSPResult:
    """Answer an arbitrary number of SSSP sources with ONE 2-D grid sweep.
    ``compress=True`` ships both per-step value exchanges through the
    sparse (index, payload) codec whenever the gather group is below the
    density threshold — results are bit-identical either way.
    ``recorder`` records a ``LayerRecord`` per phase step as in the
    other engines (None, the default, touches nothing in obs)."""
    roots = jnp.asarray(roots, jnp.int32).reshape(-1)
    num_roots = roots.shape[0]
    if num_roots < 1:
        raise ValueError("need at least one source")
    if delta is None:
        delta = default_delta_dist(dwg2)
    lanes = max(1, min(lanes, num_roots))
    delta = delta if isinstance(delta, tuple) else float(delta)
    state = dist2d_sssp_engine_init(dwg2, mesh, capacity=num_roots,
                                    lanes=lanes)
    state = dist2d_sssp_engine_enqueue(state, roots)
    if recorder is None:
        state = dist2d_sssp_engine_drain(dwg2, state, mesh, delta, max_pos,
                                         relax_impl, max_steps, compress)
    else:
        from repro.obs.sweeplog import drive_recorded
        state = drive_recorded(
            recorder, state,
            lambda s: dist2d_sssp_engine_step(dwg2, s, mesh, delta,
                                              max_pos, relax_impl,
                                              max_steps, compress),
            dist2d_sssp_engine_idle, kind="sssp",
            exch_format="compressed" if compress else "dense")
    return dist2d_sssp_engine_result(dwg2, state)
