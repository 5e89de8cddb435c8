"""Distributed multi-source BFS: the bit-lane engine sharded across devices.

Fuses the two scaling axes grown so far:

* PR 1-2's packed MS-BFS — R concurrent traversals as uint32 lane words,
  pipelined through a fixed bit-lane pool with a pending-root queue;
* ``dist_bfs``'s 1-D vertex partition — device d owns a contiguous row
  block of the CSR and all writes to it.

This is the frontier-exchange structure of Buluc & Madduri (arXiv
1104.4518) applied to the vectorisable packed representation (SlimSell):
each device runs the SAME packed step formulations as the single-host
engine (``repro.core.packed`` — the segmented-OR scan and the MAX_POS
word probe are one shared implementation, not a copy) over its local CSR
block against the full replicated ``uint32[n, W]`` frontier, producing
new-frontier words for its own rows only. The per-layer exchange is a
bitwise-OR allreduce of the placed row blocks (``allreduce_or`` — the
``lax.psum`` analog for bitmasks; for this 1-D contiguous partition it
degenerates to an all-gather concatenation, but the OR form is
partition-agnostic and ready for 2-D edge partitions).

Engine control state (root queue, lane<->queue-slot binding, per-lane
alpha/beta direction flags) is replicated: every device runs the refill
and flush logic on identical values, with the direction decision computed
from ``psum``-merged global counters, so the distributed engine's
lane/queue evolution — and therefore every per-root result and trace —
is bit-identical to the single-host pipelined engine (asserted by
``tests/test_dist_msbfs.py`` at ndev ∈ {1, 2, 4}).

Per-device state layout (``shard_map`` view; leading dim = ndev stacked):
  frontier  : uint32[n, W]            replicated, n padded to ndev*32
  visited   : uint32[ndev, n_loc, W]  device-local rows
  depth     : int32[ndev, n_loc, L]
  out_depth : int32[ndev, n_loc, cap+1]
  everything else (queue, selectors, counters, traces): replicated.

The switch rule uses ``n_orig`` (not the padded ``n``): padded vertices
have degree 0 and never traverse, so with the original vertex count in
the beta threshold every lane's TD/BU trace replays its serial run.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core.csr import CSRGraph
from repro.core.dist_bfs import DistGraph, _flat_axis_index, partition_graph
from repro.core.exchange import allreduce_or
from repro.core.hybrid import ALPHA_DEFAULT, BETA_DEFAULT, MAX_TRACE
from repro.core.msbfs import (LayerReadout, MAX_LANES, MSBFSResult,
                              msbfs_engine_enqueue, msbfs_engine_idle)
from repro.core.packed import (LANE_WORD_BITS, MODES, adaptive_lane_pool,
                               dispatch_packed_step, lane_counters,
                               num_lane_words, pack_lanes, queue_claims,
                               select_direction, unpack_lanes, word_dtype)

__all__ = [
    "DistGraph", "DistPipelineState", "allreduce_or", "dist_msbfs",
    "dist_msbfs_engine_drain", "dist_msbfs_engine_enqueue",
    "dist_msbfs_engine_idle", "dist_msbfs_engine_init",
    "dist_msbfs_engine_readout", "dist_msbfs_engine_result",
    "dist_msbfs_engine_retire", "dist_msbfs_engine_step", "host_mesh",
    "partition_graph",
]


class DistPipelineState(NamedTuple):
    """Pipelined-engine state, partitioned. Mirrors ``msbfs.PipelineState``
    field-for-field (so the host-side enqueue/idle helpers are shared);
    row-indexed arrays carry a leading stacked device dim instead."""
    frontier: jnp.ndarray        # uint32[n, W] — full, replicated
    visited: jnp.ndarray         # uint32[ndev, n_loc, W]
    depth: jnp.ndarray           # int32[ndev, n_loc, L]
    lane_layer: jnp.ndarray      # int32[L]
    lane_qidx: jnp.ndarray       # int32[L]   queue slot served; capacity = idle
    topdown: jnp.ndarray         # bool[L]
    queue: jnp.ndarray           # int32[capacity]
    queued: jnp.ndarray          # int32 scalar
    next_root: jnp.ndarray       # int32 scalar
    sweep_layers: jnp.ndarray    # int32 scalar
    out_depth: jnp.ndarray       # int32[ndev, n_loc, capacity+1]
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


def _state_specs(axes) -> DistPipelineState:
    dev = P(axes)
    rep = P()
    return DistPipelineState(
        frontier=rep, visited=dev, depth=dev, lane_layer=rep, lane_qidx=rep,
        topdown=rep, queue=rep, queued=rep, next_root=rep, sweep_layers=rep,
        out_depth=dev, out_edges=rep, out_layers=rep, trace_dir=rep,
        trace_vf=rep, trace_ef=rep, trace_eu=rep)


def _check_partition(dg: DistGraph, mesh: Mesh) -> int:
    ndev = int(np.prod(mesh.devices.shape))
    if dg.row_ptr.shape[0] != ndev:
        raise ValueError(
            f"DistGraph partitioned for {dg.row_ptr.shape[0]} devices but "
            f"mesh has {ndev} — repartition with partition_graph(g, {ndev})")
    return ndev


def dist_msbfs_engine_init(dg: DistGraph, mesh: Mesh, capacity: int,
                           lanes: int = MAX_LANES) -> DistPipelineState:
    """Fresh sharded engine: all lanes idle, empty root queue."""
    if capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    if lanes < 1:
        raise ValueError(f"lanes must be >= 1, got {lanes}")
    ndev = _check_partition(dg, mesh)
    n_loc = dg.n // ndev
    w = num_lane_words(lanes)
    cap = capacity
    return DistPipelineState(
        frontier=jnp.zeros((dg.n, w), word_dtype()),
        visited=jnp.zeros((ndev, n_loc, w), word_dtype()),
        depth=jnp.full((ndev, n_loc, lanes), -1, jnp.int32),
        lane_layer=jnp.zeros((lanes,), jnp.int32),
        lane_qidx=jnp.full((lanes,), cap, jnp.int32),
        topdown=jnp.ones((lanes,), jnp.bool_),
        queue=jnp.zeros((cap,), jnp.int32),
        queued=jnp.int32(0),
        next_root=jnp.int32(0),
        sweep_layers=jnp.int32(0),
        out_depth=jnp.full((ndev, n_loc, cap + 1), -1, jnp.int32),
        out_edges=jnp.zeros((cap + 1,), jnp.int32),
        out_layers=jnp.zeros((cap + 1,), jnp.int32),
        trace_dir=jnp.full((MAX_TRACE, cap + 1), -1, jnp.int32),
        trace_vf=jnp.zeros((MAX_TRACE, cap + 1), jnp.int32),
        trace_ef=jnp.zeros((MAX_TRACE, cap + 1), jnp.int32),
        trace_eu=jnp.zeros((MAX_TRACE, cap + 1), jnp.int32),
    )


def dist_msbfs_engine_enqueue(state: DistPipelineState,
                              roots) -> DistPipelineState:
    """Append roots to the (replicated) pending queue — the host helper is
    the single-host one: queue state is replicated, so enqueue is identical
    on every device."""
    return msbfs_engine_enqueue(state, roots)


def dist_msbfs_engine_idle(state: DistPipelineState) -> bool:
    """True when no lane is active and no enqueued root is pending."""
    return msbfs_engine_idle(state)


def _dist_pipeline_body(g_loc: CSRGraph, base, s: DistPipelineState,
                        mode: str, alpha: float, beta: float, max_pos: int,
                        probe_impl: str, n: int, n_loc: int, n_orig: int,
                        axes) -> DistPipelineState:
    """One engine step, per-device view: refill idle lanes (replicated),
    advance one layer on the local row block, exchange frontiers, flush
    finished lanes. Mirrors ``msbfs._pipeline_body`` exactly — the only
    distributed moves are two ``psum`` counter merges and one
    ``allreduce_or`` frontier exchange."""
    lanes = s.lane_qidx.shape[0]
    cap = s.queue.shape[0]
    w = s.frontier.shape[1]
    # dynamic_slice wants all start indices in ONE dtype; a bare 0 would
    # weak-type to int64 under x64 (the u64 lane-word rung) and clash
    # with the int32 device base
    col0 = jnp.zeros((), jnp.asarray(base).dtype)

    # --- refill: replicated claim logic, row-local seat writes -----------
    def do_refill(s: DistPipelineState) -> DistPipelineState:
        claim, cand, root = queue_claims(s.lane_qidx, s.next_root,
                                         s.queued, s.queue)
        onehot = claim[None, :] & (root[None, :]
                                   == jnp.arange(n, dtype=jnp.int32)[:, None])
        fresh = pack_lanes(onehot)                            # uint32[n, W]
        onehot_loc = jax.lax.dynamic_slice(onehot, (base, col0), (n_loc, lanes))
        fresh_loc = jax.lax.dynamic_slice(fresh, (base, col0), (n_loc, w))
        return s._replace(
            frontier=s.frontier | fresh,
            visited=s.visited | fresh_loc,
            depth=jnp.where(claim[None, :],
                            jnp.where(onehot_loc, 0, -1), s.depth),
            lane_layer=jnp.where(claim, 0, s.lane_layer),
            lane_qidx=jnp.where(claim, cand, s.lane_qidx),
            topdown=jnp.where(claim, mode != "bottomup", s.topdown),
            next_root=s.next_root + jnp.sum(claim, dtype=jnp.int32),
        )

    needed = jnp.any(s.lane_qidx >= cap) & (s.next_root < s.queued)
    s = jax.lax.cond(needed, do_refill, lambda s: s, s)

    # --- per-lane direction from psum-merged global counters -------------
    active = s.lane_qidx < cap
    frontier_loc = jax.lax.dynamic_slice(s.frontier, (base, col0), (n_loc, w))
    frontier_b = unpack_lanes(frontier_loc, lanes)
    visited_b = unpack_lanes(s.visited, lanes)
    pe_f, pv_f, pe_u = lane_counters(g_loc, frontier_b, visited_b)
    e_f = jax.lax.psum(pe_f, axes)
    v_f = jax.lax.psum(pv_f, axes)
    e_u = jax.lax.psum(pe_u, axes)
    topdown = select_direction(mode, s.topdown, e_f, v_f, e_u, n_orig,
                               alpha, beta, lanes)

    live = active & (v_f > 0)
    td_sel = pack_lanes(topdown & live)                       # uint32[W]
    bu_sel = pack_lanes(~topdown & live)

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

    # --- the SHARED packed step over the local block ---------------------
    new_loc = dispatch_packed_step(g_loc, s.frontier, s.visited, td_sel,
                                   bu_sel, mode, max_pos, probe_impl)

    # --- frontier exchange: place local rows, OR-merge across devices ----
    placed = jax.lax.dynamic_update_slice(
        jnp.zeros((n, w), new_loc.dtype), new_loc, (base, col0))
    new_full = allreduce_or(placed, axes)

    new_loc_b = unpack_lanes(new_loc, lanes)
    visited2 = s.visited | new_loc
    visited2_b = visited_b | new_loc_b
    lane_layer2 = s.lane_layer + active.astype(jnp.int32)
    depth2 = jnp.where(new_loc_b, lane_layer2[None, :], s.depth)

    # finish = GLOBAL frontier drained OR per-lane layer cap
    new_any = unpack_lanes(new_full, lanes).any(axis=0)
    finished = active & (~new_any | (lane_layer2 >= MAX_TRACE))

    deg = g_loc.deg.astype(jnp.int32)[:, None]
    edges_l = jax.lax.psum(
        jnp.sum(jnp.where(visited2_b, deg, 0), axis=0,
                dtype=jnp.int32), axes)
    fcol = jnp.where(finished, s.lane_qidx, cap)
    out_depth = s.out_depth.at[:, fcol].set(depth2)
    out_edges = s.out_edges.at[fcol].set(edges_l)
    out_layers = s.out_layers.at[fcol].set(lane_layer2)

    clear = pack_lanes(finished)                              # uint32[W]
    return s._replace(
        frontier=new_full & ~clear,
        visited=visited2 & ~clear,
        depth=jnp.where(finished[None, :], -1, depth2),
        lane_layer=jnp.where(finished, 0, lane_layer2),
        lane_qidx=jnp.where(finished, cap, s.lane_qidx),
        topdown=topdown,
        sweep_layers=s.sweep_layers + 1,
        out_depth=out_depth, out_edges=out_edges, out_layers=out_layers,
        trace_dir=trace_dir, trace_vf=trace_vf, trace_ef=trace_ef,
        trace_eu=trace_eu,
    )


@partial(jax.jit, static_argnames=("mesh", "mode", "alpha", "beta",
                                   "max_pos", "probe_impl", "n", "n_loc",
                                   "n_orig", "drain"))
def _dist_engine_run(row_ptr_s, col_s, srcloc_s, deg_s,
                     state: DistPipelineState, *, mesh: Mesh, mode: str,
                     alpha: float, beta: float, max_pos: int,
                     probe_impl: str, n: int, n_loc: int, n_orig: int,
                     drain: bool) -> DistPipelineState:
    axes = tuple(mesh.axis_names)
    cap = state.queue.shape[0]

    def body(row_ptr, col, src_loc, deg, s: DistPipelineState):
        # strip the stacked device dim from the sharded leaves
        g_loc = CSRGraph(row_ptr=row_ptr[0], col_idx=col[0],
                         src_idx=src_loc[0])
        del deg   # g_loc.deg (row_ptr diffs) == the stored per-device deg
        base = _flat_axis_index(axes, dict(mesh.shape)) * n_loc
        s = s._replace(visited=s.visited[0], depth=s.depth[0],
                       out_depth=s.out_depth[0])

        step = partial(_dist_pipeline_body, g_loc, base, mode=mode,
                       alpha=alpha, beta=beta, max_pos=max_pos,
                       probe_impl=probe_impl, n=n, n_loc=n_loc,
                       n_orig=n_orig, axes=axes)
        if drain:
            s = jax.lax.while_loop(
                lambda s: (s.next_root < s.queued)
                | jnp.any(s.lane_qidx < cap),
                lambda s: step(s), s)
        else:
            s = step(s)
        return s._replace(visited=s.visited[None], depth=s.depth[None],
                          out_depth=s.out_depth[None])

    spec_dev = P(axes)
    specs = _state_specs(axes)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_dev, spec_dev, spec_dev, spec_dev, specs),
        out_specs=specs, check_vma=False,
    )(row_ptr_s, col_s, srcloc_s, deg_s, state)


def dist_msbfs_engine_step(dg: DistGraph, state: DistPipelineState,
                           mesh: Mesh, mode: str = "hybrid",
                           alpha: float = ALPHA_DEFAULT,
                           beta: float = BETA_DEFAULT, max_pos: int = 8,
                           probe_impl: str = "xla") -> DistPipelineState:
    """Advance the sharded engine by one traversal layer (streaming API).

    Compiles once per (graph shapes, lanes, capacity, mode); the serving
    loop interleaves ``dist_msbfs_engine_enqueue`` between steps exactly
    like the single-host engine."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    ndev = _check_partition(dg, mesh)
    return _dist_engine_run(
        dg.row_ptr, dg.col_idx, dg.src_loc, dg.deg, state, mesh=mesh,
        mode=mode, alpha=alpha, beta=beta, max_pos=max_pos,
        probe_impl=probe_impl, n=dg.n, n_loc=dg.n // ndev,
        n_orig=dg.n_orig, drain=False)


def dist_msbfs_engine_drain(dg: DistGraph, state: DistPipelineState,
                            mesh: Mesh, mode: str = "hybrid",
                            alpha: float = ALPHA_DEFAULT,
                            beta: float = BETA_DEFAULT, max_pos: int = 8,
                            probe_impl: str = "xla") -> DistPipelineState:
    """Step the sharded engine until every enqueued root is answered."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    ndev = _check_partition(dg, mesh)
    return _dist_engine_run(
        dg.row_ptr, dg.col_idx, dg.src_loc, dg.deg, state, mesh=mesh,
        mode=mode, alpha=alpha, beta=beta, max_pos=max_pos,
        probe_impl=probe_impl, n=dg.n, n_loc=dg.n // ndev,
        n_orig=dg.n_orig, drain=True)


@partial(jax.jit, static_argnames=("mesh", "n", "n_loc", "num_roots",
                                   "lane_chunk"))
def _derive_parents_dist(row_ptr_s, col_s, srcloc_s, depth_full, roots, *,
                         mesh: Mesh, n: int, n_loc: int, num_roots: int,
                         lane_chunk: int = 16):
    """Distributed analog of ``msbfs._derive_parents``: each device scans
    its local edge slab for the min-id neighbour one level up, then the
    row blocks are gathered. Same deterministic min-id rule, chunked over
    lanes to bound the [m_loc, chunk] candidate buffer."""
    axes = tuple(mesh.axis_names)

    def body(row_ptr, col, src_loc, depth_full, roots):
        row_ptr, col, src_loc = row_ptr[0], col[0], src_loc[0]
        base = _flat_axis_index(axes, dict(mesh.shape)) * n_loc
        depth_loc = jax.lax.dynamic_slice(
            depth_full, (base, jnp.zeros((), jnp.asarray(base).dtype)),
            (n_loc, num_roots))
        colc = jnp.clip(col, 0, n - 1)
        valid = (col < n)[:, None]       # pad slots carry the sentinel n
        outs = []
        for lo in range(0, num_roots, lane_chunk):
            d_full = depth_full[:, lo:lo + lane_chunk]
            d_loc = depth_loc[:, lo:lo + lane_chunk]
            ok = valid & (d_full[colc] >= 0) & (d_full[colc] + 1
                                                == d_loc[src_loc])
            cand = jnp.where(ok, col[:, None], n).astype(jnp.int32)
            best = jnp.full((n_loc, d_loc.shape[1]), n,
                            jnp.int32).at[src_loc].min(cand)
            outs.append(jnp.where(best < n, best, -1))
        parent_loc = jnp.concatenate(outs, axis=1)
        # seat roots owned by this device; rows outside the block are
        # pushed past n_loc so mode="drop" discards them (a bare
        # ``roots - base`` would WRAP for negative rows)
        lane = jnp.arange(num_roots, dtype=jnp.int32)
        own = (roots >= base) & (roots < base + n_loc)
        lrow = jnp.where(own, roots - base, n_loc)
        parent_loc = parent_loc.at[lrow, lane].set(
            roots.astype(jnp.int32), mode="drop")
        return jax.lax.all_gather(parent_loc, axes, tiled=True)

    spec_dev = P(axes)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_dev, spec_dev, spec_dev, P(), P()),
        out_specs=P(), check_vma=False,
    )(row_ptr_s, col_s, srcloc_s, depth_full, roots)


def dist_msbfs_engine_result(dg: DistGraph, state: DistPipelineState,
                             mesh: Mesh, trim: bool = True,
                             derive_parents: bool = True) -> MSBFSResult:
    """Assemble an ``MSBFSResult`` over the answered queue slots.

    Depths come from the flushed per-device row blocks; parents are
    derived distributed (min-id neighbour one level up, the MSBFSResult
    convention: -1 for unreached/dead vertices, ``parent[root_r, r] ==
    root_r``) unless ``derive_parents=False`` (zero-width ``parent``, the
    analytics depth-only contract). With ``trim`` the arrays are cut back
    to the original (pre-padding) vertex count."""
    ndev = _check_partition(dg, mesh)
    r = int(state.queued)
    cap = state.capacity
    depth = jnp.reshape(state.out_depth, (dg.n, cap + 1))[:, :r]
    roots = state.queue[:r]
    if r and derive_parents:
        parent = _derive_parents_dist(
            dg.row_ptr, dg.col_idx, dg.src_loc, depth,
            roots.astype(jnp.int32), mesh=mesh, n=dg.n,
            n_loc=dg.n // ndev, num_roots=r)
    else:
        parent = jnp.zeros((dg.n, 0), jnp.int32)
    lim = dg.n_orig if trim else dg.n
    return MSBFSResult(
        parent=parent[:lim], depth=depth[:lim],
        num_layers=state.out_layers[:r],
        edges_traversed=state.out_edges[:r],
        trace_dir=state.trace_dir[:, :r], trace_vf=state.trace_vf[:, :r],
        trace_ef=state.trace_ef[:, :r], trace_eu=state.trace_eu[:, :r])


def dist_msbfs_engine_readout(dg: DistGraph,
                              state: DistPipelineState) -> LayerReadout:
    """Snapshot the streaming read-out surface of the sharded engine —
    the SAME ``LayerReadout`` as the host engine, with the per-device row
    blocks reassembled into global vertex order and trimmed to the
    original vertex count, so streaming consumers are partition-blind
    (control state is replicated; the depth surfaces are bit-identical
    to the host engine's at every layer)."""
    cap = state.capacity
    lanes = state.num_lanes
    depth = np.reshape(np.asarray(state.depth), (dg.n, lanes))
    out_depth = np.reshape(np.asarray(state.out_depth), (dg.n, cap + 1))
    return LayerReadout(
        layer=int(state.sweep_layers), capacity=cap,
        lane_qidx=np.asarray(state.lane_qidx),
        lane_layer=np.asarray(state.lane_layer),
        depth=depth[:dg.n_orig], out_depth=out_depth[:dg.n_orig],
        out_layers=np.asarray(state.out_layers))


@jax.jit
def _retire_dist(deg_s, state: DistPipelineState,
                 lane_mask: jnp.ndarray) -> DistPipelineState:
    cap = state.capacity
    mask = lane_mask & (state.lane_qidx < cap)
    visited_b = unpack_lanes(state.visited, state.num_lanes)
    deg = deg_s.astype(jnp.int32)[..., None]              # [ndev, n_loc, 1]
    edges_l = jnp.sum(jnp.where(visited_b, deg, 0), axis=(0, 1),
                      dtype=jnp.int32)
    fcol = jnp.where(mask, state.lane_qidx, cap)
    out_depth = state.out_depth.at[:, :, fcol].set(state.depth)
    out_edges = state.out_edges.at[fcol].set(edges_l)
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


def dist_msbfs_engine_retire(dg: DistGraph, state: DistPipelineState,
                             lane_mask) -> DistPipelineState:
    """Retire the masked ACTIVE lanes early (sharded mirror of
    ``msbfs_engine_retire``): flush their depth columns to the per-device
    output blocks and free the lanes. Control state is replicated, so the
    host-level mask applies identically on every device; like the
    enqueue helper this runs outside ``shard_map`` — the next step's jit
    re-shards the touched leaves."""
    lane_mask = jnp.asarray(lane_mask, jnp.bool_).reshape(-1)
    if lane_mask.shape[0] != state.num_lanes:
        raise ValueError(
            f"lane_mask has {lane_mask.shape[0]} lanes, engine has "
            f"{state.num_lanes}")
    return _retire_dist(dg.deg, state, lane_mask)


def first_devices(need: int, what: str) -> list:
    """The first ``need`` jax devices, or a ValueError naming the platform
    and device count found (a CPU run can fake devices; a chip host has
    the chips it has)."""
    devs = jax.devices()
    if len(devs) >= need:
        return devs[:need]
    platform = devs[0].platform
    hint = (f"set XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            f"before the first jax import" if platform == "cpu" else
            f"run on a host with at least {need} {platform} devices")
    raise ValueError(f"{what} needs {need} devices but found {len(devs)} "
                     f"{platform} device(s) — {hint}")


def host_mesh(ndev: int) -> Mesh:
    """1-D mesh over the first ``ndev`` local devices (shared by the
    graph500 harness and the serving loop)."""
    devs = first_devices(ndev, f"ndev={ndev}")
    return Mesh(np.asarray(devs), ("data",))


def dist_msbfs(dg: DistGraph, roots, mesh: Mesh, mode: str = "hybrid",
               alpha: float = ALPHA_DEFAULT, beta: float = BETA_DEFAULT,
               max_pos: int = 8, probe_impl: str = "xla",
               lanes: int | None = None, derive_parents: bool = True,
               recorder=None) -> MSBFSResult:
    """Answer an arbitrary number of roots with ONE sharded engine sweep.

    ``lanes=None`` (or 0) sizes the bit-lane pool adaptively from the pending
    root count and the graph's degree stats (``packed.adaptive_lane_pool``
    — the ROADMAP rung); pass an int to pin the pool width. Every lane's
    depths/parents match serial ``bfs()`` exactly and pass the Graph500
    spec-4 validator; results are trimmed to the original vertex count.

    ``recorder`` (a ``repro.obs.SweepRecorder``) records a ``LayerRecord``
    per layer by stepping the engine instead of the fused drain — step
    and drain share the sharded body, so results and traces are
    bit-identical; None (the default) touches nothing in ``repro.obs``.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    roots = jnp.asarray(roots, jnp.int32).reshape(-1)
    num_roots = roots.shape[0]
    if num_roots < 1:
        raise ValueError("need at least one root")
    if not lanes:                  # None or 0: the documented adaptive knob
        m_total = int(np.asarray(dg.deg, dtype=np.int64).sum())
        lanes = adaptive_lane_pool(num_roots, dg.n_orig, m_total)
    # W derives from the ACTIVE batch: small R never pays for idle words
    lanes = max(1, min(lanes, LANE_WORD_BITS * num_lane_words(num_roots)))
    state = dist_msbfs_engine_init(dg, mesh, capacity=num_roots, lanes=lanes)
    state = dist_msbfs_engine_enqueue(state, roots)
    if recorder is None:
        state = dist_msbfs_engine_drain(dg, state, mesh, mode, alpha, beta,
                                        max_pos, probe_impl)
    else:
        from repro.obs.sweeplog import drive_recorded
        state = drive_recorded(
            recorder, state,
            lambda s: dist_msbfs_engine_step(dg, s, mesh, mode, alpha,
                                             beta, max_pos, probe_impl),
            dist_msbfs_engine_idle, kind="bfs")
    return dist_msbfs_engine_result(dg, state, mesh,
                                    derive_parents=derive_parents)
