"""2-D partitioned multi-source BFS: the bit-lane engine on a pr x pc grid.

The Buluc-Madduri (arXiv 1104.4518) 2-D decomposition applied to the
packed lane-word representation. Where the 1-D engine
(``repro.core.dist_msbfs``) replicates the full ``[n, W]`` frontier on
every device and OR-allreduces whole row blocks each layer, the 2-D
engine partitions the adjacency matrix over a ``pr x pc`` device grid and
never materialises replicated global frontier state:

* vertices are cut into ``G = pr * pc`` equal chunks (each padded to a
  multiple of 32); grid device ``(i, j)`` owns chunk ``g = i*pc + j``;
* *row block* ``i`` = chunks ``[i*pc, (i+1)*pc)`` — a CONTIGUOUS global
  row range of ``n_loc_r = pc * chunk`` vertices (so results assemble by
  concatenation, exactly like the 1-D engine);
* *column block* ``j`` = chunks ``{i*pc + j}`` — strided, one chunk per
  grid row, so each column's expand gathers exactly one chunk from each
  of its ``pr`` devices;
* device ``(i, j)`` stores the CSR rows of row block ``i`` RESTRICTED to
  destinations in column block ``j`` (``partition_graph_2d``), with
  column ids rewritten to column-block-local positions.

Per layer, per device ``(i, j)``:

  expand     — all-gather the ``chunk x W`` frontier chunks along the
               "row" axis (``exchange_expand``): the devices of grid
               column ``j`` assemble ``x_j``, column block ``j``'s
               frontier slice, in grid-row order = column-local order.
  local step — the SAME packed formulations as every other engine
               (``repro.core.packed``: segmented-OR top-down, MAX_POS
               word probe + scan fallback bottom-up) over the local
               ``(i, j)`` block against ``x_j``, producing PARTIAL
               new-frontier words for row block ``i`` (this block's
               edges only).
  fold       — OR-reduce the partials along the "col" axis
               (``exchange_reduce_or``): grid row ``i`` assembles the
               complete new frontier of row block ``i``, replicated
               along "col" — which is exactly the state the next
               layer's expand slices its chunk from.

Both exchanges ride ``repro.core.exchange.gather_words`` and therefore
the sparse frontier-word codec (``repro.distributed.compression``): with
``compress=True`` each gather group ships (index, payload) pairs whenever
every member's slice is sparse enough, so bytes on the wire per layer
track the FRONTIER POPULATION, not the graph — the engine accumulates the
actual per-step byte totals (``exch_bytes`` / ``exch_log``) and the star
benchmark (``benchmarks/dist2d_teps.py``) reports them.

Bit-identity with the host and 1-D engines (asserted across the whole
grid/width/wire-format matrix by ``tests/test_dist2d.py``): the packed
step computes, for every local row, the OR of its slab neighbours'
frontier words masked by ``need`` — probe retirement only fires once a
plane's needed bits are all served, and the scan fallback covers every
position past MAX_POS, so the partial is EXACTLY (partial row OR) & need
regardless of retirement granularity. Partial-row ORs over the grid
columns compose to the full row OR, the direction decision uses
psum-merged global counters, and all control state is replicated — so
depths, parents, layer counts, and per-layer traces replay the
single-host pipelined engine bit-for-bit.

Per-device state layout (``shard_map`` view):
  frontier  : word[pr, n_loc_r, W]  row block, REPLICATED along "col"
  visited   : word[pr, n_loc_r, W]            (P("row") in the mesh)
  depth     : int32[pr, n_loc_r, L]
  out_depth : int32[pr, n_loc_r, cap+1]
  graph     : stacked [G, ...] blocks, P(("row", "col"))
  everything else (queue, selectors, counters, traces): replicated.
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

from repro.core.csr import CSRGraph, build_slot_pos
from repro.core.dist_msbfs import first_devices
from repro.core.exchange import exchange_expand, exchange_reduce_or
from repro.core.hybrid import ALPHA_DEFAULT, BETA_DEFAULT, MAX_TRACE
from repro.core.msbfs import (MAX_LANES, MSBFSResult, msbfs_engine_enqueue,
                              msbfs_engine_idle)
from repro.core.packed import (LANE_WORD_BITS, MODES, adaptive_lane_pool,
                               dispatch_packed_step, lane_counters,
                               num_lane_words, pack_lanes, queue_claims,
                               select_direction, unpack_lanes, word_dtype)

__all__ = [
    "DistGraph2D", "Dist2DPipelineState", "dist2d_msbfs",
    "dist2d_msbfs_engine_drain", "dist2d_msbfs_engine_enqueue",
    "dist2d_msbfs_engine_idle", "dist2d_msbfs_engine_init",
    "dist2d_msbfs_engine_result", "dist2d_msbfs_engine_step", "mesh2d",
    "partition_graph_2d",
]


@dataclass(frozen=True)
class DistGraph2D:
    """Host-partitioned 2-D CSR: stacked per-device blocks, leading dim
    ``G = pr * pc`` in grid-row-major order (device ``(i, j)`` = slab
    ``i*pc + j``, matching a ``P(("row", "col"))`` placement)."""
    row_ptr: jnp.ndarray   # int32[G, n_loc_r+1] — offsets into the slab
    col_loc: jnp.ndarray   # int32[G, m_loc] — column-block-LOCAL dest ids
    col_gid: jnp.ndarray   # int32[G, m_loc] — global dest ids (parents)
    src_loc: jnp.ndarray   # int32[G, m_loc] — row-block-local source row
    slot_pos: jnp.ndarray  # int32[G, m_loc] — place in row, -1 pads
    deg: jnp.ndarray       # int32[G, n_loc_r] — PARTIAL (block) degrees
    n: int                 # padded global vertex count (G * chunk)
    n_orig: int            # original vertex count
    pr: int                # grid rows
    pc: int                # grid columns
    chunk: int             # rows per chunk (multiple of 32)
    m_loc: int             # uniform per-device edge-slab size (padded)

    @property
    def n_loc_r(self) -> int:
        """Rows per row block (= pc * chunk)."""
        return self.pc * self.chunk

    @property
    def n_x(self) -> int:
        """Rows per column-block frontier slice (= pr * chunk)."""
        return self.pr * self.chunk


def partition_graph_2d(g: CSRGraph, pr: int, pc: int) -> DistGraph2D:
    """Host-side 2-D partition: split ``g`` into ``pr x pc`` adjacency
    blocks with uniform padding.

    Row blocks are contiguous global row ranges; inside block ``(i, j)``
    each row keeps only its edges whose destination chunk ``v // chunk``
    lies in column block ``j`` (chunk index ``% pc == j``), in original
    adjacency order. ``col_loc`` rewrites destinations to their position
    inside the column block's gathered frontier slice
    (``grid_row * chunk + v % chunk``); ``col_gid`` keeps the global id
    for parent derivation. Padded edge slots carry sentinel column ids
    (``n_x`` local / ``n`` global) and live past every row's read-out
    point, so the packed steps never consume them."""
    if pr < 1 or pc < 1:
        raise ValueError(f"grid dims must be >= 1, got {pr}x{pc}")
    rp = np.asarray(g.row_ptr)
    ci = np.asarray(g.col_idx)
    n_orig = g.n
    ndev = pr * pc
    chunk = -(-n_orig // (ndev * 32)) * 32       # chunk multiple of 32
    n = chunk * ndev
    n_loc_r = pc * chunk
    n_x = pr * chunk

    slabs_loc, slabs_gid, srcs, degs = [], [], [], []
    for i in range(pr):
        lo_v, hi_v = i * n_loc_r, min((i + 1) * n_loc_r, n_orig)
        if lo_v < n_orig:
            dst = ci[rp[lo_v]:rp[hi_v]]
            src = np.repeat(np.arange(hi_v - lo_v, dtype=np.int32),
                            np.diff(rp[lo_v:hi_v + 1]))
        else:
            dst = src = np.zeros(0, np.int32)
        dst_chunk = dst // chunk
        for j in range(pc):
            sel = dst_chunk % pc == j
            d, s = dst[sel], src[sel]
            # column-local id: grid row of the dest chunk, then offset
            loc = (dst_chunk[sel] // pc) * chunk + d % chunk
            slabs_loc.append(loc.astype(np.int32))
            slabs_gid.append(d.astype(np.int32))
            srcs.append(s)
            degs.append(np.bincount(s, minlength=n_loc_r).astype(np.int32))

    m_loc = max(1, max(len(s) for s in srcs))
    col_loc = np.full((ndev, m_loc), n_x, np.int32)   # sentinel pads
    col_gid = np.full((ndev, m_loc), n, np.int32)
    src_l = np.zeros((ndev, m_loc), np.int32)
    deg_l = np.stack(degs)
    row_ptr_l = np.zeros((ndev, n_loc_r + 1), np.int32)
    np.cumsum(deg_l, axis=1, out=row_ptr_l[:, 1:])
    for d in range(ndev):
        k = len(srcs[d])
        col_loc[d, :k] = slabs_loc[d]
        col_gid[d, :k] = slabs_gid[d]
        src_l[d, :k] = srcs[d]
    row_ptr_l, src_l = jnp.asarray(row_ptr_l), jnp.asarray(src_l)
    return DistGraph2D(
        row_ptr=row_ptr_l, col_loc=jnp.asarray(col_loc),
        col_gid=jnp.asarray(col_gid), src_loc=src_l,
        slot_pos=jax.vmap(build_slot_pos)(row_ptr_l, src_l),
        deg=jnp.asarray(deg_l), n=n, n_orig=n_orig, pr=pr, pc=pc,
        chunk=chunk, m_loc=m_loc)


class Dist2DPipelineState(NamedTuple):
    """Pipelined-engine state on the 2-D grid. Mirrors
    ``dist_msbfs.DistPipelineState`` field-for-field (the host enqueue /
    idle helpers are shared) with two differences: the frontier is a
    row-block slice like ``visited`` (NO replicated ``[n, W]`` state —
    the tentpole), and the exchange-byte meters ride along."""
    frontier: jnp.ndarray        # word[pr, n_loc_r, W] — row block
    visited: jnp.ndarray         # word[pr, n_loc_r, W]
    depth: jnp.ndarray           # int32[pr, n_loc_r, L]
    lane_layer: jnp.ndarray      # int32[L]
    lane_qidx: jnp.ndarray       # int32[L]  queue slot served; cap = idle
    topdown: jnp.ndarray         # bool[L]
    queue: jnp.ndarray           # int32[capacity]
    queued: jnp.ndarray          # int32 scalar
    next_root: jnp.ndarray       # int32 scalar
    sweep_layers: jnp.ndarray    # int32 scalar
    out_depth: jnp.ndarray       # int32[pr, n_loc_r, capacity+1]
    out_edges: jnp.ndarray       # int32[capacity+1]
    out_layers: jnp.ndarray      # int32[capacity+1]  0 = unanswered
    trace_dir: jnp.ndarray       # int32[MAX_TRACE, capacity+1]
    trace_vf: jnp.ndarray
    trace_ef: jnp.ndarray
    trace_eu: jnp.ndarray
    exch_bytes: jnp.ndarray      # int32 scalar — mesh-total wire bytes
    exch_log: jnp.ndarray        # int32[MAX_TRACE] — bytes per sweep step

    @property
    def num_lanes(self) -> int:
        return self.lane_qidx.shape[0]

    @property
    def capacity(self) -> int:
        return self.queue.shape[0]


def _state_specs_2d() -> Dist2DPipelineState:
    row = P("row")
    rep = P()
    return Dist2DPipelineState(
        frontier=row, visited=row, depth=row, lane_layer=rep, lane_qidx=rep,
        topdown=rep, queue=rep, queued=rep, next_root=rep, sweep_layers=rep,
        out_depth=row, out_edges=rep, out_layers=rep, trace_dir=rep,
        trace_vf=rep, trace_ef=rep, trace_eu=rep, exch_bytes=rep,
        exch_log=rep)


def _check_partition_2d(dg: DistGraph2D, mesh: Mesh) -> None:
    shape = dict(mesh.shape)
    if tuple(mesh.axis_names) != ("row", "col"):
        raise ValueError(
            f'2-D engine needs a ("row", "col") mesh — got axes '
            f"{tuple(mesh.axis_names)}; build one with mesh2d(pr, pc)")
    if (shape["row"], shape["col"]) != (dg.pr, dg.pc):
        raise ValueError(
            f"DistGraph2D partitioned for a {dg.pr}x{dg.pc} grid but mesh "
            f"is {shape['row']}x{shape['col']} — repartition with "
            f"partition_graph_2d(g, {shape['row']}, {shape['col']})")


def mesh2d(pr: int, pc: int) -> Mesh:
    """``pr x pc`` grid mesh over the first ``pr*pc`` local devices."""
    devs = first_devices(pr * pc, f"grid {pr}x{pc}")
    return Mesh(np.asarray(devs).reshape(pr, pc), ("row", "col"))


def dist2d_msbfs_engine_init(dg: DistGraph2D, mesh: Mesh, capacity: int,
                             lanes: int = MAX_LANES) -> Dist2DPipelineState:
    """Fresh 2-D engine: all lanes idle, empty root queue, byte meters 0."""
    if capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    if lanes < 1:
        raise ValueError(f"lanes must be >= 1, got {lanes}")
    _check_partition_2d(dg, mesh)
    n_loc_r = dg.n_loc_r
    w = num_lane_words(lanes)
    cap = capacity
    return Dist2DPipelineState(
        frontier=jnp.zeros((dg.pr, n_loc_r, w), word_dtype()),
        visited=jnp.zeros((dg.pr, n_loc_r, w), word_dtype()),
        depth=jnp.full((dg.pr, n_loc_r, lanes), -1, jnp.int32),
        lane_layer=jnp.zeros((lanes,), jnp.int32),
        lane_qidx=jnp.full((lanes,), cap, jnp.int32),
        topdown=jnp.ones((lanes,), jnp.bool_),
        queue=jnp.zeros((cap,), jnp.int32),
        queued=jnp.int32(0),
        next_root=jnp.int32(0),
        sweep_layers=jnp.int32(0),
        out_depth=jnp.full((dg.pr, n_loc_r, cap + 1), -1, jnp.int32),
        out_edges=jnp.zeros((cap + 1,), jnp.int32),
        out_layers=jnp.zeros((cap + 1,), jnp.int32),
        trace_dir=jnp.full((MAX_TRACE, cap + 1), -1, jnp.int32),
        trace_vf=jnp.zeros((MAX_TRACE, cap + 1), jnp.int32),
        trace_ef=jnp.zeros((MAX_TRACE, cap + 1), jnp.int32),
        trace_eu=jnp.zeros((MAX_TRACE, cap + 1), jnp.int32),
        exch_bytes=jnp.int32(0),
        exch_log=jnp.zeros((MAX_TRACE,), jnp.int32),
    )


def dist2d_msbfs_engine_enqueue(state: Dist2DPipelineState,
                                roots) -> Dist2DPipelineState:
    """Append roots to the (replicated) pending queue."""
    return msbfs_engine_enqueue(state, roots)


def dist2d_msbfs_engine_idle(state: Dist2DPipelineState) -> bool:
    """True when no lane is active and no enqueued root is pending."""
    return msbfs_engine_idle(state)


def _dist2d_pipeline_body(g_loc: CSRGraph, base_r, chunk_base,
                          s: Dist2DPipelineState, mode: str, alpha: float,
                          beta: float, max_pos: int, probe_impl: str,
                          n: int, n_loc_r: int, chunk: int, n_orig: int,
                          compress: bool) -> Dist2DPipelineState:
    """One engine step, per-device view: refill idle lanes (replicated),
    expand the column frontier along "row", advance one layer on the
    local adjacency block, OR-fold the partials along "col", flush
    finished lanes. Mirrors ``dist_msbfs._dist_pipeline_body`` with the
    allreduce-OR exchange replaced by the two 2-D moves."""
    lanes = s.lane_qidx.shape[0]
    cap = s.queue.shape[0]
    w = s.frontier.shape[1]
    # one dtype for every dynamic_slice start (a bare 0 weak-types to
    # int64 under x64 — the u64 lane-word rung — and clashes with int32)
    col0 = jnp.zeros((), jnp.asarray(base_r).dtype)

    # --- refill: replicated claim logic, row-block seat writes -----------
    def do_refill(s: Dist2DPipelineState) -> Dist2DPipelineState:
        claim, cand, root = queue_claims(s.lane_qidx, s.next_root,
                                         s.queued, s.queue)
        onehot = claim[None, :] & (root[None, :]
                                   == jnp.arange(n, dtype=jnp.int32)[:, None])
        fresh = pack_lanes(onehot)                            # word[n, W]
        onehot_loc = jax.lax.dynamic_slice(onehot, (base_r, col0),
                                           (n_loc_r, lanes))
        fresh_loc = jax.lax.dynamic_slice(fresh, (base_r, col0), (n_loc_r, w))
        return s._replace(
            frontier=s.frontier | fresh_loc,
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
    # block degrees are PARTIAL (this column block's edges only), so the
    # edge counters merge over BOTH grid axes — at fixed i the j-sum
    # rebuilds the rows' global degrees, the i-sum totals the blocks —
    # while the vertex counter merges over "row" alone (row-block state
    # is replicated along "col"; both axes would count it pc times)
    active = s.lane_qidx < cap
    frontier_b = unpack_lanes(s.frontier, lanes)
    visited_b = unpack_lanes(s.visited, lanes)
    pe_f, pv_f, pe_u = lane_counters(g_loc, frontier_b, visited_b)
    e_f = jax.lax.psum(pe_f, ("row", "col"))
    v_f = jax.lax.psum(pv_f, "row")
    e_u = jax.lax.psum(pe_u, ("row", "col"))
    topdown = select_direction(mode, s.topdown, e_f, v_f, e_u, n_orig,
                               alpha, beta, lanes)

    live = active & (v_f > 0)
    td_sel = pack_lanes(topdown & live)                       # word[W]
    bu_sel = pack_lanes(~topdown & live)

    tr_row = jnp.clip(s.lane_layer, 0, MAX_TRACE - 1)
    tr_col = jnp.where(active, s.lane_qidx, cap)
    dir_vals = jnp.where(live, jnp.where(topdown, 0, 1),
                         -1).astype(jnp.int32)
    trace_dir = s.trace_dir.at[tr_row, tr_col].set(dir_vals)
    trace_vf = s.trace_vf.at[tr_row, tr_col].set(v_f)
    trace_ef = s.trace_ef.at[tr_row, tr_col].set(e_f)
    trace_eu = s.trace_eu.at[tr_row, tr_col].set(e_u)

    # --- expand: assemble this column block's frontier slice x_j ---------
    f_own = jax.lax.dynamic_slice(s.frontier, (chunk_base, col0), (chunk, w))
    x_j, bytes_expand = exchange_expand(f_own, "row", compress)

    # --- the SHARED packed step over the local adjacency block -----------
    new_partial = dispatch_packed_step(g_loc, x_j, s.visited, td_sel,
                                       bu_sel, mode, max_pos, probe_impl)

    # --- fold: complete the row block's new frontier along "col" ---------
    new_row, bytes_fold = exchange_reduce_or(new_partial, "col", compress)

    new_row_b = unpack_lanes(new_row, lanes)
    visited2 = s.visited | new_row
    visited2_b = visited_b | new_row_b
    lane_layer2 = s.lane_layer + active.astype(jnp.int32)
    depth2 = jnp.where(new_row_b, lane_layer2[None, :], s.depth)

    # finish = GLOBAL frontier drained OR per-lane layer cap
    v_next = jax.lax.psum(
        jnp.sum(new_row_b, axis=0, dtype=jnp.int32), "row")
    finished = active & ((v_next == 0) | (lane_layer2 >= MAX_TRACE))

    deg = g_loc.deg.astype(jnp.int32)[:, None]
    edges_l = jax.lax.psum(
        jnp.sum(jnp.where(visited2_b, deg, 0), axis=0,
                dtype=jnp.int32), ("row", "col"))
    fcol = jnp.where(finished, s.lane_qidx, cap)
    out_depth = s.out_depth.at[:, fcol].set(depth2)
    out_edges = s.out_edges.at[fcol].set(edges_l)
    out_layers = s.out_layers.at[fcol].set(lane_layer2)

    # mesh-total wire bytes this step: each "row" gather group (a grid
    # column) reports its expand total, each "col" group (a grid row) its
    # fold total — summing each along the OTHER axis covers the mesh once
    step_bytes = (jax.lax.psum(bytes_expand, "col")
                  + jax.lax.psum(bytes_fold, "row")).astype(jnp.int32)
    log_row = jnp.clip(s.sweep_layers, 0, MAX_TRACE - 1)
    exch_log = s.exch_log.at[log_row].add(step_bytes)

    clear = pack_lanes(finished)                              # word[W]
    return s._replace(
        frontier=new_row & ~clear,
        visited=visited2 & ~clear,
        depth=jnp.where(finished[None, :], -1, depth2),
        lane_layer=jnp.where(finished, 0, lane_layer2),
        lane_qidx=jnp.where(finished, cap, s.lane_qidx),
        topdown=topdown,
        sweep_layers=s.sweep_layers + 1,
        out_depth=out_depth, out_edges=out_edges, out_layers=out_layers,
        trace_dir=trace_dir, trace_vf=trace_vf, trace_ef=trace_ef,
        trace_eu=trace_eu,
        exch_bytes=s.exch_bytes + step_bytes,
        exch_log=exch_log,
    )


@partial(jax.jit, static_argnames=("mesh", "mode", "alpha", "beta",
                                   "max_pos", "probe_impl", "n", "n_loc_r",
                                   "chunk", "n_orig", "compress", "drain"))
def _dist2d_engine_run(row_ptr_s, colloc_s, srcloc_s, pos_s,
                       state: Dist2DPipelineState, *, mesh: Mesh, mode: str,
                       alpha: float, beta: float, max_pos: int,
                       probe_impl: str, n: int, n_loc_r: int, chunk: int,
                       n_orig: int, compress: bool,
                       drain: bool) -> Dist2DPipelineState:
    cap = state.queue.shape[0]

    def body(row_ptr, col_loc, src_loc, pos, s: Dist2DPipelineState):
        # strip the stacked device dims from the sharded leaves
        g_loc = CSRGraph(row_ptr=row_ptr[0], col_idx=col_loc[0],
                         src_idx=src_loc[0], slot_pos=pos[0])
        i = jax.lax.axis_index("row")
        j = jax.lax.axis_index("col")
        base_r = (i * n_loc_r).astype(jnp.int32)     # row block start
        chunk_base = (j * chunk).astype(jnp.int32)   # own chunk, in-block
        s = s._replace(frontier=s.frontier[0], visited=s.visited[0],
                       depth=s.depth[0], out_depth=s.out_depth[0])

        step = partial(_dist2d_pipeline_body, g_loc, base_r, chunk_base,
                       mode=mode, alpha=alpha, beta=beta, max_pos=max_pos,
                       probe_impl=probe_impl, n=n, n_loc_r=n_loc_r,
                       chunk=chunk, n_orig=n_orig, compress=compress)
        if drain:
            s = jax.lax.while_loop(
                lambda s: (s.next_root < s.queued)
                | jnp.any(s.lane_qidx < cap),
                lambda s: step(s), s)
        else:
            s = step(s)
        return s._replace(frontier=s.frontier[None], visited=s.visited[None],
                          depth=s.depth[None], out_depth=s.out_depth[None])

    spec_dev = P(("row", "col"))
    specs = _state_specs_2d()
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_dev, spec_dev, spec_dev, spec_dev, specs),
        out_specs=specs, check_vma=False,
    )(row_ptr_s, colloc_s, srcloc_s, pos_s, state)


def dist2d_msbfs_engine_step(dg: DistGraph2D, state: Dist2DPipelineState,
                             mesh: Mesh, mode: str = "hybrid",
                             alpha: float = ALPHA_DEFAULT,
                             beta: float = BETA_DEFAULT, max_pos: int = 8,
                             probe_impl: str = "xla",
                             compress: bool = False) -> Dist2DPipelineState:
    """Advance the 2-D engine by one traversal layer (streaming API)."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    _check_partition_2d(dg, mesh)
    return _dist2d_engine_run(
        dg.row_ptr, dg.col_loc, dg.src_loc, dg.slot_pos, state, mesh=mesh,
        mode=mode, alpha=alpha, beta=beta, max_pos=max_pos,
        probe_impl=probe_impl, n=dg.n, n_loc_r=dg.n_loc_r, chunk=dg.chunk,
        n_orig=dg.n_orig, compress=compress, drain=False)


def dist2d_msbfs_engine_drain(dg: DistGraph2D, state: Dist2DPipelineState,
                              mesh: Mesh, mode: str = "hybrid",
                              alpha: float = ALPHA_DEFAULT,
                              beta: float = BETA_DEFAULT, max_pos: int = 8,
                              probe_impl: str = "xla",
                              compress: bool = False) -> Dist2DPipelineState:
    """Step the 2-D engine until every enqueued root is answered."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    _check_partition_2d(dg, mesh)
    return _dist2d_engine_run(
        dg.row_ptr, dg.col_loc, dg.src_loc, dg.slot_pos, state, mesh=mesh,
        mode=mode, alpha=alpha, beta=beta, max_pos=max_pos,
        probe_impl=probe_impl, n=dg.n, n_loc_r=dg.n_loc_r, chunk=dg.chunk,
        n_orig=dg.n_orig, compress=compress, drain=True)


@partial(jax.jit, static_argnames=("mesh", "n", "n_loc_r", "num_roots",
                                   "lane_chunk"))
def _derive_parents_2d(row_ptr_s, colgid_s, srcloc_s, depth_full, roots, *,
                       mesh: Mesh, n: int, n_loc_r: int, num_roots: int,
                       lane_chunk: int = 16):
    """2-D analog of ``dist_msbfs._derive_parents_dist``: each device
    scans its adjacency block for the min-id neighbour one level up
    (GLOBAL ids via ``col_gid``), grid rows pmin their column partials,
    then the row blocks are gathered. The min-id winner over a row's full
    adjacency is the min over its column-block partials, so parents match
    the host derivation exactly."""
    def body(row_ptr, col, src_loc, depth_full, roots):
        row_ptr, col, src_loc = row_ptr[0], col[0], src_loc[0]
        base_r = (jax.lax.axis_index("row") * n_loc_r).astype(jnp.int32)
        depth_loc = jax.lax.dynamic_slice(
            depth_full, (base_r, jnp.zeros((), base_r.dtype)),
            (n_loc_r, num_roots))
        colc = jnp.clip(col, 0, n - 1)
        valid = (col < n)[:, None]       # pad slots carry the sentinel n
        outs = []
        for lo in range(0, num_roots, lane_chunk):
            d_full = depth_full[:, lo:lo + lane_chunk]
            d_loc = depth_loc[:, lo:lo + lane_chunk]
            ok = valid & (d_full[colc] >= 0) & (d_full[colc] + 1
                                                == d_loc[src_loc])
            cand = jnp.where(ok, col[:, None], n).astype(jnp.int32)
            best = jnp.full((n_loc_r, d_loc.shape[1]), n,
                            jnp.int32).at[src_loc].min(cand)
            outs.append(best)
        parent_loc = jax.lax.pmin(jnp.concatenate(outs, axis=1), "col")
        parent_loc = jnp.where(parent_loc < n, parent_loc, -1)
        # seat roots owned by this row block; rows outside are pushed past
        # n_loc_r so mode="drop" discards them
        lane = jnp.arange(num_roots, dtype=jnp.int32)
        own = (roots >= base_r) & (roots < base_r + n_loc_r)
        lrow = jnp.where(own, roots - base_r, n_loc_r)
        parent_loc = parent_loc.at[lrow, lane].set(
            roots.astype(jnp.int32), mode="drop")
        return jax.lax.all_gather(parent_loc, "row", tiled=True)

    spec_dev = P(("row", "col"))
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_dev, spec_dev, spec_dev, P(), P()),
        out_specs=P(), check_vma=False,
    )(row_ptr_s, colgid_s, srcloc_s, depth_full, roots)


def dist2d_msbfs_engine_result(dg: DistGraph2D, state: Dist2DPipelineState,
                               mesh: Mesh, trim: bool = True,
                               derive_parents: bool = True) -> MSBFSResult:
    """Assemble an ``MSBFSResult`` over the answered queue slots (row
    blocks are contiguous, so the stacked ``out_depth`` reshapes straight
    into global row order). Same conventions as the other engines."""
    _check_partition_2d(dg, mesh)
    r = int(state.queued)
    cap = state.capacity
    depth = jnp.reshape(state.out_depth, (dg.n, cap + 1))[:, :r]
    roots = state.queue[:r]
    if r and derive_parents:
        parent = _derive_parents_2d(
            dg.row_ptr, dg.col_gid, dg.src_loc, depth,
            roots.astype(jnp.int32), mesh=mesh, n=dg.n,
            n_loc_r=dg.n_loc_r, num_roots=r)
    else:
        parent = jnp.zeros((dg.n, 0), jnp.int32)
    lim = dg.n_orig if trim else dg.n
    return MSBFSResult(
        parent=parent[:lim], depth=depth[:lim],
        num_layers=state.out_layers[:r],
        edges_traversed=state.out_edges[:r],
        trace_dir=state.trace_dir[:, :r], trace_vf=state.trace_vf[:, :r],
        trace_ef=state.trace_ef[:, :r], trace_eu=state.trace_eu[:, :r])


def dist2d_msbfs(dg: DistGraph2D, roots, mesh: Mesh, mode: str = "hybrid",
                 alpha: float = ALPHA_DEFAULT, beta: float = BETA_DEFAULT,
                 max_pos: int = 8, probe_impl: str = "xla",
                 lanes: int | None = None, compress: bool = False,
                 derive_parents: bool = True, recorder=None) -> MSBFSResult:
    """Answer an arbitrary number of roots with ONE 2-D engine sweep.

    ``compress=True`` ships both per-layer exchanges through the sparse
    frontier-word codec whenever the gather group is below the density
    threshold (wire bytes then track the frontier population — results
    are bit-identical either way). ``lanes=None`` sizes the pool
    adaptively, as in the other engines. ``recorder`` (a ``repro.obs
    .SweepRecorder``) steps layer-by-layer recording a ``LayerRecord``
    each — including this engine's per-layer ``exch_bytes`` delta —
    bit-identical to the fused drain; None touches nothing in obs."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    roots = jnp.asarray(roots, jnp.int32).reshape(-1)
    num_roots = roots.shape[0]
    if num_roots < 1:
        raise ValueError("need at least one root")
    if not lanes:
        m_total = int(np.asarray(dg.deg, dtype=np.int64).sum())
        lanes = adaptive_lane_pool(num_roots, dg.n_orig, m_total)
    lanes = max(1, min(lanes, LANE_WORD_BITS * num_lane_words(num_roots)))
    state = dist2d_msbfs_engine_init(dg, mesh, capacity=num_roots,
                                     lanes=lanes)
    state = dist2d_msbfs_engine_enqueue(state, roots)
    if recorder is None:
        state = dist2d_msbfs_engine_drain(dg, state, mesh, mode, alpha,
                                          beta, max_pos, probe_impl,
                                          compress)
    else:
        from repro.obs.sweeplog import drive_recorded
        state = drive_recorded(
            recorder, state,
            lambda s: dist2d_msbfs_engine_step(dg, s, mesh, mode, alpha,
                                               beta, max_pos, probe_impl,
                                               compress),
            dist2d_msbfs_engine_idle, kind="bfs",
            exch_format="compressed" if compress else "dense")
    return dist2d_msbfs_engine_result(dg, state, mesh,
                                      derive_parents=derive_parents)
