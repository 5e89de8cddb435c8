"""Distributed hybrid BFS via shard_map — the multi-pod form of the paper.

1-D vertex partition over *all* mesh axes flattened (pod x data x model):
device d owns a contiguous vertex slice and the CSR rows of its vertices.
Per layer:

  bottom-up  — all_gather the packed frontier bitmap (n/32 uint32 words —
               the bitmap makes the exchange cheap, the same reason the
               paper packs bits), then probe *local* vertices; all writes
               are owner-local, no scatter traffic.
  top-down   — scan local rows of local frontier vertices, emit parent
               candidates over the full vertex range, min-reduce across
               devices (pmin), owners keep their slice. No visited-bitmap
               exchange is needed: owners discard candidates for already
               visited vertices locally.
  counters   — psum of local partials; the direction decision is computed
               redundantly on every device (replicated scalars).

Determinism matches the single-device path: min parent id wins everywhere,
so dist_bfs == hybrid.bfs == numpy oracle exactly.
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

from repro.core import bitmap
from repro.core.csr import CSRGraph

MAX_LAYERS = 64


class DistBFSResult(NamedTuple):
    """Single-root distributed BFS result, sentinel conventions aligned
    with ``MSBFSResult``: dead/unreached vertices hold -1 in BOTH parent
    and depth, ``parent[root] == root`` and ``depth[root] == 0``. Arrays
    are trimmed to the original (pre-padding) vertex count."""
    parent: jnp.ndarray        # int32[n_orig], -1 unreached
    depth: jnp.ndarray         # int32[n_orig], -1 unreached
    num_layers: jnp.ndarray    # int32 scalar


@dataclass(frozen=True)
class DistGraph:
    """Host-partitioned CSR: stacked per-device blocks (leading dim = ndev)."""
    row_ptr: jnp.ndarray   # int32[ndev, n_loc+1] — local offsets into col_idx
    col_idx: jnp.ndarray   # int32[ndev, m_loc]   — global neighbour ids
    src_loc: jnp.ndarray   # int32[ndev, m_loc]   — local row of each edge
    deg: jnp.ndarray       # int32[ndev, n_loc]
    n: int                 # padded global vertex count (multiple of ndev*32)
    n_orig: int            # original vertex count
    m_loc: int             # uniform per-device edge-slab size (padded)


def partition_graph(g: CSRGraph, ndev: int) -> DistGraph:
    """Host-side 1-D partition with uniform padding across devices."""
    rp = np.asarray(g.row_ptr)
    ci = np.asarray(g.col_idx)
    n_orig = g.n
    block = -(-n_orig // (ndev * 32)) * 32          # n_loc multiple of 32
    n = block * ndev
    deg_full = np.zeros(n, np.int32)
    deg_full[:n_orig] = np.diff(rp)
    deg_l = deg_full.reshape(ndev, block)

    row_ptr_l = np.zeros((ndev, block + 1), np.int32)
    np.cumsum(deg_l, axis=1, out=row_ptr_l[:, 1:])

    slabs, srcs = [], []
    for d in range(ndev):
        lo_v, hi_v = d * block, min((d + 1) * block, n_orig)
        if lo_v < n_orig:
            slab = ci[rp[lo_v]:rp[hi_v]]
            src = np.repeat(np.arange(hi_v - lo_v, dtype=np.int32),
                            np.diff(rp[lo_v:hi_v + 1]))
        else:
            slab = src = np.zeros(0, np.int32)
        slabs.append(slab)
        srcs.append(src)
    m_loc = max(1, max(len(s) for s in slabs))
    col_l = np.full((ndev, m_loc), n, np.int32)      # sentinel pad (id = n)
    src_l = np.zeros((ndev, m_loc), np.int32)
    for d in range(ndev):
        col_l[d, :len(slabs[d])] = slabs[d]
        src_l[d, :len(srcs[d])] = srcs[d]
    # Padded edge slots: src_loc points at a vertex whose row is full, so
    # pos_e >= deg never activates them; col sentinel n fails bitmap tests.
    return DistGraph(row_ptr=jnp.asarray(row_ptr_l),
                     col_idx=jnp.asarray(col_l), src_loc=jnp.asarray(src_l),
                     deg=jnp.asarray(deg_l), n=n, n_orig=n_orig, m_loc=m_loc)


def _flat_axis_index(axes, sizes):
    # sizes come from the (static) mesh shape
    idx = jnp.int32(0)
    for name in axes:
        idx = idx * sizes[name] + jax.lax.axis_index(name)
    return idx


@partial(jax.jit,
         static_argnames=("mesh", "mode", "alpha", "beta", "max_pos",
                          "n", "n_loc", "m_loc", "n_orig", "probe_impl"))
def _dist_bfs_impl(row_ptr_s, col_s, srcloc_s, deg_s, root, *, mesh: Mesh,
                   mode: str, alpha: float, beta: float, max_pos: int,
                   n: int, n_loc: int, m_loc: int, n_orig: int,
                   probe_impl: str = "xla"):
    axes = tuple(mesh.axis_names)

    def body(row_ptr, col, src_loc, deg, root):
        row_ptr, col, src_loc, deg = (row_ptr[0], col[0], src_loc[0], deg[0])
        base = _flat_axis_index(axes, dict(mesh.shape)) * n_loc
        local_ids = base + jnp.arange(n_loc, dtype=jnp.int32)

        frontier = local_ids == root
        visited = frontier
        parent = jnp.where(frontier, root, -1).astype(jnp.int32)
        depth = jnp.where(frontier, 0, -1).astype(jnp.int32)
        starts = row_ptr[:-1]

        def cond_fn(state):
            return state[6] & (state[5] < MAX_LAYERS)

        def layer_fn(state):
            frontier, visited, parent, depth, topdown, layer, _ = state
            deg32 = deg.astype(jnp.int32)
            e_f = jax.lax.psum(jnp.sum(jnp.where(frontier, deg32, 0)), axes)
            v_f = jax.lax.psum(jnp.sum(frontier, dtype=jnp.int32), axes)
            e_u = jax.lax.psum(jnp.sum(jnp.where(visited, 0, deg32)), axes)
            if mode == "topdown":
                td = jnp.bool_(True)
            elif mode == "bottomup":
                td = jnp.bool_(False)
            else:
                go_bu = topdown & (e_f.astype(jnp.float32)
                                   > e_u.astype(jnp.float32) / alpha)
                go_td = (~topdown) & (v_f.astype(jnp.float32)
                                      < jnp.float32(n) / beta)
                td = jnp.where(go_bu, False, jnp.where(go_td, True, topdown))

            def run_td(args):
                frontier, visited, parent = args
                # col == n marks padded edge slots — exclude them, else a
                # frontier vertex at local row 0 scatters through the pad.
                act = frontier[src_loc] & (col < n)
                src_gid = (base + src_loc).astype(jnp.int32)
                cand = jnp.where(act, src_gid, n).astype(jnp.int32)
                full = jnp.full((n,), n, jnp.int32).at[
                    jnp.clip(col, 0, n - 1)].min(cand)
                full = jax.lax.pmin(full, axes)
                mine = jax.lax.dynamic_slice(full, (base,), (n_loc,))
                new = (mine < n) & ~visited
                parent = jnp.where(new, mine, parent)
                return new, visited | new, parent

            def run_bu(args):
                frontier, visited, parent = args
                fw_global = jax.lax.all_gather(bitmap.pack(frontier), axes,
                                               tiled=True)
                unv = ~visited
                if probe_impl == "pallas":
                    # the paper's probe as the Pallas kernel over the LOCAL
                    # edge slab (VMEM-resident per DESIGN §3.2)
                    from repro.kernels import (bottom_up_probe_pallas,
                                               interpret_default)
                    found_i, parent = bottom_up_probe_pallas(
                        starts, deg, unv, parent, col, fw_global,
                        max_pos=max_pos, interpret=interpret_default())
                    found = found_i != 0
                else:
                    found = jnp.zeros_like(unv)
                    for pos in range(max_pos):      # the paper's probe loop
                        live = unv & (~found) & (pos < deg)
                        vadj = col[jnp.clip(starts + pos, 0, m_loc - 1)]
                        hit = live & bitmap.test(fw_global, vadj)
                        parent = jnp.where(hit, vadj, parent)
                        found = found | hit
                # fallback: local edge-parallel scan beyond max_pos
                e = jnp.arange(m_loc, dtype=jnp.int32)
                pos_e = e - row_ptr[src_loc]
                rem = unv & (~found) & (deg > max_pos)
                act = rem[src_loc] & (pos_e >= max_pos) & bitmap.test(
                    fw_global, col)
                e_min = jnp.full((n_loc,), m_loc, jnp.int32).at[src_loc].min(
                    jnp.where(act, e, m_loc))
                hit2 = e_min < m_loc
                parent = jnp.where(
                    hit2, col[jnp.clip(e_min, 0, m_loc - 1)], parent)
                new = (found | hit2) & unv
                return new, visited | new, parent

            frontier, visited, parent = jax.lax.cond(
                td, run_td, run_bu, (frontier, visited, parent))
            depth = jnp.where(frontier, layer + 1, depth)
            nonempty = jax.lax.psum(jnp.sum(frontier, dtype=jnp.int32),
                                    axes) > 0
            return frontier, visited, parent, depth, td, layer + 1, nonempty

        state = (frontier, visited, parent, depth,
                 jnp.bool_(mode != "bottomup"), jnp.int32(0),
                 jnp.bool_(True))
        state = jax.lax.while_loop(cond_fn, layer_fn, state)
        parent, depth, layers = state[2], state[3], state[5]
        parent_full = jax.lax.all_gather(parent, axes, tiled=True)
        depth_full = jax.lax.all_gather(depth, axes, tiled=True)
        return parent_full, depth_full, layers

    spec_dev = P(axes)   # leading dim sharded over all mesh axes jointly
    # out_specs=P(): outputs are replicated (all_gather / psum products);
    # the static VMA check can't see through the while_loop, so disable it.
    parent_full, depth_full, layers = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_dev, spec_dev, spec_dev, spec_dev, P()),
        out_specs=(P(), P(), P()), check_vma=False,
    )(row_ptr_s, col_s, srcloc_s, deg_s, root)
    return parent_full[:n_orig], depth_full[:n_orig], layers


def dist_bfs(dg: DistGraph, root, mesh: Mesh, mode: str = "hybrid",
             alpha: float = 14.0, beta: float = 24.0, max_pos: int = 8,
             probe_impl: str = "xla") -> DistBFSResult:
    """Run distributed BFS; returns ``DistBFSResult(parent, depth,
    num_layers)`` with the serial/MS engines' -1 dead-vertex sentinel."""
    ndev = int(np.prod(mesh.devices.shape))
    parent, depth, layers = _dist_bfs_impl(
        dg.row_ptr, dg.col_idx, dg.src_loc, dg.deg, jnp.int32(root),
        mesh=mesh, mode=mode, alpha=alpha, beta=beta, max_pos=max_pos,
        n=dg.n, n_loc=dg.n // ndev, m_loc=dg.m_loc, n_orig=dg.n_orig,
        probe_impl=probe_impl)
    return DistBFSResult(parent=parent, depth=depth, num_layers=layers)
