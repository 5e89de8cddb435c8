"""Graph500 experimental harness (paper §6).

Runs the benchmark protocol: generate a Kronecker graph, pick 64 random
roots (degree>0, as the reference code does), run BFS per root with the
compiled executable, collect per-root wall time and TEPS, and report the
harmonic mean (the paper's headline number) plus min/max/mean.

``batched=True`` answers ALL roots — ``num_roots`` is no longer clamped to
64 — in ONE invocation of the pipelined MS-BFS engine
(``repro.core.msbfs.msbfs_pipelined``): roots beyond the ``lanes`` bit-lane
pool wait in the engine's pending queue and refill lanes the moment a
traversal finishes, so there is no per-64-batch barrier. Per-root wall time
is the shared sweep time, and ``aggregate_teps`` (total edges over total
wall time) is the number to compare against the serial loop; because
``times`` holds the single pipelined sweep time, the refill overlap is
priced in automatically — idle-lane time never inflates the denominator
the way summing per-batch sweep times would.

TEPS counts the *undirected* edges of the traversed component
(sum of degrees of reached vertices / 2), per the Graph500 spec.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.csr import CSRGraph, to_numpy_adj
from repro.core.hybrid import bfs
from repro.core.msbfs import (MAX_LANES, MSBFSResult, adaptive_lane_pool,
                              msbfs_pipelined)
from repro.graph.generator import rmat_graph, sample_roots
from repro.graph.validate import validate_bfs_tree

# serial mode name -> MS-BFS controller mode
_BATCHED_MODE = {"hybrid": "hybrid", "hybrid_nosimd": "hybrid",
                 "topdown": "topdown", "bottomup_simd": "bottomup",
                 "bottomup_nosimd": "bottomup"}


@dataclass
class Graph500Result:
    scale: int
    edgefactor: int
    mode: str
    batched: bool = False
    lanes: int = 0               # bit-lane pool size of the batched engine
    ndev: int = 1                # devices the batched engine was sharded over
    teps: list[float] = field(default_factory=list)
    times: list[float] = field(default_factory=list)
    traversed: list[int] = field(default_factory=list)
    roots: np.ndarray | None = None     # the sampled search keys
    # batched runs: the timed sweep's full result (parents, depths), so a
    # caller can validate any root of the very sweep that was timed
    sweep: MSBFSResult | None = None

    @property
    def harmonic_mean_teps(self) -> float:
        t = np.asarray([x for x in self.teps if x > 0])
        return float(len(t) / np.sum(1.0 / t)) if len(t) else 0.0

    @property
    def aggregate_teps(self) -> float:
        """Total traversed edges over total wall time — the serving-throughput
        number; for batched runs ``times`` holds the single sweep time."""
        total_t = float(np.sum(self.times))
        return float(np.sum(self.traversed)) / total_t if total_t > 0 else 0.0

    def summary(self) -> dict:
        t = np.asarray(self.teps)
        return dict(scale=self.scale, edgefactor=self.edgefactor,
                    mode=self.mode, batched=self.batched, lanes=self.lanes,
                    ndev=self.ndev, nroots=len(self.traversed),
                    harmonic_mean_teps=self.harmonic_mean_teps,
                    aggregate_teps=self.aggregate_teps,
                    mean_teps=float(t.mean()) if len(t) else 0.0,
                    max_teps=float(t.max()) if len(t) else 0.0,
                    min_teps=float(t.min()) if len(t) else 0.0,
                    mean_time=float(np.mean(self.times)) if self.times else 0.0)


def run_graph500(scale: int, edgefactor: int, mode: str = "hybrid",
                 num_roots: int = 64, seed: int = 0, validate: bool = False,
                 alpha: float = 14.0, beta: float = 24.0, max_pos: int = 8,
                 probe_impl: str = "xla", warmup: bool = True,
                 skip_empty_fallback: bool = True, td_impl: str = "edge",
                 graph: CSRGraph | None = None,
                 batched: bool = False,
                 lanes: int | None = MAX_LANES,
                 ndev: int = 1, mesh=None) -> Graph500Result:
    g = graph if graph is not None else rmat_graph(scale, edgefactor, seed)
    roots = sample_roots(g, num_roots, seed=seed + 1)
    if batched:
        if td_impl != "edge" or not skip_empty_fallback:
            raise ValueError(
                "batched=True does not support td_impl/skip_empty_fallback "
                "(the MS-BFS sweep has its own step formulations)")
        return _run_batched(g, roots, scale, edgefactor, mode, alpha, beta,
                            max_pos, probe_impl, warmup, validate, lanes,
                            ndev, mesh)
    if ndev > 1 or mesh is not None:
        raise ValueError("ndev > 1 requires batched=True (the sharded "
                         "engine is the MS-BFS one)")
    res = Graph500Result(scale=scale, edgefactor=edgefactor, mode=mode,
                         roots=roots)

    run = lambda r: bfs(g, r, mode, alpha, beta, max_pos, probe_impl,
                        skip_empty_fallback, td_impl)
    if warmup:
        jax.block_until_ready(run(int(roots[0])))  # compile once

    rp, ci = (to_numpy_adj(g) if validate else (None, None))
    for r in roots:
        t0 = time.perf_counter()
        out = run(int(r))
        jax.block_until_ready(out.parent)
        dt = time.perf_counter() - t0
        edges = int(out.edges_traversed) // 2
        res.times.append(dt)
        res.traversed.append(edges)
        res.teps.append(edges / dt if dt > 0 else 0.0)
        if validate:
            validate_bfs_tree(rp, ci, np.asarray(out.parent), int(r))
    return res


def _run_batched(g: CSRGraph, roots: np.ndarray, scale: int, edgefactor: int,
                 mode: str, alpha: float, beta: float, max_pos: int,
                 probe_impl: str, warmup: bool, validate: bool,
                 lanes: int | None, ndev: int = 1,
                 mesh=None) -> Graph500Result:
    """ALL roots in one pipelined MS-BFS engine invocation.

    Roots stream through a pool of ``lanes`` bit-lanes: a finished lane is
    refilled from the pending queue on the next layer, so R > lanes costs
    extra traversal layers but no batch barrier and no extra compilation.
    ``lanes=None`` (or 0) sizes the pool adaptively from the root count
    and the graph's degree stats (``adaptive_lane_pool``).

    ``ndev > 1`` (or an explicit ``mesh``) runs the SHARDED engine
    (``repro.core.dist_msbfs``): the graph is 1-D partitioned and each
    device traverses its row block, frontiers OR-merged per layer. Needs
    that many jax devices (CI forces host devices via XLA_FLAGS).

    The result's ``mode`` records the MS-BFS controller actually executed
    (there is no packed nosimd variant — comparing a serial ``*_nosimd``
    run against a batched one would cross the paper's SIMD axis silently).
    """
    msbfs_mode = _BATCHED_MODE[mode]
    if not lanes:
        lanes = adaptive_lane_pool(len(roots), g.n, g.m)
    batch = jnp.asarray(roots, dtype=jnp.int32)
    if ndev > 1 or mesh is not None:
        from repro.core.dist_msbfs import (dist_msbfs, host_mesh,
                                           partition_graph)
        if mesh is None:
            mesh = host_mesh(ndev)
        else:
            ndev = int(np.prod(mesh.devices.shape))
        dg = partition_graph(g, ndev)
        run = lambda: dist_msbfs(dg, batch, mesh, msbfs_mode, alpha, beta,
                                 max_pos, probe_impl, lanes=lanes)
    else:
        run = lambda: msbfs_pipelined(g, batch, msbfs_mode, alpha, beta,
                                      max_pos, probe_impl, lanes)
    res = Graph500Result(scale=scale, edgefactor=edgefactor,
                         mode=msbfs_mode, batched=True, lanes=lanes,
                         ndev=ndev, roots=roots)
    rp_ci = to_numpy_adj(g) if validate else None
    if warmup:
        jax.block_until_ready(run())  # compile once per (shape, R, lanes)
    t0 = time.perf_counter()
    out = run()
    jax.block_until_ready(out.parent)
    dt = time.perf_counter() - t0
    res.sweep = out
    edges = np.asarray(out.edges_traversed) // 2
    res.times.append(dt)
    res.traversed.extend(int(e) for e in edges)
    # per-root TEPS against the shared sweep time (the engine answers every
    # query within the one pipelined sweep); aggregate_teps is the headline
    res.teps.extend(float(e) / dt if dt > 0 else 0.0 for e in edges)
    if validate:
        parent = np.asarray(out.parent)
        for r_i, root in enumerate(roots):
            validate_bfs_tree(rp_ci[0], rp_ci[1], parent[:, r_i], int(root))
    return res
