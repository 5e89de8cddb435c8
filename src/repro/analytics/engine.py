"""Engine facade for the analytics subsystem.

Every analytics workload (components, closeness, k-hop, diameter bounds)
reduces to the same primitive: *one pipelined MS-BFS sweep over a batch of
roots, returning per-lane depths*. ``LaneEngine`` is that primitive with
the host/distributed choice and the lane-pool sizing folded in:

* ``ndev <= 1`` — ``repro.core.msbfs.msbfs_pipelined`` on the full graph;
* ``ndev > 1`` (or an explicit ``mesh``) — ``repro.core.dist_msbfs`` over
  a 1-D partition, results trimmed back to the original vertex count, so
  callers see identical shapes either way (the engines are bit-identical
  per ``tests/test_dist_msbfs.py``);
* ``grid=(pr, pc)`` — ``repro.core.dist2d`` over the 2-D adjacency
  partition (``compress=True`` ships the per-layer exchanges through the
  sparse frontier-word codec); bit-identical again, per
  ``tests/test_dist2d.py``;
* ``lanes=None`` — adaptive pool sizing per sweep
  (``packed.adaptive_lane_pool``), exactly the ``lanes=0`` surface of the
  graph500 / serve_bfs harnesses.

The graph is partitioned ONCE at construction; repeated sweeps (closeness
chunks, component batches, diameter re-sweeps) reuse the partition and the
compiled engine executables (one compile per distinct root-batch size —
the algorithms pad their batches to a fixed width for exactly this
reason).

Built from a ``WeightedCSRGraph`` the engine additionally serves
*weighted* sweeps: ``sssp_sweep`` runs the delta-stepping tropical-lane
engine (``repro.traversal.sssp``) over the same graph, and the weighted
analytics workloads (``SSSPQuery`` / ``WeightedClosenessQuery``) dispatch
through it. Boolean sweeps on a weighted engine simply ignore the
weights (``WeightedCSRGraph.csr`` is the identical CSR).
"""
from __future__ import annotations

import numpy as np

from repro.core.csr import CSRGraph, WeightedCSRGraph, with_slot_pos
from repro.core.hybrid import ALPHA_DEFAULT, BETA_DEFAULT
from repro.core.msbfs import MSBFSResult, msbfs_pipelined
from repro.core.packed import MODES, adaptive_lane_pool

__all__ = ["LaneEngine", "as_engine", "pad_roots"]


def pad_roots(roots: np.ndarray, width: int) -> np.ndarray:
    """Pad a root batch to the fixed sweep ``width`` by repeating the
    first root — every sweep then reuses ONE compiled engine executable;
    callers discard the padded lanes' results. Shared by the analytics
    batch loops (components / closeness / diameter)."""
    roots = np.asarray(roots, np.int32)
    if roots.size > width:
        raise ValueError(
            f"{roots.size} roots exceed the fixed sweep width {width} — "
            f"an over-width batch would silently recompile per size")
    if roots.size == width:
        return roots
    return np.concatenate(
        [roots, np.full(width - roots.size, roots[0], np.int32)])


class LaneEngine:
    """Host- or mesh-backed MS-BFS sweep runner shared by all analytics."""

    def __init__(self, g: CSRGraph | WeightedCSRGraph, *, ndev: int = 1,
                 mesh=None, grid: tuple[int, int] | None = None,
                 compress: bool = False, lanes: int | None = None,
                 mode: str = "hybrid", alpha: float = ALPHA_DEFAULT,
                 beta: float = BETA_DEFAULT, max_pos: int = 8,
                 probe_impl: str = "xla", telemetry=None):
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
        # a repro.obs.Telemetry bundle; None (the default) keeps every
        # sweep on the recorder-off fused-drain path
        self.telemetry = telemetry
        # the slot-to-row-position table, built once per graph here so no
        # engine call rebuilds it
        g = with_slot_pos(g)
        self.wg = g if isinstance(g, WeightedCSRGraph) else None
        self.g = g.csr if self.wg is not None else g
        g = self.g
        self.lanes = lanes
        self.mode = mode
        self.alpha = alpha
        self.beta = beta
        self.max_pos = max_pos
        self.probe_impl = probe_impl
        self.mesh = mesh
        self.grid = tuple(grid) if grid is not None else None
        self.compress = compress
        self.dg = self.dg2 = None
        self.dwg = self.dwg2 = None
        if self.grid is not None:
            # 2-D adjacency partition on a (pr, pc) grid mesh
            if mesh is not None:
                raise ValueError(
                    "pass grid=(pr, pc) OR a prebuilt mesh, not both — the "
                    "2-D engine builds its own ('row', 'col') grid mesh")
            from repro.core.dist2d import mesh2d, partition_graph_2d
            pr, pc = self.grid
            self.ndev = pr * pc
            self.mesh = mesh2d(pr, pc)
            self.dg2 = partition_graph_2d(g, pr, pc)
            if self.wg is not None:
                from repro.core.dist_sssp import partition_weighted_graph_2d
                self.dwg2 = partition_weighted_graph_2d(self.wg, pr, pc)
            return
        if compress:
            raise ValueError(
                "compress=True is the 2-D exchange knob — it needs "
                "grid=(pr, pc); the 1-D engine's allreduce is always dense")
        if mesh is not None:
            ndev = int(np.prod(mesh.devices.shape))
        self.ndev = max(int(ndev), 1)
        # an EXPLICIT mesh always takes the dist path, even at one device
        # (the caller asked for it; silently swapping in the host engine
        # would leave the requested code path unexercised)
        if self.ndev > 1 or mesh is not None:
            from repro.core.dist_msbfs import host_mesh, partition_graph
            if self.mesh is None:
                self.mesh = host_mesh(self.ndev)
            self.dg = partition_graph(g, self.ndev)
            if self.wg is not None:
                from repro.core.dist_sssp import partition_weighted_graph
                self.dwg = partition_weighted_graph(self.wg, self.ndev)

    @property
    def n(self) -> int:
        return self.g.n

    @property
    def m(self) -> int:
        return self.g.m

    def lanes_for(self, num_roots: int) -> int:
        """Lane-pool width for a sweep of ``num_roots`` — the pinned value
        or the adaptive sizing rule."""
        if self.lanes:
            return self.lanes
        return adaptive_lane_pool(num_roots, self.n, self.m)

    def _recorder(self, engine_name: str, **meta):
        """A fresh per-sweep ``SweepRecorder`` from the telemetry bundle
        (None when telemetry is absent or sweep recording is off — the
        drivers then take their fused-drain fast path)."""
        if self.telemetry is None:
            return None
        return self.telemetry.recorder(engine_name, ndev=self.ndev, **meta)

    def sweep(self, roots, derive_parents: bool = False) -> MSBFSResult:
        """One pipelined engine sweep; ``depth`` is [n, R] with the
        original vertex count regardless of ndev. By default ``parent``
        is zero-width: every analytics workload reads depths only, and
        skipping the parent derivation saves an O(m) scatter-min pass per
        lane chunk on every sweep — pass ``derive_parents=True`` to get
        Graph500-grade parents."""
        roots = np.asarray(roots, np.int32).reshape(-1)
        if roots.size < 1:
            raise ValueError("need at least one root")
        lanes = self.lanes_for(roots.size)
        if self.dg2 is not None:
            from repro.core.dist2d import dist2d_msbfs
            return dist2d_msbfs(self.dg2, roots, self.mesh, self.mode,
                                self.alpha, self.beta, self.max_pos,
                                self.probe_impl, lanes=lanes,
                                compress=self.compress,
                                derive_parents=derive_parents,
                                recorder=self._recorder("dist2d"))
        if self.dg is not None:
            from repro.core.dist_msbfs import dist_msbfs
            return dist_msbfs(self.dg, roots, self.mesh, self.mode,
                              self.alpha, self.beta, self.max_pos,
                              self.probe_impl, lanes=lanes,
                              derive_parents=derive_parents,
                              recorder=self._recorder("dist_msbfs"))
        return msbfs_pipelined(self.g, roots, self.mode, self.alpha,
                               self.beta, self.max_pos, self.probe_impl,
                               lanes, derive_parents=derive_parents,
                               recorder=self._recorder("msbfs"))

    @property
    def weighted(self) -> bool:
        return self.wg is not None

    def sssp_lanes_for(self, num_roots: int) -> int:
        """Dense-lane pool width for a weighted sweep: dense float32
        lanes cost ~32x a packed bit lane, so a pinned bit-pool width is
        NOT taken at face value — the tropical engine's own default caps
        it (same rule as the serving loop); call ``sssp_pipelined``
        directly to run a wider dense pool deliberately."""
        from repro.traversal.sssp import DEFAULT_LANES
        cap = min(self.lanes, DEFAULT_LANES) if self.lanes else DEFAULT_LANES
        return max(1, min(num_roots, cap))

    def sssp_sweep(self, roots, delta=None, derive_parents: bool = False):
        """One pipelined delta-stepping sweep over the engine's weighted
        graph; returns ``repro.traversal.sssp.SSSPResult`` (``dist`` is
        [n, R] float32 with the original vertex count, inf unreached).
        Requires the engine to have been built from a
        ``WeightedCSRGraph``. Dispatches on the engine's partition
        exactly like ``sweep``: host lanes at ndev 1, the 1-D sharded
        engine on a mesh, the 2-D grid engine under ``grid=(pr, pc)``
        (``compress=True`` ships the per-step value exchanges through the
        sparse codec) — all bit-identical per ``tests/test_dist_sssp.py``.
        ``delta`` is a scalar width or a per-lane tuple (the engines'
        static knob; None picks the graph default).
        ``derive_parents=True`` adds ``parent``, an SSSP tree per source
        (Graph 500 kernel 3); the host engine alone derives it."""
        if self.wg is None:
            raise TypeError(
                "weighted sweep on an unweighted engine — build the "
                "LaneEngine from a WeightedCSRGraph (e.g. "
                "graph.generator.rmat_weighted_graph) to serve "
                "sssp/weighted-closeness queries")
        roots = np.asarray(roots, np.int32).reshape(-1)
        if roots.size < 1:
            raise ValueError("need at least one source")
        if derive_parents and (self.dwg2 is not None
                               or self.dwg is not None):
            raise NotImplementedError(
                "SSSP parents are derived by the host engine only: build "
                "the LaneEngine without ndev, mesh or grid to get them")
        lanes = self.sssp_lanes_for(roots.size)
        if self.dwg2 is not None:
            from repro.core.dist_sssp import dist2d_sssp
            return dist2d_sssp(self.dwg2, roots, self.mesh, delta=delta,
                               lanes=lanes, max_pos=self.max_pos,
                               relax_impl=self.probe_impl,
                               compress=self.compress,
                               recorder=self._recorder("dist2d_sssp"))
        if self.dwg is not None:
            from repro.core.dist_sssp import dist_sssp
            return dist_sssp(self.dwg, roots, self.mesh, delta=delta,
                             lanes=lanes, max_pos=self.max_pos,
                             relax_impl=self.probe_impl,
                             recorder=self._recorder("dist_sssp"))
        from repro.traversal.sssp import sssp_pipelined
        return sssp_pipelined(self.wg, roots, delta=delta,
                              lanes=lanes,
                              max_pos=self.max_pos,
                              relax_impl=self.probe_impl,
                              recorder=self._recorder("sssp"),
                              derive_parents=derive_parents)


def as_engine(g_or_engine, **kwargs) -> LaneEngine:
    """Accept either a ``CSRGraph`` (build an engine with ``kwargs``) or an
    already-built ``LaneEngine`` (reuse it — kwargs must then be empty, a
    half-applied override would silently diverge from the engine's
    config)."""
    if isinstance(g_or_engine, LaneEngine):
        if kwargs:
            raise ValueError(
                f"engine already built; unexpected overrides {sorted(kwargs)}")
        return g_or_engine
    return LaneEngine(g_or_engine, **kwargs)
