"""Graph 500 kernel 3, batched: back-to-back ``LaneEngine.sssp_sweep``
calls with parents over ``keys_per_sweep`` search keys each.

Set-up makes the configuration's weighted graph on the device (see
``bench.lib.graphgen_weighted``), builds the engine over a
``WeightedCSRGraph`` of it and runs one sweep over vertices without
edges, which finishes in a few steps, so that every program of the sweep
is compiled (or loaded from the cache) before the window. The bucket
width is the engine's default. The window runs sweeps until the first
one that ends at or after ``seconds``; sweep ``i`` has the search keys of
the BFS sweep driver's sweep ``i``, the same for every seed, over the
lanes in an order drawn from the run's seed. Answers stay on the device
until the window has closed.

After the window every key of every sweep is checked on the device by the
kernel-3 rules (``bench.lib.ssspcheck``), which also count each key's
traversed edges from the benchmark's own CSR, and its distances are
compared with float64 shortest paths over the same slots
(``bench.lib.sssp_ref``). A lane the engine cut off at its step cap
counts as failed.
"""
from __future__ import annotations

import inspect
import time

import numpy as np

from bench.drivers import sweep
from bench.lib import g500check, graphgen, graphgen_weighted, sssp_ref
from bench.lib import ssspcheck


class Driver(sweep.Driver):
    def __init__(self, cell, seed: int, program):
        super().__init__(cell, seed, program)
        self.tolerance = cell.workload["dist_tolerance"]
        self.counters: list[tuple] = []   # (light, heavy, steps) per sweep

    def setup(self) -> None:
        import jax
        from repro.core.csr import WeightedCSRGraph
        # refuse before minutes of graph making: kernel 3 needs parents
        if "derive_parents" not in inspect.signature(
                self.program.LaneEngine.sssp_sweep).parameters:
            raise NotImplementedError(
                "this program's LaneEngine.sssp_sweep derives no parents, "
                "which Graph 500 kernel 3 needs")
        t0 = time.perf_counter()
        row_ptr, col_idx, src_idx, weights = jax.block_until_ready(
            graphgen_weighted.weighted_graph_for(self.cell.config,
                                                 self.seed))
        self.setup_parts = {"graph_s": time.perf_counter() - t0}
        self.arrays = (row_ptr, col_idx, src_idx, weights)
        self.row_ptr = np.asarray(row_ptr)
        self.n, self.m = len(self.row_ptr) - 1, int(col_idx.shape[0])
        self.candidates = graphgen.search_keys(self.row_ptr,
                                               np.asarray(col_idx))
        wg = WeightedCSRGraph(row_ptr=row_ptr, col_idx=col_idx,
                              src_idx=src_idx, weights=weights)
        self.engine = self.program.LaneEngine(wg, lanes=self.lanes)
        # warm-up sweep: keys without edges stop after a few steps
        idle = np.flatnonzero(np.diff(self.row_ptr) == 0)
        if idle.size < self.keys_per_sweep:
            idle = np.argsort(np.diff(self.row_ptr), kind="stable")
        warm = self.engine.sssp_sweep(
            idle[:self.keys_per_sweep].astype(np.int32), derive_parents=True)
        jax.block_until_ready((warm.dist, warm.parent))
        del warm
        self.setup_parts["warmup_sweep_s"] = (time.perf_counter() - t0
                                              - self.setup_parts["graph_s"])
        self._keys = [self.keys_for(i) for i in range(4)]

    def window(self, seconds: float, span) -> None:
        import jax
        t0 = time.perf_counter()
        with span("window"):
            while True:
                i = len(self.sweeps)
                keys = (self._keys[i] if i < len(self._keys)
                        else self.keys_for(i))
                start = time.perf_counter()
                with span("sweep"):
                    res = self.engine.sssp_sweep(keys, derive_parents=True)
                    jax.block_until_ready((res.dist, res.parent))
                self.sweep_s.append(time.perf_counter() - start)
                self.sweeps.append((keys, res.dist, res.parent,
                                    res.truncated))
                self.counters.append(tuple(
                    getattr(res, name, None) for name in
                    ("light_relax_passes", "heavy_relax_passes",
                     "sweep_steps")))
                if time.perf_counter() - t0 >= seconds:
                    break
        self.window_s = time.perf_counter() - t0

    def check(self) -> dict:
        """Judge every answer of the window; count traversed edges."""
        import jax.numpy as jnp
        row_ptr, col_idx, src_idx, weights = self.arrays
        col, w = np.asarray(col_idx), np.asarray(weights)
        chunks = g500check.num_chunks(self.m)
        violations = mismatch = truncated = failed = edges = 0
        self.sweep_edges: list[int] = []
        self.dist_max_err = 0.0
        judged = [ssspcheck.check_batch(row_ptr, col_idx, src_idx, weights,
                                        dist, parent, jnp.asarray(keys),
                                        chunks=chunks)
                  for keys, dist, parent, _ in self.sweeps]
        # the device judges the trees while the host computes the reference
        for (keys, dist, parent, cut), (bad, slots) in zip(self.sweeps,
                                                           judged):
            want = sssp_ref.shortest_paths(self.row_ptr, col, w, keys)
            got = np.asarray(dist, np.float64)
            bad = np.asarray(bad)
            off = sssp_ref.mismatch(got, want, self.tolerance).sum(axis=0)
            both = np.isfinite(got) & np.isfinite(want)
            self.dist_max_err = max(
                self.dist_max_err,
                float(np.abs(got[both] - want[both]).max(initial=0.0)))
            cut = np.asarray(cut, bool)
            violations += int(bad.sum())
            mismatch += int(off.sum())
            truncated += int(cut.sum())
            failed += int(((bad > 0) | (off > 0) | cut).sum())
            self.sweep_edges.append(int(np.asarray(slots, np.int64).sum())
                                    // 2)
            edges += self.sweep_edges[-1]
        self.edges = edges
        limits = self.cell.workload["limits"]
        return {
            "attempted": len(self.sweeps) * self.keys_per_sweep,
            "failed": failed,
            "numbers": {
                "sssp_tree_violations": (violations,
                                         limits["sssp_tree_violations"]),
                "dist_mismatch": (mismatch, limits["dist_mismatch"]),
                "truncated_lanes": (truncated, limits["truncated_lanes"]),
            },
        }

    def facts(self) -> dict:
        facts = dict(super().facts(), dist_max_err=self.dist_max_err)
        # the engine's counters, where it has them (the host engine)
        if self.counters and None not in self.counters[0]:
            light, heavy, steps = (
                [int(c[k]) for c in self.counters] for k in range(3))
            facts.update(light_relax_passes=light, heavy_relax_passes=heavy,
                         sweep_steps=steps,
                         relax_passes=[a + b for a, b in zip(light, heavy)])
        return facts
