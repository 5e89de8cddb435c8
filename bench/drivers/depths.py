"""Batched hop distances without parents: back-to-back
``LaneEngine.sweep`` calls over ``keys_per_sweep`` search keys each, as
batch analytics (closeness, k-hop counts, eccentricity) run them.

The BFS sweep driver's cell without the parent derivation: the same
graph, the same keys of sweep ``i`` and the same window rule. Set-up
warms up with a sweep over vertices without edges, without parents.
After the window every key of every sweep is checked on the device by
the depth-only Graph 500 rules (``bench.lib.depthcheck``), which also
count traversed edges, and the depths of one sweep, drawn from the
seed, are compared with the plain NumPy reference.
"""
from __future__ import annotations

import time

import numpy as np

from bench.drivers import sweep
from bench.lib import depthcheck, g500check, graphgen, refs


class Driver(sweep.Driver):
    def setup(self) -> None:
        import jax
        t0 = time.perf_counter()
        row_ptr, col_idx, src_idx, _ = jax.block_until_ready(
            graphgen.graph_for(self.cell.config, self.seed))
        self.setup_parts = {"graph_s": time.perf_counter() - t0}
        self.arrays = (row_ptr, col_idx, src_idx)
        self.row_ptr = np.asarray(row_ptr)
        self.n, self.m = len(self.row_ptr) - 1, int(col_idx.shape[0])
        self.candidates = graphgen.search_keys(self.row_ptr,
                                               np.asarray(col_idx))
        g = self.program.CSRGraph(row_ptr=row_ptr, col_idx=col_idx,
                                  src_idx=src_idx)
        self.engine = self.program.LaneEngine(g, lanes=self.lanes)
        idle = np.flatnonzero(np.diff(self.row_ptr) == 0)
        if idle.size < self.keys_per_sweep:
            idle = np.argsort(np.diff(self.row_ptr), kind="stable")
        warm = self.engine.sweep(idle[:self.keys_per_sweep].astype(np.int32))
        jax.block_until_ready(warm.depth)
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
                    depth = jax.block_until_ready(self.engine.sweep(keys).depth)
                self.sweep_s.append(time.perf_counter() - start)
                self.sweeps.append((keys, depth))
                if time.perf_counter() - t0 >= seconds:
                    break
        self.window_s = time.perf_counter() - t0

    def check(self) -> dict:
        """Judge every answer of the window; count traversed edges."""
        import jax.numpy as jnp
        row_ptr, col_idx, src_idx = self.arrays
        chunks = g500check.num_chunks(self.m)
        violations = failed = edges = 0
        self.sweep_edges: list[int] = []
        for keys, depth in self.sweeps:
            bad, slots = depthcheck.check_depths(
                row_ptr, col_idx, src_idx, depth, jnp.asarray(keys),
                chunks=chunks)
            bad = np.asarray(bad)
            violations += int(bad.sum())
            failed += int((bad > 0).sum())
            self.sweep_edges.append(int(np.asarray(slots, np.int64).sum())
                                    // 2)
            edges += self.sweep_edges[-1]
        pick = int(graphgen.host_rng(self.seed, 2).integers(len(self.sweeps)))
        keys, depth = self.sweeps[pick]
        want = refs.multi_source_depths(self.row_ptr, np.asarray(col_idx),
                                        keys)
        got = np.asarray(depth)
        mismatch = int((got != want).sum())
        self.edges = edges
        limits = self.cell.workload["limits"]
        return {
            "attempted": len(self.sweeps) * self.keys_per_sweep,
            "failed": max(failed, int((got != want).any(axis=0).sum())),
            "numbers": {
                "depth_violations": (violations, limits["depth_violations"]),
                "depth_mismatch": (mismatch, limits["depth_mismatch"]),
            },
        }
