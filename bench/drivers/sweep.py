"""Graph 500 kernel 2, batched: back-to-back ``LaneEngine.sweep`` calls
with parents over ``keys_per_sweep`` search keys each.

Set-up makes the configuration's graph on the device (see
``bench.lib.graphgen``), builds the engine and runs one sweep over
vertices without edges, so that every program of the sweep is compiled
(or loaded from the cache) before the window. The window runs sweeps
until the first one that ends at or after ``seconds``; sweep ``i`` has
its own Graph 500 search keys, the same for every seed, over the lanes in
an order drawn from the run's seed. Its answers stay on the device until
the window has closed.

After the window every key of every sweep is checked on the device by the
Graph 500 rules (``bench.lib.g500check``), which also count each key's
traversed edges from the benchmark's own CSR; and the depths of one
sweep, drawn from the seed, are compared with the plain NumPy reference.
"""
from __future__ import annotations

import time

import numpy as np

from bench.lib import g500check, graphgen, refs


class Driver:
    def __init__(self, cell, seed: int, program):
        self.cell, self.seed, self.program = cell, int(seed), program
        cfg, wl = cell.config, cell.workload
        self.scale, self.edgefactor = cfg["scale"], cfg["edgefactor"]
        self.lanes = cfg["lanes"]
        self.keys_per_sweep = wl["keys_per_sweep"]
        self.sweeps: list[tuple] = []     # (keys, depth, parent) per sweep
        self.sweep_s: list[float] = []
        self.window_s = 0.0

    # -- set-up --------------------------------------------------------------

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
        # warm-up sweep: keys without edges stop after one level
        idle = np.flatnonzero(np.diff(self.row_ptr) == 0)
        if idle.size < self.keys_per_sweep:
            idle = np.argsort(np.diff(self.row_ptr), kind="stable")
        warm = self.engine.sweep(idle[:self.keys_per_sweep].astype(np.int32),
                                 derive_parents=True)
        jax.block_until_ready((warm.depth, warm.parent))
        del warm
        self.setup_parts["warmup_sweep_s"] = (time.perf_counter() - t0
                                              - self.setup_parts["graph_s"])
        self._keys = [self.keys_for(i) for i in range(4)]

    def keys_for(self, i: int) -> np.ndarray:
        """The keys of sweep ``i``: the same for every seed (drawn with the
        structure seed), over the lanes in an order drawn from the seed."""
        pick = graphgen.host_rng(self.cell.config["structure_seed"], 1, i)
        keys = pick.choice(self.candidates, size=self.keys_per_sweep,
                           replace=False)
        return graphgen.host_rng(self.seed, 1, i).permutation(keys).astype(
            np.int32)

    # -- window --------------------------------------------------------------

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
                    res = self.engine.sweep(keys, derive_parents=True)
                    jax.block_until_ready((res.depth, res.parent))
                self.sweep_s.append(time.perf_counter() - start)
                self.sweeps.append((keys, res.depth, res.parent))
                if time.perf_counter() - t0 >= seconds:
                    break
        self.window_s = time.perf_counter() - t0

    def free(self) -> None:
        self.engine = None

    # -- after the window ----------------------------------------------------

    def check(self) -> dict:
        """Judge every answer of the window; count traversed edges."""
        import jax.numpy as jnp
        row_ptr, col_idx, src_idx = self.arrays
        chunks = g500check.num_chunks(self.m)
        violations = failed = 0
        edges = 0
        self.sweep_edges: list[int] = []
        for keys, depth, parent in self.sweeps:
            bad, slots = g500check.check_batch(
                row_ptr, col_idx, src_idx, depth, parent, jnp.asarray(keys),
                chunks=chunks)
            bad = np.asarray(bad)
            violations += int(bad.sum())
            failed += int((bad > 0).sum())
            edges += int(np.asarray(slots, np.int64).sum()) // 2
            self.sweep_edges.append(int(np.asarray(slots, np.int64).sum())
                                    // 2)
        pick = int(graphgen.host_rng(self.seed, 2).integers(len(self.sweeps)))
        keys, depth, _ = self.sweeps[pick]
        want = refs.multi_source_depths(self.row_ptr, np.asarray(col_idx),
                                        keys)
        got = np.asarray(depth)
        mismatch = int((got != want).sum())
        failed_ref = int((got != want).any(axis=0).sum())
        self.edges = edges
        limits = self.cell.workload["limits"]
        return {
            "attempted": len(self.sweeps) * self.keys_per_sweep,
            "failed": max(failed, failed_ref),
            "numbers": {
                "tree_violations": (violations, limits["tree_violations"]),
                "depth_mismatch": (mismatch, limits["depth_mismatch"]),
            },
        }

    def end_to_end(self) -> dict:
        return {"teps": self.edges / self.window_s}

    def facts(self) -> dict:
        return {"sweeps": len(self.sweeps), "n": self.n, "m": self.m,
                "keys_per_sweep": self.keys_per_sweep,
                "window_s": self.window_s, "sweep_s": self.sweep_s,
                "sweep_edges": self.sweep_edges}
