#!/usr/bin/env python3
"""The kernel-3 and depth-only cells with their timed path broken, to see
the check fail, and a runner for such a control on the chip.

``program_with(variant)`` returns the program's entries (as
``bench.drivers.program()`` does) with the lane engine replaced by a
subclass that breaks its answers where they are made:

* ``control``: every SSSP distance read 1e-3 high, as a cheaper
  accumulation (bfloat16 sums are off by about 1e-2) would read it. This
  breaks the configuration's guarantee of exact distances.
* ``foreign_parent``: one reached vertex of each SSSP key given a parent
  that is not its neighbour.
* ``cycle``: a parent and its child made each other's parent.
* ``dropped``: the last key of each SSSP sweep never traversed.
* ``early``: each depth-only answer read one level early: a sweep leaves
  out the last level of every key.

    python3 bench/faults_sssp.py --workload g500_s18_sssp.sssp2 \
        --seeds 11,12 --seconds 45 [--variant control]

runs, for each seed, one whole run of the cell with the variant planted
(``none`` runs the program itself) and prints the numbers compared,
beside their limits, as one JSON line. The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VARIANTS = ("control", "foreign_parent", "cycle", "dropped", "early")


def _engine(base, variant: str):
    import jax.numpy as jnp

    class Engine(base):
        def sssp_sweep(self, roots, delta=None, derive_parents=False):
            res = super().sssp_sweep(roots, delta, derive_parents)
            dist, parent = res.dist, res.parent
            if variant == "control":
                dist = dist + jnp.float32(1e-3)
            elif variant == "dropped":
                last = dist.shape[1] - 1
                dist = dist.at[:, last].set(jnp.inf)
                parent = parent.at[:, last].set(-1)
            elif variant in ("foreign_parent", "cycle"):
                p = np.asarray(parent).copy()
                for lane, key in enumerate(np.asarray(res.sources)):
                    # a reached vertex whose parent is not the key (none
                    # in a warm-up sweep over keys without edges)
                    pick = np.flatnonzero((p[:, lane] >= 0)
                                          & (p[:, lane] != key))
                    if pick.size == 0:
                        continue
                    v = int(pick[0])
                    if variant == "cycle":
                        p[p[v, lane], lane] = v
                    else:
                        p[v, lane] = self._stranger(v)
                parent = jnp.asarray(p)
            return res._replace(dist=dist, parent=parent)

        def _stranger(self, v: int) -> int:
            """A vertex that is not v's neighbour (nor v)."""
            row_ptr = np.asarray(self.g.row_ptr)
            near = set(np.asarray(self.g.col_idx)[
                row_ptr[v]:row_ptr[v + 1]].tolist()) | {v}
            return next(u for u in range(self.n) if u not in near)

        def sweep(self, roots, derive_parents: bool = False):
            res = super().sweep(roots, derive_parents)
            if variant != "early":
                return res
            depth = res.depth
            last = (depth == depth.max(axis=0, keepdims=True)) & (depth > 0)
            return res._replace(depth=jnp.where(last, -1, depth))

    return Engine


def program_with(variant: str):
    """The program's entries with ``variant`` planted underneath."""
    from bench.drivers import program
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; one of {VARIANTS}")
    p = program()
    p.LaneEngine = _engine(p.LaneEngine, variant)
    return p


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--variant", default="control")
    args = ap.parse_args(argv)
    # the TPU runtime otherwise logs to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    from bench.drivers import program
    from bench.lib import device
    device.use_compile_cache()
    devices = device.require_chips(1)
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.load_cell(args.workload)
        entries = (program() if args.variant == "none"
                   else program_with(args.variant))
        line = harness.run_cell(cell, seed, args.seconds, False,
                                started=time.perf_counter(),
                                program=entries, devices=devices)
        print(json.dumps({"variant": args.variant, "seed": seed,
                          "correct": line["correct"],
                          "attempted": line["attempted"],
                          "failed": line["failed"],
                          "metrics": line["metrics"],
                          "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
