#!/usr/bin/env python3
"""Run a cell with its timed path broken, to read the check's numbers.

    python3 bench/control.py --workload g500_s20.bfs64 \
        --seeds 11,12,13 --seconds 10 [--variant control]

For each seed, one whole run of the cell (set-up, window, check) in this
one process, with the program replaced as ``bench/faults.py`` says for
``--variant`` (``none`` runs the program itself). Prints each run's
numbers compared, beside their limits, as one JSON line. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--variant", default="control")
    args = ap.parse_args(argv)
    # the TPU runtime otherwise logs to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import faults, harness
    from bench.drivers import program
    from bench.lib import device
    device.use_compile_cache()
    devices = device.require_chips(1)
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.load_cell(args.workload)
        entries = (program() if args.variant == "none"
                   else faults.program_with(args.variant))
        line = harness.run_cell(cell, seed, args.seconds, False,
                                started=time.perf_counter(),
                                program=entries, devices=devices)
        print(json.dumps({"variant": args.variant, "seed": seed,
                          "correct": line["correct"],
                          "attempted": line["attempted"],
                          "failed": line["failed"],
                          "metrics": line["metrics"],
                          "checks": line["checks"]}), flush=True)
        del line, entries
        gc.collect()            # the last seed's service and its answers
    return 0


if __name__ == "__main__":
    sys.exit(main())
