#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell is an entry of ``BENCHMARK.json``;
``bench/harness.py`` says how it is found and run. The last line of
standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``; the
numbers compared come last under ``checks``), and the last lines of
standard error repeat each number compared beside its limit.

It exits non-zero and prints no result when JAX finds no TPU, or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the TPU runtime otherwise logs to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for path in (ROOT, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)

    from bench import harness
    from bench.lib import device as chip
    cell = harness.load_cell(args.workload)
    chip.use_compile_cache()
    try:
        devices = chip.require_chips(cell.chips)
    except chip.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    line = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            started=STARTED, devices=devices)
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
