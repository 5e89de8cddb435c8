#!/usr/bin/env python3
"""Find the highest open-loop rate a served cell sustains, on the chip.

    python3 bench/knee.py --workload g500_s18_bfs_service.open80 \
        --rates 10,20,30,40 --seconds 30 --seed 7

One process: for each rate, lowest first, the cell's whole set-up (a new
service, so that every rate starts from the state the cell's runs start
from), then the cell's traffic at that rate for ``--seconds`` after its
lead-in, drained. For each rate it prints one JSON line: the
requests due in the window and answered, p50/p95 latency, and the
backlog (requests submitted and not yet answered) averaged over the
ticks of the first and of the last third of the window. A rate is
sustained when the backlog of the last third is no more than 1.5 times
that of the first third and every request was answered; the last line
names the highest such rate. The cell's own rate is about 0.8 of it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def backlog(log, lo: float, hi: float) -> float | None:
    """Mean count of requests submitted and not answered at the end of
    each tick that ends in [lo, hi)."""
    counts = []
    for _, end in log.ticks:
        if lo <= end < hi:
            counts.append(sum(1 for a in log.arrivals
                              if a.submitted is not None
                              and a.submitted <= end
                              and (a.done is None or a.done > end)))
    return sum(counts) / len(counts) if counts else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--drain-limit", type=float, default=None,
                    help="seconds to wait after the window for the "
                         "answers (default: the traffic file's); a rate "
                         "above the knee then ends before its backlog "
                         "fills the host's memory")
    args = ap.parse_args(argv)
    # the TPU runtime otherwise logs to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import gc
    from contextlib import nullcontext

    from bench import harness
    from bench.drivers import load, program
    from bench.lib import device
    from bench.lib.stats import percentile
    device.use_compile_cache()
    device.require_chips(1)
    best = None
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell = harness.load_cell(args.workload)
        cell.seconds = args.seconds
        cell.workload["rate_qps"] = rate
        if args.drain_limit is not None:
            cell.workload["drain_limit_s"] = args.drain_limit
        driver = load(cell.workload["driver"])(cell, args.seed + i,
                                               program())
        driver.setup()
        t0 = time.perf_counter()
        driver.window(args.seconds, lambda name: nullcontext())
        log = driver.log
        lat = driver.latencies_ms()
        reqs = log.window_requests()
        answered = sum(1 for a in reqs if a.done is not None)
        third = args.seconds / 3
        early, late = backlog(log, 0, third), backlog(log, 2 * third,
                                                      args.seconds)
        sustained = (answered == len(reqs) and early is not None
                     and late is not None and late <= 1.5 * max(early, 1.0))
        if sustained:
            best = rate
        print(json.dumps({
            "rate_qps": rate, "due": len(reqs), "answered": answered,
            "p50_ms": percentile(lat, 50), "p95_ms": percentile(lat, 95),
            "backlog_first_third": early, "backlog_last_third": late,
            "ticks": len(log.ticks),
            "tick_ms_mean": (1e3 * sum(e - s for s, e in log.ticks)
                             / max(len(log.ticks), 1)),
            "sustained": sustained,
            "seconds_with_drain": time.perf_counter() - t0,
            "host_rss_peak_bytes": driver.facts()["host_rss_peak_bytes"]}),
            flush=True)
        del driver, log, reqs
        gc.collect()
    print(json.dumps({"highest_sustained_qps": best}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
