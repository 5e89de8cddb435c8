"""CI benchmark gate: run the fast benches, emit BENCH_pr.json, compare.

Collects one higher-is-better throughput number per benchmark:

* every ``benchmarks/run.py`` fast-default bench as calls/sec
  (1e6 / us_per_call — the paper-table analogs have no TEPS axis);
* MS-BFS aggregate TEPS, serial loop and pipelined batched engine
  (scale 10, R=64);
* the analytics smoke (components / closeness / khop TEPS-equivalents on
  the lane engine, ``analytics_bench.bench_points`` at scale 10);
* the weighted-path smoke (delta-stepping SSSP / unit-weight anchor /
  weighted closeness, ``sssp_bench.bench_points`` at scale 10);
* the serving smoke (``serve_bench.bench_points`` at scale 10): a
  replayed mixed-workload trace through ``AnalyticsService`` — mix TEPS,
  answered-early fraction, and the khop layers saved by the streaming
  read-outs gate; p50/p99 sojourn layers are recorded as derived
  metadata;
* the distributed MS-BFS smoke (``dist_msbfs_teps.py --smoke``), run in a
  subprocess so the forced host-device count never leaks into the
  single-device timings;
* the 2-D grid smoke (``dist2d_teps.py --smoke``, same subprocess
  isolation): per-wire-format TEPS plus the exchange-volume reduction
  ratio from frontier compression;
* the distributed SSSP smoke (``dist_sssp_teps.py --smoke``, same
  isolation): the sharded delta-stepping engine's TEPS-equivalents per
  wire format plus ITS exchange-volume reduction ratio;
* the telemetry-overhead gate (``obs.overhead``): recorder-off TEPS over
  the raw drain's — proves ``recorder=None`` stays free (< 3% bound via
  its own per-bench ``tolerance``).

Gate: with ``--baseline BENCH_baseline.json``, exit 1 when any benchmark
regresses more than ``--tolerance`` (default 25%) below its baseline
value; a baseline entry carrying its own ``tolerance`` key gates at that
bound instead. New benchmarks absent from the baseline pass (and are
reported); refresh the checked-in baseline with ``--write-baseline`` on
a quiet machine when a PR legitimately shifts throughput.

  PYTHONPATH=src python benchmarks/ci_bench.py --out BENCH_pr.json \
      --baseline BENCH_baseline.json --tolerance 0.25
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

# a CPU count gate: pin JAX to the CPU before anything imports it, so on
# a chip host neither this process nor the dist-smoke children it starts
# (they inherit the environment) ever claim the accelerator
os.environ["JAX_PLATFORMS"] = "cpu"

# allow `python benchmarks/ci_bench.py` (sys.path[0] = benchmarks/)
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _bench_run_py() -> dict:
    from benchmarks.run import BENCHES, MissingArtifacts
    out = {}
    for name, fn in BENCHES:
        try:
            us, derived = fn(False)
        except MissingArtifacts as e:   # roofline needs dry-run artifacts
            print(f"skip run.{name}: {e}")
            continue
        out[f"run.{name}"] = dict(value=1e6 / max(us, 1e-9),
                                  unit="calls_per_sec", derived=derived)
    return out


def _bench_msbfs(scale: int = 10, roots: int = 64) -> dict:
    from repro.graph.generator import rmat_graph
    from repro.graph.graph500 import run_graph500
    g = rmat_graph(scale, 16, 0)
    out = {}
    for label, batched in (("serial", False), ("batched", True)):
        res = run_graph500(scale, 16, mode="hybrid", num_roots=roots,
                           seed=0, graph=g, batched=batched)
        out[f"msbfs.{label}_s{scale}_R{roots}"] = dict(
            value=res.aggregate_teps, unit="teps")
    return out


def _bench_analytics(scale: int = 10) -> dict:
    """Analytics smoke: components + closeness + khop TEPS-equivalents on
    the lane engine (``analytics_bench.bench_points``) — the new
    subsystem's regressions gate exactly like BFS TEPS."""
    from benchmarks.analytics_bench import bench_points
    return {f"analytics.{k}": dict(value=v, unit="teps_equiv")
            for k, v in bench_points(scale).items()}


def _bench_sssp(scale: int = 10) -> dict:
    """Weighted-path smoke: delta-stepping sweep + unit-weight anchor +
    weighted closeness TEPS-equivalents (``sssp_bench.bench_points``) —
    weighted regressions gate exactly like BFS TEPS."""
    from benchmarks.sssp_bench import bench_points
    return {f"sssp.{k}": dict(value=v, unit="teps_equiv")
            for k, v in bench_points(scale).items()}


def _bench_serve_smoke() -> dict:
    """Serving smoke (``serve_bench.bench_points`` at scale 10): one
    mixed bfs/khop/reach/closeness/sssp trace replayed through
    ``AnalyticsService`` with streaming read-outs on vs off. Gates the
    aggregate mix TEPS, the answered-early fraction, and the mean khop
    layers saved by streaming; the lower-is-better p50/p99 sojourn
    points ride along as ``derived`` metadata (recorded in the bench
    JSON, never compared — the dist benches' byte-counter precedent)."""
    from benchmarks.serve_bench import bench_points
    points = bench_points(10)
    sojourn = {k: v for k, v in points.items() if "sojourn" in k}
    out = {}
    for k, v in points.items():
        if "sojourn" in k:
            continue
        unit = ("teps" if "teps" in k
                else "ratio" if "frac" in k else "layers")
        out[f"serve.{k}"] = dict(value=v, unit=unit)
        if "teps" in k:
            out[f"serve.{k}"]["derived"] = sojourn
    return out


def _bench_dist_smoke() -> dict:
    here = os.path.dirname(os.path.abspath(__file__))
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        tmp = f.name
    try:
        subprocess.run(
            [sys.executable, os.path.join(here, "dist_msbfs_teps.py"),
             "--smoke", "--json", tmp],
            check=True, env=dict(os.environ), timeout=1800)
        with open(tmp) as f:
            points = json.load(f)
    finally:
        os.unlink(tmp)
    return {f"dist_msbfs.{k}": dict(value=v, unit="teps")
            for k, v in points.items()}


def _bench_dist2d_smoke() -> dict:
    """2-D grid smoke (``dist2d_teps.py --smoke``): TEPS per wire format
    plus the headline ``xreduction`` ratio (dense bytes / compressed
    bytes, higher is better). Raw ``bytes_per_layer`` points are
    lower-is-better and so stay out of the gate — the ratio carries the
    same signal in gateable form."""
    here = os.path.dirname(os.path.abspath(__file__))
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        tmp = f.name
    try:
        subprocess.run(
            [sys.executable, os.path.join(here, "dist2d_teps.py"),
             "--smoke", "--json", tmp],
            check=True, env=dict(os.environ), timeout=1800)
        with open(tmp) as f:
            points = json.load(f)
    finally:
        os.unlink(tmp)
    out = {}
    for k, v in points.items():
        if k.endswith("_bytes_per_layer"):
            continue
        unit = "ratio" if k.endswith("_xreduction") else "teps"
        out[f"dist2d.{k}"] = dict(value=v, unit=unit)
    return out


def _bench_dist_sssp_smoke() -> dict:
    """Distributed SSSP smoke (``dist_sssp_teps.py --smoke``):
    TEPS-equivalents per wire format plus the exchange-volume
    ``xreduction`` ratio. Raw ``bytes_per_step`` points are
    lower-is-better and stay out of the gate — the ratio carries the
    compression signal in gateable form."""
    here = os.path.dirname(os.path.abspath(__file__))
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        tmp = f.name
    try:
        subprocess.run(
            [sys.executable, os.path.join(here, "dist_sssp_teps.py"),
             "--smoke", "--json", tmp],
            check=True, env=dict(os.environ), timeout=1800)
        with open(tmp) as f:
            points = json.load(f)
    finally:
        os.unlink(tmp)
    out = {}
    for k, v in points.items():
        if k.endswith("_bytes_per_step"):
            continue
        unit = "ratio" if k.endswith("_xreduction") else "teps_equiv"
        out[f"sssp_dist.{k}"] = dict(value=v, unit=unit)
    return out


def _bench_obs_overhead(scale: int = 10, roots: int = 64,
                        reps: int = 3) -> dict:
    """The telemetry-overhead gate: TEPS of the recorder-OFF driver path
    (``msbfs_pipelined(recorder=None)``, which must compile to exactly
    the pre-obs fused drain) over TEPS of the raw engine drain called
    directly. A ratio below ~0.97 means the ``recorder=None`` branch is
    no longer free — the ISSUE's < 3% acceptance bound, gated with this
    bench's own tight per-bench ``tolerance``. The recorder-ON TEPS ride
    along as derived metadata (recording steps host-side per layer, so
    it is EXPECTED to be slower — that cost is opt-in, never gated)."""
    import jax
    import numpy as np

    from repro.core.msbfs import (msbfs_engine_drain, msbfs_engine_enqueue,
                                  msbfs_engine_init, msbfs_engine_result,
                                  msbfs_pipelined)
    from repro.graph.generator import rmat_graph
    from repro.obs import SweepRecorder

    g = rmat_graph(scale, 16, 0)
    rts = np.arange(roots, dtype=np.int32) % g.n
    lanes = 64

    def run_raw():
        s = msbfs_engine_init(g, capacity=roots, lanes=lanes)
        s = msbfs_engine_enqueue(s, rts)
        s = msbfs_engine_drain(g, s, "hybrid", 8.0, 8.0, 8, "xla")
        return msbfs_engine_result(g, s, derive_parents=False)

    def run_off():
        return msbfs_pipelined(g, rts, lanes=lanes, derive_parents=False)

    def teps_of(fn):
        res = fn()
        jax.block_until_ready(res.depth)       # warm compile out of timing
        edges = float(np.asarray(res.edges_traversed).sum()) / 2
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn().depth)
            best = min(best, time.perf_counter() - t0)
        return edges / best

    teps_raw = teps_of(run_raw)
    teps_off = teps_of(run_off)
    res_on = msbfs_pipelined(g, rts, lanes=lanes, derive_parents=False,
                             recorder=SweepRecorder(engine="msbfs"))
    jax.block_until_ready(res_on.depth)
    t0 = time.perf_counter()
    jax.block_until_ready(
        msbfs_pipelined(g, rts, lanes=lanes, derive_parents=False,
                        recorder=SweepRecorder(engine="msbfs")).depth)
    wall_on = time.perf_counter() - t0
    edges = float(np.asarray(res_on.edges_traversed).sum()) / 2
    return {"obs.overhead": dict(
        value=teps_off / max(teps_raw, 1e-9), unit="ratio",
        tolerance=0.03,
        derived=dict(teps_recorder_off=round(teps_off),
                     teps_raw_drain=round(teps_raw),
                     teps_recorder_on=round(edges / max(wall_on, 1e-9))))}


def append_history(path: str, benches: dict) -> dict | None:
    """Append this run's ``{git_sha, benchmarks}`` entry to the JSONL
    trajectory file and return the PREVIOUS entry (None on first run).
    The sha comes from the environment (GITHUB_SHA in CI, GIT_SHA as a
    local override) — no wall-clock in the entry, so replaying the bench
    at the same sha appends an identical record."""
    sha = os.environ.get("GITHUB_SHA") or os.environ.get("GIT_SHA") \
        or "unknown"
    prev = None
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    prev = json.loads(line)
    entry = dict(git_sha=sha,
                 benchmarks={k: round(v["value"], 6)
                             for k, v in benches.items()})
    with open(path, "a") as f:
        f.write(json.dumps(entry, sort_keys=True) + "\n")
    return prev


def print_trend(benches: dict, prev: dict | None) -> None:
    """Per-benchmark trend vs the previous history entry."""
    if prev is None:
        print("bench history: first entry, no trend yet")
        return
    print(f"bench trend vs {prev.get('git_sha', '?')[:12]}:")
    prev_b = prev.get("benchmarks", {})
    for name in sorted(benches):
        cur = benches[name]["value"]
        old = prev_b.get(name)
        if old is None:
            print(f"  {name:40s} {cur:12.4g}  (new)")
        elif old == 0:
            print(f"  {name:40s} {cur:12.4g}  (prev 0)")
        else:
            delta = cur / old - 1.0
            print(f"  {name:40s} {cur:12.4g}  {delta:+.1%}")


def compare(pr: dict, baseline: dict, tolerance: float) -> list[str]:
    """Regressions worse than the tolerance (fractional drop), as
    human-readable failure lines. A baseline entry may carry its own
    ``tolerance`` key (e.g. the tight ``obs.overhead`` gate) overriding
    the global one."""
    failures = []
    for name, base in baseline["benchmarks"].items():
        cur = pr["benchmarks"].get(name)
        if cur is None:
            failures.append(f"{name}: present in baseline but not in PR run")
            continue
        tol = float(base.get("tolerance", tolerance))
        floor = base["value"] * (1.0 - tol)
        if cur["value"] < floor:
            drop = 1.0 - cur["value"] / max(base["value"], 1e-12)
            failures.append(
                f"{name}: {cur['value']:.3g} {cur['unit']} is "
                f"{drop:.0%} below baseline {base['value']:.3g} "
                f"(tolerance {tol:.0%})")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_pr.json")
    ap.add_argument("--baseline", default=None)
    ap.add_argument("--tolerance", type=float, default=0.25)
    ap.add_argument("--write-baseline", action="store_true",
                    help="also write the result to the --baseline path")
    ap.add_argument("--skip-dist", action="store_true",
                    help="skip the subprocess dist smoke (debug aid)")
    ap.add_argument("--history", default=None, metavar="JSONL",
                    help="append this run's {git_sha, benchmarks} to the "
                         "JSONL trajectory file and print the trend vs "
                         "the previous entry")
    args = ap.parse_args()

    t0 = time.perf_counter()
    benches: dict = {}
    benches.update(_bench_run_py())
    benches.update(_bench_msbfs())
    benches.update(_bench_analytics())
    benches.update(_bench_sssp())
    benches.update(_bench_serve_smoke())
    benches.update(_bench_obs_overhead())
    if not args.skip_dist:
        benches.update(_bench_dist_smoke())
        benches.update(_bench_dist2d_smoke())
        benches.update(_bench_dist_sssp_smoke())
    pr = dict(tolerance=args.tolerance,
              wall_s=round(time.perf_counter() - t0, 2),
              benchmarks=benches)

    with open(args.out, "w") as f:
        json.dump(pr, f, indent=2, sort_keys=True)
    print(f"wrote {args.out} ({len(benches)} benchmarks, "
          f"{pr['wall_s']}s)")
    for name in sorted(benches):
        b = benches[name]
        print(f"  {name:40s} {b['value']:12.4g} {b['unit']}")

    if args.history:
        prev = append_history(args.history, benches)
        print_trend(benches, prev)

    if args.write_baseline and args.baseline:
        with open(args.baseline, "w") as f:
            json.dump(pr, f, indent=2, sort_keys=True)
        print(f"wrote baseline {args.baseline}")
        return
    if args.baseline:
        with open(args.baseline) as f:
            baseline = json.load(f)
        failures = compare(pr, baseline, args.tolerance)
        if failures:
            print("TEPS regression gate FAILED:")
            for line in failures:
                print(f"  {line}")
            sys.exit(1)
        print(f"regression gate passed vs {args.baseline} "
              f"(tolerance {args.tolerance:.0%})")


if __name__ == "__main__":
    main()
