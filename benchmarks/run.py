"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV for each benchmark, where
``us_per_call`` is the wall time of the benchmark's core measured operation
and ``derived`` the benchmark's headline derived quantity.

  PYTHONPATH=src python -m benchmarks.run            # fast defaults
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale sweep
"""
from __future__ import annotations

import argparse
import time


class MissingArtifacts(FileNotFoundError):
    """A bench reads artifacts another command writes, and none exist."""


def _timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, (time.perf_counter() - t0) * 1e6


def bench_table2(full: bool):
    from benchmarks.table2_switching import run
    rows, us = _timed(run, 14 if full else 11, 16)
    bu_layers = sum(1 for r in rows if r["approach"] == "bottom-up")
    return us, f"bu_layers={bu_layers}/{len(rows)}"


def bench_table3(full: bool):
    from benchmarks.table3_maxpos import run
    rows, us = _timed(run, 13 if full else 11, 16)
    big = max(rows, key=lambda r: r["found"])
    return us, f"retired@8={big['retired_frac'][8]:.3f}"


def bench_fig3(full: bool):
    from benchmarks.fig3_teps import run
    scales = (12, 13, 14) if full else (10, 11)
    efs = (16, 32, 64) if full else (16, 32)
    res, us = _timed(run, scales, efs, 16 if full else 4)
    sc = scales[-1]
    simd = res[(sc, efs[-1], "hybrid")]
    nosimd = res[(sc, efs[-1], "hybrid_nosimd")]
    return us, f"simd_vs_nosimd={simd / max(nosimd, 1):.3f}x"


def bench_table4(full: bool):
    from benchmarks.table4_counters import run
    rows, us = _timed(run, 13 if full else 11, 32 if full else 16)
    tot_no = sum(r["t_nosimd_ms"] for r in rows)
    tot_si = sum(r["t_simd_ms"] for r in rows)
    return us, f"bu_speedup={tot_no / max(tot_si, 1e-9):.2f}x"


def bench_roofline(full: bool):
    from benchmarks.roofline import ART, load_records
    recs, us = _timed(load_records, "pod16x16")
    ok = [r for r in recs if r["status"] == "ok" and "roofline" in r]
    if not ok:
        raise MissingArtifacts(f"no dry-run roofline records under {ART}")
    best = max(ok, key=lambda r: r["roofline"]["roofline_fraction"])
    return us, (f"cells={len(ok)};best_frac="
                f"{best['roofline']['roofline_fraction']:.3f}"
                f"@{best.get('arch', 'bfs')}/{best.get('shape', '')}")


BENCHES = [
    ("table2_switching", bench_table2),
    ("table3_maxpos", bench_table3),
    ("fig3_teps", bench_fig3),
    ("table4_counters", bench_table4),
    ("roofline", bench_roofline),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sweep (slower)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    print("name,us_per_call,derived")
    for name, fn in BENCHES:
        if args.only and args.only != name:
            continue
        us, derived = fn(args.full)
        print(f"{name},{us:.0f},{derived}", flush=True)


if __name__ == "__main__":
    main()
