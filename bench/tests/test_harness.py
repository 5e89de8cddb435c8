"""The harness rehearsed on the CPU at a tiny size: each cell runs end to
end through ``harness.run_cell`` (only the look for a chip is skipped), a
cell added as files alone is found and run, ``bench/run.py`` refuses a
machine without a TPU, and every planted fault makes ``correct`` false."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import faults, harness
from bench.tests.conftest import BENCH, ROOT

CELLS = ("g500_s20.bfs64", "g500_s18_bfs_service.open80")
FAULTS = [(cell, variant) for cell in CELLS
          for variant in faults.variants_for(
              harness.load_cell(cell).workload["driver"])]


def _run(root, cell, seed=2**31 + 5, seconds=1.0, trace=False, program=None):
    c = harness.load_cell(cell, root)
    return harness.run_cell(c, seed, seconds, trace,
                            started=time.perf_counter(), program=program)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_reports_its_metrics(tiny_root, cell):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    line = _run(tiny_root, cell)
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert list(line)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())
    traced = _run(tiny_root, cell, seed=3, trace=True)
    assert traced["correct"] is True
    assert {"busy_s", "window_s"} <= set(traced["device"])
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    layer = {m["name"] for m in bench["per_layer"]
             if cell in m["workloads"]}
    # the CPU trace has no TPU plane: only host-clock metrics are read
    host = {m["name"] for m in bench["per_layer"]
            if cell in m["workloads"] and m["source"] == "host_clock"}
    assert host <= set(traced["metrics"]) <= layer


def test_cell_added_as_files_alone_is_found_and_run(tiny_root):
    """A later PR adds a configuration, a traffic mix and a per-layer
    metric as new files and new entries; nothing else changes."""
    bench_dir = os.path.join(tiny_root, "bench")
    with open(os.path.join(bench_dir, "configs", "g500_s20.json")) as f:
        cfg = json.load(f)
    cfg.update(name="g500_s9", scale=9, edgefactor=8)
    with open(os.path.join(bench_dir, "configs", "g500_s9.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench_dir, "workloads",
                           "g500_s9.bfs32.json"), "w") as f:
        json.dump({"config": "g500_s9", "traffic": "bfs32", "chips": 1,
                   "driver": "sweep", "keys_per_sweep": 32,
                   "limits": {"tree_violations": 0, "depth_mismatch": 0}},
                  f)
    with open(os.path.join(bench_dir, "metrics",
                           "sweeps_in_window.bfs.py"), "w") as f:
        f.write('UNIT = "sweeps"\n\n\ndef read(run):\n'
                '    return run.facts.get("sweeps") or None\n')
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "g500_s9", "source": "x",
                             "file": "bench/configs/g500_s9.json",
                             "reduced": ["scale"], "why": "x"})
    bench["workloads"].append({"name": "g500_s9.bfs32", "config": "g500_s9",
                               "traffic": "bfs32", "chips": 1, "why": "x"})
    bench["end_to_end"][0]["workloads"].append("g500_s9.bfs32")
    bench["per_layer"].append({"name": "sweeps_in_window.bfs",
                               "unit": "sweeps", "better": "higher",
                               "source": "host_clock", "layer": "x",
                               "moves": "teps",
                               "workloads": ["g500_s9.bfs32"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    line = _run(tiny_root, "g500_s9.bfs32")
    assert line["correct"] and line["attempted"] % 32 == 0
    assert set(line["metrics"]) == {"teps", "setup_s"}
    traced = _run(tiny_root, "g500_s9.bfs32", seed=4, trace=True)
    assert traced["metrics"]["sweeps_in_window.bfs"]["value"] >= 1


@pytest.mark.parametrize("variant", ["none", "control"])
def test_served_mix_added_as_files_alone_is_judged(tiny_root, variant):
    """A traffic mix of k-hop and reach requests over the served
    configuration, added as a file and an entry, runs and is judged."""
    with open(os.path.join(tiny_root, "bench", "workloads",
                           "g500_s18_bfs_service.hops.json"), "w") as f:
        json.dump({"config": "g500_s18_bfs_service", "traffic": "hops",
                   "chips": 1, "driver": "serve", "rate_qps": 40.0,
                   "lead_in_s": 0.5, "drain_limit_s": 3.0,
                   "mix": [{"kind": "khop", "k": 1, "share": 0.3},
                           {"kind": "khop", "k": 2, "share": 0.3},
                           {"kind": "reach", "share": 0.4}],
                   "limits": {"rejected": 0, "unanswered": 0,
                              "wrong_answers": 0}}, f)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "g500_s18_bfs_service.hops",
                               "config": "g500_s18_bfs_service",
                               "traffic": "hops", "chips": 1, "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "g500_s18_bfs_service.open80" in m.get("workloads", []):
            m["workloads"].append("g500_s18_bfs_service.hops")
    with open(path, "w") as f:
        json.dump(bench, f)
    line = _run(tiny_root, "g500_s18_bfs_service.hops", seed=2**31 + 9,
                program=(None if variant == "none"
                         else faults.program_with(variant)))
    assert line["attempted"] > 0
    assert line["correct"] is (variant == "none"), line["checks"]
    assert set(line["metrics"]) == {"latency_p95_ms", "latency_p50_ms",
                                    "setup_s"}


def test_run_refuses_a_machine_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for where in (ROOT, str(tmp_path)):
        if where != ROOT:       # BENCHMARK.json and bench/ alone
            shutil.copytree(BENCH, os.path.join(where, "bench"),
                            ignore=shutil.ignore_patterns(
                                ".jax_cache", "traces", "__pycache__"))
            shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), where)
        out = subprocess.run(
            [sys.executable, "bench/run.py", "--workload", CELLS[0],
             "--seed", "1", "--seconds", "1", "--trace", "0"],
            cwd=where, env=env, capture_output=True, text=True, timeout=120)
        assert out.returncode != 0
        assert "no TPU" in out.stderr and "cpu" in out.stderr
        assert out.stdout.strip() == ""


@pytest.mark.parametrize("cell,variant", FAULTS)
def test_planted_fault_makes_correct_false(tiny_root, cell, variant):
    line = _run(tiny_root, cell, seed=17, program=faults.program_with(variant))
    assert line["correct"] is False, (variant, line["checks"])
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
