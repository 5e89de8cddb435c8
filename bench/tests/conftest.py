"""Fixtures of the benchmark's CPU tests: a copy of the benchmark's files
with every configuration cut to a tiny scale."""
from __future__ import annotations

import json
import os
import shutil

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
# scale of each configuration in the tiny copy, by driver
TINY_SCALE = {"sweep": 10, "serve": 9}


def make_tiny_root(dest: str) -> str:
    """A root holding ``BENCHMARK.json`` and ``bench/{configs,workloads,
    metrics}`` copied from this checkout, with each configuration at a
    tiny scale and each served traffic shortened. Returns ``dest``."""
    for sub in ("configs", "workloads", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub),
                        os.path.join(dest, "bench", sub))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    driver_of = {}
    for w in bench["workloads"]:
        path = os.path.join(dest, "bench", "workloads", f"{w['name']}.json")
        with open(path) as f:
            wl = json.load(f)
        driver_of[w["config"]] = wl["driver"]
        if wl["driver"] == "serve":
            wl.update(rate_qps=40.0, lead_in_s=0.5, drain_limit_s=3.0)
        with open(path, "w") as f:
            json.dump(wl, f)
    for c in bench["configs"]:
        path = os.path.join(dest, c["file"])
        with open(path) as f:
            cfg = json.load(f)
        cfg["scale"] = TINY_SCALE[driver_of[c["name"]]]
        with open(path, "w") as f:
            json.dump(cfg, f)
    return dest


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(str(tmp_path))
