"""The kernel-3 cell and the depth-only cell, rehearsed on the CPU at a
tiny size: the weighted generator against the unweighted one, the
kernel-3 and depth-only checks and the float64 reference against brute
force, both cells end to end through ``harness.run_cell``, and every
planted fault of ``bench/faults_sssp.py`` making ``correct`` false."""
import json
import os
import time
from contextlib import nullcontext

import numpy as np
import pytest

from bench import faults_sssp, harness
from bench.lib import (depthcheck, g500check, graphgen, graphgen_weighted,
                       refs, sssp_ref, ssspcheck)
from bench.lib.roofline_sssp import sssp_min_bytes
from bench.tests.conftest import ROOT

CELLS = ("g500_s18_sssp.sssp2", "g500_s20.depths64")
FAULTS = [("g500_s18_sssp.sssp2", v)
          for v in ("control", "foreign_parent", "cycle", "dropped")] + [
          ("g500_s20.depths64", "early")]


def _weighted(scale=9, edgefactor=8, structure=2**40 + 3):
    cfg = {"scale": scale, "edgefactor": edgefactor,
           "structure_seed": structure}
    return tuple(np.asarray(a) for a in
                 graphgen_weighted.weighted_graph_for(cfg, 1))


def test_weighted_generator_is_graph_for_with_weights():
    cfg = {"scale": 9, "edgefactor": 8, "structure_seed": 2**40 + 3}
    row_ptr, col, src, w = _weighted()
    want = graphgen.graph_for(cfg, 7)
    for got, ref in zip((row_ptr, col, src), want[:3]):
        np.testing.assert_array_equal(got, np.asarray(ref))
    assert w.dtype == np.float32 and w.shape == col.shape
    assert (w >= 0).all() and (w < 1).all()
    # both directions of a tuple carry its weight
    n = len(row_ptr) - 1
    fwd = np.lexsort((w, col, src))
    rev = np.lexsort((w, src, col))
    np.testing.assert_array_equal(w[fwd], w[rev])
    assert np.unique(w).size > col.size // 4          # a weight per tuple
    # another structure seed, another graph
    assert not np.array_equal(_weighted(structure=5)[1], col)
    assert sorted(np.unique(src).tolist()) == sorted(
        set(range(n)) & set(src.tolist()))


def _tree(row_ptr, col, src, w, keys):
    """The program's SSSP answer over the benchmark's graph."""
    from repro.core.csr import WeightedCSRGraph
    from repro.traversal import sssp_pipelined
    wg = WeightedCSRGraph(row_ptr=row_ptr, col_idx=col, src_idx=src,
                          weights=w)
    res = sssp_pipelined(wg, keys, lanes=len(keys), derive_parents=True)
    return np.asarray(res.dist), np.asarray(res.parent)


def test_sssp_check_and_reference_against_brute_force():
    from repro.traversal import dijkstra_reference
    row_ptr, col, src, w = _weighted()
    keys = graphgen.search_keys(row_ptr, col)[:3]
    dist, parent = _tree(row_ptr, col, src, w, keys)
    want = sssp_ref.shortest_paths(row_ptr, col, w, keys)
    for j, k in enumerate(keys):
        np.testing.assert_allclose(
            want[:, j], dijkstra_reference(row_ptr, col, w, int(k)))
    assert not sssp_ref.mismatch(dist, want, 1e-4).any()
    assert sssp_ref.mismatch(dist + 1e-3, want, 1e-4)[np.isfinite(dist)].all()

    def check(d, p):
        bad, slots = ssspcheck.check_batch(
            row_ptr, col, src, w, d, p, keys,
            chunks=g500check.num_chunks(col.size, 1 << 10))
        return np.asarray(bad), np.asarray(slots)

    bad, slots = check(dist, parent)
    assert bad.sum() == 0
    for j in range(len(keys)):
        reached = np.isfinite(dist[:, j])
        assert slots[j] == np.diff(row_ptr)[reached].sum()
    # each fault caught in its own key alone
    v = int(np.flatnonzero(np.isfinite(dist[:, 0]) & (dist[:, 0] > 0)
                           & (parent[:, 0] != keys[0]))[0])
    pv = int(parent[v, 0])
    for poke in ("high", "parent", "cycle", "unreached", "short"):
        d, p = dist.copy(), parent.copy()
        if poke == "high":
            d[:, 0] += 1e-3
        elif poke == "parent":
            p[v, 0] = v
        elif poke == "cycle":
            p[pv, 0] = v
        elif poke == "unreached":
            d[v, 0], p[v, 0] = np.inf, -1
        else:
            d[v, 0] = d[v, 0] * 0.5
        bad, _ = check(d, p)
        assert bad[0] > 0 and bad[1:].sum() == 0, poke


def test_depth_check_against_brute_force():
    row_ptr, col, src, _ = _weighted()
    keys = graphgen.search_keys(row_ptr, col)[:40]
    depth = refs.multi_source_depths(row_ptr, col, keys)

    def check(d):
        bad, slots = depthcheck.check_depths(
            row_ptr, col, src, d, keys,
            chunks=g500check.num_chunks(col.size, 1 << 10))
        return np.asarray(bad), np.asarray(slots)

    bad, slots = check(depth)
    assert bad.sum() == 0
    assert slots[0] == np.diff(row_ptr)[depth[:, 0] >= 0].sum()
    for poke in ("deeper", "early", "key"):
        d = depth.copy()
        last = d[:, 33].max()
        if poke == "deeper":          # a whole level one deeper: no
            d[d[:, 33] == last, 33] += 1    # edge spans two, none leads up
        elif poke == "early":
            d[d[:, 33] == last, 33] = -1
        else:
            d[keys[33], 33] = 1
        bad, _ = check(d)
        assert bad[33] > 0 and bad.sum() == bad[33], poke


def test_sssp_min_bytes():
    n, m = 1 << 18, 1 << 23
    assert sssp_min_bytes(n, m, 2) == 4 * (n + 1) + 8 * m + 2 * 8 * n
    assert sssp_min_bytes(n, m, 2) < sssp_min_bytes(n, m, 3)


def _run(root, cell, seed=2**31 + 11, seconds=1.0, trace=False,
         program=None):
    c = harness.load_cell(cell, root)
    return harness.run_cell(c, seed, seconds, trace,
                            started=time.perf_counter(), program=program)


@pytest.mark.parametrize("cell", CELLS)
def test_new_cell_runs_and_reports_its_metrics(tiny_root, cell):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    line = _run(tiny_root, cell)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"teps", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    traced = _run(tiny_root, cell, seed=2**33 + 1, trace=True)
    assert traced["correct"] is True
    layer = {m["name"] for m in bench["per_layer"]
             if cell in m["workloads"]}
    assert layer
    # the CPU trace has no TPU plane: the host clock and the program's
    # counters are read, the device metrics stay silent
    host = {m["name"] for m in bench["per_layer"] if cell in m["workloads"]
            and m["source"] in ("host_clock", "program_counter")}
    assert host <= set(traced["metrics"]) <= layer


def test_relax_passes_are_the_engines_counters(tiny_root):
    cell = harness.load_cell("g500_s18_sssp.sssp2", tiny_root)
    from bench.drivers import load, program
    driver = load("sssp")(cell, 2**32 + 5, program())
    driver.setup()
    driver.window(0.5, lambda name: nullcontext())
    assert driver.check()["numbers"]["sssp_tree_violations"][0] == 0
    facts = driver.facts()
    assert facts["relax_passes"] == [
        a + b for a, b in zip(facts["light_relax_passes"],
                              facts["heavy_relax_passes"])]
    assert all(0 < p <= 2 * s for p, s in zip(facts["relax_passes"],
                                              facts["sweep_steps"]))
    reader = harness.metric_reader("relax_passes.sssp", tiny_root)
    view = harness.RunView(trace=None, facts=facts, peaks={}, cell=cell)
    assert reader.read(view) == (sum(facts["relax_passes"])
                                 / len(facts["relax_passes"]))


@pytest.mark.parametrize("cell,variant", FAULTS)
def test_planted_fault_makes_correct_false(tiny_root, cell, variant):
    line = _run(tiny_root, cell, seed=23,
                program=faults_sssp.program_with(variant))
    assert line["correct"] is False, (variant, line["checks"])
    assert line["failed"] > 0
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_program_without_sssp_parents_is_refused_before_the_graph(
        tiny_root, monkeypatch):
    from bench.drivers import program
    entries = program()

    class Engine(entries.LaneEngine):
        def sssp_sweep(self, roots, delta=None):
            return super().sssp_sweep(roots, delta)

    entries.LaneEngine = Engine
    made = []
    monkeypatch.setattr(graphgen_weighted, "weighted_graph_for",
                        lambda *a: made.append(a))
    with pytest.raises(NotImplementedError, match="derives no parents"):
        _run(tiny_root, "g500_s18_sssp.sssp2", program=entries)
    assert not made
