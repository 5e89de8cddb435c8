"""SSSP trees from the tropical lane engine (Graph 500 kernel 3).

``sssp_pipelined(..., derive_parents=True)`` and
``LaneEngine.sssp_sweep(..., derive_parents=True)`` against a float64
heap Dijkstra and the kernel-3 tree rules, on seeded weighted Kronecker
graphs; planted zero and sub-ulp weights, on which a least-id parent
rule would cycle; the relaxation-pass counters against the bucket/phase
trace; the named scopes and program names the device trace reads; and
the refusal of the sharded engines.
"""
import re

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analytics import LaneEngine
from repro.core.csr import WeightedCSRGraph, from_weighted_edges
from repro.graph.generator import rmat_weighted_graph, sample_roots
from repro.traversal import (dijkstra_reference, sssp_pipelined,
                             to_numpy_weighted)
from repro.traversal import sssp as ts


@pytest.fixture(scope="module")
def wg10():
    return rmat_weighted_graph(10, 16, seed=3)


def _assert_sssp_tree(wg, roots, dist, parent):
    """The kernel-3 rules, on the host: the key is its own parent at 0;
    every other reached v has a neighbour parent u with
    ``float32(d[u] + w) == d[v]`` over some slot of the pair; unreached
    vertices have parent -1; following parents reaches the key."""
    rp, ci, w = to_numpy_weighted(wg)
    n = wg.n
    dist, parent = np.asarray(dist), np.asarray(parent)
    assert parent.shape == dist.shape and parent.dtype == np.int32
    src = np.repeat(np.arange(n), np.diff(rp))
    for lane, key in enumerate(np.asarray(roots)):
        d, p = dist[:, lane], parent[:, lane]
        reached = np.isfinite(d)
        assert d[key] == 0 and p[key] == key
        assert (p[~reached] == -1).all()
        others = reached & (np.arange(n) != key)
        # a tree slot: the row's parent, at the row's distance
        tree = (p[src] == ci) & (d[ci] + w == d[src])
        has = np.zeros(n, bool)
        has[src[tree]] = True
        assert has[others].all(), np.flatnonzero(others & ~has)[:5]
        ptr = np.where(reached, p, key)
        for _ in range(int(np.ceil(np.log2(n))) + 1):
            ptr = ptr[ptr]
        assert (ptr == key).all(), "parent pointers do not reach the key"


def _assert_dist_matches_dijkstra(wg, roots, dist):
    rp, ci, w = to_numpy_weighted(wg)
    for lane, key in enumerate(np.asarray(roots)):
        want = dijkstra_reference(rp, ci, w, int(key))
        got = np.asarray(dist[:, lane], np.float64)
        np.testing.assert_array_equal(np.isfinite(got), np.isfinite(want))
        fin = np.isfinite(want)
        np.testing.assert_allclose(got[fin], want[fin], atol=1e-4)


@pytest.mark.parametrize("scale,seed,lanes", [(8, 1, 2), (10, 3, 2),
                                              (9, 7, 1)])
def test_parents_form_a_tree_and_distances_match_dijkstra(scale, seed,
                                                          lanes):
    wg = rmat_weighted_graph(scale, 16, seed=seed)
    roots = sample_roots(wg, 4, seed=seed)
    res = LaneEngine(wg, lanes=lanes).sssp_sweep(roots, derive_parents=True)
    assert not bool(np.asarray(res.truncated).any())
    _assert_dist_matches_dijkstra(wg, roots, res.dist)
    _assert_sssp_tree(wg, roots, res.dist, res.parent)


def _reweighted(wg, u, v, weight) -> WeightedCSRGraph:
    """``wg`` with both slots of every (u, v) edge set to ``weight``."""
    rp, ci, w = to_numpy_weighted(wg)
    src = np.repeat(np.arange(wg.n), np.diff(rp))
    w = w.astype(np.float64).copy()
    w[((src == u) & (ci == v)) | ((src == v) & (ci == u))] = weight
    return from_weighted_edges(src, ci, w, wg.n, symmetrize=False,
                               drop_self_loops=False)


@pytest.mark.parametrize("weight", [0.0, 2.0 ** -24],
                         ids=["zero", "sub_ulp"])
def test_planted_tie_still_gives_a_tree(weight):
    """A weight of 0, or of 2**-24 between vertices at distance 2 or more
    (below half an ulp there, so ``d[u] + w`` rounds to ``d[u]``), makes
    both ends candidates of each other at one distance."""
    # weights on [0, 4): paths long enough to pass distance 2
    wg0 = rmat_weighted_graph(10, 8, seed=4, weight_range=(0.0, 4.0))
    roots = sample_roots(wg0, 2, seed=5)
    base = sssp_pipelined(wg0, roots, lanes=2)
    d = np.asarray(base.dist[:, 0])
    rp, ci, _ = to_numpy_weighted(wg0)
    src = np.repeat(np.arange(wg0.n), np.diff(rp))
    # an edge between two reached vertices past distance 2, not a loop
    pick = np.flatnonzero((src != ci) & (d[src] >= 2) & (d[ci] >= 2)
                          & np.isfinite(d[src]) & np.isfinite(d[ci]))
    assert pick.size
    u, v = int(src[pick[0]]), int(ci[pick[0]])
    wg = _reweighted(wg0, u, v, weight)
    res = sssp_pipelined(wg, roots, lanes=2, derive_parents=True)
    dist = np.asarray(res.dist)
    assert dist[u, 0] == dist[v, 0]          # the planted tie
    # the least-id neighbour that matches would make u and v each
    # other's parent
    rp, ci, w = to_numpy_weighted(wg)
    d = dist[:, 0]
    match = (d[ci] + w == d[src]) & np.isfinite(d[src])
    least = np.full(wg.n, wg.n)
    np.minimum.at(least, src[match], ci[match])
    assert (least[u], least[v]) == (v, u)
    _assert_dist_matches_dijkstra(wg, roots, res.dist)
    _assert_sssp_tree(wg, roots, res.dist, res.parent)


def test_zero_weight_chain_off_the_key():
    """Vertices at distance 0 besides the key, and a zero-weight cycle
    hanging off a positive edge: every tie broken toward the key."""
    src = np.asarray([0, 1, 2, 3, 4, 5, 3])
    dst = np.asarray([1, 2, 0, 4, 5, 3, 0])
    w = np.asarray([0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.5])
    wg = from_weighted_edges(src, dst, w, 7)
    res = sssp_pipelined(wg, [0, 4], lanes=2, derive_parents=True)
    np.testing.assert_array_equal(np.asarray(res.dist[:6, 0]),
                                  [0, 0, 0, 0.5, 0.5, 0.5])
    _assert_sssp_tree(wg, [0, 4], res.dist, res.parent)
    assert np.asarray(res.parent)[6].tolist() == [-1, -1]


def _passes_from_trace(trace_phase, phase):
    """Engine steps in which some lane was in ``phase``, when every lane
    is seated at step 0 (lanes == sources): row k is step k of all."""
    return int((np.asarray(trace_phase) == phase).any(axis=1).sum())


def test_relax_pass_counters_match_the_trace(wg10):
    roots = sample_roots(wg10, 3, seed=2)
    res = sssp_pipelined(wg10, roots, lanes=3)
    steps = int(np.asarray(res.steps).max())
    assert steps < res.trace_phase.shape[0]
    assert int(res.sweep_steps) == steps
    assert int(res.light_relax_passes) == _passes_from_trace(
        res.trace_phase, 0)
    assert int(res.heavy_relax_passes) == _passes_from_trace(
        res.trace_phase, 1)
    # one lane serves the sources one after another: the sweep's steps
    # are theirs end to end
    one = sssp_pipelined(wg10, roots, lanes=1)
    phase = np.asarray(one.trace_phase)
    assert int(one.light_relax_passes) == int((phase == 0).sum())
    assert int(one.heavy_relax_passes) == int((phase == 1).sum())
    assert int(one.sweep_steps) == int(np.asarray(one.steps).sum())


def test_parents_leave_distances_and_traces_as_they_were(wg10):
    roots = sample_roots(wg10, 5, seed=9)
    plain = sssp_pipelined(wg10, roots, lanes=2)
    treed = sssp_pipelined(wg10, roots, lanes=2, derive_parents=True)
    assert plain.parent is None
    for field in ("sources", "dist", "steps", "truncated", "trace_bucket",
                  "trace_phase", "light_relax_passes", "heavy_relax_passes",
                  "sweep_steps"):
        np.testing.assert_array_equal(np.asarray(getattr(plain, field)),
                                      np.asarray(getattr(treed, field)),
                                      err_msg=field)
    _assert_sssp_tree(wg10, roots, treed.dist, treed.parent)


@pytest.mark.parametrize("layout", ["mesh", "grid"])
def test_sharded_engines_refuse_parents(wg10, layout):
    from repro.core.dist_sssp import host_mesh
    eng = (LaneEngine(wg10, mesh=host_mesh(1)) if layout == "mesh"
           else LaneEngine(wg10, grid=(1, 1)))
    with pytest.raises(NotImplementedError, match="host engine"):
        eng.sssp_sweep([0, 1], derive_parents=True)


@pytest.mark.parametrize("program", ["_sssp_drain", "sssp_engine_step"])
def test_sssp_programs_carry_every_step_scope(program):
    """Each piece of an engine step sits under one of
    ``SSSP_STEP_SCOPES``, and the traversal and the parent derivation are
    programs of their own names (``jit__sssp_drain``,
    ``jit__sssp_parents`` in a device trace)."""
    wg = rmat_weighted_graph(8, 8, seed=0)
    state = ts.sssp_engine_enqueue(
        ts.sssp_engine_init(wg, 4, 2, track_steps=True),
        jnp.arange(4, dtype=jnp.int32))
    lowered = getattr(ts, program).lower(wg, state, 0.05, 8, "xla",
                                         ts.MAX_SSSP_STEPS)
    text = lowered.as_text(debug_info=True)
    for scope in ts.SSSP_STEP_SCOPES:
        assert scope in text, scope
    body = {"_sssp_drain": "jit(_sssp_drain)/while/body",
            "sssp_engine_step": "jit(sssp_engine_step)"}[program]
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    assert any(n.startswith(body + "/") for n in names)
    assert not {n for n in names if n.startswith(body + "/")
                and not set(n.split("/")) & set(ts.SSSP_STEP_SCOPES)}
    parents = ts._sssp_parents.lower(
        wg, state.out_dist[:, :2], state.out_dist_step[:, :2],
        state.queue[:2]).as_text(debug_info=True)
    assert "jit__sssp_parents" in parents and "sssp_parents/" in parents
