"""The graph's stored slot-to-row-position table (``slot_pos``).

Every builder fills the table once: ``from_edges``, ``from_weighted_edges``
(and ``WeightedCSRGraph.csr`` carries it), the 1-D and 2-D partitioners
per local block, and ``LaneEngine`` for a graph built by hand. A graph
without it (the benchmark builds its CSR by keyword from three arrays)
still runs and answers bit for bit as one with it.

The structural test keeps the table from being rebuilt inside a step: in
the optimised HLO of the fused drains and of one engine step, no gather
of ``row_ptr`` (``s32[n+1]``) or of the degrees (``s32[n]``) at m indices
sits in a while body or a conditional branch. A gather in a ``lax.cond``
branch of the drain loop is not hoisted by the compiler, so such a gather
would be paid once per step.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analytics import BFSQuery, LaneEngine, SSSPQuery
from repro.core import msbfs as ms
from repro.core.csr import (CSRGraph, WeightedCSRGraph, from_edges,
                            from_weighted_edges, with_slot_pos)
from repro.core.dist2d import partition_graph_2d
from repro.core.dist_bfs import partition_graph
from repro.core.dist_sssp import (partition_weighted_graph,
                                  partition_weighted_graph_2d)
from repro.graph.generator import rmat_weighted_graph, sample_roots
from repro.serving import AnalyticsService
from repro.traversal import sssp as ts


def _reference(row_ptr, m):
    """Place in row of each of ``m`` slots, -1 at or past ``row_ptr[-1]``:
    a plain loop over the rows."""
    row_ptr = np.asarray(row_ptr)
    want = np.full(m, -1, np.int64)
    for v in range(len(row_ptr) - 1):
        for k, e in enumerate(range(row_ptr[v], row_ptr[v + 1])):
            want[e] = k
    return want


def _edges():
    rng = np.random.default_rng(7)
    n = 37
    src = rng.integers(0, n, 90)
    dst = rng.integers(0, n, 90)
    return src, dst, rng.uniform(0.0, 1.0, 90), n


def _blocks(kind):
    """(row_ptr, slot_pos) of every block a builder makes."""
    src, dst, w, n = _edges()
    g = from_edges(src, dst, n)
    wg = from_weighted_edges(src, dst, w, n)
    if kind == "from_edges":
        return [(g.row_ptr, g.slot_pos)]
    if kind == "from_weighted_edges":
        return [(wg.row_ptr, wg.slot_pos)]
    if kind == "weighted_csr":
        return [(wg.csr.row_ptr, wg.csr.slot_pos)]
    if kind.startswith("partition_graph_2d"):
        pr, pc = map(int, kind.rsplit("_", 1)[1].split("x"))
        dg = partition_graph_2d(g, pr, pc)
    elif kind.startswith("partition_weighted_graph_2d"):
        pr, pc = map(int, kind.rsplit("_", 1)[1].split("x"))
        dg = partition_weighted_graph_2d(wg, pr, pc).g2
    elif kind.startswith("partition_graph"):
        dg = partition_graph(g, int(kind.rsplit("_", 1)[1]))
    else:
        dg = partition_weighted_graph(wg, int(kind.rsplit("_", 1)[1]))
    return list(zip(np.asarray(dg.row_ptr), np.asarray(dg.slot_pos)))


@pytest.mark.parametrize("kind", [
    "from_edges", "from_weighted_edges", "weighted_csr",
    "partition_graph_1", "partition_graph_3",
    "partition_graph_2d_2x2", "partition_graph_2d_1x3",
    "partition_weighted_graph_2", "partition_weighted_graph_2d_2x2"])
def test_slot_pos_matches_numpy_reference(kind):
    blocks = _blocks(kind)
    padded = 0
    for row_ptr, slot_pos in blocks:
        slot_pos = np.asarray(slot_pos)
        assert slot_pos.dtype == np.int32
        np.testing.assert_array_equal(slot_pos,
                                      _reference(row_ptr, len(slot_pos)))
        padded += int((slot_pos < 0).sum())
    if kind.startswith("partition") and len(blocks) > 1:
        assert padded > 0, "no block of the partition has a padded tail"


@pytest.mark.parametrize("weighted", [False, True])
def test_with_slot_pos_is_idempotent(weighted):
    src, dst, w, n = _edges()
    g = (from_weighted_edges(src, dst, w, n) if weighted
         else from_edges(src, dst, n))
    assert with_slot_pos(g) is g
    bare = g._replace(slot_pos=None)
    built = with_slot_pos(bare)
    assert type(built) is type(g)
    np.testing.assert_array_equal(np.asarray(built.slot_pos),
                                  np.asarray(g.slot_pos))
    assert with_slot_pos(built) is built
    traced = jax.jit(lambda h: with_slot_pos(h).slot_pos)(bare)
    np.testing.assert_array_equal(np.asarray(traced), np.asarray(g.slot_pos))


def _by_keyword(g):
    """``g`` as the benchmark builds it: by keyword, without the table."""
    if isinstance(g, WeightedCSRGraph):
        return WeightedCSRGraph(row_ptr=g.row_ptr, col_idx=g.col_idx,
                                src_idx=g.src_idx, weights=g.weights)
    return CSRGraph(row_ptr=g.row_ptr, col_idx=g.col_idx, src_idx=g.src_idx)


def _answers(path, g, roots):
    if path == "sweep":
        r = LaneEngine(g, lanes=32).sweep(roots, derive_parents=True)
        return [r.depth, r.parent, r.num_layers, r.edges_traversed,
                r.trace_dir, r.trace_vf, r.trace_ef, r.trace_eu,
                r.bu_fallback_passes]
    if path == "sssp_sweep":
        r = LaneEngine(g, lanes=32).sssp_sweep(roots[:2],
                                               derive_parents=True)
        return [r.dist, r.parent, r.steps, r.trace_bucket, r.trace_phase,
                r.light_relax_passes, r.heavy_relax_passes, r.sweep_steps]
    if path == "service":
        svc = AnalyticsService(g, slots=8, sssp_slots=4)
        bfs = svc.submit(BFSQuery(sources=tuple(int(r) for r in roots[:4])))
        sp = svc.submit(SSSPQuery(sources=tuple(int(r) for r in roots[:2])))
        svc.run_until_idle()
        return [bfs.answer.result.depth, bfs.answer.result.num_layers,
                sp.answer.result.dist]
    if path == "msbfs":
        r = ms.msbfs(g.csr, jnp.asarray(roots[:8]))
        return [r.depth, r.parent, r.trace_dir]
    r = ts.sssp_pipelined(g, roots[:2], lanes=2, derive_parents=True)
    return [r.dist, r.parent, r.steps, r.light_relax_passes,
            r.heavy_relax_passes]


@pytest.mark.parametrize("path", ["sweep", "sssp_sweep", "service", "msbfs",
                                  "sssp_pipelined"])
def test_graph_built_by_keyword_answers_bit_identically(path):
    wg = rmat_weighted_graph(7, 8, seed=3)
    roots = sample_roots(wg.csr, 8, seed=2).astype(np.int32)
    with_table = _answers(path, wg if path != "sweep" else wg.csr, roots)
    bare = _by_keyword(wg if path != "sweep" else wg.csr)
    assert bare.slot_pos is None
    without = _answers(path, bare, roots)
    assert len(with_table) == len(without)
    for a, b in zip(with_table, without):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --- structural: the table is never rebuilt inside a loop -----------------

_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_CALLS = re.compile(
    r"(body|condition|true_computation|false_computation|calls|to_apply)"
    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_DEF = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]")
_GATHER = re.compile(r"= (\w+)\[([\d,]*)\]\S* gather\(%([\w.\-]+)")


def _computations(text):
    comps, name = {}, None
    for line in text.splitlines():
        head = _COMP.match(line)
        if head and not line.startswith(" "):
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _looped_gathers(text, n, m):
    """Gathers of ``s32[n+1]`` or ``s32[n]`` at m indices in computations
    reached from a while body or a conditional branch."""
    comps = _computations(text)
    calls, roots = {}, set()
    for name, lines in comps.items():
        callees = set()
        for line in lines:
            for kind, callee in _CALLS.findall(line):
                callees.add(callee)
                if kind in ("body", "true_computation",
                            "false_computation"):
                    roots.add(callee)
            for group in _BRANCHES.findall(line):
                branches = {b.strip().lstrip("%") for b in group.split(",")}
                callees |= branches
                roots |= branches
        calls[name] = callees
    looped, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name in looped or name not in comps:
            continue
        looped.add(name)
        todo.extend(calls[name])
    found = []
    for name in looped:
        shapes = {d.group(1): d.group(2, 3)
                  for d in map(_DEF.match, comps[name]) if d}
        for line in comps[name]:
            for rtype, rdims, operand in _GATHER.findall(line):
                otype, odims = shapes.get(operand, ("", ""))
                if (rtype == otype == "s32"
                        and rdims.split(",")[0] == str(m)
                        and odims in (str(n + 1), str(n))):
                    found.append((name, line.strip()))
    return found


@pytest.fixture(scope="module")
def scale10():
    wg = rmat_weighted_graph(10, 16, seed=0)
    assert wg.m not in (wg.n, wg.n + 1)
    return wg


@pytest.mark.parametrize("table", ["stored", "missing"])
@pytest.mark.parametrize("program", ["_drain", "msbfs_engine_step",
                                     "_sssp_drain"])
def test_no_row_ptr_gather_inside_loops(scale10, program, table):
    wg = scale10 if table == "stored" else _by_keyword(scale10)
    if program == "_sssp_drain":
        state = ts.sssp_engine_init(wg, 2, 2, track_steps=True)
        lowered = ts._sssp_drain.lower(wg, state, 0.05, 8, "xla",
                                       ts.MAX_SSSP_STEPS)
    else:
        g = wg.csr
        state = ms.msbfs_engine_init(g, 64, 64)
        fn = ms._drain if program == "_drain" else ms.msbfs_engine_step
        lowered = fn.lower(g, state, "hybrid", 14.0, 24.0, 8, "xla")
    text = lowered.compile().as_text()
    assert " while(" in text or "conditional(" in text
    assert _looped_gathers(text, wg.n, wg.m) == []
