"""Distributed delta-stepping SSSP: the cross-engine parity + property
test matrix.

The pinning story of the distributed-SSSP rung mirrors ``test_dist2d``:
distances, per-lane step counts, truncation flags, AND the bucket/phase
traces must be bit-identical across

  {host pipelined engine, 1-D dist engine, 2-D dist engine}
    x ndev {1, 2, 4} / grid {1x2, 2x1, 2x2}
    x wire format {dense, compressed}
    x LANE_WORD_BITS {32, 64}                  (u64 = x64 subprocess leg)

over the weighted graph zoo of ``test_sssp_properties.build_case``, plus
the unit-weight boolean anchor (distributed ``as_depth()`` == distributed
MS-BFS depths), streaming (mid-sweep enqueue), the MIN-monoid exchange
primitives with exact byte totals, the bytes-on-the-wire accounting
(path graph: compressed bytes track the active relaxation frontier,
dense bytes are population-blind), weighted-partition unit tests, and
identity guards that BOTH engines ride the one shared exchange layer.

Multi-device legs run in subprocesses with forced host devices (conftest
pattern); the u64 legs re-run the SAME code under LANE_WORD_BITS=64 +
JAX_ENABLE_X64=1 via ``run_in_subprocess(env_extra=...)``.
"""
import numpy as np
import pytest

from conftest import run_in_subprocess

U64_ENV = {"LANE_WORD_BITS": "64", "JAX_ENABLE_X64": "1"}
# the u32 leg pins its env too: under the tier1-u64 CI job every
# subprocess inherits LANE_WORD_BITS=64, so "the default width" must be
# forced back explicitly for the W=32 assertion to mean anything
U32_ENV = {"LANE_WORD_BITS": "32", "JAX_ENABLE_X64": "0"}

FIELDS = ("sources", "dist", "steps", "truncated", "trace_bucket",
          "trace_phase")


# --------------------------------------------------------------------------
# the parity matrix
# --------------------------------------------------------------------------

MATRIX_CODE = """
import sys
sys.path.insert(0, "tests")
import numpy as np
from repro.core import packed
from repro.core.dist_sssp import (default_delta_dist, dist2d_sssp,
                                  dist_sssp, host_mesh, mesh2d,
                                  partition_weighted_graph,
                                  partition_weighted_graph_2d)
from repro.traversal.sssp import default_delta, sssp_pipelined
from test_sssp_properties import build_case

FIELDS = ("sources", "dist", "steps", "truncated", "trace_bucket",
          "trace_phase")
GRIDS = ((1, 2), (2, 1), (2, 2))

for shape, wm, seed in (("random", "uniform", 3),
                        ("two_components", "with_zeros", 11)):
    wg, sources, delta = build_case(48, 140, seed=seed, shape=shape,
                                    weight_model=wm, dup_edges=False)
    lanes = max(1, len(sources) // 2)     # queue refill is exercised
    want = sssp_pipelined(wg, sources, delta=delta, lanes=lanes)
    for ndev in (1, 2, 4):
        dwg = partition_weighted_graph(wg, ndev)
        assert default_delta_dist(dwg) == default_delta(wg), (shape, ndev)
        mesh = host_mesh(ndev)
        for compress in (False, True):
            got = dist_sssp(dwg, sources, mesh, delta=delta, lanes=lanes,
                            compress=compress)
            for f in FIELDS:
                assert np.array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(want, f))), (
                    "1d", shape, ndev, compress, f)
    for (pr, pc) in GRIDS:
        dwg2 = partition_weighted_graph_2d(wg, pr, pc)
        assert default_delta_dist(dwg2) == default_delta(wg), (shape, pr, pc)
        mesh = mesh2d(pr, pc)
        for compress in (False, True):
            got = dist2d_sssp(dwg2, sources, mesh, delta=delta,
                              lanes=lanes, compress=compress)
            for f in FIELDS:
                assert np.array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(want, f))), (
                    "2d", shape, pr, pc, compress, f)
print("W=%d SSSP_MATRIX_OK" % packed.LANE_WORD_BITS)
"""


def test_dist_sssp_parity_matrix():
    out = run_in_subprocess(MATRIX_CODE, devices=4, timeout=900,
                            env_extra=U32_ENV)
    assert "W=32 SSSP_MATRIX_OK" in out


def test_dist_sssp_parity_matrix_u64():
    out = run_in_subprocess(MATRIX_CODE, devices=4, timeout=900,
                            env_extra=U64_ENV)
    assert "W=64 SSSP_MATRIX_OK" in out


# --------------------------------------------------------------------------
# the boolean anchor, distributed: unit weights == distributed MS-BFS
# --------------------------------------------------------------------------

ANCHOR_CODE = """
import sys
sys.path.insert(0, "tests")
import numpy as np
from repro.core.dist_msbfs import dist_msbfs, host_mesh, partition_graph
from repro.core.dist_sssp import (dist2d_sssp, dist_sssp, mesh2d,
                                  partition_weighted_graph,
                                  partition_weighted_graph_2d)
from test_sssp_properties import build_case

wg, sources, _ = build_case(48, 140, seed=5, shape="random",
                            weight_model="unit", dup_edges=False)
src = np.asarray(sources, np.int32)
depth = np.asarray(dist_msbfs(partition_graph(wg.csr, 2), src,
                              host_mesh(2)).depth)
d1 = dist_sssp(partition_weighted_graph(wg, 2), src, host_mesh(2),
               delta=1.0, lanes=max(1, len(src) // 2))
assert np.array_equal(np.asarray(d1.as_depth()), depth)
d2 = dist2d_sssp(partition_weighted_graph_2d(wg, 2, 2), src, mesh2d(2, 2),
                 delta=1.0, lanes=max(1, len(src) // 2), compress=True)
assert np.array_equal(np.asarray(d2.as_depth()), depth)
print("SSSP_ANCHOR_OK")
"""


def test_dist_sssp_unit_weight_anchor_matches_dist_msbfs():
    out = run_in_subprocess(ANCHOR_CODE, devices=4, timeout=600)
    assert "SSSP_ANCHOR_OK" in out


# --------------------------------------------------------------------------
# streaming: mid-sweep enqueue on the 2-D engine + byte-meter identity
# --------------------------------------------------------------------------

STREAM_CODE = """
import sys
sys.path.insert(0, "tests")
import numpy as np
from repro.core.dist_sssp import (dist2d_sssp_engine_drain,
                                  dist2d_sssp_engine_enqueue,
                                  dist2d_sssp_engine_idle,
                                  dist2d_sssp_engine_init,
                                  dist2d_sssp_engine_result,
                                  dist2d_sssp_engine_step, mesh2d,
                                  partition_weighted_graph_2d)
from repro.traversal.sssp import sssp_pipelined
from test_sssp_properties import build_case

FIELDS = ("sources", "dist", "steps", "truncated", "trace_bucket",
          "trace_phase")
wg, sources, delta = build_case(48, 140, seed=9, shape="random",
                                weight_model="uniform", dup_edges=False)
sources = np.asarray(sources, np.int32)
mesh = mesh2d(2, 2)
dwg2 = partition_weighted_graph_2d(wg, 2, 2)
s = dist2d_sssp_engine_init(dwg2, mesh, capacity=len(sources), lanes=2)
s = dist2d_sssp_engine_enqueue(s, sources[:2])
s = dist2d_sssp_engine_step(dwg2, s, mesh, delta, compress=True)
s = dist2d_sssp_engine_enqueue(s, sources[2:])
while not dist2d_sssp_engine_idle(s):
    s = dist2d_sssp_engine_step(dwg2, s, mesh, delta, compress=True)
res = dist2d_sssp_engine_result(dwg2, s)
want = sssp_pipelined(wg, sources, delta=delta, lanes=2)
for f in FIELDS:
    assert np.array_equal(np.asarray(getattr(res, f)),
                          np.asarray(getattr(want, f))), f
# the scalar meter is exactly the per-step log's total
assert int(s.exch_bytes) == int(np.asarray(s.exch_log).sum())
assert int(s.exch_bytes) > 0
print("SSSP_STREAM_OK")
"""


def test_dist2d_sssp_streaming_enqueue_and_byte_meter():
    out = run_in_subprocess(STREAM_CODE, devices=4, timeout=600)
    assert "SSSP_STREAM_OK" in out


# --------------------------------------------------------------------------
# bytes on the wire: dense is population-blind, compressed tracks the
# active relaxation frontier
# --------------------------------------------------------------------------

BYTES_CODE = """
import numpy as np
from repro.core.csr import from_weighted_edges
from repro.core.dist_sssp import (dist2d_sssp_engine_enqueue,
                                  dist2d_sssp_engine_idle,
                                  dist2d_sssp_engine_init,
                                  dist2d_sssp_engine_result,
                                  dist2d_sssp_engine_step, mesh2d,
                                  partition_weighted_graph_2d)

n = 32
src = np.arange(n - 1)
wg = from_weighted_edges(src, src + 1, np.ones(n - 1), n)
mesh = mesh2d(2, 2)
dwg2 = partition_weighted_graph_2d(wg, 2, 2)
logs = {}
for compress in (False, True):
    s = dist2d_sssp_engine_init(dwg2, mesh, capacity=1, lanes=1)
    s = dist2d_sssp_engine_enqueue(s, np.array([0], np.int32))
    while not dist2d_sssp_engine_idle(s):
        s = dist2d_sssp_engine_step(dwg2, s, mesh, 1.0, compress=compress)
    res = dist2d_sssp_engine_result(dwg2, s)
    assert np.array_equal(np.asarray(res.dist)[:, 0],
                          np.arange(n, dtype=np.float32)), compress
    logs[compress] = np.asarray(s.exch_log)
log_d, log_c = logs[False], logs[True]
live = log_d > 0
assert live.sum() >= n // 2      # a path is one long chain of steps
# dense value exchange ships every entry every step: population-blind
assert (log_d[live] == log_d[live][0]).all()
# the active frontier is ~1 vertex/step: compressed stays well below
assert (log_c[live] < log_d[live][0]).all()
assert log_c[live].max() * 2 < log_d[live][0]
print("SSSP_BYTES_OK live=%d dense=%d comp_max=%d"
      % (live.sum(), log_d[live][0], log_c[live].max()))
"""


def test_dist2d_sssp_compressed_bytes_track_frontier():
    out = run_in_subprocess(BYTES_CODE, devices=4, timeout=600)
    assert "SSSP_BYTES_OK" in out


# --------------------------------------------------------------------------
# MIN-monoid exchange primitives: exact byte totals
# --------------------------------------------------------------------------

EXCHANGE_VALUES_CODE = """
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.dist_msbfs import host_mesh
from repro.core.exchange import (allreduce_min, exchange_reduce_min,
                                 gather_values)
from repro.distributed.compression import sparse_budget

mesh = host_mesh(2)
INF = np.float32(np.inf)

def run(vals, fn):
    return jax.shard_map(fn, mesh=mesh, in_specs=(P("data"),),
                         out_specs=(P("data"), P("data")),
                         check_vma=False)(vals)
# fn returns per-device (block[1, ...], bytes[1]) so both carry the
# device axis the out_specs name

# per-device [8, 2] float32 block: total=16 entries, itemsize 4
# dense bytes/group  = ndev * total * itemsize = 2 * 16 * 4 = 128
# sparse bytes/entry = count_header 4 + count * (idx 4 + payload 4)
sparse = np.full((2, 8, 2), INF, np.float32)
sparse[0, 3, 1] = 0.5
sparse[1, 6, 0] = 2.5
dense_pop = np.arange(32, dtype=np.float32).reshape(2, 8, 2)
mixed = np.full((2, 8, 2), INF, np.float32)
mixed[0, 3, 1] = 0.5
mixed[1] = 7.0                       # one dense member forces the group

assert sparse_budget(16) == 4

def fold(v):
    out = allreduce_min(v, ("data",))
    return out, jnp.zeros((1,), jnp.int32)

folded, _ = run(sparse, fold)
want = np.minimum(sparse[0], sparse[1])
assert np.array_equal(np.asarray(folded)[0], want)
assert np.array_equal(np.asarray(folded)[1], want)

for compress, pop, expect in ((False, sparse, 128),   # population-blind
                              (False, dense_pop, 128),
                              (True, sparse, 24),     # 4 + 1*8, x2 devs
                              (True, dense_pop, 128), # over budget: dense
                              (True, mixed, 128)):    # pmax group consensus
    def reduce_min(v, compress=compress):
        out, nbytes = exchange_reduce_min(v, "data", compress=compress)
        return out, nbytes.reshape(1)
    folded, nbytes = run(pop, reduce_min)
    want = np.minimum(pop[0], pop[1])
    assert np.array_equal(np.asarray(folded)[0], want), compress
    assert np.array_equal(np.asarray(folded)[1], want), compress
    assert int(np.asarray(nbytes)[0]) == expect, (compress, expect,
                                                  int(np.asarray(nbytes)[0]))

# gather keeps per-device order (the expand side of the 2-D exchange)
def gather(v):
    stacked, nbytes = gather_values(v, "data", compress=True)
    return stacked[None], nbytes.reshape(1)
stacked, nbytes = run(sparse, gather)
assert np.array_equal(np.asarray(stacked)[0][:, 0], sparse)
assert int(np.asarray(nbytes)[0]) == 24
print("SSSP_EXCHANGE_OK")
"""


def test_min_exchange_primitives_exact_bytes():
    out = run_in_subprocess(EXCHANGE_VALUES_CODE, devices=2, timeout=600)
    assert "SSSP_EXCHANGE_OK" in out


# --------------------------------------------------------------------------
# one shared exchange layer: both engines import THE SAME primitives
# --------------------------------------------------------------------------


def test_both_engines_ride_shared_exchange():
    from repro.core import dist2d, dist_msbfs, dist_sssp, exchange
    # the MS-BFS engines' OR surface is untouched by the SSSP growth
    assert dist_msbfs.allreduce_or is exchange.allreduce_or
    assert dist2d.exchange_reduce_or is exchange.exchange_reduce_or
    assert dist2d.exchange_expand is exchange.exchange_expand
    # and the SSSP engines ride the extracted MIN surface, not a copy
    assert dist_sssp.allreduce_min is exchange.allreduce_min
    assert dist_sssp.exchange_reduce_min is exchange.exchange_reduce_min
    assert dist_sssp.exchange_expand_values is exchange.exchange_expand_values


# --------------------------------------------------------------------------
# weighted partitions: slab cuts, inf pads, exact edge/weight accounting
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def wg_small():
    from repro.graph.generator import uniform_random_weighted_graph
    return uniform_random_weighted_graph(30, 90, seed=1)


def test_partition_weighted_1d_slabs(wg_small):
    from repro.core.dist_sssp import partition_weighted_graph
    wg = wg_small
    dwg = partition_weighted_graph(wg, 4)
    n_loc = dwg.n // 4
    assert dwg.n % 4 == 0 and dwg.n >= wg.n and dwg.n_orig == wg.n
    assert dwg.row_ptr.shape == (4, n_loc + 1)
    assert dwg.weights.shape == dwg.col_idx.shape == (4, dwg.m_loc)
    w = np.asarray(dwg.weights)
    fin = np.isfinite(w)
    rp = np.asarray(dwg.row_ptr)
    for d in range(4):
        k = int(rp[d, -1])
        # real edges first, inf pads after — nothing in between
        assert fin[d, :k].all() and not fin[d, k:].any()
    # slabs are contiguous cuts of the original weight array, in order
    assert int(fin.sum()) == wg.m
    flat = np.concatenate([w[d][fin[d]] for d in range(4)])
    assert np.array_equal(flat, np.asarray(wg.weights))


def test_partition_weighted_2d_blocks(wg_small):
    from repro.core.dist_sssp import partition_weighted_graph_2d
    wg = wg_small
    dwg2 = partition_weighted_graph_2d(wg, 2, 2)
    g2 = dwg2.g2
    w = np.asarray(dwg2.weights)
    assert w.shape == (4, g2.m_loc)
    assert dwg2.n == g2.n and dwg2.n_orig == wg.n
    fin = np.isfinite(w)
    rp = np.asarray(g2.row_ptr)
    for d in range(4):
        k = int(rp[d, -1])
        assert int(fin[d].sum()) == k
        assert fin[d, :k].all()
    # every edge lands in exactly one block; weights survive as a multiset
    assert int(fin.sum()) == wg.m
    assert np.array_equal(np.sort(w[fin]), np.sort(np.asarray(wg.weights)))


def test_partition_mesh_mismatch_and_bad_delta(wg_small):
    from repro.core.dist_sssp import (dist_sssp_engine_init,
                                      dist_sssp_engine_step, host_mesh,
                                      partition_weighted_graph)
    wg = wg_small
    with pytest.raises(ValueError, match="repartition"):
        dist_sssp_engine_init(partition_weighted_graph(wg, 2),
                              host_mesh(1), capacity=1)
    dwg = partition_weighted_graph(wg, 1)
    mesh = host_mesh(1)
    s = dist_sssp_engine_init(dwg, mesh, capacity=1, lanes=1)
    with pytest.raises(ValueError, match="delta"):
        dist_sssp_engine_step(dwg, s, mesh, 0.0)
    with pytest.raises(ValueError, match="delta"):
        dist_sssp_engine_step(dwg, s, mesh, (1.0, -2.0))


# --------------------------------------------------------------------------
# the LaneEngine facade dispatches weighted sweeps onto the partitions
# --------------------------------------------------------------------------

ENGINE_SSSP_CODE = """
import sys
sys.path.insert(0, "tests")
import numpy as np
from repro.analytics.engine import LaneEngine
from repro.traversal.sssp import sssp_pipelined
from test_sssp_properties import build_case

FIELDS = ("sources", "dist", "steps", "truncated", "trace_bucket",
          "trace_phase")
wg, sources, delta = build_case(48, 140, seed=13, shape="random",
                                weight_model="uniform", dup_edges=False)
sources = np.asarray(sources, np.int32)
eng1 = LaneEngine(wg, ndev=2)
eng2 = LaneEngine(wg, grid=(2, 2), compress=True)
lanes = eng1.sssp_lanes_for(len(sources))
want = sssp_pipelined(wg, sources, delta=delta, lanes=lanes)
for eng in (eng1, eng2):
    got = eng.sssp_sweep(sources, delta=delta)
    for f in FIELDS:
        assert np.array_equal(np.asarray(getattr(got, f)),
                              np.asarray(getattr(want, f))), (eng.grid, f)
    # the boolean workloads keep working on the same weighted engine
    assert np.asarray(eng.sweep(sources[:2]).depth).shape[0] == wg.n
print("ENGINE_SSSP_OK")
"""


def test_lane_engine_sssp_sweep_on_partitions():
    out = run_in_subprocess(ENGINE_SSSP_CODE, devices=4, timeout=600)
    assert "ENGINE_SSSP_OK" in out
