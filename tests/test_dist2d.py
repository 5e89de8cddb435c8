"""2-D partitioned MS-BFS: the cross-configuration parity matrix.

The pinning test story of the 2-D rung: depths, parents, layer counts,
edge counters, AND per-layer TD/BU traces must be bit-identical across

  {host pipelined engine, 1-D dist engine, 2-D dist engine}
    x grid {1x1, 1x2, 2x1, 2x2, 4x1, 1x4}     (non-square included)
    x wire format {dense, compressed}
    x LANE_WORD_BITS {32, 64}                  (u64 = x64 subprocess leg)

plus streaming (mid-sweep enqueue), the shared exchange primitives, the
bytes-on-the-wire accounting (star graph: compressed bytes per layer
track the frontier population), and a guard that the 1-D engine still
rides the extracted exchange interface.

Multi-device legs run in subprocesses with forced host devices (conftest
pattern); the u64 legs re-run the SAME code under LANE_WORD_BITS=64 +
JAX_ENABLE_X64=1 via ``run_in_subprocess(env_extra=...)``.
"""
import numpy as np
import pytest

from conftest import run_in_subprocess

U64_ENV = {"LANE_WORD_BITS": "64", "JAX_ENABLE_X64": "1"}
# the u32 leg pins its env too: under the tier1-u64 CI job every
# subprocess inherits LANE_WORD_BITS=64, so the W=32 assertion only
# holds if the default width is forced back explicitly
U32_ENV = {"LANE_WORD_BITS": "32", "JAX_ENABLE_X64": "0"}


# --------------------------------------------------------------------------
# the parity matrix
# --------------------------------------------------------------------------

MATRIX_CODE = """
import sys
sys.path.insert(0, "tests")
import numpy as np
from repro.core import packed
from repro.core.dist_msbfs import dist_msbfs, host_mesh, partition_graph
from repro.core.dist2d import dist2d_msbfs, mesh2d, partition_graph_2d
from repro.core.msbfs import msbfs_pipelined
from test_msbfs_properties import build_case

FIELDS = ("depth", "parent", "num_layers", "edges_traversed",
          "trace_dir", "trace_vf", "trace_ef", "trace_eu")
GRIDS = ((1, 1), (1, 2), (2, 1), (2, 2), (4, 1), (1, 4))

for shape, seed in (("random", 3), ("two_components", 11)):
    g, _ = build_case(60, 150, seed=seed, shape=shape, self_loops=False,
                      dup_edges=False)
    roots = np.array([0, 5, 17, 33, 59], np.int32)
    want = msbfs_pipelined(g, roots, mode="hybrid")
    # 1-D engine row of the matrix
    d1 = dist_msbfs(partition_graph(g, 2), roots, host_mesh(2))
    for f in FIELDS:
        assert np.array_equal(np.asarray(getattr(d1, f)),
                              np.asarray(getattr(want, f))), ("1d", f)
    for (pr, pc) in GRIDS:
        dg = partition_graph_2d(g, pr, pc)
        mesh = mesh2d(pr, pc)
        for compress in (False, True):
            got = dist2d_msbfs(dg, roots, mesh, compress=compress)
            for f in FIELDS:
                assert np.array_equal(
                    np.asarray(getattr(got, f)),
                    np.asarray(getattr(want, f))), (shape, pr, pc,
                                                    compress, f)
print("W=%d MATRIX_OK" % packed.LANE_WORD_BITS)
"""


def test_dist2d_parity_matrix():
    out = run_in_subprocess(MATRIX_CODE, devices=4, timeout=900,
                            env_extra=U32_ENV)
    assert "W=32 MATRIX_OK" in out


def test_dist2d_parity_matrix_u64():
    out = run_in_subprocess(MATRIX_CODE, devices=4, timeout=900,
                            env_extra=U64_ENV)
    assert "W=64 MATRIX_OK" in out


# --------------------------------------------------------------------------
# forced modes + pallas probe through the 2-D exchange
# --------------------------------------------------------------------------

MODES_CODE = """
import sys
sys.path.insert(0, "tests")
import numpy as np
from repro.core import packed
from repro.core.dist2d import dist2d_msbfs, mesh2d, partition_graph_2d
from repro.core.msbfs import msbfs_pipelined
from test_msbfs_properties import build_case

g, _ = build_case(60, 150, seed=7, shape="random", self_loops=False,
                  dup_edges=False)
roots = np.array([0, 5, 17, 33, 59], np.int32)
dg = partition_graph_2d(g, 2, 2)
mesh = mesh2d(2, 2)
for mode in ("topdown", "bottomup"):
    want = msbfs_pipelined(g, roots, mode=mode)
    got = dist2d_msbfs(dg, roots, mesh, mode=mode, compress=True)
    assert np.array_equal(np.asarray(got.depth), np.asarray(want.depth)), mode
    assert np.array_equal(np.asarray(got.parent),
                          np.asarray(want.parent)), mode
# pallas probe (at LANE_WORD_BITS=64: the u64 gather path) x wire format
want = msbfs_pipelined(g, roots, mode="hybrid", probe_impl="pallas")
for compress in (False, True):
    got = dist2d_msbfs(dg, roots, mesh, probe_impl="pallas",
                       compress=compress)
    assert np.array_equal(np.asarray(got.depth), np.asarray(want.depth))
    assert np.array_equal(np.asarray(got.parent), np.asarray(want.parent))
    assert np.array_equal(np.asarray(got.trace_dir),
                          np.asarray(want.trace_dir))
print("W=%d MODES2D_OK" % packed.LANE_WORD_BITS)
"""


def test_dist2d_forced_modes_and_pallas_probe():
    out = run_in_subprocess(MODES_CODE, devices=4, timeout=900,
                            env_extra=U32_ENV)
    assert "W=32 MODES2D_OK" in out


def test_dist2d_forced_modes_and_pallas_probe_u64():
    out = run_in_subprocess(MODES_CODE, devices=4, timeout=900,
                            env_extra=U64_ENV)
    assert "W=64 MODES2D_OK" in out


# --------------------------------------------------------------------------
# streaming enqueue mid-sweep
# --------------------------------------------------------------------------

STREAM_CODE = """
import sys
sys.path.insert(0, "tests")
import numpy as np
from repro.core.dist2d import (dist2d_msbfs_engine_drain,
                               dist2d_msbfs_engine_enqueue,
                               dist2d_msbfs_engine_idle,
                               dist2d_msbfs_engine_init,
                               dist2d_msbfs_engine_result,
                               dist2d_msbfs_engine_step, mesh2d,
                               partition_graph_2d)
from repro.core.msbfs import msbfs_pipelined
from test_msbfs_properties import build_case

g, _ = build_case(60, 150, seed=5, shape="random", self_loops=False,
                  dup_edges=False)
roots = np.array([2, 9, 21, 40, 57], np.int32)
want = msbfs_pipelined(g, roots, mode="hybrid")
dg = partition_graph_2d(g, 2, 2)
mesh = mesh2d(2, 2)
s = dist2d_msbfs_engine_init(dg, mesh, capacity=5, lanes=32)
assert dist2d_msbfs_engine_idle(s)
s = dist2d_msbfs_engine_enqueue(s, roots[:2])
s = dist2d_msbfs_engine_step(dg, s, mesh, compress=True)
assert not dist2d_msbfs_engine_idle(s)
s = dist2d_msbfs_engine_enqueue(s, roots[2:])     # mid-sweep refill
s = dist2d_msbfs_engine_drain(dg, s, mesh, compress=True)
assert dist2d_msbfs_engine_idle(s)
res = dist2d_msbfs_engine_result(dg, s, mesh)
assert np.array_equal(np.asarray(res.depth), np.asarray(want.depth))
assert np.array_equal(np.asarray(res.parent), np.asarray(want.parent))
assert int(s.exch_bytes) > 0 and int(s.exch_bytes) == np.asarray(
    s.exch_log).sum()
print("STREAM2D_OK")
"""


def test_dist2d_streaming_enqueue():
    out = run_in_subprocess(STREAM_CODE, devices=4, timeout=900)
    assert "STREAM2D_OK" in out


# --------------------------------------------------------------------------
# bytes-on-the-wire accounting: compressed layers track the frontier
# --------------------------------------------------------------------------

BYTES_CODE = """
import sys
sys.path.insert(0, "tests")
import numpy as np
from repro.core.dist2d import (dist2d_msbfs_engine_drain,
                               dist2d_msbfs_engine_enqueue,
                               dist2d_msbfs_engine_init, mesh2d,
                               partition_graph_2d)
from test_msbfs_properties import build_case

mesh = mesh2d(2, 2)

def run(g, compress):
    dg = partition_graph_2d(g, 2, 2)
    s = dist2d_msbfs_engine_init(dg, mesh, capacity=1, lanes=32)
    s = dist2d_msbfs_engine_enqueue(s, [0])
    s = dist2d_msbfs_engine_drain(dg, s, mesh, compress=compress)
    return np.asarray(s.exch_log)

# star from the hub: step 0 = sparse expand ({root}) + DENSE fold (the
# 255 discovered leaves), step 1 = dense expand + near-empty fold. The
# switch is per exchange, so each compressed step undercuts dense (which
# ships graph-sized messages regardless of population) but stays in the
# same order of magnitude — only the sparse halves shrink.
g, _ = build_case(256, 0, seed=0, shape="star", self_loops=False,
                  dup_edges=False)
log_c, log_d = run(g, True), run(g, False)
live = log_d > 0
assert log_d[0] == log_d[1] and live.sum() == 2   # dense: population-blind
assert log_c[0] < log_d[0] and log_c[1] < log_d[1], (log_c, log_d)
assert log_c.sum() < log_d.sum()
# step 1's fold is near-empty while step 0's is saturated: the
# difference between the two steps is exactly the dense-vs-sparse fold
assert log_c[1] < log_c[0], (log_c,)

# path: EVERY layer's frontier and discovery is a single vertex, so with
# compression every live layer ships a few index/payload pairs — an
# order of magnitude under the population-blind dense cost
g, _ = build_case(64, 0, seed=0, shape="path", self_loops=False,
                  dup_edges=False)
log_c, log_d = run(g, True), run(g, False)
live = log_d > 0
assert (log_d[live] == log_d[0]).all()
assert (log_c[live] < log_d[0] // 4).all(), (log_c, log_d)
print("BYTES2D_OK")
"""


def test_dist2d_bytes_track_frontier_population():
    out = run_in_subprocess(BYTES_CODE, devices=4, timeout=900)
    assert "BYTES2D_OK" in out


# --------------------------------------------------------------------------
# the shared exchange interface
# --------------------------------------------------------------------------

EXCHANGE_CODE = """
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import exchange
from jax import shard_map
from repro.distributed.compression import sparse_budget

devs = np.array(jax.devices()[:4]).reshape(2, 2)
mesh = Mesh(devs, ("row", "col"))
rng = np.random.default_rng(0)
own = np.zeros((4, 8, 2), np.uint32)
own[0, 3, 1] = 7                    # grid column 0: sparse slices
own[2, 5, 0] = 9
own[1] = rng.integers(1, 2 ** 31, (8, 2), dtype=np.uint32)   # column 1:
own[3] = rng.integers(1, 2 ** 31, (8, 2), dtype=np.uint32)   # dense

def body(x):
    x = x[0]
    exp_c, b_c = exchange.exchange_expand(x, "row", compress=True)
    exp_d, b_d = exchange.exchange_expand(x, "row", compress=False)
    red_c, rb_c = exchange.exchange_reduce_or(x, "col", compress=True)
    red_d, rb_d = exchange.exchange_reduce_or(x, "col", compress=False)
    ok = (jnp.all(exp_c == exp_d) & jnp.all(red_c == red_d))
    return (ok[None], b_c[None], b_d[None], rb_c[None],
            exp_d[None], red_d[None])

spec = P(("row", "col"))
fn = shard_map(body, mesh=mesh, in_specs=spec,
               out_specs=(spec,) * 4 + (spec, spec), check_vma=False)
ok, b_c, b_d, rb_c, exp_full, red_full = jax.jit(fn)(jnp.asarray(own))
assert bool(np.asarray(ok).all())
# expand for device (i, j): concat over i' of (i', j)'s slice
for i in range(2):
    for j in range(2):
        want = np.concatenate([own[k * 2 + j] for k in range(2)])
        assert np.array_equal(np.asarray(exp_full[i * 2 + j]), want)
        wantr = own[i * 2] | own[i * 2 + 1]
        assert np.array_equal(np.asarray(red_full[i * 2 + j]), wantr)
# byte accounting: 16 words -> budget 4. column 0 ships sparse
# (2 messages x (4 + 1*(4+4)) = 24 B), column 1 over budget -> dense
# (2 x 64 = 128 B); the per-group totals are replicated within the group
b = np.asarray(b_c).reshape(2, 2)
assert (b[:, 0] == 24).all() and (b[:, 1] == 128).all(), b
assert (np.asarray(b_d) == 128).all()
# reduce groups mix one sparse + one dense slice -> pmax forces dense
assert (np.asarray(rb_c) == 128).all()
print("EXCHANGE_OK")
"""


def test_exchange_primitives_on_grid():
    """gather/expand/reduce-OR: compressed == dense content, group-local
    density switch (different grid columns take different cond branches),
    and exact wire-byte totals."""
    out = run_in_subprocess(EXCHANGE_CODE, devices=4, timeout=900)
    assert "EXCHANGE_OK" in out


def test_dist_msbfs_rides_shared_exchange():
    """The 1-D engine's allreduce-OR IS the extracted exchange primitive
    (not a stale copy), and it still matches a host OR-fold exactly."""
    from repro.core import dist_msbfs, exchange
    assert dist_msbfs.allreduce_or is exchange.allreduce_or


ONED_UNCHANGED_CODE = """
import sys
sys.path.insert(0, "tests")
import numpy as np
from repro.core.dist_msbfs import dist_msbfs, host_mesh, partition_graph
from repro.core.msbfs import msbfs_pipelined
from test_msbfs_properties import build_case

g, _ = build_case(48, 120, seed=2, shape="random", self_loops=False,
                  dup_edges=False)
roots = np.array([1, 7, 30], np.int32)
want = msbfs_pipelined(g, roots, mode="hybrid")
got = dist_msbfs(partition_graph(g, 4), roots, host_mesh(4))
for f in ("depth", "parent", "num_layers", "edges_traversed", "trace_dir"):
    assert np.array_equal(np.asarray(getattr(got, f)),
                          np.asarray(getattr(want, f))), f
print("ONED_OK")
"""


def test_dist_msbfs_results_unchanged():
    """1-D engine parity after the exchange extraction (regression guard
    for the refactor — the full 1-D suite lives in test_dist_msbfs.py)."""
    out = run_in_subprocess(ONED_UNCHANGED_CODE, devices=4, timeout=900)
    assert "ONED_OK" in out


# --------------------------------------------------------------------------
# partition + analytics facade (host-side, no subprocess)
# --------------------------------------------------------------------------

def test_partition_graph_2d_shapes_and_edges():
    """Every edge lands in exactly one block, with correct local ids."""
    from repro.core.csr import from_edges
    from repro.core.dist2d import partition_graph_2d
    rng = np.random.default_rng(4)
    src, dst = rng.integers(0, 70, 200), rng.integers(0, 70, 200)
    g = from_edges(src, dst, 70, symmetrize=True, drop_self_loops=True,
                   dedup=False)
    for pr, pc in ((1, 1), (2, 2), (2, 3), (3, 2)):
        dg = partition_graph_2d(g, pr, pc)
        assert dg.n % (pr * pc * 32) == 0
        assert dg.chunk * pr * pc == dg.n
        assert dg.row_ptr.shape == (pr * pc, dg.n_loc_r + 1)
        deg = np.asarray(dg.deg)
        # partial degrees over a row's blocks rebuild its global degree
        gdeg = np.zeros(dg.n, np.int64)
        for i in range(pr):
            for j in range(pc):
                d = i * pc + j
                gdeg[i * dg.n_loc_r:(i + 1) * dg.n_loc_r] += deg[d]
        np.testing.assert_array_equal(gdeg[:g.n], np.asarray(g.deg))
        assert gdeg[g.n:].sum() == 0
        assert int(deg.sum()) == g.m
        # local col ids decode back to the global ids
        col_loc = np.asarray(dg.col_loc)
        col_gid = np.asarray(dg.col_gid)
        for i in range(pr):
            for j in range(pc):
                d = i * pc + j
                k = int(deg[d].sum())
                loc, gid = col_loc[d, :k], col_gid[d, :k]
                assert (gid // dg.chunk % pc == j).all()
                back = (gid // (dg.chunk * pc)) * dg.chunk + gid % dg.chunk
                np.testing.assert_array_equal(loc, back)
                # pads carry the sentinels
                assert (col_loc[d, k:] == dg.n_x).all()
                assert (col_gid[d, k:] == dg.n).all()


def test_partition_graph_2d_validation():
    from repro.core.csr import from_edges
    from repro.core.dist2d import partition_graph_2d
    g = from_edges(np.array([0]), np.array([1]), 4)
    with pytest.raises(ValueError):
        partition_graph_2d(g, 0, 2)


def test_mesh_grid_mismatch_raises():
    from repro.core.csr import from_edges
    from repro.core.dist2d import (dist2d_msbfs_engine_init, mesh2d,
                                   partition_graph_2d)
    g = from_edges(np.array([0, 1]), np.array([1, 2]), 8)
    import jax
    from jax.sharding import Mesh
    dg = partition_graph_2d(g, 1, 1)
    mesh = mesh2d(1, 1)
    dist2d_msbfs_engine_init(dg, mesh, capacity=1)    # matching grid: fine
    with pytest.raises(ValueError, match="repartition"):
        dist2d_msbfs_engine_init(partition_graph_2d(g, 2, 1), mesh,
                                 capacity=1)
    with pytest.raises(ValueError, match="mesh2d"):
        dist2d_msbfs_engine_init(
            dg, Mesh(np.asarray(jax.devices()[:1]), ("data",)), capacity=1)


def test_mesh_helpers_name_platform_and_device_count():
    """Too few devices: the error names what JAX found, and the remedy
    fits that platform (forced host devices only help on the CPU)."""
    import jax
    from repro.core.dist2d import mesh2d
    from repro.core.dist_msbfs import host_mesh
    devs = jax.devices()
    found = f"found {len(devs)} {devs[0].platform} device"
    with pytest.raises(ValueError, match=found) as e1:
        host_mesh(len(devs) + 1)
    with pytest.raises(ValueError, match=found) as e2:
        mesh2d(len(devs) + 1, 1)
    for err in (e1, e2):
        assert "xla_force_host_platform_device_count" in str(err.value)

ENGINE_GRID_CODE = """
import numpy as np
from repro.analytics.engine import LaneEngine
from repro.core.csr import from_edges

rng = np.random.default_rng(1)
src, dst = rng.integers(0, 50, 140), rng.integers(0, 50, 140)
g = from_edges(src, dst, 50, symmetrize=True, drop_self_loops=True,
               dedup=False)
host = LaneEngine(g).sweep([1, 2, 3])
got = LaneEngine(g, grid=(2, 2), compress=True).sweep([1, 2, 3])
assert np.array_equal(np.asarray(got.depth), np.asarray(host.depth))
assert got.depth.shape == host.depth.shape
try:
    LaneEngine(g, grid=(2, 2), mesh=object())
    raise SystemExit("grid+mesh should have raised")
except ValueError:
    pass
try:
    LaneEngine(g, compress=True)
    raise SystemExit("compress without grid should have raised")
except ValueError:
    pass
print("ENGINE_GRID_OK")
"""


def test_lane_engine_grid_path():
    out = run_in_subprocess(ENGINE_GRID_CODE, devices=4, timeout=900)
    assert "ENGINE_GRID_OK" in out
