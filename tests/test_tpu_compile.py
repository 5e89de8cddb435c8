"""The main path compiled for a TPU v5e that is described, not attached.

Each test lowers and compiles one jitted program at the scale-20 Graph500
shapes (n = 2**20 vertices, 33,552,126 directed edge slots: the
``rmat_graph(20, 16)`` CSR) for one chip of a described ``v5e:2x2``
topology, or for its 2x2 mesh, and holds the compiler's memory analysis
to the chip's 16 GB of HBM. Nothing runs: a pass says that the chip's
compiler accepts the program and that it fits.

The Pallas kernels are refused by the TPU compiler at these shapes (and
at every smaller one tried): their tests pin the refusal, and that the
engines' ``probe_impl="pallas"`` path then fails with the compiler's
reason instead of running in interpret mode or on the XLA path.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library at a time.
"""
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import dist2d as d2
from repro.core import msbfs as ms
from repro.core.csr import WeightedCSRGraph
from repro.traversal import sssp as ts

N = 1 << 20
M = 33_552_126
HBM_BYTES = 16e9
LANES = 64
REFUSED = "block shape|Only 2D gather|Shape mismatch"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    enabled = jax.config.jax_enable_compilation_cache
    # a compile for a described chip is written to an enabled persistent
    # cache but cannot be read back without one: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.fixture(scope="module")
def graph(one_chip):
    i32 = functools.partial(_shape, dtype=jnp.int32, sharding=one_chip)
    return WeightedCSRGraph(i32((N + 1,)), i32((M,)), i32((M,)),
                            _shape((M,), jnp.float32, one_chip))


def _on(tree, sharding):
    return jax.tree.map(lambda a: _shape(a.shape, a.dtype, sharding), tree)


def _fits(compiled) -> None:
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB per device"


@pytest.mark.parametrize("program", ["step", "drain"])
def test_msbfs_engine_compiles_at_scale20(graph, one_chip, program):
    g = graph.csr
    state = _on(jax.eval_shape(lambda: ms.msbfs_engine_init(g, LANES,
                                                            LANES)),
                one_chip)
    fn = ms.msbfs_engine_step if program == "step" else ms._drain
    _fits(fn.lower(g, state, "hybrid", 14.0, 24.0, 8, "xla").compile())


def test_derive_parents_compiles_at_scale20(graph, one_chip):
    depth = _shape((N, LANES), jnp.int32, one_chip)
    roots = _shape((LANES,), jnp.int32, one_chip)
    _fits(ms._derive_parents.lower(graph.csr, depth, roots).compile())


@pytest.mark.parametrize("lanes", [1, 8])
def test_sssp_step_compiles_at_scale20(graph, one_chip, lanes):
    state = _on(jax.eval_shape(lambda: ts.sssp_engine_init(graph, 8,
                                                           lanes)),
                one_chip)
    _fits(ts.sssp_engine_step.lower(graph, state, 0.1, 8, "xla",
                                    ts.MAX_SSSP_STEPS).compile())


def test_sssp_drain_with_parent_steps_compiles_at_scale20(graph, one_chip):
    state = _on(jax.eval_shape(lambda: ts.sssp_engine_init(
        graph, 2, 2, track_steps=True)), one_chip)
    _fits(ts._sssp_drain.lower(graph, state, 0.03, 8, "xla",
                               ts.MAX_SSSP_STEPS).compile())


def test_sssp_parents_compile_at_scale20(graph, one_chip):
    dist = _shape((N, 2), jnp.float32, one_chip)
    steps = _shape((N, 2), jnp.int32, one_chip)
    roots = _shape((2,), jnp.int32, one_chip)
    _fits(ts._sssp_parents.lower(graph, dist, steps, roots).compile())


def test_dist2d_step_compiles_on_2x2_mesh(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("row", "col"))
    grid = 4
    chunk = -(-N // (grid * 32)) * 32
    n_loc_r = 2 * chunk
    m_loc = M // grid * 11 // 10     # the largest block, with headroom
    i32 = functools.partial(_shape, dtype=jnp.int32,
                            sharding=NamedSharding(mesh, P(("row", "col"))))
    dg = d2.DistGraph2D(row_ptr=i32((grid, n_loc_r + 1)),
                        col_loc=i32((grid, m_loc)),
                        col_gid=i32((grid, m_loc)),
                        src_loc=i32((grid, m_loc)),
                        deg=i32((grid, n_loc_r)), n=N, n_orig=N, pr=2,
                        pc=2, chunk=chunk, m_loc=m_loc)
    state = jax.eval_shape(
        lambda: d2.dist2d_msbfs_engine_init(dg, mesh, LANES, LANES))
    state = jax.tree.map(
        lambda a, spec: _shape(a.shape, a.dtype,
                               NamedSharding(mesh, spec)),
        state, d2._state_specs_2d())
    compiled = d2._dist2d_engine_run.lower(
        dg.row_ptr, dg.col_loc, dg.src_loc, state, mesh=mesh, mode="hybrid",
        alpha=14.0, beta=24.0, max_pos=8, probe_impl="xla", n=dg.n,
        n_loc_r=dg.n_loc_r, chunk=dg.chunk, n_orig=dg.n_orig,
        compress=False, drain=False).compile()
    _fits(compiled)
    assert "all-gather" in compiled.as_text()


def _kernel_args(name, sh):
    """(pallas entry, argument shapes, static keywords) of one kernel at
    the scale-20 shapes its engine call would hand it."""
    from repro import kernels as K
    i32 = functools.partial(_shape, dtype=jnp.int32, sharding=sh)
    u32 = functools.partial(_shape, dtype=jnp.uint32, sharding=sh)
    f32 = functools.partial(_shape, dtype=jnp.float32, sharding=sh)
    plane = N // 32
    return {
        "msbfs_probe": (K.msbfs_probe_pallas,
                        (i32((N,)), i32((N,)), u32((N, 2)), i32((M,)),
                         u32((N, 2))), dict(max_pos=8)),
        "bottom_up_probe": (K.bottom_up_probe_pallas,
                            (i32((N,)), i32((N,)), i32((N,)), i32((N,)),
                             i32((M,)), u32((plane,))), dict(max_pos=8)),
        "semiring_relax": (K.semiring_relax_pallas,
                           (i32((N,)), i32((N,)), i32((M,)), f32((M,)),
                            f32((N, 8))), dict(max_pos=8)),
        "topdown_scan": (K.topdown_scan_pallas,
                         (i32((M,)), i32((M,)), u32((plane,)),
                          u32((plane,))), dict(n=N)),
        "ell_spmm": (K.ell_spmm_pallas,
                     (i32((N, 16)), i32((N, 16)), f32((N, 128))), {}),
    }[name]


@pytest.mark.parametrize("name", ["msbfs_probe", "bottom_up_probe",
                                  "semiring_relax", "topdown_scan",
                                  "ell_spmm"])
def test_pallas_kernel_refused_at_scale20(one_chip, name):
    fn, args, static = _kernel_args(name, one_chip)
    call = jax.jit(functools.partial(fn, interpret=False, **static))
    with pytest.raises((ValueError, NotImplementedError), match=REFUSED):
        call.lower(*args).compile()


@pytest.fixture
def kernels_as_on_tpu(monkeypatch):
    """The op wrappers ask ``jax.default_backend()``, which is the CPU
    here: make them choose what they choose on a TPU."""
    import repro.kernels  # noqa: F401  (binds the ops modules)
    for name in ("msbfs_probe", "semiring_relax"):
        monkeypatch.setattr(sys.modules[f"repro.kernels.{name}.ops"],
                            "interpret_default", lambda: False)


def test_msbfs_pallas_probe_fails_loudly_on_tpu(graph, one_chip,
                                                kernels_as_on_tpu):
    g = graph.csr
    state = _on(jax.eval_shape(lambda: ms.msbfs_engine_init(g, LANES,
                                                            LANES)),
                one_chip)
    with pytest.raises((ValueError, NotImplementedError), match=REFUSED):
        ms.msbfs_engine_step.lower(g, state, "bottomup", 14.0, 24.0, 8,
                                   "pallas").compile()


def test_sssp_pallas_relax_fails_loudly_on_tpu(graph, one_chip,
                                               kernels_as_on_tpu):
    state = _on(jax.eval_shape(lambda: ts.sssp_engine_init(graph, 8, 8)),
                one_chip)
    with pytest.raises((ValueError, NotImplementedError), match=REFUSED):
        ts.sssp_engine_step.lower(graph, state, 0.1, 8, "pallas",
                                  ts.MAX_SSSP_STEPS).compile()
