"""Distributed BFS on 8 fake host devices (subprocess sets XLA_FLAGS)."""
from conftest import run_in_subprocess

CODE = """
import numpy as np, jax
from repro.graph.generator import rmat_graph, sample_roots, uniform_random_graph
from repro.core.dist_bfs import partition_graph, dist_bfs
from repro.core.ref import bfs_reference
from repro.core.csr import to_numpy_adj

meshes = [jax.make_mesh((4, 2), ('data', 'model')),
          jax.make_mesh((2, 2, 2), ('pod', 'data', 'model'))]
for g in [rmat_graph(9, 8, seed=0), uniform_random_graph(333, 2000, seed=4)]:
    rp, ci = to_numpy_adj(g)
    dg = partition_graph(g, 8)
    roots = sample_roots(g, 2, seed=1)
    for mesh in meshes:
        for mode in ['hybrid', 'topdown', 'bottomup']:
            for r in roots:
                res = dist_bfs(dg, int(r), mesh, mode)
                pref, dref = bfs_reference(rp, ci, int(r))
                assert (np.asarray(res.parent) == pref).all(), (mode, int(r))
                assert (np.asarray(res.depth) == dref).all(), (mode, int(r))
                assert int(res.num_layers) >= int(dref.max())
print('DIST_OK')
"""


def test_dist_bfs_matches_oracle():
    out = run_in_subprocess(CODE, devices=8)
    assert "DIST_OK" in out


PALLAS_CODE = """
import numpy as np, jax
from repro.graph.generator import rmat_graph, sample_roots
from repro.core.dist_bfs import partition_graph, dist_bfs
from repro.core.ref import bfs_reference
from repro.core.csr import to_numpy_adj
g = rmat_graph(9, 8, seed=3)
rp, ci = to_numpy_adj(g)
mesh = jax.make_mesh((4, 2), ('data', 'model'))
dg = partition_graph(g, 8)
r = int(sample_roots(g, 1, seed=1)[0])
res = dist_bfs(dg, r, mesh, 'hybrid', probe_impl='pallas')
pref, dref = bfs_reference(rp, ci, r)
assert (np.asarray(res.parent) == pref).all()
assert (np.asarray(res.depth) == dref).all()
print('PALLAS_DIST_OK')
"""


def test_dist_bfs_pallas_probe():
    out = run_in_subprocess(PALLAS_CODE, devices=8)
    assert "PALLAS_DIST_OK" in out


OWNER_AGG_CODE = """
import jax, jax.numpy as jnp, numpy as np
from jax import set_mesh
from repro.distributed.aggregate import owner_gather_scatter

n, e, d = 64, 256, 8   # divisible by 8 devices
ks = jax.random.split(jax.random.PRNGKey(0), 4)
feats = jax.random.normal(ks[0], (n, d))
snd = jax.random.randint(ks[1], (e,), 0, n, jnp.int32)
rcv = jax.random.randint(ks[2], (e,), 0, n, jnp.int32)
w = jax.random.normal(ks[3], (e,))
fn = lambda hj, ww: hj * ww[:, None]

plain = owner_gather_scatter(feats, snd, rcv, w, fn, n)   # no mesh
mesh = jax.make_mesh((2, 2, 2), ('pod', 'data', 'model'))
with set_mesh(mesh):
    sharded = jax.jit(lambda f: owner_gather_scatter(f, snd, rcv, w, fn, n))(feats)
np.testing.assert_allclose(np.asarray(plain), np.asarray(sharded),
                           rtol=1e-5, atol=1e-5)
# grads flow through the shard_map path
with set_mesh(mesh):
    gr = jax.jit(jax.grad(lambda f: owner_gather_scatter(
        f, snd, rcv, w, fn, n).sum()))(feats)
assert np.isfinite(np.asarray(gr)).all()
print('OWNER_AGG_OK')
"""


def test_owner_gather_scatter_equivalence_and_grads():
    out = run_in_subprocess(OWNER_AGG_CODE, devices=8)
    assert "OWNER_AGG_OK" in out
