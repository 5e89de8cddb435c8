"""Graph 500 Kronecker graphs, made on the device from seeds.

The Graph 500 generator (initiator A=0.57, B=0.19, C=0.19, D=0.05;
``2**scale`` vertices, ``edgefactor * 2**scale`` edge tuples, a random
relabelling of the vertices), symmetrised into a CSR whose rows are sorted
by neighbour id. One jitted call builds the whole graph, so set-up pays no
host generation and no host-to-device copy.

The edge tuples come from one key and the relabelling from another. A
configuration that states a ``structure_seed`` takes both from it: every
run of its cells traverses one graph, and the run's seed draws only the
traffic over it (which keys, in which order, at which times). That keeps
the work of a run the same from seed to seed: the bottom-up step probes
the first neighbours of each row, in id order, and skips its fallback
pass when that probe finds every parent, so even a relabelling of one
graph moves a sweep's length by a tenth. Without a ``structure_seed`` the
run's seed gives the whole graph.

Self-loops and repeated edges are kept. Graph 500 counts them in a
search's traversed edges, and keeping them fixes the slot count at
``2 * edgefactor * 2**scale``, so every seed gives the same array shapes
and reuses one set of compiled programs. The layout is the program's CSR:
``row_ptr int32[n+1]``, ``col_idx int32[m]``, ``src_idx int32[m]``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

ABCD = (0.57, 0.19, 0.19, 0.05)


def seed_key(seed: int, stream: int) -> jax.Array:
    """A threefry key for (``seed``, ``stream``). Any non-negative seed
    works, also past 32 bits: it is hashed on the host into two words."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def host_rng(seed: int, *stream: int) -> np.random.Generator:
    """The host-side generator of one stream of a seed."""
    return np.random.default_rng([int(seed), *map(int, stream)])


@partial(jax.jit, static_argnums=(2, 3))
def kronecker_csr(edge_key: jax.Array, label_key: jax.Array, scale: int,
                  edgefactor: int):
    """``(row_ptr, col_idx, src_idx, label)`` of a symmetrised Graph 500
    graph; ``label[u]`` is the vertex id given to generated vertex u."""
    n = 1 << scale
    tuples = n * edgefactor
    a, b, c, _ = ABCD

    def level(i, ends):
        src, dst = ends
        u = jax.random.uniform(jax.random.fold_in(edge_key, i), (tuples,))
        # quadrant (0,0) w.p. A, (0,1) B, (1,0) C, (1,1) D
        src_bit = u >= a + b
        dst_bit = ((u >= a) & (u < a + b)) | (u >= a + b + c)
        return ((src << 1) | src_bit.astype(jnp.int32),
                (dst << 1) | dst_bit.astype(jnp.int32))

    zeros = jnp.zeros((tuples,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, level, (zeros, zeros))
    label = jax.random.permutation(label_key, n).astype(jnp.int32)
    src, dst = label[src], label[dst]
    rows = jnp.concatenate([src, dst])
    cols = jnp.concatenate([dst, src])
    rows, cols = jax.lax.sort((rows, cols), num_keys=2)
    row_ptr = jnp.searchsorted(rows, jnp.arange(n + 1, dtype=jnp.int32),
                               side="left").astype(jnp.int32)
    return row_ptr, cols, rows, label


def graph_for(config: dict, seed: int):
    """The graph of ``config`` (its ``scale`` and ``edgefactor``): the one
    its ``structure_seed`` gives where it states one, else ``seed``'s."""
    structure = config.get("structure_seed", seed)
    return kronecker_csr(seed_key(structure, 0), seed_key(structure, 1),
                         config["scale"], config["edgefactor"])


def search_keys(row_ptr: np.ndarray, col_idx: np.ndarray) -> np.ndarray:
    """Vertex ids a Graph 500 search may start from: those with an edge
    to another vertex (degree one or more, self-loops not counted)."""
    n = len(row_ptr) - 1
    src = np.repeat(np.arange(n, dtype=np.int32), np.diff(row_ptr))
    other = np.bincount(src[src != np.asarray(col_idx)], minlength=n)
    return np.flatnonzero(other > 0).astype(np.int32)
