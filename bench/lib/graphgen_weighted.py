"""Graph 500 kernel-3 graphs: the Kronecker graph of ``bench.lib.graphgen``
with one weight per edge tuple, made on the device from seeds.

Kernel 3 uses kernel 2's generator and gives every edge tuple a weight
drawn uniformly from [0, 1). The edge tuples and the relabelling come
from the same keys as ``graphgen.graph_for``, so the CSR (``row_ptr``,
``col_idx``, ``src_idx``) is bit-identical to that of the unweighted
configuration at the same ``structure_seed``; the weights come from a
stream of their own (stream 2 of the structure seed). Both directions of
a tuple carry its weight, and self-loops and repeats are kept; the slots
of a repeated pair keep the order of their tuples (a stable sort with the
weights carried along, which the TPU compiles in about half the time of a
sort that orders by weight too).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.lib.graphgen import ABCD, seed_key

WEIGHT_STREAM = 2


@partial(jax.jit, static_argnums=(3, 4))
def weighted_kronecker_csr(edge_key: jax.Array, label_key: jax.Array,
                           weight_key: jax.Array, scale: int,
                           edgefactor: int):
    """``(row_ptr, col_idx, src_idx, weights)``: ``graphgen.kronecker_csr``'s
    graph with a float32 weight on [0, 1) per edge tuple."""
    n = 1 << scale
    tuples = n * edgefactor
    a, b, c, _ = ABCD

    # the same draws, in the same order, as graphgen.kronecker_csr
    def level(i, ends):
        src, dst = ends
        u = jax.random.uniform(jax.random.fold_in(edge_key, i), (tuples,))
        src_bit = u >= a + b
        dst_bit = ((u >= a) & (u < a + b)) | (u >= a + b + c)
        return ((src << 1) | src_bit.astype(jnp.int32),
                (dst << 1) | dst_bit.astype(jnp.int32))

    zeros = jnp.zeros((tuples,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, level, (zeros, zeros))
    label = jax.random.permutation(label_key, n).astype(jnp.int32)
    src, dst = label[src], label[dst]
    w = jax.random.uniform(weight_key, (tuples,), jnp.float32)
    rows = jnp.concatenate([src, dst])
    cols = jnp.concatenate([dst, src])
    weights = jnp.concatenate([w, w])
    rows, cols, weights = jax.lax.sort((rows, cols, weights), num_keys=2,
                                       is_stable=True)
    row_ptr = jnp.searchsorted(rows, jnp.arange(n + 1, dtype=jnp.int32),
                               side="left").astype(jnp.int32)
    return row_ptr, cols, rows, weights


def weighted_graph_for(config: dict, seed: int):
    """The weighted graph of ``config``: the tuples and labels of
    ``graphgen.graph_for(config, seed)`` and their weights."""
    structure = config.get("structure_seed", seed)
    return weighted_kronecker_csr(
        seed_key(structure, 0), seed_key(structure, 1),
        seed_key(structure, WEIGHT_STREAM), config["scale"],
        config["edgefactor"])
