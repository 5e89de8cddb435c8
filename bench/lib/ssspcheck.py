"""Graph 500 kernel-3 checks of a batch of SSSP answers, on the device.

``check_batch`` judges every search key of one sweep by what its answer
says (float32 distances, inf unreached, and int32 parents), in plain
``jax.numpy`` over the benchmark's own weighted CSR:

1. the key has distance 0 and is its own parent;
2. every other reached vertex has a parent that is a neighbour, with
   ``d[v] == float32(d[p] + w)`` over some slot of that pair; an
   unreached vertex has parent -1;
3. no edge joins a reached and an unreached vertex;
4. no slot has ``d[v] > float32(d[u] + w)`` (the distances are a
   fixpoint of every relaxation);
5. following parents (pointer jumping, ``ceil(log2 n)`` rounds) takes
   every reached vertex to its key, so the parents form a tree.

Rules 3 and 4 make the distances the shortest float32 relaxation lengths
over the key's whole component; 1, 2 and 5 make the parents an SSSP tree
of them. The exact distances are left to ``bench.lib.sssp_ref``. It also
returns each key's traversed edges, ``sum(deg[v] for v reached) / 2``
counted as edge slots, as ``g500check`` does.

Edge slots go through in ``chunks`` blocks (``g500check.num_chunks``),
so memory stays at a few words per slot and key of one block above the
answers and one bit per slot and key for rule 2.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.lib import rowscan


@partial(jax.jit, static_argnames=("chunks",))
def check_batch(row_ptr, col_idx, src_idx, weights, dist, parent, keys, *,
                chunks: int):
    """Per key: (rule violations int32[R], component edge slots int32[R])."""
    n = row_ptr.shape[0] - 1
    m = col_idx.shape[0]
    num_keys = keys.shape[0]
    lane = jnp.arange(num_keys)
    reached = jnp.isfinite(dist)
    is_key = jnp.zeros((n, num_keys), jnp.bool_).at[keys, lane].set(True)

    bad_key = ((dist[keys, lane] != 0)
               | (parent[keys, lane] != keys)).astype(jnp.int32)
    in_range = (parent >= 0) & (parent < n)
    bad_vertex = ((reached & ~is_key & ~in_range)
                  | (~reached & (parent != -1)))

    size = m // chunks

    def block(bad_edges, rcw):
        r, c, w = rcw
        d_r, d_c = dist[r], dist[c]                       # [size, R]
        bad_edges = bad_edges + ((jnp.isfinite(d_r) != jnp.isfinite(d_c))
                                 | (d_r > d_c + w[:, None])).sum(
                                     axis=0, dtype=jnp.int32)
        tree = (parent[r] == c[:, None]) & (d_r == d_c + w[:, None])
        return bad_edges, rowscan.pack(tree)

    zero = jnp.zeros((num_keys,), jnp.int32)
    bad_edges, tree_words = jax.lax.scan(
        block, zero, (src_idx.reshape(chunks, size),
                      col_idx.reshape(chunks, size),
                      weights.reshape(chunks, size)))
    tree_words = tree_words.reshape(m, -1)
    has_tree = rowscan.unpack(rowscan.row_any(tree_words, row_ptr, src_idx),
                              num_keys)
    bad_vertex = bad_vertex | (reached & ~is_key & ~has_tree)

    # pointer jumping: a vertex off the tree points at itself
    ptr = jnp.where(reached & in_range, parent,
                    jnp.arange(n, dtype=jnp.int32)[:, None])
    rounds = max(1, (n - 1).bit_length())
    ptr = jax.lax.fori_loop(
        0, rounds, lambda _, p: jnp.take_along_axis(p, p, axis=0), ptr)
    bad_vertex = bad_vertex | (reached & (ptr != keys[None, :]))

    violations = (bad_key + bad_edges
                  + bad_vertex.sum(axis=0, dtype=jnp.int32))
    deg = (row_ptr[1:] - row_ptr[:-1])[:, None]
    slots = jnp.where(reached, deg, 0).sum(axis=0, dtype=jnp.int32)
    return violations, slots
