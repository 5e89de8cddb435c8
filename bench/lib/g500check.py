"""Graph 500 checks of a whole batch of BFS answers, on the device.

``check_batch`` judges every search key of one sweep by what its answer
says, in plain ``jax.numpy`` over the benchmark's own CSR:

1. the key has depth 0 and is its own parent;
2. every other reached vertex has depth >= 1 and a parent that is a graph
   neighbour one level up; an unreached vertex has parent -1;
3. no edge joins a reached and an unreached vertex, and no edge between
   reached vertices spans more than one level.

Together these hold exactly when the depths are the hop distances from
the key over its whole component and the parents form a BFS tree. It
also returns each key's traversed edges, ``sum(deg[v] for v reached) / 2``
counted as edge slots, from the CSR's own row offsets.

Edge slots go through in ``chunks`` blocks, so memory stays at about
``3 * m / chunks * R`` words above the answers themselves. A parent edge
is found by counting, over the first slot of each distinct neighbour,
the slots whose neighbour is the row's parent: at most one per vertex,
so the count per key equals its reached non-key vertices exactly when
every tree edge exists (no scatter).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def num_chunks(m: int, slots_per_chunk: int = 1 << 21) -> int:
    """Blocks of edge slots for ``check_batch``: a power of two that
    divides ``m`` whenever ``m`` is a power-of-two multiple."""
    chunks = 1
    while m % (2 * chunks) == 0 and m // chunks > slots_per_chunk:
        chunks *= 2
    return chunks


@partial(jax.jit, static_argnames=("chunks",))
def check_batch(row_ptr, col_idx, src_idx, depth, parent, keys, *,
                chunks: int):
    """Per key: (rule violations int32[R], component edge slots int32[R])."""
    n = row_ptr.shape[0] - 1
    m = col_idx.shape[0]
    num_keys = keys.shape[0]
    lane = jnp.arange(num_keys)
    reached = depth >= 0
    is_key = jnp.zeros((n, num_keys), jnp.bool_).at[keys, lane].set(True)

    bad_key = ((depth[keys, lane] != 0)
               | (parent[keys, lane] != keys)).astype(jnp.int32)
    up = jnp.take_along_axis(depth, jnp.clip(parent, 0, n - 1), axis=0)
    bad_tree = reached & ~is_key & ((depth < 1) | (parent < 0)
                                    | (parent >= n) | (up != depth - 1))
    bad_unreached = ~reached & (parent != -1)

    size = m // chunks
    rows = src_idx.reshape(chunks, size)
    cols = col_idx.reshape(chunks, size)
    # the first slot of each distinct neighbour: rows are sorted, so a
    # repeated edge sits right after its first copy
    prev_same = jnp.concatenate([jnp.zeros((1,), jnp.bool_),
                                 (src_idx[1:] == src_idx[:-1])
                                 & (col_idx[1:] == col_idx[:-1])])
    firsts = (~prev_same).reshape(chunks, size)

    def block(carry, rcf):
        bad_edges, tree_edges = carry
        r, c, first = rcf
        d_r, d_c = depth[r], depth[c]                      # [size, R]
        seen_r, seen_c = d_r >= 0, d_c >= 0
        spans = (seen_r != seen_c) | (seen_r & seen_c
                                      & (jnp.abs(d_r - d_c) > 1))
        bad_edges = bad_edges + spans.sum(axis=0, dtype=jnp.int32)
        # each vertex has at most one slot whose distinct neighbour is
        # its parent, so these count the vertices whose tree edge exists
        tree = (parent[r] == c[:, None]) & first[:, None] & (d_r >= 1)
        tree_edges = tree_edges + tree.sum(axis=0, dtype=jnp.int32)
        return (bad_edges, tree_edges), None

    zero = jnp.zeros((num_keys,), jnp.int32)
    (bad_edges, tree_edges), _ = jax.lax.scan(block, (zero, zero),
                                              (rows, cols, firsts))
    non_keys = (reached & ~is_key).sum(axis=0, dtype=jnp.int32)
    # a key at depth >= 1 is flagged by bad_key; abs keeps its tree edge
    # from cancelling a missing one
    violations = (bad_key + bad_edges + jnp.abs(non_keys - tree_edges)
                  + (bad_tree | bad_unreached).sum(axis=0, dtype=jnp.int32))
    deg = (row_ptr[1:] - row_ptr[:-1])[:, None]
    slots = jnp.where(reached, deg, 0).sum(axis=0, dtype=jnp.int32)
    return violations, slots
