"""Graph 500 checks of a batch of hop distances without parents, on the
device.

``check_depths`` judges every search key of one sweep by its depths
alone (int32, -1 unreached), in plain ``jax.numpy`` over the benchmark's
own CSR:

1. the key has depth 0, every other reached vertex depth 1 or more;
2. no edge joins a reached and an unreached vertex, and no edge between
   reached vertices spans more than one level;
3. every reached vertex but the key has a neighbour one level up.

Together these hold exactly when the depths are the hop distances from
the key over its whole component: by 3 a vertex at depth k has a path of
k edges to the key, and by 2 no path is shorter. It also returns each
key's traversed edges (``sum(deg[v] for v reached) / 2`` as edge slots).
Edge slots go through in ``chunks`` blocks; rule 3 keeps one bit per slot
and key (``bench.lib.rowscan``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.lib import rowscan


@partial(jax.jit, static_argnames=("chunks",))
def check_depths(row_ptr, col_idx, src_idx, depth, keys, *, chunks: int):
    """Per key: (rule violations int32[R], component edge slots int32[R])."""
    n = row_ptr.shape[0] - 1
    m = col_idx.shape[0]
    num_keys = keys.shape[0]
    lane = jnp.arange(num_keys)
    reached = depth >= 0
    is_key = jnp.zeros((n, num_keys), jnp.bool_).at[keys, lane].set(True)
    bad_key = (depth[keys, lane] != 0).astype(jnp.int32)
    bad_vertex = reached & ~is_key & (depth < 1)

    size = m // chunks

    def block(bad_edges, rc):
        r, c = rc
        d_r, d_c = depth[r], depth[c]                     # [size, R]
        seen_r, seen_c = d_r >= 0, d_c >= 0
        spans = (seen_r != seen_c) | (seen_r & seen_c
                                      & (jnp.abs(d_r - d_c) > 1))
        bad_edges = bad_edges + spans.sum(axis=0, dtype=jnp.int32)
        return bad_edges, rowscan.pack(seen_c & (d_c == d_r - 1))

    zero = jnp.zeros((num_keys,), jnp.int32)
    bad_edges, up_words = jax.lax.scan(
        block, zero, (src_idx.reshape(chunks, size),
                      col_idx.reshape(chunks, size)))
    has_up = rowscan.unpack(
        rowscan.row_any(up_words.reshape(m, -1), row_ptr, src_idx),
        num_keys)
    bad_vertex = bad_vertex | (reached & ~is_key & ~has_up)
    violations = bad_key + bad_edges + bad_vertex.sum(axis=0,
                                                      dtype=jnp.int32)
    deg = (row_ptr[1:] - row_ptr[:-1])[:, None]
    slots = jnp.where(reached, deg, 0).sum(axis=0, dtype=jnp.int32)
    return violations, slots
