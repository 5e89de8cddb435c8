"""Owner-aligned gather/scatter aggregation via shard_map.

The structural fix for collective-bound message passing under GSPMD
(EXPERIMENTS §Perf P2): instead of letting the SPMD partitioner schedule
the `H[senders]` gather and `segment_sum` scatter (measured ~73 GB
wire/layer/device for mace × ogb_products), do the exchange explicitly —
the same pattern as the distributed BFS bottom-up (DESIGN §3.4):

  forward : one all-gather of node features (payload = n·feat bytes)
            + one psum_scatter of the edge-owners' partial sums;
  backward: the transposes of the two collectives (psum_scatter,
            all-gather) — nothing else crosses the links.

Requires node/edge dims divisible by the mesh size (the input-spec builders
pad to multiples of 8192, divisible by both production meshes). Falls back
to the plain segment-sum path when no ambient mesh is set (CPU smoke tests
trace without a mesh) or divisibility fails.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _ambient_axes():
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names or mesh.size <= 1:
        return None, 1
    ndev = 1
    for s in dict(mesh.shape).values():
        ndev *= s
    return tuple(mesh.axis_names), ndev


def owner_gather_scatter(node_feats: jnp.ndarray, senders: jnp.ndarray,
                         receivers: jnp.ndarray, edge_data,
                         edge_fn: Callable, n_nodes: int):
    """A[v] = sum_{e: receivers[e]=v} edge_fn(node_feats[senders[e]],
    edge_data[e]).

    ``edge_data`` is a pytree of [E, ...] arrays (sharded on the edge dim by
    the caller); ``edge_fn(hj, edge_data)`` maps gathered sender features
    [E_loc, ...] + local edge data -> messages [E_loc, ...]. Returns the
    node-sharded aggregate with msgs' trailing shape.
    """
    axes, ndev = _ambient_axes()
    if (axes is None or n_nodes % ndev
            or senders.shape[0] % ndev):
        msgs = edge_fn(node_feats[senders], edge_data)
        return jax.ops.segment_sum(msgs, receivers, num_segments=n_nodes)

    def body(h_loc, snd, rcv, ed):
        h_full = jax.lax.all_gather(h_loc, axes, tiled=True)   # [n, ...]
        msgs = edge_fn(h_full[snd], ed)                        # local edges
        a_part = jnp.zeros((n_nodes,) + msgs.shape[1:], msgs.dtype)
        a_part = a_part.at[rcv].add(msgs)
        return jax.lax.psum_scatter(a_part, axes, scatter_dimension=0,
                                    tiled=True)

    spec = P(axes)   # leading dim sharded over all mesh axes jointly
    ed_specs = jax.tree.map(lambda _: spec, edge_data)
    return jax.shard_map(body, in_specs=(spec, spec, spec, ed_specs),
                         out_specs=spec, check_vma=False)(
        node_feats, senders, receivers, edge_data)
