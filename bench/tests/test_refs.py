"""The benchmark's plain references against the program's own oracles
at scale 10, so that a drift on either side shows."""
import numpy as np
import pytest

from bench.lib import graphgen, refs


@pytest.fixture(scope="module")
def graph():
    from repro.graph.generator import rmat_graph
    g = rmat_graph(10, 16, seed=3)
    return np.asarray(g.row_ptr), np.asarray(g.col_idx)


def test_bfs_reference_matches_program_oracle(graph):
    from repro.core.ref import bfs_reference
    row_ptr, col_idx = graph
    for root in (0, 5, 77, 1023):
        want_p, want_d = bfs_reference(row_ptr, col_idx, root)
        got_p, got_d = refs.bfs_reference(row_ptr, col_idx, root)
        np.testing.assert_array_equal(got_p, want_p)
        np.testing.assert_array_equal(got_d, want_d)


def test_validator_agrees_with_program_validator(graph):
    from repro.graph.validate import ValidationError, validate_bfs_tree
    row_ptr, col_idx = graph
    root = int(np.flatnonzero(np.diff(row_ptr) > 3)[0])
    parent, _ = refs.bfs_reference(row_ptr, col_idx, root)
    assert (refs.validate_bfs_tree(row_ptr, col_idx, parent, root)
            == validate_bfs_tree(row_ptr, col_idx, parent, root))
    bad = parent.copy()
    leaf = int(np.flatnonzero((bad >= 0) & (np.arange(bad.size) != root))[-1])
    bad[leaf] = leaf                      # a self-parent breaks the tree
    with pytest.raises(ValidationError):
        validate_bfs_tree(row_ptr, col_idx, bad, root)
    with pytest.raises(refs.ValidationError):
        refs.validate_bfs_tree(row_ptr, col_idx, bad, root)


def test_multi_source_depths_matches_program_oracle(graph):
    from repro.core.ref import bfs_queue
    row_ptr, col_idx = graph
    sources = np.arange(0, 1024, 17)[:64]
    got = refs.multi_source_depths(row_ptr, col_idx, sources)
    for j, s in enumerate(sources):
        np.testing.assert_array_equal(got[:, j],
                                      bfs_queue(row_ptr, col_idx, int(s)))
    capped = refs.multi_source_depths(row_ptr, col_idx, sources, max_depth=2)
    np.testing.assert_array_equal(capped, np.where(got <= 2, got, -1))


def test_khop_and_reach_match_run_query(graph):
    from repro.analytics.api import KHopQuery, ReachQuery, run_query
    from repro.core.csr import CSRGraph
    import jax.numpy as jnp
    row_ptr, col_idx = graph
    g = CSRGraph(jnp.asarray(row_ptr), jnp.asarray(col_idx),
                 jnp.asarray(np.repeat(np.arange(row_ptr.size - 1),
                                       np.diff(row_ptr)).astype(np.int32)))
    src, tgt = 9, 500
    depth = refs.multi_source_depths(row_ptr, col_idx, [src])[:, 0]
    for k in (1, 2, 3):
        ans = run_query(g, KHopQuery(sources=(src,), k=k))
        np.testing.assert_array_equal(ans.members(0),
                                      np.flatnonzero(refs.khop_band(depth, k)))
    hops = run_query(g, ReachQuery(sources=(src,), targets=(tgt,))).hops
    np.testing.assert_array_equal(hops.reshape(-1),
                                  refs.reach_hops(depth, [tgt]))


def test_device_graph_meets_the_references():
    cfg = {"structure_seed": 11, "scale": 9, "edgefactor": 16}
    row_ptr, col_idx, _, _ = (np.asarray(a)
                              for a in graphgen.graph_for(cfg, 2**33))
    root = int(graphgen.search_keys(row_ptr, col_idx)[0])
    parent, depth = refs.bfs_reference(row_ptr, col_idx, root)
    refs.validate_bfs_tree(row_ptr, col_idx, parent, root)
    np.testing.assert_array_equal(
        refs.multi_source_depths(row_ptr, col_idx, [root])[:, 0], depth)
