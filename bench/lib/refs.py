"""Plain NumPy references for the benchmark's correctness checks.

Written apart from the program and importing nothing of it, so that a
change to the program cannot move the yardstick:

* ``bfs_reference``: level-synchronous BFS from one root; parent[v] is the
  smallest-id neighbour one level up.
* ``validate_bfs_tree``: the Graph 500 BFS-tree rules (spec section on
  kernel 2 validation).
* ``multi_source_depths``: hop distances from up to 64 sources at once,
  one bit of a ``uint64`` word per source, level by level. Its
  ``max_depth`` stops early for k-hop answers.
* ``khop_band`` / ``reach_hops``: what a k-hop or reach answer must hold,
  read from those depths.

Each is tested against the program's own oracle at a small scale, so a
drift on either side shows.
"""
from __future__ import annotations

import numpy as np


class ValidationError(AssertionError):
    """A BFS tree that breaks a Graph 500 rule."""


def bfs_reference(row_ptr: np.ndarray, col_idx: np.ndarray, root: int):
    """Level-synchronous BFS; parent[v] = min-id frontier neighbour of v.

    Returns (parent, depth) int32 arrays (-1 unreached; parent[root]=root).
    """
    n = len(row_ptr) - 1
    src = np.repeat(np.arange(n), np.diff(row_ptr))
    dst = np.asarray(col_idx)
    parent = np.full(n, -1, np.int32)
    depth = np.full(n, -1, np.int32)
    parent[root] = root
    depth[root] = 0
    frontier = np.zeros(n, bool)
    visited = np.zeros(n, bool)
    frontier[root] = visited[root] = True
    layer = 0
    while frontier.any():
        active = frontier[src] & ~visited[dst]
        cand = np.full(n, n, np.int64)
        np.minimum.at(cand, dst[active], src[active])
        new = (cand < n) & ~visited
        parent[new] = cand[new]
        depth[new] = layer + 1
        visited |= new
        frontier = new
        layer += 1
    return parent, depth


def _edges_exist(row_ptr, col_idx, u, v) -> np.ndarray:
    """Is v[i] in the sorted adjacency of u[i]? (one searchsorted over the
    global key src * n + dst, which is sorted because the rows are)."""
    n = len(row_ptr) - 1
    src = np.repeat(np.arange(n, dtype=np.int64), np.diff(row_ptr))
    keys = src * n + col_idx.astype(np.int64)
    q = u.astype(np.int64) * n + v.astype(np.int64)
    pos = np.clip(np.searchsorted(keys, q), 0, len(keys) - 1)
    return keys[pos] == q


def _depths_from_parents(parent: np.ndarray, root: int,
                         max_depth: int = 64) -> np.ndarray:
    """Depth of every reached vertex by pointer doubling; raises on cycles
    or chains that do not reach the root within ``max_depth`` levels."""
    reached = parent >= 0
    ptr = np.where(reached, parent, root).astype(np.int64)
    ptr[root] = root
    dist = np.where(reached, 1, 0).astype(np.int64)
    dist[root] = 0
    rounds = 0
    while True:
        live = reached & (ptr != root)
        if not live.any():
            break
        rounds += 1
        if (1 << rounds) > 4 * max_depth:
            raise ValidationError("rule 2: parent pointers do not reach root")
        dist = dist + np.where(live, dist[ptr], 0)
        ptr = np.where(live, ptr[ptr], ptr)
    return np.where(reached, dist, -1).astype(np.int64)


def validate_bfs_tree(row_ptr: np.ndarray, col_idx: np.ndarray,
                      parent: np.ndarray, root: int) -> dict:
    """Raise ``ValidationError`` unless ``parent`` is a BFS tree of the
    root's whole component (Graph 500 rules 1-5)."""
    row_ptr = np.asarray(row_ptr)
    col_idx = np.asarray(col_idx)
    parent = np.asarray(parent)
    n = len(row_ptr) - 1
    reached = parent >= 0
    if not reached[root] or parent[root] != root:
        raise ValidationError("rule 1: root not its own parent")
    depth = _depths_from_parents(parent, root)
    tree_v = np.flatnonzero(reached & (np.arange(n) != root))
    if len(tree_v):
        tree_p = parent[tree_v]
        if not reached[tree_p].all():
            raise ValidationError("rule 2: parent of reached vertex unreached")
        if not _edges_exist(row_ptr, col_idx, tree_v, tree_p).all():
            raise ValidationError("rule 2: tree edge missing from graph")
        if not (depth[tree_v] == depth[tree_p] + 1).all():
            raise ValidationError("rule 3: tree edge does not span one level")
    src = np.repeat(np.arange(n), np.diff(row_ptr))
    dst = col_idx
    if (reached[src] & ~reached[dst]).any():
        raise ValidationError("rule 5: reached set not edge-closed")
    both = reached[src] & reached[dst]
    if both.any() and np.abs(depth[src[both]] - depth[dst[both]]).max() > 1:
        raise ValidationError("rule 4: graph edge spans >1 level")
    return {"n_reached": int(reached.sum()), "max_depth": int(depth.max())}


def multi_source_depths(row_ptr: np.ndarray, col_idx: np.ndarray,
                        sources, max_depth: int | None = None) -> np.ndarray:
    """Hop distances ``int32[n, S]`` from each of ``S <= 64`` sources
    (-1 unreached, or farther than ``max_depth`` when it is given).

    Source s is bit s of one ``uint64`` word per vertex. A level ORs the
    words of each row's neighbours (the graph is symmetric, so a row's
    neighbours are also its in-neighbours) and keeps the bits not seen
    before.
    """
    sources = np.asarray(sources, np.int64).reshape(-1)
    if not 1 <= sources.size <= 64:
        raise ValueError(f"need 1 to 64 sources, got {sources.size}")
    row_ptr = np.asarray(row_ptr, np.int64)
    col_idx = np.asarray(col_idx)
    n = len(row_ptr) - 1
    starts = row_ptr[:-1]
    has_edges = np.diff(row_ptr) > 0
    # one trailing zero word: rows that end the array reduce up to it
    gathered = np.zeros(len(col_idx) + 1, np.uint64)
    bits = np.left_shift(np.uint64(1),
                         np.arange(sources.size, dtype=np.uint64))
    frontier = np.zeros(n, np.uint64)
    np.bitwise_or.at(frontier, sources, bits)
    seen = frontier.copy()
    depth = np.full((n, sources.size), -1, np.int32)
    depth[sources, np.arange(sources.size)] = 0
    level = 0
    while frontier.any() and (max_depth is None or level < max_depth):
        level += 1
        np.take(frontier, col_idx, out=gathered[:-1])
        reach = np.bitwise_or.reduceat(gathered, starts)
        reach[~has_edges] = 0
        frontier = reach & ~seen
        seen |= frontier
        rows = np.flatnonzero(frontier)
        hit = (frontier[rows, None] & bits[None, :]) != 0
        r, s = np.nonzero(hit)
        depth[rows[r], s] = level
    return depth


def khop_band(depth_col: np.ndarray, k: int) -> np.ndarray:
    """bool[n]: the vertices within ``k`` hops (the source included)."""
    return (depth_col >= 0) & (depth_col <= k)


def reach_hops(depth_col: np.ndarray, targets) -> np.ndarray:
    """Hop distance to each target (-1 unreachable)."""
    return depth_col[np.asarray(targets, np.int64)].astype(np.int64)
