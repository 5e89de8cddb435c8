"""Shared packed bit-lane primitives for multi-source BFS.

ONE implementation of the lane-word machinery serves both MS-BFS engines:

* the single-host engines in ``repro.core.msbfs`` (single-batch sweep and
  the pipelined root-queue engine), and
* the sharded engine in ``repro.core.dist_msbfs`` (lane words traversing a
  1-D partitioned graph, Buluc & Madduri frontier exchange applied to the
  packed representation).

The key property that makes sharing possible: every step function takes
the graph as a ``CSRGraph`` *view* and only assumes

  - ``row_ptr``/``src_idx`` index LOCAL rows (the rows this caller owns),
  - ``col_idx`` holds GLOBAL neighbour ids (indices into ``frontier``),
  - ``frontier`` covers the full global vertex range,
  - ``visited``/``need`` cover the local rows only.

On a single host "local" and "global" coincide and these are exactly the
PR-1/PR-2 formulations; under ``shard_map`` each device passes its CSR
block and the replicated full-width frontier, and the SAME code computes
that device's slice of the next frontier. Rows padded with the sentinel
column id ``frontier.shape[0]`` (the distributed edge-slab pad) are
neutralised by the ``pos < deg`` probe guard, the ``pos_e < deg`` fallback
guard, and the segmented scan's read-out points all sitting before the pad
region.

Each piece of one engine step runs under a ``jax.named_scope`` from
``STEP_SCOPES``, so the device trace can split a step (the op-name path
of every operation names its scope); the scopes are op metadata only and
leave the compiled program as it was.

``segment_scan_rows`` is the one segmented row reduction behind the
top-down step, the bottom-up fallback and the numeric semirings
(``repro.traversal.semiring``). Edge-lane values are built lane-major
(``[W, m]``): on a TPU the minor axis is tiled 128 wide, so an ``[m, W]``
array with a few lane words would take tens of times its size.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.core.csr import CSRGraph
from repro.core.hybrid import switch_direction

# the single knob of the ROADMAP uint64-lane rung: settable per process
# via the LANE_WORD_BITS env var (the CI uint64 tier-1 leg runs the whole
# engine stack under LANE_WORD_BITS=64 + JAX_ENABLE_X64=1), or swapped at
# runtime by tests (tests/test_msbfs.py lane_word_bits context manager)
LANE_WORD_BITS = int(os.environ.get("LANE_WORD_BITS", "32"))
if LANE_WORD_BITS not in (32, 64):
    raise ValueError(
        f"LANE_WORD_BITS must be 32 or 64, got {LANE_WORD_BITS}")

MODES = ("hybrid", "topdown", "bottomup")

# the named scopes of one engine step, in step order: lane refill, the
# direction counters and choice, the per-root trace rows, the top-down
# step, the bottom-up probe, its fallback scan, and the flush of finished
# lanes (with the merge of the two directions' new bits)
STEP_SCOPES = ("bfs_refill", "bfs_direction", "bfs_trace", "bfs_topdown",
               "bfs_bu_probe", "bfs_bu_fallback", "bfs_flush")


def word_dtype():
    """Lane-word dtype for the current ``LANE_WORD_BITS``. Everything
    downstream derives the dtype from here. 64-bit words hard-require jax
    x64: without it jnp silently materializes uint64 as uint32 and lanes
    32-63 of every word would vanish without an error — fail loudly,
    naming the fix."""
    if LANE_WORD_BITS == 64:
        if not jax.config.jax_enable_x64:
            raise RuntimeError(
                'LANE_WORD_BITS=64 requires jax x64 — run with '
                'jax.config.update("jax_enable_x64", True) (or set '
                'JAX_ENABLE_X64=1) before any jax call; without it '
                'uint64 lane words silently downcast to uint32 and '
                'lanes 32-63 of every word are lost')
        return jnp.uint64
    return jnp.uint32


def num_lane_words(num_roots: int) -> int:
    return (num_roots + LANE_WORD_BITS - 1) // LANE_WORD_BITS


def pack_lanes(mask: jnp.ndarray) -> jnp.ndarray:
    """Pack bool[..., R] lane masks into uint[..., W] words (LSB-first)."""
    r = mask.shape[-1]
    w = num_lane_words(r)
    dt = word_dtype()
    pad = w * LANE_WORD_BITS - r
    if pad:
        mask = jnp.concatenate(
            [mask, jnp.zeros(mask.shape[:-1] + (pad,), mask.dtype)], axis=-1)
    lanes = mask.reshape(mask.shape[:-1] + (w, LANE_WORD_BITS))
    weights = jnp.asarray(1, dt) << jnp.arange(LANE_WORD_BITS, dtype=dt)
    return (lanes.astype(dt) * weights).sum(axis=-1, dtype=dt)


def unpack_lanes(words: jnp.ndarray, num_roots: int) -> jnp.ndarray:
    """Unpack uint[..., W] lane words into bool[..., R]."""
    dt = words.dtype
    shifts = jnp.arange(LANE_WORD_BITS, dtype=dt)
    bits = (words[..., None] >> shifts) & jnp.asarray(1, dt)
    flat = bits.reshape(words.shape[:-1] + (-1,))
    return flat[..., :num_roots].astype(jnp.bool_)


def depth_slice_words(depth: jnp.ndarray, max_depth,
                      min_depth=0) -> jnp.ndarray:
    """Re-pack per-lane depths into frontier-style lane words, sliced to
    the band ``min_depth <= depth <= max_depth``.

    ``depth`` is the engines' int32[n, R] output (-1 unreached); the result
    is uint[n, W] in the SAME bit layout the engines traverse with —
    bit ``r % LANE_WORD_BITS`` of word ``r // LANE_WORD_BITS``. This is the
    k-hop / reachability read-out surface: ``max_depth=k`` yields the
    packed k-hop neighbourhood of every lane root at once, and
    ``min_depth=max_depth=d`` reconstructs the layer-``d`` frontier.
    """
    return pack_lanes((depth >= min_depth) & (depth <= max_depth))


def segment_scan_rows(vals: jnp.ndarray, row_ptr: jnp.ndarray,
                      src_idx: jnp.ndarray, add, zero) -> jnp.ndarray:
    """Per-CSR-row ``add``-reduction of LANE-MAJOR edge values
    ``[L, m] -> [L, n]``; empty rows produce ``zero``. ``src_idx[e]`` is
    the row that owns edge slot ``e``.

    CSR rows are contiguous runs of edge slots, so this is a segmented
    inclusive scan read out at each row's last slot. It runs as a
    Hillis-Steele doubling loop: step ``s`` folds slot ``i - s`` into slot
    ``i`` while both sit in one row, so ``ceil(log2(longest row))`` steps
    of whole-array ``add`` finish it. Every step has the same shape and the
    edge axis stays the minor (densely tiled) one on a TPU. A slot's place
    in its row comes from ``src_idx``, not from a cumulative max over the
    row starts: on a TPU that scan over millions of slots takes most of a
    minute to compile. ``add`` must be associative and commutative; for OR
    and min the result is exact. Slots past ``row_ptr[-1]`` (distributed
    edge-slab padding) only feed later slots, all past every read-out
    point.
    """
    m = vals.shape[-1]
    n = row_ptr.shape[0] - 1
    if m == 0:
        return jnp.full(vals.shape[:-1] + (n,), zero, vals.dtype)
    back = jnp.arange(m, dtype=jnp.int32) - row_ptr[src_idx]  # place in row
    deg = row_ptr[1:] - row_ptr[:-1]
    longest = jnp.max(deg)

    def double(c):
        step, x = c
        return 2 * step, jnp.where(back >= step,
                                   add(x, jnp.roll(x, step, axis=-1)), x)

    _, scanned = jax.lax.while_loop(lambda c: c[0] < longest, double,
                                    (jnp.int32(1), vals))
    last = jnp.clip(row_ptr[1:] - 1, 0, m - 1)
    return jnp.where(deg > 0, scanned[..., last],
                     jnp.asarray(zero, vals.dtype))


def slot_rows(row_ptr: jnp.ndarray, m: int) -> jnp.ndarray:
    """int32[m] row of each edge slot, from ``row_ptr`` alone (slots past
    ``row_ptr[-1]`` land on the last row)."""
    n = row_ptr.shape[0] - 1
    return jnp.repeat(jnp.arange(n, dtype=jnp.int32),
                      row_ptr[1:] - row_ptr[:-1], total_repeat_length=m)


def gather_lanes(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``table[idx].T`` built lane by lane: ``[n, L]`` rows gathered at
    ``idx[m]`` as a lane-major ``[L, m]`` array. One 1-D gather per lane
    column; a single batched gather would materialise ``[m, L]`` first."""
    return jnp.stack([table[:, lane][idx] for lane in range(table.shape[1])])


def segment_or(vals: jnp.ndarray, row_ptr: jnp.ndarray) -> jnp.ndarray:
    """Per-CSR-row bitwise OR of uint32[m, W] edge-lane words -> uint32[n, W]
    (``segment_scan_rows`` in the engines' row-major layout)."""
    return segment_scan_rows(vals.T, row_ptr,
                             slot_rows(row_ptr, vals.shape[0]),
                             jnp.bitwise_or, 0).T


def probe_xla(g: CSRGraph, frontier: jnp.ndarray, need: jnp.ndarray,
              max_pos: int) -> jnp.ndarray:
    """Word-packed MAX_POS probe, XLA formulation (static unroll).

    For each local vertex, OR the lane words of its first ``max_pos``
    neighbours, retiring the gather once every needed lane has found a
    parent. ``pos < deg`` keeps the gather inside real adjacency (pad
    slots are never read). The result must be masked with ``need`` by the
    caller.
    """
    m = g.m
    starts = g.row_ptr[:-1]
    deg = g.deg
    acc = jnp.zeros_like(need)
    for pos in range(max_pos):
        live = ((need & ~acc) != 0).any(axis=-1) & (pos < deg)
        vadj = g.col_idx[jnp.clip(starts + pos, 0, m - 1)]
        acc = acc | jnp.where(live[:, None], frontier[vadj],
                              jnp.zeros((), frontier.dtype))
    return acc


def bottomup_packed_step(g: CSRGraph, frontier: jnp.ndarray,
                         visited: jnp.ndarray, bu_sel: jnp.ndarray,
                         max_pos: int, probe_impl: str):
    """Packed bottom-up: probe + lax.cond-skipped segmented-scan fallback.
    Returns new frontier bits for bottom-up lanes (already & ~visited) and
    a bool scalar: whether the fallback scan ran (some row past
    ``max_pos`` still needed a parent)."""
    with jax.named_scope("bfs_bu_probe"):
        need = (~visited) & bu_sel
        if probe_impl == "pallas":
            from repro.kernels import msbfs_probe
            acc = msbfs_probe(g.row_ptr, g.col_idx, frontier, need,
                              max_pos=max_pos)
        else:
            acc = probe_xla(g, frontier, need, max_pos)
        found = acc & need

        residue = ((need & ~found) != 0).any(axis=-1) & (g.deg > max_pos)

    def run_fallback(found):
        pos_e = jnp.arange(g.m, dtype=jnp.int32) - g.row_ptr[g.src_idx]
        # pos_e < deg keeps pad slots (distributed slab tail) inert: their
        # src row is already full, so they never contribute
        act = (residue[g.src_idx] & (pos_e >= max_pos)
               & (pos_e < g.deg[g.src_idx]))
        contrib = jnp.where(act, gather_lanes(frontier, g.col_idx),
                            jnp.zeros((), frontier.dtype))       # [W, m]
        return found | (segment_scan_rows(contrib, g.row_ptr, g.src_idx,
                                          jnp.bitwise_or, 0).T & need)

    with jax.named_scope("bfs_bu_fallback"):
        ran = jnp.any(residue)
        return jax.lax.cond(ran, run_fallback, lambda f: f, found), ran


def topdown_packed_step(g: CSRGraph, frontier: jnp.ndarray,
                        visited: jnp.ndarray,
                        td_sel: jnp.ndarray) -> jnp.ndarray:
    """Packed top-down: every edge lane forwards its col-side frontier words
    (masked to top-down lanes); per-row segmented OR gathers them. On the
    symmetrised Graph500 graphs this is exactly the TD expansion — the row
    owner collects from neighbours whose frontier bit is set."""
    with jax.named_scope("bfs_topdown"):
        col = jnp.clip(g.col_idx, 0, frontier.shape[0] - 1)
        contrib = gather_lanes(frontier, col) & td_sel[:, None]   # [W, m]
        return segment_scan_rows(contrib, g.row_ptr, g.src_idx,
                                 jnp.bitwise_or, 0).T & ~visited


def lane_counters(g: CSRGraph, frontier_b: jnp.ndarray,
                  visited_b: jnp.ndarray):
    """Per-lane (e_f, v_f, e_u) from unpacked bool[n, R] state. Under
    sharding these are per-device partials the caller psums."""
    deg = g.deg.astype(jnp.int32)[:, None]
    # int32 accumulators even under x64 (the u64 lane-word rung): the
    # trace buffers are int32 and m < 2**31 is enforced at build time
    e_f = jnp.sum(jnp.where(frontier_b, deg, 0), axis=0, dtype=jnp.int32)
    v_f = jnp.sum(frontier_b, axis=0, dtype=jnp.int32)
    e_u = jnp.sum(jnp.where(visited_b, 0, deg), axis=0, dtype=jnp.int32)
    return e_f, v_f, e_u


def select_direction(mode: str, topdown_prev: jnp.ndarray, e_f, v_f, e_u,
                     n: int, alpha: float, beta: float,
                     lanes: int) -> jnp.ndarray:
    """Per-lane TD/BU decision for one layer — shared by all engines.
    ``n`` is the switch-rule vertex count (the ORIGINAL graph size: the
    distributed engine passes ``n_orig`` so padded vertices never skew the
    beta threshold and traces replay the serial controller exactly)."""
    if mode == "topdown":
        return jnp.ones((lanes,), jnp.bool_)
    if mode == "bottomup":
        return jnp.zeros((lanes,), jnp.bool_)
    return switch_direction(topdown_prev, e_f, v_f, e_u, n, alpha, beta)


def dispatch_packed_step(g: CSRGraph, frontier: jnp.ndarray,
                         visited: jnp.ndarray, td_sel: jnp.ndarray,
                         bu_sel: jnp.ndarray, mode: str, max_pos: int,
                         probe_impl: str) -> jnp.ndarray:
    """Run the packed TD/BU step(s) for one layer under the lane selectors
    — shared by the single-batch sweep, the pipelined engine, and the
    per-device body of the distributed engine (all three must advance
    frontiers bit-for-bit identically)."""
    return _dispatch_packed_step(g, frontier, visited, td_sel, bu_sel, mode,
                                 max_pos, probe_impl)[0]


def _dispatch_packed_step(g: CSRGraph, frontier: jnp.ndarray,
                          visited: jnp.ndarray, td_sel: jnp.ndarray,
                          bu_sel: jnp.ndarray, mode: str, max_pos: int,
                          probe_impl: str):
    """``dispatch_packed_step`` and a bool scalar: whether the bottom-up
    fallback scan ran this layer (the pipelined engine counts them)."""
    if mode == "topdown":
        return (topdown_packed_step(g, frontier, visited, td_sel),
                jnp.zeros((), jnp.bool_))
    if mode == "bottomup":
        return bottomup_packed_step(g, frontier, visited, bu_sel, max_pos,
                                    probe_impl)
    # middle layers usually have EVERY lane on one side — cond-skip the
    # other direction's O(m)/O(n*max_pos) work (the packed analog of the
    # serial controller's lax.cond)
    # the bottom-up conditional itself stays outside the scopes: its
    # branch holds both the probe and the fallback, and a scope on it would
    # put the one inside the other's path
    with jax.named_scope("bfs_direction"):
        zero = jnp.zeros_like(visited)
        any_td = jnp.any(td_sel != 0)
    with jax.named_scope("bfs_topdown"):
        new_td = jax.lax.cond(
            any_td,
            lambda: topdown_packed_step(g, frontier, visited, td_sel),
            lambda: zero)
    with jax.named_scope("bfs_direction"):
        any_bu = jnp.any(bu_sel != 0)
    new_bu, ran = jax.lax.cond(
        any_bu,
        lambda: bottomup_packed_step(g, frontier, visited, bu_sel,
                                     max_pos, probe_impl),
        lambda: (zero, jnp.zeros((), jnp.bool_)))
    with jax.named_scope("bfs_flush"):
        return new_td | new_bu, ran


def queue_claims(lane_qidx: jnp.ndarray, next_root: jnp.ndarray,
                 queued: jnp.ndarray, queue: jnp.ndarray):
    """Pending-queue claim rule of the pipelined engines: idle lanes (those
    with ``lane_qidx >= capacity``) claim consecutive pending queue slots
    in lane order. Returns ``(claim bool[L], cand int32[L], root int32[L])``
    — the slot index and root id are only meaningful where ``claim``.

    ONE implementation shared by the single-host and the sharded engine:
    their lane/queue evolution must stay bit-identical, so the claim rule
    lives here and only the seat writes are engine-specific.
    """
    cap = queue.shape[0]
    idle = lane_qidx >= cap
    rank = jnp.cumsum(idle.astype(jnp.int32)) - 1
    cand = next_root + rank
    claim = idle & (cand < queued)
    root = queue[jnp.clip(cand, 0, cap - 1)]
    return claim, cand, root


def adaptive_lane_pool(pending: int, n: int, m: int, max_lanes: int = 256,
                       state_budget_bytes: int = 64 << 20) -> int:
    """Pick the bit-lane pool width from queue depth + graph degree stats.

    The ROADMAP "adaptive lane-pool sizing" rung. Rules, in order:

    * never wider than the pending root count, rounded up to a full
      32-bit lane word (a partial word costs the same as a full one);
    * average degree tiers the width: sparse graphs run deep, layer-bound
      sweeps where refill opportunities are frequent and extra lane words
      amortise over many layers, so they earn wide pools; dense graphs
      saturate the segmented scan within a few layers, so extra words only
      inflate every gather — the pool stays near the 64-lane default;
    * capped so the packed state (frontier + visited ``uint32[n, W]`` plus
      ``int32 depth[n, lanes]``) stays inside ``state_budget_bytes``.

    Returns a positive multiple of 32 (one full lane word minimum); the
    engines clamp it down to ``ceil32(pending)`` themselves.
    """
    if n < 1:
        raise ValueError(f"need a non-empty graph, got n={n}")
    pending = max(int(pending), 1)
    avg_deg = m / n
    if avg_deg >= 16.0:
        tier_cap = 64
    elif avg_deg >= 4.0:
        tier_cap = 128
    else:
        tier_cap = max_lanes
    # bytes per lane: frontier + visited cost n/8 B each, depth costs 4n B
    per_lane = 4.25 * n
    budget_cap = max(int(state_budget_bytes / per_lane), 1)
    want = max(1, min(pending, tier_cap, budget_cap, max_lanes))
    return LANE_WORD_BITS * num_lane_words(want)
