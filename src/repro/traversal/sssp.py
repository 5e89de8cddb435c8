"""Bucketed delta-stepping SSSP — dense tropical lanes, pipelined sources.

Meyer & Sanders' delta-stepping, reformulated as lane-batched tropical
semiring relaxations so it runs on the same machinery as the packed
MS-BFS engines:

* R concurrent single-source problems occupy R dense float32 *lanes*
  (``dist[n, L]``, inf = unreached) — the numeric analog of the packed
  bit lanes; sources stream through a fixed lane pool from a pending
  queue, claimed/flushed/refilled mid-sweep with the SAME
  ``packed.queue_claims`` rule as ``msbfs_pipelined``.
* Each lane walks its buckets independently (``lane_bucket[l]``): bucket
  ``b`` holds unsettled vertices with ``dist in [b*delta, (b+1)*delta)``.
  Per engine step every lane is in one of two phases — the delta-stepping
  analog of the per-lane alpha/beta direction switch:

  - **light iteration**: relax light edges (w <= delta) from bucket
    members whose distance changed since their last relaxation (the
    request set, tracked by the ``relaxed`` flags); repeated until the
    bucket reaches fixpoint;
  - **heavy settle**: the bucket is at fixpoint — its members' distances
    are final; relax their heavy edges (w > delta) once and advance to
    the next non-empty bucket (computed directly from the unsettled
    minimum, so empty buckets cost nothing).

  Both phases are ONE masked min-plus relaxation
  (``traversal.semiring.tropical_relax``): inactive sources carry inf
  values and phase-excluded edges inf weights, so light and heavy lanes
  share each edge-parallel pass, cond-skipped when no lane is in that
  phase (exactly the TD/BU dispatch pattern of
  ``packed.dispatch_packed_step``).

With unit weights and ``delta = 1`` bucket ``b`` IS the BFS layer ``b``
frontier and the engine reproduces ``msbfs_pipelined`` depths exactly —
the boolean-semiring anchor ``tests/test_traversal.py`` pins.

Graph 500 kernel 3 asks for an SSSP tree besides the distances:
``sssp_pipelined(..., derive_parents=True)`` has the drain record, per
vertex and lane, the step at which its distance last fell, and derives
the parents after the drain in one jitted program (``_sssp_parents``).

Each piece of one engine step runs under a ``jax.named_scope`` from
``SSSP_STEP_SCOPES`` (op metadata only), and the traversal and the parent
derivation are jitted programs of their own names (``_sssp_drain``,
``_sssp_parents``), so the device trace tells them from the BFS drain.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.csr import WeightedCSRGraph
from repro.core.packed import queue_claims
from repro.traversal.semiring import INF, tropical_relax

__all__ = [
    "DEFAULT_LANES", "MAX_SSSP_STEPS", "MAX_SSSP_TRACE", "SSSPResult",
    "SSSP_STEP_SCOPES",
    "adaptive_delta", "default_delta", "sssp_engine_drain",
    "sssp_engine_enqueue", "sssp_engine_idle", "sssp_engine_init",
    "sssp_engine_result", "sssp_engine_step", "sssp_pipelined",
]

# dense float lanes cost 32x the state of packed bit lanes — the default
# pool is correspondingly narrower than MAX_LANES * words
DEFAULT_LANES = 32

# hard per-lane step bound (safety net mirroring MAX_TRACE): every light
# iteration either changes a distance or settles the bucket, so real
# workloads finish in O(buckets + light rounds) << this
MAX_SSSP_STEPS = 4096

# per-lane bucket/phase trace depth: rows are engine steps (clipped —
# steps past the buffer overwrite the last row identically on the host
# and distributed engines, so traces stay bit-comparable either way)
MAX_SSSP_TRACE = 256

# the named scopes of one engine step: lane refill; the light and the
# heavy relaxation (masks and the cond-skipped pass); bucket membership,
# the phase choice, the unsettled minimum and the bucket advance; the
# per-root trace rows; the merge of the candidates and the flush of
# finished lanes. The parent derivation runs under ``sssp_parents``.
SSSP_STEP_SCOPES = ("sssp_refill", "sssp_light", "sssp_heavy",
                    "sssp_settle", "sssp_trace", "sssp_flush")


class SSSPResult(NamedTuple):
    sources: jnp.ndarray       # int32[R] root vertex per lane
    dist: jnp.ndarray          # float32[n, R], inf unreached
    steps: jnp.ndarray         # int32[R] engine steps the lane ran
    truncated: jnp.ndarray     # bool[R] — lane hit max_steps; dist is a
    #                            PARTIAL relaxation, not shortest paths
    trace_bucket: jnp.ndarray  # int32[MAX_SSSP_TRACE, R] bucket per step
    #                            (-1 = lane idle / step never ran)
    trace_phase: jnp.ndarray   # int32[MAX_SSSP_TRACE, R] 0 light-iterate,
    #                            1 heavy-settle, -1 idle
    # int32[n, R] SSSP tree (``derive_parents=True``): a key is its own
    # parent, -1 unreached; None where not derived
    parent: jnp.ndarray | None = None
    # int32 scalars on the device: engine steps whose light / heavy
    # relaxation pass ran, and all engine steps of the sweep (the host
    # engine; None from engines that do not count them)
    light_relax_passes: jnp.ndarray | None = None
    heavy_relax_passes: jnp.ndarray | None = None
    sweep_steps: jnp.ndarray | None = None

    def reached(self) -> jnp.ndarray:
        """bool[n, R] — vertices with a finite distance per lane."""
        return jnp.isfinite(self.dist)

    def as_depth(self) -> jnp.ndarray:
        """int32[n, R] MS-BFS-style depths (-1 unreached) — exact for
        unit weights, where distance == hop count; the representation the
        boolean-anchor equivalence test compares bit-for-bit."""
        return jnp.where(jnp.isfinite(self.dist),
                         jnp.round(self.dist), -1).astype(jnp.int32)


class SSSPState(NamedTuple):
    dist: jnp.ndarray          # float32[n, L]  lane distances (inf idle)
    relaxed: jnp.ndarray       # bool[n, L]     light edges relaxed at dist
    lane_bucket: jnp.ndarray   # int32[L]       current bucket per lane
    lane_steps: jnp.ndarray    # int32[L]       steps run for the lane's root
    lane_qidx: jnp.ndarray     # int32[L]       queue slot served; capacity = idle
    queue: jnp.ndarray         # int32[capacity] enqueued source ids
    queued: jnp.ndarray        # int32 scalar
    next_root: jnp.ndarray     # int32 scalar
    sweep_steps: jnp.ndarray   # int32 scalar   total engine steps
    out_dist: jnp.ndarray      # float32[n, capacity+1] (+1 = trash column)
    out_steps: jnp.ndarray     # int32[capacity+1]  0 = unanswered
    out_truncated: jnp.ndarray  # bool[capacity+1]  lane flushed by the cap
    trace_bucket: jnp.ndarray  # int32[MAX_SSSP_TRACE, capacity+1]
    trace_phase: jnp.ndarray   # int32[MAX_SSSP_TRACE, capacity+1]
    light_relax_passes: jnp.ndarray  # int32 scalar  light passes run
    heavy_relax_passes: jnp.ndarray  # int32 scalar  heavy passes run
    # the lane step at which each distance last fell (0 at the root), for
    # the parent derivation; zero-width columns when not tracked
    dist_step: jnp.ndarray     # int32[n, L or 0]
    out_dist_step: jnp.ndarray  # int32[n, capacity+1 or 0]

    @property
    def num_lanes(self) -> int:
        return self.lane_qidx.shape[0]

    @property
    def capacity(self) -> int:
        return self.queue.shape[0]


def default_delta(wg: WeightedCSRGraph) -> float:
    """Meyer & Sanders' Theta(1/d) rule scaled to the weight range:
    ``max_w / avg_degree`` — buckets wide enough that light phases do a
    few iterations, narrow enough that heavy edges skip bucket work.
    Falls back to 1.0 on edgeless or all-zero-weight graphs (one bucket
    holds everything and light iteration degenerates to Bellman-Ford)."""
    if wg.m == 0:
        return 1.0
    w_max = float(np.asarray(wg.weights).max())
    avg_deg = wg.m / max(wg.n, 1)
    delta = w_max / max(avg_deg, 1.0)
    return delta if delta > 0 else 1.0


def adaptive_delta(wg: WeightedCSRGraph, lanes: int | None = None):
    """Bucket width from the weight HISTOGRAM, not just the range.

    ``default_delta`` is one global ``max_w / avg_deg`` width — on a
    bimodal weight distribution (many light local edges + a heavy long-
    haul mode, the classic road-network/R-MAT-with-tiers shape) that
    width lands inside the light mode, so every heavy edge spans many
    buckets and the settle phase walks them one by one. This rule finds
    the dominant gap in the log-weight histogram and, when a real
    light/heavy split exists (gap >= 4x, both modes carrying >= 5% of the
    edges), widens delta to the geometric midpoint of the gap: light
    edges stay light (few intra-bucket iterations), heavy edges cross in
    one hop (far fewer buckets). Unimodal weights see no gap and fall
    back to ``default_delta`` unchanged. Distances are delta-invariant —
    any positive width yields exact shortest paths at fixpoint — so the
    knob only moves step/bucket counts.

    With ``lanes`` the width is broadcast to a ``lanes``-tuple: the engine
    accepts per-lane deltas (a static tuple), so callers with per-source
    heuristics can hand different sources different widths.
    """
    base = default_delta(wg)
    w = np.asarray(wg.weights, np.float64).reshape(-1)
    w = w[np.isfinite(w) & (w > 0)]
    delta = base
    if w.size >= 2:
        logw = np.sort(np.log(w))
        gaps = np.diff(logw)
        k = int(np.argmax(gaps))
        heavy_frac = (logw.size - (k + 1)) / logw.size
        light_frac = (k + 1) / logw.size
        if (gaps[k] >= np.log(4.0) and heavy_frac >= 0.05
                and light_frac >= 0.05):
            mid = float(np.exp((logw[k] + logw[k + 1]) / 2.0))
            delta = max(base, mid)
    if lanes is None:
        return float(delta)
    return (float(delta),) * lanes


def _delta_lanes(delta, lanes: int) -> jnp.ndarray:
    """Per-lane bucket widths [L] from a scalar or a lanes-length tuple."""
    if isinstance(delta, tuple):
        if len(delta) != lanes:
            raise ValueError(
                f"per-lane delta needs {lanes} entries, got {len(delta)}")
        return jnp.asarray(delta, jnp.float32)
    return jnp.full((lanes,), jnp.float32(delta))


def _check_delta(delta) -> None:
    vals = delta if isinstance(delta, tuple) else (delta,)
    if len(vals) == 0 or not all(v > 0 for v in vals):
        raise ValueError(f"delta must be > 0, got {delta}")


def sssp_engine_init(wg: WeightedCSRGraph, capacity: int,
                     lanes: int = DEFAULT_LANES,
                     track_steps: bool = False) -> SSSPState:
    """Fresh SSSP engine: all lanes idle, empty source queue of
    ``capacity`` slots — the weighted mirror of ``msbfs_engine_init``.
    ``track_steps`` keeps the step at which each distance last fell, which
    the parent derivation needs (an int32 per vertex per lane and slot);
    without it those arrays have no columns and the steps skip them."""
    if capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    if lanes < 1:
        raise ValueError(f"lanes must be >= 1, got {lanes}")
    n = wg.n
    cap = capacity
    return SSSPState(
        dist=jnp.full((n, lanes), jnp.inf, jnp.float32),
        relaxed=jnp.zeros((n, lanes), jnp.bool_),
        lane_bucket=jnp.zeros((lanes,), jnp.int32),
        lane_steps=jnp.zeros((lanes,), jnp.int32),
        lane_qidx=jnp.full((lanes,), cap, jnp.int32),
        queue=jnp.zeros((cap,), jnp.int32),
        queued=jnp.int32(0),
        next_root=jnp.int32(0),
        sweep_steps=jnp.int32(0),
        out_dist=jnp.full((n, cap + 1), jnp.inf, jnp.float32),
        out_steps=jnp.zeros((cap + 1,), jnp.int32),
        out_truncated=jnp.zeros((cap + 1,), jnp.bool_),
        trace_bucket=jnp.full((MAX_SSSP_TRACE, cap + 1), -1, jnp.int32),
        trace_phase=jnp.full((MAX_SSSP_TRACE, cap + 1), -1, jnp.int32),
        light_relax_passes=jnp.int32(0),
        heavy_relax_passes=jnp.int32(0),
        dist_step=jnp.zeros((n, lanes if track_steps else 0), jnp.int32),
        out_dist_step=jnp.zeros((n, cap + 1 if track_steps else 0),
                                jnp.int32),
    )


def sssp_engine_enqueue(state: SSSPState, roots) -> SSSPState:
    """Append sources to the pending queue (host helper, mid-sweep safe) —
    same contract as ``msbfs_engine_enqueue``."""
    roots = jnp.asarray(roots, jnp.int32).reshape(-1)
    k = roots.shape[0]
    queued = int(state.queued)
    if queued + k > state.capacity:
        raise ValueError(
            f"queue overflow: {queued} queued + {k} new > capacity "
            f"{state.capacity}")
    queue = jax.lax.dynamic_update_slice(state.queue, roots,
                                         (state.queued,))
    return state._replace(queue=queue, queued=state.queued + jnp.int32(k))


def sssp_engine_idle(state: SSSPState) -> bool:
    """True when no lane is active and no enqueued source is pending."""
    return (int(state.next_root) >= int(state.queued)
            and not bool(jnp.any(state.lane_qidx < state.capacity)))


def _refill(wg: WeightedCSRGraph, s: SSSPState) -> SSSPState:
    """Claim pending queue slots for idle lanes and seat their sources at
    distance 0, bucket 0 — ``packed.queue_claims`` keeps the claim rule
    bit-identical to the MS-BFS engines'."""
    n = wg.n

    def do_refill(s: SSSPState) -> SSSPState:
        claim, cand, root = queue_claims(s.lane_qidx, s.next_root,
                                         s.queued, s.queue)
        onehot = claim[None, :] & (root[None, :]
                                   == jnp.arange(n, dtype=jnp.int32)[:, None])
        return s._replace(
            dist=jnp.where(claim[None, :],
                           jnp.where(onehot, jnp.float32(0), INF), s.dist),
            relaxed=jnp.where(claim[None, :], False, s.relaxed),
            dist_step=(jnp.where(claim[None, :], 0, s.dist_step)
                       if s.dist_step.shape[1] else s.dist_step),
            lane_bucket=jnp.where(claim, 0, s.lane_bucket),
            lane_steps=jnp.where(claim, 0, s.lane_steps),
            lane_qidx=jnp.where(claim, cand, s.lane_qidx),
            next_root=s.next_root + jnp.sum(claim, dtype=jnp.int32),
        )

    needed = jnp.any(s.lane_qidx >= s.capacity) & (s.next_root < s.queued)
    return jax.lax.cond(needed, do_refill, lambda s: s, s)


def _phase_relax(g, sel: jnp.ndarray, dist: jnp.ndarray,
                 phase_w: jnp.ndarray, max_pos: int,
                 relax_impl: str) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One cond-skipped masked relaxation: sources where ``sel``, edge
    weights ``phase_w`` (inf = excluded). Returns the min-plus candidate
    distances [n, L] (inf when the phase is empty this step) and whether
    the pass ran (a bool scalar)."""
    def run(dist):
        vals = jnp.where(sel, dist, INF)
        return tropical_relax(g, phase_w, vals, max_pos, relax_impl)

    ran = jnp.any(sel)
    return jax.lax.cond(ran, run,
                        lambda dist: jnp.full_like(dist, jnp.inf), dist), ran


def _sssp_body(wg: WeightedCSRGraph, s: SSSPState, delta,
               max_pos: int, relax_impl: str,
               max_steps: int) -> SSSPState:
    """One engine step: refill idle lanes, run the light/heavy phase each
    lane is in, settle + advance fixpoint buckets, flush finished lanes.
    Every operation runs under one of ``SSSP_STEP_SCOPES``."""
    g = wg.csr
    cap = s.capacity
    with jax.named_scope("sssp_refill"):
        s = _refill(wg, s)

    with jax.named_scope("sssp_settle"):
        d32 = _delta_lanes(delta, s.num_lanes)                # [L]
        active = s.lane_qidx < cap
        # membership is CEILING-ONLY (dist < (b+1)*delta, no lower
        # bound): already-settled vertices re-enter the mask but their
        # re-relaxations are idempotent, and no vertex can fall between
        # buckets when float32 rounding of floor(dist/delta) disagrees
        # with the boundary product — the correctness-over-thrift call
        # for the masked dense formulation, where the per-step
        # edge-parallel cost is O(m*L) regardless
        b_hi = (s.lane_bucket.astype(jnp.float32) + 1) * d32  # [L]
        in_bucket = active[None, :] & (s.dist < b_hi[None, :])  # [n, L]
        light_pending = in_bucket & ~s.relaxed

        # phase per lane: request set non-empty -> keep iterating light
        # edges; empty -> the bucket is at fixpoint, settle it (heavy
        # relax + advance)
        iterating = light_pending.any(axis=0)                 # bool[L]
        settling = active & ~iterating

    # the light/heavy edge split depends on the lane's OWN delta, but the
    # weight masks are per-edge (shared across lanes) — so lanes are
    # grouped by DISTINCT width and each group runs its own masked relax
    # pair, min-folded into the shared candidates. A scalar delta is one
    # group with an all-lanes selector: the exact relaxations of the
    # single-width engine, bit for bit.
    cand_light = jnp.full_like(s.dist, jnp.inf)
    cand_heavy = jnp.full_like(s.dist, jnp.inf)
    light_runs = heavy_runs = jnp.int32(0)
    widths = (sorted(set(delta)) if isinstance(delta, tuple)
              else [float(delta)])
    lane_widths = (delta if isinstance(delta, tuple)
                   else (float(delta),) * s.num_lanes)
    for dv in widths:
        gsel = jnp.asarray([lw == dv for lw in lane_widths], jnp.bool_)
        dv32 = jnp.float32(dv)
        with jax.named_scope("sssp_light"):
            light_w = jnp.where(wg.weights <= dv32, wg.weights, INF)
            g_light, ran = _phase_relax(
                g, light_pending & (iterating & gsel)[None, :],
                s.dist, light_w, max_pos, relax_impl)
            cand_light = jnp.minimum(cand_light, g_light)
            light_runs = light_runs + ran.astype(jnp.int32)
        with jax.named_scope("sssp_heavy"):
            heavy_w = jnp.where(wg.weights > dv32, wg.weights, INF)
            g_heavy, ran = _phase_relax(
                g, in_bucket & (settling & gsel)[None, :],
                s.dist, heavy_w, max_pos, relax_impl)
            cand_heavy = jnp.minimum(cand_heavy, g_heavy)
            heavy_runs = heavy_runs + ran.astype(jnp.int32)

    with jax.named_scope("sssp_flush"):
        new_dist = jnp.minimum(s.dist, jnp.minimum(cand_light, cand_heavy))
        changed = new_dist < s.dist
        # sources just relaxed are served at their current distance; any
        # vertex whose distance improved re-enters its bucket's request set
        relaxed2 = ((s.relaxed | (light_pending & iterating[None, :]))
                    & ~changed)

    with jax.named_scope("sssp_settle"):
        # settling lanes advance straight to the next non-empty bucket:
        # the minimum unsettled distance names it, empty buckets are
        # never visited; the max() keeps the advance strictly monotone
        # even when float32 division rounds the quotient below the
        # bucket boundary
        unsettled = jnp.where(new_dist >= b_hi[None, :], new_dist, INF)
        min_unsettled = jnp.min(unsettled, axis=0)            # [L]
        exhausted = settling & ~jnp.isfinite(min_unsettled)
        next_bucket = jnp.where(
            settling & jnp.isfinite(min_unsettled),
            jnp.maximum(jnp.floor(min_unsettled / d32).astype(jnp.int32),
                        s.lane_bucket + 1),
            s.lane_bucket)

        lane_steps2 = s.lane_steps + active.astype(jnp.int32)
        # the cap is a safety net, not an answer: a capped lane's
        # distances are a PARTIAL relaxation, so its flush is marked
        # truncated — the one bit that separates "converged" from "gave
        # up" downstream
        capped = active & (lane_steps2 >= max_steps) & ~exhausted
        finished = exhausted | capped

    # bucket/phase trace: one row per engine step of the lane's root
    # (clipped to the buffer — overwrites land identically everywhere),
    # written to the lane's OUTPUT column so finished traces persist
    with jax.named_scope("sssp_trace"):
        tr_row = jnp.clip(s.lane_steps, 0, MAX_SSSP_TRACE - 1)
        tr_col = jnp.where(active, s.lane_qidx, cap)
        trace_bucket = s.trace_bucket.at[tr_row, tr_col].set(
            jnp.where(active, s.lane_bucket, -1))
        trace_phase = s.trace_phase.at[tr_row, tr_col].set(
            jnp.where(active, jnp.where(iterating, 0, 1),
                      -1).astype(jnp.int32))

    with jax.named_scope("sssp_flush"):
        fcol = jnp.where(finished, s.lane_qidx, cap)
        out_dist = s.out_dist.at[:, fcol].set(new_dist)
        out_steps = s.out_steps.at[fcol].set(lane_steps2)
        out_truncated = s.out_truncated.at[fcol].set(capped)
        dist_step, out_dist_step = s.dist_step, s.out_dist_step
        if dist_step.shape[1]:
            # the step that wrote a distance is this one: a vertex's
            # final distance always comes from a read of an earlier step
            dist_step = jnp.where(changed, lane_steps2[None, :], dist_step)
            out_dist_step = out_dist_step.at[:, fcol].set(dist_step)

        return s._replace(
            dist=jnp.where(finished[None, :], INF, new_dist),
            relaxed=relaxed2 & ~finished[None, :],
            lane_bucket=jnp.where(finished, 0, next_bucket),
            lane_steps=jnp.where(finished, 0, lane_steps2),
            lane_qidx=jnp.where(finished, cap, s.lane_qidx),
            sweep_steps=s.sweep_steps + 1,
            out_dist=out_dist, out_steps=out_steps,
            out_truncated=out_truncated,
            trace_bucket=trace_bucket, trace_phase=trace_phase,
            light_relax_passes=s.light_relax_passes + light_runs,
            heavy_relax_passes=s.heavy_relax_passes + heavy_runs,
            dist_step=dist_step, out_dist_step=out_dist_step,
        )


@partial(jax.jit, static_argnums=(2, 3, 4, 5))
def sssp_engine_step(wg: WeightedCSRGraph, state: SSSPState, delta,
                     max_pos: int = 8, relax_impl: str = "xla",
                     max_steps: int = MAX_SSSP_STEPS) -> SSSPState:
    """Advance the SSSP engine by one phase step (streaming API).

    ``delta`` is a scalar bucket width or a per-lane tuple (static either
    way). Compiles once per (graph shape, lanes, capacity, delta); the
    serving loop interleaves ``sssp_engine_enqueue`` between steps to
    feed idle lanes mid-sweep, exactly like the MS-BFS engine it mirrors.
    """
    _check_delta(delta)
    return _sssp_body(wg, state, delta, max_pos, relax_impl, max_steps)


@partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _sssp_drain(wg: WeightedCSRGraph, state: SSSPState, delta,
                max_pos: int, relax_impl: str, max_steps: int) -> SSSPState:
    cap = state.queue.shape[0]

    def cond_fn(s: SSSPState):
        return (s.next_root < s.queued) | jnp.any(s.lane_qidx < cap)

    def body_fn(s: SSSPState):
        return _sssp_body(wg, s, delta, max_pos, relax_impl, max_steps)

    return jax.lax.while_loop(cond_fn, body_fn, state)


def sssp_engine_drain(wg: WeightedCSRGraph, state: SSSPState, delta,
                      max_pos: int = 8, relax_impl: str = "xla",
                      max_steps: int = MAX_SSSP_STEPS) -> SSSPState:
    """Step the engine until every enqueued source has been answered."""
    _check_delta(delta)
    return _sssp_drain(wg, state, delta, max_pos, relax_impl, max_steps)


def sssp_engine_result(state: SSSPState) -> SSSPResult:
    """Assemble an ``SSSPResult`` over the answered queue slots (columns
    of unanswered slots hold init values: inf distances, 0 steps).
    ``truncated`` lanes hit the ``max_steps`` cap — their distances are
    partial relaxations, NOT shortest paths (re-run with a larger delta
    or a larger cap). ``parent`` is None: ``sssp_pipelined`` derives it."""
    r = int(state.queued)
    return SSSPResult(sources=state.queue[:r],
                      dist=state.out_dist[:, :r],
                      steps=state.out_steps[:r],
                      truncated=state.out_truncated[:r],
                      trace_bucket=state.trace_bucket[:, :r],
                      trace_phase=state.trace_phase[:, :r],
                      light_relax_passes=state.light_relax_passes,
                      heavy_relax_passes=state.heavy_relax_passes,
                      sweep_steps=state.sweep_steps)


def _less(a: tuple, b: tuple) -> jnp.ndarray:
    """Elementwise lexicographic ``a < b`` over tuples of equal-shape
    arrays (floats compare as numbers, inf above every distance)."""
    less = jnp.zeros(a[0].shape, jnp.bool_)
    equal = jnp.ones(a[0].shape, jnp.bool_)
    for x, y in zip(a, b):
        less = less | (equal & (x < y))
        equal = equal & (x == y)
    return less


def _row_lexmin(keys: tuple, back: jnp.ndarray, row_ptr: jnp.ndarray):
    """Per CSR row, the lexicographic minimum of the slot tuples ``keys``
    (each ``[m]``): ``packed.segment_scan_rows``' doubling scan with a
    tuple-valued min. ``back[e]`` is slot e's place in its row. Rows with
    no slots read a neighbour's value; callers mask them by degree."""
    m = back.shape[0]
    longest = jnp.max(row_ptr[1:] - row_ptr[:-1])

    def double(c):
        step, x = c
        y = tuple(jnp.roll(k, step) for k in x)
        take = (back >= step) & _less(y, x)
        return 2 * step, tuple(jnp.where(take, b, a) for a, b in zip(x, y))

    _, scanned = jax.lax.while_loop(lambda c: c[0] < longest, double,
                                    (jnp.int32(1), keys))
    last = jnp.clip(row_ptr[1:] - 1, 0, m - 1)
    return tuple(k[last] for k in scanned)


@jax.jit
def _sssp_parents(wg: WeightedCSRGraph, dist: jnp.ndarray,
                  dist_step: jnp.ndarray, roots: jnp.ndarray) -> jnp.ndarray:
    """An SSSP tree per lane: ``int32[n, R]``, a key its own parent, -1
    unreached. Every other reached v gets a neighbour u with
    ``float32(dist[u] + w(u, v)) == dist[v]`` (a candidate): the candidate
    least by ``(dist[u], t[u], u)``, where ``t`` is ``dist_step``, the
    step at which the drain last lowered the distance (0 at the key).

    Why this cannot cycle: the key ``(dist, t)`` falls strictly along
    every parent pointer. A float32 sum never falls below its addend, so
    dist never rises toward the key. Where v has a candidate of smaller
    distance the least one is taken. Where every candidate ties with v
    (a zero weight, or one below half an ulp of the distance, so that the
    sum rounds back to dist[u]), look at the u whose read wrote dist[v]:
    its distance then was at most dist[v], and had it fallen since, u
    would now be a candidate of smaller distance; so it was already
    final, written at an earlier step, and the least ``t`` among the
    candidates is below t[v]. A plain least-id rule picks two tied
    neighbours as each other's parent.

    At the drain's fixpoint dist[v] is the row minimum of
    ``dist[u] + w``, so one lexicographic row minimum of
    ``(dist[u] + w, dist[u], u)`` finds the candidates without reading
    dist[v] at the slots: one gather of m per lane (``dist[col]``). Only
    where some row's best candidate ties with it does a second pass
    gather ``t[col]`` as well (cond-skipped, lane by lane).
    """
    n = wg.n
    with jax.named_scope("sssp_parents"):
        col, w = wg.col_idx, wg.weights
        back = jnp.arange(wg.m, dtype=jnp.int32) - wg.row_ptr[wg.src_idx]
        has_slots = wg.deg > 0
        ids = jnp.arange(n, dtype=jnp.int32)

        def lane(args):
            d, t, root = args
            at_col = d[col]
            via = at_col + w
            best, best_d, best_u = _row_lexmin((via, at_col, col), back,
                                               wg.row_ptr)
            ok = has_slots & jnp.isfinite(d) & (best == d) & (ids != root)
            parent = jnp.where(ok & (best_d < d), best_u, -1)
            tied = ok & (best_d == d)

            def untie(parent):
                _, _, _, tied_u = _row_lexmin((via, at_col, t[col], col),
                                              back, wg.row_ptr)
                return jnp.where(tied, tied_u, parent)

            parent = jax.lax.cond(jnp.any(tied), untie, lambda p: p, parent)
            return parent.at[root].set(root)

        parent = jax.lax.map(lane, (dist.T, dist_step.T, roots))  # [R, n]
        return parent.T


def sssp_pipelined(wg: WeightedCSRGraph, roots, delta=None,
                   lanes: int = DEFAULT_LANES, max_pos: int = 8,
                   relax_impl: str = "xla",
                   max_steps: int = MAX_SSSP_STEPS,
                   recorder=None, derive_parents: bool = False) -> SSSPResult:
    """Answer an arbitrary number of SSSP sources in ONE pipelined sweep.

    Sources beyond the lane pool wait in the pending queue and stream
    into lanes as they free up — no barrier between lane generations, so
    a many-bucket source never stalls shallow ones. ``delta=None`` picks
    ``default_delta(wg)``; a per-lane tuple (length == the effective lane
    count) hands each lane its own bucket width.

    ``derive_parents=True`` also returns ``parent``, an SSSP tree per
    source (``_sssp_parents``), derived after the drain; the distances
    and traces are those of a sweep without it.

    ``recorder`` (a ``repro.obs.SweepRecorder``) records a ``LayerRecord``
    per engine step by stepping instead of the fused drain (shared
    ``_sssp_body`` — distances, steps and traces bit-identical); None
    (the default) touches nothing in ``repro.obs``.
    """
    roots = jnp.asarray(roots, jnp.int32).reshape(-1)
    num_roots = roots.shape[0]
    if num_roots < 1:
        raise ValueError("need at least one source")
    if delta is None:
        delta = default_delta(wg)
    lanes = max(1, min(lanes, num_roots))
    delta = delta if isinstance(delta, tuple) else float(delta)
    state = sssp_engine_init(wg, capacity=num_roots, lanes=lanes,
                             track_steps=derive_parents)
    state = sssp_engine_enqueue(state, roots)
    if recorder is None:
        state = sssp_engine_drain(wg, state, delta, max_pos, relax_impl,
                                  max_steps)
    else:
        from repro.obs.sweeplog import drive_recorded
        state = drive_recorded(
            recorder, state,
            lambda s: sssp_engine_step(wg, s, delta, max_pos, relax_impl,
                                       max_steps),
            sssp_engine_idle, kind="sssp")
    result = sssp_engine_result(state)
    if not derive_parents:
        return result
    parent = _sssp_parents(wg, result.dist,
                           state.out_dist_step[:, :num_roots],
                           result.sources)
    return result._replace(parent=parent)
