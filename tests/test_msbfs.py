"""Bit-packed MS-BFS parity: 64 packed roots == 64 serial ``bfs`` runs.

Parents use the same deterministic min-id rule as the serial steps, so the
comparison is exact array equality on parent AND depth, plus Graph500
validator equivalence. Ring/star fixtures exercise lanes that terminate at
different layers; the lane-word sweep covers R below/at/above one word.
"""
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import msbfs as ms
from repro.core import packed
from repro.core.csr import from_edges, to_numpy_adj
from repro.core.hybrid import bfs
from repro.core.msbfs import (msbfs, msbfs_engine_enqueue, msbfs_engine_idle,
                              msbfs_engine_init, msbfs_engine_result,
                              msbfs_engine_step, msbfs_pipelined, pack_lanes,
                              segment_or, unpack_lanes)
from repro.core.ref import bfs_reference
from repro.graph.generator import rmat_graph, sample_roots
from repro.graph.validate import validate_bfs_tree
from repro.kernels import msbfs_probe_pallas, msbfs_probe_ref


@pytest.fixture(scope="module")
def g_rmat():
    return rmat_graph(10, 16, seed=0)


def ring_graph(n):
    v = np.arange(n)
    return from_edges(v, (v + 1) % n, n)


def star_graph(n):
    leaves = np.arange(1, n)
    return from_edges(np.zeros(n - 1, np.int64), leaves, n)


def _assert_lanes_match_serial(g, roots, out, mode="hybrid"):
    rp, ci = to_numpy_adj(g)
    for r_i, root in enumerate(roots):
        pref, dref = bfs_reference(rp, ci, int(root))
        np.testing.assert_array_equal(np.asarray(out.depth[:, r_i]), dref,
                                      err_msg=f"lane {r_i} depth")
        np.testing.assert_array_equal(np.asarray(out.parent[:, r_i]), pref,
                                      err_msg=f"lane {r_i} parent")
        validate_bfs_tree(rp, ci, np.asarray(out.parent[:, r_i]), int(root))


@pytest.mark.parametrize("mode", ["hybrid", "topdown", "bottomup"])
def test_msbfs_matches_serial_rmat(g_rmat, mode):
    """Full 64-lane batch on R-MAT == 64 serial runs, all controller modes."""
    roots = sample_roots(g_rmat, 64, seed=1)
    out = msbfs(g_rmat, jnp.asarray(roots), mode)
    _assert_lanes_match_serial(g_rmat, roots, out, mode)


@pytest.mark.parametrize("num_roots", [1, 5, 32, 33])
def test_msbfs_lane_word_sweep(g_rmat, num_roots):
    """R below / at / above one 32-bit lane word."""
    roots = sample_roots(g_rmat, num_roots, seed=2)
    out = msbfs(g_rmat, jnp.asarray(roots), "hybrid")
    assert out.parent.shape == (g_rmat.n, num_roots)
    _assert_lanes_match_serial(g_rmat, roots, out)


def test_msbfs_lanes_terminate_at_different_layers():
    """Star (eccentricity 1-2) and ring (eccentricity n/2) lanes packed in
    one batch: per-lane num_layers must match the serial loop count even
    though the sweep keeps running for the deepest lane."""
    n = 48
    ring = ring_graph(n)
    roots = np.array([0, 1, n // 2, n - 1])
    out = msbfs(ring, jnp.asarray(roots), "hybrid")
    _assert_lanes_match_serial(ring, roots, out)
    for r_i, root in enumerate(roots):
        s = bfs(ring, int(root), "hybrid")
        assert int(out.num_layers[r_i]) == int(s.num_layers)
        assert int(out.edges_traversed[r_i]) == int(s.edges_traversed)

    star = star_graph(n)
    roots = np.array([0, 1, 2, n - 1])     # center lane ends 2 layers early
    out = msbfs(star, jnp.asarray(roots), "hybrid")
    _assert_lanes_match_serial(star, roots, out)
    layers = [int(x) for x in out.num_layers]
    assert layers[0] < layers[1], "center lane must terminate first"
    # idle lanes show -1 in the trace once their frontier empties
    dirs = np.asarray(out.trace_dir)
    assert (dirs[layers[0]:layers[1], 0] == -1).all()
    assert (dirs[:layers[1] - 1, 1] != -1).all()


def test_msbfs_per_lane_trace_matches_serial(g_rmat):
    """Per-lane switching replays the serial alpha/beta decisions: the
    lane's TD/BU trace equals the serial trace for the same root."""
    roots = sample_roots(g_rmat, 8, seed=3)
    out = msbfs(g_rmat, jnp.asarray(roots), "hybrid")
    for r_i, root in enumerate(roots):
        s = bfs(g_rmat, int(root), "hybrid")
        nl = int(s.num_layers)
        np.testing.assert_array_equal(
            np.asarray(out.trace_dir[:nl, r_i]),
            np.asarray(s.trace_dir[:nl]), err_msg=f"lane {r_i} trace_dir")
        np.testing.assert_array_equal(np.asarray(out.trace_vf[:nl, r_i]),
                                      np.asarray(s.trace_vf[:nl]))
        np.testing.assert_array_equal(np.asarray(out.trace_ef[:nl, r_i]),
                                      np.asarray(s.trace_ef[:nl]))
        np.testing.assert_array_equal(np.asarray(out.trace_eu[:nl, r_i]),
                                      np.asarray(s.trace_eu[:nl]))


def test_msbfs_pallas_probe_end_to_end(g_rmat):
    # runs at either LANE_WORD_BITS: 64-bit words take the kernel's u64
    # gather path (hi/lo uint32 half-planes) — the tier1-u64 CI leg
    # exercises this test with zero skips
    roots = sample_roots(g_rmat, 40, seed=4)
    out = msbfs(g_rmat, jnp.asarray(roots), "hybrid", 14.0, 24.0, 8,
                "pallas")
    _assert_lanes_match_serial(g_rmat, roots, out)


@contextmanager
def lane_word_bits(bits):
    """Run packed-word code under a different ``packed.LANE_WORD_BITS`` —
    the single knob of the ROADMAP uint64-lane rung. The packed helpers
    read the constant (and derive the word dtype) at call time, so the
    swap is a plain module-global override; 64-bit words additionally
    need jax x64 (without it jnp silently downcasts uint64 to uint32)."""
    old = packed.LANE_WORD_BITS
    packed.LANE_WORD_BITS = bits
    try:
        if bits == 64:
            with jax.enable_x64(True):
                yield
        else:
            yield
    finally:
        packed.LANE_WORD_BITS = old


def test_word_dtype_x64_guard_names_fix():
    """64-bit lane words without jax x64 must fail loudly (a silent
    uint64->uint32 downcast drops lanes 32-63), and the error must NAME
    the fix — the exact config call to run."""
    old = packed.LANE_WORD_BITS
    packed.LANE_WORD_BITS = 64
    try:
        with jax.enable_x64(False):
            with pytest.raises(RuntimeError) as exc:
                packed.word_dtype()
    finally:
        packed.LANE_WORD_BITS = old
    msg = str(exc.value)
    assert 'jax.config.update("jax_enable_x64", True)' in msg
    assert "JAX_ENABLE_X64" in msg


@pytest.mark.parametrize("bits", [32, 64])
def test_pack_unpack_roundtrip(bits):
    with lane_word_bits(bits):
        rng = np.random.default_rng(0)
        for r in (1, bits - 1, bits, bits + 1, 2 * bits):
            mask = jnp.asarray(rng.random((17, r)) < 0.5)
            words = pack_lanes(mask)
            assert words.dtype == packed.word_dtype()
            assert words.shape == (17, packed.num_lane_words(r))
            np.testing.assert_array_equal(np.asarray(unpack_lanes(words, r)),
                                          np.asarray(mask))


@pytest.mark.parametrize("bits", [32, 64])
def test_pack_lanes_top_bit(bits):
    """Lane ``bits - 1`` must land in the word's MSB — the first bit a
    32-bit-assuming shift would lose at 64-bit words."""
    with lane_word_bits(bits):
        mask = jnp.zeros((3, bits), jnp.bool_).at[1, bits - 1].set(True)
        words = pack_lanes(mask)
        assert words.shape == (3, 1)
        expect = np.zeros((3, 1), np.uint64)
        expect[1, 0] = np.uint64(1) << np.uint64(bits - 1)
        np.testing.assert_array_equal(np.asarray(words).astype(np.uint64),
                                      expect)


@pytest.mark.parametrize("bits", [32, 64])
def test_segment_or_with_empty_and_trailing_rows(bits):
    """Empty rows (including trailing ones, whose row start == m) OR to 0
    and must not corrupt their neighbours' segments — at either lane-word
    width (the 64-bit values exercise bits a uint32 pipeline would
    truncate)."""
    with lane_word_bits(bits):
        dt = np.uint64 if bits == 64 else np.uint32
        hi = 1 << (bits - 1)
        # rows: [a, b], [], [c], [] -> row_ptr [0, 2, 2, 3, 3]
        row_ptr = jnp.asarray([0, 2, 2, 3, 3], jnp.int32)
        vals = jnp.asarray(np.asarray([[1], [4 + hi], [8]], dt))
        out = np.asarray(segment_or(vals, row_ptr))
        np.testing.assert_array_equal(
            out, np.asarray([[5 + hi], [0], [8], [0]], dt))


@pytest.mark.parametrize("bits", [32, 64])
def test_depth_slice_words_roundtrip(bits):
    """depth_slice_words repacks depth bands into the engines' bit layout
    for any word width (the k-hop read-out surface)."""
    with lane_word_bits(bits):
        rng = np.random.default_rng(1)
        r = bits + 3                       # spill into a second word
        depth = jnp.asarray(rng.integers(-1, 5, size=(29, r)), jnp.int32)
        words = packed.depth_slice_words(depth, 2)
        assert words.dtype == packed.word_dtype()
        assert words.shape == (29, packed.num_lane_words(r))
        band = (np.asarray(depth) >= 0) & (np.asarray(depth) <= 2)
        np.testing.assert_array_equal(np.asarray(unpack_lanes(words, r)),
                                      band)
        layer1 = packed.depth_slice_words(depth, 1, min_depth=1)
        np.testing.assert_array_equal(
            np.asarray(unpack_lanes(layer1, r)), np.asarray(depth) == 1)


@pytest.mark.parametrize("scale,ef,seed", [(8, 4, 0), (9, 8, 1), (7, 32, 2)])
@pytest.mark.parametrize("max_pos", [1, 8])
def test_msbfs_probe_kernel_vs_ref(scale, ef, seed, max_pos):
    g = rmat_graph(scale, ef, seed=seed)
    rng = np.random.default_rng(seed)
    fro = jnp.asarray(rng.integers(0, 2 ** 32, g.n, dtype=np.uint32))
    need = jnp.asarray(rng.integers(0, 2 ** 32, g.n, dtype=np.uint32))
    a1 = msbfs_probe_pallas(g.row_ptr[:-1], g.deg, need, g.col_idx, fro,
                            max_pos=max_pos, interpret=True)
    a2 = msbfs_probe_ref(g.row_ptr[:-1], g.deg, need, g.col_idx, fro,
                         max_pos=max_pos)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))


def test_msbfs_rejects_bad_batches(g_rmat):
    with pytest.raises(ValueError, match="at most"):
        msbfs(g_rmat, jnp.zeros((65,), jnp.int32))
    with pytest.raises(ValueError, match="mode"):
        msbfs(g_rmat, jnp.zeros((2,), jnp.int32), "sideways")
    with pytest.raises(ValueError, match="mode"):
        msbfs_pipelined(g_rmat, jnp.zeros((2,), jnp.int32), "sideways")
    with pytest.raises(ValueError, match="at least one root"):
        msbfs_pipelined(g_rmat, jnp.zeros((0,), jnp.int32))


# --------------------------- pipelined engine ---------------------------


@pytest.mark.parametrize("num_roots,lanes", [(96, 64), (20, 8), (7, 32)])
def test_pipelined_matches_serial_beyond_lane_pool(g_rmat, num_roots, lanes):
    """R above / below the lane pool: refilled lanes replay serial runs."""
    roots = sample_roots(g_rmat, num_roots, seed=11)
    out = msbfs_pipelined(g_rmat, jnp.asarray(roots), "hybrid", lanes=lanes)
    assert out.parent.shape == (g_rmat.n, num_roots)
    _assert_lanes_match_serial(g_rmat, roots, out)


def test_pipelined_equals_single_batch_sweep(g_rmat):
    """Same roots through both engines: bit-for-bit identical results,
    including per-root traces (lane refill must not perturb a root's
    switching decisions)."""
    roots = jnp.asarray(sample_roots(g_rmat, 40, seed=12))
    a = msbfs(g_rmat, roots, "hybrid")
    b = msbfs_pipelined(g_rmat, roots, "hybrid", lanes=16)
    for name in MSBFSResult_fields():
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(b, name)),
                                      err_msg=name)


def MSBFSResult_fields():
    return ("parent", "depth", "num_layers", "edges_traversed", "trace_dir",
            "trace_vf", "trace_ef", "trace_eu")


@pytest.mark.parametrize("mode", ["topdown", "bottomup"])
def test_pipelined_forced_modes(g_rmat, mode):
    roots = sample_roots(g_rmat, 70, seed=13)
    out = msbfs_pipelined(g_rmat, jnp.asarray(roots), mode, lanes=64)
    _assert_lanes_match_serial(g_rmat, roots, out, mode)


def test_pipelined_pallas_probe(g_rmat):
    """R > MAX_LANES through the W-parametric Pallas probe kernel (at
    64-bit lane words this is the u64 gather path, W half-plane pairs)."""
    roots = sample_roots(g_rmat, 72, seed=14)
    out = msbfs_pipelined(g_rmat, jnp.asarray(roots), "hybrid",
                          probe_impl="pallas", lanes=64)
    _assert_lanes_match_serial(g_rmat, roots, out)


def test_pipelined_sweep_is_shorter_than_batch_sum():
    """The refill pipeline's whole point: mixing deep (ring) and shallow
    (star) roots, total engine layers must beat the barriered word-batch
    schedule (each batch waits for its deepest lane)."""
    n = 96
    v = np.arange(n)
    ring_edges = (v, (v + 1) % n)
    star_src = np.full(n - 2, n, np.int64)
    g = from_edges(np.concatenate([ring_edges[0], star_src]),
                   np.concatenate([ring_edges[1],
                                   np.arange(1, n - 1) + n]),
                   2 * n)
    # 2 lanes, 4 roots: lane pool must process [deep, shallow, shallow,
    # shallow]; pipelining lets the shallow lane chew through queue while
    # the ring lane is still going
    roots = jnp.asarray([0, n, n + 1, n + 2], jnp.int32)
    state = msbfs_engine_init(g, capacity=4, lanes=2)
    state = msbfs_engine_enqueue(state, roots)
    layers = 0
    while not msbfs_engine_idle(state):
        state = msbfs_engine_step(g, state, "hybrid")
        layers += 1
    deep = int(bfs(g, 0, "hybrid").num_layers)
    sh = [int(bfs(g, int(r), "hybrid").num_layers) for r in roots[1:]]
    # barriered word-batches of 2: (deep | sh0) then (sh1 | sh2)
    barriered = max(deep, sh[0]) + max(sh[1], sh[2])
    assert layers < barriered, (layers, barriered)
    # refill keeps lane 2 busy back-to-back while lane 1 walks the ring:
    # total layers = the longer of the two lane schedules, no bubbles
    assert layers == max(deep, sum(sh)), (layers, deep, sh)


def test_streaming_enqueue_mid_sweep(g_rmat):
    """Roots enqueued WHILE the sweep runs land in idle lanes and finish
    validator-clean — the serve_bfs serving loop in miniature."""
    roots = sample_roots(g_rmat, 24, seed=15)
    state = msbfs_engine_init(g_rmat, capacity=24, lanes=8)
    state = msbfs_engine_enqueue(state, roots[:8])
    fed, steps = 8, 0
    while fed < 24 or not msbfs_engine_idle(state):
        state = msbfs_engine_step(g_rmat, state, "hybrid")
        steps += 1
        if steps % 2 == 0 and fed < 24:
            state = msbfs_engine_enqueue(state, roots[fed:fed + 4])
            fed += 4
    out = msbfs_engine_result(g_rmat, state)
    _assert_lanes_match_serial(g_rmat, roots, out)
    assert (np.asarray(state.out_layers[:24]) > 0).all()


def test_engines_agree_on_multi_component_traces():
    """A lane that finishes early (small component) must leave its unused
    trace rows at init values in BOTH engines — the single-batch sweep
    keeps looping for deeper lanes, but dead lanes record nothing."""
    # path 0-..-5, star at 10-15, plus an unreached blob 20-23
    src = np.concatenate([np.arange(5), np.full(5, 10), np.arange(20, 23)])
    dst = np.concatenate([np.arange(1, 6), np.arange(11, 16),
                          np.arange(21, 24)])
    g = from_edges(src, dst, 24)
    roots = jnp.asarray([0, 10], jnp.int32)
    a = msbfs(g, roots, "hybrid")
    b = msbfs_pipelined(g, roots, "hybrid", lanes=2)
    for name in MSBFSResult_fields():
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(b, name)),
                                      err_msg=name)
    # star lane (num_layers 2-3) leaves later rows untouched
    nl = int(a.num_layers[1])
    assert (np.asarray(a.trace_eu)[nl:, 1] == 0).all()
    assert (np.asarray(a.trace_dir)[nl:, 1] == -1).all()


def test_engines_agree_at_max_trace_cap():
    """Component diameter >= MAX_TRACE: both engines cap num_layers at
    MAX_TRACE (the serial loop bound) with identical truncated depths."""
    n = ms.MAX_TRACE + 10
    v = np.arange(n - 1)
    g = from_edges(v, v + 1, n)          # path graph, diameter n-1 > cap
    roots = jnp.asarray([0], jnp.int32)
    a = msbfs(g, roots, "topdown")
    b = msbfs_pipelined(g, roots, "topdown", lanes=1)
    s = bfs(g, 0, "topdown")
    assert int(a.num_layers[0]) == int(b.num_layers[0]) \
        == int(s.num_layers) == ms.MAX_TRACE
    np.testing.assert_array_equal(np.asarray(a.depth[:, 0]),
                                  np.asarray(s.depth))
    np.testing.assert_array_equal(np.asarray(b.depth[:, 0]),
                                  np.asarray(s.depth))


def test_engine_result_on_fresh_engine_is_empty(g_rmat):
    state = msbfs_engine_init(g_rmat, capacity=4, lanes=2)
    out = msbfs_engine_result(g_rmat, state)
    assert out.parent.shape == (g_rmat.n, 0)
    assert out.depth.shape == (g_rmat.n, 0)
    assert out.num_layers.shape == (0,)


def test_engine_queue_overflow_and_init_guards(g_rmat):
    state = msbfs_engine_init(g_rmat, capacity=4, lanes=2)
    state = msbfs_engine_enqueue(state, jnp.zeros((4,), jnp.int32))
    with pytest.raises(ValueError, match="overflow"):
        msbfs_engine_enqueue(state, jnp.zeros((1,), jnp.int32))
    with pytest.raises(ValueError, match="capacity"):
        msbfs_engine_init(g_rmat, capacity=0)
    with pytest.raises(ValueError, match="lanes"):
        msbfs_engine_init(g_rmat, capacity=4, lanes=0)


# ---------------------------------------------------------------------------
# Named scopes and the fallback-pass counter of the pipelined engine.
# ---------------------------------------------------------------------------


def _engine_args(g, roots=64):
    st = msbfs_engine_enqueue(msbfs_engine_init(g, capacity=roots, lanes=64),
                              jnp.arange(roots, dtype=jnp.int32))
    return g, st, "hybrid", 14.0, 24.0, 8, "xla"


@pytest.mark.parametrize("program", ["_drain", "msbfs_engine_step"])
def test_engine_programs_carry_every_step_scope(g_rmat, program):
    """Each piece of an engine step sits under one of ``STEP_SCOPES``; only
    the drain loop's condition and the bottom-up dispatch conditional (a
    container whose branch holds both the probe and the fallback) and its
    predicate stay outside."""
    lowered = getattr(ms, program).lower(*_engine_args(g_rmat))
    text = lowered.as_text(debug_info=True)
    for scope in packed.STEP_SCOPES:
        assert scope in text, scope
    import re
    body = {"_drain": "jit(_drain)/while/body",
            "msbfs_engine_step": "jit(msbfs_engine_step)"}[program]
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    outside = {n for n in names if n.startswith(body + "/")
               and not set(n.split("/")) & set(packed.STEP_SCOPES)}
    assert outside <= {body + "/cond", body + "/convert_element_type"}


def _fallback_steps(g, depth, trace_dir, max_pos=8):
    """Engine steps whose bottom-up residue was non-empty, from the answers
    of a sweep that seated every key at step 0 (so step ``t`` is layer
    ``t`` of every lane): a bottom-up lane at layer ``t`` leaves a residue
    when a row of degree over ``max_pos`` it has not reached has none of
    its first ``max_pos`` neighbours at depth ``t``."""
    rp, ci = to_numpy_adj(g)
    deg = np.diff(rp)
    pos = np.arange(max_pos)
    valid = pos[None, :] < deg[:, None]
    nbr = ci[np.minimum(rp[:-1, None] + pos[None, :], ci.size - 1)]
    depth, trace_dir = np.asarray(depth), np.asarray(trace_dir)
    steps = 0
    for t in range(trace_dir.shape[0]):
        for r in np.flatnonzero(trace_dir[t] == 1):
            d = depth[:, r]
            need = (d < 0) | (d > t)
            found = ((d[nbr] == t) & valid).any(axis=1)
            if (need & ~found & (deg > max_pos)).any():
                steps += 1
                break
    return steps


@pytest.mark.parametrize("graph,mode", [("rmat", "hybrid"),
                                        ("rmat", "bottomup"),
                                        ("ring", "bottomup")])
def test_bu_fallback_passes_counts_residue_steps(g_rmat, graph, mode):
    """The drain's counter equals the step loop's and the steps whose
    bottom-up residue was non-empty; a graph with no row past ``max_pos``
    never runs the fallback."""
    g = g_rmat if graph == "rmat" else ring_graph(64)
    roots = (sample_roots(g, 40, seed=21) if graph == "rmat"
             else np.arange(0, 64, 8))
    out = msbfs_pipelined(g, jnp.asarray(roots), mode, lanes=64)
    state = msbfs_engine_enqueue(msbfs_engine_init(g, len(roots), lanes=64),
                                 jnp.asarray(roots))
    while not msbfs_engine_idle(state):
        state = msbfs_engine_step(g, state, mode)
    want = _fallback_steps(g, out.depth, out.trace_dir)
    assert int(out.bu_fallback_passes) == int(state.bu_fallback_passes)
    assert int(out.bu_fallback_passes) == want
    assert (np.asarray(out.trace_dir) == 1).any()
    assert (want > 0) == (graph == "rmat")


# ---------------------------------------------------------------------------
# Parent derivation: one keyed per-row minimum per four lanes.
# ---------------------------------------------------------------------------


def _parents_numpy(g, depth, roots):
    """The min-id row entry one level up, -1 where there is none, each
    root its own parent."""
    rp, ci = to_numpy_adj(g)
    src = np.repeat(np.arange(g.n), np.diff(rp))
    depth = np.asarray(depth)
    want = np.full(depth.shape, -1, np.int64)
    for lane, root in enumerate(np.asarray(roots)):
        d = depth[:, lane]
        up = (d[src] > 0) & (d[ci] == d[src] - 1)
        best = np.full(g.n, g.n, np.int64)
        np.minimum.at(best, src[up], ci[up])
        want[:, lane] = np.where(best < g.n, best, -1)
        want[root, lane] = root
    return want


def _retired_after(g, roots, steps):
    """Depths of partial columns: every lane retired after ``steps`` steps."""
    state = msbfs_engine_enqueue(
        msbfs_engine_init(g, capacity=len(roots), lanes=len(roots)),
        jnp.asarray(roots, jnp.int32))
    for _ in range(steps):
        state = msbfs_engine_step(g, state)
    state = ms.msbfs_engine_retire(g, state, np.ones(len(roots), bool))
    return msbfs_engine_result(g, state, derive_parents=False).depth


def _parent_case(name):
    """(graph, depth, roots) of one parent-derivation case."""
    rng = np.random.default_rng(7)
    if name.startswith("rmat"):
        g = rmat_graph(10, 16, seed=0)
        roots = sample_roots(g, int(name.split("_")[1]), seed=31)
    elif name == "path_capped":
        n = ms.MAX_TRACE + 10
        v = np.arange(n - 1)
        g = from_edges(v, v + 1, n)
        roots = np.array([0, n // 2, n - 1, 5, 70])
    elif name == "retired":
        g = rmat_graph(9, 8, seed=3)
        roots = sample_roots(g, 9, seed=32)
        return g, _retired_after(g, roots, 2), roots
    elif name == "self_loops_isolated":
        n = 300          # vertices 250 and up have no edge
        s, d = rng.integers(0, 250, 900), rng.integers(0, 250, 900)
        loops = rng.integers(0, 250, 60)
        g = from_edges(np.concatenate([s, loops]), np.concatenate([d, loops]),
                       n, drop_self_loops=False)
        roots = np.array([int(loops[0]), 260, 0, int(loops[1]), 299, 17])
    elif name == "non_symmetric":
        n = 400
        s, d = rng.integers(0, n, 2400), rng.integers(0, n, 2400)
        g = from_edges(s, d, n, symmetrize=False)
        roots = np.array([0, 1, 2, 3, 4, 5, 6])
    out = msbfs_pipelined(g, jnp.asarray(roots), "hybrid", lanes=64,
                          derive_parents=False)
    return g, out.depth, roots


PARENT_CASES = ["rmat_1", "rmat_3", "rmat_4", "rmat_5", "rmat_64",
                "path_capped", "retired", "self_loops_isolated",
                "non_symmetric"]


@pytest.mark.parametrize("case", PARENT_CASES)
def test_derive_parents_matches_numpy_rule(case):
    """``_derive_parents`` on engine depths equals the plain rule: full and
    partial four-lane words, a lane capped at ``MAX_TRACE``, columns
    flushed by ``msbfs_engine_retire``, self-loops, isolated vertices and
    a CSR that is not symmetric."""
    g, depth, roots = _parent_case(case)
    if case == "path_capped":      # lane 0 stops before the path's end
        assert int(np.asarray(depth)[:, 0].max()) == ms.MAX_TRACE
        assert (np.asarray(depth)[:, 0] < 0).any()
    if case == "retired":
        assert int(np.asarray(depth).max()) == 2
    got = ms._derive_parents(g, depth, jnp.asarray(roots, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got),
                                  _parents_numpy(g, depth, roots))


def _chunk_words(depth):
    """The four-lane ``depth + 1`` words ``_derive_parents`` maps over."""
    biased = np.asarray(depth, np.int64) + 1
    biased = np.pad(biased, ((0, 0), (0, -biased.shape[1] % 4)))
    chunks = biased.reshape(biased.shape[0], -1, 4) << (8 * np.arange(4))
    return jnp.asarray(chunks.sum(axis=-1).T.astype(np.uint32))


@pytest.mark.parametrize("case", ["rmat_64", "path_capped", "retired",
                                  "self_loops_isolated", "non_symmetric"])
def test_keyed_rule_equals_two_gather_rule(case):
    """The per-chunk rule for ``n <= 2**24`` and the one kept for larger
    graphs give the same parents."""
    g, depth, _ = _parent_case(case)
    shifts = 8 * jnp.arange(4, dtype=jnp.uint32)
    for w in _chunk_words(depth):
        np.testing.assert_array_equal(
            np.asarray(ms._chunk_parents_keyed(g, w, shifts)),
            np.asarray(ms._chunk_parents_pair(g, w, shifts)))


@pytest.mark.parametrize("rule,gathers", [("keyed", 1), ("pair", 2)])
def test_parent_map_gathers_words_once_per_chunk(rule, gathers):
    """In the lowered program the map's body gathers the chunk's uint32[n]
    words at m indices once (the two-gather rule: twice). No other gather
    runs over m indices: a slot's place in its row is the graph's stored
    ``slot_pos``, not ``row_ptr[src_idx]``."""
    import re
    g = rmat_graph(8, 8, seed=0)
    n, m = g.n, g.m
    assert n + 1 != m and n <= 1 << 24
    roots = jnp.asarray(sample_roots(g, 8, seed=1))
    depth = msbfs_pipelined(g, roots, lanes=64, derive_parents=False).depth
    if rule == "keyed":
        lowered = ms._derive_parents.lower(g, depth, roots)
    else:
        shifts = 8 * jnp.arange(4, dtype=jnp.uint32)
        lowered = jax.jit(lambda g, words: jax.lax.map(
            lambda w: ms._chunk_parents_pair(g, w, shifts), words)).lower(
                g, _chunk_words(depth))
    text = lowered.as_text()
    operands = re.findall(
        rf'"stablehlo\.gather".* : \((tensor<[^>]*>), tensor<{m}x1xi32>\)',
        text)
    assert sorted(operands) == [f"tensor<{n}xui32>"] * gathers
