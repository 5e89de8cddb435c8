"""Unit tests of the yardstick: generator, TEPS count, roofline bytes,
peaks, percentile, open-loop schedule and client."""
import numpy as np
import pytest

from bench.lib import g500check, graphgen, openloop, refs
from bench.lib.peaks import peaks_for
from bench.lib.roofline import sweep_min_bytes
from bench.lib.stats import percentile


def _graph(structure, label, scale, edgefactor):
    """The graph of edge tuples ``structure`` relabelled by ``label``."""
    return tuple(np.asarray(a) for a in graphgen.kronecker_csr(
        graphgen.seed_key(structure, 0), graphgen.seed_key(label, 1),
        scale, edgefactor))


@pytest.mark.parametrize("scale,edgefactor", [(8, 16), (10, 8)])
def test_generator_shape_symmetry_and_determinism(scale, edgefactor):
    n = 1 << scale
    row_ptr, col, src, label = _graph(2**40 + 7, 3, scale, edgefactor)
    assert row_ptr.shape == (n + 1,) and row_ptr[0] == 0
    assert col.size == src.size == row_ptr[-1] == 2 * edgefactor * n
    np.testing.assert_array_equal(src, np.repeat(np.arange(n),
                                                 np.diff(row_ptr)))
    keys = src.astype(np.int64) * n + col
    assert (np.diff(keys) >= 0).all()              # rows sorted by neighbour
    rev = np.sort(col.astype(np.int64) * n + src)
    np.testing.assert_array_equal(rev, keys)       # symmetric multiset
    loops = int((src == col).sum())
    assert loops % 2 == 0 and loops < col.size // 20
    assert sorted(label.tolist()) == list(range(n))
    again = _graph(2**40 + 7, 3, scale, edgefactor)
    np.testing.assert_array_equal(again[1], col)
    other = _graph(8, 3, scale, edgefactor)
    assert not np.array_equal(other[1], col)


def test_seed_gives_the_structure_unless_the_config_fixes_it():
    cfg = {"scale": 8, "edgefactor": 8}
    a, b = (np.asarray(graphgen.graph_for(cfg, s)[1]) for s in (1, 2))
    assert not np.array_equal(np.sort(a), np.sort(b))
    fixed = dict(cfg, structure_seed=2**40 + 1)
    a, b = (graphgen.graph_for(fixed, s) for s in (1, 2**35))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_seed_relabels_the_same_structure():
    """Another label key gives the same graph under other vertex ids."""
    n = 1 << 9

    def structure_edges(label_seed):
        row_ptr, col, src, label = _graph(5, label_seed, 9, 16)
        gen_id = np.argsort(label)           # vertex id -> generated vertex
        return np.sort(gen_id[src].astype(np.int64) * n + gen_id[col]), col

    a, col_a = structure_edges(2**35)
    b, col_b = structure_edges(12)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(col_a, col_b)


def test_generator_degree_skew_matches_program_generator():
    """Same distribution as the program's host generator, not the same
    bytes: the share of isolated vertices and the top degree agree."""
    from repro.graph.generator import rmat_graph
    want = np.diff(np.asarray(rmat_graph(12, 16, seed=1).row_ptr))
    got = np.diff(_graph(1, 1, 12, 16)[0])
    assert abs((got == 0).mean() - (want == 0).mean()) < 0.02
    assert abs(got.max() / want.max() - 1) < 0.2


def test_teps_numerator_and_checker_against_brute_force():
    rp, ci, si, _ = _graph(4, 4, 9, 8)
    row_ptr, col, src = rp, ci, si
    keys = graphgen.search_keys(rp, ci)[:8]
    parents, depths = zip(*(refs.bfs_reference(rp, ci, int(k)) for k in keys))
    depth = np.stack(depths, 1)
    parent = np.stack(parents, 1)
    bad, slots = g500check.check_batch(
        row_ptr, col, src, depth, parent, keys,
        chunks=g500check.num_chunks(ci.size, 1 << 10))
    assert int(np.asarray(bad).sum()) == 0
    for j, k in enumerate(keys):
        reached = set(np.flatnonzero(depth[:, j] >= 0).tolist())
        # every edge tuple with an end in the component, loops included
        pairs = [(u, v) for u in reached
                 for v in ci[rp[u]:rp[u + 1]].tolist()]
        assert int(np.asarray(slots)[j]) == len(pairs)
        assert len(pairs) % 2 == 0
    # one wrong depth, one wrong parent, one missing vertex: each caught
    for poke in ("depth", "parent", "unreached"):
        d, p = depth.copy(), parent.copy()
        v = int(np.flatnonzero(d[:, 0] >= 2)[0])
        if poke == "depth":
            d[v, 0] += 1
        elif poke == "parent":
            p[v, 0] = v
        else:
            d[v, 0], p[v, 0] = -1, -1
        bad, _ = g500check.check_batch(row_ptr, col, src, d, p, keys,
                                       chunks=g500check.num_chunks(ci.size))
        assert int(np.asarray(bad)[0]) > 0, poke
        assert int(np.asarray(bad)[1:].sum()) == 0, poke


def test_sweep_min_bytes():
    n, m = 1 << 20, 1 << 25
    assert sweep_min_bytes(n, m, 64) == 4 * (n + 1) + 4 * m + 2 * 4 * n * 64
    assert sweep_min_bytes(10, 40, 0) == 4 * 11 + 4 * 40
    assert sweep_min_bytes(n, m, 64) < sweep_min_bytes(n, m, 65)


def test_peaks_table():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert percentile(xs, 95) == 95
    assert percentile(xs, 50) == 50
    assert percentile([3.0], 95) == 3.0
    assert percentile([5, 1, 4, 2, 3], 50) == 3
    with pytest.raises(ValueError):
        percentile([], 50)


TRAFFIC = {"rate_qps": 20.0, "lead_in_s": 2.0,
           "mix": [{"kind": "khop", "k": 1, "share": 0.2},
                   {"kind": "khop", "k": 2, "share": 0.5},
                   {"kind": "reach", "share": 0.3}]}


def test_schedule_is_seeded_and_holds_exact_counts():
    cand = np.arange(100, 200)

    def sched(seed):
        return openloop.make_schedule(TRAFFIC, 10.0,
                                      graphgen.host_rng(seed, 1), cand)

    a, b, c = sched(2**33), sched(2**33), sched(5)
    assert [(x.due, x.kind, x.params) for x in a] == \
           [(x.due, x.kind, x.params) for x in b]
    assert [x.due for x in a] != [x.due for x in c]
    for s in (a, c):
        win = [x for x in s if x.in_window]
        assert len(win) == 200 and len(s) == 240
        assert sorted(x.due for x in s) == [x.due for x in s]
        assert all(0 <= x.due < 10 for x in win)
        kinds = [(x.kind, x.params.get("k")) for x in win]
        assert kinds.count(("khop", 1)) == 40
        assert kinds.count(("khop", 2)) == 100
        assert kinds.count(("reach", None)) == 60
        assert all(100 <= x.params["source"] < 200 for x in s)


class FakeService:
    """A service whose tick takes ``tick`` seconds of a fake clock and
    answers every request submitted before the tick began."""

    def __init__(self, clock, tick):
        self.clock, self.tick = clock, tick
        self.status, self.queue = {}, []

    def submit(self, a):
        h = len(self.status)
        self.status[h] = openloop.QUEUED
        self.queue.append(h)
        return h

    def step(self):
        for h in self.queue:
            self.status[h] = openloop.DONE
        self.queue = []
        self.clock.t += self.tick


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


def test_client_measures_lateness_and_latency():
    from contextlib import nullcontext
    clock = FakeClock()
    svc = FakeService(clock, tick=0.5)
    arrivals = [openloop.Arrival(due=d, kind="khop", params={},
                                 in_window=d >= 0)
                for d in (-0.2, 0.1, 0.2, 1.3, 2.9)]
    client = openloop.OpenLoopClient(
        submit=svc.submit, status=lambda h: svc.status[h], step=svc.step,
        busy=lambda: bool(svc.queue), span=lambda name: nullcontext(),
        clock=clock, sleep=clock.sleep)
    log = client.run(arrivals, seconds=3.0, drain_limit_s=5.0)
    assert not log.gave_up
    by_due = {a.due: a for a in log.arrivals}
    # the lead-in request's tick runs from -0.2 to 0.3: the two requests
    # due in it are submitted late, when it ends, and answered at 0.8
    assert by_due[0.1].submitted == pytest.approx(0.3)
    assert by_due[0.2].submitted - 0.2 == pytest.approx(0.1)
    assert by_due[0.1].done == by_due[0.2].done == pytest.approx(0.8)
    assert by_due[1.3].submitted == pytest.approx(1.3)
    assert by_due[1.3].done == pytest.approx(1.8)
    assert [(s, e) for s, e in log.ticks][0] == pytest.approx((-0.2, 0.3))
    assert all(a.running == a.done for a in log.arrivals)
    assert len(log.window_requests()) == 4
