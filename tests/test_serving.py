"""AnalyticsService: streaming read-out parity, admission control, and
the unified envelope.

The serving answers are parity-checked against offline ``run_query`` for
every query kind (same graph, same sources -> bit-identical arrays); the
mid-sweep streaming read-outs must land khop/reach answers EARLIER than
lane flush while staying bit-identical to the flush-time answer (BFS
depth finality). The admission front door (bounded queue + per-tenant
quota), the REJECTED/QUEUED/RUNNING/DONE lifecycle, the worker-thread
submit/result path, the envelope wire codec, and the QueryMeta
deprecation shim are covered as units. A forced multi-device leg
(conftest subprocess pattern, ndev in {2, 4}) pins the sharded service
bit-identical to the host offline path.
"""
import warnings

import numpy as np
import pytest
from conftest import run_in_subprocess

from repro.analytics import (BFSQuery, ClosenessQuery, ComponentsQuery,
                             DiameterQuery, KHopQuery, LaneEngine,
                             ReachQuery, SSSPQuery, run_query)
from repro.analytics.api import (AnalyticsRequest, QUERY_KINDS, QUERY_TYPES,
                                 query_kind)
from repro.core.csr import from_edges
from repro.graph.generator import rmat_graph, rmat_weighted_graph
from repro.serving import (AdmissionController, AnalyticsService, DONE,
                           QUEUED, REJECTED, RUNNING, ServiceConfig,
                           parse_mix, synthetic_trace)


def path_graph(n):
    return from_edges(np.arange(n - 1), np.arange(1, n), n)


@pytest.fixture(scope="module")
def wg():
    """Small weighted R-MAT graph: serves every query kind."""
    return rmat_weighted_graph(8, 8, seed=1)


@pytest.fixture(scope="module")
def offline(wg):
    """The reference engine the service answers are checked against."""
    return LaneEngine(wg)


# ---------------------------------------------------------------------------
# Service vs run_query parity — every query kind, one service instance.
# ---------------------------------------------------------------------------

def test_service_answers_match_run_query_per_kind(wg, offline):
    queries = {
        "bfs": BFSQuery(sources=(0, 3, 5)),
        "khop": KHopQuery(sources=(1, 2), k=2),
        "reach": ReachQuery(sources=(0, 1), targets=(2, 3)),
        "closeness": ClosenessQuery(sources=(0, 1, 2, 3), chunk=4),
        "sssp": SSSPQuery(sources=(0, 4)),
        "components": ComponentsQuery(batch=32),
        "diameter": DiameterQuery(num_seeds=2, seed=0),
    }
    svc = AnalyticsService(wg, slots=16, sssp_slots=8)
    recs = {k: svc.submit(q) for k, q in queries.items()}
    svc.run_until_idle()
    for k, rec in recs.items():
        assert rec.status == DONE, k
        assert rec.answer.meta.kind == k
        assert rec.sojourn >= 1, "layer-clock sojourn must be positive"
    ref = {k: run_query(offline, q) for k, q in queries.items()}

    got = {k: recs[k].answer.result for k in queries}
    np.testing.assert_array_equal(got["bfs"].depth, ref["bfs"].depth)
    np.testing.assert_array_equal(got["bfs"].num_layers,
                                  ref["bfs"].num_layers)
    np.testing.assert_array_equal(got["khop"].words, ref["khop"].words)
    np.testing.assert_array_equal(got["khop"].counts, ref["khop"].counts)
    np.testing.assert_array_equal(got["reach"].hops, ref["reach"].hops)
    np.testing.assert_allclose(got["closeness"].closeness,
                               ref["closeness"].closeness, rtol=1e-12)
    assert got["closeness"].method == ref["closeness"].method
    np.testing.assert_array_equal(got["sssp"].dist, ref["sssp"].dist)
    assert got["sssp"].delta == ref["sssp"].delta
    np.testing.assert_array_equal(got["components"].labels,
                                  ref["components"].labels)
    assert got["diameter"].lower == ref["diameter"].lower
    assert got["diameter"].upper == ref["diameter"].upper


def test_foreign_delta_sssp_takes_batch_path(wg, offline):
    """An sssp request whose bucket width differs from the service's
    pinned delta can't ride the compiled tropical pool — it must fall
    back to the inline batch path and still answer exactly."""
    svc = AnalyticsService(wg, sssp_slots=8)
    foreign = float(svc.delta) * 3.0
    rec = svc.submit(SSSPQuery(sources=(2,), delta=foreign))
    assert rec.engine == "batch"
    svc.run_until_idle()
    ref = run_query(offline, SSSPQuery(sources=(2,), delta=foreign))
    np.testing.assert_array_equal(rec.answer.result.dist, ref.dist)
    assert rec.answer.result.delta == foreign


def test_sssp_on_unweighted_service_raises():
    svc = AnalyticsService(rmat_graph(6, 4, seed=0))
    with pytest.raises(ValueError, match="WeightedCSRGraph"):
        svc.submit(SSSPQuery(sources=(0,)))


# ---------------------------------------------------------------------------
# Streaming read-outs: early AND bit-identical (the depth-finality unlock).
# ---------------------------------------------------------------------------

def test_streaming_khop_answers_early_and_bit_identical():
    g = path_graph(64)
    q = KHopQuery(sources=(0,), k=2)
    stream = AnalyticsService(g, slots=4, streaming=True)
    flush = AnalyticsService(g, slots=4, streaming=False)
    r_s = stream.submit(AnalyticsRequest(query=q, id="s"))
    r_f = flush.submit(AnalyticsRequest(query=q, id="f"))
    stream.run_until_idle()
    flush.run_until_idle()
    assert r_s.answered_early and not r_f.answered_early
    # a depth-2 band on a 64-path is final ~60 layers before lane flush
    assert r_f.sojourn - r_s.sojourn >= 1
    a, b = r_s.answer.result, r_f.answer.result
    np.testing.assert_array_equal(a.words, b.words)
    np.testing.assert_array_equal(a.counts, b.counts)
    ref = run_query(g, q)
    np.testing.assert_array_equal(a.words, ref.words)
    np.testing.assert_array_equal(a.counts, ref.counts)
    np.testing.assert_array_equal(a.members(0), ref.members(0))


def test_streaming_reach_answers_on_target_discovery():
    g = path_graph(64)
    q = ReachQuery(sources=(0,), targets=(3,))
    svc = AnalyticsService(g, slots=4, streaming=True)
    rec = svc.submit(q)
    svc.run_until_idle()
    assert rec.answered_early
    assert rec.answer.result.hops[0, 0] == 3
    # vertex 3 is discovered at layer 3; the lane itself runs to 63
    assert rec.sojourn <= 8
    ref = run_query(g, q)
    np.testing.assert_array_equal(rec.answer.result.hops, ref.hops)


def test_streaming_retire_returns_capacity_to_pool():
    """An early-answered lane must actually retire: a second khop request
    that didn't fit the pool at submit dispatches after the retire,
    without waiting for the first lane's natural flush."""
    g = path_graph(64)
    svc = AnalyticsService(g, lanes=1, slots=4, streaming=True)
    r1 = svc.submit(KHopQuery(sources=(0,), k=1))
    r2 = svc.submit(KHopQuery(sources=(0,), k=1))
    svc.run_until_idle()
    assert r1.status == DONE and r2.status == DONE
    assert r1.answered_early and r2.answered_early
    # both answered from streamed bands long before a 64-layer flush
    assert max(r1.answer_layer, r2.answer_layer) < 32


# ---------------------------------------------------------------------------
# Admission control + lifecycle.
# ---------------------------------------------------------------------------

def test_admission_controller_bounded_queue():
    adm = AdmissionController(max_pending=2)
    assert adm.admit("a") == (True, None)
    assert adm.admit("a") == (True, None)
    ok, reason = adm.admit("a")
    assert not ok and "queue full" in reason
    assert adm.rejected == 1
    adm.on_dispatch("a")              # one leaves the queue
    assert adm.admit("a") == (True, None)


def test_admission_controller_tenant_quota():
    adm = AdmissionController(max_pending=8, tenant_quota=1)
    assert adm.admit("a") == (True, None)
    ok, reason = adm.admit("a")
    assert not ok and "quota" in reason and "'a'" in reason
    assert adm.admit("b") == (True, None)   # other tenants unaffected
    adm.on_dispatch("a")
    ok, _ = adm.admit("a")
    assert not ok, "quota spans QUEUED + RUNNING, not just the queue"
    adm.on_done("a")
    assert adm.admit("a") == (True, None)
    assert adm.inflight("a") == 1


def test_service_rejects_over_max_pending(wg):
    svc = AnalyticsService(wg, max_pending=1)
    r1 = svc.submit(BFSQuery(sources=(0,)))
    r2 = svc.submit(BFSQuery(sources=(1,)))
    assert r1.status == QUEUED
    assert r2.status == REJECTED and "queue full" in r2.reason
    svc.run_until_idle()
    assert r1.status == DONE
    assert r2.status == REJECTED, "rejection is terminal"
    stats = svc.stats()
    assert stats["done"] == 1 and stats["rejected"] == 1


def test_service_tenant_quota_releases_after_done(wg):
    svc = AnalyticsService(wg, tenant_quota=1)
    r1 = svc.submit(AnalyticsRequest(query=BFSQuery(sources=(0,)),
                                     tenant="t0"))
    r2 = svc.submit(AnalyticsRequest(query=BFSQuery(sources=(1,)),
                                     tenant="t0"))
    r3 = svc.submit(AnalyticsRequest(query=BFSQuery(sources=(2,)),
                                     tenant="t1"))
    assert r2.status == REJECTED and "quota" in r2.reason
    assert r3.status == QUEUED
    svc.run_until_idle()
    assert r1.status == DONE and r3.status == DONE
    r4 = svc.submit(AnalyticsRequest(query=BFSQuery(sources=(3,)),
                                     tenant="t0"))
    assert r4.status == QUEUED, "quota released once the request is DONE"


def test_lifecycle_transitions_and_poll():
    g = path_graph(32)
    svc = AnalyticsService(g, slots=4)
    rec = svc.submit(BFSQuery(sources=(0,)))
    rid = rec.request.id
    assert svc.poll(rid) == QUEUED
    svc.step()
    assert svc.poll(rid) == RUNNING     # a 32-path takes ~32 layers
    while svc.busy():
        svc.step()
    assert svc.poll(rid) == DONE
    assert rec.dispatch_layer >= rec.submit_layer
    assert rec.answer_layer > rec.dispatch_layer


def test_duplicate_request_id_raises(wg):
    svc = AnalyticsService(wg)
    svc.submit(AnalyticsRequest(query=BFSQuery(sources=(0,)), id="dup"))
    with pytest.raises(ValueError, match="duplicate request id"):
        svc.submit(AnalyticsRequest(query=BFSQuery(sources=(1,)),
                                    id="dup"))


def test_epoch_recycle_under_tight_slots():
    """More root demand than one epoch holds: the pool must drain and
    recycle its slots (epochs advance) and still answer everything."""
    g = path_graph(16)
    svc = AnalyticsService(g, slots=2)
    recs = [svc.submit(BFSQuery(sources=(i,))) for i in range(5)]
    svc.run_until_idle()
    assert all(r.status == DONE for r in recs)
    assert svc._packed.epochs >= 2
    ref = run_query(g, BFSQuery(sources=(4,)))
    np.testing.assert_array_equal(recs[4].answer.result.depth, ref.depth)


# ---------------------------------------------------------------------------
# Async front door (worker thread).
# ---------------------------------------------------------------------------

def test_threaded_submit_result_roundtrip(wg, offline):
    with AnalyticsService(wg, slots=16) as svc:
        rec = svc.submit(KHopQuery(sources=(3,), k=2))
        ans = svc.result(rec.request.id, timeout=120.0)
    ref = run_query(offline, KHopQuery(sources=(3,), k=2))
    np.testing.assert_array_equal(ans.result.counts, ref.counts)
    np.testing.assert_array_equal(ans.result.words, ref.words)


def test_result_without_worker_thread_raises(wg):
    svc = AnalyticsService(wg)
    rec = svc.submit(BFSQuery(sources=(0,)))
    with pytest.raises(RuntimeError, match="worker thread"):
        svc.result(rec.request.id)


def test_result_of_rejected_request_raises(wg):
    svc = AnalyticsService(wg, max_pending=1)
    svc.submit(BFSQuery(sources=(0,)))
    rec = svc.submit(BFSQuery(sources=(1,)))   # over the bound: REJECTED
    assert rec.status == REJECTED
    with svc:                                  # rejection is terminal —
        with pytest.raises(RuntimeError, match="rejected"):
            svc.result(rec.request.id, timeout=5.0)


# ---------------------------------------------------------------------------
# Replay + trace + mix parsing.
# ---------------------------------------------------------------------------

def test_replay_mixed_trace_answers_everything(wg):
    trace = synthetic_trace(wg.n, 12, mix="bfs:2,khop:2,reach:1,sssp:1",
                            seed=3, tenants=("t0", "t1"))
    svc = AnalyticsService(wg, slots=16, sssp_slots=8)
    stats = svc.replay(trace)
    assert stats["requests"] == 12 and stats["done"] == 12
    assert stats["rejected"] == 0
    assert set(stats["per_type"]) <= set(QUERY_KINDS)
    assert stats["sojourn_layers"]["p50"] >= 1
    for env in trace:
        rec = svc.record(env.id)
        assert rec.status == DONE
        ref = run_query(wg, env.query)
        if rec.kind == "sssp":
            np.testing.assert_array_equal(rec.answer.result.dist, ref.dist)
        elif rec.kind == "khop":
            np.testing.assert_array_equal(rec.answer.result.words,
                                          ref.words)


def test_parse_mix_normalizes_and_rejects_unknown_tags():
    w = parse_mix("bfs:3, khop:1")
    assert w == {"bfs": 0.75, "khop": 0.25}
    assert parse_mix("sssp") == {"sssp": 1.0}
    with pytest.raises(ValueError, match="unknown query tag 'bogus'"):
        parse_mix("bfs:1,bogus:2")
    with pytest.raises(ValueError, match="bad weight"):
        parse_mix("bfs:x")
    with pytest.raises(ValueError, match="empty workload mix"):
        parse_mix("bfs:0")


def test_trace_is_deterministic():
    a = synthetic_trace(256, 8, mix="bfs:1,khop:1", seed=5)
    b = synthetic_trace(256, 8, mix="bfs:1,khop:1", seed=5)
    assert [r.query for r in a] == [r.query for r in b]
    assert [r.arrival for r in a] == [r.arrival for r in b]
    assert a[0].arrival == 0 and a[-1].arrival == (7 // 4) * 2


# ---------------------------------------------------------------------------
# Envelope codec + tag registry.
# ---------------------------------------------------------------------------

def test_envelope_wire_roundtrip():
    req = AnalyticsRequest(query=KHopQuery(sources=(3, 17), k=2),
                           id="r1", tenant="acme", arrival=4)
    wire = req.to_wire()
    assert wire["kind"] == "khop" and wire["query"]["sources"] == [3, 17]
    back = AnalyticsRequest.from_wire(wire)
    assert back.query == req.query
    assert (back.id, back.tenant, back.arrival) == ("r1", "acme", 4)


def test_envelope_unknown_tag_is_one_error_path():
    with pytest.raises(ValueError, match="unknown query tag 'nope'"):
        AnalyticsRequest.from_wire(dict(kind="nope", query={}))


def test_envelope_rejects_untyped_query():
    with pytest.raises(TypeError, match="unknown analytics query type"):
        AnalyticsRequest(query=object())


def test_every_query_type_declares_its_own_kind():
    for t in QUERY_TYPES:
        assert QUERY_KINDS[query_kind(t)] is t

    class Tagless:
        pass

    with pytest.raises(TypeError, match="declares no wire tag"):
        query_kind(Tagless)


def test_query_meta_deprecated_dict_access(wg, offline):
    res = run_query(offline, KHopQuery(sources=(0,), k=1))
    assert res.meta.kind == "khop" and res.meta.lanes >= 1
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert res.meta["ndev"] == res.meta.ndev
        assert res.meta.get("kind") == "khop"
        assert "lanes" in res.meta      # membership stays silent
    assert all(issubclass(x.category, DeprecationWarning) for x in w)
    assert len(w) == 2                  # one per __getitem__/.get()


# ---------------------------------------------------------------------------
# Forced multi-device parity: the sharded service streams the same bits.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ndev", [2, 4])
def test_serving_dist_streaming_parity(ndev):
    """Streaming khop/reach answers from an ndev-sharded service must be
    bit-identical to host offline ``run_query`` AND land early."""
    run_in_subprocess(f"""
import numpy as np
from repro.analytics import KHopQuery, ReachQuery, run_query
from repro.core.csr import from_edges
from repro.serving import AnalyticsService

n = 96
g = from_edges(np.arange(n - 1), np.arange(1, n), n)
svc = AnalyticsService(g, slots=4, ndev={ndev}, streaming=True)
kq = KHopQuery(sources=(0, 7), k=2)
rq = ReachQuery(sources=(0,), targets=(5,))
rk = svc.submit(kq)
rr = svc.submit(rq)
svc.run_until_idle()
assert rk.answered_early and rr.answered_early
assert rk.answer.meta.ndev == {ndev}
ref_k = run_query(g, kq)
ref_r = run_query(g, rq)
np.testing.assert_array_equal(rk.answer.result.words, ref_k.words)
np.testing.assert_array_equal(rk.answer.result.counts, ref_k.counts)
np.testing.assert_array_equal(rr.answer.result.hops, ref_r.hops)
assert rr.answer.result.hops[0, 0] == 5
# flush twin on the same mesh: streamed band == flushed band, later
flush = AnalyticsService(g, slots=4, ndev={ndev}, streaming=False)
fk = flush.submit(kq)
flush.run_until_idle()
np.testing.assert_array_equal(rk.answer.result.words,
                              fk.answer.result.words)
assert fk.sojourn - rk.sojourn >= 1
print("ok")
""", devices=ndev)


# ---------------------------------------------------------------------------
# Host-clock stamps, tick spans and the occupancy read from the read-out.
# ---------------------------------------------------------------------------


def _busy_service(wg):
    """More one-key requests than lanes and than one epoch's slots, of the
    pooled kinds and the inline batch path."""
    svc = AnalyticsService(wg, lanes=8, slots=24, sssp_slots=8)
    recs = []
    for i in range(40):
        kind = i % 4
        q = (BFSQuery(sources=(i,)) if kind == 0 else
             KHopQuery(sources=(i,), k=1) if kind == 1 else
             SSSPQuery(sources=(i,)) if kind == 2 else
             ReachQuery(sources=(i,), targets=(i + 1,)))
        recs.append(svc.submit(q))
    recs.append(svc.submit(ComponentsQuery(batch=32)))
    return svc, recs


def test_request_stamps_are_ordered(wg):
    svc, recs = _busy_service(wg)
    occupancy = []
    while svc.busy():
        svc.step()
        occupancy.append(svc._occupancy[-1])
        # the occupancy taken from the tick's read-out is the pools' own
        assert occupancy[-1] == sum(p.active_lanes() for p in
                                    (svc._packed, svc._tropical) if p)
    assert max(occupancy) > 0
    for r in recs:
        assert r.status == DONE
        assert r.t_submit <= r.t_dispatch <= r.t_seated <= r.t_done, r.kind
    # of the requests the first tick dispatched, some waited for a lane
    first = [r for r in recs if r.engine == "packed"
             and r.dispatch_layer == 1]
    assert len(first) > svc._packed.lanes
    assert len({r.t_seated for r in first}) > 1


def test_tick_phases_and_waits_in_metrics_text(wg):
    svc, _ = _busy_service(wg)
    svc.run_until_idle()
    text = svc.metrics_text()
    for phase in ("tick", "dispatch", "launch", "wait", "readout",
                  "collect", "account"):
        assert (f'service_tick_phase_seconds_count{{phase="service.'
                f'{phase}"}}') in text, phase
    for stage in ("dispatch", "lane"):
        assert f'service_wait_seconds_count{{stage="{stage}"}}' in text
    assert "# TYPE service_wait_seconds histogram" in text
