"""Telemetry subsystem tests (``repro.obs``).

Four surfaces:

* the metrics registry — exposition format, idempotent registration,
  label-cardinality bound;
* sweep-log parity — the ``SweepRecorder`` stream reconstructs the
  engines' trace arrays BIT-FOR-BIT and the recorded results equal the
  recorder-off run, on the host engines in-process and on the
  distributed engines (ndev 2/4, grids 1x2/2x2) in forced-device
  subprocesses;
* trace-event export — schema validation, Chrome-JSON round-trip, the
  JSONL flight sink;
* the disabled path — ``recorder=None`` provably never touches
  ``repro.obs.sweeplog`` (a poisoned hook does not fire), and the
  nearest-rank percentile pins (the CI sojourn gates' arithmetic).
"""
import json
from unittest import mock

import numpy as np
import pytest

from conftest import run_in_subprocess
from repro.graph.generator import rmat_graph, rmat_weighted_graph
from repro.obs import (FlightSink, MetricsRegistry, SweepRecorder,
                       Telemetry, metrics_text, service_trace_events,
                       sweep_trace_events, validate_trace_events,
                       write_chrome_trace)

# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_exposition():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "requests", ("kind", "status"))
    c.labels(kind="bfs", status="QUEUED").inc()
    c.labels(kind="bfs", status="QUEUED").inc(2)
    c.labels(kind="sssp", status="REJECTED").inc()
    reg.gauge("occupancy", "active lanes").set(37.5)
    text = reg.expose()
    assert "# TYPE requests_total counter" in text
    assert '# HELP requests_total requests' in text
    assert 'requests_total{kind="bfs",status="QUEUED"} 3' in text
    assert 'requests_total{kind="sssp",status="REJECTED"} 1' in text
    assert "# TYPE occupancy gauge" in text
    assert "occupancy 37.5" in text
    assert text.endswith("\n")


def test_histogram_exposition_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("sojourn", "layers", buckets=(1, 5, 10))
    for v in (0.5, 3, 7, 100):
        h.observe(v)
    text = reg.expose()
    assert 'sojourn_bucket{le="1"} 1' in text
    assert 'sojourn_bucket{le="5"} 2' in text
    assert 'sojourn_bucket{le="10"} 3' in text
    assert 'sojourn_bucket{le="+Inf"} 4' in text
    assert "sojourn_sum 110.5" in text
    assert "sojourn_count 4" in text


def test_registry_idempotent_and_mismatch():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "x", ("k",))
    assert reg.counter("x_total", "x", ("k",)) is a
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("x_total", "x", ("other",))


def test_label_cardinality_bound():
    from repro.obs.metrics import Counter
    c = Counter("leaky_total", labelnames=("id",), max_series=5)
    for i in range(5):
        c.labels(id=str(i)).inc()
    with pytest.raises(ValueError, match="cardinality bound"):
        c.labels(id="one-too-many")
    with pytest.raises(ValueError, match="expected labels"):
        c.labels(wrong="name")
    with pytest.raises(ValueError):
        c.labels(id="0").inc(-1)       # counters are monotone
    with pytest.raises(ValueError, match="labelled"):
        c.inc()                        # labelled counters need .labels()


def test_metrics_text_default_registry():
    assert metrics_text(MetricsRegistry()) == ""
    reg = MetricsRegistry()
    reg.counter("solo_total").inc(4)
    assert "solo_total 4" in metrics_text(reg)


# ---------------------------------------------------------------------------
# nearest-rank percentile (the CI sojourn gate arithmetic)
# ---------------------------------------------------------------------------


def test_percentile_nearest_rank_pinned():
    from repro.serving.stats import percentile
    xs = list(range(1, 101))           # 1..100
    assert percentile(xs, 50) == 50.0
    assert percentile(xs, 99) == 99.0
    assert percentile(xs, 100) == 100.0
    # the case that distinguishes nearest-rank from linear interpolation:
    # np.percentile([1,2,3,4], 50) == 2.5 — never an observed sample
    assert percentile([1, 2, 3, 4], 50) == 2.0
    assert percentile([1, 2, 3, 4], 99) == 4.0
    assert percentile([7], 99) == 7.0
    assert percentile([], 50) == 0.0
    # always an actual sample
    xs = [0.3, 11.0, 2.5, 8.125]
    for p in (1, 25, 50, 75, 99):
        assert percentile(xs, p) in xs


# ---------------------------------------------------------------------------
# host sweep-log parity
# ---------------------------------------------------------------------------


def test_host_msbfs_recorder_parity():
    from repro.core.hybrid import MAX_TRACE
    from repro.core.msbfs import msbfs_pipelined
    g = rmat_graph(8, edgefactor=8, seed=11)
    roots = np.arange(24, dtype=np.int32) % g.n
    base = msbfs_pipelined(g, roots, lanes=8)
    rec = SweepRecorder(engine="msbfs")
    got = msbfs_pipelined(g, roots, lanes=8, recorder=rec)
    for f in ("parent", "depth", "num_layers", "edges_traversed",
              "trace_dir", "trace_vf", "trace_ef", "trace_eu"):
        assert np.array_equal(np.asarray(getattr(base, f)),
                              np.asarray(getattr(got, f))), f
    # the recorder's layer/mode stream rebuilds the engine traces exactly
    tr = rec.reconstruct_traces(MAX_TRACE, roots.size)
    for f in ("trace_dir", "trace_vf", "trace_ef", "trace_eu"):
        assert np.array_equal(tr[f], np.asarray(getattr(base, f))), f
    assert rec.num_layers == len(rec.records) > 0
    assert set(rec.modes()) <= {"td", "bu", "mixed", "idle"}
    assert any(r.active_lanes > 0 for r in rec.records)
    for r in rec.records:
        assert r.kind == "bfs" and r.engine == "msbfs"
        assert r.active_lanes == len(r.slots)
        assert 0.0 <= r.frontier_density <= 1.0
        assert r.exch_bytes == 0 and r.exch_format == "none"
        assert r.edges_relaxed >= 0 and r.words_touched >= 0


def test_host_sssp_recorder_parity():
    from repro.traversal.sssp import MAX_SSSP_TRACE, sssp_pipelined
    wg = rmat_weighted_graph(8, edgefactor=8, seed=12)
    src = np.arange(10, dtype=np.int32) % wg.csr.n
    base = sssp_pipelined(wg, src, lanes=4)
    rec = SweepRecorder(engine="sssp")
    got = sssp_pipelined(wg, src, lanes=4, recorder=rec)
    for f in ("sources", "dist", "steps", "truncated", "trace_bucket",
              "trace_phase"):
        assert np.array_equal(np.asarray(getattr(base, f)),
                              np.asarray(getattr(got, f))), f
    tr = rec.reconstruct_traces(MAX_SSSP_TRACE, src.size)
    assert np.array_equal(tr["trace_bucket"], np.asarray(base.trace_bucket))
    assert np.array_equal(tr["trace_phase"], np.asarray(base.trace_phase))
    assert set(rec.modes()) <= {"light", "heavy", "mixed", "idle"}


def test_recorder_disabled_never_touches_obs():
    """The zero-cost guarantee: with ``recorder=None`` the drivers and
    the service must never call into ``repro.obs.sweeplog`` — poisoning
    the snapshot hook proves it."""
    from repro.core.msbfs import msbfs_pipelined
    g = rmat_graph(7, edgefactor=8, seed=13)
    roots = np.arange(6, dtype=np.int32)
    boom = mock.patch("repro.obs.sweeplog.snapshot_state",
                      side_effect=AssertionError("obs touched"))
    with boom:
        msbfs_pipelined(g, roots, lanes=8)          # recorder=None: fine
        from repro.serving import AnalyticsService, ServiceConfig
        from repro.serving.trace import synthetic_trace
        wg = rmat_weighted_graph(7, 8, 13)
        svc = AnalyticsService(wg, ServiceConfig(lanes=8, slots=16))
        svc.replay(synthetic_trace(wg.n, 4, mix="bfs", seed=0))
    # ...and the poison is real: a live recorder DOES hit the hook
    with boom, pytest.raises(AssertionError, match="obs touched"):
        msbfs_pipelined(g, roots, lanes=8,
                        recorder=SweepRecorder(engine="msbfs"))


# ---------------------------------------------------------------------------
# distributed sweep-log parity (forced-device subprocesses)
# ---------------------------------------------------------------------------

_DIST_CODE = """
import numpy as np
from repro.graph.generator import rmat_graph
from repro.core.hybrid import MAX_TRACE
from repro.core.msbfs import msbfs_pipelined
from repro.obs import SweepRecorder

g = rmat_graph(8, edgefactor=8, seed=21)
roots = np.arange(16, dtype=np.int32) %% g.n
host = msbfs_pipelined(g, roots, lanes=8)

%(engine_setup)s

rec = SweepRecorder(engine=%(engine_name)r)
res = %(engine_call)s
assert np.array_equal(np.asarray(host.depth), np.asarray(res.depth))
tr = rec.reconstruct_traces(MAX_TRACE, roots.size)
for f in ("trace_dir", "trace_vf", "trace_ef", "trace_eu"):
    assert np.array_equal(tr[f], np.asarray(getattr(res, f))), f
    assert np.array_equal(tr[f], np.asarray(getattr(host, f))), f
assert rec.num_layers == len(rec.records) > 0
assert set(rec.modes()) <= {"td", "bu", "mixed", "idle"}
%(extra)s
print("OBS_DIST_OK", rec.num_layers)
"""


@pytest.mark.parametrize("ndev", [2, 4])
def test_dist_msbfs_recorder_parity(ndev):
    setup = f"""
from repro.core.dist_msbfs import dist_msbfs, host_mesh, partition_graph
mesh = host_mesh({ndev})
dg = partition_graph(g, {ndev})
"""
    code = _DIST_CODE % dict(
        engine_setup=setup, engine_name="dist_msbfs",
        engine_call="dist_msbfs(dg, roots, mesh, lanes=8, recorder=rec)",
        extra="assert all(r.exch_bytes == 0 for r in rec.records)")
    assert "OBS_DIST_OK" in run_in_subprocess(code, devices=ndev)


@pytest.mark.parametrize("grid", [(1, 2), (2, 2)])
def test_dist2d_recorder_parity(grid):
    pr, pc = grid
    setup = f"""
from repro.core.dist2d import dist2d_msbfs, mesh2d, partition_graph_2d
mesh = mesh2d({pr}, {pc})
dg2 = partition_graph_2d(g, {pr}, {pc})
"""
    extra = """
# per-layer exchange deltas must sum to the state's total byte meter
from repro.core import dist2d as d2
st = d2.dist2d_msbfs_engine_init(dg2, mesh, capacity=roots.size, lanes=8)
st = d2.dist2d_msbfs_engine_enqueue(st, roots)
st = d2.dist2d_msbfs_engine_drain(dg2, st, mesh, compress=True)
assert int(rec.total("exch_bytes")) == int(np.asarray(st.exch_bytes))
assert {r.exch_format for r in rec.records} == {"compressed"}
"""
    code = _DIST_CODE % dict(
        engine_setup=setup, engine_name="dist2d",
        engine_call="dist2d_msbfs(dg2, roots, mesh, lanes=8, "
                    "compress=True, recorder=rec)",
        extra=extra)
    assert "OBS_DIST_OK" in run_in_subprocess(code, devices=pr * pc)


@pytest.mark.parametrize("ndev", [2])
def test_dist_sssp_recorder_parity(ndev):
    code = f"""
import numpy as np
from repro.graph.generator import rmat_weighted_graph
from repro.traversal.sssp import MAX_SSSP_TRACE, sssp_pipelined
from repro.core.dist_sssp import (dist_sssp, partition_weighted_graph)
from repro.core.dist_msbfs import host_mesh
from repro.obs import SweepRecorder

wg = rmat_weighted_graph(8, edgefactor=8, seed=22)
src = np.arange(8, dtype=np.int32) % wg.csr.n
host = sssp_pipelined(wg, src, lanes=4)
mesh = host_mesh({ndev})
dwg = partition_weighted_graph(wg, {ndev})
rec = SweepRecorder(engine="dist_sssp")
res = dist_sssp(dwg, src, mesh, lanes=4, compress=True, recorder=rec)
assert np.array_equal(np.asarray(host.dist), np.asarray(res.dist))
tr = rec.reconstruct_traces(MAX_SSSP_TRACE, src.size)
assert np.array_equal(tr["trace_bucket"], np.asarray(res.trace_bucket))
assert np.array_equal(tr["trace_phase"], np.asarray(res.trace_phase))
assert np.array_equal(tr["trace_phase"], np.asarray(host.trace_phase))
assert int(rec.total("exch_bytes")) > 0
print("OBS_DIST_SSSP_OK", rec.num_layers)
"""
    assert "OBS_DIST_SSSP_OK" in run_in_subprocess(code, devices=ndev)


# ---------------------------------------------------------------------------
# trace-event export
# ---------------------------------------------------------------------------


def _recorded_sweep():
    from repro.core.msbfs import msbfs_pipelined
    g = rmat_graph(7, edgefactor=8, seed=31)
    rec = SweepRecorder(engine="msbfs")
    msbfs_pipelined(g, np.arange(8, dtype=np.int32), lanes=8, recorder=rec)
    return rec


def test_sweep_trace_events_schema(tmp_path):
    rec = _recorded_sweep()
    events = validate_trace_events(sweep_trace_events(rec))
    spans = [e for e in events if e["ph"] == "X"]
    assert len(spans) == rec.num_layers
    for e in spans:
        assert e["dur"] > 0 and e["ts"] >= 0
        assert e["args"]["mode"] in ("td", "bu", "mixed", "idle")
    # metadata names the process for Perfetto's track grouping
    metas = [e for e in events if e["ph"] == "M"]
    assert any(e["args"]["name"] == "sweep:msbfs" for e in metas)
    path = write_chrome_trace(str(tmp_path / "sweep.json"), events)
    with open(path) as f:
        doc = json.load(f)
    assert doc["traceEvents"] == events


def test_service_trace_events(tmp_path):
    from repro.serving import AnalyticsService, ServiceConfig
    from repro.serving.trace import synthetic_trace
    tel = Telemetry()
    wg = rmat_weighted_graph(7, 8, 32)
    svc = AnalyticsService(wg, ServiceConfig(lanes=8, slots=32,
                                             telemetry=tel))
    svc.replay(synthetic_trace(wg.n, 8, mix="bfs:2,khop:1", seed=1))
    events = validate_trace_events(svc.trace_events())
    names = " ".join(e["name"] for e in events)
    assert "QUEUED" in names and "RUNNING" in names
    write_chrome_trace(str(tmp_path / "svc.json"), events)
    # telemetry collected the pool's per-layer stream + service metrics
    assert tel.sweeps and tel.sweeps[0].num_layers > 0
    text = svc.metrics_text()
    assert "service_requests_total" in text
    assert "service_answers_total" in text
    assert "service_layers_total" in text
    assert "obs_sweep_layers_total" in text


def test_validate_trace_events_rejects():
    with pytest.raises(ValueError, match="must be a list"):
        validate_trace_events({"not": "a list"})
    with pytest.raises(ValueError, match="unknown phase"):
        validate_trace_events([dict(name="x", ph="Z", pid=1, tid=1)])
    with pytest.raises(ValueError, match="missing 'dur'"):
        validate_trace_events([dict(name="x", ph="X", pid=1, tid=1, ts=0)])
    with pytest.raises(ValueError, match="pid/tid must be integers"):
        validate_trace_events([dict(name="x", ph="i", pid="p", tid=1,
                                    ts=0)])


def test_flight_sink_jsonl(tmp_path):
    path = str(tmp_path / "flight.jsonl")
    rec = SweepRecorder(engine="msbfs", sink=FlightSink(path))
    from repro.core.msbfs import msbfs_pipelined
    g = rmat_graph(7, edgefactor=8, seed=33)
    msbfs_pipelined(g, np.arange(6, dtype=np.int32), lanes=8, recorder=rec)
    rec.sink.close()
    with open(path) as f:
        lines = [json.loads(ln) for ln in f]
    assert len(lines) == rec.num_layers
    for ln, r in zip(lines, rec.records):
        assert ln["layer"] == r.layer and ln["mode"] == r.mode
        assert ln["engine"] == "msbfs" and ln["kind"] == "bfs"


def test_telemetry_bundle_off_returns_none():
    tel = Telemetry(record_sweeps=False)
    assert tel.recorder("msbfs") is None
    assert tel.sweeps == [] and tel.last_sweep() is None
    tel.registry.counter("still_works_total").inc()
    assert "still_works_total 1" in tel.metrics_text()


def test_telemetry_sweep_eviction_is_counted():
    """No silent caps: every sweep evicted by the ``max_sweeps`` bound
    bumps ``obs_sweeps_dropped_total`` on the bundle's registry."""
    tel = Telemetry(max_sweeps=3)
    recs = [tel.recorder("msbfs") for _ in range(3)]
    assert tel.sweeps == recs                   # under the bound: no drop
    assert "obs_sweeps_dropped_total" not in tel.metrics_text()
    tel.recorder("msbfs")
    tel.recorder("msbfs")
    assert len(tel.sweeps) == 3                 # bound held...
    kept = [id(r) for r in tel.sweeps]          # ...oldest two evicted
    # identity, not ==: empty recorders are value-equal dataclasses
    assert id(recs[0]) not in kept and id(recs[1]) not in kept
    assert id(recs[2]) in kept
    assert "obs_sweeps_dropped_total 2" in tel.metrics_text()
