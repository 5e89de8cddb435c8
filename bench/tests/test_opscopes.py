"""The op-name scopes and the program's spans of a trace
(``bench.lib.opscopes``), on two small traces recorded on a TPU v5e:
``data/tiny_sweep.xplane.pb`` (``data/record_trace.py``, a program without
scopes) and ``data/tiny_scoped.xplane.pb`` (``data/record_scoped_trace.py``:
a scoped sweep and eight service ticks in ``repro:`` spans)."""
import os

import pytest

from bench import harness
from bench.lib import opscopes, xplane
from bench.lib.peaks import peaks_for

DATA = os.path.join(os.path.dirname(__file__), "data")
SWEEP = os.path.join(DATA, "tiny_sweep.xplane.pb")
SCOPED = os.path.join(DATA, "tiny_scoped.xplane.pb")
# the engine step's named scopes (``repro.core.packed.STEP_SCOPES``)
STEP_SCOPES = ("bfs_refill", "bfs_direction", "bfs_trace", "bfs_topdown",
               "bfs_bu_probe", "bfs_bu_fallback", "bfs_flush")
FACTS = {"sweeps": 2, "n": 4096, "m": 131072, "keys_per_sweep": 64,
         "client_lag_ms": [3.0, 1.0, 2.0],
         "queue_wait_ms": [10.0, 30.0, 20.0], "tick_ms": [5.0, 7.0]}
# what each accepted per-layer metric reads from tiny_sweep and FACTS
READINGS = {
    "client_lag_p95_ms.serve": 3.0,
    "queue_wait_p95_ms.serve": 30.0,
    "tick_ms.serve": 6.0,
    "device_idle_share.serve": 31.877626226470923,
    "drain_ms.bfs": 27.47592900000001,
    "parents_ms.bfs": 33.386219000000004,
    "sweep_roofline": 0.005291082201906443,
    "device_idle_share.bfs": 31.877626226470923,
}


@pytest.fixture(scope="module")
def sweep():
    return xplane.load(SWEEP), opscopes.load(SWEEP)


@pytest.fixture(scope="module")
def scoped():
    return opscopes.load(SCOPED)


@pytest.mark.parametrize("name", sorted(READINGS))
def test_accepted_metrics_read_the_same_from_either_reduction(sweep, name):
    reader = harness.metric_reader(name)
    got = [reader.read(harness.RunView(trace=t, facts=FACTS, cell=None,
                                       peaks=peaks_for("TPU v5 lite")))
           for t in sweep]
    assert got[0] == pytest.approx(READINGS[name], rel=1e-12)
    assert got[1] == got[0]


def test_same_events_as_profile_data(sweep):
    old, new = sweep
    assert new.window == old.window
    for a, b in zip(old.ops + old.modules + [old.spans],
                    new.ops + new.modules + [new.spans]):
        assert [(e.name, e.start, e.end) for e in a] == \
            [(e.name, e.start, e.end) for e in b]
    assert new.idle_gaps(10) == old.idle_gaps(10)
    assert new.top_ops(10) == old.top_ops(10)


def test_unscoped_program_reads_no_scope(sweep):
    t = sweep[1]
    assert all(t.scope_seconds(s) == 0 for s in STEP_SCOPES)
    # its operations still carry paths: the drain's leaves are 99% of it
    drain = t.module_seconds(["jit__drain"])
    assert 0.98 * drain < t.scope_seconds("jit(_drain)") <= drain
    assert t.span_seconds("repro:service.tick") == (0, 0)


def test_scopes_split_the_scoped_programs(scoped):
    modules = scoped.module_seconds(["jit__drain", "jit_msbfs_engine_step"])
    per_scope = {s: scoped.scope_seconds(s) for s in STEP_SCOPES}
    for s in ("bfs_direction", "bfs_trace", "bfs_topdown", "bfs_bu_probe",
              "bfs_flush"):
        assert per_scope[s] > 0, s
    assert sum(per_scope.values()) == pytest.approx(modules, rel=0.05)


@pytest.mark.parametrize("path", [SWEEP, SCOPED], ids=["sweep", "scoped"])
def test_operations_with_a_path_enclose_none(path):
    """Leaves: no operation with a path holds another of nonzero length,
    so a scope's seconds count nothing twice."""
    ops = sorted(opscopes.load(path).ops[0],
                 key=lambda e: (e.start, -e.end))
    for e, nxt in zip(ops, ops[1:]):
        if e.path is not None and nxt.end > nxt.start:
            assert not (nxt.start < e.end and nxt.end <= e.end), e.path


def test_service_spans_and_gaps_inside_them(scoped):
    ticks, n = scoped.span_seconds("repro:service.tick")
    assert n == 8 and ticks > 0
    for phase in ("dispatch", "launch", "wait", "readout", "collect",
                  "account"):
        seconds, count = scoped.span_seconds(f"repro:service.{phase}")
        assert count == 8 and 0 < seconds < ticks, phase
    # a gap is named by the innermost span around its middle: after the
    # host pause, the longest gaps are the ticks' read-out copies, inside
    # repro:service.tick and bench:serve as well
    gaps = scoped.idle_gaps(5)
    assert gaps[0][0] == "bench:wait"
    assert [name for name, _ in gaps[1:]] == ["repro:service.readout"] * 4
