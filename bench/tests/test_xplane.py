"""The trace reduction on a small trace recorded on a TPU v5e
(``data/tiny_sweep.xplane.pb``, made by ``data/record_trace.py``): two
64-key sweeps at scale 12 with a 20 ms host wait between them."""
import os

import pytest

from bench.lib import xplane

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "tiny_sweep.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return xplane.load(DATA)


def test_window_and_spans(trace):
    assert trace.devices == 1
    assert 0.15 < trace.window_s < 0.25
    names = [s.name for s in trace.spans]
    assert names.count("bench:sweep") == 2 and names.count("bench:wait") == 1
    assert all(trace.window[0] <= s.start <= s.end <= trace.window[1]
               for s in trace.spans)


def test_busy_is_a_union_inside_the_window(trace):
    busy = trace.busy_s()
    # nested ops (a while loop and its body) are counted once
    total = sum(e.end - e.start for e in trace.ops[0])
    assert 0 < busy < trace.window_s < total
    assert trace.idle_share() == pytest.approx(1 - busy / trace.window_s)


def test_programs_of_the_sweep(trace):
    drain = trace.module_seconds(["jit__drain"])
    parents = trace.module_seconds(["jit__derive_parents"])
    assert drain > 0 and parents > 0
    top = dict(trace.top_ops(10))
    assert top["jit__drain"] == pytest.approx(drain)
    # a program's span also covers the few ns between its ops
    assert sum(top.values()) == pytest.approx(trace.busy_s(), rel=0.01)
    assert trace.module_seconds(["jit_no_such_program"]) == 0


def test_longest_idle_gap_is_the_host_wait(trace):
    gaps = trace.idle_gaps(3)
    assert gaps[0][0] == "bench:wait"
    assert 0.02 <= gaps[0][1] < 0.03
    assert gaps[0][1] >= gaps[1][1] >= gaps[2][1]


def test_union_and_merge_helpers():
    assert xplane._union([(0, 2), (1, 3), (5, 6)]) == 4
    assert xplane._union([]) == 0
    assert xplane._merged([(1, 2), (0, 1.5), (3, 4)]) == [[0, 2], [3, 4]]
    assert xplane.module_name("jit__drain(3336116665453214687)") == \
        "jit__drain"
