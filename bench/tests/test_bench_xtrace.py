"""The trace reduction, on a small trace recorded on one TPU v5e: two
8-row REACH flushes at 2^10 vertices, served with the harness's profiled
tracer inside a ``bench_window`` annotation."""
import os

import pytest

from bench.lib import harness, xtrace

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return xtrace.reduce_trace(TRACE, harness.HOST_SPANS)


def test_window_and_busy_time(reduced):
    assert reduced.window_s == pytest.approx(0.009437919)
    assert reduced.busy_s == [pytest.approx(0.001150673)]
    assert 0 < reduced.busy_s[0] < reduced.window_s


def test_top_operations_by_self_time(reduced):
    names = [n for n, _ in reduced.device_ops]
    secs = [s for _, s in reduced.device_ops]
    assert len(names) == 10 and secs == sorted(secs, reverse=True)
    assert not any(n.startswith("while") for n in names[:3])
    assert all(" " not in n and not n.startswith("%") for n in names)
    assert sum(secs) <= reduced.busy_s[0]


def test_idle_gaps_named_by_host_span(reduced):
    gaps = reduced.idle_gaps
    assert len(gaps) == 10
    assert {n for n, _ in gaps} <= harness.HOST_SPANS | {xtrace.IDLE_HOST}
    assert gaps[0][0] == "finalize_batch"
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    assert sum(s for _, s in gaps) <= reduced.window_s - reduced.busy_s[0]


def test_merge_and_self_times():
    assert xtrace.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3], [5, 9]]
    t = xtrace.self_times([(0, 10, "while"), (1, 3, "a"), (4, 8, "b"),
                           (5, 6, "c"), (12, 13, "a")])
    assert t == {"while": 4, "a": 3, "b": 3, "c": 1}
    assert xtrace.short_name("%fusion.3 = pred[8]{0} fusion(x)") == "fusion.3"
