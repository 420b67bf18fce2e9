"""The reduction from `.xplane.pb` to busy and idle time, per-operation time
and the host owner of each idle gap, on the small trace recorded beside it
(`benchmarks/testdata/plan-fit.xplane.pb`: one traced plan of plan-fit on a
TPU v5 lite)."""

import os

import pytest

from benchmarks import xplane

TRACE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "testdata", "plan-fit.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return xplane.reduce(TRACE)


def test_the_window_is_the_harness_annotation_and_busy_fits_inside_it(red):
    assert red["window_s"] == pytest.approx(8.888622586, rel=1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["busy_s"] == pytest.approx(0.197415032, rel=1e-6)
    assert list(red["busy"]) == ["/device:TPU:0"]
    merged = red["busy"]["/device:TPU:0"]
    assert all(a[1] <= b[0] for a, b in zip(merged, merged[1:]))  # disjoint and in order
    assert xplane.total(merged) == pytest.approx(red["busy_s"])


def test_operations_are_named_without_their_fingerprint_and_sorted_by_time(red):
    names = [n for n, _s in red["device_ops"]]
    assert names[0] == "jit_wrapped"  # the megakernel's pallas_call
    assert not any("(" in n for n in names)
    seconds = [s for _n, s in red["device_ops"]]
    assert seconds == sorted(seconds, reverse=True)
    assert sum(seconds) == pytest.approx(red["busy_s"], rel=1e-3)  # one device: programs do not overlap


def test_a_trace_without_the_marker_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce(TRACE, marker="no.such.annotation")


def test_interval_arithmetic():
    assert xplane.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert xplane.clip([(0, 2), (3, 5), (7, 8)], 1, 4) == [(1, 2), (3, 4)]
    assert xplane.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert xplane.gaps([], 0, 5) == [(0, 5)]
    assert xplane.op_name("jit__schedule_pods_jit(2000788161615187272)") == "jit__schedule_pods_jit"


def test_idle_gaps_go_to_the_deepest_host_span_that_covers_them():
    # device busy 2-3 and 6-7 of a window 0-10 (trace clock = host clock + 100)
    red = {"window": (100.0, 110.0), "busy": {"/device:TPU:0": [(102.0, 103.0), (106.0, 107.0)]}}
    tree = {"name": "apply", "start": 0.0, "end": 9.0, "children": [
        {"name": "prepare", "start": 0.5, "end": 2.0, "children": []},
        {"name": "schedule", "start": 2.0, "end": 7.5, "children": [
            {"name": "engine.xla", "start": 2.0, "end": 7.0, "children": []}]},
    ]}
    got = dict(xplane.idle_gaps(red, [tree], host_at_window_open=0.0))
    assert got["prepare"] == pytest.approx(1.5)
    assert got["engine.xla"] == pytest.approx(3.0)  # 3-6: idle inside the engine's own span
    assert got["schedule"] == pytest.approx(0.5)  # 7-7.5: after the engine, before the span closed
    assert got["apply"] == pytest.approx(0.5 + 1.5)  # 0-0.5 and 7.5-9
    assert got["(no span)"] == pytest.approx(1.0)  # 9-10
    assert sum(got.values()) == pytest.approx(10.0 - 2.0)
