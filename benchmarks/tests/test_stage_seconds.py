"""The `stage_seconds` reader on hand-made `CompileWatch` snapshots."""

from types import SimpleNamespace

import pytest

from benchmarks.readers import stage_seconds

STAGES = ["trace", "lower", "backend", "cache_retrieval"]


def snapshot(trace, lower, backend, retrieval):
    return {"backend": {"compiles": 3, "seconds": backend}, "cache_events": {},
            "stages": {"trace": {"count": 4, "seconds": trace}, "lower": {"count": 4, "seconds": lower},
                       "backend": {"count": 3, "seconds": backend},
                       "cache_retrieval": {"count": 3, "seconds": retrieval}}}


def run_of(before, after, items=2):
    return SimpleNamespace(compiles={"before": before, "after": after},
                           window=SimpleNamespace(items=[object()] * items))


def test_the_difference_of_two_snapshots_over_the_named_stages_per_item():
    run = run_of(snapshot(1.0, 0.5, 2.0, 0.1), snapshot(1.4, 0.7, 2.9, 0.2))
    assert stage_seconds.read(run, STAGES) == pytest.approx((0.4 + 0.2 + 0.9 + 0.1) / 2)
    assert stage_seconds.read(run, ["trace"]) == pytest.approx(0.2)
    assert stage_seconds.read(run_of(snapshot(1, 1, 1, 1), snapshot(1, 1, 1, 1)), STAGES) == 0.0


def test_a_program_without_the_counter_reads_nothing():
    old = {"backend": {"compiles": 3, "seconds": 2.0}, "cache_events": {}}
    assert stage_seconds.read(run_of(old, old), STAGES) is None
    assert stage_seconds.read(run_of(old, snapshot(1, 1, 1, 1)), STAGES) is None
    assert stage_seconds.read(SimpleNamespace(compiles=None, window=SimpleNamespace(items=[1])), STAGES) is None
    assert stage_seconds.read(run_of(snapshot(1, 1, 1, 1), snapshot(2, 2, 2, 2), items=0), STAGES) is None
