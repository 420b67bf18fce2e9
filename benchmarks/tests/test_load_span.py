"""The hand-over of a plan's load from the harness's `bench.load` bracket to
the program's own `load` span (ISSUE 37), on a hand-made span tree fed
through `plan_loop.bracket()` and the readers the metric files name:
`load_program_s.plan` reads the `load` span, `load_s.plan` is left with the
gap from the call to `load`, and `other_s.plan`, whose names do not list
`load`, takes the span's time, so that the two old metrics sum as before."""

import copy
import importlib
import os
from types import SimpleNamespace

import pytest

from benchmarks.drivers.plan_loop import bracket
from benchmarks.harness import HERE, load_json
from benchmarks.window import Item

CALL, RETURN = 100.0, 102.0


def span(name, start, end, *children):
    return {"name": name, "start": start, "end": end, "children": list(children)}


#: one plan as the program traces it, on the harness's clock round the call
PLAN = span(
    "apply", CALL, RETURN,
    span("load", 100.001, 100.801,
         span("load.parse", 100.001, 100.401), span("load.objects", 100.401, 100.501),
         span("load.parse", 100.502, 100.702), span("load.objects", 100.702, 100.781)),
    span("prepare", 100.81, 101.0),
    span("schedule", 101.0, 101.5),
    span("decode", 101.5, 101.55),
    span("report", 101.55, 101.9,
         span("report.nodes", 101.55, 101.6), span("report.pods", 101.6, 101.8),
         span("report.apps", 101.8, 101.89)),
)


def without_load(tree):
    """The same plan as the parent traces it: no span over the load."""
    out = copy.deepcopy(tree)
    out["children"] = [c for c in out["children"] if c["name"] != "load"]
    return out


def read(metric, tree):
    spec = load_json(os.path.join(HERE, "metrics", metric + ".json"))
    reader = importlib.import_module("benchmarks.readers." + spec["reader"])
    tree = copy.deepcopy(tree)
    bracket(tree)
    run = SimpleNamespace(window=SimpleNamespace(items=[Item(start=CALL, end=RETURN, ok=True, spans=tree)]))
    return reader.read(run, **spec.get("args", {}))


def test_the_new_metrics_read_the_programs_spans():
    assert read("load_program_s.plan", PLAN) == pytest.approx(0.8)
    assert read("load_parse_s.plan", PLAN) == pytest.approx(0.4 + 0.2)
    assert read("load_objects_s.plan", PLAN) == pytest.approx(0.1 + 0.079)
    assert read("report_nodes_s.plan", PLAN) == pytest.approx(0.05)
    assert read("report_apps_s.plan", PLAN) == pytest.approx(0.09)
    assert read("report_s.plan", PLAN) == pytest.approx(0.35)


def test_the_bracket_is_left_with_the_gap_from_the_call_to_load():
    assert read("load_s.plan", PLAN) == pytest.approx(0.001)
    assert read("load_s.plan", without_load(PLAN)) == pytest.approx(0.81)


def test_other_seconds_takes_what_load_covers_and_the_two_old_metrics_sum_as_before():
    spanned = 0.19 + 0.5 + 0.05  # prepare, schedule, decode
    other = read("other_s.plan", PLAN)
    assert other == pytest.approx((RETURN - CALL) - 0.001 - spanned)
    assert other - read("other_s.plan", without_load(PLAN)) == pytest.approx(
        read("load_program_s.plan", PLAN) + 0.009)  # the span, and the gap from it to prepare
    assert read("load_s.plan", PLAN) + other == pytest.approx(
        read("load_s.plan", without_load(PLAN)) + read("other_s.plan", without_load(PLAN)))


def test_a_parent_without_the_spans_reads_nothing_for_the_new_metrics():
    for metric in ("load_program_s.plan", "load_parse_s.plan", "load_objects_s.plan"):
        assert read(metric, without_load(PLAN)) is None
