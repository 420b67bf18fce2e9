"""`plan-15k` (the configuration `gke-15k-50k`), at sizes a test run can
hold: the files load and say what `BENCHMARK.json` says; the program, on the
interpreted kernel, replays through the reference with every check nought on
four seeds; `plan-loop-kernel` fails the run at the warm-up plan's first XLA
rung and where the warm-up report names another engine than the megakernel,
and keeps each span's attributes; `span_attr_max` reads the largest
one-sublane VMEM estimate, and None where nothing carries it."""

import argparse
import importlib
import json
import os
import time
from types import SimpleNamespace

import pytest

from benchmarks import harness
from benchmarks.drivers import Context, plan_loop, plan_loop_kernel
from benchmarks.harness import ROOT, load_json, resolve
from benchmarks.readers import span_attr_max
from benchmarks.tests.test_faults import failing
from benchmarks.window import Item, Window

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(HERE, "configs", "gke-15k-50k.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(HERE, "traffic", "short-15k.json")) as f:
    TRAFFIC = json.load(f)
NOTHING_DIFFERS = {
    "misplaced_pods": 0, "worst_score_gap": 0.0, "infeasible_pods": 0, "unscheduled_diff": 0,
    "answer_diff": 0, "added_nodes_diff": 0, "plans_differing": 0, "plans_unanswered": 0,
}


def drive(tmp_path, seed):
    ctx = Context(config=CONFIG, traffic=TRAFFIC, seed=seed, scratch=str(tmp_path), rehearse=True,
                  sizes=CONFIG["tiny"])
    driver = importlib.import_module("benchmarks.drivers.plan_loop_kernel").Driver(ctx)
    driver.prepare()
    return driver


# -- the files -----------------------------------------------------------------


def test_the_cell_and_its_metric_are_in_the_benchmark_as_the_files_have_them():
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    parts = resolve(bench, "plan-15k")
    entry = next(c for c in bench["configs"] if c["name"] == "gke-15k-50k")
    assert parts["cell"] == {"name": "plan-15k", "config": "gke-15k-50k", "traffic": "short-15k", "chips": 1,
                             "why": parts["cell"]["why"]}
    assert len(parts["cell"]["why"]) <= 200 and len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == CONFIG["reduced"] == ["pods"]
    assert CONFIG["source_sizes"]["pods"] > CONFIG["sizes"]["pods"]
    # k8s-5k-50k's shape and stream at the source's node count
    base = load_json(os.path.join(HERE, "configs", "k8s-5k-50k.json"))
    assert CONFIG["sizes"] == dict(base["sizes"], nodes=15000, short_nodes=13800, ssd_cap=1, new_cap=110)
    assert CONFIG["tiny"] == base["tiny"] and CONFIG["generator"] == base["generator"]
    assert CONFIG["roofline_shape"] == base["roofline_shape"] and CONFIG["guarantees"] == base["guarantees"]
    assert TRAFFIC == dict(load_json(os.path.join(HERE, "traffic", "short.json")), driver="plan-loop-kernel")
    assert {m["name"] for m in parts["end_to_end"]} == {"plan_s", "setup_s"}
    mine = [m for m in bench["per_layer"] if m.get("workloads") == ["plan-15k"]]
    assert [m["name"] for m in mine] == ["mk_vmem_mb.plan"] and mine[0]["layer"] == "engine selection and scan"
    reported = {m["name"] for m in parts["per_layer"]}
    assert {"mk_inputs_s.plan", "mk_launch_s.plan", "mk_wait_s.plan", "compile_path_s.plan", "report_s.plan",
            "report_nodes_s.plan", "report_apps_s.plan", "load_program_s.plan", "load_parse_s.plan",
            "load_objects_s.plan", "scan_roofline.plan", "mk_vmem_mb.plan"} <= reported
    assert not {"xla_launch_s.plan", "xla_wait_s.plan"} & reported


def test_the_short_plan_adds_nodes_inside_the_candidates_at_the_cells_size():
    """The arithmetic of `assumed`: 9,200 ssd slots of the 13,800 short nodes
    meet 12,500 ssd-pinned pods, and 30 nodes of 110 pods take the rest."""
    sizes = CONFIG["sizes"]
    ssd_slots = sum(1 for j in range(sizes["short_nodes"]) if j % 3 != 0) * sizes["ssd_cap"]
    pinned = sum(sizes["pods"] // sizes["workloads"] for w in range(sizes["workloads"]) if w % 4 == 0)
    added = -(-(pinned - ssd_slots) // sizes["new_cap"])
    assert (ssd_slots, pinned, added) == (9200, 12500, 30) and added <= sizes["max_new_nodes"]


# -- the program against the reference -----------------------------------------


@pytest.mark.parametrize("seed", [5, 77, 2147483659, 3000000019])
def test_the_plan_on_the_kernel_replays_through_the_reference_with_every_check_nought(tmp_path, monkeypatch, seed):
    monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")
    driver = drive(tmp_path, seed)
    warm = Window(opened=0.0, closed=1.0, items=[driver.one(-1, False)])
    driver.warmed(warm)  # the warm-up plan stayed on the kernel
    window = Window(opened=0.0, closed=1.0, items=[driver.one(0, True)])
    driver.after_window(window)
    report = window.items[0].info["report"]
    assert report["success"] and report["engine"] == "megakernel" and report["added"] > 0
    assert {c["name"]: c["value"] for c in driver.compare(window)} == NOTHING_DIFFERS
    names = {sp["name"] for sp in span_attr_max.spans(window.items[0].spans)}
    assert {"engine.megakernel", "sweep.megakernel"} <= names and not {"engine.xla", "sweep.xla"} & names


# -- the driver's fail-fast ----------------------------------------------------


def test_the_warm_up_fails_at_the_first_xla_rung(tmp_path, monkeypatch):
    """Off the kernel (no TPU here, no interpreter asked for) the warm-up
    plan stops where the XLA scan's rung opens, and the run with it."""
    monkeypatch.delenv("OPENSIM_FASTPATH", raising=False)
    driver = drive(tmp_path, 5)
    item = driver.one(-1, False)
    assert not item.ok and not plan_loop.parse_report(item.answer)["success"]  # it never got that far
    with pytest.raises(RuntimeError, match="warm-up plan failed"):
        driver.warmed(Window(opened=0.0, closed=1.0, items=[item]))
    args = argparse.Namespace(workload="plan-15k", seed=5, seconds=0.3, trace=0, rehearse=True)
    lines, start = [], time.monotonic()
    with pytest.raises(RuntimeError):
        harness.run_cell(args, time.monotonic(), lines.append)
    assert len(lines) == 1 and lines[0]["correct"] is False and lines[0]["attempted"] == 0
    assert time.monotonic() - start < 60


def test_a_warm_up_report_that_names_the_xla_scan_fails_the_run(tmp_path):
    driver = drive(tmp_path, 5)
    path = os.path.join(str(tmp_path), "report-warm.txt")
    with open(path, "w") as f:
        f.write("Simulation success!\nScheduling engine: xla (skipped megakernel: VMEM estimate 21.4 MB exceeds "
                "the 10 MB budget)\n")
    with pytest.raises(plan_loop_kernel.OffTheKernel, match="not on the megakernel"):
        driver.warmed(Window(opened=0.0, closed=1.0, items=[Item(start=0.0, end=1.0, ok=True, answer=path)]))
    with open(path, "w") as f:
        f.write("Simulation success!\nScheduling engine: megakernel\n")
    driver.warmed(Window(opened=0.0, closed=1.0, items=[Item(start=0.0, end=1.0, ok=True, answer=path)]))


def test_a_sound_rehearsal_is_correct(monkeypatch):
    monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")
    args = argparse.Namespace(workload="plan-15k", seed=7, seconds=0.3, trace=1, rehearse=True)
    lines = []
    harness.run_cell(args, time.monotonic(), lines.append)
    (line,) = lines
    assert line["correct"] is True and not failing(line) and line["failed"] == 0
    assert line["metrics"]["mk_vmem_mb.plan"]["value"] > 0


# -- the reader ----------------------------------------------------------------

ARGS = load_json(os.path.join(HERE, "metrics", "mk_vmem_mb.plan.json"))["args"]


def _run_with(*trees):
    items = [Item(start=0.0, end=1.0, ok=True, spans=t) for t in trees]
    return SimpleNamespace(window=Window(opened=0.0, closed=1.0, items=items))


def _span(name, children=(), **attrs):
    return {"name": name, "start": 0.0, "end": 1.0, "attrs": attrs, "children": list(children)}


def test_the_reader_takes_the_largest_one_sublane_estimate_in_mb():
    tree = _span("apply", [
        _span("schedule", [_span("engine.megakernel", [_span("mk.inputs", sublanes=1, vmem_estimate_bytes=5_545_000)])]),
        _span("capacity.search", [_span("sweep.megakernel", [
            _span("mk.inputs", sublanes=8, vmem_estimate_bytes=17_012_000)])]),
        _span("schedule", [_span("engine.megakernel", [_span("mk.inputs", sublanes=1, vmem_estimate_bytes=5_546_000)])]),
    ])
    assert span_attr_max.read(_run_with(tree, None), **ARGS) == pytest.approx(5.546)


def test_nothing_to_read_is_none_not_zero():
    assert span_attr_max.read(_run_with(None), **ARGS) is None
    # a driver that keeps no attributes (`plan-loop`), or a program that ran no kernel
    bare = {"name": "apply", "start": 0.0, "end": 1.0, "children": [
        {"name": "mk.inputs", "start": 0.0, "end": 1.0, "children": []}]}
    assert span_attr_max.read(_run_with(bare), **ARGS) is None
    assert span_attr_max.read(_run_with(_span("apply", [_span("engine.xla")])), **ARGS) is None
