"""`plan-binpack` (the configuration `k8s-5k-50k-binpack`), at sizes a test
run can hold: the generator writes the profile's file and hands the reference
the same profile; the program, on its XLA scan and on the interpreted kernel,
replays through `kube_binpack_reference` with nothing misplaced and the exact
count of added nodes, and packs (fewer nodes than the default profile); a pod
moved reads not correct; the bfloat16 control of the comparison reads not
correct and float32 put in the same place reads nought; the reference's shape
function and its f > 0 rule; `roofline_binpack` and the counter's reader; the
files say the same thing as `BENCHMARK.json`."""

import importlib
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest
import yaml

from benchmarks import promtext, roofline, roofline_binpack, roofline_gpushare
from benchmarks.control import control
from benchmarks.drivers import Context
from benchmarks.harness import ROOT, load_json, resolve
from benchmarks.readers import counter_delta, scan_roofline_binpack
from benchmarks.reference import kube_binpack_reference as R
from benchmarks.reference.kube_reference import HOSTNAME, F32, NodeSpec, Workload
from benchmarks.tests.test_faults import break_answer, failing, move_one_pod, run
from benchmarks.window import Item, Window

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(HERE, "configs", "k8s-5k-50k-binpack.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(HERE, "traffic", "short-binpack.json")) as f:
    TRAFFIC = json.load(f)
SHAPE = CONFIG["roofline_shape"]
SHRUNK = {"nodes": 300, "pods": 3000, "short_nodes": 270}
NOTHING_DIFFERS = {
    "misplaced_pods": 0, "worst_score_gap": 0.0, "infeasible_pods": 0, "unscheduled_diff": 0,
    "answer_diff": 0, "added_nodes_diff": 0, "plans_differing": 0, "plans_unanswered": 0,
}


def drive(tmp_path, seed, sizes=None):
    ctx = Context(config=CONFIG, traffic=TRAFFIC, seed=seed, scratch=str(tmp_path), rehearse=True,
                  sizes=sizes or CONFIG["tiny"])
    driver = importlib.import_module("benchmarks.drivers.plan_loop_profile").Driver(ctx)
    driver.prepare()
    return driver


# -- the generator -------------------------------------------------------------


def test_the_generator_writes_the_profile_and_hands_the_reference_the_same(tmp_path):
    driver = drive(tmp_path, 5)
    with open(driver.inputs["scheduler_config"]) as f:
        doc = yaml.safe_load(f)
    (profile,) = doc["profiles"]
    assert doc["kind"] == "KubeSchedulerConfiguration" and profile["schedulerName"] == "default-scheduler"
    assert profile["plugins"]["score"] == {"disabled": [{"name": "NodeResourcesLeastAllocated"}],
                                           "enabled": [{"name": "RequestedToCapacityRatio", "weight": 1}]}
    cluster = driver.inputs["variants"]["short"]["cluster"]
    assert cluster.profile == R.Profile(balanced=1.0, least=0.0, rtcr=1.0, spread=2.0, share=2.0,
                                        shape=((0, 0), (100, 10)), resources=(("cpu", 1), ("memory", 1)))
    assert cluster.with_new_nodes(3).profile == cluster.profile and len(cluster.with_new_nodes(3).nodes) == 29


# -- the program against the reference -----------------------------------------


@pytest.mark.parametrize("seed", [5, 3000000023])
@pytest.mark.parametrize("engine", ["xla", "megakernel"])
def test_the_plan_replays_through_the_reference_with_the_exact_count(tmp_path, monkeypatch, engine, seed):
    monkeypatch.setenv("OPENSIM_DISABLE_NATIVE", "1")
    if engine == "megakernel":
        monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")
    driver = drive(tmp_path, seed)
    window = Window(opened=0.0, closed=1.0, items=[driver.one(0, False)])
    driver.after_window(window)
    report = window.items[0].info["report"]
    assert report["success"] and report["engine"].startswith(engine), report["engine"]
    assert report["added"] > 0
    assert {c["name"]: c["value"] for c in driver.compare(window)} == NOTHING_DIFFERS


def test_the_profile_packs_tighter_than_the_default_one(tmp_path):
    """The tiny cluster under both profiles, in the reference: bin packing
    needs no more nodes, and puts the first Deployment's pods on fewer."""
    cluster = drive(tmp_path, 5).inputs["variants"]["short"]["cluster"]
    default = R.ProfiledCluster(cluster.nodes, cluster.bound, cluster.workloads, cluster.new_node, R.Profile())
    placed = {}
    for name, c in (("binpack", cluster), ("default", default)):
        ref = R.Reference(c.with_new_nodes(8))
        ref.free_run()
        placed[name] = ref.order()
    used = {name: len(set(order[cluster.workloads[0].name])) for name, order in placed.items()}
    assert used["binpack"] < used["default"]


def test_one_pod_moved_where_the_answer_is_produced(monkeypatch):
    break_answer(monkeypatch, move_one_pod)
    line = run("plan-binpack")
    assert line["correct"] is False and failing(line) & {"worst_score_gap", "misplaced_pods", "infeasible_pods"}


def test_a_sound_run_is_correct():
    line = run("plan-binpack")
    assert line["correct"] is True and not failing(line) and line["failed"] == 0


@pytest.mark.parametrize("seed", [5, 2147483659, 3000000019])
def test_the_low_precision_control_is_not_correct(seed):
    """At a tenth of the cell's pods the control misplaces fewer than the
    limit of 100 pods; at the cell's own size it fails both (`PERF.md`)."""
    got = control("plan-binpack", seed, SHRUNK)
    assert got["control"] == "bfloat16" and got["control_correct"] is False
    checks = {c["name"]: c["value"] for c in got["checks"]}
    assert checks["worst_score_gap"] > 0.05 and checks["misplaced_pods"] > 0


def test_the_reference_in_its_own_precision_put_in_the_programs_place_is_correct():
    got = control("plan-binpack", 5, SHRUNK, precision="float32")
    assert got["control_correct"] is True and all(c["value"] == 0 for c in got["checks"])


# -- the reference's own arithmetic --------------------------------------------


def _one_node_ref(profile, cpu_m=4000, mem=8 << 30):
    node = NodeSpec("n0", cpu_m, mem, 110, {HOSTNAME: "n0"})
    w = Workload("default/w", 1, 1000, 2 << 30, {"app": "w"})
    ref = R.Reference(R.ProfiledCluster([node], [], [w], None, profile))
    return ref


@pytest.mark.parametrize("util,want", [(0.0, 0.0), (20.0, 35.0), (40.0, 70.0), (70.0, 50.0), (100.0, 30.0), (110.0, 30.0)])
def test_the_shape_is_kubes_broken_linear_function(util, want):
    ref = _one_node_ref(R.Profile(rtcr=1.0, shape=((0, 0), (40, 7), (100, 3))))
    assert ref._shape_score(np.array([util], F32))[0] == pytest.approx(want)


def test_a_resource_whose_score_is_0_is_left_out_of_the_mean():
    """With the source's shape an idle resource scores 0 and does not count:
    the node's score is the other resource's alone, not half of it."""
    ref = _one_node_ref(R.Profile(rtcr=1.0, shape=((0, 0), (100, 10))))
    hundred = F32(100.0)
    cap = {"cpu": np.array([F32(4000)]), "memory": np.array([F32(8 << 30)])}
    both = ref._rtcr({"cpu": np.array([F32(1000)]), "memory": np.array([F32(2 << 30)])}, cap)
    assert both[0] == pytest.approx(25.0)
    one = ref._rtcr({"cpu": np.array([F32(2000)]), "memory": np.array([F32(0)])}, cap)
    assert one[0] == pytest.approx(50.0)  # memory's f is 0: the mean is cpu's alone
    none = ref._rtcr({"cpu": np.array([F32(0)]), "memory": np.array([F32(0)])}, cap)
    assert none[0] == 0.0
    over = ref._rtcr({"cpu": np.array([F32(5000)]), "memory": np.array([F32(0)])}, cap)
    assert over[0] == hundred  # a request over the capacity reads the shape at 100


# -- the count of the work -----------------------------------------------------


def test_the_rtcr_term_adds_operations_and_no_bytes():
    q = {"nodes": 4600, "pods": 50000, "resident": 0, "scenario_nodes": [4600 + k for k in
                                                                        roofline_gpushare.sweep_counts(24, 128)]}
    plain = {"ops": 0.0, "bytes": 0.0}
    for nodes in [q["nodes"]] + q["scenario_nodes"]:
        w = roofline.question_work(nodes, q["pods"], 0, SHAPE)
        plain["ops"] += w["ops"]
        plain["bytes"] += w["bytes"]
    got = roofline_binpack.question_work(q, SHAPE)
    assert got["bytes"] == plain["bytes"]
    per_cell = SHAPE["rtcr_resources"] * (roofline.OPS_PER_COLUMN + 2 * SHAPE["shape_points"] + 4)
    assert got["ops"] - plain["ops"] == sum(q["pods"] * n * per_cell for n in [q["nodes"]] + q["scenario_nodes"])
    assert roofline_binpack.rtcr_ops(dict(SHAPE, rtcr_resources=0)) == 0


PATTERN = load_json(os.path.join(HERE, "metrics", "scan_roofline.binpack.json"))["args"]["ops"]
QUESTION = {"nodes": 4624, "pods": 50000, "resident": 0, "scenario_nodes": [4600 + k for k in (0, 1, 2, 4, 8, 16, 32)]}


def run_with(ops, questions, shape=SHAPE, prom=None):
    items = [Item(start=0.0, end=1.0, ok=True) for _ in questions]
    return SimpleNamespace(
        trace={"device_ops": ops, "busy_s": sum(s for _n, s in ops), "window_s": 10.0},
        window=Window(opened=0.0, closed=1.0, items=items), questions=questions,
        config={"roofline_shape": shape}, device_kind="TPU v5 lite", prom=prom)


def test_the_share_of_a_synthetic_trace_never_passes_100():
    work = roofline_binpack.question_work(QUESTION, SHAPE)
    least = roofline.least_seconds(work, roofline.load_peaks("TPU v5 lite"))
    at_peak = scan_roofline_binpack.read(run_with([["jit_run_fast_scan", least["seconds"]]], [QUESTION]), PATTERN)
    assert at_peak == pytest.approx(100.0) and at_peak <= 100.0 + 1e-9
    for slower in (1.5, 10.0, 400.0):
        share = scan_roofline_binpack.read(
            run_with([["jit_run_fast_scan", slower * least["seconds"]]], [QUESTION]), PATTERN)
        assert share == pytest.approx(100.0 / slower) and 0 < share < 100


def test_nothing_to_read_is_none_not_zero():
    assert scan_roofline_binpack.read(run_with([], [QUESTION]), PATTERN) is None
    assert scan_roofline_binpack.read(run_with([["jit_dynamic_slice", 1.0]], [QUESTION]), PATTERN) is None
    k8s = run_with([["jit_run_fast_scan", 1.0]], [QUESTION], shape={"resources": 3, "selector_labels": 1, "spread_keys": 2})
    assert scan_roofline_binpack.read(k8s, PATTERN) is None


PROFILE_SCANS = load_json(os.path.join(HERE, "metrics", "profile_xla_scans.plan.json"))["args"]


def _prom(lines):
    return promtext.parse("\n".join(lines))


@pytest.mark.parametrize("before,after,want", [
    # the profile on the kernel in every pass: a real 0
    (['simon_engine_profile_total{engine="megakernel",profile="rtcr"} 4'],
     ['simon_engine_profile_total{engine="megakernel",profile="rtcr"} 8'], 0.0),
    # the profile fell off the kernel: each XLA scan under it counts, the default profile's do not
    (['simon_engine_profile_total{engine="xla",profile="rtcr"} 1'],
     ['simon_engine_profile_total{engine="xla",profile="rtcr"} 5',
      'simon_engine_profile_total{engine="xla",profile="default"} 3',
      'simon_engine_profile_total{engine="megakernel",profile="rtcr"} 2'], 4.0),
    # a program without the counter: nothing to read
    (['simon_engine_declined_total{engine="megakernel",reason="U"} 1'],
     ['simon_engine_declined_total{engine="megakernel",reason="U"} 2'], None),
])
def test_the_profiles_xla_scans_are_the_counters_difference(before, after, want):
    got = counter_delta.read(run_with([], [QUESTION], prom={"before": _prom(before), "after": _prom(after)}),
                             **PROFILE_SCANS)
    assert got == want
    assert counter_delta.read(run_with([], [QUESTION]), **PROFILE_SCANS) is None


# -- the files -----------------------------------------------------------------


def test_the_cell_and_its_metrics_are_in_the_benchmark_as_the_files_have_them():
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    parts = resolve(bench, "plan-binpack")
    entry = next(c for c in bench["configs"] if c["name"] == "k8s-5k-50k-binpack")
    assert parts["cell"] == {"name": "plan-binpack", "config": "k8s-5k-50k-binpack", "traffic": "short-binpack",
                             "chips": 1, "why": parts["cell"]["why"]}
    assert len(parts["cell"]["why"]) <= 200 and len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == CONFIG["reduced"] == ["pods"]
    base = load_json(os.path.join(HERE, "configs", "k8s-5k-50k.json"))
    assert CONFIG["sizes"] == base["sizes"] and CONFIG["tiny"] == base["tiny"]
    assert CONFIG["roofline_shape"] == dict(base["roofline_shape"], rtcr_resources=2, shape_points=2)
    assert CONFIG["reference"] == "kube_binpack_reference" and CONFIG["precision"] == "float32"
    assert parts["traffic"]["driver"] == "plan-loop-profile" and parts["traffic"]["traced_items"] == 1
    assert parts["traffic"]["limits"] == load_json(os.path.join(HERE, "traffic", "short.json"))["limits"]
    assert {m["name"] for m in parts["end_to_end"]} == {"plan_s", "setup_s"}
    mine = {m["name"] for m in bench["per_layer"] if m.get("workloads") == ["plan-binpack"]}
    assert mine == {"scan_roofline.binpack", "profile_xla_scans.plan"}
    reported = {m["name"] for m in parts["per_layer"]}
    assert {"mk_inputs_s.plan", "mk_launch_s.plan", "mk_wait_s.plan", "compile_path_s.plan", "report_s.plan",
            "load_program_s.plan", "load_parse_s.plan", "load_objects_s.plan", "report_nodes_s.plan",
            "report_apps_s.plan"} <= reported
    assert not {"xla_launch_s.plan", "xla_wait_s.plan", "scan_roofline.plan"} & reported
