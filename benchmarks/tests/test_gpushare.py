"""`plan-gpushare` (the configuration `openb-gpushare-1523`), at sizes a test
run can hold: the generator holds its counts and meets the source's totals
on every seed; the bfloat16 control of the comparison reads not correct and
float32 put in the same place reads nought; a pod moved where the answer is
produced, a device charged for another's pod and one node too many each read
`correct` false; `roofline_gpushare` against a count made by hand on four
nodes, and its share of a synthetic trace never passes 100 %."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmarks import roofline, roofline_gpushare
from benchmarks.control import control
from benchmarks.generators import openb_gpushare as gen
from benchmarks.readers import scan_roofline_gpushare
from benchmarks.tests.test_faults import break_answer, failing, move_one_pod, one_node_too_many, run
from benchmarks.window import Item, Window

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(HERE, "configs", "openb-gpushare-1523.json")) as f:
    CONFIG = json.load(f)
SHAPE = CONFIG["roofline_shape"]
MI = 1 << 20


def shrunk(counts, load_pct):
    """Every class and list of the configuration kept, the node counts shrunk."""
    classes = [dict(c, count=k) for c, k in zip(CONFIG["sizes"]["node_classes"], counts)]
    return {"node_classes": classes, "load_pct": load_pct, "max_new_nodes": 32}


#: 150 nodes with 612 GPUs and some 850 tasks, short of GPUs by a few nodes
SHRUNK = shrunk([20, 10, 21, 20, 20, 47, 12], 100)


# -- the generator -----------------------------------------------------------


@pytest.mark.parametrize("seed", [5, 2147483659, 3000000019])
def test_the_generator_holds_its_counts_and_meets_the_sources_totals(tmp_path, seed):
    src, sizes = CONFIG["source_sizes"], CONFIG["sizes"]
    got = gen.generate(sizes, seed, str(tmp_path))
    cluster = got["variants"]["short"]["cluster"]
    assert len(cluster.nodes) == src["nodes"] and sum(1 for n in cluster.nodes if n.gpus) == src["gpu_nodes"]
    assert sum(n.gpus for n in cluster.nodes) == src["gpus"] and max(n.gpus for n in cluster.nodes) == 8
    assert sum(1 for n in cluster.nodes if not n.gpus) == src["cpu_only_nodes"]
    assert all(n.gpu_mem == 1000 * MI for n in cluster.nodes if n.gpus)
    # allocatable lies below capacity by the seed's reservation, never above it
    by_class = {(c["gpus"], c["cpu"]): c for c in sizes["node_classes"]}
    assert all(0 <= by_class[(n.gpus, -(-n.cpu_m // 1000 // 32) * 32)]["cpu"] * 1000 - n.cpu_m <= 2000 for n in cluster.nodes)
    # the tasks: the counts of every class and fraction are the rule's, whatever the seed
    counts = gen.task_counts(sizes)
    assert got["counts"] == counts and len(cluster.workloads) == counts["tasks"] == 8602
    tasks = cluster.workloads
    assert sum(1 for w in tasks if not w.gpu_count) == counts["by_class"]["none"]
    assert sum(1 for w in tasks if w.gpu_count == 1 and w.gpu_mem < 1000 * MI) == counts["by_class"]["fraction"]
    for gpus, name in ((1, "one"), (2, "two"), (4, "four"), (8, "eight")):
        assert sum(1 for w in tasks if w.gpu_count == gpus and w.gpu_mem == 1000 * MI) == counts["by_class"][name]
    for milli, k in counts["by_milli"]:
        assert sum(1 for w in tasks if w.gpu_mem == milli * MI) == k
    share = {c: 100.0 * k / counts["tasks"] for c, k in counts["by_class"].items()}
    assert all(abs(share[c] - pct) < 0.05 for c, pct in src["gpu_request_share_pct"].items())
    # between 513 and 2,048 request shapes, which is what a template is
    shapes = {(w.cpu_m, w.mem_bytes, w.gpu_mem, w.gpu_count) for w in tasks}
    assert got["shapes"] == len(shapes) == 866 and 513 <= len(shapes) <= 2048  # the same on every seed
    assert sorted((w.cpu_m, w.mem_bytes, w.gpu_mem, w.gpu_count) for w in tasks) == sorted(
        (c, m * MI, g * MI if k else 0, k) for c, m, g, k in gen.task_list(sizes))
    # the files say what the plain data says
    with open(os.path.join(str(tmp_path), "plan", "tasks", "pods.yaml")) as f:
        docs = [json.loads(line) for line in f if line.startswith("{")]
    assert [d["metadata"]["name"] for d in docs] == [w.name.split("/", 1)[1] for w in tasks]
    assert all("labels" not in d["metadata"] and "ownerReferences" not in d["metadata"] for d in docs)
    first_gpu = next(d for d in docs if "annotations" in d["metadata"])
    w = tasks[docs.index(first_gpu)]
    assert first_gpu["metadata"]["annotations"] == {gen.GPU_MEM: f"{w.gpu_mem // MI}Mi", gen.GPU_COUNT: str(w.gpu_count)}


def test_two_seeds_differ_in_order_and_nodes_and_not_in_what_arrives(tmp_path):
    a = gen.generate(SHRUNK | small_rest(), 5, str(tmp_path / "a"))["variants"]["short"]["cluster"]
    b = gen.generate(SHRUNK | small_rest(), 6, str(tmp_path / "b"))["variants"]["short"]["cluster"]
    assert [w.gpu_mem for w in a.workloads] != [w.gpu_mem for w in b.workloads]
    assert sorted(w.gpu_mem for w in a.workloads) == sorted(w.gpu_mem for w in b.workloads)
    assert [n.name for n in a.nodes] != [n.name for n in b.nodes]


def small_rest():
    return {k: v for k, v in CONFIG["sizes"].items() if k not in SHRUNK}


# -- the control and the faults ----------------------------------------------


@pytest.mark.parametrize("seed", [5, 2147483659, 3000000019])
def test_the_low_precision_control_is_not_correct(seed):
    got = control("plan-gpushare", seed, SHRUNK)
    assert got["control"] == "bfloat16" and got["control_correct"] is False
    failing_checks = {c["name"] for c in got["checks"] if c["value"] > c["limit"]}
    assert {"worst_score_gap", "misplaced_pods"} <= failing_checks
    # the control's filters and device arithmetic are exact
    values = {c["name"]: c["value"] for c in got["checks"]}
    assert values["infeasible_pods"] == 0 and values["gpu_device_diff"] == 0


def test_the_reference_in_its_own_precision_put_in_the_programs_place_is_correct():
    got = control("plan-gpushare", 5, SHRUNK, precision="float32")
    assert got["control_correct"] is True
    assert all(c["value"] == 0 for c in got["checks"])


def test_a_sound_run_is_correct():
    line = run("plan-gpushare")
    assert line["correct"] is True and not failing(line) and line["failed"] == 0


def test_one_pod_moved_where_the_answer_is_produced(monkeypatch):
    break_answer(monkeypatch, move_one_pod)
    line = run("plan-gpushare")
    assert line["correct"] is False
    assert failing(line) & {"worst_score_gap", "infeasible_pods", "misplaced_pods", "gpu_device_diff"}


def charge_another_device(result):
    """A node's first two devices swap what they hold, in the annotation the
    report's GPU table is written from: the pods stay where they are."""
    from opensim_tpu.models.objects import ANNO_NODE_GPU_SHARE

    for status in result.node_status:
        anno = status.node.metadata.annotations.get(ANNO_NODE_GPU_SHARE)
        if not anno:
            continue
        info = json.loads(anno)
        devs = info["DevsBrief"]
        if len(devs) >= 2 and devs["0"]["GpuUsedMemory"] != devs["1"]["GpuUsedMemory"]:
            devs["0"], devs["1"] = devs["1"], devs["0"]
            status.node.metadata.annotations[ANNO_NODE_GPU_SHARE] = json.dumps(info)
            return
    raise AssertionError("no node whose first two devices differ")


def test_a_pod_on_another_device_than_the_tightest(monkeypatch):
    break_answer(monkeypatch, charge_another_device)
    line = run("plan-gpushare")
    assert line["correct"] is False and failing(line) == {"gpu_device_diff"}
    # two devices in every plan of the window: each plan's table is compared
    assert {c["name"]: c["value"] for c in line["checks"]}["gpu_device_diff"] == 2 * line["attempted"]


def test_the_count_of_added_nodes_off_by_one(monkeypatch):
    one_node_too_many(monkeypatch)
    line = run("plan-gpushare")
    assert line["correct"] is False and "added_nodes_diff" in failing(line)


# -- the count of the work ---------------------------------------------------


def test_the_sweeps_counts_are_the_planners_ladder_and_the_open_bracket():
    ladder = [0, 1, 2, 4, 8, 16, 32, 64, 128]
    assert roofline_gpushare.sweep_counts(0, 128) == []
    assert roofline_gpushare.sweep_counts(24, 128) == ladder + list(range(17, 32))
    assert roofline_gpushare.sweep_counts(17, 128) == roofline_gpushare.sweep_counts(32, 128) == ladder + list(range(17, 32))
    assert len(roofline_gpushare.sweep_counts(20, 128)) == 9 + 15
    assert roofline_gpushare.sweep_counts(2, 128) == ladder and roofline_gpushare.sweep_counts(3, 128) == ladder + [3]
    assert roofline_gpushare.sweep_counts(3, 16) == [0, 1, 2, 4, 8, 16, 3]


def test_the_work_of_a_plan_on_four_nodes_counted_by_hand():
    """Four nodes, three pods, one node added out of at most two: the ladder
    is {0, 1, 2} and the bracket (0, 1) is closed, so the passes are the
    stream on 5 nodes and scenarios on 4, 5 and 6. A node is 2 x 5 resource
    columns and 8 device cells; a pass reads every cell for every pod, reads
    the state once, and writes 8 cells on the node chosen for each pod."""
    q = {"nodes": 5, "pods": 3, "resident": 0, "scenario_nodes": [4 + k for k in roofline_gpushare.sweep_counts(1, 2)]}
    assert q["scenario_nodes"] == [4, 5, 6]
    work = roofline_gpushare.question_work(q, SHAPE)
    cells, ops, bytes_ = 2 * 5 + 8, 0, 0
    for nodes in (5, 4, 5, 6):
        ops += 3 * nodes * (10 * 4 + 2) + 3 * nodes * 8 * 4
        bytes_ += 4 * (3 * nodes * cells + nodes * cells + 3 * 8)
    assert work == {"ops": float(ops), "bytes": float(bytes_)}
    assert ops == 3 * 20 * (42 + 32) and bytes_ == 4 * (60 * 18 + 20 * 18 + 4 * 24)
    # without device cells it is roofline.py's own count, pass by pass
    plain = roofline_gpushare.question_work(q, dict(SHAPE, gpu_devices=0))
    assert plain["ops"] == sum(roofline.question_work(n, 3, 0, SHAPE)["ops"] for n in (5, 4, 5, 6))


PATTERN = "^jit_(wrapped|_schedule_pods|.*sweep|.*scan)"
QUESTION = {"nodes": 1523 + 24, "pods": 8602, "resident": 0,
            "scenario_nodes": [1523 + k for k in roofline_gpushare.sweep_counts(24, 128)]}


def run_with(ops, questions, shape=SHAPE):
    items = [Item(start=0.0, end=1.0, ok=True) for _ in questions]
    return SimpleNamespace(
        trace={"device_ops": ops, "busy_s": sum(s for _n, s in ops), "window_s": 10.0},
        window=Window(opened=0.0, closed=1.0, items=items), questions=questions,
        config={"roofline_shape": shape}, device_kind="TPU v5 lite")


def test_the_share_of_a_synthetic_trace_never_passes_100():
    least = roofline.least_seconds(roofline_gpushare.question_work(QUESTION, SHAPE), roofline.load_peaks("TPU v5 lite"))
    assert least["bound"] == "bytes"
    at_peak = scan_roofline_gpushare.read(run_with([["jit_wrapped", least["seconds"]]], [QUESTION]), PATTERN)
    assert at_peak == pytest.approx(100.0) and at_peak <= 100.0 + 1e-9
    for slower in (1.5, 10.0, 400.0):
        share = scan_roofline_gpushare.read(run_with([["jit_wrapped", slower * least["seconds"]]], [QUESTION]), PATTERN)
        assert share == pytest.approx(100.0 / slower) and 0 < share < 100
    # kernel and XLA scan times add up, whichever the trace names
    both = scan_roofline_gpushare.read(run_with([["jit_wrapped", 0.5], ["jit__schedule_pods_jit", 1.5]], [QUESTION]), PATTERN)
    one = scan_roofline_gpushare.read(run_with([["jit_wrapped", 2.0]], [QUESTION]), PATTERN)
    assert both == one and 0 < one < 100


def test_nothing_to_read_is_none_not_zero():
    assert scan_roofline_gpushare.read(run_with([], [QUESTION]), PATTERN) is None
    assert scan_roofline_gpushare.read(run_with([["jit_dynamic_slice", 1.0]], [QUESTION]), PATTERN) is None
    k8s = run_with([["jit_wrapped", 1.0]], [QUESTION], shape={"resources": 3, "selector_labels": 1, "spread_keys": 2})
    assert scan_roofline_gpushare.read(k8s, PATTERN) is None
    no_trace = run_with([["jit_wrapped", 1.0]], [QUESTION])
    no_trace.trace = None
    assert scan_roofline_gpushare.read(no_trace, PATTERN) is None
    assert scan_roofline_gpushare.read(run_with([["jit_wrapped", 1.0]], []), PATTERN) is None
