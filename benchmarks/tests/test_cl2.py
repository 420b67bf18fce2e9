"""`plan-cl2` (the configuration `cl2-load-5k`), at sizes a test run can
hold: the generator's counts by the source's formulas; a sound run reads
correct, and a DaemonSet pod moved to another node, a pod of one namespace
counted in another's selector, and one pod moved, each read not correct; the
bfloat16 control of the comparison reads not correct and float32 put in the
same place reads nought; `roofline_cl2` counts a pinned pod as one node's row
and its share of a synthetic trace never passes 100 %; the files say the same
thing as `BENCHMARK.json`."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmarks import roofline, roofline_cl2
from benchmarks.control import control
from benchmarks.generators import cl2_load
from benchmarks.harness import ROOT, load_json, resolve
from benchmarks.readers import scan_roofline, scan_roofline_cl2
from benchmarks.tests.test_faults import break_answer, failing, move_one_pod, run
from benchmarks.window import Item, Window

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(HERE, "configs", "cl2-load-5k.json")) as f:
    CONFIG = json.load(f)
SHAPE = CONFIG["roofline_shape"]
#: every shape kept, the counts shrunk: three namespaces of 100 nodes
SHRUNK = {"nodes": 300}

# -- the generator -------------------------------------------------------------


@pytest.mark.parametrize("pods_per_node,groups,pods", [(10, (1, 8, 100), 990), (30, (3, 25, 300), 3000), (8, (0, 6, 80), 580)])
def test_a_namespace_by_the_sources_formulas(pods_per_node, groups, pods):
    sizes = dict(CONFIG["sizes"], pods_per_node=pods_per_node)
    made = cl2_load.groups_of_a_namespace(sizes)
    by_size = {s: [(k, n, r) for k, n, r in made if n.startswith(s)] for s in ("big", "medium", "small")}
    assert tuple(len(by_size[s]) for s in ("big", "medium", "small")) == groups
    assert {s: {r for _k, _n, r in by_size[s]} for s in by_size if by_size[s]} == {
        s: {size} for s, size in (("big", 250), ("medium", 30), ("small", 5)) if by_size[s]}
    assert sum(r for _k, _n, r in made) == pods
    kinds = {s: [k for k, _n, _r in by_size[s]] for s in by_size}
    # one small and one medium StatefulSet; one small, one medium and one big Job; the rest Deployments
    assert kinds["small"].count("StatefulSet") == kinds["medium"].count("StatefulSet") == 1
    assert kinds["small"].count("Job") == kinds["medium"].count("Job") == 1
    assert kinds["big"].count("Job") == min(1, groups[0]) and "StatefulSet" not in kinds["big"]
    assert [n for _k, n, _r in made if "deployment" in n][:2] == (
        ["big-deployment-0", "big-deployment-1"] if groups[0] > 1 else ["medium-deployment-0", "medium-deployment-1"])


@pytest.mark.parametrize("seed", [5, 3000000019])
def test_the_full_size_has_the_same_counts_on_every_seed(tmp_path, seed):
    inputs = cl2_load.generate(CONFIG["sizes"], seed, str(tmp_path))
    cluster = inputs["variants"]["fit"]["cluster"]
    made = CONFIG["sizes_make"]
    assert len(cluster.nodes) == 5000 and len(cluster.workloads) == made["workload_documents"] == 5451
    assert sum(w.replicas for w in cluster.workloads) == made["pods"] == 54500
    assert cluster.workloads[0].kind == "DaemonSet" and cluster.workloads[0].replicas == 5000
    root = os.path.dirname(inputs["variants"]["fit"]["simon_config"])
    apps = sorted(d for d in os.listdir(root) if d.startswith("app-"))
    assert len(apps) == 50 and sorted(os.listdir(os.path.join(root, "cluster"))) == ["daemonset.yaml", "nodes.yaml"]
    with open(os.path.join(root, "app-7", "workloads.yaml")) as f:
        docs = [json.loads(line) for line in f if line.startswith("{")]
    assert len(docs) == 109 and {d["metadata"]["namespace"] for d in docs} == {"test-load-7"}
    assert [d["kind"] for d in docs[:3]] == ["Job", "Deployment", "Deployment"]  # the source's order: big first
    assert docs[1]["spec"]["template"]["metadata"]["labels"] == {"group": "load", "name": "medium-deployment-0"}
    # within an app the program schedules Deployments, then StatefulSets, then Jobs
    kinds = [w.kind for w in cluster.workloads if w.namespace == "test-load-7"]
    assert kinds == ["Deployment"] * 104 + ["StatefulSet"] * 2 + ["Job"] * 3


# -- the comparison ------------------------------------------------------------


def test_a_sound_run_is_correct():
    line = run("plan-cl2")
    assert line["correct"] is True and not failing(line) and line["failed"] == 0


def a_daemonset_pod_on_another_node(result):
    """The first node's own pod of the DaemonSet goes to the second node."""
    src, dst = result.node_status[0], result.node_status[1]
    pod = next(p for p in src.pods if p.metadata.name.startswith("daemonset-0-"))
    src.pods.remove(pod)
    dst.pods.append(pod)


def test_a_daemonset_pod_moved_to_another_node(monkeypatch):
    break_answer(monkeypatch, a_daemonset_pod_on_another_node)
    line = run("plan-cl2")
    assert line["correct"] is False and {"infeasible_pods", "unscheduled_diff"} <= failing(line)


def test_one_pod_moved_where_the_answer_is_produced(monkeypatch):
    break_answer(monkeypatch, move_one_pod)
    line = run("plan-cl2")
    assert line["correct"] is False and failing(line) & {"worst_score_gap", "misplaced_pods"}


def test_a_pod_of_one_namespace_counted_in_anothers_selector(monkeypatch):
    """The program with the namespace taken out of its selectors: at the tiny
    size two namespaces of one app carry the same names and labels, so each
    counts the other's pods and spreads away from them."""
    from opensim_tpu.encoding import templates

    real = templates.selector_matches

    def any_namespace(canon, ns, labels):
        if canon is not None and canon[0] != "AND":
            canon = ((ns,),) + tuple(canon[1:])
        return real(canon, ns, labels)

    def every_template_against_every_selector(self):
        import numpy as np

        return np.array([[any_namespace(c, t.namespace, t.labels) for c in self.selectors] for t in self.templates],
                        dtype=bool).reshape(len(self.templates), len(self.selectors))

    monkeypatch.setattr(templates.TemplateSet, "match_matrix", every_template_against_every_selector)
    line = run("plan-cl2")
    assert line["correct"] is False and {"worst_score_gap", "misplaced_pods"} & failing(line)


@pytest.mark.parametrize("seed", [5, 2147483659, 3000000019])
def test_the_low_precision_control_is_not_correct(seed):
    got = control("plan-cl2", seed, SHRUNK)
    assert got["control"] == "bfloat16" and got["control_correct"] is False
    assert {"worst_score_gap", "misplaced_pods"} <= {c["name"] for c in got["checks"] if c["value"] > c["limit"]}
    # the control's filters are exact: every DaemonSet pod is on its own node
    assert {c["name"]: c["value"] for c in got["checks"]}["infeasible_pods"] == 0


def test_the_reference_in_its_own_precision_put_in_the_programs_place_is_correct():
    got = control("plan-cl2", 5, SHRUNK, precision="float32")
    assert got["control_correct"] is True and all(c["value"] == 0 for c in got["checks"])


# -- the count of the work -----------------------------------------------------


@pytest.mark.parametrize("nodes,pods,pinned", [(5000, 54500, 5000), (24, 240, 24), (5000, 3000, 3000), (1, 1, 1)])
def test_a_pinned_pod_is_one_nodes_row(nodes, pods, pinned):
    assert SHAPE["pinned_pods"] == 5000  # one DaemonSet on 5,000 nodes
    assert roofline_cl2.pinned_pods(nodes, pods, SHAPE) == pinned
    cols = roofline.columns(SHAPE)
    plain = roofline.question_work(nodes, pods, 0, SHAPE)
    cl2 = roofline_cl2.question_work(nodes, pods, 0, SHAPE)
    free = roofline.question_work(nodes, pods - pinned, 0, SHAPE)
    assert cl2["bytes"] - free["bytes"] == 4 * pinned * cols
    assert cl2["ops"] - free["ops"] == pinned * (cols * roofline.OPS_PER_COLUMN + roofline.OPS_SELECT)
    assert cl2["bytes"] <= plain["bytes"] and cl2["ops"] <= plain["ops"]
    assert roofline_cl2.question_work(nodes, pods, 0, dict(SHAPE, pinned_pods=0)) == plain


PATTERN = load_json(os.path.join(HERE, "metrics", "scan_roofline.cl2.json"))["args"]["ops"]
QUESTION = {"nodes": 5000, "pods": 54500, "resident": 0}


def run_with(ops, questions, shape=SHAPE):
    items = [Item(start=0.0, end=1.0, ok=True) for _ in questions]
    return SimpleNamespace(
        trace={"device_ops": ops, "busy_s": sum(s for _n, s in ops), "window_s": 10.0},
        window=Window(opened=0.0, closed=1.0, items=items), questions=questions,
        config={"roofline_shape": shape}, device_kind="TPU v5 lite")


def test_the_share_of_a_synthetic_trace_never_passes_100():
    work = roofline_cl2.question_work(5000, 54500, 0, SHAPE)
    least = roofline.least_seconds(work, roofline.load_peaks("TPU v5 lite"))
    assert least["bound"] == "bytes"
    at_peak = scan_roofline_cl2.read(run_with([["jit__schedule_pods_jit", least["seconds"]]], [QUESTION]), PATTERN)
    assert at_peak == pytest.approx(100.0) and at_peak <= 100.0 + 1e-9
    for slower in (1.5, 10.0, 400.0):
        share = scan_roofline_cl2.read(
            run_with([["jit__schedule_pods_jit", slower * least["seconds"]]], [QUESTION]), PATTERN)
        assert share == pytest.approx(100.0 / slower) and 0 < share < 100
    # the same device time, whichever engine the trace names, and below the plain share:
    # the pinned pods' passes over the table are not the question's
    xla = scan_roofline_cl2.read(run_with([["jit__schedule_pods_jit", 6.0]], [QUESTION]), PATTERN)
    mega = scan_roofline_cl2.read(run_with([["jit_run_fast_scan", 6.0]], [QUESTION]), PATTERN)
    plain = scan_roofline.read(run_with([["jit__schedule_pods_jit", 6.0]], [QUESTION]), PATTERN)
    assert xla == mega and 0 < xla < plain < 100
    assert xla / plain == pytest.approx(49500 / 54500, rel=1e-3)


def test_nothing_to_read_is_none_not_zero():
    assert scan_roofline_cl2.read(run_with([], [QUESTION]), PATTERN) is None
    assert scan_roofline_cl2.read(run_with([["jit_dynamic_slice", 1.0]], [QUESTION]), PATTERN) is None
    unpinned = run_with([["jit__schedule_pods_jit", 1.0]], [QUESTION], shape=dict(SHAPE, pinned_pods=0))
    assert scan_roofline_cl2.read(unpinned, PATTERN) is None
    k8s = run_with([["jit__schedule_pods_jit", 1.0]], [QUESTION],
                   shape={"resources": 3, "selector_labels": 1, "spread_keys": 2})
    assert scan_roofline_cl2.read(k8s, PATTERN) is None
    no_trace = run_with([["jit__schedule_pods_jit", 1.0]], [QUESTION])
    no_trace.trace = None
    assert scan_roofline_cl2.read(no_trace, PATTERN) is None


# -- the files -----------------------------------------------------------------


def test_the_cell_and_its_metrics_are_in_the_benchmark_as_the_files_have_them():
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    parts = resolve(bench, "plan-cl2")
    entry = next(c for c in bench["configs"] if c["name"] == "cl2-load-5k")
    assert parts["cell"] == {"name": "plan-cl2", "config": "cl2-load-5k", "traffic": "fit-cl2", "chips": 1,
                             "why": parts["cell"]["why"]}
    assert len(parts["cell"]["why"]) <= 200 and len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == CONFIG["reduced"] == ["pods"]
    assert CONFIG["reference"] == "kube_daemonset_reference" and CONFIG["precision"] == "float32"
    for key in ("source_sizes", "sizes", "tiny", "assumed", "reduced_why", "guarantees", "roofline_shape"):
        assert CONFIG[key], key
    assert parts["traffic"]["driver"] == "plan-loop-kinds" and parts["traffic"]["traced_items"] == 1
    assert parts["traffic"]["limits"] == load_json(os.path.join(HERE, "traffic", "fit-interpod.json"))["limits"]
    assert {m["name"] for m in parts["end_to_end"]} == {"plan_s", "setup_s"}
    mine = {m["name"] for m in bench["per_layer"] if m.get("workloads") == ["plan-cl2"]}
    assert mine == {"expand_daemonset_s.plan", "encode_match_s.plan", "scan_roofline.cl2"}
    reported = {m["name"] for m in parts["per_layer"]}
    assert mine | {"xla_launch_s.plan", "xla_wait_s.plan", "report_s.plan", "compile_path_s.plan"} <= reported
    assert not {"mk_inputs_s.plan", "mk_launch_s.plan", "mk_wait_s.plan", "scan_roofline.plan"} & reported
