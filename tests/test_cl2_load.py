"""`cl2-load-5k` (ISSUE 35): SIG-scalability's load-test cluster at its `tiny`
size, through `Applier.run()` by each engine the CPU has, replayed pod for pod
through the plain reference of
`benchmarks/reference/kube_daemonset_reference.py`; the DaemonSet's pins; the
namespaces that repeat every name and label; which kinds carry the
system-default spread; and the spans, attributes and counter the path reports."""

import importlib
import json
import os

import numpy as np
import pytest

from benchmarks.drivers import Context
from benchmarks.generators.k8s_cluster import write_docs
from benchmarks.reference import kube_daemonset_reference as R
from benchmarks.reference.kube_reference import HOSTNAME, ZONE, Cluster, NodeSpec
from benchmarks.window import Window
from opensim_tpu.obs import trace as tracing
from opensim_tpu.obs.metrics import RECORDER

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "benchmarks", "configs", "cl2-load-5k.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(REPO, "benchmarks", "traffic", "fit-cl2.json")) as f:
    TRAFFIC = json.load(f)
TINY = CONFIG["tiny"]
#: five namespaces of 16 selectors: one more than the megakernel's 64 columns
FIVE = dict(TINY, nodes=30)
DAEMONSET = "kube-system/daemonset-0"
#: how a test asks for an engine on the CPU, and what the report then names
ENGINES = {
    "xla": ({"OPENSIM_DISABLE_NATIVE": "1"}, "xla"),
    "megakernel": ({"OPENSIM_FASTPATH": "interpret"}, "megakernel"),
    "native": ({}, "native"),
}
NOTHING_DIFFERS = {
    "misplaced_pods": 0, "worst_score_gap": 0.0, "infeasible_pods": 0, "unscheduled_diff": 0,
    "answer_diff": 0, "added_nodes_diff": 0, "plans_differing": 0, "plans_unanswered": 0,
}


def drive(tmp_path, sizes, seed):
    ctx = Context(config=CONFIG, traffic=TRAFFIC, seed=seed, scratch=str(tmp_path), rehearse=True, sizes=sizes)
    driver = importlib.import_module("benchmarks.drivers.plan_loop_kinds").Driver(ctx)
    driver.prepare()
    return driver


def plan(driver):
    window = Window(opened=0.0, closed=1.0, items=[driver.one(0, False)])
    driver.after_window(window)
    return window


def loaded(driver):
    from opensim_tpu.planner.apply import Applier, Options

    applier = Applier(Options(simon_config=driver.simon_config))
    return applier.load_cluster(), applier.load_apps()


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [5, 3000000023])
@pytest.mark.parametrize("engine", list(ENGINES))
def test_the_plan_replays_through_the_reference_pod_for_pod(tmp_path, monkeypatch, engine, seed):
    env, named = ENGINES[engine]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    driver = drive(tmp_path, TINY, seed)
    window = plan(driver)
    report = window.items[0].info["report"]
    assert report["success"] and report["engine"].startswith(named), report["engine"]
    assert {c["name"]: c["value"] for c in driver.compare(window)} == NOTHING_DIFFERS
    cluster = driver.inputs["variants"]["fit"]["cluster"]
    placed = report["placed"]  # workload -> the node of each pod, in the order they were scheduled
    assert {w: len(seq) for w, seq in placed.items()} == {w.name: w.replicas for w in cluster.workloads}
    # one pod of the DaemonSet a node, each on its own node, scheduled in the order of the node list
    assert placed[DAEMONSET] == [nd.name for nd in cluster.nodes]
    # and the reference left to itself gives the program's answer
    ref = R.Reference(cluster)
    assert ref.free_run()[1] == {} and ref.order() == placed


def test_the_tiny_size_has_every_kind_and_namespaces_that_repeat_names_and_labels(tmp_path):
    cluster = drive(tmp_path, TINY, 5).inputs["variants"]["fit"]["cluster"]
    kinds = {w.kind for w in cluster.workloads}
    assert kinds == {"DaemonSet", "Deployment", "StatefulSet", "Job"}
    assert sum(w.kind == "DaemonSet" for w in cluster.workloads) == 1
    by_namespace = {}
    for w in cluster.workloads[1:]:
        by_namespace.setdefault(w.namespace, []).append((w.name.split("/")[1], w.kind, tuple(sorted(w.labels.items()))))
    assert len(by_namespace) == 4 >= 3
    assert len({tuple(sorted(v)) for v in by_namespace.values()}) == 1  # the same names and labels in each


def test_the_seed_moves_the_nodes_and_nothing_else(tmp_path):
    a = drive(tmp_path / "a", TINY, 5).inputs["variants"]["fit"]["cluster"]
    b = drive(tmp_path / "b", TINY, 6).inputs["variants"]["fit"]["cluster"]
    assert [(w.name, w.kind, w.replicas, w.cpu_m, w.mem_bytes) for w in a.workloads] == [
        (w.name, w.kind, w.replicas, w.cpu_m, w.mem_bytes) for w in b.workloads]
    assert [n.name for n in a.nodes] != [n.name for n in b.nodes]
    assert sorted(n.name for n in a.nodes) == sorted(n.name for n in b.nodes)
    assert {(n.cpu_m, n.mem_bytes) for n in a.nodes} != {(1000, 3840 << 20)}  # allocatable below capacity


# ---------------------------------------------------------------------------
# a DaemonSet pod belongs to one node
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", list(ENGINES))
def test_a_daemonset_pod_whose_node_is_full_is_unschedulable_and_goes_nowhere_else(tmp_path, monkeypatch, engine):
    from opensim_tpu.engine.simulator import pinned_node_name, simulate

    for k, v in ENGINES[engine][0].items():
        monkeypatch.setenv(k, v)
    driver = drive(tmp_path, TINY, 5)
    cluster = driver.inputs["variants"]["fit"]["cluster"]
    # the third node has 8m of CPU left: less than the DaemonSet's 10m, enough for one pod of 5m
    full = cluster.nodes[2]
    full.cpu_m = 8
    path = os.path.join(os.path.dirname(driver.simon_config), "cluster", "nodes.yaml")
    with open(path) as f:
        docs = [json.loads(line) for line in f if line.startswith("{")]
    docs[2]["status"]["allocatable"]["cpu"] = "8m"
    assert docs[2]["metadata"]["name"] == full.name
    write_docs(path, docs)

    # (a kernel pass with a failure in mid-stream is discarded and the next rung answers)
    result = simulate(*loaded(driver))
    (left,) = result.unscheduled_pods
    assert left.pod.metadata.name.startswith("daemonset-0-") and pinned_node_name(left.pod) == full.name
    for status in result.node_status:
        daemons = [p for p in status.pods if p.metadata.name.startswith("daemonset-0-")]
        want = [] if status.node.metadata.name == full.name else [status.node.metadata.name]
        assert [pinned_node_name(p) for p in daemons] == want
    # the reference: that pod alone is unschedulable, and the others' failures do not follow from it
    ref = R.Reference(cluster)
    _placed, unscheduled = ref.free_run()
    assert unscheduled == {0: 1}
    assert ref.order()[DAEMONSET] == [nd.name for nd in cluster.nodes if nd.name != full.name]
    # replayed, the program's answer is the reference's: nothing differs, the pod is missing on both sides
    assert R.replay(cluster, ref.order(), {}) == {k: v for k, v in NOTHING_DIFFERS.items() if k in (
        "misplaced_pods", "worst_score_gap", "infeasible_pods", "unscheduled_diff", "answer_diff")}
    assert sum(len(s.pods) for s in result.node_status) == sum(w.replicas for w in cluster.workloads) - 1


def nodes(n, cpu_m=1000):
    return [NodeSpec(name=f"n{i}", cpu_m=cpu_m - 10 * i, mem_bytes=4 << 30, pods=110,
                     labels={HOSTNAME: f"n{i}", ZONE: "z"}) for i in range(n)]


def workload(name, kind, replicas, namespace="ns", cpu_m=5):
    return R.KindWorkload(name=f"{namespace}/{name}", replicas=replicas, cpu_m=cpu_m, mem_bytes=20_000_000,
                          labels={"group": "load", "name": name}, namespace=namespace, kind=kind)


def test_replay_catches_a_daemonset_pod_on_another_node():
    cluster = Cluster(nodes=nodes(4), bound=[], workloads=[workload("daemonset-0", "DaemonSet", 4)], new_node=None)
    right = {"ns/daemonset-0": ["n0", "n1", "n2", "n3"]}
    assert all(v == 0 for v in R.replay(cluster, right, {}).values())
    moved = R.replay(cluster, {"ns/daemonset-0": ["n0", "n1", "n3", "n3"]}, {})
    assert moved["infeasible_pods"] == 1 and moved["unscheduled_diff"] == 1
    missing = R.replay(cluster, {"ns/daemonset-0": ["n0", "n1", "n3"]}, {})
    assert missing["unscheduled_diff"] == 1 and missing["infeasible_pods"] == 0


# ---------------------------------------------------------------------------
# namespaces, and which kinds spread by default
# ---------------------------------------------------------------------------


def counts_seen_by(ref, wi):
    """Matching pods per (hostname domains, zone domains) as workload `wi` meets them."""
    return [c["counts"].sum() for c in ref._enter(wi)["spread"]]


def test_two_namespaces_with_the_same_names_and_labels_do_not_count_each_others_pods():
    """In the reference, worked by hand: a Deployment meets the pods of its
    own namespace's namesake, not those of the next namespace's."""
    twice = [workload("small-deployment-0", "Deployment", 2, "test-1"),
             workload("small-deployment-0", "Deployment", 3, "test-2")]
    ref = R.Reference(Cluster(nodes=nodes(3), bound=[], workloads=twice, new_node=None))
    ref.free_run()
    assert counts_seen_by(ref, 0) == [2.0, 2.0] and counts_seen_by(ref, 1) == [3.0, 3.0]
    # the same two in one namespace count each other's: five pods under each key
    for w in twice:
        w.namespace = "test-1"
    ref = R.Reference(Cluster(nodes=nodes(3), bound=[], workloads=twice, new_node=None))
    ref.free_run()
    assert counts_seen_by(ref, 0) == counts_seen_by(ref, 1) == [5.0, 5.0]


def test_the_programs_selectors_match_within_their_namespace(tmp_path):
    """In the program: at the tiny size two namespaces of one app carry the
    same label sets, `simon/app-name` included, and every template matches
    the selectors of its own namespace alone."""
    from opensim_tpu.engine.simulator import prepare

    driver = drive(tmp_path, TINY, 5)
    prep = prepare(*loaded(driver))
    ts = prep.encoder.ts
    matches = ts.match_matrix()
    label_sets = {}
    for u, t in enumerate(ts.templates):
        if t.namespace != "kube-system":
            label_sets.setdefault(tuple(sorted(t.labels.items())), set()).add(t.namespace)
        for a in np.nonzero(matches[u])[0]:
            assert ts.selectors[a][0] == (t.namespace,)
    shared = [namespaces for namespaces in label_sets.values() if len(namespaces) > 1]
    assert shared and all(len(namespaces) == 2 for namespaces in shared)
    assert matches.sum() == 64  # each Deployment's and StatefulSet's template matches its own selector


def test_a_jobs_and_a_daemonsets_pods_carry_no_default_spread(tmp_path):
    """kube's `DefaultSelector` finds a selector for the pods of a ReplicaSet
    or a StatefulSet; the program follows it (before ISSUE 35 every owned pod
    with labels spread by its own labels, and 12 of the 240 pods of this
    stream went elsewhere than kube-scheduler puts them)."""
    from opensim_tpu.engine.simulator import prepare

    driver = drive(tmp_path, TINY, 5)
    prep = prepare(*loaded(driver))
    spread_by_kind = {}
    for pod, u in zip(prep.ordered, prep.tmpl_ids):
        kind = pod.metadata.annotations["simon/workload-kind"]
        spread_by_kind.setdefault(kind, set()).add(len(prep.encoder.ts.templates[u].spread))
    assert spread_by_kind == {"DaemonSet": {0}, "Job": {0}, "ReplicaSet": {2}, "StatefulSet": {2}}
    cluster = driver.inputs["variants"]["fit"]["cluster"]
    assert {w.kind: w.spread for w in cluster.workloads} == {
        "DaemonSet": [], "Job": [], "Deployment": None, "StatefulSet": None}


# ---------------------------------------------------------------------------
# what the path reports: expand.daemonsets, encode.match, the count reads, declined, the counter
# ---------------------------------------------------------------------------


def traced_plan(driver):
    from opensim_tpu.planner.apply import Applier, Options

    opts = Options(simon_config=driver.simon_config, output_file=os.path.join(driver.ctx.scratch, "report.txt"),
                   report_pods=True, max_new_nodes=driver.inputs["max_new_nodes"])
    tr = tracing.start_trace("apply", force=True)
    with tracing.trace_scope(tr):
        assert Applier(opts).run() == 0
    tr.finish()
    return tr


def find(tr, name):
    return [sp for sp in tr.walk() if sp.name == name]


def test_the_spans_of_the_expansion_and_of_the_match_matrix(tmp_path, monkeypatch):
    monkeypatch.setenv("OPENSIM_DISABLE_NATIVE", "1")
    tr = traced_plan(drive(tmp_path, TINY, 7))
    (expand,) = find(tr, "prep.expand")
    (daemons,) = find(tr, "expand.daemonsets")  # the cluster's; the apps have none
    assert daemons in expand.children
    assert daemons.attrs == {"daemonsets": 1, "nodes": 24, "pods": 24}
    (encode,) = find(tr, "encode")
    (match,) = find(tr, "encode.match")
    assert match in encode.children and encode.start <= match.start and match.end <= encode.end
    # 24 pinned pods and 76 workloads are 100 templates; 64 of them have a selector, and meet no other
    assert match.attrs == {"templates": 100, "selectors": 64, "evaluated": 64}
    # the XLA scan reads the columns of a pod's two default spread constraints (hostname, zone) of a
    # count table of 24 hostname domains, one zone and the trash row by 64 selectors
    (rung,) = find(tr, "engine.xla")
    assert (rung.attrs["count_columns"], rung.attrs["count_table_bytes"]) == (2, (24 + 1 + 1) * 64 * 4)
    # hostname's counts are read as a slice, the one zone's by compare-select, nothing by gather
    reads = tuple(rung.attrs[f"count_keys_{path}"] for path in ("sliced", "selected", "gathered"))
    assert reads == (1, 1, 0)


def test_the_count_read_paths_are_counted_scan_by_scan(tmp_path, monkeypatch):
    monkeypatch.setenv("OPENSIM_DISABLE_NATIVE", "1")
    RECORDER.reset()
    traced_plan(drive(tmp_path, TINY, 7))
    lines = RECORDER.render_lines()
    assert 'simon_count_read_keys_total{path="slice"} 1' in lines
    assert 'simon_count_read_keys_total{path="select"} 1' in lines
    assert not [line for line in lines if line.startswith('simon_count_read_keys_total{path="gather"}')]
    RECORDER.reset()


def test_a_run_the_kernel_declines_says_so_on_the_rung_that_runs(tmp_path, monkeypatch):
    monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")
    monkeypatch.setenv("OPENSIM_DISABLE_NATIVE", "1")
    RECORDER.reset()
    driver = drive(tmp_path, FIVE, 7)
    tr = traced_plan(driver)
    assert not find(tr, "engine.megakernel")
    (rung,) = find(tr, "engine.xla")
    assert rung.attrs["declined"] == "megakernel:A"
    assert (rung.attrs["templates"], rung.attrs["selectors"], rung.attrs["pinned_pods"]) == (30 + 5 * 19, 80, 30)
    assert 'simon_engine_declined_total{engine="megakernel",reason="A"} 1' in RECORDER.render_lines()
    RECORDER.reset()
    # the answer is still the reference's
    window = plan(driver)
    assert {c["name"]: c["value"] for c in driver.compare(window)} == NOTHING_DIFFERS


def test_a_run_the_kernel_takes_carries_no_such_attribute(tmp_path, monkeypatch):
    monkeypatch.setenv("OPENSIM_DISABLE_NATIVE", "1")  # the kernel is off by policy on the CPU: nothing turned away
    RECORDER.reset()
    tr = traced_plan(drive(tmp_path, TINY, 7))
    (rung,) = find(tr, "engine.xla")
    assert "declined" not in rung.attrs and "pinned_pods" not in rung.attrs
    assert not any(line.startswith("simon_engine_declined_total{") for line in RECORDER.render_lines())


def test_the_full_size_is_the_sources_cluster_at_ten_pods_a_node():
    from benchmarks.generators.cl2_load import groups_of_a_namespace

    src, sizes, made = CONFIG["source_sizes"], CONFIG["sizes"], CONFIG["sizes_make"]
    assert CONFIG["reduced"] == ["pods"] and set(CONFIG["reduced_why"]) == {"pods"}
    for key in ("nodes", "nodes_per_namespace", "big_group_size", "medium_group_size", "small_group_size"):
        assert sizes[key] == src[key]
    assert (src["pods_per_node"], sizes["pods_per_node"]) == (30, 10)
    groups = groups_of_a_namespace(sizes)
    namespaces = sizes["nodes"] // sizes["nodes_per_namespace"]
    assert namespaces == src["namespaces"] == made["namespaces"] == 50
    assert len(groups) == 109 and sum(r for _k, _n, r in groups) == made["pods_per_namespace"] == 990
    assert namespaces * len(groups) + 1 == made["workload_documents"] == 5451
    assert namespaces * 990 + sizes["nodes"] == made["pods"] == 54500
    assert CONFIG["roofline_shape"]["pinned_pods"] == sizes["nodes"]
