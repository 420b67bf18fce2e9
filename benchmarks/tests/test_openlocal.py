"""`plan-local` (the configuration `k8s-5k-50k-openlocal`), at sizes a test run
can hold: every capacity and claim the generator writes is a whole number of
GiB that float32 holds; the program, on its XLA scan and on the interpreted
kernel, replays through `kube_openlocal_reference` with nothing misplaced,
the exact count of added nodes and the same storage table; the reference's
choice of VG and device worked by hand; a device charged for another's claim,
a pod moved and one node too many each read `correct` false; the bfloat16
control reads not correct and float32 put in the same place reads nought;
without its storage the cluster needs fewer nodes; `roofline_local` and its
share; the files say the same thing as `BENCHMARK.json`."""

import importlib
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import roofline, roofline_gpushare, roofline_local
from benchmarks.control import control
from benchmarks.drivers import Context, plan_loop_local
from benchmarks.generators import k8s_openlocal as gen
from benchmarks.harness import ROOT, load_json, resolve
from benchmarks.readers import scan_roofline_local
from benchmarks.reference import kube_openlocal_reference as R
from benchmarks.reference.kube_reference import HOSTNAME, Cluster, Reference as PlainReference
from benchmarks.tests.test_faults import break_answer, failing, move_one_pod, one_node_too_many, run
from benchmarks.window import Item, Window

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(HERE, "configs", "k8s-5k-50k-openlocal.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(HERE, "traffic", "short-local.json")) as f:
    TRAFFIC = json.load(f)
SHAPE = CONFIG["roofline_shape"]
GI = 1 << 30
#: a tenth of the cell's pods: 270 nodes (180 pools of four) short of 8 for 750 database pods
SHRUNK = {"nodes": 300, "pods": 3000, "short_nodes": 270}
NOTHING_DIFFERS = {
    "misplaced_pods": 0, "worst_score_gap": 0.0, "infeasible_pods": 0, "unscheduled_diff": 0, "answer_diff": 0,
    "added_nodes_diff": 0, "storage_diff": 0, "plans_differing": 0, "plans_unanswered": 0,
}


def drive(tmp_path, seed, sizes=None):
    ctx = Context(config=CONFIG, traffic=TRAFFIC, seed=seed, scratch=str(tmp_path), rehearse=True,
                  sizes=sizes or CONFIG["tiny"])
    driver = importlib.import_module("benchmarks.drivers.plan_loop_local").Driver(ctx)
    driver.prepare()
    return driver


def least_added(cluster, most=16):
    for k in range(most + 1):
        if not R.Reference(cluster.with_new_nodes(k)).free_run(stop_at_unschedulable=True)[1]:
            return k
    return None


# -- the generator -------------------------------------------------------------


def test_every_capacity_and_claim_is_whole_gib_that_float32_holds(tmp_path):
    """The configuration's guarantee: multiples of 4Mi below 64Ti are exact in
    float32 (24 bits of mantissa), and so is every sum a pod's claims make."""
    cluster = gen.generate(CONFIG["sizes"], 3000000019, str(tmp_path))["variants"]["short"]["cluster"]
    sizes = {size for nd in cluster.nodes + [cluster.new_node] for _n, size in nd.vgs}
    sizes |= {size for nd in cluster.nodes + [cluster.new_node] for _n, size, _m in nd.devices}
    sizes |= {size for w in cluster.workloads for size in w.lvm} | {sum(w.lvm) for w in cluster.workloads if w.lvm}
    sizes |= {size for w in cluster.workloads for size, _m in w.devices}
    assert sizes and all(s % GI == 0 and s % (4 << 20) == 0 and 0 < s < 64 << 40 for s in sizes)
    assert all(int(np.float32(s)) == s for s in sizes)
    # 59 nodes to add: 12,500 database pods at four a pool, against 3,066 pools
    pools = sum(1 for nd in cluster.nodes if nd.vgs)
    db = sum(w.replicas for w in cluster.workloads if w.lvm)
    assert (pools, db) == (3066, 12500) and -(-db // 4) - pools == 59
    assert all(4 * (sum(w.lvm)) <= CONFIG["sizes"]["vg_gi"] * GI < 5 * sum(w.lvm) for w in cluster.workloads if w.lvm)


def test_the_files_carry_the_storage_the_reference_is_given(tmp_path):
    driver = drive(tmp_path, 5)
    cluster = driver.inputs["variants"]["short"]["cluster"]
    root = os.path.join(str(tmp_path), "plan")
    nodes = gen.read_docs(os.path.join(root, "cluster-short", "nodes.yaml"))
    by_name = {nd.name: nd for nd in cluster.nodes}
    for doc in nodes:
        storage = json.loads(doc["metadata"]["annotations"][gen.ANNO_NODE_LOCAL_STORAGE])
        spec = by_name[doc["metadata"]["name"]]
        assert [(v["name"], int(v["capacity"])) for v in storage["vgs"]] == list(spec.vgs)
        assert [(d["device"], int(d["capacity"]), d["mediaType"]) for d in storage["devices"]] == list(spec.devices)
    apps = gen.read_docs(os.path.join(root, "apps", "deployments.yaml"))
    kinds = {f"default/{d['metadata']['name']}": d["kind"] for d in apps}
    assert sum(1 for k in kinds.values() if k == "StatefulSet") == 8
    # the reference's workloads: the Deployments, then the StatefulSets, as the simulator expands them
    assert [w.kind for w in cluster.workloads] == ["Deployment"] * 12 + ["StatefulSet"] * 8
    assert all(kinds[w.name] == w.kind for w in cluster.workloads)
    (new,) = gen.read_docs(os.path.join(root, "newnode", "node.yaml"))
    assert new["metadata"]["labels"]["disk"] == "ssd" and cluster.new_node.vgs and len(cluster.new_node.devices) == 2


# -- the program against the reference -----------------------------------------


@pytest.mark.parametrize("seed", [5, 11, 2147483659, 3000000023])
def test_the_plan_replays_through_the_reference_with_its_storage(tmp_path, monkeypatch, seed):
    monkeypatch.setenv("OPENSIM_DISABLE_NATIVE", "1")
    driver = drive(tmp_path, seed)
    window = Window(opened=0.0, closed=1.0, items=[driver.one(0, False)])
    driver.after_window(window)
    report = window.items[0].info["report"]
    assert report["success"] and report["engine"].startswith("xla"), report["engine"]
    assert {c["name"]: c["value"] for c in driver.compare(window)} == NOTHING_DIFFERS
    cluster = driver.inputs["variants"]["short"]["cluster"]
    assert report["added"] == least_added(cluster) == 5
    # a row for every VG and device of the cluster and of the nodes added
    grown = cluster.with_new_nodes(report["added"])
    assert len(report["storage"]) == sum(len(nd.vgs) + len(nd.devices) for nd in grown.nodes)


def test_the_interpreted_kernel_replays_through_the_reference_with_its_storage(tmp_path, monkeypatch):
    monkeypatch.setenv("OPENSIM_DISABLE_NATIVE", "1")
    monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")
    driver = drive(tmp_path, 3000000019)
    window = Window(opened=0.0, closed=1.0, items=[driver.one(0, False)])
    driver.after_window(window)
    report = window.items[0].info["report"]
    assert report["success"] and report["engine"].startswith("megakernel"), report["engine"]
    assert {c["name"]: c["value"] for c in driver.compare(window)} == NOTHING_DIFFERS


def test_without_its_storage_the_cluster_needs_fewer_nodes(tmp_path):
    """The LVM pools decide the count: the same nodes and pods with no
    storage fit as they are."""
    cluster = drive(tmp_path, 5).inputs["variants"]["short"]["cluster"]
    assert least_added(cluster) == 5
    plain = Cluster(cluster.nodes, cluster.bound, cluster.workloads, cluster.new_node)
    _placed, unscheduled = PlainReference(plain).free_run()
    assert not unscheduled


# -- the reference's choice, worked by hand --------------------------------------


def one_node(vgs=(), devices=(), claims=()):
    """A node with `vgs` (GiB) and `devices` ((GiB, media)), and one workload
    of a pod a claim list: ("lvm", GiB) or (media, GiB)."""
    node = R.LocalNodeSpec("n0", 64000, 256 * GI, 110, {HOSTNAME: "n0"},
                           vgs=tuple((f"vg{i}", g * GI) for i, g in enumerate(vgs)),
                           devices=tuple((f"/dev/d{i}", g * GI, m) for i, (g, m) in enumerate(devices)))
    workloads = [R.LocalWorkload(f"default/w{k}", 1, 100, 1 << 20, {"app": f"w{k}"}, kind="StatefulSet",
                                 lvm=tuple(g * GI for kind, g in c if kind == "lvm"),
                                 devices=tuple((g * GI, kind) for kind, g in c if kind != "lvm"))
                 for k, c in enumerate(claims)]
    return R.Reference(R.LocalCluster([node], [], workloads, None))


def feasible_and_bind(ref, wi):
    ref._enter(wi)
    ok = bool(ref.step()[0][0])
    if ok:
        ref.bind(0)
    return ok


def test_lvm_claims_are_one_allocation_in_the_tightest_vg():
    ref = one_node(vgs=(100, 40, 60), claims=[[("lvm", 30), ("lvm", 20)], [("lvm", 35)], [("lvm", 60)], [("lvm", 1)]])
    assert feasible_and_bind(ref, 0)  # 50: the 60 is the tightest that holds it
    assert ref.vg_free[0].tolist() == [100 * GI, 40 * GI, 10 * GI]
    assert feasible_and_bind(ref, 1)  # 35: the 40 now
    assert feasible_and_bind(ref, 2)  # 60: only the 100
    assert ref.vg_free[0].tolist() == [40 * GI, 5 * GI, 10 * GI]
    assert feasible_and_bind(ref, 3)  # 1: the 5, the tightest of three that hold it
    assert ref.storage() == {("n0", "vg0"): ("VG", 60 * GI, 100 * GI), ("n0", "vg1"): ("VG", 36 * GI, 40 * GI),
                             ("n0", "vg2"): ("VG", 50 * GI, 60 * GI)}


def test_device_claims_take_the_smallest_device_that_holds_them_the_smallest_claim_first():
    ref = one_node(devices=((30, "ssd"), (10, "ssd"), (20, "ssd"), (20, "ssd")),
                   claims=[[("ssd", 15), ("ssd", 5)], [("ssd", 20)], [("ssd", 1)]])
    assert feasible_and_bind(ref, 0)  # 5 onto the 10, then 15 onto the first 20
    assert ref.dev_held[0].tolist() == [False, True, True, False]
    assert feasible_and_bind(ref, 1)  # 20 onto the other 20
    assert feasible_and_bind(ref, 2)  # 1: only the 30 is left
    assert ref.dev_held[0].all()


def test_devices_10_and_20_hold_no_claims_of_15_and_25():
    """`PARITY.md` #3: the vendored check can pass this node with the 25
    left over; a one-device-per-claim matching does not exist."""
    ref = one_node(devices=((10, "hdd"), (20, "hdd")), claims=[[("hdd", 15), ("hdd", 25)], [("hdd", 15), ("hdd", 5)]])
    assert not feasible_and_bind(ref, 0)
    assert feasible_and_bind(ref, 1) and ref.dev_held[0].tolist() == [True, True]


def test_a_claim_that_only_a_device_of_the_other_media_holds_fits_nowhere():
    ref = one_node(devices=((100, "hdd"), (10, "ssd")), claims=[[("ssd", 50)], [("hdd", 50)]])
    assert not feasible_and_bind(ref, 0)
    assert feasible_and_bind(ref, 1) and ref.dev_held[0].tolist() == [True, False]


def test_the_score_is_the_mean_of_requested_over_capacity_and_packs():
    """Two nodes, a pool of 100Gi and one device each, of 80Gi and 40Gi: a pod
    of an LVM claim of 25Gi and a device claim of 30Gi scores 10 x the mean of
    25/100 and 30/80, or of 25/100 and 30/40; the smaller device, which the
    claim fills more, wins."""
    nodes = [R.LocalNodeSpec(f"n{i}", 64000, 256 * GI, 110, {HOSTNAME: f"n{i}"},
                             vgs=(("vg", 100 * GI),), devices=(("/dev/a", dev * GI, "ssd"),))
             for i, dev in enumerate((80, 40))]
    w = R.LocalWorkload("default/w", 2, 100, 1 << 20, {"app": "w"}, kind="StatefulSet", lvm=(25 * GI,),
                        devices=((30 * GI, "ssd"),))
    ref = R.Reference(R.LocalCluster(nodes, [], [w], None))
    ref._enter(0)
    assert ref.local_raw().tolist() == [3.125, 5.0]
    feasible, score = ref.step()
    assert feasible.all() and int(np.argmax(score)) == 1
    ref.bind(1)
    # the second pod: the 40Gi device is held, so only n0 holds the claim
    assert ref.step()[0].tolist() == [True, False]


# -- the comparison --------------------------------------------------------------


def test_the_storage_table_is_read_and_written_by_the_reports_rule():
    assert plan_loop_local.quantity_text(3576 * GI) == "3.49Ti" and plan_loop_local.quantity_text(0) == "0"
    assert plan_loop_local.quantity_text(1788 * GI) == "1.75Ti" and plan_loop_local.quantity_text(50 * GI) == "50Gi"
    assert plan_loop_local.requests_text(3400 * GI, 3576 * GI) == "3.32Ti(95%)"
    assert plan_loop_local.requests_text(0, 3576 * GI) == "0(0%)"


def charge_another_device(result):
    """On one node a device a claim holds and one no claim holds swap, in the
    annotation the report's storage table is written from: the pods stay
    where they are."""
    from opensim_tpu.models.objects import ANNO_NODE_LOCAL_STORAGE

    for status in result.node_status:
        anno = status.node.metadata.annotations.get(ANNO_NODE_LOCAL_STORAGE)
        storage = json.loads(anno) if anno else {}
        devices = storage.get("devices") or []
        held = [d for d in devices if d["isAllocated"]]
        free = [d for d in devices if not d["isAllocated"] and held and d["mediaType"] == held[0]["mediaType"]]
        if held and free:
            held[0]["isAllocated"], free[0]["isAllocated"] = False, True
            status.node.metadata.annotations[ANNO_NODE_LOCAL_STORAGE] = json.dumps(storage)
            return
    raise AssertionError("no node with a held and a free device of one media")


# each harness run and each control below has a seed of its own: the scratch directory is named by
# the seed, and parallel workers must not share one


def test_a_sound_run_is_correct():
    line = run("plan-local", seed=7)
    assert line["correct"] is True and not failing(line) and line["failed"] == 0


def test_a_claim_charged_to_another_device(monkeypatch):
    break_answer(monkeypatch, charge_another_device)
    line = run("plan-local", seed=8)
    assert line["correct"] is False and failing(line) == {"storage_diff"}
    # two rows in every plan of the window: each plan's table is compared
    assert {c["name"]: c["value"] for c in line["checks"]}["storage_diff"] == 2 * line["attempted"]


def test_one_pod_moved_where_the_answer_is_produced(monkeypatch):
    break_answer(monkeypatch, move_one_pod)
    line = run("plan-local", seed=9)
    assert line["correct"] is False
    assert failing(line) & {"worst_score_gap", "infeasible_pods", "misplaced_pods", "storage_diff"}


def test_the_count_of_added_nodes_off_by_one(monkeypatch):
    one_node_too_many(monkeypatch)
    line = run("plan-local", seed=10)
    assert line["correct"] is False and "added_nodes_diff" in failing(line)


@pytest.mark.parametrize("seed", [5, 2147483659])
def test_the_low_precision_control_is_not_correct(seed):
    got = control("plan-local", seed, SHRUNK)
    assert got["control"] == "bfloat16" and got["control_correct"] is False
    values = {c["name"]: c["value"] for c in got["checks"]}
    assert values["worst_score_gap"] > 0.05 and values["misplaced_pods"] > 0
    # the control's filters and storage arithmetic are exact
    assert values["infeasible_pods"] == 0 and values["added_nodes_diff"] == 0


def test_the_reference_in_its_own_precision_put_in_the_programs_place_is_correct():
    got = control("plan-local", 6, SHRUNK, precision="float32")
    assert got["control_correct"] is True and all(c["value"] == 0 for c in got["checks"])


# -- the count of the work -------------------------------------------------------


def test_the_local_rows_add_their_cells_to_every_pass():
    q = {"nodes": 4659, "pods": 50000, "resident": 0,
         "scenario_nodes": [4600 + k for k in roofline_gpushare.sweep_counts(59, 128)]}
    got = roofline_local.question_work(q, SHAPE)
    cells = 2 * SHAPE["local_vgs"] + 3 * SHAPE["local_devices"]
    assert roofline_local.local_cells(SHAPE) == cells == 14
    for nodes in [q["nodes"]] + q["scenario_nodes"]:
        plain = roofline.question_work(nodes, q["pods"], 0, SHAPE)
        one = roofline_local.pass_work(nodes, q["pods"], SHAPE)
        assert one["ops"] - plain["ops"] == q["pods"] * nodes * cells * roofline.OPS_PER_COLUMN
        assert one["bytes"] - plain["bytes"] == 4 * (q["pods"] * nodes * cells + nodes * cells + q["pods"] * 5)
    assert got["ops"] == sum(roofline_local.pass_work(n, q["pods"], SHAPE)["ops"]
                             for n in [q["nodes"]] + q["scenario_nodes"])


PATTERN = load_json(os.path.join(HERE, "metrics", "scan_roofline.local.json"))["args"]["ops"]
QUESTION = {"nodes": 4659, "pods": 50000, "resident": 0,
            "scenario_nodes": [4600 + k for k in roofline_gpushare.sweep_counts(59, 128)]}


def run_with(ops, questions, shape=SHAPE):
    items = [Item(start=0.0, end=1.0, ok=True) for _ in questions]
    return SimpleNamespace(
        trace={"device_ops": ops, "busy_s": sum(s for _n, s in ops), "window_s": 10.0},
        window=Window(opened=0.0, closed=1.0, items=items), questions=questions,
        config={"roofline_shape": shape}, device_kind="TPU v5 lite")


def test_the_share_of_a_synthetic_trace_never_passes_100_and_is_bound_by_bytes():
    work = roofline_local.question_work(QUESTION, SHAPE)
    least = roofline.least_seconds(work, roofline.load_peaks("TPU v5 lite"))
    assert least["bound"] == "bytes"
    at_peak = scan_roofline_local.read(run_with([["jit_run_fast_scan", least["seconds"]]], [QUESTION]), PATTERN)
    assert at_peak == pytest.approx(100.0) and at_peak <= 100.0 + 1e-9
    for slower in (1.5, 10.0, 400.0):
        share = scan_roofline_local.read(run_with([["jit_run_fast_scan", slower * least["seconds"]]], [QUESTION]),
                                         PATTERN)
        assert share == pytest.approx(100.0 / slower) and 0 < share < 100


def test_nothing_to_read_is_none_not_zero():
    assert scan_roofline_local.read(run_with([], [QUESTION]), PATTERN) is None
    assert scan_roofline_local.read(run_with([["jit_dynamic_slice", 1.0]], [QUESTION]), PATTERN) is None
    k8s = run_with([["jit_run_fast_scan", 1.0]], [QUESTION], shape={"resources": 3, "selector_labels": 1, "spread_keys": 2})
    assert scan_roofline_local.read(k8s, PATTERN) is None


# -- the files -------------------------------------------------------------------


def test_the_cell_and_its_metrics_are_in_the_benchmark_as_the_files_have_them():
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    parts = resolve(bench, "plan-local")
    entry = next(c for c in bench["configs"] if c["name"] == "k8s-5k-50k-openlocal")
    assert parts["cell"] == {"name": "plan-local", "config": "k8s-5k-50k-openlocal", "traffic": "short-local",
                             "chips": 1, "why": parts["cell"]["why"]}
    assert len(parts["cell"]["why"]) <= 200 and len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == CONFIG["reduced"] == ["pods"]
    assert "alibaba/open-local" in entry["source"] and "cluster-large" in entry["source"]
    base = load_json(os.path.join(HERE, "configs", "k8s-5k-50k.json"))
    # the cluster and its apps are k8s-5k-50k's: no pod cap, an ssd newNode of 110 pods, and the storage
    for key in ("nodes", "pods", "workloads", "zones", "node_cpu", "node_memory_gi", "node_pods", "pod_cpu_m",
                "pod_memory_mi", "zone_max_skew", "short_nodes", "max_new_nodes"):
        assert CONFIG["sizes"][key] == base["sizes"][key], key
    assert "ssd_cap" not in CONFIG["sizes"] and CONFIG["sizes"]["new_cap"] == 110
    assert CONFIG["guarantees"][:3] == base["guarantees"] and len(CONFIG["guarantees"]) == 6
    assert CONFIG["roofline_shape"] == dict(base["roofline_shape"], local_vgs=1, local_devices=4)
    assert CONFIG["reference"] == "kube_openlocal_reference" and CONFIG["precision"] == "float32"
    traffic = parts["traffic"]
    assert traffic["driver"] == "plan-loop-local" and traffic["traced_items"] == 1
    assert traffic["params"] == {"variant": "short", "extended_resources": ["open-local"]}
    gpushare = dict(load_json(os.path.join(HERE, "traffic", "short-gpushare.json"))["limits"])
    del gpushare["gpu_device_diff"]
    assert traffic["limits"] == dict(gpushare, storage_diff=0)
    assert {m["name"] for m in parts["end_to_end"]} == {"plan_s", "setup_s"}
    mine = {m["name"] for m in bench["per_layer"] if m.get("workloads") == ["plan-local"]}
    assert mine == {"scan_roofline.local", "encode_local_s.plan", "decode_local_s.plan"}
    reported = {m["name"] for m in parts["per_layer"]}
    assert {"mk_inputs_s.plan", "mk_launch_s.plan", "mk_wait_s.plan", "compile_path_s.plan", "report_s.plan",
            "load_program_s.plan", "load_parse_s.plan", "load_objects_s.plan", "report_nodes_s.plan",
            "report_apps_s.plan"} <= reported
    assert not {"xla_launch_s.plan", "xla_wait_s.plan", "scan_roofline.plan"} & reported
