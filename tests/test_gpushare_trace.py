"""`openb-gpushare-1523` (ISSUE 33): Alibaba's GPU-sharing cluster at sizes a
test run can hold, through `Applier.run()` by each engine the CPU has,
replayed pod for pod and device for device through the plain Open-Gpu-Share
reference of `benchmarks/reference/kube_gpushare_reference.py`; the exact
floor of a quotient at the boundary where a device holds exactly k requests;
hand-worked cases of the device choice; two independent plain
implementations against each other; and the spans, attributes and counter
the configuration's path reports."""

import importlib
import json
import os

import numpy as np
import pytest

from benchmarks.drivers import Context
from benchmarks.generators import openb_gpushare as gen
from benchmarks.reference import kube_gpushare_reference as R
from benchmarks.window import Window
from opensim_tpu.obs import trace as tracing
from opensim_tpu.obs.metrics import RECORDER

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "benchmarks", "configs", "openb-gpushare-1523.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(REPO, "benchmarks", "traffic", "short-gpushare.json")) as f:
    TRAFFIC = json.load(f)

MI = 1 << 20


def shrunk(counts, load_pct, **over):
    sizes = json.loads(json.dumps(CONFIG["tiny"]))
    for cls, k in zip(sizes["node_classes"], counts):
        cls["count"] = k
    return dict(sizes, load_pct=load_pct, **over)


#: `tiny` is the configuration's own rehearsal size (24 nodes, 125 tasks, 2-3 nodes added); `small` is smaller
#: still and short of GPUs by more; `fits` needs no node
SIZES = {
    "tiny": CONFIG["tiny"],
    "small": shrunk([1, 1, 2, 2, 2, 2, 1], 120, max_new_nodes=16),
    "fits": shrunk([2, 1, 3, 3, 3, 4, 2], 60, max_new_nodes=16),
}
#: how a test asks for an engine on the CPU, what the report then names, and whether big-U is forced
ENGINES = {
    "xla": ({}, "xla", None),
    "megakernel": ({"OPENSIM_FASTPATH": "interpret"}, "megakernel", False),
    "megakernel-big-u": ({"OPENSIM_FASTPATH": "interpret"}, "megakernel", True),
}
NOUGHT = {
    "misplaced_pods": 0, "worst_score_gap": 0.0, "infeasible_pods": 0, "unscheduled_diff": 0, "answer_diff": 0,
    "added_nodes_diff": 0, "gpu_device_diff": 0, "plans_differing": 0, "plans_unanswered": 0,
}


def engine(monkeypatch, name):
    env, named, big_u = ENGINES[name]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    if big_u is not None:
        from opensim_tpu.engine import fastpath, select

        monkeypatch.setattr(fastpath, "use_big_u", lambda U, N: big_u)
        # the tests' eight virtual devices would shard a sweep over the XLA scan: one, as on the chip
        real = select.policy
        monkeypatch.setattr(select, "policy", lambda: real()._replace(devices=1))
    return named, big_u


def drive(tmp_path, sizes, seed):
    ctx = Context(config=CONFIG, traffic=TRAFFIC, seed=seed, scratch=str(tmp_path), rehearse=True, sizes=sizes)
    driver = importlib.import_module("benchmarks.drivers.plan_loop_gpu").Driver(ctx)
    driver.prepare()
    return driver


def plan(driver, traced=False):
    window = Window(opened=0.0, closed=1.0, items=[driver.one(0, traced)])
    driver.after_window(window)
    return window


def least_added(cluster, most=16):
    """The least count of new nodes with which the reference's own run
    schedules everything, by the reference alone."""
    for k in range(most + 1):
        if not R.Reference(cluster.with_new_nodes(k)).free_run(stop_at_unschedulable=True)[1]:
            return k
    return None


# ---------------------------------------------------------------------------
# the program against the reference, pod for pod and device for device
# ---------------------------------------------------------------------------


def spans_of(window, name):
    found = []

    def walk(tree):
        if tree["name"] == name:
            found.append(tree)
        for child in tree.get("children", []):
            walk(child)

    walk(window.items[0].spans)
    return found


@pytest.fixture
def span_attrs(monkeypatch):
    """`plan_loop.span_tree` keeps names and times; these tests read attributes too."""
    from benchmarks.drivers import plan_loop

    real = plan_loop.span_tree

    def with_attrs(span):
        tree = real(span)
        tree["attrs"] = dict(span.attrs)
        tree["children"] = [with_attrs(c) for c in span.children]
        return tree

    monkeypatch.setattr(plan_loop, "span_tree", with_attrs)


@pytest.mark.usefixtures("span_attrs")
@pytest.mark.parametrize("seed", [5, 3000000023, 17])
@pytest.mark.parametrize("size", ["tiny", "small"])
@pytest.mark.parametrize("name", list(ENGINES))
def test_the_plan_replays_through_the_reference_pod_for_pod_and_device_for_device(tmp_path, monkeypatch, name, size, seed):
    named, big_u = engine(monkeypatch, name)
    driver = drive(tmp_path, SIZES[size], seed)
    window = plan(driver, traced=named == "megakernel")
    report = window.items[0].info["report"]
    assert report["success"] and report["engine"].startswith(named), report["engine"]
    assert {c["name"]: c["value"] for c in driver.compare(window)} == NOUGHT
    cluster = driver.inputs["variants"]["short"]["cluster"]
    # a short plan: nodes are added, and their count is the least the reference finds alone
    assert report["added"] == least_added(cluster) > 0
    assert sum(len(seq) for seq in report["placed"].values()) == len(cluster.workloads)
    # the table has a row for every device of every node, and none is charged beyond its 1000Mi
    devices = report["devices"]
    assert len(devices) == sum(nd.gpus for nd in cluster.nodes) + 8 * report["added"]
    assert 0 < max(devices.values()) <= 1000 * MI and min(devices.values()) >= 0
    if named == "megakernel":
        launches = spans_of(window, "mk.launch")
        # the first pass, the two sweeps (one where the bracket is closed) and the masked final pass
        assert 3 <= len(launches) <= 4 and all(sp["attrs"]["big_u"] is big_u for sp in launches)
        # a newNode template is there, so the first pass is asked for no reasons: its result is kept as it
        # is, tasks that fit nowhere and all, and no XLA scan is left in the plan (ISSUE 36)
        assert not spans_of(window, "engine.xla")
        assert [sp["attrs"]["attribution"] for sp in spans_of(window, "engine.megakernel")] == ["not_asked", "none"]


@pytest.mark.parametrize("name", ["xla", "megakernel"])
def test_a_cluster_that_fits_adds_no_node(tmp_path, monkeypatch, name):
    named, _ = engine(monkeypatch, name)
    driver = drive(tmp_path, SIZES["fits"], 9)
    window = plan(driver)
    report = window.items[0].info["report"]
    assert report["success"] and report["added"] == 0 and report["engine"].startswith(named)
    assert {c["name"]: c["value"] for c in driver.compare(window)} == NOUGHT


def test_the_seed_decides_the_answer_and_the_counts_are_held(tmp_path):
    a = drive(tmp_path / "a", CONFIG["tiny"], 5).inputs
    b = drive(tmp_path / "b", CONFIG["tiny"], 6).inputs
    assert a["counts"] == b["counts"] and a["counts"]["tasks"] == len(a["variants"]["short"]["cluster"].workloads)
    ca, cb = a["variants"]["short"]["cluster"], b["variants"]["short"]["cluster"]
    assert [n.name for n in ca.nodes] != [n.name for n in cb.nodes]
    assert sorted(n.name for n in ca.nodes) == sorted(n.name for n in cb.nodes)
    assert sum(n.gpus for n in ca.nodes) == sum(n.gpus for n in cb.nodes) == a["counts"]["gpus"]
    # at least one task of every GPU class, and an exact divisor of a device among the fractions
    assert all(k >= 1 for k in a["counts"]["by_class"].values())
    asked = {w.gpu_mem // MI for w in ca.workloads if w.gpu_count == 1}
    assert asked & {100, 125, 200, 250, 500} and 1000 in asked
    ra, rb = R.Reference(ca.with_new_nodes(4)), R.Reference(cb.with_new_nodes(4))
    ra.free_run()
    rb.free_run()
    assert ra.order() != rb.order()


def test_the_full_size_is_the_sources_own():
    src, sizes = CONFIG["source_sizes"], CONFIG["sizes"]
    assert CONFIG["reduced"] == [] and "cluster-trace-gpu-v2023" in CONFIG["source"] and len(CONFIG["source"]) <= 200
    classes = sizes["node_classes"]
    assert sum(c["count"] for c in classes) == src["nodes"] == 1523
    assert sum(c["count"] for c in classes if c["gpus"]) == src["gpu_nodes"] == 1213
    assert sum(c["count"] * c["gpus"] for c in classes) == src["gpus"] == 6212
    assert max(c["gpus"] for c in classes) == src["max_gpus_per_node"] == 8
    assert abs(sum(c["count"] * c["cpu"] for c in classes) - src["cpu_cores_about"]) < 100
    assert {k: v["share_pct"] for k, v in sizes["task_classes"].items()} == src["gpu_request_share_pct"]
    counts = gen.task_counts(sizes)
    asked = sum(m * k for m, k in counts["by_milli"]) / 1000.0 + sum(
        gen.WHOLE_GPUS[c] * k for c, k in counts["by_class"].items() if c != "fraction")
    assert sizes["load_pct"] == 100 and 0 <= asked - 6212 < 1  # the GPUs asked for just reach the cluster's
    assert counts["tasks"] == 8602 and counts["asked_gpus"] == asked
    # the commonest 8-GPU class is the newNode, and the reference's eight counts lie in one bracket of the sweep
    eight = [c for c in classes if c["gpus"] == 8]
    assert classes[sizes["new_node_class"]] is max(eight, key=lambda c: c["count"])
    assert len(sizes["added_nodes_reference"]) == 8 and all(17 <= k <= 32 for k in sizes["added_nodes_reference"].values())


# ---------------------------------------------------------------------------
# the exact-divisor boundary: a device with k x mem free holds k, never k - 1
# ---------------------------------------------------------------------------

DIVISORS = [100, 125, 200, 250, 500]


@pytest.mark.parametrize("mem", DIVISORS)
def test_the_floor_of_a_quotient_that_is_two_ulp_off_is_still_exact(mem):
    """On the CPU the hardware quotient is IEEE's; a TPU's is up to 2 ulp off
    (PERF.md, PR 29), and at k x mem over mem the floor of a quotient that
    is one ulp under k is k - 1."""
    import jax

    from opensim_tpu.ops import kernels

    ks = np.arange(0, 11, dtype=np.float32)
    b = np.full_like(ks, mem * MI)
    for extra in (0, 1, mem - 1):  # exact multiples, one Mi over, one Mi short of the next
        a = (ks * mem + extra).astype(np.float32) * MI
        want = np.floor((ks * mem + extra) / mem).astype(np.float32)
        assert np.array_equal(np.asarray(jax.jit(kernels.floor_div32)(a, b)), want)
        for ulps in (-2, -1, 1, 2):
            off = a / b
            for _ in range(abs(ulps)):
                off = np.nextafter(off, np.float32(np.inf if ulps > 0 else -np.inf))
            assert np.array_equal(np.asarray(jax.jit(kernels._floor_corrected)(a, b, off)), want), (extra, ulps)
    # the plain floor is what goes wrong: one ulp under k reads k - 1
    under = np.nextafter(np.float32(3.0), np.float32(0.0))
    assert np.floor(under) == 2.0


def boundary_cluster(mem):
    """One node for each k in 1..10 whose single device has exactly k x mem
    Mi, and for each a pod, pinned there by its hostname, that asks k GPUs
    of mem Mi: it fits if and only if the device reads k slots."""
    nodes, pods, specs, workloads = [], [], [], []
    for k in range(1, 11):
        name = f"n{k:02d}"
        cap = {"cpu": "8", "memory": "32Gi", "pods": "110", gen.GPU_COUNT: "1", gen.GPU_MEM: f"{k * mem}Mi"}
        nodes.append(gen.node_doc(name, "T4", cap, dict(cap)))
        pod = gen.pod_doc(f"p{k:02d}", 1000, 1024, mem, k)
        pod["spec"]["nodeSelector"] = {gen.HOSTNAME: name}
        pods.append(pod)
        specs.append(R.GpuNodeSpec(name=name, cpu_m=8000, mem_bytes=32 << 30, pods=110, labels={gen.HOSTNAME: name},
                                   gpus=1, gpu_mem=k * mem * MI))
        workloads.append(R.GpuWorkload(name=f"openb/p{k:02d}", replicas=1, cpu_m=1000, mem_bytes=1 << 30, labels={},
                                       node_selector={gen.HOSTNAME: name}, gpu_mem=mem * MI, gpu_count=k))
    return nodes, pods, R.GpuCluster(nodes=specs, bound=[], workloads=workloads, new_node=None)


def simulate_docs(node_docs, pod_docs):
    from opensim_tpu.engine.simulator import AppResource, simulate
    from opensim_tpu.models import expand

    cluster, _ = expand.resources_from_dicts(node_docs)
    app, _ = expand.resources_from_dicts(pod_docs)
    return simulate(cluster, [AppResource("openb", app)])


def placements(result):
    """pod name -> (node, the gpu-index annotation)."""
    from opensim_tpu.models.objects import ANNO_GPU_INDEX

    return {p.metadata.name: (s.node.metadata.name, p.metadata.annotations.get(ANNO_GPU_INDEX, ""))
            for s in result.node_status for p in s.pods}


@pytest.mark.parametrize("mem", DIVISORS)
@pytest.mark.parametrize("name", ["xla", "megakernel", "megakernel-big-u"])
def test_a_device_with_k_requests_free_holds_k(monkeypatch, name, mem):
    named, _ = engine(monkeypatch, name)
    nodes, pods, cluster = boundary_cluster(mem)
    result = simulate_docs(nodes, pods)
    assert result.engine.name == named and not result.unscheduled_pods
    got = placements(result)
    assert got == {f"p{k:02d}": (f"n{k:02d}", "-".join(["0"] * k)) for k in range(1, 11)}
    ref = R.Reference(cluster)
    _placed, unscheduled = ref.free_run()
    assert not unscheduled and ref.devices() == {(f"n{k:02d}", 0): k * mem * MI for k in range(1, 11)}
    # one more slot of the same size fits nowhere: every device is full
    again = simulate_docs(nodes, pods + [gen.pod_doc("late", 1000, 1024, mem, 1)])
    assert [u.pod.metadata.name for u in again.unscheduled_pods] == ["late"]


# ---------------------------------------------------------------------------
# the device choice, worked by hand
# ---------------------------------------------------------------------------


def one_node(gpus, tasks):
    """A node of `gpus` devices of 1000Mi and a stream of (gpu_milli, GPUs)
    tasks, for the program and for the reference."""
    cap = {"cpu": "96", "memory": "512Gi", "pods": "110", gen.GPU_COUNT: str(gpus), gen.GPU_MEM: f"{gpus * 1000}Mi"}
    nodes = [gen.node_doc("n0", "G2", cap, dict(cap))]
    pods = [gen.pod_doc(f"t{i:02d}", 1000, 1024, milli, k) for i, (milli, k) in enumerate(tasks)]
    cluster = R.GpuCluster(
        nodes=[R.GpuNodeSpec(name="n0", cpu_m=96000, mem_bytes=512 << 30, pods=110, labels={gen.HOSTNAME: "n0"},
                             gpus=gpus, gpu_mem=1000 * MI)],
        bound=[], new_node=None,
        workloads=[R.GpuWorkload(name=f"openb/t{i:02d}", replicas=1, cpu_m=1000, mem_bytes=1 << 30, labels={},
                                 gpu_mem=milli * MI, gpu_count=k) for i, (milli, k) in enumerate(tasks)])
    return nodes, pods, cluster


def reference_ids(cluster):
    """The reference's own device choice for every task, as the gpu-index annotation writes it."""
    ref, out = R.Reference(cluster), []
    for wi in range(len(cluster.workloads)):
        ref._enter(wi)
        feasible, _score = ref.step()
        if not feasible[0]:
            out.append(None)
            continue
        take = ref.allocate(0)
        out.append("-".join(str(d) for d, k in enumerate(take) for _ in range(k)))
        ref.bind(0)
    return out, ref


TIGHTEST = [(600, 1), (300, 1), (500, 1), (500, 1), (100, 1), (1000, 1), (1000, 1), (250, 1), (1000, 1)]
#: equal devices go by the lowest index; then the device with the least room that still fits
TIGHTEST_IDS = ["0", "0", "1", "1", "0", "2", "3", None, None]
#: 600 leaves device 0 with 400: two GPUs of 400 take one slot there and one of device 1's two; four of 250 reuse
#: device 1 twice and device 2 twice; eight of 125 find device 1 with 100, device 2 with 500 and device 3 whole
#: then two halves find one half left on device 3 and fit nowhere; one half takes it
GREEDY = [(600, 1), (400, 2), (250, 4), (125, 8), (1000, 4), (500, 2), (500, 1)]
GREEDY_IDS = ["0", "0-1", "1-1-2-2", "2-2-2-2-3-3-3-3", "4-5-6-7", None, "3"]


@pytest.mark.parametrize("name", ["xla", "megakernel"])
@pytest.mark.parametrize("case", ["tightest", "greedy"])
def test_the_device_a_task_sits_on_worked_by_hand(monkeypatch, name, case):
    named, _ = engine(monkeypatch, name)
    gpus, tasks, want = (4, TIGHTEST, TIGHTEST_IDS) if case == "tightest" else (8, GREEDY, GREEDY_IDS)
    nodes, pods, cluster = one_node(gpus, tasks)
    own, ref = reference_ids(cluster)
    assert own == want
    result = simulate_docs(nodes, pods)
    # a task that fits nowhere with one after it that does: a caller that asks for reasons (the default)
    # has the kernel's pass discarded for the XLA scan's attribution of the failure
    mid_stream = None in want[:-1] and want[-1] is not None
    assert result.engine.name == ("xla" if named == "megakernel" and mid_stream else named)
    got = placements(result)
    assert [got.get(f"t{i:02d}", (None, None))[1] for i in range(len(tasks))] == want
    assert sorted(u.pod.metadata.name for u in result.unscheduled_pods) == [f"t{i:02d}" for i, w in enumerate(want) if w is None]
    # and the table of the report says what the reference holds, device for device
    from opensim_tpu.models.objects import ANNO_NODE_GPU_SHARE

    info = json.loads(result.node_status[0].node.metadata.annotations[ANNO_NODE_GPU_SHARE])
    assert {("n0", int(d)): v["GpuUsedMemory"] for d, v in info["DevsBrief"].items()} == ref.devices()


def test_a_node_without_a_device_takes_no_gpu_task_and_a_count_of_nought_fits_nowhere():
    nodes = [R.GpuNodeSpec(name="cpu", cpu_m=8000, mem_bytes=32 << 30, pods=110, labels={}),
             R.GpuNodeSpec(name="gpu", cpu_m=8000, mem_bytes=32 << 30, pods=110, labels={}, gpus=2, gpu_mem=1000 * MI)]
    tasks = [R.GpuWorkload(name="plain", replicas=1, cpu_m=100, mem_bytes=MI, labels={}),
             R.GpuWorkload(name="half", replicas=1, cpu_m=100, mem_bytes=MI, labels={}, gpu_mem=500 * MI, gpu_count=1),
             R.GpuWorkload(name="none", replicas=1, cpu_m=100, mem_bytes=MI, labels={}, gpu_mem=500 * MI, gpu_count=0),
             R.GpuWorkload(name="five", replicas=1, cpu_m=100, mem_bytes=MI, labels={}, gpu_mem=500 * MI, gpu_count=5)]
    ref = R.Reference(R.GpuCluster(nodes=nodes, bound=[], workloads=tasks, new_node=None))
    feasible = []
    for wi in range(4):
        ref._enter(wi)
        feasible.append(ref.step()[0].tolist())
    assert feasible == [[True, True], [False, True], [False, False], [False, False]]
    # the replay reads a pod put on the node without a device as infeasible, and the device table as differing
    cluster = ref.cluster
    got = R.replay(cluster, {"plain": ["cpu"], "half": ["cpu"]}, {"none": 1, "five": 1})
    assert got["infeasible_pods"] == 1 and got["answer_diff"] == 0 and got["unscheduled_diff"] == 0
    assert R.device_diff(cluster, {"half": ["gpu"]}, {("gpu", 0): 500 * MI, ("gpu", 1): 0}) == 0
    assert R.device_diff(cluster, {"half": ["gpu"]}, {("gpu", 0): 0, ("gpu", 1): 500 * MI}) == 2
    assert R.device_diff(cluster, {"half": ["gpu"]}, {("gpu", 0): 500 * MI, ("gpu", 7): MI}) == 1
    assert R.device_diff(cluster, {"half": ["gpu"]}, None) == 0
    with pytest.raises(ValueError):
        R.Reference(cluster, "float16")


# ---------------------------------------------------------------------------
# two independent plain implementations
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [4, 2147483659])
def test_the_xla_scan_and_the_reference_agree(tmp_path, seed):
    """`simulate` on the XLA scan and `kube_gpushare_reference` (numpy
    float32 over plain data) share no code and put every task on the same
    node."""
    from opensim_tpu.engine.simulator import simulate
    from opensim_tpu.planner.apply import Applier, Options

    driver = drive(tmp_path, SIZES["fits"], seed)
    applier = Applier(Options(simon_config=driver.simon_config))
    result = simulate(applier.load_cluster(), applier.load_apps())
    assert result.engine.name == "xla" and not result.unscheduled_pods
    cluster = driver.inputs["variants"]["short"]["cluster"]
    ref = R.Reference(cluster)
    _placed, left = ref.free_run()
    assert not left
    placed = {f"{p.metadata.namespace}/{p.metadata.name}": ns.node.metadata.name
              for ns in result.node_status for p in ns.pods}
    want = {w.name: ref.order()[w.name][0] for w in cluster.workloads}
    assert {name: placed[name] for name in want} == want


# ---------------------------------------------------------------------------
# what the path reports: encode.gpushare, gpu_devices, decode.gpu, report.gpu, the counter
# ---------------------------------------------------------------------------


def traced_plan(driver):
    from opensim_tpu.planner.apply import Applier, Options

    opts = Options(simon_config=driver.simon_config, output_file=os.path.join(driver.ctx.scratch, "report.txt"),
                   report_pods=True, max_new_nodes=driver.inputs["max_new_nodes"], extended_resources=["gpu"])
    tr = tracing.start_trace("apply", force=True)
    with tracing.trace_scope(tr):
        assert Applier(opts).run() == 0
    tr.finish()
    return tr


def find(tr, name):
    return [sp for sp in tr.walk() if sp.name == name]


@pytest.mark.parametrize("name", ["xla", "megakernel"])
def test_the_path_names_its_devices_its_templates_and_the_pods_it_placed(tmp_path, monkeypatch, name):
    named, _ = engine(monkeypatch, name)
    RECORDER.reset()
    driver = drive(tmp_path, CONFIG["tiny"], 7)
    cluster = driver.inputs["variants"]["short"]["cluster"]
    tr = traced_plan(driver)
    rungs = find(tr, "engine." + named)
    assert rungs and all(r.attrs["features"] == "gpu" for r in rungs)
    (encode,) = find(tr, "encode")
    halves = [sp for sp in find(tr, "encode.gpushare") if sp in encode.children]
    nodes_half = next(sp for sp in halves if "devices" in sp.attrs)
    templates_half = next(sp for sp in halves if "gpu_templates" in sp.attrs)
    assert nodes_half.attrs["gpu_nodes"] == sum(1 for nd in cluster.nodes if nd.gpus)
    assert nodes_half.attrs["devices"] == sum(nd.gpus for nd in cluster.nodes) == 84
    shapes = {(w.cpu_m, w.mem_bytes, w.gpu_mem, w.gpu_count) for w in cluster.workloads}
    assert templates_half.attrs["templates"] == len(shapes) == driver.inputs["shapes"]
    assert templates_half.attrs["gpu_templates"] == sum(1 for s in shapes if s[2])
    # the candidate nodes are appended under their own span, 8 devices each
    appended = [sp for sp in find(tr, "encode.gpushare") if sp.attrs.get("nodes") == driver.inputs["max_new_nodes"]]
    assert appended and appended[0].attrs["devices"] == 8 * driver.inputs["max_new_nodes"]
    decodes = find(tr, "decode.gpu")
    assert len(decodes) == 2 and all(any(sp in d.children for d in find(tr, "decode")) for sp in decodes)
    with_gpu = sum(1 for w in cluster.workloads if w.gpu_count)
    assert decodes[-1].attrs["pods"] == with_gpu
    (report,) = find(tr, "report")
    (nodes,) = find(tr, "report.nodes")
    (table,) = find(tr, "report.gpu")
    assert nodes in report.children and table in nodes.children
    if named == "megakernel":
        launches = find(tr, "mk.launch")
        assert launches and all(sp.attrs["gpu_devices"] == 8 and sp.attrs["templates"] == len(shapes) for sp in launches)
        assert find(tr, "engine.megakernel")[-1].attrs["masked"] is True
    # the counter: the final pass placed every task that asked a GPU, by kind
    kinds = {"fraction": sum(1 for w in cluster.workloads if w.gpu_count == 1 and w.gpu_mem < 1000 * MI),
             "whole": sum(1 for w in cluster.workloads if w.gpu_count == 1 and w.gpu_mem == 1000 * MI),
             "multi": sum(1 for w in cluster.workloads if w.gpu_count > 1)}
    lines = [l for l in RECORDER.render_lines() if l.startswith("simon_gpushare_pods_total{")]
    for kind, n in kinds.items():
        series = [l for l in lines if f'kind="{kind}"' in l]
        assert series and sum(int(float(l.rsplit(" ", 1)[1])) for l in series) >= n, (kind, lines)
    assert any(f'engine="{named}"' in l for l in lines)
    RECORDER.reset()


def test_a_stream_without_gpu_requests_opens_no_gpu_decode_and_counts_nothing(monkeypatch):
    from opensim_tpu.engine.simulator import AppResource, simulate
    from opensim_tpu.models import ResourceTypes, fixtures as fx
    rt = ResourceTypes()
    for i in range(3):
        rt.nodes.append(fx.make_fake_node(f"n{i}", "16", "64Gi", "110"))
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("web", 4, "100m", "128Mi"))
    RECORDER.reset()
    tr = tracing.start_trace("test", force=True)
    with tracing.trace_scope(tr):
        result = simulate(rt, [AppResource("web", app)])
    tr.finish()
    assert not result.unscheduled_pods and not find(tr, "decode.gpu")
    assert "gpu" not in find(tr, "engine.xla")[0].attrs["features"]
    assert not [l for l in RECORDER.render_lines() if l.startswith("simon_gpushare_pods_total{")]
    RECORDER.reset()
