"""What an open-local plan reports (`k8s-5k-50k-openlocal` at its rehearsal
size, `simon apply -e open-local`): `encode.local` round the node storage
tensors and round the template claims, under `encode` and again under
`prep.delta_nodes` for the candidate nodes; `decode.local` round the
write-back of each VG's and device's state; and the claims bound, by kind and
by the rung that answered, on `simon_local_volumes_total`."""

import importlib
import json
import os

import pytest

from benchmarks.drivers import Context
from opensim_tpu.obs import trace as tracing
from opensim_tpu.obs.metrics import RECORDER

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "benchmarks", "configs", "k8s-5k-50k-openlocal.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(REPO, "benchmarks", "traffic", "short-local.json")) as f:
    TRAFFIC = json.load(f)


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.setenv("OPENSIM_DISABLE_NATIVE", "1")
    RECORDER.reset()
    yield
    RECORDER.reset()


def find(tr, name):
    return [sp for sp in tr.walk() if sp.name == name]


def volumes():
    """simon_local_volumes_total as (engine, kind) -> claims."""
    out = {}
    for line in RECORDER.render_lines():
        if line.startswith("simon_local_volumes_total{"):
            labels, value = line.rsplit(" ", 1)
            pairs = dict(p.split("=") for p in labels[len("simon_local_volumes_total{"):-1].split(","))
            out[(pairs["engine"].strip('"'), pairs["kind"].strip('"'))] = int(float(value))
    return out


@pytest.fixture(scope="module")
def plan(tmp_path_factory):
    """One traced plan on the XLA scan, and the generator's plain description."""
    from opensim_tpu.planner.apply import Applier, Options

    mp = pytest.MonkeyPatch()
    mp.setenv("OPENSIM_DISABLE_NATIVE", "1")
    RECORDER.reset()
    scratch = str(tmp_path_factory.mktemp("openlocal"))
    ctx = Context(config=CONFIG, traffic=TRAFFIC, seed=7, scratch=scratch, rehearse=True, sizes=CONFIG["tiny"])
    driver = importlib.import_module("benchmarks.drivers.plan_loop_local").Driver(ctx)
    driver.prepare()
    opts = Options(simon_config=driver.simon_config, output_file=os.path.join(scratch, "report.txt"),
                   report_pods=True, max_new_nodes=driver.inputs["max_new_nodes"], extended_resources=["open-local"])
    tr = tracing.start_trace("apply", force=True)
    with tracing.trace_scope(tr):
        assert Applier(opts).run() == 0
    tr.finish()
    counted = volumes()
    mp.undo()
    return tr, driver, counted


def test_encode_local_names_the_nodes_storage_and_the_templates_claims(plan):
    tr, driver, _ = plan
    cluster = driver.inputs["variants"]["short"]["cluster"]
    (encode,) = find(tr, "encode")
    halves = [sp for sp in find(tr, "encode.local") if sp in encode.children]
    nodes_half = next(sp for sp in halves if "devices" in sp.attrs)
    templates_half = next(sp for sp in halves if "device_claims" in sp.attrs)
    assert nodes_half.attrs["nodes"] == len(cluster.nodes) == CONFIG["tiny"]["short_nodes"]
    assert nodes_half.attrs["vg_nodes"] == nodes_half.attrs["vgs"] == sum(1 for nd in cluster.nodes if nd.vgs)
    assert nodes_half.attrs["devices"] == sum(len(nd.devices) for nd in cluster.nodes)
    assert templates_half.attrs["templates"] == len(cluster.workloads)
    assert templates_half.attrs["lvm_templates"] == sum(1 for w in cluster.workloads if w.lvm) == 5
    assert templates_half.attrs["device_claims"] == sum(len(w.devices) for w in cluster.workloads) == 4
    # the candidate nodes are appended under their own span: ssd nodes, a VG and two devices each
    (delta,) = find(tr, "prep.delta_nodes")
    appended = [sp for sp in delta.children if sp.name == "encode.local"]
    k = driver.inputs["max_new_nodes"]
    assert appended and appended[0].attrs == {"nodes": k, "vg_nodes": k, "vgs": k, "devices": 2 * k}


def test_decode_local_writes_back_every_node_with_storage_and_the_claims_are_counted(plan):
    tr, driver, counted = plan
    cluster = driver.inputs["variants"]["short"]["cluster"]
    rungs = find(tr, "engine.xla")
    assert rungs and all("local" in r.attrs["features"].split("+") for r in rungs)
    decodes = find(tr, "decode.local")
    assert decodes and all(any(sp in d.children for d in find(tr, "decode")) for sp in decodes)
    # the last is the final pass's, over the cluster and the nodes it adds
    with open(os.path.join(driver.ctx.scratch, "report.txt")) as f:
        added = int(next(line for line in f if line.startswith("(added "))[len("(added "):].split()[0])
    assert added > 0 and decodes[-1].attrs["nodes"] == len(cluster.nodes) + added
    # every pod is placed in the final pass: its claims are counted at least once each, by kind
    want = {"lvm": sum(w.replicas * len(w.lvm) for w in cluster.workloads)}
    for media in ("ssd", "hdd"):
        want[media] = sum(w.replicas * sum(1 for _s, m in w.devices if m == media) for w in cluster.workloads)
    assert set(counted) == {("xla", kind) for kind in want}
    assert all(counted[("xla", kind)] >= n > 0 for kind, n in want.items())


def test_a_cluster_without_storage_opens_no_local_decode_and_counts_nothing():
    from opensim_tpu.engine.simulator import AppResource, simulate
    from opensim_tpu.models import ResourceTypes, fixtures as fx

    rt = ResourceTypes()
    for i in range(3):
        rt.nodes.append(fx.make_fake_node(f"n{i}", "16", "64Gi", "110"))
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("web", 4, "100m", "128Mi"))
    tr = tracing.start_trace("test", force=True)
    with tracing.trace_scope(tr):
        result = simulate(rt, [AppResource("web", app)])
    tr.finish()
    assert not result.unscheduled_pods and not find(tr, "decode.local")
    assert "local" not in find(tr, "engine.xla")[0].attrs["features"].split("+")
    assert [sp.attrs["vg_nodes"] for sp in find(tr, "encode.local") if "vg_nodes" in sp.attrs] == [0]
    assert not volumes()


def test_storage_with_no_claim_is_written_back_and_counts_nothing():
    """A node with a VG and a device and pods that claim neither: the
    annotation is written back as it was, `decode.local` names the node, and
    no claim is counted."""
    from opensim_tpu.engine.simulator import AppResource, simulate
    from opensim_tpu.models import ResourceTypes, fixtures as fx
    from opensim_tpu.models.objects import ANNO_NODE_LOCAL_STORAGE

    rt = ResourceTypes()
    rt.nodes.append(fx.make_fake_node("disk", "16", "64Gi", "110", fx.with_node_local_storage(
        vgs=[{"name": "pool", "capacity": str(100 << 30)}],
        devices=[{"device": "/dev/sdb", "capacity": str(50 << 30), "mediaType": "hdd"}])))
    rt.nodes.append(fx.make_fake_node("plain", "16", "64Gi", "110"))
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("web", 2, "100m", "128Mi"))
    tr = tracing.start_trace("test", force=True)
    with tracing.trace_scope(tr):
        result = simulate(rt, [AppResource("web", app)])
    tr.finish()
    assert [sp.attrs for sp in find(tr, "decode.local")] == [{"nodes": 1}]
    status = {s.node.metadata.name: s for s in result.node_status}
    storage = json.loads(status["disk"].node.metadata.annotations[ANNO_NODE_LOCAL_STORAGE])
    assert storage["vgs"] == [{"name": "pool", "capacity": 100 << 30, "requested": 0}]
    assert [d["isAllocated"] for d in storage["devices"]] == [False]
    assert ANNO_NODE_LOCAL_STORAGE not in status["plain"].node.metadata.annotations
    assert not volumes()


def test_the_local_rows_leave_the_packed_sweep_eight_sublanes_at_the_cells_width(tmp_path, monkeypatch):
    """`fastpath.vmem_estimate` at plan-local's sweep widths (4,600 nodes and
    128 candidates, 4,736 padded; one VG and four devices a node, padded to
    eight rows each): the local rows fit the packed kernel, eight scenarios a
    step, and the single-scenario kernel's budget."""
    import numpy as np
    from types import SimpleNamespace

    from opensim_tpu.engine import fastpath
    from opensim_tpu.engine.simulator import prepare
    from opensim_tpu.planner.apply import Applier, Options

    ctx = Context(config=CONFIG, traffic=TRAFFIC, seed=5, scratch=str(tmp_path), rehearse=True, sizes=CONFIG["tiny"])
    driver = importlib.import_module("benchmarks.drivers.plan_loop_local").Driver(ctx)
    driver.prepare()
    applier = Applier(Options(simon_config=driver.simon_config))
    prep = prepare(applier.load_cluster(), applier.load_apps())
    ec = prep.ec_np
    n, wide = ec.node_valid.shape[0], 4736
    grow = {f: np.resize(a, (wide,) + a.shape[1:]) for f, a in ec._asdict().items()
            if np.ndim(a) and a.shape[0] == n}
    wide_prep = SimpleNamespace(features=prep.features, ec_np=ec._replace(**grow), ec=None, meta=prep.meta)
    assert prep.features.local and ec.node_vg_cap.shape[1] == 1 and ec.node_dev_cap.shape[1] == 4
    assert ec.dev_req_sizes.shape[2] == 2
    # the single-scenario kernel's budget, and twice the packed rows under the compile's limit
    assert fastpath.vmem_estimate(wide_prep) <= fastpath._VMEM_BUDGET
    assert 2 * fastpath.vmem_estimate(wide_prep, 8) <= fastpath.VMEM_LIMIT_BYTES
    assert fastpath.sweep_sublanes(wide_prep, 31) == fastpath.SWEEP_SUBLANES == 8
