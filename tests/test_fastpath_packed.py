"""A sweep packs eight scenarios into the eight sublanes of each kernel step
(ISSUE 38): `fastpath.sweep` packed eight a step answers as unpacked,
bit for bit, in every signature of the kernel; a sweep packs where it has
two or more scenarios and the packed VMEM estimate fits, and says so on its
spans and on `/metrics`. The kernel runs in the Pallas interpreter here;
`OPENSIM_TEST_BACKEND=tpu` compiles it. Tier-1, small shapes."""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from opensim_tpu.engine import fastpath
from opensim_tpu.engine.scheduler import pad_pod_stream, schedule_pods
from opensim_tpu.engine.simulator import AppResource, prepare
from opensim_tpu.models import ResourceTypes, fixtures as fx
from opensim_tpu.obs import trace as tracing
from opensim_tpu.obs.metrics import RECORDER
from opensim_tpu.ops.pallas_scan import CHUNK

_INTERPRET = os.environ.get("OPENSIM_TEST_BACKEND") != "tpu"
ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
GPU = {"alibabacloud.com/gpu-mem": "32Gi", "alibabacloud.com/gpu-count": "4"}


@pytest.fixture(autouse=True)
def _kernel_on(monkeypatch):
    monkeypatch.delenv("OPENSIM_DISABLE_FASTPATH", raising=False)
    if _INTERPRET:  # the interpreter is asked for by name, never inferred
        monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")


def _gpu_pod(name, mem, count):
    share = fx.with_annotations({"alibabacloud.com/gpu-mem": mem, "alibabacloud.com/gpu-count": count})
    return fx.make_fake_pod(name, "1", "1Gi", share)


def _plain_spread():
    """Equal nodes, so scores tie on many of them; a soft and a hard zone
    spread; two pods bound to nodes beforehand (forced)."""
    cluster = ResourceTypes()
    for i in range(12):
        cluster.nodes.append(fx.make_fake_node(f"n{i:02d}", "8", "16Gi", "110", fx.with_labels({ZONE: f"z{i % 3}"})))
    cluster.pods.append(fx.make_fake_pod("bound-a", "1", "1Gi", fx.with_node_name("n03")))
    cluster.pods.append(fx.make_fake_pod("bound-b", "2", "2Gi", fx.with_node_name("n07")))
    app = ResourceTypes()
    soft = fx.with_topology_spread([{"maxSkew": 1, "topologyKey": ZONE, "whenUnsatisfiable": "ScheduleAnyway",
                                     "labelSelector": {"matchLabels": {"app": "soft"}}}])
    hard = fx.with_topology_spread([{"maxSkew": 2, "topologyKey": ZONE, "whenUnsatisfiable": "DoNotSchedule",
                                     "labelSelector": {"matchLabels": {"app": "hard"}}}])
    app.deployments.append(fx.make_fake_deployment("web", 24, "500m", "1Gi"))
    app.deployments.append(fx.make_fake_deployment("soft", 12, "700m", "512Mi", soft))
    app.deployments.append(fx.make_fake_deployment("hard", 9, "1", "2Gi", hard))
    return cluster, app


def _ports():
    cluster = ResourceTypes()
    for i in range(8):
        cluster.nodes.append(fx.make_fake_node(f"n{i}", "16", "32Gi", "110"))
    app = ResourceTypes()
    app.pods += [fx.make_fake_pod(f"gw{k}", "500m", "1Gi", fx.with_host_ports([8080])) for k in range(9)]
    app.deployments.append(fx.make_fake_deployment("web", 10, "250m", "512Mi"))
    return cluster, app


def _gpu(shapes):
    cluster = ResourceTypes()
    for i in range(6):
        cluster.nodes.append(fx.make_fake_node(f"g{i}", "64", "128Gi", "110", fx.with_allocatable(GPU)))
    app = ResourceTypes()
    for j, (mem, count, n) in enumerate(shapes):
        app.pods += [_gpu_pod(f"gpu-{j}-{k}", mem, count) for k in range(n)]
    return cluster, app


def _local():
    cluster = ResourceTypes()
    for i in range(4):
        cluster.nodes.append(fx.make_fake_node(
            f"s{i}", "32", "64Gi", "110",
            fx.with_node_local_storage(
                vgs=[{"name": "pool0", "capacity": 100 * 1024**3}, {"name": "pool1", "capacity": 50 * 1024**3}],
                devices=[{"device": "/dev/vdb", "capacity": 80 * 1024**3, "mediaType": "ssd"},
                         {"device": "/dev/vdc", "capacity": 120 * 1024**3, "mediaType": "hdd"}],
            ),
        ))
    app = ResourceTypes()
    for name, n, cls, size in (("db", 6, "open-local-lvm", "30Gi"), ("disk", 3, "open-local-device-hdd", "100Gi")):
        sts = fx.make_fake_stateful_set(name, n, "500m", "1Gi")
        sts.volume_claim_templates = [{"metadata": {"name": "d"}, "spec": {
            "storageClassName": cls, "resources": {"requests": {"storage": size}}}}]
        app.stateful_sets.append(sts)
    return cluster, app


def _claims(*claims):
    return [{"metadata": {"name": f"v{j}"}, "spec": {"storageClassName": cls, "resources": {"requests": {"storage": size}}}}
            for j, (cls, size) in enumerate(claims)]


def _local_claims():
    """The open-local block's real rows and claim branches: a VG on three
    nodes and none on the others, at most three devices a node of mixed
    media (both tables padded to eight rows from neither one nor eight), a
    node with no storage; pods with no claim, an LVM claim, one ssd device
    claim and two hdd device claims in turn, and last an ssd claim that only
    an hdd device is large enough for."""
    ssd = lambda cap: {"capacity": cap * 1024**3, "mediaType": "ssd"}
    hdd = lambda cap: {"capacity": cap * 1024**3, "mediaType": "hdd"}
    shapes = [([100], [ssd(80), hdd(200), hdd(150)])] * 3 + [([], [hdd(200), hdd(120), ssd(40)])] * 2 + [([], [])]
    cluster = ResourceTypes()
    for i, (vgs, devs) in enumerate(shapes):
        storage = fx.with_node_local_storage(
            vgs=[{"name": "pool0", "capacity": cap * 1024**3} for cap in vgs],
            devices=[dict(dev, device=f"/dev/vd{'bcd'[j]}") for j, dev in enumerate(devs)])
        cluster.nodes.append(fx.make_fake_node(f"l{i}", "16", "32Gi", "110", storage))
    app = ResourceTypes()
    kinds = (("web", 2, []), ("lvm", 2, [("open-local-lvm", "45Gi")]), ("ssd", 1, [("open-local-device-ssd", "50Gi")]),
             ("hdd", 1, [("open-local-device-hdd", "100Gi"), ("open-local-device-hdd", "150Gi")]))
    for k in range(3):
        for name, n, claims in kinds:
            sts = fx.make_fake_stateful_set(f"{name}{k}", n, "500m", "1Gi")
            sts.volume_claim_templates = _claims(*claims)
            app.stateful_sets.append(sts)
    sts = fx.make_fake_stateful_set("cross", 1, "500m", "1Gi")
    sts.volume_claim_templates = _claims(("open-local-device-ssd", "180Gi"))
    app.stateful_sets.append(sts)
    return cluster, app


def _interpod():
    cluster = ResourceTypes()
    for i in range(10):
        labels = {} if i % 4 == 3 else {ZONE: f"z{i % 3}"}
        cluster.nodes.append(fx.make_fake_node(f"n{i:02d}", "16", "32Gi", "110", fx.with_labels(labels)))
    app = ResourceTypes()
    app.pods.append(fx.make_fake_pod("anchor", "100m", "128Mi", fx.with_labels({"role": "anchor"})))
    follow = fx.with_affinity({"podAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": [
        {"labelSelector": {"matchLabels": {"role": "anchor"}}, "topologyKey": ZONE}]}})
    lonely = fx.with_affinity({"podAntiAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": [
        {"labelSelector": {"matchLabels": {"app": "lonely"}}, "topologyKey": HOST}]}})
    near = fx.with_affinity({"podAffinity": {"preferredDuringSchedulingIgnoredDuringExecution": [
        {"weight": 40, "podAffinityTerm": {"labelSelector": {"matchLabels": {"app": "followers"}}, "topologyKey": HOST}}]}})
    app.deployments.append(fx.make_fake_deployment("followers", 6, "200m", "256Mi", follow))
    app.deployments.append(fx.make_fake_deployment("lonely", 7, "200m", "256Mi", lonely))
    app.deployments.append(fx.make_fake_deployment("near", 5, "300m", "256Mi", near))
    return cluster, app


def _big_u():
    cluster = ResourceTypes()
    for i in range(6):
        cluster.nodes.append(fx.make_fake_node(f"n{i}", "8", "16Gi", "110", fx.with_labels({ZONE: f"z{i % 2}"})))
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("web", 8, "2", "2Gi"))
    app.pods += [fx.make_fake_pod(f"u{i:03d}", f"{50 + i}m", "64Mi") for i in range(40)]
    return cluster, app


SIGNATURES = {
    "plain_spread": _plain_spread,
    "ports": _ports,
    "gpu_fraction": lambda: _gpu([("4Gi", "1", 10), ("10Gi", "1", 8), ("20Gi", "1", 4)]),
    "gpu_whole_multi": lambda: _gpu([("32Gi", "1", 5), ("6Gi", "2", 4), ("8Gi", "3", 3), ("32Gi", "2", 2)]),
    "local": _local,
    "local_claims": _local_claims,
    "interpod": _interpod,
    "big_u": _big_u,  # the template tables in HBM, one DMA a step for the whole block
}
CASES = [("plain_spread", S) for S in (2, 8, 9, 15, 17)] + [
    ("ports", 9), ("gpu_fraction", 15), ("gpu_whole_multi", 9), ("local", 17), ("local_claims", 9),
    ("interpod", 2), ("interpod", 9), ("big_u", 8), ("big_u", 15),
]


def _prep(kind):
    cluster, app = SIGNATURES[kind]()
    return prepare(cluster, [AppResource("a", app)], node_pad=128)


def _masks(prep, S, seed=0):
    """Per-scenario node sets that start at different nodes (so the lowest
    tied index differs from sublane to sublane), pods left out at random,
    and bound pods released at random (a drain's shape)."""
    rng = np.random.RandomState(seed + S)
    n_real = int(np.asarray(prep.ec_np.node_valid).sum())
    N, P = int(np.asarray(prep.ec_np.node_valid).shape[0]), len(prep.ordered)
    nodes = np.zeros((S, N), bool)
    for s in range(S):
        lo = s % 3
        nodes[s, lo:lo + 2 + (s * 5) % (n_real - lo - 1)] = True
    pods = rng.rand(S, P) > 0.15
    forced = np.broadcast_to(prep.forced, (S, P)) & (rng.rand(S, P) > 0.3)
    return nodes, pods, forced


def _sweep(prep, masks, big_u=False):
    return fastpath.sweep(prep, *masks, interpret=_INTERPRET, big_u=True if big_u else None)


@pytest.mark.parametrize("kind,S", CASES, ids=[f"{k}-S{S}" for k, S in CASES])
def test_a_packed_sweep_answers_as_the_unpacked_one_bit_for_bit(monkeypatch, kind, S):
    prep = _prep(kind)
    assert fastpath.applicable(prep)
    big_u = kind == "big_u"
    assert prep.features.gpu == kind.startswith("gpu") and prep.features.local == kind.startswith("local")
    masks = _masks(prep, S)
    monkeypatch.setattr(fastpath, "sweep_sublanes", lambda prep, S: 1)
    one = _sweep(prep, masks, big_u)
    monkeypatch.setattr(fastpath, "sweep_sublanes", lambda prep, S: 8)
    eight = _sweep(prep, masks, big_u)
    for name, a, b in zip(("unscheduled", "used", "chosen", "vg_used"), one, eight):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_array_equal(b, a, err_msg=name)
    chosen = np.asarray(one[2])
    assert (chosen >= 0).any()
    if kind == "plain_spread":
        # scenarios differ, and where two place a pod on different nodes the
        # lowest index among equal scores was taken in each sublane alone
        assert len({tuple(row) for row in chosen}) > 1


def _kernel_states(prep, masks, sublanes):
    """Every scenario of `masks` on the kernel, `sublanes` a step: chosen
    [S, P], used [S, N, R], VG free [S, N, Vg] and device free [S, N, Dv]."""
    nodes, pods, forced = masks
    S, P = pods.shape
    fi, _meta = fastpath.build_inputs(prep)
    fi, _nv = fastpath._scenario_rows(prep, fi, nodes)
    pad = (-P) % CHUNK
    tmpl = np.concatenate([np.asarray(prep.tmpl_ids), np.zeros(pad, np.int32)])
    stream = lambda m: np.concatenate([m, np.zeros((S, pad), bool)], axis=1)
    chosen, used, _gt, _gf, vg, dev = fastpath._launch(
        prep, fi, tmpl, stream(pods), stream(forced), _INTERPRET, False, sublanes)
    Vg, Dv = prep.st0.vg_free.shape[1], prep.st0.dev_free.shape[1]
    return (np.asarray(chosen)[:, :P], np.asarray(used).transpose(0, 2, 1),
            np.asarray(vg)[:, :Vg].transpose(0, 2, 1), np.asarray(dev)[:, :Dv].transpose(0, 2, 1))


def _xla_states(prep, masks):
    """The same scenarios on the XLA scan, one at a time."""
    nodes, pods, forced = masks
    P = pods.shape[1]
    outs = []
    for nv, pv, fm in zip(nodes, pods, forced):
        out = schedule_pods(prep.ec._replace(node_valid=jnp.asarray(nv)), prep.st0,
                            *pad_pod_stream(np.asarray(prep.tmpl_ids), pv, fm), features=prep.features)
        st = out.final_state
        outs.append((np.asarray(out.chosen)[:P], np.asarray(st.used), np.asarray(st.vg_free), np.asarray(st.dev_free)))
    return [np.stack(col) for col in zip(*outs)]


@pytest.mark.parametrize("sublanes", [1, 8])
@pytest.mark.parametrize("kind", ["local", "local_claims"])
def test_the_local_block_answers_as_the_xla_scan_bit_for_bit(kind, sublanes):
    """The kernel's open-local block walks a node's real VG and device rows
    only, and runs each part only for a claim the pod has: placements,
    usage, unscheduled pods and the VG and device state are the XLA scan's,
    bit for bit, one scenario a step and eight."""
    prep = _prep(kind)
    nodes, pods, forced = masks = _masks(prep, 8)
    nodes[0] = np.asarray(prep.ec_np.node_valid)  # and the whole cluster
    kernel, xla = _kernel_states(prep, masks, sublanes), _xla_states(prep, masks)
    for name, a, b in zip(("chosen", "used", "vg_free", "dev_free"), kernel, xla):
        assert a.shape == b.shape, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    unscheduled = lambda chosen: ((chosen < 0) & pods).sum(axis=1)
    np.testing.assert_array_equal(unscheduled(kernel[0]), unscheduled(xla[0]))
    assert (kernel[0] >= 0).any() and (kernel[3] != np.asarray(prep.st0.dev_free)).any()
    if kind == "local_claims":
        # the ssd claim only an hdd device could hold stays unplaced
        assert (kernel[0][:, -1] == -1).all()


def _launch_attrs(fn):
    tr = tracing.start_trace("test", force=True)
    with tracing.trace_scope(tr):
        fn()
    tr.finish()
    keys = ("scenarios", "sublanes", "blocks", "pad_scenarios")
    return [(sp.name, tuple(sp.attrs[k] for k in keys if k in sp.attrs))
            for sp in tr.walk() if sp.name in ("mk.inputs", "mk.launch")]


def test_a_sweep_packs_eight_a_block_and_counts_its_blocks():
    prep = _prep("plain_spread")
    RECORDER.reset()
    try:
        assert fastpath.sweep_sublanes(prep, 9) == 8 and fastpath.sweep_sublanes(prep, 1) == 1
        attrs = _launch_attrs(lambda: _sweep(prep, _masks(prep, 9)))
        assert attrs == [("mk.inputs", (9, 8, 2, 7)), ("mk.launch", (9, 8, 2, 7))]
        _sweep(prep, _masks(prep, 17))
        _sweep(prep, _masks(prep, 1))
        lines = [l for l in RECORDER.render_lines() if l.startswith("simon_megakernel_sweep_blocks_total{")]
        assert sorted(lines) == [
            'simon_megakernel_sweep_blocks_total{sublanes="1"} 1',  # one scenario: nothing to pack
            'simon_megakernel_sweep_blocks_total{sublanes="8"} 5',  # ceil(9/8) + ceil(17/8)
        ]
    finally:
        RECORDER.reset()


def test_a_local_launch_says_its_real_rows_and_its_claim_steps():
    """`mk.launch` of the open-local variant carries the VG and device rows
    its loops walk and the steps whose template has a claim; a launch of
    another variant carries none of the three."""
    prep = _prep("local_claims")
    tr = tracing.start_trace("test", force=True)
    with tracing.trace_scope(tr):
        _sweep(prep, _masks(prep, 9))
    tr.finish()
    (launch,) = [sp for sp in tr.walk() if sp.name == "mk.launch"]
    ec = prep.ec_np
    claims = (np.asarray(ec.lvm_req) > 0) | (np.asarray(ec.dev_req) > 0).any(axis=1)
    assert not claims[0]  # the stream's padding repeats template 0, which has none
    assert int(claims[np.asarray(prep.tmpl_ids)].sum()) == 13  # six LVM, three ssd, three two-hdd, one cross
    assert (launch.attrs["local_vgs"], launch.attrs["local_devices"], launch.attrs["claim_steps"]) == (1, 3, 13)
    tr = tracing.start_trace("test", force=True)
    with tracing.trace_scope(tr):
        _sweep(_prep("plain_spread"), _masks(_prep("plain_spread"), 2))
    tr.finish()
    (launch,) = [sp for sp in tr.walk() if sp.name == "mk.launch"]
    assert not {"local_vgs", "local_devices", "claim_steps"} & set(launch.attrs)


def test_a_schedule_is_one_block_of_one_scenario():
    prep = _prep("plain_spread")
    P = len(prep.ordered)
    attrs = _launch_attrs(lambda: fastpath.schedule(prep, prep.tmpl_ids, np.ones(P, bool), prep.forced,
                                                    interpret=_INTERPRET))
    assert attrs == [("mk.inputs", (1, 1, 0)), ("mk.launch", (1, 1, 1, 0))]


def test_a_sweep_whose_packed_rows_do_not_fit_runs_unpacked_with_the_same_answer(monkeypatch):
    prep = _prep("interpod")
    masks = _masks(prep, 9)
    packed = _sweep(prep, masks)
    one, eight = fastpath.vmem_estimate(prep, 1), fastpath.vmem_estimate(prep, 8)
    assert one < eight < 8 * one  # the shared tables are held once
    monkeypatch.setattr(fastpath, "VMEM_LIMIT_BYTES", 2 * eight - 1)
    assert fastpath.sweep_sublanes(prep, 9) == 1
    attrs = _launch_attrs(lambda: _sweep(prep, masks))
    assert attrs == [("mk.inputs", (9, 1, 9, 0)), ("mk.launch", (9, 1, 9, 0))]
    for name, a, b in zip(("unscheduled", "used", "chosen", "vg_used"), packed, _sweep(prep, masks)):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a), err_msg=name)
