"""The Pallas megakernel must produce IDENTICAL placements to the XLA scan
on its supported feature subset. Runs in interpret mode on CPU;
OPENSIM_TEST_BACKEND=tpu compiles the kernel through Mosaic for real."""

import os

import numpy as np
import pytest

from opensim_tpu.engine import fastpath
from opensim_tpu.engine.scheduler import pad_pod_stream, schedule_pods
from opensim_tpu.engine.simulator import AppResource, prepare

pytestmark = pytest.mark.slow  # nightly tier: full megakernel-vs-XLA parity matrix (README: test tiering)
from opensim_tpu.models import ResourceTypes, fixtures as fx

_INTERPRET = os.environ.get("OPENSIM_TEST_BACKEND") != "tpu"


@pytest.fixture(autouse=True)
def _enable_interpret_fastpath(monkeypatch):
    """applicable() requires a TPU backend unless interpret mode is forced
    (the rest of the suite intentionally exercises the XLA path on CPU)."""
    if _INTERPRET:  # the chip run compiles; the interpreter is asked for by name
        monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")


def _prep(n_nodes=16, with_spread=True, with_zone=True, replicas=64):
    cluster = ResourceTypes()
    for i in range(n_nodes):
        labels = {}
        if with_zone and i % 4 != 3:  # some nodes lack the zone label
            labels["topology.kubernetes.io/zone"] = f"z{i % 3}"
        cluster.nodes.append(
            fx.make_fake_node(f"n{i:03d}", "16", "32Gi", "110", fx.with_labels(labels))
        )
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("plain", replicas, "500m", "1Gi"))
    app.deployments.append(fx.make_fake_deployment("tiny", replicas // 2, "100m", "128Mi"))
    if with_spread:
        app.deployments.append(
            fx.make_fake_deployment(
                "spread",
                replicas // 2,
                "250m",
                "512Mi",
                fx.with_topology_spread(
                    [
                        {
                            "maxSkew": 2,
                            "topologyKey": "kubernetes.io/hostname",
                            "whenUnsatisfiable": "DoNotSchedule",
                            "labelSelector": {"matchLabels": {"app": "spread"}},
                        },
                        {
                            "maxSkew": 3,
                            "topologyKey": "topology.kubernetes.io/zone",
                            "whenUnsatisfiable": "ScheduleAnyway",
                            "labelSelector": {"matchLabels": {"app": "spread"}},
                        },
                    ]
                ),
            )
        )
    # overload so some pods genuinely fail
    app.deployments.append(fx.make_fake_deployment("fat", 8, "8", "16Gi"))
    prep = prepare(cluster, [AppResource("a", app)], node_pad=128)
    assert prep is not None
    return prep


def _xla_chosen(prep):
    P = len(prep.ordered)
    t, v, f = pad_pod_stream(prep.tmpl_ids, np.ones(P, bool), prep.forced)
    out = schedule_pods(prep.ec, prep.st0, t, v, f, features=prep.features)
    return np.asarray(out.chosen)[:P], np.asarray(out.final_state.used)


def test_fastpath_applicable():
    prep = _prep()
    assert fastpath.applicable(prep)


def test_fastpath_rejects_unsupported():
    from opensim_tpu.engine.schedconfig import DEFAULT_CONFIG

    cluster = ResourceTypes()
    cluster.nodes.append(fx.make_fake_node("n0"))
    app = ResourceTypes()
    app.pods.append(fx.make_fake_pod("p", "1", "1Gi"))

    # a scheduler config the kernel cannot compute (a disabled filter) stays on the XLA path;
    # its score weights are served
    prep = prepare(cluster, [AppResource("a", app)], node_pad=128)
    assert fastpath.applicable(prep)
    assert fastpath.applicable(prep, DEFAULT_CONFIG._replace(w_least=3.0))
    assert not fastpath.applicable(prep, DEFAULT_CONFIG._replace(f_taints=False))

    # two non-hostname topology keys are in scope; a third is not
    def spread_app(keys):
        rt = ResourceTypes()
        rt.pods.append(
            fx.make_fake_pod(
                "spread", "1", "1Gi",
                fx.with_topology_spread(
                    [
                        {"maxSkew": 1, "topologyKey": k, "whenUnsatisfiable": "ScheduleAnyway",
                         "labelSelector": {"matchLabels": {"x": "y"}}}
                        for k in keys
                    ]
                ),
            )
        )
        return rt

    prep2 = prepare(
        cluster,
        [AppResource("a", spread_app(["topology.kubernetes.io/zone", "topology.kubernetes.io/region"]))],
        node_pad=128,
    )
    assert fastpath.applicable(prep2)
    # up to four non-hostname keys are in scope; a fifth is not
    prep2b = prepare(
        cluster,
        [AppResource("a", spread_app([
            "topology.kubernetes.io/zone", "topology.kubernetes.io/region",
            "topology.rack", "topology.row",
        ]))],
        node_pad=128,
    )
    assert fastpath.applicable(prep2b)
    prep2c = prepare(
        cluster,
        [AppResource("a", spread_app([
            "topology.kubernetes.io/zone", "topology.kubernetes.io/region",
            "topology.rack", "topology.row", "topology.cell",
        ]))],
        node_pad=128,
    )
    assert not fastpath.applicable(prep2c)

    # non-128-multiple node padding is padded at marshalling time, not rejected
    prep3 = prepare(cluster, [AppResource("a", app)], node_pad=8)
    assert fastpath.applicable(prep3)


def test_fastpath_matches_xla_gpu():
    """GPU device packing through the megakernel must match the XLA scan:
    placements, device assignments (gpu_take), and final device state."""
    cluster = ResourceTypes()
    for i in range(6):
        cluster.nodes.append(
            fx.make_fake_node(
                f"g{i}", "64", "128Gi", "110",
                fx.with_allocatable({"alibabacloud.com/gpu-mem": "32Gi", "alibabacloud.com/gpu-count": "4"}),
            )
        )
    app = ResourceTypes()
    for j, (mem, cnt, n) in enumerate([("4Gi", "1", 10), ("10Gi", "1", 6), ("6Gi", "2", 4), ("8Gi", "3", 3)]):
        for k in range(n):
            app.pods.append(
                fx.make_fake_pod(
                    f"gpu-{j}-{k}", "1", "1Gi",
                    fx.with_annotations({"alibabacloud.com/gpu-mem": mem, "alibabacloud.com/gpu-count": cnt}),
                )
            )
    prep = prepare(cluster, [AppResource("a", app)], node_pad=128)
    assert prep.features.gpu
    assert fastpath.applicable(prep)
    P = len(prep.ordered)
    t, v, f = pad_pod_stream(prep.tmpl_ids, np.ones(P, bool), prep.forced)
    out = schedule_pods(prep.ec, prep.st0, t, v, f, features=prep.features)
    want_chosen = np.asarray(out.chosen)[:P]
    want_take = np.asarray(out.gpu_take)[:P]
    want_gpu = np.asarray(out.final_state.gpu_free)
    got_chosen, got_used, _sf, got_take, got_gpu, _vg, _dv = fastpath.schedule(
        prep, prep.tmpl_ids, np.ones(P, bool), prep.forced, interpret=_INTERPRET
    )
    np.testing.assert_array_equal(got_chosen, want_chosen)
    np.testing.assert_allclose(got_take, want_take, rtol=1e-6)
    np.testing.assert_allclose(got_gpu, want_gpu, rtol=1e-6)


def test_fastpath_matches_xla_ports_na_tt():
    """Host ports, preferred node affinity, and PreferNoSchedule scoring
    through the megakernel must match the XLA scan."""
    cluster = ResourceTypes()
    for i in range(6):
        opts = [fx.with_labels({"disk": "ssd" if i % 2 else "hdd"})]
        if i < 2:
            opts.append(fx.with_taints([{"key": "soft", "value": "x", "effect": "PreferNoSchedule"}]))
        cluster.nodes.append(fx.make_fake_node(f"n{i}", "16", "32Gi", "110", *opts))
    app = ResourceTypes()
    for k in range(5):
        app.pods.append(fx.make_fake_pod(f"web-{k}", "500m", "1Gi", fx.with_host_ports([8080])))
    app.deployments.append(
        fx.make_fake_deployment(
            "pref", 6, "250m", "512Mi",
            fx.with_affinity(
                {
                    "nodeAffinity": {
                        "preferredDuringSchedulingIgnoredDuringExecution": [
                            {"weight": 50, "preference": {"matchExpressions": [{"key": "disk", "operator": "In", "values": ["ssd"]}]}}
                        ]
                    }
                }
            ),
        )
    )
    prep = prepare(cluster, [AppResource("a", app)], node_pad=128)
    assert prep.features.ports and prep.features.pref_node_affinity and prep.features.prefer_taints
    assert fastpath.applicable(prep)
    P = len(prep.ordered)
    want_chosen, want_used = _xla_chosen(prep)
    got_chosen, got_used, *_rest = fastpath.schedule(
        prep, prep.tmpl_ids, np.ones(P, bool), prep.forced, interpret=_INTERPRET
    )
    np.testing.assert_array_equal(got_chosen, want_chosen)
    np.testing.assert_allclose(got_used, want_used, rtol=1e-5)


def test_fastpath_matches_xla_local_storage():
    """Open-local VG + exclusive-device packing through the megakernel must
    match the XLA scan: placements, VG free, and device occupancy."""
    cluster = ResourceTypes()
    for i in range(4):
        cluster.nodes.append(
            fx.make_fake_node(
                f"s{i}", "32", "64Gi", "110",
                fx.with_node_local_storage(
                    vgs=[
                        {"name": "pool0", "capacity": 100 * 1024**3},
                        {"name": "pool1", "capacity": 50 * 1024**3},
                    ],
                    devices=[
                        {"device": "/dev/vdb", "capacity": 80 * 1024**3, "mediaType": "ssd"},
                        {"device": "/dev/vdd", "capacity": 30 * 1024**3, "mediaType": "ssd"},
                        {"device": "/dev/vdc", "capacity": 120 * 1024**3, "mediaType": "hdd"},
                    ],
                ),
            )
        )
    app = ResourceTypes()
    sts = fx.make_fake_stateful_set("db", 6, "500m", "1Gi")
    sts.volume_claim_templates = [
        {"metadata": {"name": "data"}, "spec": {"storageClassName": "open-local-lvm", "resources": {"requests": {"storage": "30Gi"}}}},
    ]
    app.stateful_sets.append(sts)
    sts2 = fx.make_fake_stateful_set("disk", 3, "250m", "512Mi")
    sts2.volume_claim_templates = [
        {"metadata": {"name": "d"}, "spec": {"storageClassName": "open-local-device-hdd", "resources": {"requests": {"storage": "100Gi"}}}},
    ]
    app.stateful_sets.append(sts2)
    # mixed-size device volumes of one media: per-volume matching, not
    # count × max-size (common.go:290-349)
    sts3 = fx.make_fake_stateful_set("mixed", 2, "250m", "512Mi")
    sts3.volume_claim_templates = [
        {"metadata": {"name": "small"}, "spec": {"storageClassName": "open-local-device-ssd", "resources": {"requests": {"storage": "10Gi"}}}},
        {"metadata": {"name": "big"}, "spec": {"storageClassName": "open-local-device-ssd", "resources": {"requests": {"storage": "60Gi"}}}},
    ]
    app.stateful_sets.append(sts3)
    prep = prepare(cluster, [AppResource("a", app)], node_pad=128)
    assert prep.features.local
    assert fastpath.applicable(prep)
    P = len(prep.ordered)
    t, v, f = pad_pod_stream(prep.tmpl_ids, np.ones(P, bool), prep.forced)
    out = schedule_pods(prep.ec, prep.st0, t, v, f, features=prep.features)
    want_chosen = np.asarray(out.chosen)[:P]
    got_chosen, got_used, _sf, _gt, _gf, got_vg, got_dev = fastpath.schedule(
        prep, prep.tmpl_ids, np.ones(P, bool), prep.forced, interpret=_INTERPRET
    )
    np.testing.assert_array_equal(got_chosen, want_chosen)
    np.testing.assert_allclose(got_vg, np.asarray(out.final_state.vg_free), rtol=1e-6)
    np.testing.assert_allclose(got_dev, np.asarray(out.final_state.dev_free), rtol=1e-6)


@pytest.mark.parametrize("with_spread,with_zone", [(False, False), (True, True), (True, False)])
def test_fastpath_matches_xla(with_spread, with_zone):
    prep = _prep(with_spread=with_spread, with_zone=with_zone)
    assert fastpath.applicable(prep)
    P = len(prep.ordered)
    want_chosen, want_used = _xla_chosen(prep)
    got_chosen, got_used, *_rest = fastpath.schedule(
        prep, prep.tmpl_ids, np.ones(P, bool), prep.forced, interpret=_INTERPRET
    )
    mismatches = np.nonzero(want_chosen != got_chosen)[0]
    assert mismatches.size == 0, (
        f"{mismatches.size} placement mismatches, first at {mismatches[:5]}: "
        f"xla={want_chosen[mismatches[:5]]} pallas={got_chosen[mismatches[:5]]}"
    )
    np.testing.assert_allclose(got_used, want_used, rtol=1e-5)


def test_fastpath_matches_xla_interpod():
    """Inter-pod affinity / anti-affinity / preferred terms through the
    megakernel must match the XLA scan exactly."""
    cluster = ResourceTypes()
    for i in range(12):
        # every 4th node lacks the zone label: k8s gives label-less nodes no
        # topology contribution, and both paths must agree on that
        labels = {} if i % 4 == 3 else {"topology.kubernetes.io/zone": f"z{i % 3}"}
        cluster.nodes.append(fx.make_fake_node(f"n{i:02d}", "16", "32Gi", "110", fx.with_labels(labels)))
    app = ResourceTypes()
    app.pods.append(fx.make_fake_pod("anchor", "100m", "128Mi", fx.with_labels({"role": "anchor"})))
    app.pods.append(
        fx.make_fake_pod("anchor-b", "100m", "128Mi", fx.with_labels({"role": "anchor", "grade": "gold"}))
    )
    app.deployments.append(
        fx.make_fake_deployment(
            "followers", 6, "200m", "256Mi",
            fx.with_affinity(
                {
                    "podAffinity": {
                        "requiredDuringSchedulingIgnoredDuringExecution": [
                            {"labelSelector": {"matchLabels": {"role": "anchor"}}, "topologyKey": "topology.kubernetes.io/zone"}
                        ]
                    }
                }
            ),
        )
    )
    # multi-term required affinity: only a pod matching BOTH terms counts
    # (filtering.go:113-127) — anchor-b satisfies, anchor alone must not
    app.deployments.append(
        fx.make_fake_deployment(
            "picky", 4, "200m", "256Mi",
            fx.with_affinity(
                {
                    "podAffinity": {
                        "requiredDuringSchedulingIgnoredDuringExecution": [
                            {"labelSelector": {"matchLabels": {"role": "anchor"}}, "topologyKey": "topology.kubernetes.io/zone"},
                            {"labelSelector": {"matchLabels": {"grade": "gold"}}, "topologyKey": "kubernetes.io/hostname"},
                        ]
                    }
                }
            ),
        )
    )
    app.stateful_sets.append(
        fx.make_fake_stateful_set(
            "spread-db", 8, "500m", "1Gi",
            fx.with_affinity(
                {
                    "podAntiAffinity": {
                        "requiredDuringSchedulingIgnoredDuringExecution": [
                            {"labelSelector": {"matchLabels": {"app": "spread-db"}}, "topologyKey": "kubernetes.io/hostname"}
                        ],
                        "preferredDuringSchedulingIgnoredDuringExecution": [
                            {
                                "weight": 100,
                                "podAffinityTerm": {
                                    "labelSelector": {"matchLabels": {"app": "spread-db"}},
                                    "topologyKey": "topology.kubernetes.io/zone",
                                },
                            }
                        ],
                    }
                }
            ),
        )
    )
    prep = prepare(cluster, [AppResource("a", app)], node_pad=128)
    assert prep.features.interpod and prep.features.prefg
    assert fastpath.applicable(prep)
    P = len(prep.ordered)
    want_chosen, want_used = _xla_chosen(prep)
    got_chosen, got_used, *_rest = fastpath.schedule(
        prep, prep.tmpl_ids, np.ones(P, bool), prep.forced, interpret=_INTERPRET
    )
    mism = np.nonzero(want_chosen != got_chosen)[0]
    assert mism.size == 0, (
        f"{mism.size} mismatches at {mism[:5]}: xla={want_chosen[mism[:5]]} fast={got_chosen[mism[:5]]}"
    )
    np.testing.assert_allclose(got_used, want_used, rtol=1e-5)


def test_fastpath_two_zone_keys_matches_xla():
    """Workloads spanning hostname + TWO zone-like topology keys (zone and
    region) run on the megakernel's stacked per-key count blocks; placements
    must match the XLA scan exactly across spread and inter-pod terms on
    either key."""
    cluster = ResourceTypes()
    for i in range(12):
        labels = {}
        if i % 4 != 3:  # some nodes lack the zone label
            labels["topology.kubernetes.io/zone"] = f"z{i % 3}"
        if i % 5 != 4:  # and some lack the region label — independently
            labels["topology.kubernetes.io/region"] = f"r{i % 2}"
        cluster.nodes.append(fx.make_fake_node(f"n{i:02d}", "16", "32Gi", "110", fx.with_labels(labels)))
    app = ResourceTypes()
    app.deployments.append(
        fx.make_fake_deployment(
            "zonal", 9, "250m", "512Mi",
            fx.with_topology_spread(
                [
                    {"maxSkew": 1, "topologyKey": "topology.kubernetes.io/zone",
                     "whenUnsatisfiable": "DoNotSchedule",
                     "labelSelector": {"matchLabels": {"app": "zonal"}}},
                    {"maxSkew": 2, "topologyKey": "topology.kubernetes.io/region",
                     "whenUnsatisfiable": "ScheduleAnyway",
                     "labelSelector": {"matchLabels": {"app": "zonal"}}},
                ]
            ),
        )
    )
    app.pods.append(fx.make_fake_pod("anchor", "100m", "128Mi", fx.with_labels({"role": "anchor"})))
    app.deployments.append(
        fx.make_fake_deployment(
            "regional", 4, "200m", "256Mi",
            fx.with_affinity(
                {
                    "podAffinity": {
                        "requiredDuringSchedulingIgnoredDuringExecution": [
                            {"labelSelector": {"matchLabels": {"role": "anchor"}},
                             "topologyKey": "topology.kubernetes.io/region"}
                        ]
                    }
                }
            ),
        )
    )
    app.stateful_sets.append(
        fx.make_fake_stateful_set(
            "iso", 4, "500m", "1Gi",
            fx.with_affinity(
                {
                    "podAntiAffinity": {
                        "requiredDuringSchedulingIgnoredDuringExecution": [
                            {"labelSelector": {"matchLabels": {"app": "iso"}},
                             "topologyKey": "topology.kubernetes.io/zone"}
                        ],
                        "preferredDuringSchedulingIgnoredDuringExecution": [
                            {"weight": 50, "podAffinityTerm": {
                                "labelSelector": {"matchLabels": {"app": "iso"}},
                                "topologyKey": "topology.kubernetes.io/region"}},
                        ],
                    }
                }
            ),
        )
    )
    prep = prepare(cluster, [AppResource("a", app)], node_pad=128)
    assert fastpath.applicable(prep)
    P = len(prep.ordered)
    want_chosen, want_used = _xla_chosen(prep)
    got_chosen, got_used, *_rest = fastpath.schedule(
        prep, prep.tmpl_ids, np.ones(P, bool), prep.forced, interpret=_INTERPRET
    )
    mism = np.nonzero(want_chosen != got_chosen)[0]
    assert mism.size == 0, (
        f"{mism.size} mismatches at {mism[:5]}: xla={want_chosen[mism[:5]]} fast={got_chosen[mism[:5]]}"
    )
    np.testing.assert_allclose(got_used, want_used, rtol=1e-5)


def test_fastpath_big_u_matches_xla():
    """>512 distinct templates switch the kernel to big-U mode (template
    tables in HBM, per-step DMA); placements must still match the XLA scan
    exactly — including inter-pod and port features whose tables move."""
    cluster = ResourceTypes()
    for i in range(8):
        cluster.nodes.append(fx.make_fake_node(f"n{i}", "64", "128Gi", "110"))
    app = ResourceTypes()
    # 515 unique specs (distinct cpu requests) → >512 templates
    for i in range(515):
        app.pods.append(fx.make_fake_pod(f"p{i:04d}", f"{100 + i}m", "64Mi"))
    app.pods.append(fx.make_fake_pod("anchor", "100m", "64Mi", fx.with_labels({"role": "anchor"})))
    app.deployments.append(
        fx.make_fake_deployment(
            "followers", 4, "200m", "128Mi",
            fx.with_affinity(
                {
                    "podAffinity": {
                        "requiredDuringSchedulingIgnoredDuringExecution": [
                            {"labelSelector": {"matchLabels": {"role": "anchor"}}, "topologyKey": "kubernetes.io/hostname"}
                        ]
                    }
                }
            ),
        )
    )
    app.pods.append(
        fx.make_fake_pod("gateway", "100m", "64Mi", fx.with_host_ports([31080]))
    )
    app.pods.append(
        fx.make_fake_pod("gateway-2", "100m", "64Mi", fx.with_host_ports([31080]))
    )
    prep = prepare(cluster, [AppResource("a", app)], node_pad=128)
    assert int(prep.ec_np.req.shape[0]) > 512
    # the VMEM-aware heuristic keeps this small-N case resident, engaging
    # only when the resident tables would crowd VMEM (headline-N cases)
    assert not fastpath.use_big_u(int(prep.ec_np.req.shape[0]), 128)
    assert fastpath.use_big_u(513, 5120) and fastpath.use_big_u(1000, 5120)
    assert fastpath.applicable(prep)
    P = len(prep.ordered)
    want_chosen, want_used = _xla_chosen(prep)
    # force big_u to exercise the HBM template-table DMA path at small N
    got_chosen, got_used, *_rest = fastpath.schedule(
        prep, prep.tmpl_ids, np.ones(P, bool), prep.forced, interpret=_INTERPRET, big_u=True
    )
    mism = np.nonzero(want_chosen != got_chosen)[0]
    assert mism.size == 0, (
        f"{mism.size} mismatches at {mism[:5]}: xla={want_chosen[mism[:5]]} fast={got_chosen[mism[:5]]}"
    )
    np.testing.assert_allclose(got_used, want_used, rtol=1e-5)


def test_fastpath_failure_reasons_without_rescan(monkeypatch):
    """Unschedulable pods through the fast path get kube-style reasons from
    a per-template evaluation against the final carry — NOT a second full
    XLA scan — and the reasons match the XLA path exactly (exactness holds
    because nothing binds after the first failure)."""
    from opensim_tpu.engine import fastpath as fp
    from opensim_tpu.engine import simulator as sim_mod
    from opensim_tpu.engine.simulator import simulate

    scans = []
    orig_scan = sim_mod.schedule_pods

    def spy_scan(*args, **kwargs):
        scans.append(1)
        return orig_scan(*args, **kwargs)

    monkeypatch.setattr(sim_mod, "schedule_pods", spy_scan)

    cluster = ResourceTypes()
    for i in range(4):
        cluster.nodes.append(fx.make_fake_node(f"n{i}", "8", "16Gi"))
    app = ResourceTypes()
    # 12 × 3cpu on 4 × 8cpu nodes: 8 bind (2/node), 4 fail on cpu
    app.deployments.append(fx.make_fake_deployment("web", 12, "3", "1Gi"))
    res = simulate(cluster, [AppResource("a", app)])
    assert not scans, "fast path fell back to a full XLA re-scan"
    assert len(res.unscheduled_pods) == 4
    fast_reasons = sorted(u.reason for u in res.unscheduled_pods)

    monkeypatch.delenv("OPENSIM_FASTPATH", raising=False)
    monkeypatch.setenv("OPENSIM_DISABLE_FASTPATH", "1")  # on the chip the kernel would engage again
    res2 = simulate(cluster, [AppResource("a", app)])
    assert sorted(u.reason for u in res2.unscheduled_pods) == fast_reasons
    assert "Insufficient cpu" in fast_reasons[0]


def test_fastpath_engages_through_simulate(monkeypatch):
    """End-to-end: simulate() must take the fast branch (interpret mode on
    CPU via OPENSIM_FASTPATH, compiled on the chip) and produce the same
    placements as the engine below it."""
    from opensim_tpu.engine import fastpath as fp
    from opensim_tpu.engine.simulator import simulate

    calls = []
    orig = fp.schedule

    def spy(*args, **kwargs):
        calls.append(1)
        return orig(*args, **kwargs)

    monkeypatch.setattr(fp, "schedule", spy)

    cluster = ResourceTypes()
    for i in range(4):
        cluster.nodes.append(fx.make_fake_node(f"n{i}", "8", "16Gi"))
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("web", 8, "1", "1Gi"))
    res = simulate(cluster, [AppResource("a", app)])
    assert calls, "fast path did not engage"
    assert not res.unscheduled_pods
    per_node = sorted(len(ns.pods) for ns in res.node_status)
    assert sum(per_node) == 8

    # same workload through the XLA path gives identical placement (pod
    # names get fresh suffixes per expansion; compare in name order)
    monkeypatch.delenv("OPENSIM_FASTPATH", raising=False)
    monkeypatch.setenv("OPENSIM_DISABLE_FASTPATH", "1")  # on the chip the kernel would engage again
    res2 = simulate(cluster, [AppResource("a", app)])

    def placement_seq(r):
        pairs = [(p.metadata.name, ns.node.metadata.name) for ns in r.node_status for p in ns.pods]
        return [node for _name, node in sorted(pairs)]

    assert placement_seq(res) == placement_seq(res2)


def test_fastpath_forced_pods():
    cluster = ResourceTypes()
    for i in range(4):
        cluster.nodes.append(fx.make_fake_node(f"n{i}", "8", "16Gi"))
    cluster.pods.append(fx.make_fake_pod("pinned", "1", "1Gi", fx.with_node_name("n2")))
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("d", 6, "1", "1Gi"))
    prep = prepare(cluster, [AppResource("a", app)], node_pad=128)
    assert fastpath.applicable(prep)
    P = len(prep.ordered)
    want_chosen, want_used = _xla_chosen(prep)
    got_chosen, got_used, *_rest = fastpath.schedule(
        prep, prep.tmpl_ids, np.ones(P, bool), prep.forced, interpret=_INTERPRET
    )
    np.testing.assert_array_equal(got_chosen, want_chosen)
    np.testing.assert_allclose(got_used, want_used, rtol=1e-5)


def test_fastpath_matches_xla_prefer_avoid():
    """NodePreferAvoidPods (w=10000 raw 0/100 table) through the megakernel
    must match the XLA scan — including the avoided node winning when it is
    the only feasible one."""
    import json

    cluster = ResourceTypes()
    avoid = json.dumps(
        {"preferAvoidPods": [
            {"podSignature": {"podController": {"kind": "ReplicaSet", "uid": "rs-avoid"}}}
        ]}
    )
    for i in range(6):
        opts = [fx.with_labels({"disk": "ssd" if i < 4 else "hdd"})]
        if i < 4:  # the four best-fit nodes all carry the avoid annotation
            opts.append(
                fx.with_annotations({"scheduler.alpha.kubernetes.io/preferAvoidPods": avoid})
            )
        cluster.nodes.append(fx.make_fake_node(f"n{i}", "8", "16Gi", "110", *opts))
    app = ResourceTypes()
    for k in range(12):
        p = fx.make_fake_pod(f"av-{k}", "1", "1Gi")
        from opensim_tpu.models.objects import OwnerReference

        p.metadata.owner_references = [
            OwnerReference(kind="ReplicaSet", name="rs-avoid", uid="rs-avoid", controller=True)
        ]
        app.pods.append(p)
    for k in range(4):
        app.pods.append(fx.make_fake_pod(f"plain-{k}", "1", "1Gi"))
    prep = prepare(cluster, [AppResource("a", app)], node_pad=128)
    assert prep.features.prefer_avoid, "fixture must trigger the avoid table"
    assert fastpath.applicable(prep)
    want_chosen, want_used = _xla_chosen(prep)
    P = len(prep.ordered)
    got_chosen, got_used, *_ = fastpath.schedule(
        prep, prep.tmpl_ids, np.ones(P, bool), prep.forced, interpret=_INTERPRET
    )
    np.testing.assert_array_equal(got_chosen, want_chosen)
    np.testing.assert_allclose(got_used, want_used, rtol=1e-6)


def test_fastpath_matches_xla_unpadded_nodes():
    """node_pad=8 encodings (N not a multiple of 128) are lane-padded at
    marshalling time; placements and final state must still match the XLA
    scan bit-for-bit."""
    cluster = ResourceTypes()
    for i in range(21):  # pads to 24 under node_pad=8
        labels = {"topology.kubernetes.io/zone": f"z{i % 3}"} if i % 5 else {}
        cluster.nodes.append(
            fx.make_fake_node(f"n{i:03d}", "16", "32Gi", "110", fx.with_labels(labels))
        )
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("plain", 48, "500m", "1Gi"))
    app.deployments.append(
        fx.make_fake_deployment(
            "spread", 24, "250m", "512Mi",
            fx.with_topology_spread(
                [{"maxSkew": 2, "topologyKey": "topology.kubernetes.io/zone",
                  "whenUnsatisfiable": "DoNotSchedule",
                  "labelSelector": {"matchLabels": {"app": "spread"}}}]
            ),
        )
    )
    app.deployments.append(fx.make_fake_deployment("fat", 6, "9", "20Gi"))
    prep = prepare(cluster, [AppResource("a", app)], node_pad=8)
    assert int(prep.ec_np.node_valid.shape[0]) % 128 != 0
    assert fastpath.applicable(prep)
    want_chosen, want_used = _xla_chosen(prep)
    P = len(prep.ordered)
    got_chosen, got_used, *_ = fastpath.schedule(
        prep, prep.tmpl_ids, np.ones(P, bool), prep.forced, interpret=_INTERPRET
    )
    np.testing.assert_array_equal(got_chosen, want_chosen)
    np.testing.assert_allclose(got_used, want_used, rtol=1e-6)


def test_fastpath_matches_xla_four_zone_keys():
    """Four non-hostname topology keys (the new cap) must match the XLA
    scan, mixing hard and soft constraints across keys."""
    keys = ["topology.kubernetes.io/zone", "topology.kubernetes.io/region",
            "topology.rack", "topology.row"]
    cluster = ResourceTypes()
    for i in range(16):
        labels = {
            keys[0]: f"z{i % 3}", keys[1]: f"r{i % 2}",
            keys[2]: f"k{i % 4}", keys[3]: f"w{i % 5}",
        }
        if i % 7 == 6:
            labels.pop(keys[2])  # some nodes lack a key
        cluster.nodes.append(
            fx.make_fake_node(f"n{i:03d}", "16", "32Gi", "110", fx.with_labels(labels))
        )
    app = ResourceTypes()
    constraints = [
        {"maxSkew": 2, "topologyKey": keys[0], "whenUnsatisfiable": "DoNotSchedule",
         "labelSelector": {"matchLabels": {"app": "multi"}}},
        {"maxSkew": 1, "topologyKey": keys[1], "whenUnsatisfiable": "ScheduleAnyway",
         "labelSelector": {"matchLabels": {"app": "multi"}}},
        {"maxSkew": 3, "topologyKey": keys[2], "whenUnsatisfiable": "ScheduleAnyway",
         "labelSelector": {"matchLabels": {"app": "multi"}}},
        {"maxSkew": 2, "topologyKey": keys[3], "whenUnsatisfiable": "DoNotSchedule",
         "labelSelector": {"matchLabels": {"app": "multi"}}},
    ]
    app.deployments.append(
        fx.make_fake_deployment("multi", 40, "500m", "1Gi",
                                fx.with_topology_spread(constraints))
    )
    app.deployments.append(fx.make_fake_deployment("plain", 24, "250m", "512Mi"))
    prep = prepare(cluster, [AppResource("a", app)], node_pad=128)
    assert fastpath.applicable(prep)
    want_chosen, want_used = _xla_chosen(prep)
    P = len(prep.ordered)
    got_chosen, got_used, *_ = fastpath.schedule(
        prep, prep.tmpl_ids, np.ones(P, bool), prep.forced, interpret=_INTERPRET
    )
    np.testing.assert_array_equal(got_chosen, want_chosen)
    np.testing.assert_allclose(got_used, want_used, rtol=1e-6)


def test_megakernel_failure_degrades_to_xla(monkeypatch, caplog):
    """A Mosaic compile failure (constructs that pass interpret mode can
    fail the real compiler) must degrade to the XLA scan with a warning,
    never kill the simulation — placements are identical either way."""
    import logging

    from opensim_tpu.engine import fastpath
    from opensim_tpu.engine.simulator import AppResource, simulate
    from opensim_tpu.models import ResourceTypes, fixtures as fx

    import jax

    # simulate a REAL-hardware failure: tpu backend, no interpret mode (in
    # interpret/test mode the exception re-raises so CI can't silently
    # validate the fallback engine instead of the kernel)
    monkeypatch.delenv("OPENSIM_FASTPATH", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def boom(*a, **k):
        raise RuntimeError("Mosaic lowering failed (simulated)")

    monkeypatch.setattr(fastpath, "schedule", boom)
    cluster = ResourceTypes()
    cluster.nodes.append(fx.make_fake_node("n0", "8", "16Gi"))
    app = ResourceTypes()
    app.pods.append(fx.make_fake_pod("p", "100m", "128Mi"))
    with caplog.at_level(logging.WARNING, logger="opensim_tpu"):
        res = simulate(cluster, [AppResource("a", app)], node_pad=8)
    assert not res.unscheduled_pods
    assert any("falling back to a slower engine" in r.message for r in caplog.records)
