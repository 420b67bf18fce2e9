"""Randomized differential testing: megakernel vs XLA scan on generated
workloads mixing every supported feature. Any placement mismatch is a bug in
one of the two pipelines (they implement the same semantics twice)."""

import os
import random

import numpy as np
import pytest

from opensim_tpu.engine import fastpath
from opensim_tpu.engine.scheduler import pad_pod_stream, schedule_pods
from opensim_tpu.engine.simulator import AppResource, prepare
from opensim_tpu.models import ResourceTypes, fixtures as fx

pytestmark = pytest.mark.slow  # nightly tier (README: test tiering)

_INTERPRET = os.environ.get("OPENSIM_TEST_BACKEND") != "tpu"


@pytest.fixture(autouse=True)
def _enable_interpret_fastpath(monkeypatch):
    if _INTERPRET:  # the chip run compiles; the interpreter is asked for by name
        monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")


def random_cluster(rng: random.Random, n_nodes: int) -> ResourceTypes:
    import json

    rt = ResourceTypes()
    for i in range(n_nodes):
        opts = []
        labels = {}
        if rng.random() < 0.8:
            labels["topology.kubernetes.io/zone"] = f"z{rng.randrange(3)}"
        if rng.random() < 0.5:
            labels["topology.kubernetes.io/region"] = f"r{rng.randrange(2)}"
        if rng.random() < 0.25:
            labels["topology.rack"] = f"k{rng.randrange(4)}"
        if rng.random() < 0.15:
            labels["topology.row"] = f"w{rng.randrange(2)}"
        if rng.random() < 0.5:
            labels["disk"] = rng.choice(["ssd", "hdd"])
        opts.append(fx.with_labels(labels))
        if rng.random() < 0.15:
            # NodePreferAvoidPods: repel one of the fuzz RS controllers
            opts.append(
                fx.with_annotations(
                    {
                        "scheduler.alpha.kubernetes.io/preferAvoidPods": json.dumps(
                            {"preferAvoidPods": [
                                {"podSignature": {"podController": {
                                    "kind": "ReplicaSet",
                                    "uid": f"rs-fuzz-{rng.randrange(3)}",
                                }}}
                            ]}
                        )
                    }
                )
            )
        if rng.random() < 0.25:
            effect = rng.choice(["NoSchedule", "PreferNoSchedule"])
            opts.append(fx.with_taints([{"key": "dedicated", "value": "x", "effect": effect}]))
        if rng.random() < 0.3:
            opts.append(
                fx.with_allocatable(
                    {"alibabacloud.com/gpu-mem": "16Gi", "alibabacloud.com/gpu-count": "2"}
                )
            )
        if rng.random() < 0.25:
            opts.append(
                fx.with_node_local_storage(
                    vgs=[{"name": "pool0", "capacity": rng.choice([50, 100]) * 1024**3}],
                    devices=[
                        {
                            "device": "/dev/vdb",
                            "capacity": 100 * 1024**3,
                            "mediaType": rng.choice(["ssd", "hdd"]),
                        }
                    ],
                )
            )
        rt.nodes.append(
            fx.make_fake_node(f"n{i:03d}", str(rng.choice([8, 16, 32])), "64Gi", "110", *opts)
        )
    return rt


def random_app(rng: random.Random, n_workloads: int) -> ResourceTypes:
    rt = ResourceTypes()
    for w in range(n_workloads):
        opts = []
        if rng.random() < 0.3:
            opts.append(fx.with_node_selector({"disk": rng.choice(["ssd", "hdd"])}))
        if rng.random() < 0.3:
            opts.append(
                fx.with_tolerations(
                    [{"key": "dedicated", "operator": "Equal", "value": "x", "effect": "NoSchedule"}]
                )
            )
        if rng.random() < 0.3:
            opts.append(
                fx.with_topology_spread(
                    [
                        {
                            "maxSkew": rng.choice([1, 2, 5]),
                            "topologyKey": rng.choice(
                                ["kubernetes.io/hostname", "topology.kubernetes.io/zone",
                                 "topology.kubernetes.io/region", "topology.rack",
                                 "topology.row"]
                            ),
                            "whenUnsatisfiable": rng.choice(["DoNotSchedule", "ScheduleAnyway"]),
                            "labelSelector": {"matchLabels": {"app": f"w{w}"}},
                        }
                    ]
                )
            )
        if rng.random() < 0.25:
            kind = rng.choice(["podAffinity", "podAntiAffinity"])
            mode = rng.choice(["required", "preferred"])
            term = {
                "labelSelector": {"matchLabels": {"app": f"w{max(w - 1, 0)}"}},
                "topologyKey": rng.choice(
                    ["kubernetes.io/hostname", "topology.kubernetes.io/zone",
                     "topology.kubernetes.io/region", "topology.rack"]
                ),
            }
            if rng.random() < 0.3:
                term["namespaces"] = rng.sample(["ns-a", "ns-b", "default"], rng.randrange(1, 3))
            if mode == "required":
                aff = {kind: {"requiredDuringSchedulingIgnoredDuringExecution": [term]}}
            else:
                aff = {
                    kind: {
                        "preferredDuringSchedulingIgnoredDuringExecution": [
                            {"weight": rng.choice([10, 50, 100]), "podAffinityTerm": term}
                        ]
                    }
                }
            opts.append(fx.with_affinity(aff))
        if rng.random() < 0.2:
            opts.append(fx.with_host_ports([rng.choice([8080, 9090, 9443])]))
        if rng.random() < 0.15:
            # whole-GPU pods: gpu-count as a SPEC resource exercises the
            # dynamic allocatable (gpushare Reserve rewrite) fit/share path
            opts.append(fx.with_requests(
                {"alibabacloud.com/gpu-count": rng.choice(["1", "2"])}))
        if rng.random() < 0.4:
            opts.append(fx.with_namespace(rng.choice(["ns-a", "ns-b"])))
        deploy = fx.make_fake_deployment(
            f"w{w}",
            rng.randrange(2, 10),
            f"{rng.choice([100, 250, 500, 1000])}m",
            f"{rng.choice([128, 512, 1024])}Mi",
            *opts,
        )
        if rng.random() < 0.2:
            deploy.template_metadata.annotations.update(
                {"alibabacloud.com/gpu-mem": "2Gi", "alibabacloud.com/gpu-count": "1"}
            )
            deploy.template_raw.setdefault("metadata", {}).setdefault("annotations", {}).update(
                {"alibabacloud.com/gpu-mem": "2Gi", "alibabacloud.com/gpu-count": "1"}
            )
        rt.deployments.append(deploy)
    # occasionally: a stateful set with local storage + anti-affinity, and a
    # bare pre-bound pod (forced-bind path)
    if rng.random() < 0.4:
        sts = fx.make_fake_stateful_set(
            "db", rng.randrange(2, 5), "250m", "512Mi",
            fx.with_affinity(
                {
                    "podAntiAffinity": {
                        "requiredDuringSchedulingIgnoredDuringExecution": [
                            {"labelSelector": {"matchLabels": {"app": "db"}}, "topologyKey": "kubernetes.io/hostname"}
                        ]
                    }
                }
            ),
        )
        if rng.random() < 0.5:
            sts.volume_claim_templates = [
                {
                    "metadata": {"name": "data"},
                    "spec": {"storageClassName": "open-local-lvm", "resources": {"requests": {"storage": "10Gi"}}},
                }
            ]
        rt.stateful_sets.append(sts)
    if rng.random() < 0.3:
        rt.pods.append(fx.make_fake_pod("pinned", "100m", "128Mi", fx.with_node_name("n000")))
    if rng.random() < 0.3:
        # bare pods owned by the RS controllers the avoid annotations name
        from opensim_tpu.models.objects import OwnerReference

        rs = rng.randrange(3)
        for k in range(rng.randrange(1, 5)):
            p = fx.make_fake_pod(f"avoided-{rs}-{k}", "200m", "256Mi")
            p.metadata.owner_references = [
                OwnerReference(kind="ReplicaSet", name=f"rs-fuzz-{rs}",
                               uid=f"rs-fuzz-{rs}", controller=True)
            ]
            rt.pods.append(p)
    return rt


def _seeds():
    """Default CI seeds; OPENSIM_FUZZ_SEEDS=<n> widens the sweep (e.g. a
    nightly run with hundreds of seeds)."""
    import os

    extra = int(os.environ.get("OPENSIM_FUZZ_SEEDS", "0"))
    base = [1, 7, 23, 99]
    return base + list(range(1000, 1000 + extra))


@pytest.mark.parametrize("seed", _seeds())
def test_fuzz_fastpath_vs_xla(seed):
    rng = random.Random(seed)
    cluster = random_cluster(rng, rng.randrange(8, 20))
    app = random_app(rng, rng.randrange(3, 8))
    # node_pad=8 leaves N off the 128-lane grid; build_inputs pads it
    prep = prepare(cluster, [AppResource("fuzz", app)], node_pad=rng.choice([8, 128]))
    if prep is None or not fastpath.applicable(prep):
        pytest.skip("generated workload outside fast-path bounds")
    P = len(prep.ordered)
    t, v, f = pad_pod_stream(prep.tmpl_ids, np.ones(P, bool), prep.forced)
    out = schedule_pods(prep.ec, prep.st0, t, v, f, features=prep.features)
    want = np.asarray(out.chosen)[:P]
    got, got_used, *_rest = fastpath.schedule(
        prep, prep.tmpl_ids, np.ones(P, bool), prep.forced, interpret=_INTERPRET
    )
    mism = np.nonzero(want != got)[0]
    assert mism.size == 0, (
        f"seed={seed}: {mism.size}/{P} mismatches at {mism[:10]}; "
        f"xla={want[mism[:10]]} fast={got[mism[:10]]}"
    )
    np.testing.assert_allclose(got_used, np.asarray(out.final_state.used), rtol=1e-5)


@pytest.mark.parametrize("seed", [5, 42])
def test_fuzz_big_u_fastpath_vs_xla(seed):
    """Same differential check with the template space inflated past the
    VMEM-resident cap, forcing the kernel's big-U (HBM tables + per-step
    DMA) mode."""
    rng = random.Random(seed)
    cluster = random_cluster(rng, rng.randrange(6, 12))
    app = random_app(rng, rng.randrange(2, 5))
    for i in range(520):
        app.pods.append(fx.make_fake_pod(f"u{i:04d}", f"{50 + i}m", f"{64 + (i % 7)}Mi"))
    prep = prepare(cluster, [AppResource("fuzz", app)], node_pad=128)
    if prep is None or not fastpath.applicable(prep):
        pytest.skip("generated workload outside fast-path bounds")
    assert int(prep.ec_np.req.shape[0]) > 512
    P = len(prep.ordered)
    t, v, f = pad_pod_stream(prep.tmpl_ids, np.ones(P, bool), prep.forced)
    out = schedule_pods(prep.ec, prep.st0, t, v, f, features=prep.features)
    want = np.asarray(out.chosen)[:P]
    # big_u forced: the heuristic keeps small-N resident, but the fuzz must
    # cover the HBM template-table DMA path
    got, got_used, *_rest = fastpath.schedule(
        prep, prep.tmpl_ids, np.ones(P, bool), prep.forced, interpret=_INTERPRET, big_u=True
    )
    mism = np.nonzero(want != got)[0]
    assert mism.size == 0, (
        f"seed={seed}: {mism.size}/{P} mismatches at {mism[:10]}; "
        f"xla={want[mism[:10]]} fast={got[mism[:10]]}"
    )
    np.testing.assert_allclose(got_used, np.asarray(out.final_state.used), rtol=1e-5)
