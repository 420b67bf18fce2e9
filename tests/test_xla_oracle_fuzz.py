"""The XLA scan against the independent kube oracle (tests/test_k8s_oracle.py):
every bind the scan makes is feasible per the oracle, and every pod it leaves
unscheduled has no feasible node. The XLA scan is the engine of every CPU host
and the one every kernel failure falls back to, so it gets the fuzzing of the
carry classes (inter-pod terms, host ports, gpu-share, open-local, all at once
with forced binds) and the fixed spread and inter-pod fixtures."""

import os
import random
import sys

import numpy as np
import pytest

from opensim_tpu.engine.scheduler import pad_pod_stream, schedule_pods
from opensim_tpu.engine.simulator import AppResource, prepare, simulate
from opensim_tpu.models import ResourceTypes, fixtures as fx

sys.path.insert(0, os.path.dirname(__file__))
from test_k8s_oracle import ExtOracle, Oracle  # noqa: E402


def _xla_chosen(prep, config=None):
    P = len(prep.ordered)
    t, v, f = pad_pod_stream(prep.tmpl_ids, np.ones(P, bool), prep.forced)
    out = schedule_pods(prep.ec, prep.st0, t, v, f, features=prep.features, config=config)
    return np.asarray(out.chosen)[:P]


def _oracle_replay(cluster, prep, chosen, oracle):
    """Replay a placement stream against an independent kube-semantics
    oracle: every scheduler-made bind must be oracle-feasible, every failure
    must have no oracle-feasible node. Forced pods bypass the scheduler (but
    still drain oracle state)."""
    node_names = prep.meta.node_names
    lenient = False
    for i, pod in enumerate(prep.ordered):
        c = int(chosen[i])
        forced = bool(prep.forced[i])
        if c >= 0:
            node = oracle.by_name[node_names[c]]
            if not forced:
                assert oracle.feasible(pod, node), (
                    f"engine bound {pod.metadata.name} to {node.metadata.name}; "
                    "oracle says infeasible"
                )
            try:
                oracle.bind(pod, node)
            except (TypeError, ValueError, IndexError):
                # a FORCED pin outside the oracle's allocation model (e.g. a
                # device volume pinned onto a node with no free device): the
                # oracle state now under-counts usage, so stop asserting the
                # unscheduled side (feasible-bind asserts only get laxer)
                assert forced, "oracle.bind failed on a scheduler-made bind"
                lenient = True
        elif not forced and not lenient:
            feas = [n.metadata.name for n in cluster.nodes if oracle.feasible(pod, n)]
            assert not feas, (
                f"{pod.metadata.name} unscheduled but oracle finds {feas}"
            )


def _replay(cluster, app, oracle_cls, patch=None, node_pad=128):
    """Prepare, scan, replay through the oracle; the chosen nodes."""
    prep = prepare(cluster, [AppResource("fuzz", app)], node_pad=node_pad, patch_pods_fn=patch)
    chosen = _xla_chosen(prep)
    _oracle_replay(cluster, prep, chosen, oracle_cls(cluster.nodes))
    return prep, chosen


# ---------------------------------------------------------------------------
# the fuzz families
# ---------------------------------------------------------------------------


def _ip_fuzz_case(rng):
    """Interpod-rich random workloads that stay INSIDE the incremental
    envelope (no gpu/local/ports): required/preferred affinity and anti
    terms over zone/hostname/rack, mixed with hard/soft spread."""
    cluster = ResourceTypes()
    n_nodes = rng.randrange(10, 18)
    for i in range(n_nodes):
        labels = {}
        if rng.random() < 0.85:
            labels["topology.kubernetes.io/zone"] = f"z{rng.randrange(3)}"
        if rng.random() < 0.4:
            labels["topology.rack"] = f"k{rng.randrange(4)}"
        cluster.nodes.append(
            fx.make_fake_node(
                f"n{i:03d}", str(rng.choice([8, 16])), "32Gi", "110",
                fx.with_labels(labels),
            )
        )
    app = ResourceTypes()
    n_workloads = rng.randrange(3, 7)
    for w in range(n_workloads):
        opts = []
        aff = {}
        target = f"w{max(w - 1, 0)}" if rng.random() < 0.6 else f"w{w}"
        key = rng.choice(
            ["kubernetes.io/hostname", "topology.kubernetes.io/zone", "topology.rack"]
        )
        if rng.random() < 0.4:
            kind = rng.choice(["podAffinity", "podAntiAffinity"])
            aff.setdefault(kind, {})[
                "requiredDuringSchedulingIgnoredDuringExecution"
            ] = [{"labelSelector": {"matchLabels": {"app": target}}, "topologyKey": key}]
        if rng.random() < 0.5:
            kind = rng.choice(["podAffinity", "podAntiAffinity"])
            aff.setdefault(kind, {})[
                "preferredDuringSchedulingIgnoredDuringExecution"
            ] = [
                {"weight": rng.choice([10, 50, 100]), "podAffinityTerm": {
                    "labelSelector": {"matchLabels": {"app": target}},
                    "topologyKey": key}}
            ]
        if aff:
            opts.append(fx.with_affinity(aff))
        if rng.random() < 0.4:
            opts.append(
                fx.with_topology_spread([
                    {"maxSkew": rng.choice([1, 2, 4]),
                     "topologyKey": "topology.kubernetes.io/zone",
                     "whenUnsatisfiable": rng.choice(["DoNotSchedule", "ScheduleAnyway"]),
                     "labelSelector": {"matchLabels": {"app": f"w{w}"}}},
                ])
            )
        app.deployments.append(
            fx.make_fake_deployment(
                f"w{w}", rng.randrange(5, 16),
                f"{rng.choice([100, 250, 500])}m",
                f"{rng.choice([128, 256, 512])}Mi", *opts,
            )
        )
    return cluster, app


def _tmpl_annotate(deploy, anno):
    """Pod-TEMPLATE annotations on a workload (gpu-share / open-local pod
    requests live on the pod, not the controller)."""
    deploy.template_metadata.annotations.update(anno)
    deploy.template_raw.setdefault("metadata", {}).setdefault(
        "annotations", {}
    ).update(anno)


def _ports_fuzz_case(rng):
    """Host-port-bearing workloads over-subscribed enough to conflict: every
    template carries ports, so each incremental step exercises the per-node
    port-bitmap carry and binds flip verdicts (bail class B_PORTS)."""
    cluster = ResourceTypes()
    n_nodes = rng.randrange(6, 14)
    for i in range(n_nodes):
        cluster.nodes.append(
            fx.make_fake_node(
                f"n{i:03d}", "16", "32Gi", "110",
                fx.with_labels({"topology.kubernetes.io/zone": f"z{rng.randrange(3)}"}),
            )
        )
    app = ResourceTypes()
    for w in range(rng.randrange(2, 5)):
        opts = [fx.with_host_ports(
            rng.sample([8080, 9090, 9443, 5000], rng.randrange(1, 3))
        )]
        if rng.random() < 0.4:
            opts.append(
                fx.with_topology_spread([
                    {"maxSkew": rng.choice([1, 2]),
                     "topologyKey": "topology.kubernetes.io/zone",
                     "whenUnsatisfiable": rng.choice(["DoNotSchedule", "ScheduleAnyway"]),
                     "labelSelector": {"matchLabels": {"app": f"w{w}"}}},
                ])
            )
        app.deployments.append(
            fx.make_fake_deployment(
                f"w{w}", rng.randrange(4, n_nodes + 5), "250m", "512Mi", *opts
            )
        )
    return cluster, app


def _gpu_fuzz_case(rng):
    """GPU-share templates (gpu-mem annotations) mixed with whole-GPU pods
    (gpu-count spec requests → the gc_dyn dynamic allocatable): per-GPU-index
    headroom carry + the dynamic share score term."""
    cluster = ResourceTypes()
    n_nodes = rng.randrange(5, 10)
    for i in range(n_nodes):
        opts = [fx.with_labels({"topology.kubernetes.io/zone": f"z{i % 2}"})]
        if rng.random() < 0.8:
            opts.append(fx.with_allocatable(
                {"alibabacloud.com/gpu-mem": rng.choice(["16Gi", "32Gi"]),
                 "alibabacloud.com/gpu-count": rng.choice(["2", "4"])}))
        cluster.nodes.append(fx.make_fake_node(f"n{i:03d}", "16", "64Gi", "110", *opts))
    app = ResourceTypes()
    for w in range(rng.randrange(2, 5)):
        d = fx.make_fake_deployment(
            f"w{w}", rng.randrange(6, 20),
            f"{rng.choice([250, 500])}m", "512Mi",
        )
        if rng.random() < 0.6:
            _tmpl_annotate(d, {
                "alibabacloud.com/gpu-mem": rng.choice(["2Gi", "4Gi", "8Gi"]),
                "alibabacloud.com/gpu-count": rng.choice(["1", "1", "2"]),
            })
        else:
            # whole-GPU: gc_dyn fit + dynamic share (Reserve rewrite)
            d = fx.make_fake_deployment(
                f"w{w}", rng.randrange(3, 8), "250m", "512Mi",
                fx.with_requests(
                    {"alibabacloud.com/gpu-count": rng.choice(["1", "1", "2"])}),
            )
        app.deployments.append(d)
    return cluster, app


def _local_fuzz_case(rng):
    """open-local LVM + exclusive-device volumes: per-disk allocation carry
    for the local filter AND the w_local score term (use_loc now rides the
    incremental path)."""
    import json as _json

    cluster = ResourceTypes()
    n_nodes = rng.randrange(5, 10)
    for i in range(n_nodes):
        opts = [fx.with_labels({"topology.kubernetes.io/zone": f"z{i % 2}"})]
        if rng.random() < 0.85:
            opts.append(fx.with_node_local_storage(
                vgs=[{"name": "pool0",
                      "capacity": rng.choice([50, 100, 200]) * 1024**3}],
                devices=[
                    {"device": "/dev/vdb",
                     "capacity": rng.choice([40, 80]) * 1024**3,
                     "mediaType": rng.choice(["ssd", "hdd"])},
                    {"device": "/dev/vdc", "capacity": 60 * 1024**3,
                     "mediaType": rng.choice(["ssd", "hdd"])},
                ]))
        cluster.nodes.append(fx.make_fake_node(f"n{i:03d}", "16", "64Gi", "110", *opts))
    app = ResourceTypes()
    for w in range(rng.randrange(2, 5)):
        vols = [{"size": str(rng.choice([5, 10, 20]) * 1024**3), "kind": "LVM",
                 "scName": "open-local-lvm"}]
        if rng.random() < 0.5:
            vols.append({"size": str(rng.choice([10, 30]) * 1024**3),
                         "kind": rng.choice(["SSD", "HDD"]),
                         "scName": "open-local-device"})
        d = fx.make_fake_deployment(
            f"w{w}", rng.randrange(4, 12), "250m", "512Mi",
        )
        _tmpl_annotate(d, {"simon/pod-local-storage": _json.dumps({"volumes": vols})})
        app.deployments.append(d)
    return cluster, app


def _storm_fuzz_case(rng):
    """Everything at once — ports + gpu-share + gc_dyn + local-PV + interpod
    + spread — with forced foreign binds spliced into the stream: every carry
    class must fold foreign deltas or bail, never drift."""
    c1, a1 = _ports_fuzz_case(rng)
    _c2, a2 = _gpu_fuzz_case(rng)
    _c3, a3 = _local_fuzz_case(rng)
    cluster = ResourceTypes()
    # gpu + local capable node set, zoned, sized to fit all three node shapes
    n_nodes = max(len(c1.nodes), 8)
    for i in range(n_nodes):
        opts = [fx.with_labels({"topology.kubernetes.io/zone": f"z{i % 3}"})]
        if rng.random() < 0.6:
            opts.append(fx.with_allocatable(
                {"alibabacloud.com/gpu-mem": "16Gi",
                 "alibabacloud.com/gpu-count": "2"}))
        if rng.random() < 0.6:
            opts.append(fx.with_node_local_storage(
                vgs=[{"name": "pool0", "capacity": 100 * 1024**3}],
                devices=[{"device": "/dev/vdb", "capacity": 80 * 1024**3,
                          "mediaType": "ssd"}]))
        cluster.nodes.append(fx.make_fake_node(f"n{i:03d}", "32", "64Gi", "110", *opts))
    app = ResourceTypes()
    for src, tag in ((a1, "p"), (a2, "g"), (a3, "l")):
        for d in src.deployments:
            d.metadata.name = f"{tag}-{d.metadata.name}"
            app.deployments.append(d)
    if rng.random() < 0.6:
        app.deployments.append(
            fx.make_fake_deployment(
                "aff", rng.randrange(4, 10), "250m", "512Mi",
                fx.with_affinity({
                    "podAntiAffinity": {
                        "requiredDuringSchedulingIgnoredDuringExecution": [
                            {"labelSelector": {"matchLabels": {"app": "aff"}},
                             "topologyKey": "kubernetes.io/hostname"}]}}),
            )
        )
    return cluster, app


@pytest.mark.parametrize("seed", [211, 223, 251, 257, 263, 269])
def test_interpod_fuzz(seed):
    cluster, app = _ip_fuzz_case(random.Random(seed))
    _prep, chosen = _replay(cluster, app, Oracle)
    assert (chosen >= 0).any()


@pytest.mark.parametrize("seed", [211, 223, 251, 271, 277, 281])
def test_ports_fuzz(seed):
    cluster, app = _ports_fuzz_case(random.Random(seed))
    _prep, chosen = _replay(cluster, app, Oracle)
    assert (chosen >= 0).any()


@pytest.mark.parametrize("seed", [307, 311, 331, 337, 347, 349])
def test_gpu_share_fuzz(seed):
    cluster, app = _gpu_fuzz_case(random.Random(seed))
    _prep, chosen = _replay(cluster, app, ExtOracle)
    assert (chosen >= 0).any()


@pytest.mark.parametrize("seed", [401, 409, 419, 421, 431, 433])
def test_local_pv_fuzz(seed):
    cluster, app = _local_fuzz_case(random.Random(seed))
    _prep, chosen = _replay(cluster, app, ExtOracle)
    assert (chosen >= 0).any()


@pytest.mark.parametrize("seed", [503, 509, 521, 523, 541, 547])
def test_mixed_storm_forced_binds_fuzz(seed):
    """All carry classes at once with forced foreign binds spliced every 7th
    pod."""
    cluster, app = _storm_fuzz_case(random.Random(seed))
    n_nodes = len(cluster.nodes)

    def patch(app_name, pods):
        for i, p in enumerate(pods):
            if i % 7 == 3:
                p.spec.node_name = f"n{i % n_nodes:03d}"

    prep, _chosen = _replay(cluster, app, ExtOracle, patch=patch)
    assert prep.forced.sum() > 3


@pytest.mark.parametrize("tie_seed", [0, 1, 7])
def test_sampled_tie_break_fuzz(tie_seed):
    """The sampled tie-break permutes only within equal-score sets: every
    bind stays oracle-feasible and as many pods schedule as deterministically."""
    cluster, app = _ip_fuzz_case(random.Random(97))
    prep = prepare(cluster, [AppResource("fuzz", app)], node_pad=128)
    P = len(prep.ordered)
    t, v, f = pad_pod_stream(prep.tmpl_ids, np.ones(P, bool), prep.forced)
    out = schedule_pods(prep.ec, prep.st0, t, v, f, features=prep.features, tie_seed=tie_seed)
    sampled = np.asarray(out.chosen)[:P]
    _oracle_replay(cluster, prep, sampled, Oracle(cluster.nodes))
    assert (sampled >= 0).sum() == (_xla_chosen(prep) >= 0).sum()


# ---------------------------------------------------------------------------
# the fixed spread fixtures
# ---------------------------------------------------------------------------


def test_default_spread_with_unlabeled_nodes():
    """A node WITHOUT the zone label is spread-ignored but still schedulable,
    and its per-host pod count can exceed every scored zone's level range."""
    cluster = ResourceTypes()
    for i in range(4):
        cluster.nodes.append(
            fx.make_fake_node(
                f"z{i}", "4", "8Gi", "110",
                fx.with_labels({"topology.kubernetes.io/zone": f"zone-{i % 2}"}),
            )
        )
    # zone-less big node: attracts many pods once the labeled ones fill
    cluster.nodes.append(fx.make_fake_node("plain", "64", "128Gi"))
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("web", 60, "500m", "512Mi"))
    _prep, chosen = _replay(cluster, app, Oracle, node_pad=8)
    # the unlabeled node really did absorb a level beyond the zoned hosts
    assert int((chosen == 4).sum()) > 15


def test_hier_mode_reversed_constraint_order():
    """Explicit soft spread [zone, hostname] puts the fine (singleton)
    constraint second."""
    cluster = ResourceTypes()
    for i in range(6):
        cluster.nodes.append(
            fx.make_fake_node(
                f"n{i}", "8", "16Gi", "110",
                fx.with_labels({"topology.kubernetes.io/zone": f"z{i % 3}"}),
            )
        )
    app = ResourceTypes()
    app.deployments.append(
        fx.make_fake_deployment(
            "rev", 24, "200m", "256Mi",
            fx.with_topology_spread([
                {"maxSkew": 2, "topologyKey": "topology.kubernetes.io/zone",
                 "whenUnsatisfiable": "ScheduleAnyway",
                 "labelSelector": {"matchLabels": {"app": "rev"}}},
                {"maxSkew": 1, "topologyKey": "kubernetes.io/hostname",
                 "whenUnsatisfiable": "ScheduleAnyway",
                 "labelSelector": {"matchLabels": {"app": "rev"}}},
            ]),
        )
    )
    prep, chosen = _replay(cluster, app, Oracle, node_pad=8)
    assert (chosen >= 0).all()
    # hostname (fine) really is the SECOND constraint
    topo = np.asarray(prep.ec_np.spr_topo)[int(prep.tmpl_ids[0])]
    keys = list(prep.meta.vocab.topo_keys.items())
    active = [keys[t] for t in topo if t >= 0]
    assert active and active[-1] == "kubernetes.io/hostname", active


def test_dom_mode_with_hard_constraint_mix():
    """One soft + one hard spread constraint on a cluster whose capacity
    forces skew, so the hard filter rejects the tail."""
    cluster = ResourceTypes()
    for i in range(3):
        cluster.nodes.append(
            fx.make_fake_node(
                f"n{i}", "8", "16Gi", "110",
                fx.with_labels({"topology.kubernetes.io/zone": f"z{i % 2}"}),
            )
        )
    app = ResourceTypes()
    app.deployments.append(
        fx.make_fake_deployment(
            "mix", 20, "1", "1Gi",
            fx.with_topology_spread([
                {"maxSkew": 1, "topologyKey": "topology.kubernetes.io/zone",
                 "whenUnsatisfiable": "DoNotSchedule",
                 "labelSelector": {"matchLabels": {"app": "mix"}}},
                {"maxSkew": 3, "topologyKey": "topology.kubernetes.io/zone",
                 "whenUnsatisfiable": "ScheduleAnyway",
                 "labelSelector": {"matchLabels": {"app": "mix"}}},
            ]),
        )
    )
    _prep, chosen = _replay(cluster, app, Oracle, node_pad=8)
    assert (chosen == -1).sum() > 0  # the hard-skew failure path ran


def test_hier_mode_feasibility_flip_rebuild():
    """Default-spread pods that fill nodes mid-run flip feasibility; the
    placements hold through the flip, including the final failures."""
    cluster = ResourceTypes()
    for i in range(3):
        cluster.nodes.append(
            fx.make_fake_node(
                f"n{i}", "4", "8Gi", "110",
                fx.with_labels({"topology.kubernetes.io/zone": f"z{i % 2}"}),
            )
        )
    app = ResourceTypes()
    # 4-cpu nodes, 1-cpu pods: every 4th bind on a node flips it infeasible
    app.deployments.append(fx.make_fake_deployment("fill", 15, "1", "512Mi"))
    _prep, chosen = _replay(cluster, app, Oracle, node_pad=8)
    assert (chosen == -1).sum() == 3  # 12 fit, 3 fail


# ---------------------------------------------------------------------------
# the fixed inter-pod fixtures
# ---------------------------------------------------------------------------


def _ip_cluster(n_nodes=18, unlabeled_every=6):
    """Zoned nodes plus a few zone-LESS ones (trash-domain members: their
    interpod/spread reads must stay vacuous through the delta path)."""
    cluster = ResourceTypes()
    for i in range(n_nodes):
        labels = {}
        if unlabeled_every == 0 or i % unlabeled_every != unlabeled_every - 1:
            labels["topology.kubernetes.io/zone"] = f"z{i % 3}"
        cluster.nodes.append(
            fx.make_fake_node(f"n{i:03d}", "8", "16Gi", "110", fx.with_labels(labels))
        )
    return cluster


def _ip_apps():
    """Required + preferred + anti-affinity terms MIXED with hard and soft
    spread — the full surface the widened envelope must keep bit-exact."""
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("base", 30, "250m", "512Mi"))
    # required affinity to base (zone) + preferred anti on itself (hostname):
    # negative symmetric weights — the score raw SHRINKS as copies land
    app.deployments.append(
        fx.make_fake_deployment(
            "follow", 40, "200m", "256Mi",
            fx.with_affinity({
                "podAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [
                        {"labelSelector": {"matchLabels": {"app": "base"}},
                         "topologyKey": "topology.kubernetes.io/zone"}
                    ]
                },
                "podAntiAffinity": {
                    "preferredDuringSchedulingIgnoredDuringExecution": [
                        {"weight": 100, "podAffinityTerm": {
                            "labelSelector": {"matchLabels": {"app": "follow"}},
                            "topologyKey": "kubernetes.io/hostname"}}
                    ]
                },
            }),
        )
    )
    # required anti on ITSELF per hostname: every bind flips the bound
    # node's filter verdict — the bail-heavy worst case for the cache
    app.deployments.append(
        fx.make_fake_deployment(
            "excl", 12, "100m", "128Mi",
            fx.with_affinity({
                "podAntiAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [
                        {"labelSelector": {"matchLabels": {"app": "excl"}},
                         "topologyKey": "kubernetes.io/hostname"}
                    ]
                }
            }),
        )
    )
    # hard spread + preferred affinity (positive weights) + soft spread mix
    app.deployments.append(
        fx.make_fake_deployment(
            "spread", 30, "150m", "256Mi",
            fx.with_topology_spread([
                {"maxSkew": 2, "topologyKey": "topology.kubernetes.io/zone",
                 "whenUnsatisfiable": "DoNotSchedule",
                 "labelSelector": {"matchLabels": {"app": "spread"}}},
                {"maxSkew": 1, "topologyKey": "topology.kubernetes.io/zone",
                 "whenUnsatisfiable": "ScheduleAnyway",
                 "labelSelector": {"matchLabels": {"app": "spread"}}},
            ]),
            fx.with_affinity({
                "podAffinity": {
                    "preferredDuringSchedulingIgnoredDuringExecution": [
                        {"weight": 50, "podAffinityTerm": {
                            "labelSelector": {"matchLabels": {"app": "base"}},
                            "topologyKey": "topology.kubernetes.io/zone"}}
                    ]
                }
            }),
        )
    )
    return app


def test_interpod_mixed_terms():
    """Required + preferred + anti terms mixed with hard and soft spread."""
    _prep, chosen = _replay(_ip_cluster(), _ip_apps(), Oracle)
    assert (chosen >= 0).sum() > 50


def test_interpod_oracle_cross_check():
    """The mixed workload on zone-less nodes only every third: trash-domain
    members whose inter-pod and spread reads must stay vacuous."""
    _prep, chosen = _replay(_ip_cluster(n_nodes=15, unlabeled_every=3), _ip_apps(), Oracle)
    assert (chosen >= 0).sum() > 50


def test_interpod_bind_heavy_segments(tmp_path):
    """Two scheduler profiles chain the carry through consecutive scans;
    the second segment starts from the first segment's counts. The
    segmented stream, read back in stream order from the decision audit,
    replays clean."""
    from opensim_tpu.engine.schedconfig import load_scheduler_config

    cfg_path = tmp_path / "profiles.yaml"
    cfg_path.write_text(
        "kind: KubeSchedulerConfiguration\n"
        "profiles:\n"
        "  - schedulerName: default-scheduler\n"
        "  - schedulerName: lean\n"
        "    plugins:\n"
        "      score:\n"
        "        disabled:\n"
        "          - name: \"*\"\n"
    )
    cfg = load_scheduler_config(cfg_path)

    def patch(app_name, pods):
        # route the second workload's pods onto the lean profile
        for p in pods:
            if p.metadata.labels.get("app") == "excl":
                p.spec.scheduler_name = "lean"
                p.raw.setdefault("spec", {})["schedulerName"] = "lean"

    cluster = _ip_cluster(12)
    res = simulate(cluster, [AppResource("a", _ip_apps())], sched_config=cfg, patch_pods_fn=patch, explain=True)
    ctx = res.engine.explain_ctx
    assert res.engine.name == "xla" and len(ctx.segments) > 1
    _oracle_replay(cluster, ctx.prep, ctx.chosen, Oracle(cluster.nodes))


def test_interpod_forced_foreign_interleaving():
    """Forced pins spliced INTO an inter-pod template run (every 7th pod is
    pinned): the foreign binder's selector matches move the counts of a
    template that is not the one running."""
    cluster = _ip_cluster(12)

    def patch(app_name, pods):
        for i, p in enumerate(pods):
            if i % 7 == 3:
                p.spec.node_name = f"n{i % 12:03d}"

    prep, _chosen = _replay(cluster, _ip_apps(), Oracle, patch=patch)
    assert prep.forced.sum() > 5
