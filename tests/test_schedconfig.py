"""--default-scheduler-config parsing and effect tests."""

from opensim_tpu.engine.schedconfig import DEFAULT_CONFIG, load_scheduler_config
from opensim_tpu.engine.simulator import AppResource, simulate
from opensim_tpu.models import ResourceTypes
from opensim_tpu.models import fixtures as fx


def test_load_scheduler_config(tmp_path):
    p = tmp_path / "sched.yaml"
    p.write_text(
        """apiVersion: kubescheduler.config.k8s.io/v1beta1
kind: KubeSchedulerConfiguration
profiles:
  - plugins:
      score:
        enabled:
          - name: NodeResourcesLeastAllocated
            weight: 5
        disabled:
          - name: PodTopologySpread
      filter:
        disabled:
          - name: TaintToleration
"""
    )
    cfg = load_scheduler_config(str(p))
    assert cfg.w_least == 5.0
    assert cfg.w_spread == 0.0
    assert not cfg.f_taints
    assert cfg.f_fit  # untouched defaults remain
    assert cfg.w_balanced == 1.0


def test_disabled_taint_filter_schedules_onto_tainted_node(tmp_path):
    cluster = ResourceTypes()
    cluster.nodes.append(
        fx.make_fake_node(
            "tainted", "8", "16Gi", "110",
            fx.with_taints([{"key": "dedicated", "value": "x", "effect": "NoSchedule"}]),
        )
    )
    app = ResourceTypes()
    app.pods.append(fx.make_fake_pod("p", "100m", "128Mi"))

    # default config: blocked by the taint
    res = simulate(cluster, [AppResource("a", app)])
    assert len(res.unscheduled_pods) == 1

    cfg = DEFAULT_CONFIG._replace(f_taints=False)
    res = simulate(cluster, [AppResource("a", app)], sched_config=cfg)
    assert not res.unscheduled_pods


def test_default_config_file_is_identity(tmp_path):
    p = tmp_path / "empty.yaml"
    p.write_text("apiVersion: kubescheduler.config.k8s.io/v1beta1\nkind: KubeSchedulerConfiguration\n")
    assert load_scheduler_config(str(p)) == DEFAULT_CONFIG


def test_extra_plugins_registry():
    """WithExtraRegistry parity: out-of-tree jittable filter and score
    plugins compose into the pipeline."""
    import jax.numpy as jnp

    cluster = ResourceTypes()
    for i in range(3):
        cluster.nodes.append(fx.make_fake_node(f"n{i}", "8", "16Gi"))
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("w", 4, "100m", "128Mi"))

    def ban_node_zero(ec, st, u):
        return jnp.arange(ec.node_valid.shape[0]) != 0

    def prefer_node_two(ec, st, u, feasible):
        return jnp.where(jnp.arange(ec.node_valid.shape[0]) == 2, 100.0, 0.0)

    res = simulate(
        cluster,
        [AppResource("a", app)],
        extra_plugins=(("filter", ban_node_zero), ("score", prefer_node_two, 1000.0)),
    )
    assert not res.unscheduled_pods
    placed = {ns.node.metadata.name: len(ns.pods) for ns in res.node_status}
    assert placed.get("n0", 0) == 0  # custom filter banned it
    assert placed["n2"] == 4  # heavy custom score wins every bind


def test_extra_plugins_validation_and_reason():
    import pytest as _pytest

    cluster = ResourceTypes()
    cluster.nodes.append(fx.make_fake_node("n0", "8", "16Gi"))
    app = ResourceTypes()
    app.pods.append(fx.make_fake_pod("p", "100m", "128Mi"))

    with _pytest.raises(ValueError):
        simulate(cluster, [AppResource("a", app)], extra_plugins=[("filter", lambda *a: None)])
    with _pytest.raises(ValueError):
        simulate(cluster, [AppResource("a", app)], extra_plugins=(("prefilter", lambda *a: None),))
    with _pytest.raises(ValueError):
        simulate(cluster, [AppResource("a", app)], extra_plugins=(("score", lambda *a: None),))

    import jax.numpy as jnp

    def ban_all(ec, st, u):
        return jnp.zeros(ec.node_valid.shape[0], bool)

    res = simulate(cluster, [AppResource("a", app)], extra_plugins=(("filter", ban_all),))
    assert len(res.unscheduled_pods) == 1
    assert "out-of-tree plugin" in res.unscheduled_pods[0].reason


def test_node_prefer_avoid_pods():
    """NodePreferAvoidPods (node_prefer_avoid_pods.go:47-82): an RS-owned
    pod avoids the annotated node when its controller uid matches."""
    import json as _json

    from opensim_tpu.models import expand as _expand

    cluster = ResourceTypes()
    cluster.nodes.append(fx.make_fake_node("avoided", "8", "16Gi"))
    cluster.nodes.append(fx.make_fake_node("ok", "8", "16Gi"))
    rs = fx.make_fake_replica_set("web", 2, "100m", "128Mi")
    pods = _expand.pods_from_replica_set(rs)
    rs_uid = pods[0].metadata.owner_references[0].uid
    cluster.nodes[0].metadata.annotations["scheduler.alpha.kubernetes.io/preferAvoidPods"] = _json.dumps(
        {"preferAvoidPods": [{"podSignature": {"podController": {"kind": "ReplicaSet", "uid": rs_uid}}}]}
    )
    app = ResourceTypes()
    app.pods.extend(pods)  # pre-expanded pods keep the known controller uid
    res = simulate(cluster, [AppResource("a", app)])
    assert not res.unscheduled_pods
    placed = {ns.node.metadata.name: len(ns.pods) for ns in res.node_status}
    # the 10000-weight avoidance dominates: both replicas land on 'ok'
    assert placed.get("avoided", 0) == 0
    assert placed["ok"] == 2


# ---------------------------------------------------------------------------
# multi-profile + per-plugin args (pkg/simulator/utils.go:304-381 loads the
# full v1beta1 surface; VERDICT r3 #7)
# ---------------------------------------------------------------------------

import pytest

from opensim_tpu.engine.schedconfig import SchedulerProfiles


def _write(tmp_path, text):
    p = tmp_path / "sched.yaml"
    p.write_text(text)
    return str(p)


def test_multi_profile_selects_by_scheduler_name(tmp_path):
    """profiles[0] being a NAMED profile must not shadow default-scheduler:
    pods route by spec.schedulerName, defaulting to default-scheduler."""
    path = _write(tmp_path, """apiVersion: kubescheduler.config.k8s.io/v1beta1
kind: KubeSchedulerConfiguration
profiles:
  - schedulerName: custom-sched
    plugins:
      filter:
        disabled:
          - name: TaintToleration
  - schedulerName: default-scheduler
""")
    cfg = load_scheduler_config(path)
    assert isinstance(cfg, SchedulerProfiles)

    cluster = ResourceTypes()
    cluster.nodes.append(fx.make_fake_node(
        "tainted", "8", "16Gi", "110",
        fx.with_taints([{"key": "d", "value": "x", "effect": "NoSchedule"}]),
    ))
    app = ResourceTypes()
    app.pods.append(fx.make_fake_pod("p", "100m", "128Mi"))
    # the pod uses default-scheduler (second profile, defaults) -> taint blocks
    res = simulate(cluster, [AppResource("a", app)], sched_config=cfg)
    assert len(res.unscheduled_pods) == 1
    assert "taint" in res.unscheduled_pods[0].reason

    # a pod explicitly naming custom-sched gets that profile (taints off)
    app2 = ResourceTypes()
    pod = fx.make_fake_pod("p2", "100m", "128Mi")
    pod.spec.scheduler_name = "custom-sched"
    pod.raw.setdefault("spec", {})["schedulerName"] = "custom-sched"
    app2.pods.append(pod)
    res = simulate(cluster, [AppResource("a", app2)], sched_config=cfg)
    assert not res.unscheduled_pods


def test_unknown_profile_pod_gets_explicit_reason(tmp_path):
    path = _write(tmp_path, """kind: KubeSchedulerConfiguration
profiles:
  - schedulerName: default-scheduler
  - schedulerName: batch
""")
    cfg = load_scheduler_config(path)
    assert isinstance(cfg, SchedulerProfiles)
    cluster = ResourceTypes()
    cluster.nodes.append(fx.make_fake_node("n0", "8", "16Gi"))
    app = ResourceTypes()
    pod = fx.make_fake_pod("ghost", "100m", "128Mi")
    pod.spec.scheduler_name = "no-such-scheduler"
    pod.raw.setdefault("spec", {})["schedulerName"] = "no-such-scheduler"
    app.pods.append(pod)
    app.pods.append(fx.make_fake_pod("ok", "100m", "128Mi"))
    res = simulate(cluster, [AppResource("a", app)], sched_config=cfg)
    assert len(res.unscheduled_pods) == 1
    assert "no scheduler profile named 'no-such-scheduler'" in res.unscheduled_pods[0].reason
    placed = sum(len(ns.pods) for ns in res.node_status)
    assert placed == 1  # the default-profile pod scheduled normally


def test_differing_referenced_profiles_schedule_segmented(tmp_path):
    """Differing referenced profiles now schedule via segmentation (round
    5); the capacity-sweep path (resolve_profiles) still fails loudly —
    see test_non_segmentable_interleaving_raises for the segmented path's
    remaining loud failure."""
    from opensim_tpu.engine.schedconfig import resolve_profiles

    path = _write(tmp_path, """kind: KubeSchedulerConfiguration
profiles:
  - schedulerName: default-scheduler
  - schedulerName: lean
    plugins:
      score:
        disabled:
          - name: "*"
""")
    cfg = load_scheduler_config(path)
    cluster = ResourceTypes()
    cluster.nodes.append(fx.make_fake_node("n0", "8", "16Gi"))
    app = ResourceTypes()
    app.pods.append(fx.make_fake_pod("a1", "100m", "128Mi"))
    lean = fx.make_fake_pod("a2", "100m", "128Mi")
    lean.spec.scheduler_name = "lean"
    lean.raw.setdefault("spec", {})["schedulerName"] = "lean"
    app.pods.append(lean)
    res = simulate(cluster, [AppResource("a", app)], sched_config=cfg)
    assert not res.unscheduled_pods
    assert sum(len(ns.pods) for ns in res.node_status) == 2
    assert "segmented multi-profile" in res.engine.skipped["megakernel"]

    # the single-config resolver (scenario sweeps) still refuses
    pods = [p for ns in res.node_status for p in ns.pods]
    with pytest.raises(ValueError, match="differing plugin configurations"):
        resolve_profiles(cfg, pods, ["cpu", "memory"], forced=[False] * len(pods))


def test_fit_ignored_resources(tmp_path):
    """NodeResourcesFitArgs.ignoredResources: a pod over-requesting an
    ignored extended resource schedules anyway (fit skips the column)."""
    path = _write(tmp_path, """kind: KubeSchedulerConfiguration
profiles:
  - schedulerName: default-scheduler
    pluginConfig:
      - name: NodeResourcesFit
        args:
          ignoredResources:
            - example.com/widget
""")
    cfg = load_scheduler_config(path)
    assert isinstance(cfg, SchedulerProfiles)

    cluster = ResourceTypes()
    cluster.nodes.append(fx.make_fake_node("n0", "8", "16Gi"))
    app = ResourceTypes()
    app.pods.append(fx.make_fake_pod(
        "widgety", "100m", "128Mi",
        fx.with_requests({"example.com/widget": "4"}),
    ))
    # without the config: no node declares the resource -> unschedulable
    res = simulate(cluster, [AppResource("a", app)])
    assert len(res.unscheduled_pods) == 1
    assert "Insufficient example.com/widget" in res.unscheduled_pods[0].reason
    # with ignoredResources: schedules
    res = simulate(cluster, [AppResource("a", app)], sched_config=cfg)
    assert not res.unscheduled_pods


def test_fit_ignored_resource_groups(tmp_path):
    path = _write(tmp_path, """kind: KubeSchedulerConfiguration
profiles:
  - schedulerName: default-scheduler
    pluginConfig:
      - name: NodeResourcesFit
        args:
          ignoredResourceGroups:
            - example.com
""")
    cfg = load_scheduler_config(path)
    cluster = ResourceTypes()
    cluster.nodes.append(fx.make_fake_node("n0", "8", "16Gi"))
    app = ResourceTypes()
    app.pods.append(fx.make_fake_pod(
        "widgety", "100m", "128Mi",
        fx.with_requests({"example.com/widget": "4"}),
    ))
    res = simulate(cluster, [AppResource("a", app)], sched_config=cfg)
    assert not res.unscheduled_pods


def test_unsupported_fields_fail_loudly(tmp_path):
    # unknown plugin name in an enable list
    with pytest.raises(ValueError, match="unknown plugin 'Fancy'"):
        load_scheduler_config(_write(tmp_path, """kind: KubeSchedulerConfiguration
profiles:
  - plugins:
      score:
        enabled:
          - name: Fancy
"""))
    # percentageOfNodesToScore != 100
    with pytest.raises(ValueError, match="percentageOfNodesToScore=50"):
        load_scheduler_config(_write(tmp_path, """kind: KubeSchedulerConfiguration
percentageOfNodesToScore: 50
profiles:
  - plugins: {}
"""))
    # outcome-changing plugin args
    with pytest.raises(ValueError, match="PodTopologySpread"):
        load_scheduler_config(_write(tmp_path, """kind: KubeSchedulerConfiguration
profiles:
  - pluginConfig:
      - name: PodTopologySpread
        args:
          defaultConstraints:
            - maxSkew: 1
"""))
    # non-default hardPodAffinityWeight
    with pytest.raises(ValueError, match="hardPodAffinityWeight=7"):
        load_scheduler_config(_write(tmp_path, """kind: KubeSchedulerConfiguration
profiles:
  - pluginConfig:
      - name: InterPodAffinity
        args:
          hardPodAffinityWeight: 7
"""))
    # unknown extension point
    with pytest.raises(ValueError, match="extension point 'scorer'"):
        load_scheduler_config(_write(tmp_path, """kind: KubeSchedulerConfiguration
profiles:
  - plugins:
      scorer:
        enabled:
          - name: Simon
"""))
    # duplicate profile names
    with pytest.raises(ValueError, match="duplicate profile"):
        load_scheduler_config(_write(tmp_path, """kind: KubeSchedulerConfiguration
profiles:
  - schedulerName: default-scheduler
  - schedulerName: default-scheduler
"""))


def test_vacuous_plugin_args_accepted(tmp_path):
    """DefaultPreemption / VolumeBinding args cannot change a simulation's
    outcome in either implementation (PARITY.md) and must be accepted."""
    path = _write(tmp_path, """kind: KubeSchedulerConfiguration
profiles:
  - schedulerName: default-scheduler
    pluginConfig:
      - name: DefaultPreemption
        args:
          minCandidateNodesPercentage: 10
      - name: VolumeBinding
        args:
          bindTimeoutSeconds: 600
""")
    cfg = load_scheduler_config(path)
    assert cfg == DEFAULT_CONFIG  # single default profile, no mapped args


# ---------------------------------------------------------------------------
# --tie-break=sample[:seed] (selectHost reservoir sampling,
# generic_scheduler.go:188-210; VERDICT r3 #5)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_tie_break_sample_covers_equal_score_set():
    """Over seeds, sampled placements must cover more than one member of
    the equal-score node set while structural results stay identical to
    the deterministic run — and every sampled bind stays score-optimal."""
    import numpy as np

    from opensim_tpu.engine.scheduler import pad_pod_stream, schedule_pods
    from opensim_tpu.engine.simulator import parse_tie_break, prepare

    assert parse_tie_break("lowest") is None
    assert parse_tie_break("sample") == 0
    assert parse_tie_break("sample:7") == 7
    with pytest.raises(ValueError):
        parse_tie_break("bogus")

    cluster = ResourceTypes()
    for i in range(6):  # identical nodes -> every score ties
        cluster.nodes.append(fx.make_fake_node(f"n{i}", "8", "16Gi"))
    app = ResourceTypes()
    app.pods.append(fx.make_fake_pod("p", "100m", "128Mi"))
    apps = [AppResource("a", app)]

    det = simulate(cluster, apps, node_pad=8)
    det_node = det.node_status[0].node.metadata.name if det.node_status[0].pods else None
    assert not det.unscheduled_pods

    prep = prepare(cluster, apps, node_pad=8)
    P = len(prep.ordered)
    t, v, f = pad_pod_stream(prep.tmpl_ids, np.ones(P, bool), prep.forced)
    landed = set()
    for seed in range(10):
        out = schedule_pods(
            prep.ec, prep.st0, t, v, f, features=prep.features, tie_seed=seed
        )
        c = int(np.asarray(out.chosen)[0])
        assert c >= 0  # structural parity: still scheduled
        landed.add(c)
    assert len(landed) > 1, "sampling never left the lowest index"

    res = simulate(cluster, apps, node_pad=8, tie_seed=3)
    assert not res.unscheduled_pods
    assert sum(len(ns.pods) for ns in res.node_status) == 1


def test_tie_break_sampled_binds_stay_score_optimal():
    """A sampled run on an affinity-bearing workload must keep every bind
    score-optimal per the independent kube oracle (sampling only permutes
    WITHIN the max set, never off it)."""
    import random as _random

    import numpy as np

    from test_k8s_oracle import _replay_with_scores, random_app, random_cluster

    from opensim_tpu.engine.scheduler import pad_pod_stream, schedule_pods
    from opensim_tpu.engine.simulator import prepare

    rng = _random.Random(29)
    cluster = random_cluster(rng, 8)
    app = random_app(rng, 5)
    prep = prepare(cluster, [AppResource("oracle", app)], node_pad=8)
    P = len(prep.ordered)
    t, v, f = pad_pod_stream(prep.tmpl_ids, np.ones(P, bool), prep.forced)
    out = schedule_pods(
        prep.ec, prep.st0, t, v, f, features=prep.features, tie_seed=11
    )
    chosen = np.asarray(out.chosen)[:P]
    assert _replay_with_scores(prep, cluster, chosen) == 0


def test_forced_pod_scheduler_name_never_routes(tmp_path):
    """A pre-bound (forced) pod bypasses every scheduler — its
    schedulerName must neither raise the differing-profiles error nor mark
    it invalid (review regression)."""
    path = _write(tmp_path, """kind: KubeSchedulerConfiguration
profiles:
  - schedulerName: default-scheduler
  - schedulerName: lean
    plugins:
      score:
        disabled:
          - name: "*"
""")
    cfg = load_scheduler_config(path)
    cluster = ResourceTypes()
    cluster.nodes.append(fx.make_fake_node("n0", "8", "16Gi"))
    bound = fx.make_fake_pod("pre", "100m", "128Mi", fx.with_node_name("n0"))
    bound.raw.setdefault("spec", {})["schedulerName"] = "lean"
    cluster.pods.append(bound)
    app = ResourceTypes()
    app.pods.append(fx.make_fake_pod("new", "100m", "128Mi"))
    res = simulate(cluster, [AppResource("a", app)], sched_config=cfg)
    assert not res.unscheduled_pods
    assert sum(len(ns.pods) for ns in res.node_status) == 2


def test_sweep_auto_masks_unknown_profile_pods(tmp_path):
    """Scenario sweeps must apply the same profile routing as simulate():
    unknown-profile pods are masked out of every scenario so capacity
    verdicts don't chase pods that can never schedule."""
    import numpy as np

    from opensim_tpu.engine.simulator import prepare
    from opensim_tpu.parallel import scenarios

    path = _write(tmp_path, """kind: KubeSchedulerConfiguration
profiles:
  - schedulerName: default-scheduler
  - schedulerName: batch
""")
    cfg = load_scheduler_config(path)
    cluster = ResourceTypes()
    for i in range(3):
        cluster.nodes.append(fx.make_fake_node(f"n{i}", "8", "16Gi"))
    app = ResourceTypes()
    ghost = fx.make_fake_pod("ghost", "100m", "128Mi")
    ghost.spec.scheduler_name = "nope"
    ghost.raw.setdefault("spec", {})["schedulerName"] = "nope"
    app.pods.append(ghost)
    app.pods.append(fx.make_fake_pod("ok", "100m", "128Mi"))
    prep = prepare(cluster, [AppResource("a", app)], node_pad=8)
    P = len(prep.ordered)
    N = prep.ec.node_valid.shape[0]
    node_valid = np.zeros((2, N), bool)
    node_valid[:, :3] = True
    res = scenarios.sweep_auto(prep, node_valid, np.ones((2, P), bool), config=cfg)
    # the ghost pod is masked (not counted unscheduled), the ok pod binds
    assert list(np.asarray(res.unscheduled)) == [0, 0]
    ghost_idx = [i for i, p in enumerate(prep.ordered)
                 if p.metadata.name == "ghost"][0]
    assert (np.asarray(res.chosen)[:, ghost_idx] == -1).all()


# ---------------------------------------------------------------------------
# segmented multi-profile scheduling (VERDICT r4 #7; utils.go:304-381)
# ---------------------------------------------------------------------------


def _two_profile_config(tmp_path):
    p = tmp_path / "profiles.yaml"
    p.write_text(
        "apiVersion: kubescheduler.config.k8s.io/v1beta1\n"
        "kind: KubeSchedulerConfiguration\n"
        "profiles:\n"
        "  - schedulerName: default-scheduler\n"
        "  - schedulerName: packer\n"
        "    plugins:\n"
        "      score:\n"
        "        disabled:\n"
        "          - name: NodeResourcesBalancedAllocation\n"
        "          - name: NodeResourcesLeastAllocated\n"
    )
    return load_scheduler_config(str(p))


def test_segmented_two_differing_profiles_schedule(tmp_path):
    """Two differing profiles in one stream: consecutive scans share the
    carry; each segment runs its own plugin config (the packer profile
    packs where the default spreads)."""
    cfg = _two_profile_config(tmp_path)
    cluster = ResourceTypes()
    for i in range(4):
        cluster.nodes.append(fx.make_fake_node(f"n{i}", "8", "16Gi"))
    rt = ResourceTypes()
    d1 = fx.make_fake_deployment("default-app", 6, "500m", "1Gi")
    d2 = fx.make_fake_deployment("packer-app", 6, "500m", "1Gi")
    d2.template_spec.scheduler_name = "packer"
    rt.deployments.extend([d1, d2])
    res = simulate(cluster, [AppResource("a", rt)], sched_config=cfg)
    assert not res.unscheduled_pods
    assert res.engine.name in ("native", "xla")
    assert "segmented multi-profile" in res.engine.skipped["megakernel"]
    by_app = {}
    for ns in res.node_status:
        for p in ns.pods:
            app = p.metadata.labels.get("app", "")
            by_app.setdefault(app, {}).setdefault(ns.node.metadata.name, 0)
            by_app[app][ns.node.metadata.name] += 1
    # default profile spreads its 6 pods; the packer profile concentrates
    assert len(by_app["default-app"]) == 4
    assert max(by_app["packer-app"].values()) >= 4


def test_segmented_profiles_share_the_carry(tmp_path):
    """Segment 2 must see segment 1's binds: a full node cannot be reused,
    and a failing pod's reason reflects the shared usage."""
    cfg = _two_profile_config(tmp_path)
    cluster = ResourceTypes()
    cluster.nodes.append(fx.make_fake_node("n0", "8", "16Gi"))
    cluster.nodes.append(fx.make_fake_node("n1", "8", "16Gi"))
    rt = ResourceTypes()
    d1 = fx.make_fake_deployment("filler", 2, "7", "1Gi")  # one per node
    d2 = fx.make_fake_deployment("late", 2, "4", "1Gi")
    d2.template_spec.scheduler_name = "packer"
    rt.deployments.extend([d1, d2])
    res = simulate(cluster, [AppResource("a", rt)], sched_config=cfg)
    # both nodes carry one 7-cpu filler; neither fits a 4-cpu late pod
    assert len(res.unscheduled_pods) == 2
    for up in res.unscheduled_pods:
        assert "0/2 nodes are available: 2 Insufficient cpu." == up.reason


def test_segmented_unknown_profile_reason(tmp_path):
    cfg = _two_profile_config(tmp_path)
    cluster = ResourceTypes()
    cluster.nodes.append(fx.make_fake_node("n0", "8", "16Gi"))
    rt = ResourceTypes()
    d1 = fx.make_fake_deployment("ok", 1, "500m", "1Gi")
    d2 = fx.make_fake_deployment("ghost", 1, "500m", "1Gi")
    d2.template_spec.scheduler_name = "packer"
    d3 = fx.make_fake_deployment("lost", 1, "500m", "1Gi")
    d3.template_spec.scheduler_name = "no-such-profile"
    rt.deployments.extend([d1, d2, d3])
    res = simulate(cluster, [AppResource("a", rt)], sched_config=cfg)
    assert len(res.unscheduled_pods) == 1
    assert "no scheduler profile named 'no-such-profile'" in res.unscheduled_pods[0].reason


def test_non_segmentable_interleaving_raises(tmp_path):
    """A pathological alternation (one scan per pod) still fails loudly."""
    from opensim_tpu.engine.schedconfig import MAX_PROFILE_SEGMENTS

    cfg = _two_profile_config(tmp_path)
    cluster = ResourceTypes()
    cluster.nodes.append(fx.make_fake_node("n0", "64", "64Gi"))
    rt = ResourceTypes()
    for i in range(MAX_PROFILE_SEGMENTS + 2):
        pod = fx.make_fake_pod(f"p{i}", "10m", "16Mi")
        if i % 2:
            pod.spec.scheduler_name = "packer"
        rt.pods.append(pod)
    with pytest.raises(ValueError, match="non-segmentable"):
        simulate(cluster, [AppResource("a", rt)], sched_config=cfg)


def test_differing_profiles_capacity_sweep(tmp_path):
    """Full `simon apply` with DIFFERING profiles and a cluster that needs
    new nodes: the batched sweep cannot run one pipeline, so the planner
    probes candidate counts with segmented masked simulations and still
    finds the minimum node count."""
    import yaml as _yaml

    from opensim_tpu.planner.apply import Applier, Options

    cfgdir = tmp_path / "cluster"
    cfgdir.mkdir()
    (cfgdir / "node.yaml").write_text(
        _yaml.safe_dump(fx.make_fake_node("n0", "8", "16Gi").raw)
    )
    newnode = tmp_path / "newnode"
    newnode.mkdir()
    (newnode / "node.yaml").write_text(
        _yaml.safe_dump(fx.make_fake_node("tmpl", "16", "32Gi").raw)
    )
    appdir = tmp_path / "app"
    appdir.mkdir()
    d1 = fx.make_fake_deployment("default-app", 6, "2", "2Gi")
    d2 = fx.make_fake_deployment("packer-app", 6, "2", "2Gi")
    d2.template_spec.scheduler_name = "packer"
    d2.raw["spec"]["template"].setdefault("spec", {})["schedulerName"] = "packer"
    (appdir / "apps.yaml").write_text(
        "---\n".join(_yaml.safe_dump(w.raw) for w in (d1, d2))
    )
    sched = tmp_path / "profiles.yaml"
    sched.write_text(
        "apiVersion: kubescheduler.config.k8s.io/v1beta1\n"
        "kind: KubeSchedulerConfiguration\n"
        "profiles:\n"
        "  - schedulerName: default-scheduler\n"
        "  - schedulerName: packer\n"
        "    plugins:\n"
        "      score:\n"
        "        disabled:\n"
        "          - name: NodeResourcesBalancedAllocation\n"
        "          - name: NodeResourcesLeastAllocated\n"
    )
    cfg = tmp_path / "simon-config.yaml"
    cfg.write_text(
        "apiVersion: simon/v1alpha1\nkind: Config\nmetadata: {name: t}\n"
        "spec:\n"
        f"  cluster: {{customConfig: '{cfgdir}'}}\n"
        f"  newNode: '{newnode}'\n"
        "  appList:\n"
        f"    - {{name: apps, path: '{appdir}'}}\n"
    )
    out = tmp_path / "report.txt"
    rc = Applier(
        Options(
            simon_config=str(cfg),
            default_scheduler_config=str(sched),
            output_file=str(out),
            max_new_nodes=8,
        )
    ).run()
    text = out.read_text()
    assert rc == 0, text
    assert "Simulation success!" in text
    # 12 pods x 2 cpu = 24 cpu; n0 has 8 => at least 1 new 16-cpu node
    assert "(added" in text
    assert "segmented multi-profile" in text  # engine footer names the path


def test_sweep_auto_mixed_profiles_matches_solo_segmented(tmp_path):
    """The ISSUE 8 satellite: DIFFERING profiles no longer raise in a
    scenario sweep — they route through per-segment scans sharing each
    scenario's carry, and every scenario's placements equal a solo
    segmented simulate of that sub-cluster."""
    import numpy as np

    from opensim_tpu.engine.simulator import (
        prepare, restore_bind_state, snapshot_bind_state,
    )
    from opensim_tpu.parallel import scenarios

    cfg = _two_profile_config(tmp_path)
    cluster = ResourceTypes()
    for i in range(6):
        cluster.nodes.append(fx.make_fake_node(f"n{i}", "8", "16Gi"))
    rt = ResourceTypes()
    d1 = fx.make_fake_deployment("default-app", 5, "500m", "1Gi")
    d2 = fx.make_fake_deployment("packer-app", 5, "500m", "1Gi")
    d2.template_spec.scheduler_name = "packer"
    rt.deployments.extend([d1, d2])
    prep = prepare(cluster, [AppResource("a", rt)], node_pad=8)
    P = len(prep.ordered)
    N = int(np.asarray(prep.ec_np.node_valid).shape[0])
    ks = (3, 4, 6)
    node_valid = np.zeros((len(ks), N), bool)
    for s, k in enumerate(ks):
        node_valid[s, :k] = True
    res = scenarios.sweep_auto(prep, node_valid, np.ones((len(ks), P), bool), config=cfg)

    snap = snapshot_bind_state(prep)
    for s, k in enumerate(ks):
        sub = ResourceTypes(nodes=cluster.nodes[:k])
        solo = simulate(sub, [], prep=prep, node_valid=node_valid[s], sched_config=cfg)
        restore_bind_state(prep, snap)
        ch = np.asarray(res.chosen)[s]
        assert len(solo.unscheduled_pods) == int(np.asarray(res.unscheduled)[s])
        placed = {
            f"{p.metadata.namespace}/{p.metadata.name}": ns.node.metadata.name
            for ns in solo.node_status
            for p in ns.pods
        }
        for i, pod in enumerate(prep.ordered):
            key = f"{pod.metadata.namespace}/{pod.metadata.name}"
            got = prep.meta.node_names[ch[i]] if ch[i] >= 0 else None
            assert placed.get(key) == got, (s, key)


def test_sweep_auto_single_profile_still_routes_one_config(tmp_path):
    """A multi-profile config whose referenced profiles RESOLVE identically
    keeps the single-config sweep path (no segmented scans)."""
    import numpy as np

    from opensim_tpu.engine.simulator import prepare
    from opensim_tpu.parallel import scenarios

    cfg = _two_profile_config(tmp_path)
    cluster = ResourceTypes()
    for i in range(4):
        cluster.nodes.append(fx.make_fake_node(f"n{i}", "8", "16Gi"))
    rt = ResourceTypes()
    rt.deployments.append(fx.make_fake_deployment("only-default", 4, "500m", "1Gi"))
    prep = prepare(cluster, [AppResource("a", rt)], node_pad=8)
    P = len(prep.ordered)
    N = int(np.asarray(prep.ec_np.node_valid).shape[0])
    node_valid = np.zeros((2, N), bool)
    node_valid[:, :4] = True
    res = scenarios.sweep_auto(prep, node_valid, np.ones((2, P), bool), config=cfg)
    assert list(np.asarray(res.unscheduled)) == [0, 0]


# ---------------------------------------------------------------------------
# RequestedToCapacityRatio (kube 1.21's in-tree bin-packing score)
# ---------------------------------------------------------------------------

#: the Resource Bin Packing page's profile, LeastAllocated off as the
#: >= 1.23 scoring strategy replaces it
BINPACK_PROFILE = """kind: KubeSchedulerConfiguration
apiVersion: kubescheduler.config.k8s.io/v1beta1
profiles:
  - schedulerName: default-scheduler
    plugins:
      score:
        disabled:
          - name: NodeResourcesLeastAllocated
        enabled:
          - name: RequestedToCapacityRatio
            weight: 1
    pluginConfig:
      - name: RequestedToCapacityRatio
        args:
          shape:
            - {utilization: 0, score: 0}
            - {utilization: 100, score: 10}
          resources:
            - {name: cpu, weight: 1}
            - {name: memory, weight: 1}
"""


def test_the_bin_packing_profile_parses_to_its_config(tmp_path):
    from opensim_tpu.engine.schedconfig import kernel_gap, profile_of

    cfg = load_scheduler_config(_write(tmp_path, BINPACK_PROFILE))
    # cpu and memory are columns of every vocabulary: no routing needed
    assert cfg == DEFAULT_CONFIG._replace(
        w_least=0.0, w_rtcr=1.0, rtcr_shape=((0.0, 0.0), (100.0, 100.0)), rtcr_resources=((0, 1.0), (1, 1.0)),
    )
    assert profile_of(cfg) == "rtcr" and kernel_gap(cfg) is None


def test_rtcr_defaults_and_an_extended_resource(tmp_path):
    # no resources: cpu and memory at weight 1; a weight of 0 is 1 (the v1beta1 defaults)
    cfg = load_scheduler_config(_write(tmp_path, """kind: KubeSchedulerConfiguration
profiles:
  - plugins:
      score:
        enabled: [{name: RequestedToCapacityRatio, weight: 4}]
    pluginConfig:
      - name: RequestedToCapacityRatio
        args:
          shape: [{utilization: 20, score: 3}]
"""))
    assert (cfg.w_rtcr, cfg.w_least, cfg.rtcr_shape, cfg.rtcr_resources) == (4.0, 1.0, ((20.0, 30.0),), ((0, 1.0), (1, 1.0)))
    # an extended resource resolves against the cluster's vocabulary
    got = load_scheduler_config(_write(tmp_path, """kind: KubeSchedulerConfiguration
profiles:
  - plugins:
      score:
        enabled: [{name: RequestedToCapacityRatio}]
    pluginConfig:
      - name: RequestedToCapacityRatio
        args:
          shape: [{utilization: 0, score: 10}, {utilization: 100, score: 0}]
          resources: [{name: example.com/foo, weight: 0}, {name: memory, weight: 3}, {name: example.com/none, weight: 2}]
"""))
    assert isinstance(got, SchedulerProfiles)
    from opensim_tpu.engine.schedconfig import resolve_profiles

    cluster = ResourceTypes()
    cluster.nodes.append(fx.make_fake_node("n0", "8", "16Gi", "110", fx.with_allocatable({"example.com/foo": "4"})))
    app = ResourceTypes()
    app.pods.append(fx.make_fake_pod("p", "1", "1Gi"))
    from opensim_tpu.engine.simulator import prepare

    prep = prepare(cluster, [AppResource("a", app)])
    cfg, _invalid = resolve_profiles(got, prep.ordered, prep.meta.resource_names)
    foo = prep.meta.resource_names.index("example.com/foo")
    assert cfg.rtcr_resources == ((foo, 1.0), (1, 3.0), (-1, 2.0))
    assert cfg.rtcr_shape == ((0.0, 100.0), (100.0, 0.0))
    # a profile that does not enable the plugin scores nothing with its args: no config
    off = load_scheduler_config(_write(tmp_path, """kind: KubeSchedulerConfiguration
profiles:
  - pluginConfig:
      - name: RequestedToCapacityRatio
        args:
          shape: [{utilization: 0, score: 0}]
"""))
    assert off == DEFAULT_CONFIG


RTCR_INVALID = {
    "utilization_not_increasing": ("shape: [{utilization: 50, score: 1}, {utilization: 50, score: 2}]",
                                   "utilization values must be sorted in increasing order"),
    "utilization_decreasing": ("shape: [{utilization: 60, score: 1}, {utilization: 20, score: 2}]",
                               "utilization values must be sorted in increasing order"),
    "utilization_over_100": ("shape: [{utilization: 101, score: 1}]", "utilization 101 is not in the range 0..100"),
    "score_over_10": ("shape: [{utilization: 0, score: 11}]", "score 11 is not in the range 0..10"),
    "score_negative": ("shape: [{utilization: 0, score: -1}]", "score -1 is not in the range 0..10"),
    "weight_under_1": ("shape: [{utilization: 0, score: 1}]\n          resources: [{name: cpu, weight: -1}]",
                       "weight -1 is under 1"),
    "empty_shape": ("shape: []", "at least one point must be specified"),
    "no_shape": ("resources: [{name: cpu, weight: 1}]", "at least one point must be specified"),
    "not_whole": ("shape: [{utilization: 12.5, score: 1}]", "is not a whole number"),
    "unknown_field": ("shape: [{utilization: 0, score: 1}]\n          scoringStrategy: x", "scoringStrategy is not supported"),
}


@pytest.mark.parametrize("case", sorted(RTCR_INVALID))
def test_rtcr_args_are_validated_as_kube_validates_them(tmp_path, case):
    args, message = RTCR_INVALID[case]
    with pytest.raises(ValueError, match=message):
        load_scheduler_config(_write(tmp_path, f"""kind: KubeSchedulerConfiguration
profiles:
  - plugins:
      score:
        enabled: [{{name: RequestedToCapacityRatio}}]
    pluginConfig:
      - name: RequestedToCapacityRatio
        args:
          {args}
"""))


def test_rtcr_enabled_without_args_fails_loudly(tmp_path):
    with pytest.raises(ValueError, match="RequestedToCapacityRatio is enabled without pluginConfig args"):
        load_scheduler_config(_write(tmp_path, """kind: KubeSchedulerConfiguration
profiles:
  - plugins:
      score:
        enabled: [{name: RequestedToCapacityRatio}]
"""))


NO_CONFIG = {
    "no_profiles": "kind: KubeSchedulerConfiguration\n",
    "one_default_profile": "kind: KubeSchedulerConfiguration\nprofiles:\n  - schedulerName: default-scheduler\n",
    "one_profile_at_the_default_weights": """kind: KubeSchedulerConfiguration
profiles:
  - plugins:
      score:
        enabled: [{name: NodeResourcesLeastAllocated, weight: 1}, {name: PodTopologySpread, weight: 2}]
""",
}


@pytest.mark.parametrize("case", sorted(NO_CONFIG))
def test_a_file_that_changes_nothing_is_no_config_and_stays_on_the_kernel(tmp_path, monkeypatch, case):
    from opensim_tpu.engine import select

    cfg = load_scheduler_config(_write(tmp_path, NO_CONFIG[case]))
    assert cfg == DEFAULT_CONFIG
    monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")
    monkeypatch.delenv("OPENSIM_DISABLE_FASTPATH", raising=False)
    cluster = ResourceTypes()
    cluster.nodes.append(fx.make_fake_node("n0", "8", "16Gi"))
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("web", 3, "1", "1Gi"))
    from opensim_tpu.engine.simulator import prepare

    prep = prepare(cluster, [AppResource("a", app)], node_pad=128)
    assert select.ladder(prep, select.Ask(sched_config=cfg))["megakernel"] is None
    res = simulate(cluster, [AppResource("a", app)], sched_config=cfg)
    assert res.engine.name == "megakernel" and not res.unscheduled_pods
