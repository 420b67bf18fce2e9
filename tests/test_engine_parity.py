"""The two engines against each other, and the paths only the XLA scan runs.

The megakernel (in the Pallas interpreter here) against the XLA scan,
placement for placement, on fixtures that run a template through hundreds
of binds, soft spread, forced binds interleaved, score weights, failures at
the tail, and a scenario sweep. Then the XLA-only paths: a segmented sweep
and a two-profile stream replayed through the kube oracle, request batches
of 1, 3 and 8 riders against their solo runs, a campaign step from a
caller's scan state, and the CLI's backend choices."""

import json
import os
import sys

import numpy as np
import pytest

from opensim_tpu.engine import fastpath, select
from opensim_tpu.engine.schedconfig import SchedulerConfig
from opensim_tpu.engine.scheduler import pad_pod_stream, schedule_pods
from opensim_tpu.engine.simulator import AppResource, prepare, simulate
from opensim_tpu.models import ResourceTypes, fixtures as fx

sys.path.insert(0, os.path.dirname(__file__))
from test_k8s_oracle import ExtOracle, Oracle  # noqa: E402
import test_xla_oracle_fuzz as fz  # noqa: E402
from test_xla_oracle_fuzz import _oracle_replay, _ports_fuzz_case, _tmpl_annotate  # noqa: E402

ZONE = "topology.kubernetes.io/zone"


def _run_cluster(n_nodes=24):
    cluster = ResourceTypes()
    for i in range(n_nodes):
        cluster.nodes.append(
            fx.make_fake_node(f"n{i:03d}", "8", "16Gi", "110", fx.with_labels({ZONE: f"z{i % 3}"}))
        )
    return cluster


def _xla(prep, config=None):
    P = len(prep.ordered)
    t, v, f = pad_pod_stream(prep.tmpl_ids, np.ones(P, bool), prep.forced)
    out = schedule_pods(prep.ec, prep.st0, t, v, f, features=prep.features, config=config)
    return np.asarray(out.chosen)[:P], np.asarray(out.final_state.used)


def _assert_kernel_matches(prep, config=None):
    """The kernel's placements and final usage equal the XLA scan's."""
    assert fastpath.why_not(prep, config) is None
    P = len(prep.ordered)
    want_chosen, want_used = _xla(prep, config)
    chosen, used, *_ = fastpath.schedule(prep, prep.tmpl_ids, np.ones(P, bool), prep.forced,
                                         config=config, interpret=True)
    np.testing.assert_array_equal(np.asarray(chosen), want_chosen)
    np.testing.assert_allclose(np.asarray(used)[: want_used.shape[0]], want_used, rtol=1e-6)
    return want_chosen


# ---------------------------------------------------------------------------
# the megakernel against the XLA scan
# ---------------------------------------------------------------------------


def test_long_run_with_failures():
    """One workload far over capacity: hundreds of binds, then failures."""
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("big", 600, "500m", "1Gi"))
    chosen = _assert_kernel_matches(prepare(_run_cluster(), [AppResource("a", app)], node_pad=128))
    assert (chosen >= 0).sum() > 300 and (chosen < 0).sum() > 100


def test_with_soft_spread():
    app = ResourceTypes()
    app.deployments.append(
        fx.make_fake_deployment(
            "spr", 200, "250m", "512Mi",
            fx.with_topology_spread([
                {"maxSkew": 2, "topologyKey": ZONE, "whenUnsatisfiable": "ScheduleAnyway",
                 "labelSelector": {"matchLabels": {"app": "spr"}}},
            ]),
        )
    )
    app.deployments.append(fx.make_fake_deployment("other", 150, "100m", "256Mi"))
    _assert_kernel_matches(prepare(_run_cluster(), [AppResource("a", app)], node_pad=128))


def test_forced_interleaving():
    """Pre-bound pods interleave foreign binds into a template run."""
    cluster = _run_cluster(8)
    for i in range(40):
        cluster.pods.append(
            fx.make_fake_pod(f"bound-{i:02d}", "250m", "512Mi", fx.with_node_name(f"n{i % 8:03d}"))
        )
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("run", 120, "500m", "1Gi"))
    prep = prepare(cluster, [AppResource("a", app)], node_pad=128)
    assert prep.forced.sum() == 40
    _assert_kernel_matches(prep)


def test_sched_config_weights_and_disables():
    """Score weights are the kernel's to compute; a disabled filter is not:
    the kernel serves the weights alone, and with the disabled filter the
    XLA scan replays clean through the oracle."""
    cluster = _run_cluster(12)
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("w", 80, "500m", "1Gi"))
    prep = prepare(cluster, [AppResource("a", app)], node_pad=128)
    weights = SchedulerConfig(w_least=3.0, w_balanced=0.0, w_spread=5.0)
    _assert_kernel_matches(prep, config=weights)
    disables = weights._replace(f_ports=False)
    assert fastpath.why_not(prep, disables)[0] == "sched_config:disabled_filter"
    chosen, _used = _xla(prep, config=disables)
    _oracle_replay(cluster, prep, chosen, Oracle(cluster.nodes))


@pytest.mark.parametrize("family, seed", [
    ("_ip_fuzz_case", 211), ("_ports_fuzz_case", 223), ("_gpu_fuzz_case", 307), ("_local_fuzz_case", 401),
])
def test_fuzz_family_kernel_matches_xla(family, seed):
    """One workload of each carry class of the oracle fuzz, on the kernel."""
    import random

    cluster, app = getattr(fz, family)(random.Random(seed))
    _assert_kernel_matches(prepare(cluster, [AppResource("fuzz", app)], node_pad=128))


def test_fit_disabled_zeroes_insufficient():
    """With NodeResourcesFit disabled the XLA scan reports zero per-resource
    shortfalls even when a later filter fails."""
    cluster = ResourceTypes()
    for i in range(2):
        cluster.nodes.append(fx.make_fake_node(f"n{i:03d}", "2", "4Gi", "110"))
    app = ResourceTypes()
    app.deployments.append(
        fx.make_fake_deployment(
            "blocked", 2, "3", "1Gi",
            fx.with_affinity({"podAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": [
                {"labelSelector": {"matchLabels": {"app": "absent"}}, "topologyKey": "kubernetes.io/hostname"},
            ]}}),
        )
    )
    prep = prepare(cluster, [AppResource("a", app)], node_pad=128)
    P = len(prep.ordered)
    t, v, f = pad_pod_stream(prep.tmpl_ids, np.ones(P, bool), prep.forced)
    fit_off = schedule_pods(prep.ec, prep.st0, t, v, f, features=prep.features, config=SchedulerConfig(f_fit=False))
    fit_on = schedule_pods(prep.ec, prep.st0, t, v, f, features=prep.features)
    assert (np.asarray(fit_off.chosen)[:P] < 0).all()
    assert np.asarray(fit_off.insufficient)[:P].sum() == 0
    assert np.asarray(fit_on.insufficient)[:P].sum() > 0


def _reasons(cluster_fn, apps, monkeypatch, engine):
    if engine == "megakernel":
        monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")
    else:
        monkeypatch.delenv("OPENSIM_FASTPATH", raising=False)
    res = simulate(cluster_fn(), apps)
    return res, sorted(u.reason for u in res.unscheduled_pods)


def test_failure_reasons_identical_through_simulate(monkeypatch):
    """The kernel's failure reasons, read from its final carry when nothing
    binds after the first failure, equal the XLA scan's."""
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("fine", 6, "500m", "1Gi"))
    app.deployments.append(fx.make_fake_deployment("fat", 4, "32", "64Gi"))
    apps = [AppResource("a", app)]
    mk, mk_reasons = _reasons(lambda: _run_cluster(6), apps, monkeypatch, "megakernel")
    assert (mk.engine.name, mk.engine.attribution) == ("megakernel", "tail")
    xla, xla_reasons = _reasons(lambda: _run_cluster(6), apps, monkeypatch, "xla")
    assert xla.engine.name == "xla"
    assert mk_reasons == xla_reasons
    assert len(mk_reasons) == 4 and "Insufficient" in mk_reasons[0]


def test_class_failure_reasons_parity_through_simulate(monkeypatch):
    """Over-capacity host ports, gpu-share and open-local workloads, one
    stream each: the kernel's reasons equal the XLA scan's."""

    def cluster():
        rt = ResourceTypes()
        for i in range(3):
            rt.nodes.append(
                fx.make_fake_node(
                    f"n{i:03d}", "16", "32Gi", "110",
                    fx.with_allocatable({"alibabacloud.com/gpu-mem": "8Gi", "alibabacloud.com/gpu-count": "2"}),
                    fx.with_node_local_storage(vgs=[{"name": "pool0", "capacity": 20 * 1024**3}]),
                )
            )
        return rt

    ports = fx.make_fake_deployment("ports", 5, "100m", "128Mi", fx.with_host_ports([8080]))
    gpu = fx.make_fake_deployment("gpu", 8, "100m", "128Mi")
    _tmpl_annotate(gpu, {"alibabacloud.com/gpu-mem": "4Gi", "alibabacloud.com/gpu-count": "1"})
    loc = fx.make_fake_deployment("loc", 4, "100m", "128Mi")
    _tmpl_annotate(loc, {"simon/pod-local-storage": json.dumps(
        {"volumes": [{"size": str(15 * 1024**3), "kind": "LVM", "scName": "open-local-lvm"}]})})
    for workload in (ports, gpu, loc):
        app = ResourceTypes()
        app.deployments.append(workload)
        apps = [AppResource("a", app)]
        mk, mk_reasons = _reasons(cluster, apps, monkeypatch, "megakernel")
        assert (mk.engine.name, mk.engine.attribution) == ("megakernel", "tail"), workload.metadata.name
        _xla_res, xla_reasons = _reasons(cluster, apps, monkeypatch, "xla")
        assert mk_reasons == xla_reasons and mk_reasons, workload.metadata.name


def test_scenario_sweep_matches_xla_sweep(monkeypatch):
    """The kernel's scenario sweep and the XLA sweep give the same verdicts:
    unscheduled counts, placements and usage."""
    from opensim_tpu.parallel import scenarios

    cluster = ResourceTypes()
    for i in range(6):
        cluster.nodes.append(fx.make_fake_node(f"n{i}", "8", "16Gi", "20"))
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("w", 30, "1", "2Gi"))
    prep = prepare(cluster, [AppResource("a", app)], node_pad=8)
    P, N, S = len(prep.ordered), prep.ec.node_valid.shape[0], 5
    node_valid = np.zeros((S, N), bool)
    for s in range(S):
        node_valid[s, : s + 2] = True  # 2..6 nodes available
    pod_valid = np.ones((S, P), bool)
    forced = np.broadcast_to(prep.forced, (S, P))
    mk_unscheduled, mk_used, mk_chosen, _vg = fastpath.sweep(prep, node_valid, pod_valid, forced, interpret=True)
    monkeypatch.delenv("OPENSIM_FASTPATH", raising=False)
    xla = scenarios.sweep_auto(prep, node_valid, pod_valid)
    np.testing.assert_array_equal(np.asarray(mk_unscheduled), np.asarray(xla.unscheduled))
    np.testing.assert_array_equal(np.asarray(mk_chosen), np.asarray(xla.chosen))
    np.testing.assert_allclose(np.asarray(mk_used)[:, :N], np.asarray(xla.used), rtol=0, atol=0)
    assert list(np.asarray(xla.unscheduled)) == [14, 6, 0, 0, 0]


# ---------------------------------------------------------------------------
# the paths only the XLA scan runs
# ---------------------------------------------------------------------------


def _two_profiles(tmp_path):
    from opensim_tpu.engine.schedconfig import load_scheduler_config

    path = tmp_path / "profiles.yaml"
    path.write_text(
        "kind: KubeSchedulerConfiguration\n"
        "profiles:\n"
        "  - schedulerName: default-scheduler\n"
        "  - schedulerName: packed\n"
        "    pluginConfig:\n"
        "      - name: NodeResourcesLeastAllocated\n"
        "        args: {}\n"
        "    plugins:\n"
        "      score:\n"
        "        disabled:\n"
        "          - name: NodeResourcesLeastAllocated\n"
    )
    return load_scheduler_config(path)


def _packed_every_other(app_name, pods):
    for i, p in enumerate(pods):
        if (i // 5) % 2:
            p.spec.scheduler_name = "packed"
            p.raw.setdefault("spec", {})["schedulerName"] = "packed"


def test_two_profile_segmented_stream_replays_through_the_oracle(tmp_path):
    """A stream of host-port workloads whose pods alternate profiles in runs
    of five: consecutive segments on the XLA scan chain one carry."""
    import random

    cluster, app = _ports_fuzz_case(random.Random(613))
    res = simulate(cluster, [AppResource("a", app)], sched_config=_two_profiles(tmp_path),
                   patch_pods_fn=_packed_every_other, explain=True)
    ctx = res.engine.explain_ctx
    assert res.engine.name == "xla" and len(ctx.segments) > 2
    _oracle_replay(cluster, ctx.prep, ctx.chosen, Oracle(cluster.nodes))


def test_segmented_sweep_replays_through_the_oracle(tmp_path):
    """The segmented scenario sweep (one vmapped XLA dispatch a segment, a
    carry a scenario): each scenario's placements replay clean over its own
    nodes, and the whole-cluster scenario equals the segmented stream."""
    from opensim_tpu.engine.schedconfig import resolve_profile_segments
    from opensim_tpu.parallel import scenarios

    cluster = _run_cluster(9)
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("a", 20, "1", "2Gi"))
    app.deployments.append(fx.make_fake_deployment("b", 20, "500m", "1Gi"))
    cfg = _two_profiles(tmp_path)
    prep = prepare(cluster, [AppResource("a", app)], node_pad=16, patch_pods_fn=_packed_every_other)
    segments, invalid = resolve_profile_segments(cfg, prep.ordered, prep.meta.resource_names, forced=prep.forced)
    assert len(segments) > 2 and not invalid
    P, N, S = len(prep.ordered), prep.ec.node_valid.shape[0], 3
    node_valid = np.zeros((S, N), bool)
    for s, n in enumerate((9, 6, 3)):
        node_valid[s, :n] = True
    res = scenarios.sweep_segmented(prep, segments, node_valid, np.ones((S, P), bool),
                                    np.broadcast_to(prep.forced, (S, P)))
    chosen = np.asarray(res.chosen)
    for s in range(S):
        nodes = cluster.nodes[: int(node_valid[s].sum())]
        _oracle_replay(ResourceTypes(nodes=nodes), prep, chosen[s], Oracle(nodes))
    solo = simulate(cluster, [AppResource("a", app)], sched_config=cfg,
                    patch_pods_fn=_packed_every_other, explain=True)
    np.testing.assert_array_equal(chosen[0], solo.engine.explain_ctx.chosen)
    assert np.asarray(res.unscheduled)[2] > 0


def _shape(res):
    return (
        sorted((ns.node.metadata.name, len(ns.pods)) for ns in res.node_status if ns.pods),
        len(res.unscheduled_pods),
    )


@pytest.mark.parametrize("riders", [1, 3, 8])
def test_request_batch_answers_each_rider_as_its_solo_run(riders):
    """One vmapped XLA dispatch for the batch; each rider's answer is the
    one a solo simulate of its own app gives."""
    from opensim_tpu.engine import prepcache, reqbatch

    cluster = _run_cluster(6)
    cluster.pods.append(fx.make_fake_pod("pinned", "1", "1Gi", fx.with_node_name("n000")))
    base = prepcache.CacheEntry("b|base", prepare(cluster, []))
    apps = []
    for i in range(riders):
        rt = ResourceTypes()
        # the last rider of a batch asks for more than the cluster holds
        cpu = "9" if i == riders - 1 else f"{250 * (i + 1)}m"
        rt.add(fx.make_fake_deployment(f"r{i}", 3 + i % 4, cpu, "1Gi"))
        apps.append(AppResource("deploy", rt))
    with base.lock:
        base.restore()
        derived, slices = prepcache.derive_with_app_slices(base.prep, cluster, apps, base_entry=base)
        items = [
            reqbatch.BatchItem(cluster=cluster, apps=[apps[s]], lo=slices[s][0], hi=slices[s][1])
            for s in range(riders)
        ]
        try:
            dispatch = reqbatch.dispatch_request_batch(derived, items)
            results = reqbatch.decode_request_batch(derived, items, dispatch)
        finally:
            base.restore()
    assert dispatch.engine_name == "xla" and list(dispatch.skips) == ["megakernel"]
    for s in range(riders):
        assert _shape(results[s]) == _shape(simulate(cluster, [apps[s]])), s
    assert len(results[-1].unscheduled_pods) > 0


def test_campaign_step_from_a_callers_scan_state_equals_one_scan():
    """A campaign step scans from the carry its caller hands it: the stream
    run in two steps, the second from the first's final state, places every
    pod where one scan of the whole stream does."""
    from opensim_tpu.planner.campaign import _Executor

    cluster = _run_cluster(6)
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("a", 14, "1", "2Gi"))
    app.deployments.append(fx.make_fake_deployment("b", 20, "500m", "4Gi"))
    prep = prepare(cluster, [AppResource("a", app)], node_pad=8)
    P = len(prep.ordered)
    assert select.carry(prep, select.Ask(node_mask=True, start_state=True)) is not None
    whole, _used = _xla(prep)
    first = np.zeros(P, bool)
    first[: P // 2] = True
    nv = np.asarray(prep.ec_np.node_valid)
    out1 = _Executor._run_engine(None, prep, first, prep.forced, nv, prep.st0)
    out2 = _Executor._run_engine(None, prep, ~first, prep.forced, nv, out1.final_state)
    stepped = np.where(first, np.asarray(out1.chosen)[:P], np.asarray(out2.chosen)[:P])
    np.testing.assert_array_equal(stepped, whole)
    assert (whole < 0).any() and (whole >= 0).any()


def test_cli_rejects_backend_native(capsys):
    from opensim_tpu.cli.main import main

    with pytest.raises(SystemExit) as exited:
        main(["apply", "-f", "no-such-config.yaml", "--backend", "native"])
    assert exited.value.code == 2
    err = capsys.readouterr().err
    assert "invalid choice: 'native'" in err
    assert "--backend {auto,tpu,cpu,xla}" in err
