"""Scenario sweep + defragmentation tests on the 8-device virtual CPU mesh.
The megakernel sweeps run in interpret mode on CPU; OPENSIM_TEST_BACKEND=tpu
compiles the batched kernel through Mosaic for real."""

import os

import numpy as np
import pytest

from opensim_tpu.engine.simulator import AppResource, prepare
from opensim_tpu.models import ResourceTypes
from opensim_tpu.models import fixtures as fx
from opensim_tpu.parallel import scenarios
from opensim_tpu.planner.defrag import plan_drains

_INTERPRET = os.environ.get("OPENSIM_TEST_BACKEND") != "tpu"


def _arm_megakernel(monkeypatch):
    """fastpath.applicable() needs a TPU backend unless the interpreter is
    asked for by name — which only the CPU run does."""
    if _INTERPRET:
        monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")


def _setup(n_nodes=6, replicas=8):
    cluster = ResourceTypes()
    for i in range(n_nodes):
        cluster.nodes.append(fx.make_fake_node(f"n{i}", "8", "16Gi"))
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("web", replicas, "2", "2Gi"))
    return cluster, [AppResource("a", app)]


def test_sweep_over_node_counts_sharded():
    cluster, apps = _setup(n_nodes=6, replicas=16)  # 16 pods × 2cpu = 32 cpu; 6×8=48
    prep = prepare(cluster, apps)
    N = prep.ec.node_valid.shape[0]
    P = len(prep.ordered)
    # scenario s enables s+1 nodes
    S = 6
    node_valid = np.zeros((S, N), dtype=bool)
    for s in range(S):
        node_valid[s, : s + 1] = True
    pod_valid = np.ones((S, P), dtype=bool)
    res = scenarios.sweep(
        prep.ec, prep.st0, prep.tmpl_ids, prep.forced, node_valid, pod_valid,
        mesh=scenarios.default_mesh(), features=prep.features,
    )
    unscheduled = np.asarray(res.unscheduled)
    # each 8-cpu node fits 4 pods of 2 cpu; 16 pods need >= 4 nodes
    assert unscheduled.tolist() == [12, 8, 4, 0, 0, 0]
    # monotone: more nodes never hurts
    assert all(unscheduled[i] >= unscheduled[i + 1] for i in range(S - 1))


def test_defrag_drain_plans():
    # 3 nodes, light load: any single node is drainable
    cluster, apps = _setup(n_nodes=3, replicas=3)
    result = plan_drains(cluster, apps)
    assert len(result.plans) == 3
    assert all(p.feasible for p in result.plans)

    # tight load: 12 pods × 2cpu = 24 cpu on 3×8 = 24 cpu — no drain possible
    cluster, apps = _setup(n_nodes=3, replicas=12)
    result = plan_drains(cluster, apps)
    assert all(not p.feasible for p in result.plans)
    assert all(p.unscheduled == 4 for p in result.plans)


def test_defrag_reschedules_prebound_pods():
    cluster, apps = _setup(n_nodes=3, replicas=0)
    # a pod pre-bound to n0 must be rescheduled when n0 drains
    cluster.pods.append(fx.make_fake_pod("pinned", "1", "1Gi", fx.with_node_name("n0")))
    result = plan_drains(cluster, apps)
    by_node = {p.node: p for p in result.plans}
    assert by_node["n0"].feasible  # pod fits elsewhere


@pytest.mark.slow
def test_fastpath_sweep_matches_xla_sweep(monkeypatch):
    """The megakernel-backed sweep must agree with the vmapped XLA sweep on
    unscheduled counts, placements, and final usage."""
    _arm_megakernel(monkeypatch)
    from opensim_tpu.engine import fastpath

    cluster, apps = _setup(n_nodes=6, replicas=16)
    prep = prepare(cluster, apps, node_pad=128)
    assert fastpath.applicable(prep)
    N = prep.ec.node_valid.shape[0]
    P = len(prep.ordered)
    S = 6
    node_valid = np.zeros((S, N), dtype=bool)
    for s in range(S):
        node_valid[s, : s + 1] = True
    pod_valid = np.ones((S, P), dtype=bool)
    forced = np.broadcast_to(prep.forced, (S, P)).copy()

    want = scenarios.sweep(
        prep.ec, prep.st0, prep.tmpl_ids, prep.forced, node_valid, pod_valid,
        features=prep.features,
    )
    got_unsched, got_used, got_chosen, got_vg = fastpath.sweep(
        prep, node_valid, pod_valid, forced, interpret=_INTERPRET
    )
    np.testing.assert_array_equal(got_unsched, np.asarray(want.unscheduled))
    np.testing.assert_array_equal(got_chosen, np.asarray(want.chosen)[:, :P])
    np.testing.assert_allclose(got_used, np.asarray(want.used), rtol=1e-5)
    np.testing.assert_allclose(got_vg, np.asarray(want.vg_used), rtol=1e-5)


@pytest.mark.slow
def test_fastpath_sweep_large_batch(monkeypatch):
    """A larger scenario batch (S=40) through the single-dispatch vmapped
    megakernel still matches the XLA sweep — guards the batched-grid path
    (scratch reinit per scenario, unbatched table sharing)."""
    _arm_megakernel(monkeypatch)
    from opensim_tpu.engine import fastpath

    cluster, apps = _setup(n_nodes=8, replicas=24)
    prep = prepare(cluster, apps, node_pad=128)
    assert fastpath.applicable(prep)
    N = prep.ec.node_valid.shape[0]
    P = len(prep.ordered)
    S = 40
    rng = np.random.RandomState(7)
    node_valid = np.zeros((S, N), dtype=bool)
    base = np.asarray(prep.ec.node_valid)
    for s in range(S):
        node_valid[s] = base
        # drain a random real node per scenario
        node_valid[s, rng.randint(0, 8)] = False
    pod_valid = np.ones((S, P), dtype=bool)
    forced = np.broadcast_to(prep.forced, (S, P)).copy()

    want = scenarios.sweep(
        prep.ec, prep.st0, prep.tmpl_ids, prep.forced, node_valid, pod_valid,
        features=prep.features,
    )
    got_unsched, got_used, got_chosen, got_vg = fastpath.sweep(
        prep, node_valid, pod_valid, forced, interpret=_INTERPRET
    )
    np.testing.assert_array_equal(got_unsched, np.asarray(want.unscheduled))
    np.testing.assert_array_equal(got_chosen, np.asarray(want.chosen)[:, :P])
    np.testing.assert_allclose(got_used, np.asarray(want.used), rtol=1e-5)
    np.testing.assert_allclose(got_vg, np.asarray(want.vg_used), rtol=1e-5)


@pytest.mark.parametrize("seed", [13, 47])
@pytest.mark.slow
def test_fastpath_sweep_fuzz_feature_rich(monkeypatch, seed):
    """Batched-sweep differential fuzz: random FEATURE-RICH workloads
    (gpu/local/ports/interpod/spread/avoid from the fastpath fuzz
    generators) through the single-dispatch vmapped megakernel vs the XLA
    sweep, with per-scenario drains AND per-scenario forced-mask releases
    (the defrag shape). This is the strongest interpret-mode evidence for
    the batched kernel awaiting compiled-Mosaic validation."""
    import random as _random

    _arm_megakernel(monkeypatch)
    from opensim_tpu.engine import fastpath
    from test_fastpath_fuzz import random_app, random_cluster

    rng = _random.Random(seed)
    cluster = random_cluster(rng, rng.randrange(8, 14))
    apps = [AppResource("fuzz", random_app(rng, rng.randrange(3, 6)))]
    prep = prepare(cluster, apps, node_pad=128)
    if prep is None or not fastpath.applicable(prep):
        pytest.skip("generated workload outside fast-path bounds")
    N = prep.ec.node_valid.shape[0]
    P = len(prep.ordered)
    S = 12
    nrng = np.random.RandomState(seed)
    base = np.asarray(prep.ec.node_valid)
    node_valid = np.zeros((S, N), bool)
    forced = np.broadcast_to(prep.forced, (S, P)).copy()
    for s in range(S):
        node_valid[s] = base
        drain = nrng.randint(0, int(base.sum()))
        node_valid[s, drain] = False
        # defrag semantics: pods pinned to the drained node become free
        for j, pod in enumerate(prep.ordered):
            if prep.forced[j] and pod.spec.node_name == prep.meta.node_names[drain]:
                forced[s, j] = False
    pod_valid = np.ones((S, P), bool)

    want = scenarios.sweep(
        prep.ec, prep.st0, prep.tmpl_ids, prep.forced, node_valid, pod_valid,
        features=prep.features, forced_masks=forced,
    )
    got_unsched, got_used, got_chosen, got_vg = fastpath.sweep(
        prep, node_valid, pod_valid, forced, interpret=_INTERPRET
    )
    np.testing.assert_array_equal(got_unsched, np.asarray(want.unscheduled))
    np.testing.assert_array_equal(got_chosen, np.asarray(want.chosen)[:, :P])
    np.testing.assert_allclose(got_used, np.asarray(want.used), rtol=1e-5)
    np.testing.assert_allclose(got_vg, np.asarray(want.vg_used), rtol=1e-5)


@pytest.mark.slow
def test_fastpath_sweep_big_u_mode(monkeypatch):
    """Batched sweep with the template tables in HBM (big-U per-step DMA)
    — the combination of the two round-3 envelope features, previously
    only tested separately."""
    _arm_megakernel(monkeypatch)
    from opensim_tpu.engine import fastpath

    cluster, apps = _setup(n_nodes=6, replicas=8)
    # inflate the template space so big_u=True is meaningful
    extra = ResourceTypes()
    for i in range(40):
        extra.pods.append(fx.make_fake_pod(f"u{i:03d}", f"{50 + i}m", "64Mi"))
    apps = apps + [AppResource("bigu", extra)]
    prep = prepare(cluster, apps, node_pad=128)
    assert fastpath.applicable(prep)
    N = prep.ec.node_valid.shape[0]
    P = len(prep.ordered)
    S = 5
    node_valid = np.zeros((S, N), bool)
    for s in range(S):
        node_valid[s, : s + 2] = True
    pod_valid = np.ones((S, P), bool)
    forced = np.broadcast_to(prep.forced, (S, P)).copy()

    want = scenarios.sweep(
        prep.ec, prep.st0, prep.tmpl_ids, prep.forced, node_valid, pod_valid,
        features=prep.features,
    )
    got_unsched, got_used, got_chosen, got_vg = fastpath.sweep(
        prep, node_valid, pod_valid, forced, interpret=_INTERPRET, big_u=True
    )
    np.testing.assert_array_equal(got_unsched, np.asarray(want.unscheduled))
    np.testing.assert_array_equal(got_chosen, np.asarray(want.chosen)[:, :P])
    np.testing.assert_allclose(got_used, np.asarray(want.used), rtol=1e-5)
    np.testing.assert_allclose(got_vg, np.asarray(want.vg_used), rtol=1e-5)
