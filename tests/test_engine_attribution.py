"""Engine attribution (VERDICT r4 #3): SimulateResult.engine records which
scheduling engine ran and why the others were skipped; envelope misses are
logged, never silent; bench.py and the apply report surface the decision."""

import logging

import pytest

from opensim_tpu.engine.simulator import AppResource, simulate
from opensim_tpu.models import ResourceTypes
from opensim_tpu.models import fixtures as fx


def _mini_cluster(n=4):
    rt = ResourceTypes()
    for i in range(n):
        rt.nodes.append(
            fx.make_fake_node(
                f"n{i}", "8", "16Gi", "110", fx.with_labels({"topology.kubernetes.io/zone": f"z{i % 2}"})
            )
        )
    return rt


def _apps(n_pods=6, opts=()):
    rt = ResourceTypes()
    rt.deployments.append(fx.make_fake_deployment("app", n_pods, "100m", "128Mi", *opts))
    return [AppResource("app", rt)]


def test_engine_recorded_on_cpu_host():
    """On an accelerator-less host the XLA scan owns the run; the
    megakernel skip reason names the missing TPU backend."""
    res = simulate(_mini_cluster(), _apps())
    assert res.engine is not None
    assert res.engine.name == "xla"
    assert list(res.engine.skipped) == ["megakernel"]
    assert "no TPU backend" in res.engine.skipped["megakernel"]
    # the decision renders as one human-readable line (report footer)
    line = res.engine.describe()
    assert res.engine.name in line and "megakernel" in line


def test_extra_plugins_force_xla_with_reasons(monkeypatch):
    import jax.numpy as jnp

    # the kernel switched on, so that it is the plugins that turn the run away
    monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")
    noop = ("filter", lambda ec, st, u: jnp.ones((ec.node_valid.shape[0],), bool))
    res = simulate(_mini_cluster(), _apps(), extra_plugins=(noop,))
    assert res.engine.name == "xla"
    assert res.engine.skipped == {"megakernel": "out-of-tree extra_plugins run on the XLA scan"}


def test_envelope_miss_is_logged(monkeypatch, caplog):
    """A workload outside the megakernel envelope (5 non-hostname topology
    keys) must log the miss and record it in the skip map."""
    monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")
    cluster = ResourceTypes()
    keys = [f"example.com/tier-{k}" for k in range(5)]
    for i in range(4):
        labels = {k: f"v{i % 2}" for k in keys}
        cluster.nodes.append(fx.make_fake_node(f"n{i}", "8", "16Gi", "110", fx.with_labels(labels)))
    apps = ResourceTypes()
    for w, key in enumerate(keys):
        apps.deployments.append(
            fx.make_fake_deployment(
                f"w{w}",
                2,
                "100m",
                "128Mi",
                fx.with_topology_spread(
                    [
                        {
                            "maxSkew": 3,
                            "topologyKey": key,
                            "whenUnsatisfiable": "ScheduleAnyway",
                            "labelSelector": {"matchLabels": {"app": f"w{w}"}},
                        }
                    ]
                ),
            )
        )
    with caplog.at_level(logging.INFO, logger="opensim_tpu"):
        res = simulate(cluster, [AppResource("a", apps)])
    assert res.engine.name == "xla"
    assert "topology keys" in res.engine.skipped["megakernel"]
    assert any("envelope miss" in r.message for r in caplog.records)


def test_megakernel_attributed_in_interpret_mode(monkeypatch):
    monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")
    res = simulate(_mini_cluster(), _apps())
    assert res.engine.name == "megakernel"
    assert "megakernel" not in res.engine.skipped


def test_require_tpu_makes_kernel_failure_fatal(monkeypatch):
    """--backend tpu (OPENSIM_REQUIRE_TPU=1) turns a megakernel failure into
    a hard error instead of a silent fallback."""
    from opensim_tpu.engine import fastpath

    monkeypatch.setenv("OPENSIM_REQUIRE_TPU", "1")
    monkeypatch.delenv("OPENSIM_FASTPATH", raising=False)

    # make the megakernel "applicable" then blow up inside it, as a Mosaic
    # compile failure on real silicon would
    monkeypatch.setattr(fastpath, "why_not", lambda prep, config=None: None)

    def boom(*a, **k):
        raise ValueError("mosaic says no")

    monkeypatch.setattr(fastpath, "schedule", boom)
    # pretending to be a TPU backend is what arms the fastpath branch
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="refusing to silently fall back"):
        simulate(_mini_cluster(), _apps())
