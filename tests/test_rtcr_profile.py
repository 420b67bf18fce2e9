"""A scheduler profile on the megakernel: its score weights and the
RequestedToCapacityRatio term are trace-time constants of the kernel, and the
kernel answers as the XLA scan does, bit for bit, in a schedule, a packed
sweep (eight scenarios a step) and the planner's masked final pass; the
default profile lowers to the kernel it always was; the spans and the counter
say which profile ran where. The kernel runs in the Pallas interpreter here;
`OPENSIM_TEST_BACKEND=tpu` compiles it. Tier-1, small shapes."""

import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opensim_tpu.engine import fastpath
from opensim_tpu.engine.schedconfig import DEFAULT_CONFIG, load_scheduler_config, resolve_profiles
from opensim_tpu.engine.simulator import AppResource, prepare, simulate
from opensim_tpu.models import ResourceTypes, fixtures as fx
from opensim_tpu.obs import trace as tracing
from opensim_tpu.obs.metrics import RECORDER
from opensim_tpu.parallel import scenarios

_INTERPRET = os.environ.get("OPENSIM_TEST_BACKEND") != "tpu"
ZONE = "topology.kubernetes.io/zone"
FOO = "example.com/foo"

#: the Resource Bin Packing page's profile, LeastAllocated off
BINPACK = """kind: KubeSchedulerConfiguration
profiles:
  - plugins:
      score:
        disabled: [{name: NodeResourcesLeastAllocated}]
        enabled: [{name: RequestedToCapacityRatio, weight: 1}]
    pluginConfig:
      - name: RequestedToCapacityRatio
        args:
          shape: [{utilization: 0, score: 0}, {utilization: 100, score: 10}]
          resources: [{name: cpu, weight: 1}, {name: memory, weight: 1}]
"""
#: three points whose segments are not of slope 1 (each divides), weights whose
#: sums are not powers of two (the mean divides), and an extended resource
#: that some nodes have and no pod asks for: f is 0 there (left out of the
#: mean) and f(100) where a node lacks it (capacity 0)
THREE_POINTS = """kind: KubeSchedulerConfiguration
profiles:
  - plugins:
      score:
        enabled: [{name: RequestedToCapacityRatio, weight: 3}]
    pluginConfig:
      - name: RequestedToCapacityRatio
        args:
          shape: [{utilization: 0, score: 0}, {utilization: 40, score: 7}, {utilization: 100, score: 3}]
          resources: [{name: cpu, weight: 2}, {name: memory, weight: 1}, {name: example.com/foo, weight: 1}]
"""
#: the default plugins at other weights: no RequestedToCapacityRatio
WEIGHTS = """kind: KubeSchedulerConfiguration
profiles:
  - plugins:
      score:
        enabled: [{name: NodeResourcesLeastAllocated, weight: 3}, {name: PodTopologySpread, weight: 5}]
        disabled: [{name: NodeResourcesBalancedAllocation}]
"""
PROFILES = {"binpack": BINPACK, "three_points": THREE_POINTS, "weights": WEIGHTS}


@pytest.fixture(autouse=True)
def _kernel_on(monkeypatch):
    monkeypatch.delenv("OPENSIM_DISABLE_FASTPATH", raising=False)
    monkeypatch.setenv("OPENSIM_DISABLE_NATIVE", "1")  # below the kernel is the XLA scan, as on the chip
    if _INTERPRET:
        monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")


def _kernel_off(monkeypatch):
    monkeypatch.delenv("OPENSIM_FASTPATH", raising=False)
    monkeypatch.setenv("OPENSIM_DISABLE_FASTPATH", "1")


def _profile(tmp_path, name):
    path = tmp_path / f"{name}.yaml"
    path.write_text(PROFILES[name])
    return load_scheduler_config(str(path))


def _cluster():
    """Twelve nodes of three sizes in three zones, half of them with the
    extended resource; two pods bound beforehand; plain Deployments and one
    with a soft zone spread."""
    cluster = ResourceTypes()
    for i in range(12):
        extra = fx.with_allocatable({FOO: "8"}) if i % 2 else (lambda d: None)
        cpu, mem = (("8", "16Gi"), ("16", "32Gi"), ("4", "24Gi"))[i % 3]
        cluster.nodes.append(fx.make_fake_node(f"n{i:02d}", cpu, mem, "110", fx.with_labels({ZONE: f"z{i % 3}"}), extra))
    cluster.pods.append(fx.make_fake_pod("bound-a", "1", "1Gi", fx.with_node_name("n03")))
    cluster.pods.append(fx.make_fake_pod("bound-b", "2", "3Gi", fx.with_node_name("n07")))
    app = ResourceTypes()
    soft = fx.with_topology_spread([{"maxSkew": 1, "topologyKey": ZONE, "whenUnsatisfiable": "ScheduleAnyway",
                                     "labelSelector": {"matchLabels": {"app": "soft"}}}])
    app.deployments.append(fx.make_fake_deployment("web", 30, "700m", "1Gi"))
    app.deployments.append(fx.make_fake_deployment("soft", 14, "1500m", "700Mi", soft))
    app.deployments.append(fx.make_fake_deployment("tiny", 9, "0", "0"))  # the non-zero defaults
    return cluster, [AppResource("a", app)]


def _prep():
    cluster, apps = _cluster()
    return prepare(cluster, apps, node_pad=128)


def _resolved(cfg, prep):
    config, _invalid = resolve_profiles(cfg, prep.ordered, prep.meta.resource_names)
    return config


@pytest.mark.parametrize("name", sorted(PROFILES))
def test_a_schedule_under_the_profile_is_the_xla_scans_bit_for_bit(tmp_path, monkeypatch, name):
    cfg = _profile(tmp_path, name)
    cluster, apps = _cluster()
    kernel = simulate(cluster, apps, sched_config=cfg)
    assert kernel.engine.name == "megakernel", kernel.engine.skipped
    _kernel_off(monkeypatch)
    cluster, apps = _cluster()
    xla = simulate(cluster, apps, sched_config=cfg)
    assert xla.engine.name == "xla"
    placed = lambda res: sorted((s.node.metadata.name, p.metadata.name.split("-")[0])
                                for s in res.node_status for p in s.pods)
    assert placed(kernel) == placed(xla)
    # the profile moves placements: the default profile puts the pods elsewhere
    cluster, apps = _cluster()
    assert placed(simulate(cluster, apps)) != placed(xla)


@pytest.mark.parametrize("name", sorted(PROFILES))
def test_a_packed_sweep_under_the_profile_is_the_xla_sweeps_bit_for_bit(tmp_path, name):
    prep = _prep()
    cfg = _resolved(_profile(tmp_path, name), prep)
    assert fastpath.why_not(prep, cfg) is None
    S, n_real = 9, int(np.asarray(prep.ec_np.node_valid).sum())
    N, P = int(np.asarray(prep.ec_np.node_valid).shape[0]), len(prep.ordered)
    nodes = np.zeros((S, N), bool)
    for s in range(S):
        nodes[s, s % 3:4 + s] = True
    rng = np.random.RandomState(S)
    pods = rng.rand(S, P) > 0.1
    forced = np.broadcast_to(prep.forced, (S, P)).copy()
    assert fastpath.sweep_sublanes(prep, S) == 8
    kernel = fastpath.sweep(prep, nodes, pods, forced, interpret=_INTERPRET, config=cfg)
    xla = scenarios.sweep(prep.ec, prep.st0, prep.tmpl_ids, prep.forced, nodes, pods,
                          features=prep.features, forced_masks=forced, config=cfg)
    for what, a, b in zip(("unscheduled", "used", "chosen"), kernel, xla):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=what)
    assert (np.asarray(kernel[2]) >= 0).any() and n_real == 12


@pytest.mark.parametrize("name", ["binpack", "three_points"])
def test_the_masked_final_pass_under_the_profile_is_the_xla_scans_bit_for_bit(tmp_path, monkeypatch, name):
    """The planner's prep reuse: the prepared nodes masked down to the first nine."""
    cfg = _profile(tmp_path, name)

    def run():
        cluster, apps = _cluster()
        prep = prepare(cluster, apps, node_pad=128)
        mask = np.zeros(np.asarray(prep.ec_np.node_valid).shape[0], bool)
        mask[:9] = True
        sub = ResourceTypes()
        sub.nodes, sub.pods = list(cluster.nodes[:9]), list(cluster.pods)
        res = simulate(sub, apps, sched_config=cfg, prep=prep, node_valid=mask)
        return res.engine.name, sorted((s.node.metadata.name, len(s.pods)) for s in res.node_status)

    kernel = run()
    _kernel_off(monkeypatch)
    xla = run()
    assert kernel[0] == "megakernel" and xla[0] == "xla"
    assert kernel[1] == xla[1]


def _lowered_text(config=None, sublanes=1):
    """The interpreted kernel's lowering at a small shape (the Mosaic text
    holds the source lines of the kernel, the interpreter's does not)."""
    import test_kernel_compile as T
    from opensim_tpu.ops.pallas_scan import CHUNK, run_fast_scan

    cpu = jax.sharding.SingleDeviceSharding(jax.devices("cpu")[0])
    S, P = 2 * sublanes, 2 * CHUNK
    stream = lambda *shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=cpu)
    widths = dict(N=256, R=4, U=4, A=8, Cs=2)
    extra = {} if config is None else {"config": config}
    lowered = run_fast_scan.lower(
        T._inputs(cpu, S, **widths), stream(P, dt=jnp.int32), stream(S, P, dt=jnp.bool_),
        stream(S, P, dt=jnp.bool_), sublanes=sublanes, interpret=True, **T._OFF, **extra,
    )
    return lowered.as_text()


#: sha256 of `_lowered_text()` for one scenario a step and eight, as the kernel
#: lowered before it took a profile: the default profile has to lower to it
DEFAULT_KERNEL_SHA256 = {
    1: "03cb51fa627a0dc271dd0f59e5d7149613526afe07a26b04c0ef58ceb796a60f",
    8: "00e17209066f2c2320e790b9324727292f4adae91b3ffbdc3a0cc8985bd567bf",
}


@pytest.mark.parametrize("sublanes", [1, 8])
def test_the_default_profile_lowers_to_the_kernel_it_always_was(sublanes):
    plain = _lowered_text(None, sublanes)
    assert _lowered_text(DEFAULT_CONFIG, sublanes) == plain
    assert hashlib.sha256(plain.encode()).hexdigest() == DEFAULT_KERNEL_SHA256[sublanes]
    # and a profile is another program
    assert _lowered_text(DEFAULT_CONFIG._replace(w_least=3.0), sublanes) != plain


RUNGS = {f"{kind}.{engine}" for kind in ("engine", "sweep") for engine in ("megakernel", "native", "xla")}


def _spans(fn):
    tr = tracing.start_trace("test", force=True)
    with tracing.trace_scope(tr):
        fn()
    tr.finish()
    return [(sp.name, sp.attrs.get("profile"), sp.attrs.get("declined")) for sp in tr.walk()
            if sp.name in RUNGS]


def test_the_spans_and_the_counter_say_which_profile_ran_where(tmp_path, monkeypatch):
    devices = jax.devices
    monkeypatch.setattr(jax, "devices", lambda *a: devices(*a)[:1])  # a sweep on the kernel: one device
    cfg = _profile(tmp_path, "binpack")
    prep = _prep()
    RECORDER.reset()
    try:
        cluster, apps = _cluster()
        got = _spans(lambda: simulate(cluster, apps, sched_config=cfg))
        got += _spans(lambda: scenarios.sweep_counts(prep, 8, [0, 2, 4], config=cfg))
        cluster, apps = _cluster()
        got += _spans(lambda: simulate(cluster, apps))
        # a disabled filter is what the kernel cannot compute: the XLA scan runs it and says why
        off = DEFAULT_CONFIG._replace(f_taints=False, w_least=2.0)
        cluster, apps = _cluster()
        got += _spans(lambda: simulate(cluster, apps, sched_config=off))
        assert got == [
            ("engine.megakernel", "rtcr", None),
            ("sweep.megakernel", "rtcr", None),
            ("engine.megakernel", "default", None),
            ("engine.xla", "weights", "megakernel:sched_config:disabled_filter"),
        ]
        lines = sorted(l for l in RECORDER.render_lines() if l.startswith("simon_engine_profile_total{"))
        assert lines == [
            'simon_engine_profile_total{engine="megakernel",profile="default"} 1',
            'simon_engine_profile_total{engine="megakernel",profile="rtcr"} 2',
            'simon_engine_profile_total{engine="xla",profile="weights"} 1',
        ]
        declined = [l for l in RECORDER.render_lines() if l.startswith("simon_engine_declined_total{")]
        assert declined == ['simon_engine_declined_total{engine="megakernel",reason="sched_config:disabled_filter"} 1']
    finally:
        RECORDER.reset()
