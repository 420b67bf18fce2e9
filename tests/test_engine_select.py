"""engine/select.py is the one place that says which engine runs: its table,
one case per (ask, policy) row. The expected rungs and reason strings are the
ones the gates wrote before they moved there (simulator._run_engine_ladder,
fastpath.why_not, resident._why_not, scenarios.sweep_auto,
reqbatch.dispatch_request_batch at PR 30), not read back from the table."""

import ast
import dataclasses
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from opensim_tpu.engine import fastpath, reqbatch, select
from opensim_tpu.engine.schedconfig import DEFAULT_CONFIG
from opensim_tpu.engine.simulator import AppResource, prepare
from opensim_tpu.models import ResourceTypes
from opensim_tpu.models import fixtures as fx

ROOT = pathlib.Path(__file__).resolve().parents[1]
KNOBS = ("OPENSIM_DISABLE_FASTPATH", "OPENSIM_FASTPATH", "OPENSIM_REQUIRE_TPU")

NO_TPU = "no TPU backend (jax.default_backend()='cpu')"
XLA_OFF_MK = "disabled by --backend xla (OPENSIM_DISABLE_FASTPATH)"
BATCH_MK = "request-axis batches run on the vmapped XLA scan"
PLUGINS_MK = "out-of-tree extra_plugins run on the XLA scan"
EXPLAIN_MK = "explain mode audits per-filter verdicts (XLA scan)"
WEIGHTED = DEFAULT_CONFIG._replace(w_least=3.0)
BINPACK = DEFAULT_CONFIG._replace(w_least=0.0, w_rtcr=1.0, rtcr_shape=((0.0, 0.0), (100.0, 100.0)),
                                  rtcr_resources=((0, 1.0), (1, 1.0)))
NO_TAINTS = DEFAULT_CONFIG._replace(f_taints=False)
GAP_FILTER = "a scheduler config the kernel cannot compute (disabled_filter)"
GAP_COLS = "a scheduler config the kernel cannot compute (fit_ignored_cols)"
PLUGIN = (("filter", lambda ec, st, u: None),)


@pytest.fixture(scope="module")
def prep():
    cluster = ResourceTypes()
    for i in range(4):
        cluster.nodes.append(fx.make_fake_node(f"n{i}", "8", "16Gi"))
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("web", 6, "1", "1Gi"))
    return prepare(cluster, [AppResource("a", app)], node_pad=128)


def _policy(monkeypatch, env=(), platform=None, devices=None):
    for name in KNOBS:
        monkeypatch.delenv(name, raising=False)
    for name, value in dict(env).items():
        monkeypatch.setenv(name, value)
    if platform is not None:
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if devices is not None:
        monkeypatch.setattr(jax, "devices", lambda *a: [object()] * devices)


INTERPRET = {"OPENSIM_FASTPATH": "interpret"}
BACKEND_XLA = {"OPENSIM_DISABLE_FASTPATH": "1"}
BACKEND_TPU = {"OPENSIM_REQUIRE_TPU": "1"}

# id -> (policy, ask, megakernel); the XLA scan always takes it
LADDER = {
    # the platform, and the two ways onto the kernel
    "plain_cpu": ({}, {}, NO_TPU),
    "plain_tpu": ({"platform": "tpu"}, {}, None),
    "plain_interpret": ({"env": INTERPRET}, {}, None),
    # each ask alone: the asks come before the platform
    "segments": ({}, {"segments": 2}, "segmented multi-profile stream (2 segments)"),
    "explain": ({}, {"explain": True}, EXPLAIN_MK),
    # a config declines the kernel only for what the kernel cannot compute: its score
    # weights and RequestedToCapacityRatio are served, a disabled filter and ignored fit columns are not
    "sched_config": ({}, {"sched_config": NO_TAINTS}, GAP_FILTER),
    "sched_config_weights": ({"env": INTERPRET}, {"sched_config": WEIGHTED}, None),
    "sched_config_rtcr": ({"env": INTERPRET}, {"sched_config": BINPACK}, None),
    # the default config is no config
    "sched_config_default_is_still_a_config": ({"env": INTERPRET}, {"sched_config": DEFAULT_CONFIG}, None),
    "fit_ignored_cols": ({}, {"sched_config": DEFAULT_CONFIG._replace(fit_ignored_cols=(2,))}, GAP_COLS),
    "extra_plugins": ({}, {"extra_plugins": PLUGIN}, PLUGINS_MK),
    "tie_seed": ({}, {"tie_seed": 7}, "sampled tie-break runs on the XLA scan"),
    # the first gate that applies is the one recorded
    "explain_before_tie_seed": ({"env": INTERPRET}, {"explain": True, "tie_seed": 7}, EXPLAIN_MK),
    "segments_before_all": (
        {"platform": "tpu"}, {"segments": 3, "explain": True, "extra_plugins": PLUGIN},
        "segmented multi-profile stream (3 segments)",
    ),
    # a node mask is the kernel's validity row: served (PR 30)
    "node_mask_interpret": ({"env": INTERPRET}, {"node_mask": True}, None),
    "node_mask_tpu": ({"platform": "tpu"}, {"node_mask": True}, None),
    # a campaign step's own carry and forced vector: the XLA scan, as campaign._run_engine has it
    "start_state": (
        {"env": INTERPRET}, {"node_mask": True, "start_state": True},
        "a stream from the caller's scan state runs on the XLA scan",
    ),
    # a caller that reads no failure reasons (ISSUE 36): every rung serves it as before; what it changes
    # is whether the ladder re-scans a kernel result with a mid-stream failure, not which rung runs
    "no_reasons_cpu": ({}, {"reasons": False}, NO_TPU),
    "no_reasons_interpret": ({"env": INTERPRET}, {"reasons": False, "node_mask": True}, None),
    "no_reasons_tpu": ({"platform": "tpu"}, {"reasons": False}, None),
    "no_reasons_explain": ({"env": INTERPRET}, {"reasons": False, "explain": True}, EXPLAIN_MK),
    # --backend cpu / xla / tpu
    "backend_cpu": ({"platform": "cpu"}, {}, NO_TPU),
    "backend_cpu_interpret": ({"env": INTERPRET, "platform": "cpu"}, {}, None),
    "backend_xla_cpu": ({"env": BACKEND_XLA}, {}, NO_TPU),
    "backend_xla_tpu": ({"env": BACKEND_XLA, "platform": "tpu"}, {}, XLA_OFF_MK),
    "backend_xla_interpret": ({"env": {**BACKEND_XLA, **INTERPRET}}, {}, XLA_OFF_MK),
    "backend_xla_sweep": ({"env": BACKEND_XLA, "platform": "tpu", "devices": 1}, {"shape": "sweep"}, XLA_OFF_MK),
    "backend_tpu": ({"env": BACKEND_TPU, "platform": "tpu"}, {}, None),
    "backend_tpu_batch": ({"env": BACKEND_TPU, "platform": "tpu"}, {"shape": "batch"}, BATCH_MK),
    "backend_tpu_segments": (
        {"env": BACKEND_TPU, "platform": "tpu"}, {"segments": 2}, "segmented multi-profile stream (2 segments)",
    ),
    # a scenario sweep: the kernel for exactly one device
    "sweep_1_cpu": ({"devices": 1}, {"shape": "sweep"}, NO_TPU),
    "sweep_1_interpret": ({"env": INTERPRET, "devices": 1}, {"shape": "sweep"}, None),
    "sweep_1_tpu": ({"platform": "tpu", "devices": 1}, {"shape": "sweep"}, None),
    "sweep_4_tpu": ({"platform": "tpu", "devices": 4}, {"shape": "sweep"}, "4 devices"),
    "sweep_8_cpu": ({}, {"shape": "sweep"}, "8 devices"),
    "sweep_1_config": ({"env": INTERPRET, "devices": 1}, {"shape": "sweep", "sched_config": WEIGHTED}, None),
    "sweep_1_config_the_kernel_cannot_compute": (
        {"env": INTERPRET, "devices": 1}, {"shape": "sweep", "sched_config": NO_TAINTS}, GAP_FILTER,
    ),
}


@pytest.mark.parametrize("case", sorted(LADDER))
def test_ladder(case, prep, monkeypatch):
    pol, ask, want = LADDER[case]
    _policy(monkeypatch, **pol)
    got = select.ladder(prep, select.Ask(**ask))
    assert list(got) == ["megakernel", "xla"]
    assert got["xla"] is None
    if want is None or not want.endswith("devices"):
        assert got["megakernel"] == want
    else:  # a sweep's skips are never reported: the words are this PR's
        assert got["megakernel"].startswith(want)


# id -> (ask, has a base, the token of the xla.resident span)
CARRY = {
    "plain_prepare": ({}, False, "no_base"),
    "served": ({}, True, None),
    "segments": ({"segments": 2}, True, "segments"),
    "segments_before_no_base": ({"segments": 2}, False, "segments"),
    "masked_pass_over_a_plain_prepare": ({"node_mask": True}, False, "no_base"),
    "node_mask": ({"node_mask": True}, True, "node_mask"),
    "tie_seed": ({"tie_seed": 0}, True, "tie_seed"),
    "explain": ({"explain": True}, True, "explain"),
    "tie_seed_before_explain": ({"explain": True, "tie_seed": 0}, True, "tie_seed"),
    "sched_config": ({"sched_config": WEIGHTED}, True, "sched_config"),
    "sched_config_default": ({"sched_config": DEFAULT_CONFIG}, True, None),
    "extra_plugins": ({"extra_plugins": PLUGIN}, True, "extra_plugins"),
}


@pytest.mark.parametrize("case", sorted(CARRY))
def test_carry(case, prep):
    ask, has_base, want = CARRY[case]
    prep = dataclasses.replace(prep, resident_base=object() if has_base else None)
    assert select.carry(prep, select.Ask(**ask)) == want
    # failure reasons are asked by default, and no answer of this module depends on them
    assert select.Ask(**ask).reasons is True
    assert select.carry(prep, select.Ask(**ask, reasons=False)) == want


# id -> policy: a request batch runs on the vmapped XLA scan under every policy,
# and its riders report the megakernel's skip
BATCH = {
    "cpu": {},
    "interpret": {"env": INTERPRET},
    "tpu": {"platform": "tpu"},
    "backend_xla": {"env": BACKEND_XLA},
    "backend_tpu": {"env": BACKEND_TPU, "platform": "tpu"},
}


@pytest.mark.parametrize("case", sorted(BATCH))
def test_batch(case, prep, monkeypatch):
    _policy(monkeypatch, **BATCH[case])
    rider = reqbatch.BatchItem(cluster=None, apps=[], lo=0, hi=len(prep.ordered))
    dispatch = reqbatch.dispatch_request_batch(prep, [rider])
    assert (dispatch.engine_name, dispatch.skips) == ("xla", {"megakernel": BATCH_MK})
    assert (dispatch.outs[0].chosen >= 0).all() and dispatch.shed == {}


class _Breaker:
    def __init__(self):
        self.failures = []

    def record_failure(self, e):
        self.failures.append(e)


FAILED = {
    "interpret_stream": (INTERPRET, "stream", (ValueError, "mosaic says no")),
    "interpret_sweep": (INTERPRET, "sweep", (ValueError, "mosaic says no")),
    # interpret wins over strict, as the ladder's chain had it
    "interpret_and_strict": ({**INTERPRET, "OPENSIM_REQUIRE_TPU": "1"}, "stream", (ValueError, "mosaic says no")),
    "strict_stream": (
        {"OPENSIM_REQUIRE_TPU": "1"}, "stream",
        (RuntimeError, "--backend tpu: the Pallas megakernel failed to compile/run \\(ValueError: mosaic says no\\); "
         "refusing to silently fall back to a slower engine"),
    ),
    "strict_sweep": (
        {"OPENSIM_REQUIRE_TPU": "1"}, "sweep",
        (RuntimeError, "--backend tpu: the batched megakernel sweep failed \\(ValueError: mosaic says no\\); "
         "refusing to silently fall back to the XLA sweep"),
    ),
    "demoted_stream": ({}, "stream", None),
    "demoted_sweep": ({}, "sweep", None),
}


@pytest.mark.parametrize("case", sorted(FAILED))
def test_kernel_failed(case, monkeypatch):
    env, shape, raised = FAILED[case]
    _policy(monkeypatch, env=env)
    breaker, boom = _Breaker(), ValueError("mosaic says no")
    if raised is None:
        assert select.kernel_failed(boom, shape, breaker) == "ValueError: mosaic says no"
        assert breaker.failures == [boom]
        assert select.kernel_failed(boom, shape) == "ValueError: mosaic says no"  # the sweep gives no breaker
        return
    with pytest.raises(raised[0], match=raised[1]) as caught:
        select.kernel_failed(boom, shape, breaker)
    assert breaker.failures == []  # neither counts against the breaker
    assert caught.value is boom or caught.value.__cause__ is boom


def test_policy_is_read_afresh(monkeypatch):
    _policy(monkeypatch)
    first = select.policy()
    assert (first.platform, first.interpret, first.strict) == ("cpu", False, False)
    _policy(monkeypatch, env={**INTERPRET, **BACKEND_TPU}, platform="tpu", devices=4)
    second = select.policy()
    assert second[:4] == ("tpu", 4, True, True)
    # exact values: `1` and `interpret`, as the knobs' registry says
    _policy(monkeypatch, env={"OPENSIM_FASTPATH": "1", "OPENSIM_REQUIRE_TPU": "true"})
    assert select.policy()[2:4] == (False, False)


def _code_strings(tree):
    """Every string constant of a module that is not a docstring."""
    docstrings = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef, ast.AsyncFunctionDef)):
            body = node.body
            if body and isinstance(body[0], ast.Expr) and isinstance(body[0].value, ast.Constant):
                docstrings.add(id(body[0].value))
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str) and id(node) not in docstrings:
            yield node


def test_select_is_the_only_reader():
    """No module but engine/select.py (and the registry, and the CLI that
    writes them) names one of the three knobs in code, and nothing in engine/,
    parallel/ or planner/ asks the platform but select."""
    allowed = {"engine/select.py", "utils/envknobs.py", "cli/main.py"}
    offenders = []
    pkg = ROOT / "opensim_tpu"
    for path in sorted(pkg.rglob("*.py")):
        rel = path.relative_to(pkg).as_posix()
        tree = ast.parse(path.read_text())
        if rel not in allowed:
            offenders += [
                f"{rel}:{node.lineno} names {node.value}"
                for node in _code_strings(tree) if node.value in KNOBS
            ]
        if rel.split("/")[0] in ("engine", "parallel", "planner") and rel != "engine/select.py":
            offenders += [
                f"{rel}:{node.lineno} asks jax.default_backend"
                for node in ast.walk(tree)
                if isinstance(node, ast.Attribute) and node.attr == "default_backend"
            ]
    assert not offenders, offenders


def test_a_plain_simulate_on_the_cpu_does_not_import_pallas():
    code = (
        "import sys\n"
        "from opensim_tpu.engine.simulator import AppResource, simulate\n"
        "from opensim_tpu.models import ResourceTypes, fixtures as fx\n"
        "cluster, app = ResourceTypes(), ResourceTypes()\n"
        "cluster.nodes.append(fx.make_fake_node('n0', '8', '16Gi'))\n"
        "app.deployments.append(fx.make_fake_deployment('web', 2, '1', '1Gi'))\n"
        "res = simulate(cluster, [AppResource('a', app)])\n"
        "assert res.engine.name == 'xla', res.engine\n"
        "assert 'no TPU backend' in res.engine.skipped['megakernel']\n"
        "loaded = [m for m in sys.modules if m.endswith(('ops.pallas_scan', 'engine.fastpath'))]\n"
        "assert not loaded, loaded\n"
    )
    env = {k: v for k, v in os.environ.items() if k not in KNOBS}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, env=env, capture_output=True, text=True, timeout=300
    )
    assert done.returncode == 0, done.stderr[-2000:]


# id -> (policy, ask, the rung that turned the run away and the token of its reason)
TURNED_AWAY = {
    "the_kernel_serves_it": ({"env": INTERPRET}, {}, None),
    # a kernel the policy switched off turned nothing away
    "no_tpu": ({}, {}, None),
    "backend_xla": ({"env": BACKEND_XLA}, {"explain": True}, None),
    # the row's name in DECLINES
    "batch": ({"env": INTERPRET}, {"shape": "batch"}, ("megakernel", "batch")),
    "many_devices": ({"platform": "tpu", "devices": 4}, {"shape": "sweep"}, ("megakernel", "many_devices")),
    "segments": ({"env": INTERPRET}, {"segments": 2}, ("megakernel", "segments")),
    "explain": ({"env": INTERPRET}, {"explain": True}, ("megakernel", "explain")),
    # the token names what the kernel cannot compute
    "sched_config": ({"env": INTERPRET}, {"sched_config": NO_TAINTS}, ("megakernel", "sched_config:disabled_filter")),
    "sched_config_fit_ignored_cols": (
        {"env": INTERPRET}, {"sched_config": DEFAULT_CONFIG._replace(fit_ignored_cols=(2,))},
        ("megakernel", "sched_config:fit_ignored_cols"),
    ),
    "sched_config_weights_are_served": ({"env": INTERPRET}, {"sched_config": WEIGHTED}, None),
    "extra_plugins": ({"env": INTERPRET}, {"extra_plugins": PLUGIN}, ("megakernel", "extra_plugins")),
    "tie_seed": ({"env": INTERPRET}, {"tie_seed": 7}, ("megakernel", "tie_seed")),
    "start_state": ({"env": INTERPRET}, {"start_state": True}, ("megakernel", "start_state")),
    "the_first_row_that_applies": ({"env": INTERPRET}, {"explain": True, "tie_seed": 7}, ("megakernel", "explain")),
    # on the chip's policy: the kernel is on, so a row turns the run away
    "tpu_batch": ({"platform": "tpu"}, {"shape": "batch"}, ("megakernel", "batch")),
    "tpu_segments": ({"env": BACKEND_TPU, "platform": "tpu"}, {"segments": 2}, ("megakernel", "segments")),
    "tpu_one_device_sweep_is_served": ({"platform": "tpu", "devices": 1}, {"shape": "sweep"}, None),
    "backend_xla_on_tpu": ({"env": BACKEND_XLA, "platform": "tpu"}, {"shape": "batch"}, None),
}


@pytest.mark.parametrize("case", sorted(TURNED_AWAY))
def test_turned_away(case, prep, monkeypatch):
    pol, ask, want = TURNED_AWAY[case]
    _policy(monkeypatch, **pol)
    ask, pol = select.Ask(**ask), select.policy()
    rungs = select.ladder(prep, ask, pol)
    assert select.turned_away(prep, ask, pol, rungs) == want
    attrs = select.decline_attrs(prep, want)
    if want is None:
        assert attrs == {}
    else:
        assert attrs == {"declined": ":".join(want), "templates": 1, "selectors": 1, "pinned_pods": 0}


def _shaped(prep, **shapes):
    """``prep`` with encoder tables of other shapes: the envelope reads
    only their widths, so each is a broadcast zero that holds no memory."""
    arrays = {name: np.broadcast_to(np.zeros((1,) * len(shape), bool), shape) for name, shape in shapes.items()}
    return dataclasses.replace(prep, ec_np=prep.ec_np._replace(**arrays))


def _over_vmem(prep, monkeypatch):
    monkeypatch.setattr(fastpath, "vmem_estimate", lambda prep, sublanes=1: 12_300_000)
    return prep


def _high_port(prep, monkeypatch):
    ports = np.full(prep.ec_np.ports.shape, 64, prep.ec_np.ports.dtype)
    return dataclasses.replace(
        prep, features=prep.features._replace(ports=True), ec_np=prep.ec_np._replace(ports=ports)
    )


def _unlabeled_node(prep, monkeypatch):
    ec = prep.ec_np
    node_domain = np.array(ec.node_domain)
    node_domain[0, prep.meta.vocab.topo_keys.items().index(fastpath.HOSTNAME)] = ec.domain_topo.shape[0] - 1
    return dataclasses.replace(prep, ec_np=ec._replace(node_domain=node_domain))


def _five_zone_keys(prep, monkeypatch):
    keys = [f"example.com/k{i}" for i in range(5)]
    cluster = ResourceTypes()
    for i in range(4):
        labels = {key: f"d{i % 2}" for key in keys}
        cluster.nodes.append(fx.make_fake_node(f"n{i}", "8", "16Gi", "110", fx.with_labels(labels)))
    spread = [
        {"maxSkew": 1, "topologyKey": key, "whenUnsatisfiable": "ScheduleAnyway",
         "labelSelector": {"matchLabels": {"app": "web"}}}
        for key in keys
    ]
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment(
        "web", 4, "1", "1Gi", fx.with_pod_labels({"app": "web"}), fx.with_topology_spread(spread)))
    return prepare(cluster, [AppResource("a", app)], node_pad=128)


def _duplicate_hostname(prep, monkeypatch):
    ec = prep.ec_np
    node_domain = np.array(ec.node_domain)
    host = prep.meta.vocab.topo_keys.items().index(fastpath.HOSTNAME)
    node_domain[1, host] = node_domain[0, host]
    return dataclasses.replace(prep, ec_np=ec._replace(node_domain=node_domain))


def _feature(flag, **shapes):
    """A feature switched on with one of its tables wider than the kernel's rows."""

    def build(prep, monkeypatch):
        shaped = _shaped(prep, **shapes)
        return dataclasses.replace(shaped, features=prep.features._replace(**{flag: True}))

    return build


def _tables(**shapes):
    def build(prep, monkeypatch):
        ec, grown = prep.ec_np, {}
        U, A, R = shapes.get("U"), shapes.get("A"), shapes.get("R")
        if U is not None:
            grown["req"] = (U,) + ec.req.shape[1:]
        if A is not None or U is not None:
            grown["matches_sel"] = (U or ec.matches_sel.shape[0], A or ec.matches_sel.shape[1])
        if R is not None:
            grown["alloc"] = (ec.alloc.shape[0], R)
        return _shaped(prep, **grown)

    return build


# fastpath.why_not's words -> (its token, what takes the prep there); the words are the
# ones the declined attribute and the engine's skip map carry, unchanged since PR 36
ENVELOPE = {
    "table sizes outside envelope: U=10450 > 2048 supported, A=5300 > 64 supported": ("U+A", _tables(U=10450, A=5300)),
    "table sizes outside envelope: U=3001 > 2048 supported": ("U", _tables(U=3001)),
    "table sizes outside envelope: A=80 > 64 supported": ("A", _tables(A=80)),
    "table sizes outside envelope: R=9 > 8 supported": ("R", _tables(R=9)),
    "table sizes outside envelope: R=9 > 8 supported, U=2120 > 2048 supported": ("R+U", _tables(R=9, U=2120)),
    "VMEM estimate 12.3 MB exceeds the 10 MB budget": ("vmem", _over_vmem),
    "5 non-hostname topology keys > 4 supported": ("topo_keys", _five_zone_keys),
    "port-vocab ids >=64 exceed the 64 padded port rows": ("features", _high_port),
    "some valid nodes carry no hostname label": ("features", _unlabeled_node),
    "hostname domains are not node-identity (duplicate hostname labels)": ("features", _duplicate_hostname),
    "9 GPUs/node > 8 supported": ("features", _feature("gpu", node_gpu_mem=(128, 9))),
    "open-local VG/device axes > 8 supported": ("features", _feature("local", node_vg_cap=(128, 9))),
    "inter-pod global term tables > 16 rows": ("features", _feature("interpod", anti_g_sel=(17,))),
    "inter-pod per-template terms > 4 per pod": ("features", _feature("interpod", at_sel=(1, 5))),
}


@pytest.mark.parametrize("reason", sorted(ENVELOPE))
def test_the_kernels_envelope_as_a_token(reason, prep, monkeypatch):
    token, build = ENVELOPE[reason]
    _policy(monkeypatch, env=INTERPRET)
    shaped = build(prep, monkeypatch)
    assert fastpath.why_not(shaped) == (token, reason)
    pol = select.policy()
    rungs = select.ladder(shaped, select.Ask(), pol)
    assert rungs["megakernel"] == reason
    assert select.turned_away(shaped, select.Ask(), pol, rungs) == ("megakernel", token)
    assert select.decline_attrs(shaped, ("megakernel", token))["declined"] == f"megakernel:{token}"


def test_the_envelope_token_of_a_config_names_its_gap(prep):
    assert fastpath.why_not(prep, NO_TAINTS) == ("sched_config:disabled_filter", GAP_FILTER)
    assert fastpath.why_not(prep) is None
