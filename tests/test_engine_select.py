"""engine/select.py is the one place that says which engine runs: its table,
one case per (ask, policy) row. The expected rungs and reason strings are the
ones the gates wrote before they moved there (simulator._run_engine_ladder,
fastpath.why_not, nativepath.why_not, resident._why_not, scenarios.sweep_auto,
reqbatch.dispatch_request_batch at PR 30), not read back from the table."""

import ast
import dataclasses
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from opensim_tpu import native
from opensim_tpu.engine import select
from opensim_tpu.engine.schedconfig import DEFAULT_CONFIG
from opensim_tpu.engine.simulator import AppResource, prepare
from opensim_tpu.models import ResourceTypes
from opensim_tpu.models import fixtures as fx

ROOT = pathlib.Path(__file__).resolve().parents[1]
KNOBS = (
    "OPENSIM_NATIVE", "OPENSIM_DISABLE_NATIVE", "OPENSIM_DISABLE_FASTPATH",
    "OPENSIM_FASTPATH", "OPENSIM_REQUIRE_TPU", "OPENSIM_BATCH_ENGINE",
)

NO_TPU = "no TPU backend (jax.default_backend()='cpu')"
TPU_OWNS = "TPU backend present (the megakernel/XLA scan own the accelerator)"
XLA_OFF_MK = "disabled by --backend xla (OPENSIM_DISABLE_FASTPATH)"
XLA_OFF_NATIVE = "disabled by --backend xla (OPENSIM_DISABLE_NATIVE)"
NATIVE_OFF_MK = "disabled by --backend native (OPENSIM_NATIVE=1)"
BATCH_MK = "request-axis batches run on the vmapped XLA scan (or sequential C++ scans)"
BATCH_NATIVE = "request-axis batching dispatches ONE vmapped scan"
BATCH_XLA = "OPENSIM_BATCH_ENGINE routed the batch to the C++ engine"
PLUGINS_MK = "out-of-tree extra_plugins run on the XLA scan"
PLUGINS_NATIVE = "out-of-tree extra_plugins are jittable callables (XLA scan only)"
WEIGHTED = DEFAULT_CONFIG._replace(w_least=3.0)
BINPACK = DEFAULT_CONFIG._replace(w_least=0.0, w_rtcr=1.0, rtcr_shape=((0.0, 0.0), (100.0, 100.0)),
                                  rtcr_resources=((0, 1.0), (1, 1.0)))
NO_TAINTS = DEFAULT_CONFIG._replace(f_taints=False)
GAP_FILTER = "a scheduler config the kernel cannot compute (disabled_filter)"
GAP_COLS = "a scheduler config the kernel cannot compute (fit_ignored_cols)"
PLUGIN = (("filter", lambda ec, st, u: None),)


@pytest.fixture(scope="module")
def prep():
    if not native.available():  # pragma: no cover - no C++ toolchain
        pytest.skip("C++ engine unavailable")
    cluster = ResourceTypes()
    for i in range(4):
        cluster.nodes.append(fx.make_fake_node(f"n{i}", "8", "16Gi"))
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("web", 6, "1", "1Gi"))
    return prepare(cluster, [AppResource("a", app)], node_pad=128)


def _policy(monkeypatch, env=(), platform=None, devices=None, built=True):
    for name in KNOBS:
        monkeypatch.delenv(name, raising=False)
    for name, value in dict(env).items():
        monkeypatch.setenv(name, value)
    if platform is not None:
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if devices is not None:
        monkeypatch.setattr(jax, "devices", lambda *a: [object()] * devices)
    if not built:
        monkeypatch.setattr(native, "available", lambda: False)
        monkeypatch.setattr(native, "load_error", lambda: "no compiler")


INTERPRET = {"OPENSIM_FASTPATH": "interpret"}
BACKEND_XLA = {"OPENSIM_DISABLE_FASTPATH": "1", "OPENSIM_DISABLE_NATIVE": "1"}
BACKEND_NATIVE = {"OPENSIM_NATIVE": "1"}

# id -> (policy, ask, megakernel, native); the XLA scan always takes it
LADDER = {
    # the platform, and the two ways onto the kernel
    "plain_cpu": ({}, {}, NO_TPU, None),
    "plain_tpu": ({"platform": "tpu"}, {}, None, TPU_OWNS),
    "plain_interpret": ({"env": INTERPRET}, {}, None, None),
    # each ask alone: the asks come before the platform
    "segments": ({}, {"segments": 2}, "segmented multi-profile stream (2 segments)", None),
    "explain": ({}, {"explain": True}, "explain mode audits per-filter verdicts (C++/XLA engines)", None),
    # a config declines the kernel only for what the kernel cannot compute: its score
    # weights and RequestedToCapacityRatio are served, a disabled filter and ignored fit columns are not
    "sched_config": ({}, {"sched_config": NO_TAINTS}, GAP_FILTER, None),
    "sched_config_weights": ({"env": INTERPRET}, {"sched_config": WEIGHTED}, None, None),
    "sched_config_rtcr": (
        {"env": INTERPRET}, {"sched_config": BINPACK}, None,
        "RequestedToCapacityRatio runs on the megakernel or the XLA scan",
    ),
    # the default config is no config
    "sched_config_default_is_still_a_config": ({"env": INTERPRET}, {"sched_config": DEFAULT_CONFIG}, None, None),
    "fit_ignored_cols": (
        {}, {"sched_config": DEFAULT_CONFIG._replace(fit_ignored_cols=(2,))},
        GAP_COLS,
        "NodeResourcesFitArgs ignoredResources need the XLA scan's per-column skip",
    ),
    "extra_plugins": ({}, {"extra_plugins": PLUGIN}, PLUGINS_MK, PLUGINS_NATIVE),
    "tie_seed": ({}, {"tie_seed": 7}, "sampled tie-break runs on the C++ engine or XLA scan", None),
    # the first gate that applies is the one recorded
    "explain_before_tie_seed": (
        {"env": INTERPRET}, {"explain": True, "tie_seed": 7},
        "explain mode audits per-filter verdicts (C++/XLA engines)", None,
    ),
    "segments_before_all": (
        {"platform": "tpu"}, {"segments": 3, "explain": True, "extra_plugins": PLUGIN},
        "segmented multi-profile stream (3 segments)", TPU_OWNS,
    ),
    # a node mask is the kernel's validity row: served (PR 30)
    "node_mask_interpret": ({"env": INTERPRET}, {"node_mask": True}, None, None),
    "node_mask_tpu": ({"platform": "tpu"}, {"node_mask": True}, None, TPU_OWNS),
    # a campaign step's own carry and forced vector: the C++ or XLA scan, as campaign._run_engine had it
    "start_state": (
        {"env": INTERPRET}, {"node_mask": True, "start_state": True},
        "a stream from the caller's scan state runs on the C++ engine or XLA scan", None,
    ),
    # a caller that reads no failure reasons (ISSUE 36): every rung serves it as before; what it changes
    # is whether the ladder re-scans a kernel result with a mid-stream failure, not which rung runs
    "no_reasons_cpu": ({}, {"reasons": False}, NO_TPU, None),
    "no_reasons_interpret": ({"env": INTERPRET}, {"reasons": False, "node_mask": True}, None, None),
    "no_reasons_tpu": ({"platform": "tpu"}, {"reasons": False}, None, TPU_OWNS),
    "no_reasons_explain": (
        {"env": INTERPRET}, {"reasons": False, "explain": True},
        "explain mode audits per-filter verdicts (C++/XLA engines)", None,
    ),
    # --backend xla / native / tpu
    "backend_xla_cpu": ({"env": BACKEND_XLA}, {}, NO_TPU, XLA_OFF_NATIVE),
    "backend_xla_tpu": ({"env": BACKEND_XLA, "platform": "tpu"}, {}, XLA_OFF_MK, XLA_OFF_NATIVE),
    "backend_native_cpu": ({"env": BACKEND_NATIVE}, {}, NO_TPU, None),
    "backend_native_tpu": ({"env": BACKEND_NATIVE, "platform": "tpu"}, {}, NATIVE_OFF_MK, None),
    "backend_native_unbuilt": (
        {"env": BACKEND_NATIVE, "built": False}, {}, NO_TPU, "engine not built: no compiler",
    ),
    "cpu_unbuilt": ({"built": False}, {}, NO_TPU, "engine not built: no compiler"),
    "tpu_never_builds": ({"platform": "tpu", "built": False}, {}, None, TPU_OWNS),
    "backend_tpu": ({"env": {"OPENSIM_REQUIRE_TPU": "1"}, "platform": "tpu"}, {}, None, TPU_OWNS),
    # a scenario sweep: the kernel and the C++ scans for exactly one device
    "sweep_1_cpu": ({"devices": 1}, {"shape": "sweep"}, NO_TPU, None),
    "sweep_1_interpret": ({"env": INTERPRET, "devices": 1}, {"shape": "sweep"}, None, None),
    "sweep_1_tpu": ({"platform": "tpu", "devices": 1}, {"shape": "sweep"}, None, TPU_OWNS),
    "sweep_4_tpu": ({"platform": "tpu", "devices": 4}, {"shape": "sweep"}, "4 devices", "4 devices"),
    "sweep_8_cpu": ({}, {"shape": "sweep"}, "8 devices", "8 devices"),
    "sweep_1_config": ({"env": INTERPRET, "devices": 1}, {"shape": "sweep", "sched_config": WEIGHTED}, None, None),
    "sweep_1_config_the_kernel_cannot_compute": (
        {"env": INTERPRET, "devices": 1}, {"shape": "sweep", "sched_config": NO_TAINTS}, GAP_FILTER, None,
    ),
}


@pytest.mark.parametrize("case", sorted(LADDER))
def test_ladder(case, prep, monkeypatch):
    pol, ask, megakernel, native_reason = LADDER[case]
    _policy(monkeypatch, **pol)
    got = select.ladder(prep, select.Ask(**ask))
    assert list(got) == ["megakernel", "native", "xla"]
    assert got["xla"] is None
    for rung, want in (("megakernel", megakernel), ("native", native_reason)):
        if want is None or not want.endswith("devices"):
            assert got[rung] == want, rung
        else:  # a sweep's skips are never reported: the words are this PR's
            assert got[rung].startswith(want), rung


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


# id -> (policy, engine, skips) or (policy, the error)
BATCH = {
    "auto_8_cpu": ({}, "xla", {"megakernel": BATCH_MK, "native": BATCH_NATIVE}),
    "auto_1_cpu": ({"devices": 1}, "native", {"megakernel": BATCH_MK, "xla": BATCH_XLA}),
    "auto_1_tpu": ({"devices": 1, "platform": "tpu"}, "xla", {"megakernel": BATCH_MK}),
    "auto_backend_native": ({"env": BACKEND_NATIVE}, "native", {"megakernel": BATCH_MK, "xla": BATCH_XLA}),
    "auto_backend_xla": ({"env": BACKEND_XLA, "devices": 1}, "xla", {"megakernel": BATCH_MK}),
    "xla": (
        {"env": {"OPENSIM_BATCH_ENGINE": "xla"}, "devices": 1}, "xla",
        {"megakernel": BATCH_MK, "native": BATCH_NATIVE},
    ),
    "native": (
        {"env": {"OPENSIM_BATCH_ENGINE": " Native "}}, "native", {"megakernel": BATCH_MK, "xla": BATCH_XLA},
    ),
    "native_cannot_run": (
        {"env": {"OPENSIM_BATCH_ENGINE": "native", "OPENSIM_DISABLE_NATIVE": "1"}},
        (RuntimeError, "OPENSIM_BATCH_ENGINE=native but the C\\+\\+ engine cannot run this stream: disabled by"),
    ),
    "bad_value": (
        {"env": {"OPENSIM_BATCH_ENGINE": "pallas"}},
        (ValueError, "OPENSIM_BATCH_ENGINE must be auto\\|xla\\|native, got 'pallas'"),
    ),
}


@pytest.mark.parametrize("case", sorted(BATCH))
def test_batch(case, prep, monkeypatch):
    pol, *want = BATCH[case]
    _policy(monkeypatch, **pol)
    if len(want) == 1:
        with pytest.raises(want[0][0], match=want[0][1]):
            select.batch(prep)
    else:
        assert select.batch(prep) == tuple(want)


def test_a_bad_batch_mode_does_not_reach_a_plain_stream(prep, monkeypatch):
    _policy(monkeypatch, env={"OPENSIM_BATCH_ENGINE": "pallas"})
    assert select.ladder(prep)["native"] is None


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
    assert (first.platform, first.interpret, first.strict, first.forced_native) == ("cpu", False, False, False)
    _policy(monkeypatch, env={**INTERPRET, "OPENSIM_REQUIRE_TPU": "1", **BACKEND_NATIVE}, platform="tpu", devices=4)
    second = select.policy()
    assert second[:5] == ("tpu", 4, True, True, True)
    # exact values: `1` and `interpret`, as the knobs' registry says
    _policy(monkeypatch, env={"OPENSIM_FASTPATH": "1", "OPENSIM_REQUIRE_TPU": "true", "OPENSIM_NATIVE": "yes"})
    assert select.policy()[2:5] == (False, False, False)


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
    writes them) names one of the six knobs in code, and nothing in engine/,
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
        "assert res.engine.name in ('native', 'xla'), res.engine\n"
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
    # a rung the policy switched off turned nothing away; the next one is asked
    "no_tpu_and_the_cpp_scan_serves_it": ({}, {}, None),
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
    # with the kernel switched off, the C++ scan is the rung that can turn a run away
    "native_many_devices": ({}, {"shape": "sweep"}, ("native", "many_devices")),
    "native_extra_plugins": ({}, {"extra_plugins": PLUGIN}, ("native", "extra_plugins")),
    "native_fit_ignored_cols": (
        {}, {"sched_config": DEFAULT_CONFIG._replace(fit_ignored_cols=(2,))}, ("native", "fit_ignored_cols"),
    ),
    "native_rtcr": ({}, {"sched_config": BINPACK}, ("native", "rtcr")),
    "native_not_built": ({"built": False}, {}, ("native", "not_built")),
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


# the words of fastpath.why_not -> the token
ENVELOPE = {
    "table sizes outside envelope: U=10450 > 2048 supported, A=5300 > 64 supported": "U+A",
    "table sizes outside envelope: U=3001 > 2048 supported": "U",
    "table sizes outside envelope: A=80 > 64 supported": "A",
    "table sizes outside envelope: R=9 > 8 supported, U=2120 > 2048 supported": "R+U",
    "VMEM estimate 12.3 MB exceeds the 10 MB budget": "vmem",
    "5 non-hostname topology keys > 4 supported": "topo_keys",
    "port-vocab ids >=64 exceed the 64 padded port rows": "features",
    "some valid nodes carry no hostname label": "features",
}


@pytest.mark.parametrize("reason", sorted(ENVELOPE))
def test_the_kernels_envelope_as_a_token(reason, prep, monkeypatch):
    from opensim_tpu.engine import fastpath

    _policy(monkeypatch, env=INTERPRET)
    monkeypatch.setattr(fastpath, "why_not", lambda prep, config=None: reason)
    pol = select.policy()
    rungs = select.ladder(prep, select.Ask(), pol)
    assert rungs["megakernel"] == reason
    assert select.turned_away(prep, select.Ask(), pol, rungs) == ("megakernel", ENVELOPE[reason])
