"""Chaos suite (`make chaos`): deterministic fault injection through the
REST serving path. Acceptance bar (ISSUE 3): every injection point —
snapshot.http, prep.encode, engine.compile, engine.device_put, cache.stale —
either RECOVERS (retry/fallback, placements identical to an uninjected run)
or FAILS CLOSED with a typed JSON error (504/503/500 — never a hang, never a
raw traceback), with /metrics still served afterwards. Engine demotions are
visible in EngineDecision.skipped, breaker trips in /metrics, and
OPENSIM_REQUIRE_TPU=1 still fails hard with no silent demotion."""

import json
import threading
import urllib.error
import urllib.request
from contextlib import contextmanager

import pytest

from opensim_tpu.engine.simulator import AppResource, simulate
from opensim_tpu.models import ResourceTypes, fixtures as fx
from opensim_tpu.resilience import breaker as breaker_mod
from opensim_tpu.resilience import faults


@pytest.fixture(autouse=True)
def _clean_resilience_state(monkeypatch):
    monkeypatch.delenv("OPENSIM_FAULTS", raising=False)
    monkeypatch.setenv("OPENSIM_SNAPSHOT_BACKOFF_S", "0.001")
    faults.clear_faults()
    breaker_mod.reset_breakers()
    yield
    faults.clear_faults()
    breaker_mod.reset_breakers()


def _cluster(n_nodes=6):
    rt = ResourceTypes()
    for i in range(n_nodes):
        rt.nodes.append(
            fx.make_fake_node(
                f"n{i:03d}", "16", "64Gi", "110",
                fx.with_labels({"topology.kubernetes.io/zone": f"z{i % 3}"}),
            )
        )
    # a bound snapshot pod: the REST base-entry cache only engages when the
    # snapshot has schedulable pods (an empty prepare is never cached), and
    # the cache.stale chaos tests need the check_fresh path exercised
    rt.pods.append(fx.make_fake_pod("pinned", "100m", "128Mi", fx.with_node_name("n000")))
    return rt


def _payload():
    return {"deployments": [fx.make_fake_deployment("web", 6, "500m", "1Gi").raw]}


def _apps():
    rt = ResourceTypes()
    rt.deployments.append(fx.make_fake_deployment("web", 6, "500m", "1Gi"))
    return [AppResource("web", rt)]


def _shape(resp):
    """Comparable placement shape of a REST response: pod names embed a
    process-global expansion counter, so recovery equality is asserted on
    (node, pod count) plus unscheduled reasons — the same shape the
    prepcache parity tests use."""
    return (
        sorted((e["node"], len(e["pods"])) for e in resp["nodeStatus"]),
        sorted(u["reason"] for u in resp["unscheduledPods"]),
    )


def _result_shape(res):
    return (
        sorted((ns.node.metadata.name, len(ns.pods)) for ns in res.node_status),
        sorted(u.reason for u in res.unscheduled_pods),
    )


@contextmanager
def _serve(server):
    from http.server import ThreadingHTTPServer

    from opensim_tpu.server.rest import make_handler

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(server))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        yield httpd.server_address[1]
    finally:
        httpd.shutdown()


def _metrics_ok(server) -> str:
    """/metrics must render after every fault class (acceptance bar)."""
    from opensim_tpu.server.rest import METRICS

    text = METRICS.render(prep_cache=server.prep_cache)
    assert "simon_requests_total" in text
    return text


def _baseline_response(kind="deploy"):
    """The uninjected answer for (_cluster(), _payload()) — recovery tests
    assert byte-identical placements against this."""
    from opensim_tpu.server.rest import SimonServer

    server = SimonServer(base_cluster=_cluster())
    code, body = (server.deploy_apps if kind == "deploy" else server.scale_apps)(_payload())
    assert code == 200
    return body


# ---------------------------------------------------------------------------
# snapshot.http — retry, stale-serve degradation, fail-closed 503
# ---------------------------------------------------------------------------


def _kubeconfig_server(monkeypatch, ttl=3600.0):
    from opensim_tpu.server import rest

    fetches = []

    def fake_fetch(kubeconfig, master=None):
        fetches.append(kubeconfig)
        return _cluster()

    monkeypatch.setattr(rest, "cluster_from_kubeconfig", fake_fetch)
    return rest.SimonServer(kubeconfig="/tmp/kc", snapshot_ttl_s=ttl), fetches


def test_snapshot_http_transient_fault_recovers_via_retry(monkeypatch):
    from opensim_tpu.server.rest import METRICS

    server, fetches = _kubeconfig_server(monkeypatch)
    retries0 = METRICS.snapshot_retries
    # 2 injected failures, 3 attempts (OPENSIM_SNAPSHOT_RETRIES default):
    # the third attempt lands and the request must not notice
    faults.inject("snapshot.http", count=2, exc="fetch")
    code, body = server.deploy_apps(_payload())
    assert code == 200, body
    assert _shape(body) == _shape(_baseline_response())
    assert faults.fault_stats()["snapshot.http"] == 2
    assert METRICS.snapshot_retries - retries0 == 2
    assert not server.snapshot_stale
    _metrics_ok(server)


def test_snapshot_down_serves_stale_with_header(monkeypatch):
    from opensim_tpu.server.rest import METRICS

    server, fetches = _kubeconfig_server(monkeypatch)
    stale0 = METRICS.snapshot_stale_served
    with _serve(server) as port:
        body = json.dumps(_payload()).encode()

        def post():
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api/deploy-apps", data=body, method="POST"
            )
            return urllib.request.urlopen(req)

        with post() as r:
            fresh = json.load(r)
        # apiserver goes down past the TTL: every retry fails, the last
        # good snapshot serves the request, tagged stale
        server._snapshot_at -= 7200.0
        faults.inject("snapshot.http", count=99, exc="fetch")
        with post() as r:
            assert r.headers.get("X-Simon-Snapshot") == "stale"
            degraded = json.load(r)
    assert _shape(degraded) == _shape(fresh)
    assert server.snapshot_stale
    assert METRICS.snapshot_stale_served - stale0 == 1
    assert len(fetches) == 1  # the down apiserver was probed once per TTL
    text = _metrics_ok(server)
    assert "simon_snapshot_stale_served_total" in text


def test_snapshot_down_cold_fails_closed_503(monkeypatch):
    server, _ = _kubeconfig_server(monkeypatch)
    faults.inject("snapshot.http", count=99, exc="fetch")
    code, body = server.deploy_apps(_payload())
    assert code == 503
    assert body["retryable"] is True
    assert "snapshot unavailable" in body["error"]
    assert faults.fault_stats()["snapshot.http"] == 3  # bounded attempts
    _metrics_ok(server)


# ---------------------------------------------------------------------------
# prep.encode / engine.device_put — fail closed typed, then recover
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("point", ["prep.encode", "engine.device_put"])
def test_prepare_stage_fault_fails_closed_then_recovers(point):
    from opensim_tpu.server.rest import SimonServer

    server = SimonServer(base_cluster=_cluster())
    faults.inject(point, count=1, exc="runtime")
    code, body = server.deploy_apps(_payload())
    assert code == 500
    assert f"injected fault at {point}" in body["error"]
    assert body["type"] == "RuntimeError"
    _metrics_ok(server)
    # the fault burned out: the very next request recovers fully
    code, body = server.deploy_apps(_payload())
    assert code == 200
    assert _shape(body) == _shape(_baseline_response())


# ---------------------------------------------------------------------------
# engine.compile — fallback ladder demotion + circuit breaker
# ---------------------------------------------------------------------------


@pytest.fixture
def kernel_on_the_chip(monkeypatch):
    """The megakernel as the chip's policy has it: a TPU backend, no
    interpret mode (so a kernel failure demotes instead of raising), the
    stream inside the envelope. The kernel itself runs in the interpreter."""
    import jax

    from opensim_tpu.engine import fastpath

    monkeypatch.delenv("OPENSIM_FASTPATH", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fastpath, "why_not", lambda prep, config=None: None)
    monkeypatch.setattr(fastpath, "_resolve_interpret", lambda explicit: True)


def test_engine_compile_fault_demotes_to_xla_with_identical_placements(kernel_on_the_chip):
    cluster, apps = _cluster(), _apps()
    res0 = simulate(cluster, apps)
    assert res0.engine.name == "megakernel"

    faults.inject("engine.compile", count=1, exc="runtime")
    res1 = simulate(_cluster(), _apps())
    # demoted one rung, visibly, with identical placements
    assert res1.engine.name == "xla"
    assert "injected fault at engine.compile" in res1.engine.skipped["megakernel"]

    assert _result_shape(res1) == _result_shape(res0)
    # one failure does not open the breaker (threshold 3): next run is the kernel's
    br = breaker_mod.engine_breaker("megakernel")
    assert br.failures_total == 1 and br.state() == "closed"
    assert simulate(_cluster(), _apps()).engine.name == "megakernel"


def test_breaker_trips_after_threshold_then_half_open_recovers(monkeypatch, kernel_on_the_chip):
    from opensim_tpu.server.rest import METRICS

    monkeypatch.setenv("OPENSIM_BREAKER_THRESHOLD", "2")
    breaker_mod.reset_breakers()

    faults.inject("engine.compile", count=2, exc="runtime")
    for _ in range(2):
        assert simulate(_cluster(), _apps()).engine.name == "xla"
    br = breaker_mod.engine_breaker("megakernel")
    assert br.state() == "open" and br.trips_total == 1

    # breaker open: the kernel attempt is skipped outright (no fault armed,
    # yet the engine still demotes — the skip reason says breaker)
    res = simulate(_cluster(), _apps())
    assert res.engine.name == "xla"
    assert "circuit breaker open" in res.engine.skipped["megakernel"]

    # the trip is visible at /metrics
    text = METRICS.render()
    assert 'simon_engine_breaker_trips_total{engine="megakernel"} 1' in text
    assert 'simon_engine_breaker_open{engine="megakernel"} 1' in text

    # cooldown elapses → half-open probe runs the real engine and closes
    br.clock = lambda: br._opened_at + br.cooldown_s + 1.0
    res = simulate(_cluster(), _apps())
    assert res.engine.name == "megakernel"
    assert br.state() == "closed"


def test_require_tpu_fails_hard_never_demotes(monkeypatch):
    """OPENSIM_REQUIRE_TPU=1: an injected megakernel compile failure must
    raise, not demote — even with healthy fallback engines below."""
    import jax

    from opensim_tpu.engine import fastpath

    monkeypatch.setenv("OPENSIM_REQUIRE_TPU", "1")
    monkeypatch.delenv("OPENSIM_FASTPATH", raising=False)
    monkeypatch.setattr(fastpath, "why_not", lambda prep, config=None: None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    faults.inject("engine.compile", count=1, exc="runtime")
    with pytest.raises(RuntimeError, match="refusing to silently fall back"):
        simulate(_cluster(), _apps())
    assert faults.fault_stats()["engine.compile"] == 1  # the kernel WAS tried


# ---------------------------------------------------------------------------
# cache.stale — transparent single retry, fail closed on repeat
# ---------------------------------------------------------------------------


def test_cache_stale_fault_recovers_transparently():
    from opensim_tpu.server.rest import METRICS, SimonServer

    server = SimonServer(base_cluster=_cluster())
    retries0 = METRICS.stale_prep_retries
    code, first = server.deploy_apps(_payload())
    assert code == 200

    # one stale hit: check_fresh evicts, the internal retry re-prepares
    faults.inject("cache.stale", count=1, exc="stale")
    code, body = server.deploy_apps(_payload())
    assert code == 200
    assert _shape(body) == _shape(first)
    assert METRICS.stale_prep_retries - retries0 == 1
    _metrics_ok(server)


def test_cache_stale_repeat_fails_closed_then_recovers():
    from opensim_tpu.server.rest import SimonServer

    server = SimonServer(base_cluster=_cluster())
    code, first = server.deploy_apps(_payload())
    assert code == 200

    # stale on the original attempt AND on the internal retry: typed 500,
    # never a loop
    faults.inject("cache.stale", count=2, exc="stale")
    code, body = server.deploy_apps(_payload())
    assert code == 500
    assert body["type"] == "StaleFingerprintError"
    assert "injected fault at cache.stale" in body["error"]
    _metrics_ok(server)

    code, body = server.deploy_apps(_payload())
    assert code == 200 and _shape(body) == _shape(first)


# ---------------------------------------------------------------------------
# request deadlines — typed 504, server stays healthy
# ---------------------------------------------------------------------------


def test_deadline_exhaustion_returns_504_with_phase():
    from opensim_tpu.server.rest import METRICS, SimonServer

    server = SimonServer(base_cluster=_cluster())
    timeouts0 = METRICS.request_timeouts
    with _serve(server) as port:
        body = json.dumps(_payload()).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/deploy-apps", data=body, method="POST",
            headers={"X-Simon-Timeout-S": "0.000001"},
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 504
        resp = json.load(ei.value)
        assert resp["phase"] in ("snapshot", "prepare", "encode", "schedule", "decode")
        assert "deadline exceeded" in resp["error"]

        # the timed-out request left the server fully healthy
        req2 = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/deploy-apps", data=body, method="POST"
        )
        with urllib.request.urlopen(req2) as r:
            assert _shape(json.load(r)) == _shape(_baseline_response())
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics") as r:
            text = r.read().decode()
    assert METRICS.request_timeouts - timeouts0 == 1
    assert "simon_request_timeouts_total" in text


def test_env_default_deadline_applies_without_header(monkeypatch):
    from opensim_tpu.server.rest import SimonServer, request_deadline

    monkeypatch.setenv("OPENSIM_REQUEST_TIMEOUT_S", "0.000001")
    dl = request_deadline({})
    assert dl is not None and dl.budget_s == pytest.approx(1e-6)
    server = SimonServer(base_cluster=_cluster())
    code, body = server.deploy_apps(_payload(), deadline=dl)
    assert code == 504 and "phase" in body
    # unset/0 disables
    monkeypatch.setenv("OPENSIM_REQUEST_TIMEOUT_S", "0")
    assert request_deadline({}) is None
