"""Observability layer (ISSUE 5, docs/observability.md): request-scoped
span trees, request-id propagation, the flight recorder and its debug
endpoints, phase latency histograms, Prometheus label escaping, structured
access logs, Chrome-trace export — and the satellite acceptance bar: under
fault injection the recorded span tree marks the failing phase with error
status and carries demotion spans matching ``EngineDecision.skipped``."""

import json
import re
import logging
import threading
import urllib.error
import urllib.request
from contextlib import contextmanager

import pytest

from opensim_tpu.engine.simulator import AppResource, simulate
from opensim_tpu.models import ResourceTypes, fixtures as fx
from opensim_tpu.obs import trace as tracing
from opensim_tpu.obs.metrics import RECORDER, escape_label_value, parse_metrics
from opensim_tpu.obs.recorder import FLIGHT_RECORDER, FlightRecorder
from opensim_tpu.resilience import breaker as breaker_mod
from opensim_tpu.resilience import faults


@pytest.fixture(autouse=True)
def _clean_obs_state(monkeypatch):
    monkeypatch.delenv("OPENSIM_TRACE", raising=False)
    monkeypatch.delenv("OPENSIM_ACCESS_LOG", raising=False)
    monkeypatch.delenv("OPENSIM_FAULTS", raising=False)
    monkeypatch.setenv("OPENSIM_SNAPSHOT_BACKOFF_S", "0.001")
    faults.clear_faults()
    breaker_mod.reset_breakers()
    FLIGHT_RECORDER.clear()
    RECORDER.reset()
    yield
    faults.clear_faults()
    breaker_mod.reset_breakers()
    FLIGHT_RECORDER.clear()
    RECORDER.reset()


def _cluster(n_nodes=6):
    rt = ResourceTypes()
    for i in range(n_nodes):
        rt.nodes.append(
            fx.make_fake_node(
                f"n{i:03d}", "16", "64Gi", "110",
                fx.with_labels({"topology.kubernetes.io/zone": f"z{i % 3}"}),
            )
        )
    # a bound snapshot pod so the prep cache's base entry engages
    rt.pods.append(fx.make_fake_pod("pinned", "100m", "128Mi", fx.with_node_name("n000")))
    return rt


def _payload():
    return {"deployments": [fx.make_fake_deployment("web", 6, "500m", "1Gi").raw]}


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


def _span_names(trace):
    return [sp.name for sp in trace.walk()]


def _find_spans(trace, name):
    return [sp for sp in trace.walk() if sp.name == name]


# ---------------------------------------------------------------------------
# span trees on the serving path
# ---------------------------------------------------------------------------


def test_deploy_records_span_tree_with_phases_and_engine():
    from opensim_tpu.server.rest import SimonServer

    server = SimonServer(base_cluster=_cluster())
    code, _ = server.deploy_apps(_payload())
    assert code == 200
    tr = FLIGHT_RECORDER.latest()
    assert tr is not None and tr.finished
    names = _span_names(tr)
    for phase in ("prepare", "encode", "schedule", "decode"):
        assert phase in names, f"missing {phase} in {names}"
    # at least one engine rung actually ran under the schedule span
    sched = _find_spans(tr, "schedule")[0]
    assert any(c.name.startswith("engine.") for c in sched.children)
    # encode nests under prepare (inside the kind's own span, prep.full);
    # device upload nests under encode
    prep = _find_spans(tr, "prepare")[0]
    assert [c.name for c in prep.children] == ["prep.full"]
    encode = [sp for sp in prep.walk() if sp.name == "encode"]
    assert len(encode) == 1
    assert any(c.name == "engine.device_put" for c in encode[0].children)
    assert tr.root.status == "ok" and tr.http_status == 200
    assert tr.summary()["engine"]


def test_engine_decision_stamped_with_request_id(monkeypatch):
    from opensim_tpu.server import rest

    captured = []
    orig = rest._response
    monkeypatch.setattr(rest, "_response", lambda r, **kw: (captured.append(r), orig(r, **kw))[1])
    server = rest.SimonServer(base_cluster=_cluster())
    code, _ = server.deploy_apps(_payload(), request_id="my-req-7")
    assert code == 200
    assert captured[0].engine is not None
    assert captured[0].engine.request_id == "my-req-7"
    assert FLIGHT_RECORDER.get("my-req-7") is not None


def test_trace_disabled_is_dormant_but_request_id_still_flows(monkeypatch):
    from opensim_tpu.server import rest

    monkeypatch.setenv("OPENSIM_TRACE", "0")
    server = rest.SimonServer(base_cluster=_cluster())
    code, _ = server.deploy_apps(_payload())
    assert code == 200
    assert len(FLIGHT_RECORDER) == 0  # no traces recorded
    assert rest.last_request_id()  # id generated regardless
    # instrumentation points are no-ops without an ambient trace
    assert tracing.span("x") is tracing.NOOP_SPAN
    tracing.event("x")  # must not raise
    # the request histogram still observes (metrics must not go dark)
    text = rest.METRICS.render()
    assert 'simon_request_seconds_bucket{endpoint="deploy-apps",status="ok",le="+Inf"} 1' in text


def test_prep_stats_attach_as_child_spans():
    """PREP_STATS timings (full prepare / cache hit) land in the span tree."""
    from opensim_tpu.server.rest import SimonServer

    server = SimonServer(base_cluster=_cluster())
    assert server.deploy_apps(_payload())[0] == 200
    assert server.deploy_apps(_payload())[0] == 200  # warm: full-key hit
    warm = FLIGHT_RECORDER.latest()
    names = _span_names(warm)
    assert "prep.hit" in names, names


# ---------------------------------------------------------------------------
# request-id propagation + flight-recorder HTTP endpoints
# ---------------------------------------------------------------------------


def test_request_id_honored_and_echoed_over_http():
    from opensim_tpu.server.rest import SimonServer

    with _serve(SimonServer(base_cluster=_cluster())) as port:
        body = json.dumps(_payload()).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/deploy-apps", data=body, method="POST",
            headers={"X-Simon-Request-Id": "client-id-1"},
        )
        with urllib.request.urlopen(req) as resp:
            assert resp.headers.get("X-Simon-Request-Id") == "client-id-1"

        # no header -> generated id, still echoed
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/deploy-apps", data=body, method="POST"
        )
        with urllib.request.urlopen(req) as resp:
            rid = resp.headers.get("X-Simon-Request-Id")
        assert rid and rid != "client-id-1"

        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/api/debug/requests"
        ) as resp:
            summaries = json.load(resp)["requests"]
        assert [s["request_id"] for s in summaries][0] == rid  # newest first
        assert {s["request_id"] for s in summaries} == {"client-id-1", rid}
        assert all(s["endpoint"] == "deploy-apps" for s in summaries)

        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/api/debug/requests/client-id-1"
        ) as resp:
            tree = json.load(resp)
        assert tree["request_id"] == "client-id-1"
        assert tree["spans"]["name"] == "deploy-apps"
        child_names = {c["name"] for c in tree["spans"]["children"]}
        assert "schedule" in child_names and "decode" in child_names

        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/api/debug/requests/nope"
            )
        assert ei.value.code == 404


def test_hostile_request_id_is_sanitized():
    from opensim_tpu.server.rest import SimonServer

    server = SimonServer(base_cluster=_cluster())
    code, _ = server.deploy_apps(_payload(), request_id="evil\r\nX-Injected: 1")
    assert code == 200
    from opensim_tpu.server.rest import last_request_id

    rid = last_request_id()
    assert "\r" not in rid and "\n" not in rid and " " not in rid
    assert rid == "evilX-Injected:1"


def test_flight_recorder_ring_is_bounded():
    fr = FlightRecorder(capacity=2)
    for i in range(3):
        tr = tracing.TraceContext("ep", request_id=f"r{i}")
        tr.finish()
        fr.record(tr)
    assert len(fr) == 2
    assert fr.get("r0") is None
    assert fr.get("r2") is not None
    assert [s["request_id"] for s in fr.summaries()] == ["r2", "r1"]


# ---------------------------------------------------------------------------
# /metrics: histograms + exposition-format hardening
# ---------------------------------------------------------------------------


def test_phase_histograms_rendered_and_cumulative():
    from opensim_tpu.server.rest import METRICS, SimonServer

    server = SimonServer(base_cluster=_cluster())
    assert server.deploy_apps(_payload())[0] == 200
    text = METRICS.render(prep_cache=server.prep_cache)
    assert "# TYPE simon_phase_seconds histogram" in text
    rows = [
        line for line in text.splitlines()
        if line.startswith('simon_phase_seconds_bucket{phase="schedule"')
    ]
    assert rows and rows[-1].split('le="')[1].startswith("+Inf")
    counts = [int(line.rsplit(" ", 1)[1]) for line in rows]
    assert counts == sorted(counts), "bucket counts must be cumulative"
    assert counts[-1] == 1
    assert 'simon_phase_seconds_sum{phase="schedule",endpoint="deploy-apps"}' in text
    assert 'simon_phase_seconds_count{phase="schedule",endpoint="deploy-apps"} 1' in text
    # the legacy total is now derived from the request histogram
    assert "simon_simulate_seconds_total" in text


def test_hostile_label_values_cannot_corrupt_the_scrape():
    """A hostile endpoint name must not break the exposition format
    (satellite: Prometheus text-format hardening)."""
    from opensim_tpu.engine.simulator import SimulateResult
    from opensim_tpu.server.rest import METRICS

    evil = 'evil"} 1\nsimon_pwned_total{x="y'
    METRICS.record(evil, SimulateResult())
    RECORDER.observe_request(evil, 0.001)
    try:
        text = METRICS.render()
    finally:
        # METRICS is process-global: drop the hostile key for later tests
        with METRICS.lock:
            METRICS.requests.pop(evil, None)
            METRICS.simulations -= 1
    assert "simon_pwned_total" not in [
        line.split("{")[0] for line in text.splitlines()
    ]
    assert escape_label_value(evil) in text
    for line in text.splitlines():
        # every non-comment line must still parse as name{labels} value
        if not line or line.startswith("#"):
            continue
        name = line.split("{")[0].split(" ")[0]
        assert name.startswith("simon_"), f"corrupted scrape line: {line!r}"


def test_escape_label_value():
    assert escape_label_value('a"b') == 'a\\"b'
    assert escape_label_value("a\\b") == "a\\\\b"
    assert escape_label_value("a\nb") == "a\\nb"


def test_metrics_share_one_recorder_lock():
    from opensim_tpu.server.rest import METRICS

    assert METRICS.lock is RECORDER.lock


# ---------------------------------------------------------------------------
# satellite: span trees under fault injection
# ---------------------------------------------------------------------------


def test_prep_encode_fault_marks_encode_span_error():
    from opensim_tpu.server.rest import SimonServer

    server = SimonServer(base_cluster=_cluster())
    faults.inject("prep.encode", 1, "fault")
    code, body = server.deploy_apps(_payload())
    assert code == 500
    tr = FLIGHT_RECORDER.latest()
    assert tr.root.status == "error" and tr.http_status == 500
    enc = _find_spans(tr, "encode")
    assert enc and enc[0].status == "error"
    injected = _find_spans(tr, "fault.injected")
    assert injected and injected[0].attrs["point"] == "prep.encode"


def test_engine_compile_fault_demotion_spans_match_engine_decision(monkeypatch):
    """The demotion spans recorded in the trace must carry exactly the
    attribution EngineDecision.skipped reports — for every skipped rung,
    whatever this host's engine availability is."""
    from opensim_tpu.server import rest

    captured = []
    orig = rest._response
    monkeypatch.setattr(rest, "_response", lambda r, **kw: (captured.append(r), orig(r, **kw))[1])
    server = rest.SimonServer(base_cluster=_cluster())
    faults.inject("engine.compile", 1, "runtime")
    code, _ = server.deploy_apps(_payload())
    assert code == 200  # the ladder absorbs the engine failure
    engine = captured[0].engine
    tr = FLIGHT_RECORDER.latest()
    demotions = {
        sp.attrs["engine"]: sp.attrs["reason"]
        for sp in tr.walk()
        if sp.name.endswith(".skipped") and sp.status == "demoted"
    }
    assert demotions == engine.skipped
    # if the fault actually landed in an attempted engine, its span errored
    if faults.fault_stats().get("engine.compile"):
        errored = [
            sp for sp in tr.walk()
            if sp.name.startswith("engine.") and sp.status == "error"
        ]
        assert errored, "attempted engine rung should carry an error span"


def test_snapshot_fault_spans_retry_then_error(monkeypatch):
    from opensim_tpu.server import rest

    monkeypatch.setattr(
        rest, "cluster_from_kubeconfig", lambda kubeconfig, master=None: _cluster()
    )
    server = rest.SimonServer(kubeconfig="/tmp/kc", snapshot_ttl_s=3600.0)
    faults.inject("snapshot.http", 5, "fetch")  # outlasts the 3 attempts
    code, body = server.deploy_apps(_payload())
    assert code == 503 and body.get("retryable") is True
    tr = FLIGHT_RECORDER.latest()
    snap = _find_spans(tr, "snapshot")
    assert snap and snap[0].status == "error"
    retries = _find_spans(tr, "snapshot.retry")
    assert len(retries) == 2  # attempts-1 backoffs before failing closed
    assert tr.root.status == "error"

    # recovery: next request fetches clean and the snapshot span is ok
    code, _ = server.deploy_apps(_payload())
    assert code == 200
    assert _find_spans(FLIGHT_RECORDER.latest(), "snapshot")[0].status == "ok"


def test_deadline_exhaustion_marks_phase_span():
    from opensim_tpu.resilience.deadline import Deadline

    server_cluster = _cluster()
    from opensim_tpu.server.rest import SimonServer

    server = SimonServer(base_cluster=server_cluster)
    dead = Deadline.after(-1.0)  # already expired
    code, body = server.deploy_apps(_payload(), deadline=dead)
    assert code == 504
    tr = FLIGHT_RECORDER.latest()
    assert tr.root.status == "deadline-exceeded" and tr.http_status == 504
    events = _find_spans(tr, "deadline.exceeded")
    assert events and events[0].attrs["phase"] == body["phase"]
    # the failed request lands in its own histogram series and must NOT
    # inflate the success-only simulate_seconds_total continuity counter
    from opensim_tpu.server.rest import METRICS

    text = METRICS.render()
    assert "simon_simulate_seconds_total 0.000000" in text
    assert (
        'simon_request_seconds_count{endpoint="deploy-apps",status="deadline-exceeded"} 1'
        in text
    )


# ---------------------------------------------------------------------------
# access logging (satellite)
# ---------------------------------------------------------------------------


def test_access_log_opt_in_json(monkeypatch, caplog):
    from opensim_tpu.server.rest import SimonServer

    monkeypatch.setenv("OPENSIM_ACCESS_LOG", "1")
    with caplog.at_level(logging.INFO, logger="opensim_tpu.access"):
        with _serve(SimonServer(base_cluster=_cluster())) as port:
            body = json.dumps(_payload()).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api/deploy-apps", data=body, method="POST",
                headers={"X-Simon-Request-Id": "log-me"},
            )
            urllib.request.urlopen(req).read()
    records = [json.loads(r.message) for r in caplog.records if r.name == "opensim_tpu.access"]
    assert len(records) == 1
    rec = records[0]
    assert rec["endpoint"] == "/api/deploy-apps"
    assert rec["status"] == 200
    assert rec["request_id"] == "log-me"
    assert rec["method"] == "POST"
    assert rec["duration_s"] >= 0


def test_access_log_quiet_by_default(caplog):
    from opensim_tpu.server.rest import SimonServer

    with caplog.at_level(logging.INFO, logger="opensim_tpu.access"):
        with _serve(SimonServer(base_cluster=_cluster())) as port:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz").read()
    assert not [r for r in caplog.records if r.name == "opensim_tpu.access"]


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def test_chrome_trace_export_round_trip(tmp_path):
    tr = tracing.start_trace("bench", force=True)
    with tracing.trace_scope(tr):
        with tracing.span("prepare", pods=3):
            with tracing.span("encode"):
                pass
        with tracing.span("schedule") as sp:
            sp.child_from_seconds("native.delta", 0.25, steps=10)
            sp.child_from_seconds("native.bind", 0.05, steps=10)
    tr.finish()

    out = tmp_path / "trace.json"
    tracing.write_chrome(tr, str(out))
    doc = json.loads(out.read_text())
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    by_name = {e["name"]: e for e in events}
    assert {"bench", "prepare", "encode", "schedule", "native.delta", "native.bind"} <= set(by_name)
    root = by_name["bench"]
    # every span fits inside the root's window and synthetic children are
    # laid out sequentially
    assert all(e["ts"] >= 0 for e in events)
    assert by_name["native.bind"]["ts"] >= by_name["native.delta"]["ts"] + by_name["native.delta"]["dur"] - 1e-3
    assert root["dur"] >= by_name["prepare"]["dur"]
    assert by_name["schedule"]["args"]["status"] == "ok"


def test_simulate_direct_call_with_ambient_trace():
    """Library callers compose: an ambient trace picks up simulate()'s
    spans without the REST layer."""
    rt = _cluster()
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("lib", 3, "100m", "128Mi"))
    tr = tracing.start_trace("lib-call", force=True)
    with tracing.trace_scope(tr):
        res = simulate(rt, [AppResource("lib", app)])
    tr.finish()
    assert res.engine is not None
    names = _span_names(tr)
    assert "schedule" in names and "decode" in names
    # total span time ~ wall time of the traced region (the bench --trace
    # acceptance bar, asserted structurally here): the root's children are
    # disjoint and fit in the root window. No allowance: every span is a
    # real one round its work ("prep.full" lies inside "prepare").
    assert [c.name for c in tr.root.children if c.duration_s > 0] == ["prepare", "schedule", "decode"]
    kids = sorted(tr.root.children, key=lambda c: c.start)
    assert all(b.start >= a.end for a, b in zip(kids, kids[1:]))
    assert sum(c.duration_s for c in kids) <= tr.root.duration_s


def test_unclosed_spans_are_force_closed_on_finish():
    tr = tracing.TraceContext("ep")
    scope = tr.span("stuck", None)
    scope.__enter__()
    tr.finish(status="error", http_status=500)
    stuck = [sp for sp in tr.walk() if sp.name == "stuck"][0]
    assert stuck.end is not None and stuck.status == "error"
    assert tr.current_span() is tr.root


def test_native_profile_attaches_child_spans():
    from opensim_tpu import native

    if not native.available():
        pytest.skip("C++ engine not built on this host")
    import os

    rt = _cluster()
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("prof", 4, "100m", "128Mi"))
    os.environ["OPENSIM_NATIVE_PROFILE"] = "1"
    try:
        tr = tracing.start_trace("profiled", force=True)
        with tracing.trace_scope(tr):
            res = simulate(rt, [AppResource("prof", app)])
        tr.finish()
    finally:
        del os.environ["OPENSIM_NATIVE_PROFILE"]
    if res.engine is None or res.engine.name != "native":
        pytest.skip(f"native engine did not serve this run ({res.engine})")
    native_spans = _find_spans(tr, "engine.native")
    assert native_spans, _span_names(tr)
    children = {c.name for c in native_spans[0].children}
    assert any(n.startswith("native.") for n in children), children
    assert native_spans[0].attrs.get("native_path")


def test_native_bail_attribution_reaches_metrics_and_profile(monkeypatch):
    """Bail-reason attribution (abi v5): a forced-generic run must surface
    as ``simon_native_bail_total{reason="force_generic"}`` in /metrics and
    in the cumulative native snapshot served by /api/debug/profile."""
    from opensim_tpu import native
    from opensim_tpu.server import rest

    if not native.available():
        pytest.skip("C++ engine not built on this host")
    monkeypatch.setenv("OPENSIM_NATIVE_FORCE_GENERIC", "1")
    server = rest.SimonServer(base_cluster=_cluster())
    try:
        code, _body = server.deploy_apps(_payload())
        assert code == 200
        snap = rest.METRICS.native_snapshot()
        if not any(snap["steps"].values()):
            pytest.skip("native engine did not serve this run")
        text = rest.METRICS.render()
        m = re.search(r'simon_native_bail_total\{reason="force_generic"\} (\d+)', text)
        assert m and int(m.group(1)) > 0, text
        assert snap["bails"].get("force_generic", 0) > 0
        assert snap["steps"].get("generic", 0) > 0
    finally:
        # METRICS is process-global: unwind this test's contribution
        with rest.METRICS.lock:
            rest.METRICS.native_bails.clear()
            rest.METRICS.native_classes.clear()


@pytest.mark.slow
def test_bench_trace_flag_emits_chrome_json(tmp_path):
    """`bench.py --trace out.json` (acceptance bar): one JSON result line
    whose trace_span_s is within 10% of the reported wall time, plus a
    loadable Chrome-trace file covering the phases."""
    import os
    import subprocess
    import sys

    out = tmp_path / "trace.json"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"), "--pods", "400",
         "--nodes", "40", "--no-warmup", "--trace", str(out)],
        capture_output=True, text=True, timeout=560, env=env, cwd=repo,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["trace_file"] == str(out)
    assert abs(rec["trace_span_s"] - rec["value"]) <= 0.1 * rec["value"] + 0.05
    doc = json.loads(out.read_text())
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert {"bench", "schedule", "decode"} <= names


def test_busy_rejection_lands_in_request_histogram():
    from opensim_tpu.server import rest

    # single-flight mode (admission=False): the TryLock busy path is the
    # OPENSIM_ADMISSION=off configuration (ISSUE 8)
    server = rest.SimonServer(base_cluster=_cluster(), admission=False)
    assert rest._deploy_lock.acquire(blocking=False)
    try:
        code, body = server.deploy_apps(_payload())
    finally:
        rest._deploy_lock.release()
    assert code == 503 and "busy" in body["error"]
    text = rest.METRICS.render()
    assert 'simon_request_seconds_count{endpoint="deploy-apps",status="busy"} 1' in text


# ---------------------------------------------------------------------------
# metrics-exposition conformance (ISSUE 7 satellite)
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{(.*)\})?\s(-?(?:[0-9]*\.?[0-9]+(?:[eE][+-]?[0-9]+)?|Inf)|NaN|[+-]Inf)$"
)
_LABEL_RE = re.compile(r'^([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"$')


def _split_labels(body: str):
    """Split the inside of {...} into label assignments (quotes-aware)."""
    out, cur, depth, in_q, esc = [], "", 0, False, False
    for ch in body:
        if esc:
            cur += ch
            esc = False
            continue
        if ch == "\\":
            cur += ch
            esc = True
            continue
        if ch == '"':
            in_q = not in_q
            cur += ch
            continue
        if ch == "," and not in_q:
            out.append(cur)
            cur = ""
            continue
        cur += ch
    if cur:
        out.append(cur)
    return out


def _assert_exposition_conformant(text):
    """The exposition contract every scrape surface must meet: one
    # HELP/# TYPE per family, every sample matches the Prometheus
    grammar, no series emitted twice. Returns the families that rendered
    at least one sample."""
    helped, typed, seen_series = set(), {}, set()
    families_with_samples = set()
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            name = line.split()[2]
            assert name not in helped, f"duplicate HELP for {name}"
            helped.add(name)
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(None, 3)
            assert name not in typed, f"duplicate TYPE for {name}"
            assert kind in ("counter", "gauge", "histogram", "summary"), line
            typed[name] = kind
            continue
        assert not line.startswith("#"), f"unknown comment line: {line!r}"
        m = _SAMPLE_RE.match(line)
        assert m, f"sample line fails the exposition grammar: {line!r}"
        name, _, labels_body, _value = m.groups()
        series_key = (name, labels_body or "")
        assert series_key not in seen_series, f"duplicate series: {line!r}"
        seen_series.add(series_key)
        family = name
        for suffix in ("_bucket", "_sum", "_count"):
            base = name[: -len(suffix)] if name.endswith(suffix) else None
            if base and typed.get(base) == "histogram":
                family = base
        families_with_samples.add(family)
        assert family in typed, f"sample {name!r} has no # TYPE header"
        assert family in helped, f"sample {name!r} has no # HELP header"
        for part in _split_labels(labels_body or ""):
            assert _LABEL_RE.match(part), f"bad label in {line!r}: {part!r}"
    return families_with_samples


def test_metrics_exposition_conformance(tmp_path):
    """Every series in /metrics has # HELP/# TYPE, names and labels match
    the Prometheus grammar, and no series is emitted twice — regression-
    proofing the growing registry."""
    from opensim_tpu.server import rest
    from opensim_tpu.server.journal import Journal

    server = rest.SimonServer(base_cluster=_cluster())
    # traffic covering success + unschedulable so the decision counters,
    # request histograms, and per-endpoint series all render
    code, _ = server.deploy_apps(_payload())
    assert code == 200
    bad = {"deployments": [fx.make_fake_deployment("nope", 1, "640", "1Gi").raw]}
    code, _ = server.deploy_apps(bad)
    assert code == 200
    # capacity families (ISSUE 9) render once the report has bootstrapped
    # the observatory (headroom probes included)
    server.cluster_report()
    # watch-apply histogram (ISSUE 9 satellite) joins via the recorder
    RECORDER.observe_watch_apply(0.0002)
    # journal families (ISSUE 11): records of every type, an fsync, and a
    # recovery so each family renders populated
    journal = Journal(str(tmp_path / "journal"), policy={"fsync": "always"})
    journal.record_checkpoint({"pods": []}, generation=1, why="test")
    journal.record_event(
        "pods", "ADDED",
        {"metadata": {"name": "p", "namespace": "default", "resourceVersion": "2"}}, 2,
    )
    journal.record_rebase("pods", [], 3, rv="3", why="test")
    assert journal.flush(timeout=10.0)
    assert journal.recover() is not None
    journal.close()
    # admission families (ISSUE 8) and the memory observatory (ISSUE 12)
    # join the same conformance contract
    text = rest.METRICS.render(
        prep_cache=server.prep_cache, admission=server.admission,
        capacity=server.capacity, journal=journal, memory=server.memory,
    )
    families_with_samples = _assert_exposition_conformant(text)
    # the families this PR added are present and populated
    for required in (
        "simon_filter_reject_total",
        "simon_unschedulable_total",
        "simon_request_seconds",
        "simon_admission_queue_depth",
        "simon_queue_wait_seconds",
        "simon_batches_total",
        # capacity observatory (ISSUE 9)
        "simon_cluster_utilization",
        "simon_cluster_utilization_ratio",
        "simon_cluster_node_utilization",
        "simon_cluster_allocatable",
        "simon_cluster_requested",
        "simon_cluster_spread",
        "simon_cluster_fragmentation",
        "simon_cluster_headroom",
        "simon_cluster_nodes",
        "simon_cluster_pods_bound",
        "simon_cluster_pods_pending",
        "simon_watch_apply_seconds",
        # watch-event journal (ISSUE 11)
        "simon_journal_records_total",
        "simon_journal_bytes_total",
        "simon_journal_dropped_total",
        "simon_journal_fsync_seconds",
        "simon_journal_recoveries_total",
        # memory observatory + compile telemetry + phase profiles (ISSUE 12)
        "simon_mem_rss_bytes",
        "simon_mem_rss_peak_bytes",
        "simon_mem_prepcache_bytes",
        "simon_mem_prepcache_entries",
        "simon_mem_prepcache_evictions_total",
        "simon_mem_prepcache_compactions_total",
        "simon_mem_arena_bytes",
        "simon_mem_ring_entries",
        "simon_mem_ring_capacity",
        "simon_backend_compile_total",
        "simon_backend_compile_seconds_total",
        "simon_compile_stage_seconds_total",
        "simon_phase_profile_calls_total",
        "simon_phase_profile_seconds_total",
        "simon_phase_profile_exclusive_seconds_total",
    ):
        assert required in families_with_samples, f"{required} missing from /metrics"


def test_aggregated_metrics_exposition_conformance(tmp_path):
    """The fleet admin's aggregated /metrics (ISSUE 20 satellite) meets
    the SAME exposition contract as a single process: one header per
    family even when every worker ships it, summed series next to
    ``{worker="i"}``-labeled breakdowns with zero duplicates, and
    max-not-sum for the generation gauge."""
    from opensim_tpu.server import rest
    from opensim_tpu.server.fleet import render_aggregated

    server = rest.SimonServer(base_cluster=_cluster())
    code, _ = server.deploy_apps(_payload())
    assert code == 200
    server.cluster_report()
    worker_text = server.metrics_text()
    # two workers with identical traffic plus the owner's own exposition
    # (the owner ships watch/journal families, not request histograms)
    agg = render_aggregated([worker_text, worker_text], owner_text="")
    _assert_exposition_conformant(agg)
    single = parse_metrics(worker_text)
    merged = parse_metrics(agg)
    key = ("simon_request_seconds_count",
           (("endpoint", "deploy-apps"), ("status", "ok")))
    # backward compat: the summed family keeps its unlabeled shape...
    assert merged[key] == 2 * single[key]
    # ...and the per-worker breakdown rides next to it, same family
    for worker in ("0", "1"):
        labeled = (key[0], key[1] + (("worker", worker),))
        assert merged[labeled] == single[key]
    # the per-worker allowlist is a fence: unlisted families never grow
    # worker-labeled copies (cardinality × fleet size otherwise)
    assert not any(
        "worker" in dict(labels) and not name.startswith((
            "simon_request_seconds", "simon_requests_total", "simon_lane_depth",
            "simon_fleet_",
        ))
        for name, labels in merged
    )
    # a dead worker (failed scrape) degrades to the survivors' sum
    one = parse_metrics(render_aggregated([worker_text, None]))
    assert one[key] == single[key]
    # gauges in the max-set aggregate as max, not a meaningless sum
    gen_text = (
        "# TYPE simon_fleet_attach_generation gauge\n"
        "simon_fleet_attach_generation 7\n"
    )
    gen_text2 = gen_text.replace("7", "9")
    merged_gen = parse_metrics(render_aggregated([gen_text, gen_text2]))
    assert merged_gen[("simon_fleet_attach_generation", ())] == 9.0


def test_capacity_node_series_capped_under_1k_node_twin():
    """The per-node family stays cardinality-capped: a 1k-node cluster
    renders exactly top-K node series per resource (ISSUE 9 acceptance),
    and the whole capacity block stays exposition-conformant."""
    from opensim_tpu.obs.capacity import RESOURCES, CapacityEngine

    rt = ResourceTypes()
    for i in range(1000):
        rt.nodes.append(fx.make_fake_node(f"big{i:04d}", "16", "64Gi"))
    for i in range(200):
        rt.pods.append(
            fx.make_fake_pod(f"p{i}", "500m", "1Gi", fx.with_node_name(f"big{i:04d}"))
        )
    engine = CapacityEngine(topk=10)
    engine.bootstrap(rt, 1)
    lines = engine.metrics_lines()
    node_series = [l for l in lines if l.startswith("simon_cluster_node_utilization{")]
    assert len(node_series) == 10 * len(RESOURCES)
    # the cap keeps the HOTTEST nodes: every rendered node carries load
    assert all("big0" in l for l in node_series)
    for line in lines:
        if line.startswith("#"):
            assert line.startswith(("# HELP ", "# TYPE ")), line
        else:
            assert _SAMPLE_RE.match(line), f"bad sample line: {line!r}"


def test_watch_metrics_lines_conform(tmp_path):
    """The live twin's labeled counters join the same conformance contract
    (resource-labeled events and drift series)."""
    from opensim_tpu.server.watch import ClusterTwin, WatchSupervisor

    sup = WatchSupervisor.__new__(WatchSupervisor)
    sup.watched = ("pods", "nodes")
    sup.events_total = {("ADDED", "pods"): 3, ("BOOKMARK", "nodes"): 1}
    sup.reconnects_total = sup.relists_total = sup.gone_total = 0
    sup.drift_total = 2
    sup.drift_by_resource = {"pods": 2}
    sup.resyncs_total = 1
    sup._state = "live"
    sup._state_lock = threading.Lock()
    sup.twin = ClusterTwin()
    lines = sup.metrics_lines()
    text = "\n".join(lines)
    assert 'simon_watch_events_total{kind="ADDED",resource="pods"} 3' in text
    assert 'simon_twin_drift_total{resource="pods"} 2' in text
    assert 'simon_twin_drift_total{resource="nodes"} 0' in text
    assert "# HELP simon_twin_drift_total" in text
    assert "simon_twin_generation 0" in text
