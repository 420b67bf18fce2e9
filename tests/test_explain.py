"""Decision-audit layer (ISSUE 7): reason registry, cross-engine
explanation parity, deep per-pod score breakdowns, REST surfaces, and the
decision counters in /metrics."""

import copy
import json
import os
import re
import threading
import urllib.request
from collections import Counter

import numpy as np
import pytest

from opensim_tpu.engine import explain as explain_mod
from opensim_tpu.engine import reasons
from opensim_tpu.engine.simulator import AppResource, simulate
from opensim_tpu.models import ResourceTypes, fixtures as fx
from opensim_tpu.ops import kernels


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

def small_cluster(n=6):
    rt = ResourceTypes()
    for i in range(n):
        rt.nodes.append(
            fx.make_fake_node(
                f"n{i:02d}", "4", "8Gi", "110",
                fx.with_labels(
                    {
                        "topology.kubernetes.io/zone": f"z{i % 2}",
                        "disk": "ssd" if i % 2 else "hdd",
                    }
                ),
            )
        )
    return rt


def mixed_apps():
    """Schedulable + unschedulable workloads covering fit/affinity/spread."""
    rt = ResourceTypes()
    rt.deployments.append(fx.make_fake_deployment("fits", 3, "500m", "1Gi"))
    rt.deployments.append(fx.make_fake_deployment("bigcpu", 2, "16", "1Gi"))
    rt.deployments.append(
        fx.make_fake_deployment(
            "ssd", 2, "100m", "128Mi", fx.with_node_selector({"disk": "ssd"})
        )
    )
    rt.deployments.append(fx.make_fake_deployment("bigmem", 1, "100m", "100Gi"))
    rt.deployments.append(
        fx.make_fake_deployment(
            "spread", 4, "100m", "64Mi",
            fx.with_topology_spread(
                [
                    {
                        "maxSkew": 1,
                        "topologyKey": "topology.kubernetes.io/zone",
                        "whenUnsatisfiable": "DoNotSchedule",
                        "labelSelector": {"matchLabels": {"app": "spread"}},
                    }
                ]
            ),
        )
    )
    return [AppResource("t", rt)]


def canon_name(pod_name: str) -> str:
    """Pod names embed globally-counted uids assigned at expansion time, so
    two simulate() runs name the same logical pod differently — strip the
    hex-uid segments before cross-run comparison."""
    return re.sub(r"-[0-9a-f]{10}", "", pod_name)


def canon(e_dict: dict) -> dict:
    d = dict(e_dict)
    if "pod" in d:
        d["pod"] = canon_name(d["pod"])
    return d


def run_engine(cluster, apps, engine, explain=True, **kw):
    """One simulate on a forced engine, on deep copies so repeated runs see
    identical inputs (pod names included — uids are stamped at build). The
    megakernel runs in the interpreter; an audited run is the XLA scan's
    whatever the engine asked for (the kernel declines explain mode)."""
    env = {"megakernel": {"OPENSIM_FASTPATH": "interpret"}, "xla": {}}[engine]
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        return simulate(copy.deepcopy(cluster), copy.deepcopy(apps), explain=explain, **kw)
    finally:
        for k, v in saved.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


# ---------------------------------------------------------------------------
# the registered reason-code enum
# ---------------------------------------------------------------------------

def test_reason_enum_aligns_with_kernel_filter_indices():
    assert reasons.Reason.NODE_PIN.value == kernels.F_NODE_PIN
    assert reasons.Reason.UNSCHEDULABLE.value == kernels.F_UNSCHEDULABLE
    assert reasons.Reason.TAINT.value == kernels.F_TAINT
    assert reasons.Reason.AFFINITY.value == kernels.F_AFFINITY
    assert reasons.Reason.PORTS.value == kernels.F_PORTS
    assert reasons.Reason.FIT.value == kernels.F_FIT
    assert reasons.Reason.SPREAD.value == kernels.F_SPREAD
    assert reasons.Reason.INTERPOD.value == kernels.F_INTERPOD
    assert reasons.Reason.GPU.value == kernels.F_GPU
    assert reasons.Reason.LOCAL.value == kernels.F_LOCAL
    assert reasons.Reason.EXTRA.value == kernels.F_EXTRA
    assert len(reasons.FILTER_MESSAGES) == kernels.NUM_FILTERS
    # kernels.FILTER_REASONS is the registry's table, not a second copy
    assert kernels.FILTER_REASONS is reasons.FILTER_MESSAGES


def test_render_unschedulable_kube_phrasing():
    counts = [
        reasons.ReasonCount(reasons.Reason.TAINT, 3),
        reasons.ReasonCount(reasons.Reason.FIT, 1, resource="cpu"),
    ]
    msg = reasons.render_unschedulable(4, counts)
    assert msg == (
        "0/4 nodes are available: 1 Insufficient cpu, "
        "3 node(s) had taints that the pod didn't tolerate."
    )
    assert reasons.render_unschedulable(7, []) == "0/7 nodes are available."


def test_reason_helpers_format():
    assert reasons.node_not_found("gone-01") == 'node "gone-01" not found'
    assert reasons.preempted("ns", "hi") == "preempted by higher-priority pod ns/hi"
    assert "no scheduler profile named 'x'" in reasons.unknown_profile("x")


def test_primary_code_precedence():
    counts = [
        reasons.ReasonCount(reasons.Reason.FIT, 2, resource="cpu"),
        reasons.ReasonCount(reasons.Reason.TAINT, 2),
        reasons.ReasonCount(reasons.Reason.SPREAD, 5),
    ]
    assert reasons.primary_code(counts) is reasons.Reason.SPREAD
    # tie between TAINT(2) and FIT(2): lower filter index wins
    assert reasons.primary_code(counts[:2]) is reasons.Reason.TAINT
    assert reasons.primary_code([]) is None


# ---------------------------------------------------------------------------
# cross-engine explanation parity
# ---------------------------------------------------------------------------

def workload_of(pod) -> str:
    """A pod's namespace/name with its uid segments stripped: the workload
    it was expanded from (its replicas share it)."""
    return canon_name(f"{pod.metadata.namespace}/{pod.metadata.name}")


def assert_audit_matches_kernel(cluster, apps):
    """The XLA scan's decision audit against the megakernel's run of the
    same stream (kept without reasons, so the kernel's placements stand even
    past a mid-stream failure): each workload's pods land on the same nodes
    and the same count of them fail."""
    rm = run_engine(cluster, apps, "megakernel", explain=False, reasons=False)
    rx = run_engine(cluster, apps, "xla")
    assert rm.engine.name == "megakernel" and rx.engine.name == "xla"
    kernel = Counter((workload_of(p), ns.node.metadata.name) for ns in rm.node_status for p in ns.pods)
    kernel.update((workload_of(u.pod), None) for u in rm.unscheduled_pods)
    audit = Counter(
        (canon_name(e.pod), e.node if e.status == "scheduled" else None) for e in rx.engine.explanations
    )
    assert kernel == audit
    return rx


def test_explanations_identical_between_engines():
    cluster, apps = small_cluster(), mixed_apps()
    rx = assert_audit_matches_kernel(cluster, apps)
    # asking for the kernel changes nothing of an audited run
    rk = run_engine(cluster, apps, "megakernel")
    assert rk.engine.name == "xla" and "explain" in rk.engine.skipped["megakernel"]
    assert rk.engine.filter_rejects == rx.engine.filter_rejects
    ek, ex = rk.engine.explanations, rx.engine.explanations
    assert len(ek) == len(ex) == len(rx.engine.explain_ctx.prep.ordered)
    for a, b in zip(ek, ex):
        assert canon(a.to_dict()) == canon(b.to_dict())
    # the audit found the infeasible workloads with kube phrasing
    msgs = [e.message for e in ex if e.status == "unschedulable"]
    assert any("Insufficient cpu" in m for m in msgs)
    assert any("Insufficient memory" in m for m in msgs)
    assert all(m.startswith("0/6 nodes are available") for m in msgs)


def test_explain_disabled_is_unchanged_and_attaches_nothing():
    cluster, apps = small_cluster(), mixed_apps()
    r0 = run_engine(cluster, apps, "megakernel", explain=False)
    r1 = run_engine(cluster, apps, "xla", explain=True)
    assert r0.engine.explanations is None
    assert r0.engine.filter_rejects is None
    assert r0.engine.explain_ctx is None
    # explain=1 runs the count_all scan but placements are bit-identical
    placements0 = {
        ns.node.metadata.name: sorted(canon_name(p.metadata.name) for p in ns.pods)
        for ns in r0.node_status
    }
    placements1 = {
        ns.node.metadata.name: sorted(canon_name(p.metadata.name) for p in ns.pods)
        for ns in r1.node_status
    }
    assert placements0 == placements1
    assert [u.reason for u in r0.unscheduled_pods] == [
        u.reason for u in r1.unscheduled_pods
    ]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reason_parity_fuzz(seed):
    """ISSUE 7 satellite: random cluster + workload; the XLA scan's audit
    agrees pod-for-pod with the megakernel's placements, and with the
    kernel's own failure reasons where it kept them."""
    rng = np.random.default_rng(seed)
    rt = ResourceTypes()
    zones = [f"z{k}" for k in range(int(rng.integers(1, 4)))]
    n_nodes = int(rng.integers(3, 9))
    for i in range(n_nodes):
        opts = [
            fx.with_labels(
                {
                    "topology.kubernetes.io/zone": str(rng.choice(zones)),
                    "tier": str(rng.choice(["web", "db", "cache"])),
                }
            )
        ]
        if rng.random() < 0.3:
            opts.append(
                fx.with_taints(
                    [{"key": "dedicated", "value": "infra", "effect": "NoSchedule"}]
                )
            )
        rt.nodes.append(
            fx.make_fake_node(
                f"fz{i:02d}",
                str(int(rng.integers(2, 9))),
                f"{int(rng.integers(4, 17))}Gi",
                "110",
                *opts,
            )
        )
    n_workloads = int(rng.integers(2, 6))
    for w in range(n_workloads):
        opts = []
        if rng.random() < 0.4:
            opts.append(fx.with_node_selector({"tier": str(rng.choice(["web", "db", "gone"]))}))
        if rng.random() < 0.4:
            opts.append(
                fx.with_topology_spread(
                    [
                        {
                            "maxSkew": int(rng.integers(1, 3)),
                            "topologyKey": "topology.kubernetes.io/zone",
                            "whenUnsatisfiable": str(
                                rng.choice(["DoNotSchedule", "ScheduleAnyway"])
                            ),
                            "labelSelector": {"matchLabels": {"app": f"fz-{w}"}},
                        }
                    ]
                )
            )
        cpu = str(rng.choice(["100m", "500m", "2", "12"]))
        mem = str(rng.choice(["128Mi", "1Gi", "4Gi", "64Gi"]))
        rt.deployments.append(
            fx.make_fake_deployment(f"fz-{w}", int(rng.integers(1, 5)), cpu, mem, *opts)
        )
    cluster = ResourceTypes()
    cluster.nodes = rt.nodes
    apps_rt = ResourceTypes()
    apps_rt.deployments = rt.deployments
    apps = [AppResource("fuzz", apps_rt)]

    rx = assert_audit_matches_kernel(cluster, apps)
    # a kernel run that kept its result reasons its failures as the audit does
    rm = run_engine(cluster, apps, "megakernel", explain=False)
    if rm.engine.name == "megakernel":
        audit = Counter((canon_name(e.pod), e.message) for e in rx.engine.explanations if e.status != "scheduled")
        assert Counter((workload_of(u.pod), u.reason) for u in rm.unscheduled_pods) == audit
    # the audit's reject totals are its per-pod rows summed
    cx = rx.engine.explain_ctx
    unforced = ~cx.prep.forced
    derived = explain_mod.audit_rejects(cx.static_fail, cx.sf_rows, cx.fail_counts, unforced)
    assert rx.engine.filter_rejects == reasons.rejects_dict(derived)


# ---------------------------------------------------------------------------
# deep per-pod audit
# ---------------------------------------------------------------------------

def test_deep_explain_scheduled_pod_breakdown():
    r = run_engine(small_cluster(), mixed_apps(), "xla")
    ctx = r.engine.explain_ctx
    scheduled = [
        i for i, e in enumerate(r.engine.explanations)
        if e.status == "scheduled" and not e.forced
    ]
    assert scheduled
    for i in scheduled[:3]:
        deep = explain_mod.explain_pod(ctx, i)
        assert deep.status == "scheduled"
        assert deep.node == r.engine.explanations[i].node
        assert deep.scores and deep.score is not None
        # the breakdown sums to the reported total (same accumulation order)
        assert abs(sum(deep.scores.values()) - deep.score) < 1e-2
        if deep.runner_up is not None:
            assert deep.runner_up != deep.node
            assert deep.margin is not None and deep.margin >= 0.0


def test_deep_explain_unschedulable_and_engines_agree():
    cluster, apps = small_cluster(), mixed_apps()
    rx = assert_audit_matches_kernel(cluster, apps)
    rk = run_engine(cluster, apps, "megakernel")
    for r in (rk, rx):
        ctx = r.engine.explain_ctx
        bad = [i for i, e in enumerate(r.engine.explanations) if e.status == "unschedulable"]
        assert bad
        deep = explain_mod.explain_pod(ctx, bad[0])
        assert deep.reasons and deep.message.startswith("0/6 nodes are available")
    dk = explain_mod.explain_pod(rk.engine.explain_ctx, 0)
    dx = explain_mod.explain_pod(rx.engine.explain_ctx, 0)
    assert canon(dk.to_dict()) == canon(dx.to_dict())


def _feature_case(name):
    import random
    import sys

    sys.path.insert(0, os.path.dirname(__file__))
    import test_xla_oracle_fuzz as fz

    if name == "ports":
        return fz._ports_fuzz_case(random.Random(211))
    if name == "gpu_share":
        return fz._gpu_fuzz_case(random.Random(307))
    if name == "local":
        return fz._local_fuzz_case(random.Random(401))
    if name == "interpod":
        return fz._ip_cluster(12), fz._ip_apps()
    app = ResourceTypes()
    app.deployments = [d for d in mixed_apps()[0].resources.deployments if d.metadata.name in ("spread", "fits")]
    return small_cluster(), app


@pytest.mark.parametrize("feature", ["ports", "gpu_share", "local", "interpod", "spread"])
def test_deep_explain_score_parts_rank_the_scans_choice(feature):
    """The deep audit scores a scheduled pod from the XLA scan's own static
    tables (kernels.precompute_static) and the replayed carry: its parts sum
    to its total, and no node outscores the one the scan chose (a tie goes
    to the lower node index, as the scan's does)."""
    cluster, app = _feature_case(feature)
    r = run_engine(cluster, [AppResource("f", app)], "xla")
    ctx = r.engine.explain_ctx
    assert getattr(ctx.prep.features, {"gpu_share": "gpu", "spread": "spread_hard"}.get(feature, feature))
    scheduled = [i for i, e in enumerate(r.engine.explanations) if e.status == "scheduled" and not e.forced]
    assert len(scheduled) >= 3
    names = list(ctx.node_names)
    for i in scheduled[:: max(1, len(scheduled) // 3)][:3]:
        deep = explain_mod.explain_pod(ctx, i)
        assert deep.node == r.engine.explanations[i].node == names[int(ctx.chosen[i])]
        assert deep.scores and abs(sum(deep.scores.values()) - deep.score) < 1e-2
        if deep.runner_up is not None:
            assert deep.margin > 0 or (deep.margin == 0 and names.index(deep.runner_up) > names.index(deep.node))


def test_deep_explain_forced_pod():
    cluster = small_cluster()
    cluster.pods.append(
        fx.make_fake_pod("pinned", "100m", "64Mi", fx.with_node_name("n03"))
    )
    cluster.pods.append(
        fx.make_fake_pod("orphan", "100m", "64Mi", fx.with_node_name("no-such-node"))
    )
    r = run_engine(cluster, mixed_apps(), "xla")
    ctx = r.engine.explain_ctx
    i = ctx.index_of("default/pinned")
    deep = explain_mod.explain_pod(ctx, i)
    assert deep.status == "scheduled" and deep.forced and deep.node == "n03"
    j = ctx.index_of("default/orphan")
    deep = explain_mod.explain_pod(ctx, j)
    assert deep.status == "unschedulable"
    assert deep.message == 'node "no-such-node" not found'
    assert any(u.reason == deep.message for u in r.unscheduled_pods)


def test_explain_ctx_index_of_ambiguity():
    r = run_engine(small_cluster(), mixed_apps(), "xla")
    ctx = r.engine.explain_ctx
    full = f"{ctx.prep.ordered[0].metadata.namespace}/{ctx.prep.ordered[0].metadata.name}"
    assert ctx.index_of(full) == 0
    assert ctx.index_of("nope/nothing") is None


# ---------------------------------------------------------------------------
# decision counters
# ---------------------------------------------------------------------------

def test_simulate_bumps_decision_counters():
    from opensim_tpu.obs.metrics import RECORDER

    RECORDER.reset()
    run_engine(small_cluster(), mixed_apps(), "xla", explain=False)
    lines = "\n".join(RECORDER.render_lines())
    assert 'simon_unschedulable_total{reason="fit"}' in lines
    assert 'simon_filter_reject_total{filter="fit"}' in lines
    assert "# HELP simon_unschedulable_total" in lines
    assert "# TYPE simon_filter_reject_total counter" in lines
    RECORDER.reset()


def test_schedule_span_carries_reason_events():
    from opensim_tpu.obs import trace as tracing

    tr = tracing.start_trace("test-explain", force=True)
    with tracing.trace_scope(tr):
        run_engine(small_cluster(), mixed_apps(), "xla", explain=False)
    tr.finish()
    names = [sp.name for sp in tr.walk()]
    assert "placement.reasons" in names
    assert "placement.unschedulable" in names
    agg = next(sp for sp in tr.walk() if sp.name == "placement.reasons")
    assert agg.attrs["unschedulable"] >= 3
    assert agg.attrs.get("reason_fit", 0) >= 1
    ev = next(sp for sp in tr.walk() if sp.name == "placement.unschedulable")
    assert "0/6 nodes are available" in ev.attrs["reason"]


# ---------------------------------------------------------------------------
# REST surfaces
# ---------------------------------------------------------------------------

def _rest_server():
    from http.server import ThreadingHTTPServer

    from opensim_tpu.server.rest import SimonServer, make_handler

    server = SimonServer(base_cluster=small_cluster())
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(server))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, f"http://127.0.0.1:{httpd.server_address[1]}"


def _post(base, path, payload, headers=None):
    req = urllib.request.Request(
        f"{base}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=60) as resp:
            return resp.status, dict(resp.headers), json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read())


def test_rest_explain_flag_and_placements_endpoint():
    httpd, base = _rest_server()
    try:
        payload = {
            "deployments": [
                fx.make_fake_deployment("ok", 2, "100m", "128Mi").raw,
                fx.make_fake_deployment("nope", 1, "64", "1Gi").raw,
            ]
        }
        rid = "explain-rest-1"
        code, headers, body = _post(
            base, "/api/deploy-apps?explain=1", payload,
            {"X-Simon-Request-Id": rid},
        )
        assert code == 200
        assert headers.get("X-Simon-Request-Id") == rid
        bad = [u for u in body["unscheduledPods"] if "nope" in u["pod"]]
        assert bad and "explanation" in bad[0]
        exp = bad[0]["explanation"]
        assert exp["status"] == "unschedulable"
        assert any(c["code"] == "fit" for c in exp["reasons"])
        assert body["filterRejects"].get("fit", 0) >= 1

        with urllib.request.urlopen(
            f"{base}/api/debug/placements/{rid}", timeout=30
        ) as resp:
            audit = json.loads(resp.read())
        assert audit["request_id"] == rid
        assert audit["pods_total"] == 3
        assert audit["truncated"] == 0
        # unschedulable records rank first in the stored audit
        assert audit["explanations"][0]["status"] == "unschedulable"
        assert audit["filter_rejects"].get("fit", 0) >= 1

        # a request WITHOUT explain=1 records no placements
        code, headers2, _ = _post(base, "/api/deploy-apps", payload)
        rid2 = headers2.get("X-Simon-Request-Id")
        try:
            with urllib.request.urlopen(
                f"{base}/api/debug/placements/{rid2}", timeout=30
            ) as resp:
                assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
            assert "explain=1" in json.loads(e.read())["hint"]
    finally:
        httpd.shutdown()


def test_request_id_on_get_requests_and_access_log(monkeypatch, caplog):
    """ISSUE 7 satellite: every request — GETs included — gets a request id
    that shows up in the response header and the JSON access log, so logs
    join against the flight recorder without scraping anything."""
    import logging

    monkeypatch.setenv("OPENSIM_ACCESS_LOG", "1")
    httpd, base = _rest_server()
    try:
        with caplog.at_level(logging.INFO, logger="opensim_tpu.access"):
            req = urllib.request.Request(
                f"{base}/metrics", headers={"X-Simon-Request-Id": "get-join-1"}
            )
            with urllib.request.urlopen(req, timeout=30) as resp:
                assert resp.headers.get("X-Simon-Request-Id") == "get-join-1"
            with urllib.request.urlopen(f"{base}/healthz", timeout=30) as resp:
                assert resp.headers.get("X-Simon-Request-Id")
        entries = [json.loads(r.message) for r in caplog.records]
        assert all(e["request_id"] for e in entries)
        assert any(e["request_id"] == "get-join-1" for e in entries)
    finally:
        httpd.shutdown()


def test_explanations_exclude_dropped_pods():
    """Regression: drop_pods-masked pods (scale-apps cached path, live-twin
    DELETEDs) must not appear in the audit as phantom unschedulable pods."""
    from opensim_tpu.engine.simulator import prepare

    cluster, apps = small_cluster(), mixed_apps()
    cl, ap = copy.deepcopy(cluster), copy.deepcopy(apps)
    prep = prepare(cl, ap)
    drop = np.zeros(len(prep.ordered), dtype=bool)
    drop[0] = True
    dropped_name = (
        f"{prep.ordered[0].metadata.namespace}/{prep.ordered[0].metadata.name}"
    )
    r = simulate(cl, ap, prep=prep, drop_pods=drop, explain=True)
    names = [e.pod for e in r.engine.explanations]
    assert dropped_name not in names
    assert len(names) == len(prep.ordered) - 1


def test_rest_explain_with_no_schedulable_pods():
    """Regression: explain=1 against a pod-free snapshot (engine=None) must
    stay a 200, and the placements endpoint 404s cleanly."""
    httpd, base = _rest_server()
    try:
        code, headers, body = _post(
            base, "/api/deploy-apps?explain=1", {"deployments": []},
            {"X-Simon-Request-Id": "explain-empty-1"},
        )
        assert code == 200, body
        assert body["unscheduledPods"] == []
        try:
            with urllib.request.urlopen(
                f"{base}/api/debug/placements/explain-empty-1", timeout=30
            ):
                assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        httpd.shutdown()
