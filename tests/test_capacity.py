"""Capacity observatory (ISSUE 9, docs/observability.md "Watching cluster
capacity"): incremental per-node accounting vs a from-scratch bootstrap,
headroom probes bit-consistent with a fresh ``simulate``, report parity
between the JSON endpoint and the text renderer, the timeline ring, the
watch-apply histogram, in-flight batch deadline shedding, and OSL1101."""

import json
import threading
import urllib.request

import pytest

from opensim_tpu.engine.simulator import AppResource, prepare, simulate
from opensim_tpu.models import ResourceTypes, fixtures as fx
from opensim_tpu.obs.capacity import (
    CapacityEngine,
    WorkloadProfile,
    build_report,
    format_top,
    headroom_probe,
    headroom_profiles,
    snapshot_result,
)
from opensim_tpu.obs.metrics import RECORDER
from opensim_tpu.obs.timeline import Sample, Timeline


@pytest.fixture(autouse=True)
def _clean_state(monkeypatch):
    monkeypatch.delenv("OPENSIM_HEADROOM_PROFILES", raising=False)
    monkeypatch.delenv("OPENSIM_CAPACITY_TOPK", raising=False)
    RECORDER.reset()
    yield
    RECORDER.reset()


def _pod_dict(name, node="", cpu="500m", mem="1Gi", phase="Running", rv=None):
    d = {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": name, "namespace": "default"},
        "spec": {
            "containers": [
                {"name": "c", "resources": {"requests": {"cpu": cpu, "memory": mem}}}
            ]
        },
        "status": {"phase": phase},
    }
    if node:
        d["spec"]["nodeName"] = node
    if rv is not None:
        d["metadata"]["resourceVersion"] = str(rv)
    return d


def _cluster(n_nodes=4, n_pods=6):
    rt = ResourceTypes()
    for i in range(n_nodes):
        rt.nodes.append(fx.make_fake_node(f"n{i}", "8", "16Gi"))
    for i in range(n_pods):
        rt.pods.append(
            fx.make_fake_pod(f"p{i}", "500m", "1Gi", fx.with_node_name(f"n{i % n_nodes}"))
        )
    return rt


def _assert_engines_agree(a: CapacityEngine, b: CapacityEngine):
    sa, sb = a.sample(), b.sample()
    assert sa.nodes == sb.nodes
    assert sa.pods_bound == sb.pods_bound
    assert sa.pods_pending == sb.pods_pending
    for res in ("cpu", "memory", "pods"):
        assert sa.allocatable[res] == pytest.approx(sb.allocatable[res])
        assert sa.requested[res] == pytest.approx(sb.requested[res])
        assert sa.utilization[res] == pytest.approx(sb.utilization[res])
        assert sa.spread[res] == pytest.approx(sb.spread[res], abs=1e-9)
        assert sa.fragmentation[res] == pytest.approx(sb.fragmentation[res])
    assert [n for n, _ in sa.hottest] == [n for n, _ in sb.hottest]
    # the incrementally-maintained distribution equals the rebuilt one
    assert a._dist == b._dist
    assert a._n_util == b._n_util


# ---------------------------------------------------------------------------
# incremental accounting == from-scratch bootstrap
# ---------------------------------------------------------------------------


def test_event_fed_engine_matches_fresh_bootstrap():
    """Drive a storm of pod/node events through a real WatchSupervisor
    dispatch; the event-fed aggregates must equal a fresh O(cluster)
    bootstrap of the final twin state (the observatory's analogue of the
    twin's fingerprint-equality proof)."""
    from opensim_tpu.server.watch import WatchSupervisor

    policy = {"stale_s": 30.0, "resync_s": 0.0, "reconnects": 1, "backoff_s": 0.0}
    sup = WatchSupervisor(source=None, policy=policy)
    engine = CapacityEngine(topk=5)
    sup.capacity = engine
    # bootstrap: 3 nodes, 2 bound pods, 1 pending
    nodes = [fx.make_fake_node(f"n{i}", "8", "16Gi").raw for i in range(3)]
    pods = [
        _pod_dict("a", node="n0", rv=1),
        _pod_dict("b", node="n1", cpu="2", mem="4Gi", rv=2),
        _pod_dict("pending", rv=3),
    ]
    sup.twin.rebase("nodes", nodes)
    sup.twin.rebase("pods", pods)
    sup._capacity_rebase()
    assert engine.event_fed

    rv = 10
    # storm: adds, a modify (rebind), deletes, a node add, a terminal pod
    sup.dispatch("pods", "ADDED", _pod_dict("c", node="n2", cpu="1", rv=rv))
    sup.dispatch("pods", "ADDED", _pod_dict("d", rv=rv + 1))  # pending
    sup.dispatch("pods", "MODIFIED", _pod_dict("pending", node="n1", rv=rv + 2))
    sup.dispatch("pods", "DELETED", _pod_dict("a", node="n0", rv=rv + 3))
    sup.dispatch("nodes", "ADDED", fx.make_fake_node("n3", "4", "8Gi").raw)
    sup.dispatch("pods", "MODIFIED", _pod_dict("b", node="n1", cpu="2", mem="4Gi", phase="Succeeded", rv=rv + 4))
    # duplicate delivery must be a no-op for the aggregates too
    sup.dispatch("pods", "ADDED", _pod_dict("c", node="n2", cpu="1", rv=rv))

    fresh = CapacityEngine(topk=5)
    fresh.bootstrap(sup.twin.materialize(), sup.twin.generation)
    assert engine.generation == sup.twin.generation
    _assert_engines_agree(engine, fresh)
    # the watch-apply histogram saw every applied dispatch
    lines = "\n".join(RECORDER.render_lines())
    assert "simon_watch_apply_seconds_count 7" in lines


def test_node_flap_and_modify_accounting():
    """Node MODIFIED (allocatable resize) and DELETED/re-ADDED keep the
    aggregates equal to a fresh bootstrap (bound-pod requests survive the
    flap and fold back in)."""
    from opensim_tpu.server.watch import WatchSupervisor

    policy = {"stale_s": 30.0, "resync_s": 0.0, "reconnects": 1, "backoff_s": 0.0}
    sup = WatchSupervisor(source=None, policy=policy)
    engine = CapacityEngine()
    sup.capacity = engine
    sup.twin.rebase("nodes", [fx.make_fake_node(f"n{i}", "8", "16Gi").raw for i in range(2)])
    sup.twin.rebase("pods", [_pod_dict("a", node="n0", rv=1)])
    sup._capacity_rebase()

    bigger = fx.make_fake_node("n0", "32", "64Gi").raw
    bigger["metadata"]["resourceVersion"] = "20"
    sup.dispatch("nodes", "MODIFIED", bigger)
    gone = fx.make_fake_node("n1", "8", "16Gi").raw
    gone["metadata"]["resourceVersion"] = "21"
    sup.dispatch("nodes", "DELETED", gone)
    back = fx.make_fake_node("n1", "8", "16Gi").raw
    back["metadata"]["resourceVersion"] = "22"
    sup.dispatch("nodes", "ADDED", back)

    fresh = CapacityEngine()
    fresh.bootstrap(sup.twin.materialize(), sup.twin.generation)
    _assert_engines_agree(engine, fresh)


def test_ensure_bootstrap_is_keyed_and_event_fed_wins():
    engine = CapacityEngine()
    cluster = _cluster()
    engine.ensure_bootstrap(cluster, "fp1")
    gen = engine.generation
    engine.ensure_bootstrap(cluster, "fp1")  # same key: no-op
    assert engine.generation == gen
    engine.ensure_bootstrap(cluster, "fp2")  # key moved: rebuild
    assert engine.generation == gen + 1
    engine.event_fed = True
    engine.ensure_bootstrap(cluster, "fp3")  # supervisor owns the view
    assert engine.generation == gen + 1


# ---------------------------------------------------------------------------
# headroom: probe == fresh simulate frontier
# ---------------------------------------------------------------------------


def test_headroom_bit_consistent_with_fresh_simulate():
    cluster = _cluster(n_nodes=3, n_pods=4)
    profile = WorkloadProfile("t", "1500m", "3Gi", max_replicas=64)
    engine = CapacityEngine()
    engine.bootstrap(cluster, 1)
    k = headroom_probe(cluster, profile, kmax=engine.fit_upper_bound(profile))

    def fits(n):
        rt = ResourceTypes()
        rt.add(fx.make_fake_deployment("probe", n, profile.cpu, profile.memory))
        return not simulate(cluster, [AppResource("probe", rt)]).unscheduled_pods

    assert k > 0
    assert fits(k), f"probe said {k} replicas fit but simulate disagrees"
    assert not fits(k + 1), f"probe said {k} is the max but {k + 1} also fits"


def test_headroom_zero_when_cluster_is_full():
    cluster = _cluster(n_nodes=1, n_pods=0)
    # fill the single 8-cpu node almost completely
    cluster.pods.append(fx.make_fake_pod("hog", "7500m", "12Gi", fx.with_node_name("n0")))
    profile = WorkloadProfile("big", "2", "4Gi", max_replicas=16)
    engine = CapacityEngine()
    engine.bootstrap(cluster, 1)
    assert engine.fit_upper_bound(profile) == 0
    assert headroom_probe(cluster, profile, kmax=engine.fit_upper_bound(profile)) == 0


def test_headroom_through_warm_base_entry_skips_full_prepare():
    """The server-path probe derives over the cached base entry: after the
    base exists, probing costs delta re-encodes only (the capacity-smoke
    acceptance in miniature) and agrees with the cold probe."""
    from opensim_tpu.engine import prepcache
    from opensim_tpu.utils.trace import PREP_STATS

    cluster = _cluster()
    profile = WorkloadProfile("t", "1", "2Gi", max_replicas=32)
    base_key = "test|base"
    watch = prepcache.watch_snapshot(cluster, [])
    base = prepcache.CacheEntry(base_key, prepare(cluster, []), watch=watch)

    cold = headroom_probe(cluster, profile, kmax=32)
    full_before = PREP_STATS.counts.get("full", 0)
    warm = headroom_probe(cluster, profile, base=base, kmax=32)
    assert warm == cold
    assert PREP_STATS.counts.get("full", 0) == full_before, (
        "warm-base probe paid a full O(cluster) prepare"
    )


def test_headroom_regrows_ladder_when_bound_undershoots():
    """A too-small kmax must not under-report: the probe doubles the ladder
    when everything fits (profile.max_replicas is the only hard ceiling)."""
    cluster = _cluster(n_nodes=2, n_pods=0)
    profile = WorkloadProfile("t", "1", "2Gi", max_replicas=64)
    honest = headroom_probe(cluster, profile, kmax=None)
    lowball = headroom_probe(cluster, profile, kmax=2)
    assert lowball == honest


def test_headroom_profiles_env_parsing(monkeypatch):
    monkeypatch.setenv("OPENSIM_HEADROOM_PROFILES", "web=250m:512Mi,batch=2:4Gi:128")
    profiles = headroom_profiles()
    assert [(p.name, p.max_replicas) for p in profiles] == [("web", 256), ("batch", 128)]
    assert profiles[0].cpu_cores == pytest.approx(0.25)
    for bad in ("oops", "a=1", "a=0:0", "a=1:1Gi:x", "a=1:1Gi,a=2:2Gi", "b ad=1:1Gi"):
        monkeypatch.setenv("OPENSIM_HEADROOM_PROFILES", bad)
        with pytest.raises(ValueError):
            headroom_profiles()


# ---------------------------------------------------------------------------
# report parity: JSON cells byte-equal to the text table cells
# ---------------------------------------------------------------------------


def _text_section(text, title):
    lines = text.splitlines()
    start = lines.index(title) + 1
    out = []
    for line in lines[start:]:
        if not line.strip():
            break
        out.append(line)
    return out


def _rendered(rows):
    import io

    from opensim_tpu.planner.report import _table

    out = io.StringIO()
    _table(rows, out)
    return out.getvalue().splitlines()


def test_report_json_byte_equal_to_text_renderer():
    import io

    from opensim_tpu.planner import report as report_mod

    cluster = _cluster()
    cluster.pods[0].metadata.labels["simon/app-name"] = "demo"
    result = snapshot_result(cluster)
    engine = CapacityEngine()
    engine.bootstrap(cluster, 1)
    report = build_report(engine, cluster, state="test")

    out = io.StringIO()
    report_mod.report_cluster_info(result, [], out)
    report_mod.report_app_info(result, ["demo"], out)
    text = out.getvalue()

    # byte-equality: rendering the JSON rows reproduces the text renderer's
    # table exactly — the two surfaces share ONE computation path
    json_rows = [report["nodeInfo"]["header"]] + report["nodeInfo"]["rows"]
    assert _text_section(text, "Node Info") == _rendered(json_rows)
    app_rows = [report["appInfo"]["header"]] + report["appInfo"]["rows"]
    assert _text_section(text, "App Info") == _rendered(app_rows)
    # the JSON round-trips (the endpoint serializes this dict verbatim)
    assert json.loads(json.dumps(report))["nodeInfo"]["rows"] == report["nodeInfo"]["rows"]


def test_rest_report_endpoint_and_timeline_export():
    from http.server import ThreadingHTTPServer

    from opensim_tpu.server import rest

    server = rest.SimonServer(base_cluster=_cluster())
    try:
        httpd = ThreadingHTTPServer(("127.0.0.1", 0), rest.make_handler(server))
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            with urllib.request.urlopen(f"{base}/api/cluster/report", timeout=30) as resp:
                body = json.load(resp)
            assert body["capacity"]["nodes"] == 4
            assert body["capacity"]["headroom"], "headroom probes missing from the report"
            assert body["nodeInfo"]["rows"], "node table missing"
            # the same numbers the CLI renders (smoke the formatter too)
            rendered = format_top(body)
            assert "Utilization" in rendered and "Headroom" in rendered
            with urllib.request.urlopen(f"{base}/api/debug/capacity", timeout=30) as resp:
                tl = json.load(resp)
            assert tl["samples"], "timeline export is empty"
            assert tl["samples"][-1]["generation"] == body["capacity"]["generation"]
            # headroom=0 skips the probes but still reports utilization
            with urllib.request.urlopen(
                f"{base}/api/cluster/report?headroom=0", timeout=30
            ) as resp:
                assert json.load(resp)["capacity"]["nodes"] == 4
        finally:
            httpd.shutdown()
    finally:
        server.close()


def test_simon_top_cli_one_shot(capsys):
    from http.server import ThreadingHTTPServer

    from opensim_tpu.cli.main import main as cli_main
    from opensim_tpu.server import rest

    server = rest.SimonServer(base_cluster=_cluster())
    try:
        httpd = ThreadingHTTPServer(("127.0.0.1", 0), rest.make_handler(server))
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            assert cli_main(["top", "--url", base]) == 0
            out = capsys.readouterr().out
            assert "Resource" in out and "cpu" in out
            assert cli_main(["top", "--url", base, "--json", "--no-headroom"]) == 0
            body = json.loads(capsys.readouterr().out)
            assert body["capacity"]["nodes"] == 4
        finally:
            httpd.shutdown()
    finally:
        server.close()


def test_report_lists_pods_bound_to_absent_nodes():
    """A pod bound to a node missing from the view (node-flap window) has
    no table row, but the report reconciles: it appears in `orphaned` so
    pods_bound never silently disagrees with the tables."""
    cluster = _cluster(n_nodes=2, n_pods=2)
    cluster.pods.append(
        fx.make_fake_pod("ghost", "1", "1Gi", fx.with_node_name("gone-node"))
    )
    engine = CapacityEngine()
    engine.bootstrap(cluster, 1)
    rep = build_report(engine, cluster, state="test")
    assert rep["capacity"]["pods_bound"] == 3  # the aggregates still count it
    assert rep["orphaned"] == ["default/ghost (on gone-node)"]
    assert "absent nodes" in format_top(rep)


# ---------------------------------------------------------------------------
# timeline ring
# ---------------------------------------------------------------------------


def test_timeline_ring_bounds_and_generation_replacement():
    tl = Timeline(capacity=4)
    for g in range(10):
        tl.append(Sample(generation=g))
    assert len(tl) == 4
    assert [s.generation for s in tl.snapshot()] == [6, 7, 8, 9]
    enriched = Sample(generation=9)
    enriched.headroom = {"small": 3}
    tl.append(enriched)  # same generation: replace, don't append
    assert len(tl) == 4
    assert tl.latest().headroom == {"small": 3}


def test_sampling_is_generation_keyed():
    engine = CapacityEngine()
    cluster = _cluster()
    engine.bootstrap(cluster, 1)
    s1 = engine.sample()
    assert engine.sample() is s1  # memoized: no second fold, no new row
    assert len(engine.timeline) == 1
    engine.bootstrap(cluster, 2)
    s2 = engine.sample()
    assert s2 is not s1 and s2.generation == 2
    assert len(engine.timeline) == 2


# ---------------------------------------------------------------------------
# batch deadline shedding (NOTES.md rough edge)
# ---------------------------------------------------------------------------


def test_rest_batch_transports_rider_shed_as_504(monkeypatch):
    """End-to-end through the admission batch executor: a rider whose
    deadline dies in flight resolves as the typed 504, the others as 200s."""
    from opensim_tpu.resilience.deadline import Deadline, DeadlineExceeded
    from opensim_tpu.server import admission as admission_mod
    from opensim_tpu.server import rest

    server = rest.SimonServer(base_cluster=_cluster(), admission=False)
    clock = lambda: 100.0
    tickets = []
    for i, name in enumerate(("w-a", "w-b")):
        payload = {"deployments": [fx.make_fake_deployment(name, 2, "250m", "512Mi").raw]}
        tickets.append(
            admission_mod.Ticket(
                kind="deploy", payload=payload,
                deadline=Deadline(expires_at=50.0, budget_s=1.0, clock=clock)
                if i == 1
                else None,
            )
        )
    # mark the dead ticket as NOT pre-expired so it reaches the batch (the
    # in-flight case: alive at admission, dead before the dispatch)
    tickets[1]._expired_at_admission = False
    server._admitted_batch(tickets)
    assert tickets[0].error is None and tickets[0].result is not None
    assert isinstance(tickets[1].error, DeadlineExceeded)
    assert tickets[1].error.phase == "schedule"
    server.close()


# ---------------------------------------------------------------------------
# OSL1101 metric-registry
# ---------------------------------------------------------------------------


def test_osl1101_flags_registration_outside_metrics():
    from opensim_tpu.analysis import lint_source

    src = (
        "from opensim_tpu.obs.metrics import CounterVec, exposition_headers\n"
        "c = CounterVec('simon_x_total', ('a',), help='x')\n"
        "h = exposition_headers('simon_x_total', 'x')\n"
    )
    findings = lint_source(src, path="opensim_tpu/server/somewhere.py",
                           rules=["metric-registry"])
    assert [f.code for f in findings] == ["OSL1101", "OSL1101"]
    # the registry module itself and tests are exempt
    assert not lint_source(src, path="opensim_tpu/obs/metrics.py",
                           rules=["metric-registry"])
    assert not lint_source(src, path="tests/test_x.py", rules=["metric-registry"])


def test_osl1101_allows_registry_helpers():
    from opensim_tpu.analysis import lint_source

    src = (
        "from opensim_tpu.obs.metrics import family_header, make_counter\n"
        "c = make_counter('simon_shed_total', ('reason',))\n"
        "lines = family_header('simon_watch_state')\n"
    )
    assert not lint_source(src, path="opensim_tpu/server/somewhere.py",
                           rules=["metric-registry"])


def test_family_header_rejects_unregistered_family():
    from opensim_tpu.obs.metrics import family_header, make_counter, make_histogram

    with pytest.raises(KeyError):
        family_header("simon_never_registered_total")
    with pytest.raises(KeyError):
        make_counter("simon_never_registered_total", ())
    with pytest.raises(ValueError):
        make_histogram("simon_shed_total", ())  # registered as a counter
