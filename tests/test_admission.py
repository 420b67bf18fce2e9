"""Concurrent serving core (ISSUE 8): admission queue, cross-request
batching, worker pool, load-shedding.

The load-bearing gate is bit-identity: N parallel clients (mixed
deploy/scale, batched and unbatched paths, twin events mid-storm) must
produce placements identical to the same requests run serially through the
seed's proven path. Pod names embed a process-global expansion counter
(NOTES invariant) and are not stable across re-expansions, so identity is
compared on suffix-normalized names — everything else (node assignment,
counts, reasons) must match exactly.
"""

import re
import threading
import time

import pytest

from opensim_tpu.models import ResourceTypes, fixtures as fx
from opensim_tpu.models.objects import OwnerReference
from opensim_tpu.obs.metrics import RECORDER
from opensim_tpu.obs.recorder import FLIGHT_RECORDER
from opensim_tpu.resilience.deadline import Deadline


@pytest.fixture(autouse=True)
def _clean_recorders():
    FLIGHT_RECORDER.clear()
    RECORDER.reset()
    yield
    FLIGHT_RECORDER.clear()
    RECORDER.reset()


def _cluster():
    rt = ResourceTypes()
    for i in range(6):
        rt.nodes.append(
            fx.make_fake_node(
                f"n{i:03d}", "16", "64Gi", "110",
                fx.with_labels({"topology.kubernetes.io/zone": f"z{i % 3}"}),
            )
        )
    rt.pods.append(fx.make_fake_pod("pinned", "100m", "128Mi", fx.with_node_name("n000")))
    # a deployment-owned snapshot pod so scale-apps has something to remove
    owned = fx.make_fake_pod("web-1", "500m", "1Gi", fx.with_node_name("n001"))
    owned.metadata.owner_references = [
        OwnerReference(kind="Deployment", name="web", uid="u1", controller=True)
    ]
    rt.pods.append(owned)
    return rt


def _requests():
    """Mixed request set: distinct deploys, a scale, and an unschedulable
    workload (reason rendering must survive batching)."""
    reqs = []
    for i in range(5):
        reqs.append(
            ("deploy", {"deployments": [
                fx.make_fake_deployment(f"app-{i}", 2 + i % 3, "500m", "1Gi").raw
            ]})
        )
    reqs.append(
        ("scale", {"deployments": [fx.make_fake_deployment("web", 3, "200m", "256Mi").raw]})
    )
    reqs.append(
        ("deploy", {"deployments": [fx.make_fake_deployment("huge", 1, "640", "1Gi").raw]})
    )
    return reqs


def _workloads_of(payloads) -> list:
    """Deployment names in a request set — the stable identity pod names
    are canonicalized onto."""
    names = []
    for p in payloads:
        for d in p.get("deployments") or []:
            names.append(d["metadata"]["name"])
    return names


def _canon_pod(ref: str, workloads) -> str:
    """``ns/name`` → ``ns/<owning workload>``: expansion counters make the
    raw names unstable across re-expansions (NOTES invariant), but every
    expanded pod name starts with its workload's name. Longest prefix wins
    (``app-1`` vs ``app-10``)."""
    ns, _, name = ref.partition("/")
    best = ""
    for w in workloads:
        if name.startswith(w) and len(w) > len(best):
            best = w
    return f"{ns}/{best or name}"


def _canon(body: dict, workloads):
    return (
        sorted(
            (_canon_pod(u["pod"], workloads), u["reason"])
            for u in body["unscheduledPods"]
        ),
        sorted(
            (e["node"], sorted(_canon_pod(p, workloads) for p in e["pods"]))
            for e in body["nodeStatus"]
        ),
    )


def _make_server(window_s=None, pipelined=True, **kwargs):
    from opensim_tpu.server import admission as admission_mod
    from opensim_tpu.server.rest import SimonServer

    server = SimonServer(base_cluster=_cluster(), **kwargs)
    if window_s is not None and server.admission is not None:
        server.admission.stop()
        stage_fns = (
            dict(
                prep_fn=server._batch_prep, dispatch_fn=server._batch_dispatch,
                decode_fn=server._batch_decode,
            )
            if pipelined
            else {}
        )
        server.admission = admission_mod.AdmissionController(
            solo_fn=server._admitted_solo, batch_fn=server._admitted_batch,
            window_s=window_s, **stage_fns,
        )
    return server


# ---------------------------------------------------------------------------
# bit-identity: batched concurrent == serial
# ---------------------------------------------------------------------------


def test_concurrent_batched_bitidentical_to_serial():
    """N parallel mixed deploy/scale requests through the admission queue
    (wide window → guaranteed coalescing) produce placements identical to
    the same requests run serially on the single-flight path."""
    reqs = _requests()
    wl = _workloads_of([p for _, p in reqs])

    serial = _make_server(admission=False)
    expected = []
    for kind, payload in reqs:
        code, body = (
            serial.deploy_apps if kind == "deploy" else serial.scale_apps
        )(payload)
        assert code == 200, body
        expected.append(_canon(body, wl))

    batched = _make_server(window_s=0.25)
    results = [None] * len(reqs)

    def run(i, kind, payload):
        results[i] = (
            batched.deploy_apps if kind == "deploy" else batched.scale_apps
        )(payload)

    threads = [
        threading.Thread(target=run, args=(i, k, p))
        for i, (k, p) in enumerate(reqs)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    try:
        for i, (code, body) in enumerate(results):
            assert code == 200, (i, body)
            assert _canon(body, wl) == expected[i], f"request {i} diverged"
        # the run actually batched (a test that silently went solo would
        # gate nothing)
        assert batched.admission.batches_total >= 1
    finally:
        batched.close()
        serial.close()


def test_unbatchable_newnodes_rides_worker_pool_alongside_batch():
    """newnodes requests (randomized fake node names) must not join a
    batch — they run solo through the pool, concurrently with a batch, and
    still answer exactly."""
    server = _make_server(window_s=0.25)
    # the nn workload REQUIRES the fake node (simon/new-node marker), so
    # its placement deterministically proves the newnodes path ran
    new_node_payload = {
        "deployments": [
            fx.make_fake_deployment(
                "nn", 2, "500m", "1Gi",
                fx.with_node_selector({"simon/new-node": ""}),
            ).raw
        ],
        "newnodes": [fx.make_fake_node("template", "8", "16Gi").raw],
    }
    plain = {"deployments": [fx.make_fake_deployment("plain-a", 2, "250m", "512Mi").raw]}
    plain2 = {"deployments": [fx.make_fake_deployment("plain-b", 2, "250m", "512Mi").raw]}
    results = [None] * 3

    def run(i, payload):
        results[i] = server.deploy_apps(payload)

    threads = [
        threading.Thread(target=run, args=(i, p))
        for i, p in enumerate((new_node_payload, plain, plain2))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    try:
        for code, body in results:
            assert code == 200, body
        # the newnodes request bound its pods onto fresh simon-* fake nodes
        nn_nodes = {e["node"] for e in results[0][1]["nodeStatus"]}
        assert any(n.startswith("simon-") for n in nn_nodes)
    finally:
        server.close()


def test_batched_and_solo_paths_expose_queue_metrics():
    server = _make_server(window_s=0.15)
    try:
        payloads = [
            {"deployments": [fx.make_fake_deployment(f"m-{i}", 2, "250m", "256Mi").raw]}
            for i in range(4)
        ]
        results = [None] * 4

        def run(i):
            results[i] = server.deploy_apps(payloads[i])

        threads = [threading.Thread(target=run, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(code == 200 for code, _ in results)
        from opensim_tpu.server.rest import METRICS

        text = METRICS.render(prep_cache=server.prep_cache, admission=server.admission)
        for needle in (
            "# TYPE simon_admission_queue_depth gauge",
            "# TYPE simon_batch_size histogram",
            "# TYPE simon_shed_total counter",
            "# TYPE simon_queue_wait_seconds histogram",
            "simon_batches_total",
        ):
            assert needle in text, needle
        # real time-in-queue recorded for every admitted request
        m = re.search(r"simon_queue_wait_seconds_count (\d+)", text)
        assert m and int(m.group(1)) >= 4
        m = re.search(r"simon_batch_size_count (\d+)", text)
        assert m and int(m.group(1)) >= 1
    finally:
        server.close()


# ---------------------------------------------------------------------------
# shed / deadline paths: typed errors, never partial results
# ---------------------------------------------------------------------------


def test_queue_full_sheds_typed_503_with_retry_after():
    from opensim_tpu.server import admission as admission_mod
    from opensim_tpu.server import rest as rest_mod
    from opensim_tpu.server.rest import SimonServer

    server = SimonServer(base_cluster=_cluster())
    server.admission.stop()
    server.admission = admission_mod.AdmissionController(
        solo_fn=server._admitted_solo, batch_fn=server._admitted_batch,
        window_s=0.6, bound=1,
    )
    try:
        first = {}

        def hold():
            first["resp"] = server.deploy_apps(
                {"deployments": [fx.make_fake_deployment("hold", 2, "250m", "256Mi").raw]}
            )

        t = threading.Thread(target=hold)
        t.start()
        # the first ticket sits in the 0.6s coalescing window; the queue
        # (bound 1) is full, so this request must shed NOW with a typed 503
        time.sleep(0.1)
        code, body = server.deploy_apps(
            {"deployments": [fx.make_fake_deployment("shed-me", 2, "250m", "256Mi").raw]}
        )
        assert code == 503
        assert body["reason"] == "queue_full" and body["retryable"] is True
        assert "Retry-After" in rest_mod.response_extra_headers()
        t.join()
        assert first["resp"][0] == 200
        text = rest_mod.METRICS.render(admission=server.admission)
        assert 'simon_shed_total{reason="queue_full"} 1' in text
        # the shed's latency is real elapsed time, not a fake 0.0 —
        # observed while the request waited, so the series must exist with
        # the shed status
        assert 'status="shed"' in text
    finally:
        server.close()


def test_deadline_expiring_in_queue_sheds_504_queue_phase():
    from opensim_tpu.server import admission as admission_mod
    from opensim_tpu.server.rest import SimonServer

    server = SimonServer(base_cluster=_cluster())
    server.admission.stop()
    server.admission = admission_mod.AdmissionController(
        solo_fn=server._admitted_solo, batch_fn=server._admitted_batch,
        window_s=0.5,
    )
    try:
        # alive at admission, dead by the time the window closes
        dl = Deadline.after(0.1)
        code, body = server.deploy_apps(
            {"deployments": [fx.make_fake_deployment("late", 2, "250m", "256Mi").raw]},
            deadline=dl,
        )
        assert code == 504
        assert body["phase"] == "queue"
        from opensim_tpu.server.rest import METRICS

        text = METRICS.render(admission=server.admission)
        assert 'simon_shed_total{reason="deadline"} 1' in text
    finally:
        server.close()


def test_pre_expired_deadline_keeps_legacy_phase_contract():
    """A deadline already expired at admission executes and 504s at the
    first phase boundary (snapshot/prepare/...), exactly like the seed —
    the resilience tests' contract must survive the queue."""
    from opensim_tpu.server.rest import SimonServer

    server = SimonServer(base_cluster=_cluster())
    try:
        dl = Deadline.after(1e-9)
        time.sleep(0.01)
        code, body = server.deploy_apps(
            {"deployments": [fx.make_fake_deployment("dead", 2, "250m", "256Mi").raw]},
            deadline=dl,
        )
        assert code == 504
        assert body["phase"] in ("snapshot", "prepare", "encode", "schedule", "decode")
    finally:
        server.close()


def test_shutdown_resolves_queued_tickets_with_typed_shed():
    from opensim_tpu.server import admission as admission_mod

    resolved = []

    def never_solo(t):
        pass  # dispatcher never reaches it: stop() races first

    ctrl = admission_mod.AdmissionController(
        solo_fn=never_solo, batch_fn=lambda ts: None, window_s=5.0
    )
    t1 = admission_mod.Ticket(kind="deploy", payload={})
    ctrl.submit(t1)
    ctrl.stop()
    with pytest.raises(admission_mod.QueueFull):
        ctrl.wait(t1)
    # post-stop submission sheds immediately
    with pytest.raises(admission_mod.QueueFull):
        ctrl.submit(admission_mod.Ticket(kind="deploy", payload={}))
    assert not resolved


# ---------------------------------------------------------------------------
# twin events mid-storm
# ---------------------------------------------------------------------------


def test_batched_requests_with_twin_events_mid_storm(tmp_path):
    """Concurrent batched requests while the live twin absorbs watch events
    still answer 200 with placements consistent with a fresh serial run of
    the post-storm state."""
    from opensim_tpu.server import rest
    from opensim_tpu.server.stubapi import StubApiServer
    from opensim_tpu.server.watch import RestWatchSource, WatchSupervisor

    stub = StubApiServer(bookmark_interval_s=0.1).start()
    stub.seed(
        "/api/v1/nodes",
        [fx.make_fake_node(f"n{i}", "8", "16Gi").raw for i in range(4)],
    )
    stub.seed("/api/v1/pods", [
        {
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": "seed-0", "namespace": "default"},
            "spec": {"nodeName": "n0", "containers": [
                {"name": "c", "resources": {"requests": {"cpu": "100m"}}}
            ]},
            "status": {"phase": "Running"},
        }
    ])
    for p in (
        "/apis/apps/v1/daemonsets", "/apis/policy/v1/poddisruptionbudgets",
        "/api/v1/services", "/apis/storage.k8s.io/v1/storageclasses",
        "/api/v1/persistentvolumeclaims", "/api/v1/configmaps",
    ):
        stub.seed(p, [])
    kc = stub.kubeconfig(str(tmp_path))
    policy = {"stale_s": 5.0, "resync_s": 0.0, "reconnects": 3, "backoff_s": 0.02}
    sup = WatchSupervisor(RestWatchSource(kc, read_timeout_s=5.0), policy=policy)
    server = rest.SimonServer(kubeconfig=kc, watch=sup)
    sup.prep_cache = server.prep_cache
    assert sup.start(wait_s=15.0)
    try:
        results = [None] * 6

        def run(i):
            results[i] = server.deploy_apps(
                {"deployments": [
                    fx.make_fake_deployment(f"storm-{i}", 2, "500m", "1Gi").raw
                ]}
            )

        threads = [threading.Thread(target=run, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        # a twin event lands mid-storm
        stub.upsert("/api/v1/pods", {
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": "mid-storm", "namespace": "default"},
            "spec": {"nodeName": "n1", "containers": [
                {"name": "c", "resources": {"requests": {"cpu": "200m"}}}
            ]},
            "status": {"phase": "Running"},
        })
        for t in threads:
            t.join()
        for code, body in results:
            assert code == 200, body
            # typed shape, never partial: every response carries both keys
            assert set(body) >= {"unscheduledPods", "nodeStatus"}
        # quiesce, then a fresh request equals a polling server's answer on
        # the SAME post-storm cluster (the twin_smoke convergence contract)
        gen = sup.twin.generation

        def _settled():
            return "mid-storm" in {
                p.metadata.name for p in sup.twin.materialize().pods
            }

        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and not _settled():
            time.sleep(0.05)
        assert _settled()
        code, twin_body = server.deploy_apps(
            {"deployments": [fx.make_fake_deployment("after", 3, "500m", "1Gi").raw]}
        )
        assert code == 200
        polling = rest.SimonServer(kubeconfig=kc, admission=False)
        code2, poll_body = polling.deploy_apps(
            {"deployments": [fx.make_fake_deployment("after", 3, "500m", "1Gi").raw]}
        )
        assert code2 == 200
        assert _canon(twin_body, ["after"]) == _canon(poll_body, ["after"])
    finally:
        server.close()
        sup.stop()
        stub.stop()


# ---------------------------------------------------------------------------
# review regressions: poisoned batches, pre-expired riders, process pool
# ---------------------------------------------------------------------------


def test_malformed_payload_fails_only_its_own_batch_rider():
    """One undecodable payload in a coalesced batch 500s that request
    alone; every other rider still answers 200 (never a poisoned group)."""
    server = _make_server(window_s=0.25)
    try:
        payloads = [
            {"deployments": [fx.make_fake_deployment(f"ok-{i}", 2, "250m", "256Mi").raw]}
            for i in range(3)
        ] + [{"deployments": ["garbage - not an object"]}]
        results = [None] * len(payloads)

        def run(i):
            results[i] = server.deploy_apps(payloads[i])

        threads = [threading.Thread(target=run, args=(i,)) for i in range(len(payloads))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for code, body in results[:3]:
            assert code == 200, body
        code, body = results[3]
        assert code == 500 and "error" in body and "type" in body
    finally:
        server.close()


def test_pre_expired_rider_takes_solo_path_even_in_a_storm():
    """A pre-expired deadline must 504 with a legacy phase even when it
    arrives alongside batchable traffic (the batch installs no deadline
    scope, so dead tickets are routed solo at dispatch)."""
    server = _make_server(window_s=0.25)
    try:
        results = {}

        def ok_run(i):
            results[i] = server.deploy_apps(
                {"deployments": [fx.make_fake_deployment(f"live-{i}", 2, "250m", "256Mi").raw]}
            )

        def dead_run():
            dl = Deadline.after(1e-9)
            time.sleep(0.01)
            results["dead"] = server.deploy_apps(
                {"deployments": [fx.make_fake_deployment("dead", 2, "250m", "256Mi").raw]},
                deadline=dl,
            )

        threads = [threading.Thread(target=ok_run, args=(i,)) for i in range(3)]
        threads.append(threading.Thread(target=dead_run))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i in range(3):
            assert results[i][0] == 200
        code, body = results["dead"]
        assert code == 504
        assert body["phase"] in ("snapshot", "prepare", "encode", "schedule", "decode")
    finally:
        server.close()


def test_process_pool_runs_unpicklable_tasks_on_threads():
    """OPENSIM_WORKERS_MODE=process must never hang admission work: bound
    methods / Tickets (threading primitives) are unpicklable, so they run
    on the thread fallback — picklable tasks may genuinely fork."""
    from opensim_tpu.server.pool import WorkerPool

    pool = WorkerPool(workers=2, mode="process")
    try:
        ev = threading.Event()

        class Holder:
            def poke(self, e):
                e.set()
                return "threaded"

        # unpicklable (bound method + Event): must execute via threads and
        # actually set OUR event (a forked child could never do that)
        fut = pool.submit(Holder().poke, ev)
        assert fut.result(timeout=10) == "threaded"
        assert ev.is_set()
        if pool.mode == "process":
            assert pool.submit(len, (1, 2, 3)).result(timeout=30) == 3
    finally:
        pool.shutdown()


# ---------------------------------------------------------------------------
# batched explain + vmapped-batch deadline shedding (ISSUE 15 satellites)
# ---------------------------------------------------------------------------


def test_explain_requests_batch_and_match_solo_audit():
    """``?explain=1`` deploys ride the shared batch (count_all fail rows
    over the shared derive) and the audit — per-pod explanations and the
    per-filter reject totals — is bit-identical to the solo explain path.
    The unschedulable workload is the load-bearing case: its reason
    breakdown comes entirely from the audited fail rows."""
    payloads = [
        {"deployments": [fx.make_fake_deployment(f"xp-{i}", 2, "500m", "1Gi").raw]}
        for i in range(3)
    ]
    payloads.append(
        {"deployments": [fx.make_fake_deployment("xhuge", 1, "640", "1Gi").raw]}
    )
    wl = _workloads_of(payloads)

    serial = _make_server(admission=False)
    expected = []
    for p in payloads:
        code, body = serial.deploy_apps(p, explain=True)
        assert code == 200, body
        expected.append(body)

    batched = _make_server(window_s=0.25)
    results = [None] * len(payloads)

    def run(i):
        results[i] = batched.deploy_apps(payloads[i], explain=True)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(payloads))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    try:
        for i, (code, body) in enumerate(results):
            assert code == 200, (i, body)
            assert _canon(body, wl) == _canon(expected[i], wl)
            # the audit payloads match too: reject totals and, for the
            # unschedulable rider, the per-pod explanation breakdown
            assert body.get("filterRejects") == expected[i].get("filterRejects"), i
            got_expl = {
                _canon_pod(u["pod"], wl): u.get("explanation")
                for u in body["unscheduledPods"]
            }
            want_expl = {
                _canon_pod(u["pod"], wl): u.get("explanation")
                for u in expected[i]["unscheduledPods"]
            }
            def _strip(e):
                if not isinstance(e, dict):
                    return e
                return {k: v for k, v in e.items() if k != "pod"}
            assert {k: _strip(v) for k, v in got_expl.items()} == {
                k: _strip(v) for k, v in want_expl.items()
            }, i
        assert batched.admission.batches_total >= 1, "explain traffic never batched"
    finally:
        batched.close()
        serial.close()


def test_xla_batch_sheds_expired_riders_before_dispatch(monkeypatch):
    """Pre-dispatch deadline shedding on the vmapped path: a rider whose
    deadline is already dead gets the typed 504 (phase=schedule) and its
    lane is masked out of the compiled dispatch; the live riders' results
    are untouched (their masks never included the shed rider's pods)."""
    from opensim_tpu.engine import prepcache, reqbatch
    from opensim_tpu.engine.simulator import AppResource, prepare
    from opensim_tpu.resilience.deadline import DeadlineExceeded

    cluster = _cluster()
    base = prepcache.CacheEntry("b|base", prepare(cluster, []))
    apps = []
    for i in range(3):
        rt = ResourceTypes()
        rt.add(fx.make_fake_deployment(f"dl-{i}", 2, "500m", "1Gi"))
        apps.append(AppResource("deploy", rt))

    def run(deadlines):
        with base.lock:
            base.restore()
            derived, slices = prepcache.derive_with_app_slices(
                base.prep, cluster, apps, base_entry=base
            )
            items = [
                reqbatch.BatchItem(
                    cluster=cluster, apps=[apps[s]],
                    lo=slices[s][0], hi=slices[s][1],
                    deadline=deadlines[s],
                )
                for s in range(len(apps))
            ]
            try:
                return reqbatch.run_request_batch(derived, items)
            finally:
                base.restore()

    clean = run([None, None, None])
    dead = Deadline.after(0.0)
    assert dead.expired()
    mixed = run([None, dead, None])

    assert isinstance(mixed[1], DeadlineExceeded)
    assert mixed[1].phase == "schedule"
    for s in (0, 2):
        assert not isinstance(mixed[s], BaseException)
        want = sorted(
            (ns.node.metadata.name, len(ns.pods))
            for ns in clean[s].node_status if ns.pods
        )
        got = sorted(
            (ns.node.metadata.name, len(ns.pods))
            for ns in mixed[s].node_status if ns.pods
        )
        assert want == got, f"live rider {s} perturbed by the shed rider"


# ---------------------------------------------------------------------------
# pipelined admission + priority lanes (ISSUE 16)
# ---------------------------------------------------------------------------


def _storm(server, reqs):
    results = [None] * len(reqs)

    def run(i, kind, payload):
        results[i] = (
            server.deploy_apps if kind == "deploy" else server.scale_apps
        )(payload)

    threads = [
        threading.Thread(target=run, args=(i, k, p))
        for i, (k, p) in enumerate(reqs)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results


def test_pipelined_matches_nonpipelined_under_mixed_storm():
    """The tentpole gate: the same mixed deploy/scale storm through the
    staged prep/dispatch/decode pipeline and through the serial inline
    batch path produces identical placements — and the pipeline
    demonstrably engaged (all three stage aggregates recorded)."""
    reqs = _requests()
    wl = _workloads_of([p for _, p in reqs])
    serial = _make_server(window_s=0.25, pipelined=False)
    piped = _make_server(window_s=0.25)
    assert piped.admission.pipelined and not serial.admission.pipelined
    try:
        want = _storm(serial, reqs)
        got = _storm(piped, reqs)
        for i in range(len(reqs)):
            assert want[i][0] == 200, (i, want[i][1])
            assert got[i][0] == 200, (i, got[i][1])
            assert _canon(got[i][1], wl) == _canon(want[i][1], wl), (
                f"request {i} diverged between pipelined and serial"
            )
        snap = piped.admission.pipeline_snapshot()
        assert snap["enabled"] and snap["batches"] >= 1
        for stage in ("prep", "dispatch", "decode"):
            assert snap["stages"].get(stage, {}).get("count", 0) >= 1, stage
        # pipeline + lane telemetry families are live on /metrics
        from opensim_tpu.server.rest import METRICS

        text = METRICS.render(admission=piped.admission)
        for needle in (
            "# TYPE simon_pipeline_stage_seconds histogram",
            "# TYPE simon_pipeline_prep_overlap_seconds_total counter",
            "# TYPE simon_pipeline_overlapped_batches_total counter",
            "# TYPE simon_lane_depth gauge",
            "# TYPE simon_lane_admitted_total counter",
            "# TYPE simon_lane_shed_total counter",
            "# TYPE simon_lane_starvation_promotions_total counter",
            'simon_pipeline_stage_seconds_count{stage="prep"}',
        ):
            assert needle in text, needle
    finally:
        piped.close()
        serial.close()


def test_generation_swap_mid_prep_retries_once_bitidentical():
    """A stale fingerprint surfacing at the prep stage (the cache.stale
    fault point — what a twin generation swap mid-prep looks like to the
    pipeline) retries exactly once INSIDE prep; the storm still answers
    bit-identically to the serial single-flight path."""
    from opensim_tpu.resilience import faults
    from opensim_tpu.server.rest import METRICS

    reqs = _requests()
    wl = _workloads_of([p for _, p in reqs])
    serial = _make_server(admission=False)
    expected = []
    for kind, payload in reqs:
        code, body = (
            serial.deploy_apps if kind == "deploy" else serial.scale_apps
        )(payload)
        assert code == 200, body
        expected.append(_canon(body, wl))

    piped = _make_server(window_s=0.25)
    retries0 = METRICS.stale_prep_retries
    faults.inject("cache.stale", count=1, exc="stale")
    try:
        results = _storm(piped, reqs)
        for i, (code, body) in enumerate(results):
            assert code == 200, (i, body)
            assert _canon(body, wl) == expected[i], f"request {i} diverged"
        assert METRICS.stale_prep_retries - retries0 >= 1
        assert piped.admission.batches_total >= 1
    finally:
        faults.clear_faults()
        piped.close()
        serial.close()


def _lane_ticket(name, reps, explain=False):
    from opensim_tpu.server import admission as admission_mod

    return admission_mod.Ticket(
        kind="deploy",
        payload={
            "deployments": [
                fx.make_fake_deployment(name, reps, "100m", "128Mi").raw
            ]
        },
        explain=explain,
    )


def _lane_controller(batch_fn, window_s=0.4, **kwargs):
    from opensim_tpu.server import admission as admission_mod

    return admission_mod.AdmissionController(
        solo_fn=lambda t: t.resolve(result=None), batch_fn=batch_fn,
        window_s=window_s, **kwargs,
    )


def test_interactive_lane_overtakes_bulk_within_weight(monkeypatch):
    """Weighted pickup: small requests submitted AFTER large ones are
    still drained first (interactive lane wins up to the lane weight),
    while FIFO order is preserved within each lane."""
    monkeypatch.setenv("OPENSIM_LANE_STARVATION_S", "30")  # isolate the weight
    order = []
    done = threading.Event()

    def batch_fn(tickets):
        order.extend((t.lane, t.payload["deployments"][0]["metadata"]["name"]) for t in tickets)
        for t in tickets:
            t.resolve(result=None)
        done.set()

    ctrl = _lane_controller(batch_fn)
    try:
        tickets = [
            ctrl.submit(_lane_ticket("big-0", 50)),
            ctrl.submit(_lane_ticket("big-1", 50)),
            ctrl.submit(_lane_ticket("small-0", 1)),
            ctrl.submit(_lane_ticket("small-1", 1, explain=True)),
        ]
        assert done.wait(timeout=30)
        for t in tickets:
            ctrl.wait(t)
        assert order == [
            ("interactive", "small-0"), ("interactive", "small-1"),
            ("bulk", "big-0"), ("bulk", "big-1"),
        ]
        assert ctrl.lane_admitted == {"interactive": 2, "bulk": 2}
    finally:
        ctrl.stop()


def test_bulk_starvation_bound_promotes_past_weight(monkeypatch):
    """The starvation bound: a bulk head older than the bound is picked
    BEFORE waiting interactive requests regardless of lane weight, and the
    promotion is counted."""
    monkeypatch.setenv("OPENSIM_LANE_STARVATION_S", "0")
    order = []
    done = threading.Event()

    def batch_fn(tickets):
        order.extend(t.lane for t in tickets)
        for t in tickets:
            t.resolve(result=None)
        done.set()

    ctrl = _lane_controller(batch_fn, window_s=0.3)
    try:
        b = ctrl.submit(_lane_ticket("big", 50))
        i1 = ctrl.submit(_lane_ticket("small-0", 1))
        i2 = ctrl.submit(_lane_ticket("small-1", 1))
        assert done.wait(timeout=30)
        for t in (b, i1, i2):
            ctrl.wait(t)
        assert order[0] == "bulk", order
        assert ctrl.starvation_promotions >= 1
        from opensim_tpu.server.rest import METRICS

        text = METRICS.render(admission=ctrl)
        assert re.search(r"simon_lane_starvation_promotions_total [1-9]", text)
    finally:
        ctrl.stop()


def test_queue_full_shed_is_lane_attributed():
    """Sheds carry their lane: a bulk request shed at the bound lands in
    ``simon_lane_shed_total{lane="bulk",reason="queue_full"}`` alongside
    the existing un-laned ``simon_shed_total``."""
    from opensim_tpu.server import admission as admission_mod
    from opensim_tpu.server.rest import METRICS

    ctrl = _lane_controller(lambda ts: None, window_s=5.0, bound=1)
    try:
        held = ctrl.submit(_lane_ticket("held", 1))  # parks in the window
        with pytest.raises(admission_mod.QueueFull):
            ctrl.submit(_lane_ticket("shed-bulk", 50))
        text = METRICS.render(admission=ctrl)
        assert 'simon_lane_shed_total{lane="bulk",reason="queue_full"} 1' in text
        assert 'simon_shed_total{reason="queue_full"} 1' in text
        assert 'simon_lane_admitted_total{lane="interactive"} 1' in text
        assert held is not None
    finally:
        ctrl.stop()


def test_pipeline_off_knob_restores_serial_batch_path(monkeypatch):
    """OPENSIM_PIPELINE=off must construct a non-pipelined controller even
    when the staged executors are wired (the serial inline path is the
    fallback, and the storm still answers correctly)."""
    monkeypatch.setenv("OPENSIM_PIPELINE", "off")
    server = _make_server(window_s=0.2)
    try:
        assert server.admission.pipelined is False
        reqs = _requests()[:3]
        results = _storm(server, reqs)
        for code, body in results:
            assert code == 200, body
        assert server.admission.pipeline_snapshot()["enabled"] is False
    finally:
        server.close()
