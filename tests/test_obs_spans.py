"""The spans one level below the phase names (ISSUE 27): written through to
the profiler's clock, real and enclosing (no span made after the fact beside
the work it measured), the megakernel and the XLA scan split into inputs,
launch, wait and fetch, the prepare kinds with their parts, the queue where
the ticket's stamps put it, and the compile path's seconds as a counter."""

import glob
import os

import numpy as np
import pytest

from opensim_tpu.engine import prepcache
from opensim_tpu.engine.simulator import AppResource, prepare, simulate
from opensim_tpu.models import ResourceTypes, fixtures as fx
from opensim_tpu.obs import trace as tracing
from opensim_tpu.obs.metrics import RECORDER, parse_metrics
from opensim_tpu.obs.profile import COMPILES, launch_span
from opensim_tpu.obs.recorder import FLIGHT_RECORDER
from opensim_tpu.utils.trace import PREP_STATS

EPS = 1e-6


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("OPENSIM_TRACE", raising=False)
    FLIGHT_RECORDER.clear()
    RECORDER.reset()
    yield
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
    rt.pods.append(fx.make_fake_pod("pinned", "100m", "128Mi", fx.with_node_name("n000")))
    return rt


def _apps(name="web", replicas=4):
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment(name, replicas, "100m", "128Mi"))
    return [AppResource(name, app)]


def _traced(fn, endpoint="test"):
    tr = tracing.start_trace(endpoint, force=True)
    with tracing.trace_scope(tr):
        out = fn()
    tr.finish()
    return tr, out


def _find(tr, name):
    return [sp for sp in tr.walk() if sp.name == name]


def _walk_depth(sp, depth=0):
    yield depth, sp
    for c in sp.children:
        yield from _walk_depth(c, depth + 1)


def assert_spans_nest(sp):
    """Children lie inside their parent and no two siblings overlap: two
    spans of one tree share time only where one is the other's ancestor."""
    kids = sorted(sp.children, key=lambda c: c.start)
    for c in kids:
        assert c.end is not None
        assert c.start >= sp.start - EPS and c.end <= sp.end + EPS, (sp.name, c.name)
        assert_spans_nest(c)
    for a, b in zip(kids, kids[1:]):
        assert b.start >= a.end - EPS, f"{a.name} and {b.name} overlap under {sp.name}"


# ---------------------------------------------------------------------------
# (a) write-through to the profiler
# ---------------------------------------------------------------------------


def _captured(fn, endpoint, out):
    """`fn()` under an ambient trace inside a profiler capture written to
    `out`: the trace, the host plane's events by name, and what `fn` gave."""
    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        tr, res = _traced(fn, endpoint=endpoint)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    events.setdefault(ev.name, []).append(ev)
    return tr, events, res


def _at_own_interval(tr, events, name):
    """Every span of the name has a host-plane event of the name at its own
    interval, one offset (the root's) tying the two clocks."""
    (root,) = events[tr.root.name]
    offset = root.start_ns * 1e-9 - tr.root.start
    spans = _find(tr, name)
    evs = sorted(events[name], key=lambda ev: ev.start_ns)
    assert spans and len(evs) == len(spans)
    for sp, ev in zip(spans, evs):
        assert abs(ev.start_ns * 1e-9 - offset - sp.start) < 1e-3
        assert abs((ev.start_ns + ev.duration_ns) * 1e-9 - offset - sp.end) < 1e-3


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One tiny simulate() on the XLA scan under an ambient trace, inside a
    profiler capture: the request's spans and the host plane's events."""
    simulate(_cluster(), _apps())  # compile outside the capture
    tr, events, res = _captured(lambda: simulate(_cluster(), _apps()), "lib-call",
                                str(tmp_path_factory.mktemp("profile")))
    assert res.engine.name == "xla"
    return tr, events


@pytest.mark.parametrize("name", ["prepare", "schedule", "engine.xla", "xla.launch", "xla.wait", "decode"])
def test_a_span_stands_in_the_profilers_host_plane_at_its_own_interval(profiled, name):
    _at_own_interval(*profiled, name)


@pytest.fixture(scope="module")
def profiled_plan(tmp_path_factory):
    """The example plan (`simon apply`) under an ambient trace, inside a
    profiler capture (ISSUE 37): its load and its report tables."""
    from opensim_tpu.planner.apply import Applier, Options

    out = tmp_path_factory.mktemp("plan")
    opts = Options(simon_config=os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                             "example", "simon-config.yaml"),
                   output_file=str(out / "report.txt"), report_pods=True)
    tr, events, rc = _captured(lambda: Applier(opts).run(), "apply", str(out / "profile"))
    assert rc == 0
    return tr, events


@pytest.mark.parametrize("name", ["load", "load.parse", "load.objects", "report.nodes", "report.apps"])
def test_a_plans_load_and_report_spans_stand_in_the_host_plane_at_their_own_intervals(profiled_plan, name):
    _at_own_interval(*profiled_plan, name)


def test_the_roots_annotation_carries_the_request_id(profiled):
    tr, events = profiled
    (root,) = events["lib-call"]
    assert dict(root.stats)["request_id"] == tr.request_id
    assert tr.tree()["started_monotonic"] == pytest.approx(tr.root.start, abs=1e-5)
    assert tr.summary()["started_monotonic"] == tr.tree()["started_monotonic"]


def test_a_root_finished_on_another_thread_leaves_its_annotation_alone():
    import threading

    tr = tracing.start_trace("handoff", force=True)
    worker = threading.Thread(target=tr.finish)
    worker.start()
    worker.join()
    assert tr.finished and tr._ann is None


# ---------------------------------------------------------------------------
# (b) the megakernel rung, in the Pallas interpreter
# ---------------------------------------------------------------------------

MK_PARTS = ["mk.inputs", "mk.launch", "mk.wait", "mk.fetch"]


@pytest.fixture(scope="module")
def megakernel_trace():
    os.environ["OPENSIM_FASTPATH"] = "interpret"
    try:
        tr, res = _traced(lambda: simulate(_cluster(), _apps()))
    finally:
        del os.environ["OPENSIM_FASTPATH"]
    assert res.engine.name == "megakernel", res.engine.describe()
    return tr


def test_the_megakernel_rung_is_inputs_launch_wait_fetch(megakernel_trace):
    (rung,) = _find(megakernel_trace, "engine.megakernel")
    assert [c.name for c in rung.children] == MK_PARTS
    assert_spans_nest(rung)
    assert sum(c.duration_s for c in rung.children) == pytest.approx(rung.duration_s, rel=0.05)


def test_the_launch_says_what_it_launched_and_whether_it_compiled(megakernel_trace):
    (launch,) = _find(megakernel_trace, "mk.launch")
    assert launch.attrs["scenarios"] == 1 and launch.attrs["nodes"] == 128
    assert launch.attrs["pods"] % 256 == 0 and launch.attrs["templates"] >= 1
    assert launch.attrs["big_u"] is False and launch.attrs["gpu_devices"] == 0
    assert launch.attrs["backend_compiles"] >= 0 and launch.attrs["cache_hits"] >= 0
    # ISSUE 34: the attributes it had, and whether the jitted entry traced
    assert set(launch.attrs) == {
        "scenarios", "pods", "nodes", "templates", "big_u", "gpu_devices", "backend_compiles", "cache_hits", "entry",
        "sublanes", "blocks", "pad_scenarios",  # ISSUE 38: a schedule is one block of one scenario
    }
    assert (launch.attrs["sublanes"], launch.attrs["blocks"], launch.attrs["pad_scenarios"]) == (1, 1, 0)
    assert launch.attrs["entry"] in ("traced", "cached")


def test_a_second_plan_of_the_same_shapes_enters_the_kernel_from_the_cache(monkeypatch, megakernel_trace):
    """ISSUE 34: the tree is the first plan's line for line; only `entry` says the jit's cache answered."""
    monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")
    tr, res = _traced(lambda: simulate(_cluster(), _apps()))
    assert res.engine.name == "megakernel"
    names = lambda t: [(depth, sp.name) for depth, sp in _walk_depth(t.root)]
    assert names(tr) == names(megakernel_trace)
    (launch,) = _find(tr, "mk.launch")
    assert launch.attrs["entry"] == "cached" and launch.attrs["backend_compiles"] == 0


def test_only_the_kernels_launch_says_entry(monkeypatch):
    tr, _ = _traced(lambda: simulate(_cluster(), _apps()))
    (launch,) = _find(tr, "xla.launch")
    assert set(launch.attrs) == {"pods", "backend_compiles", "cache_hits"}


def test_a_megakernel_sweep_has_the_same_four_parts():
    from opensim_tpu.engine import fastpath

    prep = prepare(_cluster(), _apps())
    S, P, N = 2, len(prep.ordered), int(np.asarray(prep.ec_np.node_valid).shape[0])
    node_valid = np.zeros((S, N), bool)
    node_valid[0, :4] = node_valid[1, :6] = True
    tr, out = _traced(lambda: fastpath.sweep(
        prep, node_valid, np.ones((S, P), bool), np.broadcast_to(prep.forced, (S, P)), interpret=True
    ))
    assert [c.name for c in tr.root.children] == MK_PARTS
    assert tr.root.children[0].attrs["scenarios"] == S
    assert tr.root.children[1].attrs["scenarios"] == S
    assert out[2].shape == (S, P)
    assert_spans_nest(tr.root)


def test_the_xla_rung_is_resident_pad_launch_wait(monkeypatch):
    tr, _ = _traced(lambda: simulate(_cluster(), _apps()))
    (rung,) = _find(tr, "engine.xla")
    assert [c.name for c in rung.children] == ["xla.resident", "xla.pad", "xla.launch", "xla.wait"]
    assert rung.children[0].attrs["outcome"] == "declined"  # a plan has no base entry to start from
    assert rung.attrs["pods"] == rung.attrs["scanned"] > 0
    assert {"backend_compiles", "cache_hits", "pods"} <= set(rung.children[2].attrs)
    assert_spans_nest(tr.root)


@pytest.mark.parametrize("engine", ["xla", "megakernel"])
def test_a_rung_says_whether_its_node_set_was_masked_and_metrics_counts_it(monkeypatch, engine):
    """ISSUE 30: `masked` on the rung that ran, and on `/metrics` the masked
    simulations by the engine that answered (an unmasked one is not counted)."""
    from opensim_tpu.server.rest import METRICS

    if engine != "xla":
        monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")
    prep = prepare(_cluster(), _apps())
    mask = np.zeros(np.asarray(prep.ec_np.node_valid).shape[0], bool)
    mask[:4] = True
    sub = _cluster(4)
    tr, res = _traced(lambda: (simulate(_cluster(), _apps()), simulate(sub, _apps(), prep=prep, node_valid=mask))[1])
    assert res.engine.name == engine and not res.unscheduled_pods
    assert [sp.attrs["masked"] for sp in _find(tr, "engine." + engine)] == [False, True]
    series = {
        dict(labels)["engine"]: value
        for (name, labels), value in parse_metrics(METRICS.render()).items()
        if name == "simon_masked_pass_total"
    }
    assert series == {engine: 1}


# ---------------------------------------------------------------------------
# (c) every prepare kind is a real span round its work
# ---------------------------------------------------------------------------


def _full():
    return prepare(_cluster(), _apps())


def _delta_apps():
    base = prepare(_cluster(), [])
    return lambda: prepcache.derive_with_apps(base, _cluster(), _apps())


def _delta_nodes():
    cluster = _cluster()
    base = prepare(cluster, _apps())
    new = [fx.make_fake_node("extra-0", "16", "64Gi", "110")]
    return lambda: prepcache.extend_with_nodes(base, new, cluster, _apps())


def _twin_delta():
    entry = prepcache.CacheEntry("base", prepare(_cluster(), []))
    added = [fx.make_fake_pod("late", "100m", "128Mi", fx.with_node_name("n001"))]
    return lambda: prepcache.twin_pod_delta(entry, "next", added, set())


def _hit():
    cache = prepcache.PrepareCache()
    prepcache.simulate_cached(_cluster(), _apps(), cache, key="k")
    return lambda: prepcache.simulate_cached(_cluster(), _apps(), cache, key="k")


KINDS = {
    # kind -> (builder of the traced call, the parts its span has to hold)
    "full": (lambda: _full, ["prep.expand", "encode"]),
    "delta_apps": (_delta_apps, ["prep.expand", "prep.assemble"]),
    "delta_nodes": (_delta_nodes, ["prep.assemble"]),
    "twin_delta": (_twin_delta, ["prep.assemble"]),
    "hit": (_hit, []),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_prepare_kind_is_a_real_span_round_its_work(kind):
    build, parts = KINDS[kind]
    call = build()
    PREP_STATS.reset()
    tr, out = _traced(call)
    assert out is not None
    (sp,) = _find(tr, "prep." + kind)
    assert sp.attrs["kind"] == kind
    assert [c.name for c in sp.children if c.name in parts] == parts
    # the stats and the span are one measurement of one interval
    assert PREP_STATS.last[0] == kind
    assert PREP_STATS.last[1] == pytest.approx(sp.duration_s, abs=2e-3)
    assert PREP_STATS.counts == {kind: 1}
    if kind == "full":  # `prepare` keeps its place under the root, the kind inside it
        (phase,) = tr.root.children
        assert phase.name == "prepare" and phase.children == [sp]
    assert_spans_nest(tr.root)


def test_a_delta_that_hands_the_work_back_stays_out_of_the_stats():
    entry = prepcache.CacheEntry("base", prepare(_cluster(), []))
    PREP_STATS.reset()
    tr, out = _traced(lambda: prepcache.twin_pod_delta(entry, "next", [], {("default", "no-such-pod")}))
    assert out is None and PREP_STATS.counts == {}
    assert len(_find(tr, "prep.twin_delta")) == 1  # the span stays: the time was spent


def test_a_prepare_that_raises_marks_its_span_and_records_nothing():
    PREP_STATS.reset()
    tr = tracing.start_trace("test", force=True)
    with tracing.trace_scope(tr), pytest.raises(RuntimeError):
        with PREP_STATS.timed("full"):
            raise RuntimeError("boom")
    tr.finish(status="error")
    (sp,) = _find(tr, "prep.full")
    assert sp.status == "error" and PREP_STATS.counts == {}


def test_a_served_request_has_no_two_spans_over_the_same_time():
    from opensim_tpu.server.rest import SimonServer

    server = SimonServer(base_cluster=_cluster())
    payload = {"deployments": [fx.make_fake_deployment("web", 6, "500m", "1Gi").raw]}
    for expected in ("prep.delta_apps", "prep.hit"):  # cold base + delta, then the full-key hit
        assert server.deploy_apps(payload)[0] == 200
        tr = FLIGHT_RECORDER.latest()
        assert _find(tr, expected), [sp.name for sp in tr.walk()]
        assert_spans_nest(tr.root)
        names = [c.name for c in tr.root.children]
        assert names.index("http.parse") < names.index("schedule") < names.index("http.respond")


# ---------------------------------------------------------------------------
# (d) the compile path's seconds as a counter
# ---------------------------------------------------------------------------


def test_compile_stages_grow_on_a_first_jit_call_and_render():
    import jax
    import jax.numpy as jnp

    COMPILES.install()
    before = COMPILES.snapshot()["stages"]
    counts = COMPILES.counts()
    assert set(before) == {"trace", "lower", "backend", "cache_retrieval"}
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    after = COMPILES.snapshot()["stages"]
    for stage in ("trace", "lower", "backend"):
        assert after[stage]["count"] > before[stage]["count"], stage
        assert after[stage]["seconds"] > before[stage]["seconds"], stage
    assert COMPILES.counts()[0] == counts[0] + after["backend"]["count"] - before["backend"]["count"]
    assert COMPILES.snapshot()["backend"]["compiles"] == after["backend"]["count"]
    lines = COMPILES.metrics_lines()
    assert "# TYPE simon_compile_stage_seconds_total counter" in lines
    series = {
        dict(labels)["stage"]: value
        for (name, labels), value in parse_metrics("\n".join(lines) + "\n").items()
        if name == "simon_compile_stage_seconds_total"
    }
    assert set(series) == set(after)
    assert series["trace"] == pytest.approx(after["trace"]["seconds"], abs=1e-5)


def test_a_launch_span_counts_the_compiles_inside_it():
    import jax
    import jax.numpy as jnp

    COMPILES.install()

    def body():
        with launch_span("xla.launch", pods=3):
            jax.jit(lambda x: x - 5)(jnp.arange(11)).block_until_ready()

    tr, _ = _traced(body)
    (sp,) = _find(tr, "xla.launch")
    assert sp.attrs["backend_compiles"] >= 1 and sp.attrs["pods"] == 3


# ---------------------------------------------------------------------------
# (e) the queue where the ticket's stamps put it
# ---------------------------------------------------------------------------


def test_the_queue_span_is_the_tickets_own_interval_inside_the_root():
    from opensim_tpu.server.rest import SimonServer

    server = SimonServer(base_cluster=_cluster())
    assert server.admission is not None
    tickets = []
    submit = server.admission.submit
    server.admission.submit = lambda t: (tickets.append(t), submit(t))[1]
    payload = {"deployments": [fx.make_fake_deployment("web", 6, "500m", "1Gi").raw]}
    assert server.deploy_apps(payload)[0] == 200
    tr = FLIGHT_RECORDER.latest()
    (ticket,) = tickets
    (queue,) = _find(tr, "queue")
    assert queue.start == pytest.approx(ticket.enqueued, abs=EPS)
    assert queue.duration_s == pytest.approx(ticket.queue_s, abs=EPS)
    assert tr.root.start <= queue.start and queue.end <= tr.root.end
    # it comes first, before anything the worker did, and over nothing else
    assert tr.root.children[0] is queue
    assert queue.end <= tr.root.children[1].start + EPS
    assert_spans_nest(tr.root)


def test_child_at_clamps_to_the_parent_and_keeps_start_order():
    root = tracing.Span("root", 10.0)
    root.end = 20.0
    late = root.child_at("late", 15.0, 16.0)
    early = root.child_at("early", 9.0, 11.0, lane="bulk")
    assert root.children == [early, late]
    assert (early.start, early.end, early.attrs) == (10.0, 11.0, {"lane": "bulk"})
    assert root.child_at("backwards", 18.0, 17.0).duration_s == 0.0


# ---------------------------------------------------------------------------
# (f) dormant: no ambient trace, no span
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["xla", "megakernel"])
def test_without_an_ambient_trace_the_new_call_sites_allocate_no_span(monkeypatch, engine):
    if engine != "xla":
        monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")
    made = []
    init = tracing.Span.__init__
    monkeypatch.setattr(tracing.Span, "__init__", lambda self, *a, **k: (made.append(a[0]), init(self, *a, **k))[1])
    assert tracing.current_trace() is None
    assert launch_span("mk.launch", pods=1) is tracing.NOOP_SPAN
    PREP_STATS.reset()
    res = simulate(_cluster(), _apps())
    assert res.engine.name == engine
    assert made == []
    assert PREP_STATS.counts == {"full": 1}  # the stats do not need a trace
