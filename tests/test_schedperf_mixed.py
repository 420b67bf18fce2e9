"""`schedperf-mixed-5k` (ISSUE 29): kube-scheduler's MixedSchedulingBasePod
case at sizes a test run can hold, through `Applier.run()` by each engine the
CPU has, replayed through the plain inter-pod reference of
`benchmarks/reference/kube_interpod_reference.py`; hand-worked cases of each
rule of that reference; and the span, attributes and counter the
configuration's path reports."""

import importlib
import json
import os

import numpy as np
import pytest

from benchmarks.drivers import Context
from benchmarks.reference import kube_interpod_reference as R
from benchmarks.reference.kube_reference import HOSTNAME, ZONE, Cluster, NodeSpec
from benchmarks.window import Window
from opensim_tpu.obs import trace as tracing
from opensim_tpu.obs.metrics import RECORDER

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "benchmarks", "configs", "schedperf-mixed-5k.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(REPO, "benchmarks", "traffic", "fit-interpod.json")) as f:
    TRAFFIC = json.load(f)

#: every shape of the configuration kept, the counts shrunk: the zone's count
#: passes 128 and 60 green pods leave 60 hostnames full on 150 nodes
MID = dict(CONFIG["tiny"], nodes=150, init_pods=60, measure_pods=40)
SIZES = {"tiny": CONFIG["tiny"], "mid": MID}
#: how a test asks for an engine on the CPU, and what the report then names
ENGINES = {
    "xla": ({}, "xla"),
    "megakernel": ({"OPENSIM_FASTPATH": "interpret"}, "megakernel"),
}
FEATURES = "interpod+prefg+spread_soft"


def drive(tmp_path, sizes, seed):
    ctx = Context(config=CONFIG, traffic=TRAFFIC, seed=seed, scratch=str(tmp_path), rehearse=True, sizes=sizes)
    driver = importlib.import_module("benchmarks.drivers.plan_loop_ref").Driver(ctx)
    driver.prepare()
    return driver


def plan(driver):
    window = Window(opened=0.0, closed=1.0, items=[driver.one(0, False)])
    driver.after_window(window)
    return window


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [5, 3000000023, 17])
@pytest.mark.parametrize("size", list(SIZES))
@pytest.mark.parametrize("engine", list(ENGINES))
def test_the_plan_replays_through_the_reference_with_nothing_misplaced(tmp_path, monkeypatch, engine, size, seed):
    env, named = ENGINES[engine]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    driver = drive(tmp_path, SIZES[size], seed)
    window = plan(driver)
    report = window.items[0].info["report"]
    assert report["success"] and report["engine"].startswith(named), report["engine"]
    values = {c["name"]: c["value"] for c in driver.compare(window)}
    assert values == {
        "misplaced_pods": 0, "worst_score_gap": 0.0, "infeasible_pods": 0, "unscheduled_diff": 0,
        "answer_diff": 0, "added_nodes_diff": 0, "plans_differing": 0, "plans_unanswered": 0,
    }
    # every required term holds in the answer, read off the report itself
    sizes = SIZES[size]
    placed = report["placed"]  # workload -> the node of each pod, in the order they were scheduled
    assert sum(len(seq) for seq in placed.values()) == 5 * sizes["init_pods"] + sizes["measure_pods"]
    green = placed["sched-0/pod-with-pod-anti-affinity"]
    assert len(green) == len(set(green)) == sizes["init_pods"]


def test_the_seed_decides_the_answer_and_the_stream_is_the_sources(tmp_path):
    a = drive(tmp_path / "a", CONFIG["tiny"], 5).inputs["variants"]["fit"]["cluster"]
    b = drive(tmp_path / "b", CONFIG["tiny"], 6).inputs["variants"]["fit"]["cluster"]
    assert [w.name for w in a.workloads] == [
        "sched-0/pod-default", "sched-0/pod-with-pod-affinity", "sched-0/pod-with-pod-anti-affinity",
        "sched-0/pod-with-preferred-pod-affinity", "sched-0/pod-with-preferred-pod-anti-affinity",
        "sched-1/pod-default",
    ]
    assert [w.name for w in a.workloads] == [w.name for w in b.workloads]
    assert [n.name for n in a.nodes] != [n.name for n in b.nodes]
    assert sorted(n.name for n in a.nodes) == sorted(n.name for n in b.nodes)
    assert {(n.cpu_m, n.mem_bytes) for n in a.nodes} != {(4000, 32 << 30)}  # allocatable below capacity
    ra, rb = R.Reference(a), R.Reference(b)
    ra.free_run()
    rb.free_run()
    assert ra.order() != rb.order()
    blue = a.workloads[1]
    assert blue.affinity[0].namespaces == ("sched-1", "sched-0") and blue.affinity[0].topology_key == ZONE


def test_the_full_size_is_the_sources_own():
    src, sizes = CONFIG["source_sizes"], CONFIG["sizes"]
    assert CONFIG["reduced"] == [] and "MixedSchedulingBasePod/5000Nodes" in CONFIG["source"]
    assert (sizes["nodes"], sizes["init_pods"], sizes["measure_pods"]) == (5000, 2000, 1000)
    assert (src["nodes"], src["init_pods_per_template"], src["init_templates"], src["measure_pods"]) == (5000, 2000, 5, 1000)
    for key in ("node_cpu", "node_memory_gi", "node_pods", "pod_cpu_m", "pod_memory_mi"):
        assert sizes[key] == src[key] == CONFIG["tiny"][key]


# ---------------------------------------------------------------------------
# the reference's rules, worked by hand
# ---------------------------------------------------------------------------


def nodes(zones):
    """One node per entry, in a zone or (None) carrying no zone label."""
    out = []
    for i, z in enumerate(zones):
        labels = {HOSTNAME: f"n{i}"}
        if z is not None:
            labels[ZONE] = z
        out.append(NodeSpec(name=f"n{i}", cpu_m=4000, mem_bytes=32 << 30, pods=110, labels=labels))
    return out


def pods(name, labels, namespace="ns", replicas=4, **terms):
    return R.PodWorkload(name=name, replicas=replicas, cpu_m=100, mem_bytes=500 << 20, labels=labels,
                         namespace=namespace, **terms)


def reference(node_zones, workloads):
    return R.Reference(Cluster(nodes=nodes(node_zones), bound=[], workloads=workloads, new_node=None))


def feasible(ref, wi):
    ref._enter(wi)
    return ref.step()[0].tolist()


def place(ref, wi, node):
    ref._enter(wi)
    assert ref.step()[0][node]
    ref.bind(node)


def interpod_score(ref, wi, mask=None):
    ref._enter(wi)
    n = ref.n
    return ref._interpod_score(np.ones(n, bool) if mask is None else np.array(mask, bool)).tolist()


BLUE = {"color": "blue"}


def test_bootstrap_the_first_pod_of_a_group_that_wants_its_own_kind():
    zone_blue = [R.term(BLUE, ZONE)]
    ref = reference(["a", "a", "b", None], [
        pods("blue", BLUE, affinity=zone_blue),
        pods("wants-blue", {"color": "grey"}, affinity=zone_blue),
    ])
    # nothing matches anywhere and the pod matches its own term: every node
    # that carries the topology label takes it, the unlabelled one does not
    assert feasible(ref, 0) == [True, True, True, False]
    # a pod that does not match its own term waits for a blue pod
    assert feasible(ref, 1) == [False, False, False, False]
    place(ref, 0, 2)
    # one blue pod in zone b: the rule is off, only zone b holds a match
    assert feasible(ref, 0) == [False, False, True, False]
    assert feasible(ref, 1) == [False, False, True, False]


def test_bootstrap_needs_every_term_matched_by_one_pod():
    both = [R.term(BLUE, ZONE), R.term({"tier": "db"}, HOSTNAME)]
    ref = reference(["a", "a"], [
        pods("blue-only", BLUE),
        pods("wants-both", {"color": "blue", "tier": "db"}, affinity=both),
    ])
    place(ref, 0, 0)  # matches the first term alone: no pod matches all the terms
    assert feasible(ref, 1) == [True, True]  # so the bootstrap still holds
    place(ref, 1, 1)
    assert feasible(ref, 1) == [False, True]  # now n1 holds a pod matching both, in its zone and on its host


def test_existing_pods_anti_affinity_keeps_the_incoming_pod_out():
    green = {"color": "green"}
    ref = reference(["a", "a", "a"], [
        pods("green", green, anti_affinity=[R.term(green, HOSTNAME)]),
        pods("plain-green", green),  # carries the label and no term of its own
        pods("plain-red", {"color": "red"}),
    ])
    place(ref, 0, 1)
    assert feasible(ref, 0) == [True, False, True]  # its own term
    assert feasible(ref, 1) == [True, False, True]  # the placed pod's term, held against it
    assert feasible(ref, 2) == [True, True, True]
    place(ref, 1, 0)
    # the plain green pod on n0 carries no term, but the green workload's own term sees it
    assert feasible(ref, 0) == [False, False, True]
    assert feasible(ref, 1) == [True, False, True]


def test_a_required_affinity_term_scores_for_the_pods_it_selects_at_weight_one():
    ref = reference(["a", "a", "a"], [
        pods("blue", BLUE, affinity=[R.term(BLUE, HOSTNAME)]),
        pods("plain-blue", BLUE),
        pods("yellow", {"color": "yellow"}, preferred_anti_affinity=[R.term({"color": "yellow"}, HOSTNAME, weight=3)]),
        pods("plain-yellow", {"color": "yellow"}),
    ])
    place(ref, 0, 1)
    # the placed pod's required term selects a blue pod: +1 on its host
    assert interpod_score(ref, 1) == [0.0, 100.0, 0.0]
    assert interpod_score(ref, 3) == [0.0, 0.0, 0.0]  # and nothing for a pod it does not select
    place(ref, 2, 0)
    # the placed yellow pod's preferred anti-affinity, weight 3, against a yellow pod: -3 on n0
    assert interpod_score(ref, 3) == [0.0, 100.0, 100.0]
    # an incoming yellow pod of the same workload counts it twice, its own term and the placed pod's: -6
    assert interpod_score(ref, 2) == [0.0, 100.0, 100.0]
    place(ref, 2, 1)
    place(ref, 2, 1)
    # n0 holds one yellow pod (-6), n1 two (-12), n2 none: 100 * (raw + 12) / 12
    assert interpod_score(ref, 2) == [50.0, 0.0, 100.0]


def test_namespaces_that_match_and_that_do_not():
    green = {"color": "green"}
    ref = reference(["a", "a"], [
        pods("listed", green, namespace="one", anti_affinity=[R.term(green, HOSTNAME, namespaces=("two", "three"))]),
        pods("own", green, namespace="one", anti_affinity=[R.term(green, HOSTNAME)]),
        pods("in-two", green, namespace="two"),
        pods("in-four", green, namespace="four"),
    ])
    place(ref, 0, 0)
    # the listed namespaces leave out the pod's own: a second pod of it may share the host
    assert feasible(ref, 0) == [True, True]
    assert feasible(ref, 2) == [False, True]  # a green pod of namespace two is held off
    assert feasible(ref, 3) == [True, True]  # one of namespace four is not
    # a term without a list means the namespace of the pod that carries it
    assert feasible(ref, 1) == [False, True]  # sees the green pod of namespace one on n0
    place(ref, 1, 1)
    assert feasible(ref, 1) == [False, False]
    assert feasible(ref, 0) == [True, False]  # the pod on n1 holds its term against every green pod of namespace one
    assert feasible(ref, 2) == [False, True]


def test_normalisation_with_one_feasible_node_is_seeded_with_nought():
    red = {"color": "red"}
    ref = reference(["a", "a", "a"], [
        pods("red", red, preferred_affinity=[R.term(red, HOSTNAME, weight=2)]),
        pods("pink", {"color": "pink"}, preferred_anti_affinity=[R.term(red, HOSTNAME, weight=2)]),
    ])
    assert interpod_score(ref, 0, [False, True, False]) == [0.0, 0.0, 0.0]  # every sum 0: no range
    place(ref, 0, 1)
    # one feasible node with a positive sum (its own term and the placed pod's, 2 + 2): the
    # least is the seed 0, so it reads 100
    assert interpod_score(ref, 0, [False, True, False])[1] == 100.0
    # one feasible node with a negative sum (-2): the most is the seed 0, so it reads 0
    assert interpod_score(ref, 1, [False, True, False])[1] == 0.0
    # the sum of a node that is not feasible sets no range
    assert interpod_score(ref, 0, [True, False, True]) == [0.0, 0.0, 0.0]
    assert interpod_score(ref, 0) == [0.0, 100.0, 0.0]


def test_replay_counts_a_pod_that_breaks_a_required_term_as_infeasible():
    green = {"color": "green"}
    cluster = Cluster(nodes=nodes(["a", "a", "a"]), bound=[], new_node=None,
                      workloads=[pods("green", green, replicas=2, anti_affinity=[R.term(green, HOSTNAME)])])
    ref = R.Reference(cluster)
    ref.free_run()
    assert ref.order() == {"green": ["n0", "n1"]}
    assert R.replay(cluster, ref.order(), {}) == {
        "misplaced_pods": 0, "worst_score_gap": 0.0, "infeasible_pods": 0, "unscheduled_diff": 0, "answer_diff": 0}
    assert R.replay(cluster, {"green": ["n0", "n0"]}, {})["infeasible_pods"] == 1


def test_replay_judges_each_choice_in_the_state_the_program_made_it():
    """One pod on another node than the reference's best is one misplaced pod
    and the score it gave up, whatever the pods after it do: the reference
    binds where the program did and goes on from there."""
    red = {"color": "red"}
    cluster = Cluster(nodes=nodes(["a", "a", "a"]), bound=[], new_node=None,
                      workloads=[pods("red", red, replicas=4, preferred_affinity=[R.term(red, HOSTNAME, weight=1)])])
    ref = R.Reference(cluster)
    ref.free_run()
    own = ref.order()["red"]
    assert R.replay(cluster, {"red": own}, {})["misplaced_pods"] == 0
    # the first pod goes to the last node instead; the red pods after it follow it there
    other = ["n2"] + ["n2" if n == own[0] else n for n in own[1:]]
    got = R.replay(cluster, {"red": other}, {})
    assert got["misplaced_pods"] == 1 and got["infeasible_pods"] == 0 and got["answer_diff"] == 0
    # an answer on an unknown node, and one that is short, are both counted
    assert R.replay(cluster, {"red": own[:3] + ["n9"]}, {})["answer_diff"] == 2
    assert R.replay(cluster, {"red": own[:3]}, {})["answer_diff"] == 1
    assert R.replay(cluster, {"red": own[:3]}, {"red": 1})["unscheduled_diff"] == 1


def test_the_low_precision_control_loses_count_past_256():
    """bfloat16 holds integers to 256: a zone count past it is no longer
    exact, which is where the control departs from float32."""
    from benchmarks.reference.kube_reference import round_bf16

    assert round_bf16(np.float32(256.0)) == 256.0 and round_bf16(np.float32(257.0)) == 256.0
    with pytest.raises(ValueError):
        R.Reference(Cluster(nodes=nodes(["a"]), bound=[], workloads=[], new_node=None), "float16")


# ---------------------------------------------------------------------------
# what the path reports: encode.interpod, features, interpod_terms, the counter
# ---------------------------------------------------------------------------


def traced_plan(driver):
    from opensim_tpu.planner.apply import Applier, Options

    opts = Options(simon_config=driver.simon_config, output_file=os.path.join(driver.ctx.scratch, "report.txt"),
                   report_pods=True, max_new_nodes=driver.inputs["max_new_nodes"])
    tr = tracing.start_trace("apply", force=True)
    with tracing.trace_scope(tr):
        assert Applier(opts).run() == 0
    tr.finish()
    return tr


def find(tr, name):
    return [sp for sp in tr.walk() if sp.name == name]


@pytest.mark.parametrize("engine", ["xla", "megakernel"])
def test_the_rung_that_ran_names_its_feature_set_and_the_term_rows(tmp_path, monkeypatch, engine):
    env, _named = ENGINES[engine]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    RECORDER.reset()
    tr = traced_plan(drive(tmp_path, CONFIG["tiny"], 7))
    (rung,) = find(tr, "engine." + engine)
    assert rung.attrs["features"] == FEATURES
    # green's anti-affinity row, and the scoring rows of blue (hard weight), red and yellow
    assert rung.attrs["interpod_terms"] == 4
    (tables,) = find(tr, "encode.interpod")
    (encode,) = find(tr, "encode")
    assert tables in encode.children and tables.attrs["terms"] == 4 and tables.attrs["templates"] == 6
    assert encode.start <= tables.start and tables.end <= encode.end
    if engine == "megakernel":
        from opensim_tpu.engine import fastpath

        (inputs,) = find(tr, "mk.inputs")
        assert 0 < inputs.attrs["vmem_estimate_bytes"] <= fastpath._VMEM_BUDGET
    line = f'simon_engine_features_total{{engine="{engine}",features="{FEATURES}"}} 1'
    assert line in RECORDER.render_lines()
    RECORDER.reset()


def traced_simulate(*deployments):
    from opensim_tpu.engine.simulator import AppResource, simulate
    from opensim_tpu.models import ResourceTypes, fixtures as fx

    rt = ResourceTypes()
    for i in range(3):
        rt.nodes.append(fx.make_fake_node(f"n{i}", "16", "64Gi", "110"))
    app = ResourceTypes()
    app.deployments.extend(deployments)
    RECORDER.reset()
    tr = tracing.start_trace("test", force=True)
    with tracing.trace_scope(tr):
        result = simulate(rt, [AppResource("web", app)])
    tr.finish()
    return tr, result


def test_a_stream_without_terms_reports_none_of_them(monkeypatch):
    from opensim_tpu.models import fixtures as fx
    tr, _ = traced_simulate(fx.make_fake_deployment("web", 4, "100m", "128Mi"))
    (rung,) = find(tr, "engine.xla")
    assert rung.attrs["interpod_terms"] == 0 and "interpod" not in rung.attrs["features"]
    assert any(l.startswith('simon_engine_features_total{engine="xla"') for l in RECORDER.render_lines())
    RECORDER.reset()


def test_terms_and_host_ports_in_one_stream_are_encoded_side_by_side(monkeypatch):
    """The term tables are built apart from the template loop that fills the
    host-port table; a stream that carries both still encodes both."""
    from opensim_tpu.models import fixtures as fx
    green = {"color": "green"}
    anti = {"podAntiAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": [
        {"labelSelector": {"matchLabels": green}, "topologyKey": HOSTNAME}]}}
    tr, result = traced_simulate(
        fx.make_fake_deployment("ported", 2, "100m", "128Mi", fx.with_host_ports([8080])),
        fx.make_fake_deployment("green", 3, "100m", "128Mi", fx.with_pod_labels(green), fx.with_affinity(anti)),
    )
    (rung,) = find(tr, "engine.xla")
    assert rung.attrs["interpod_terms"] == 1 and {"ports", "interpod"} <= set(rung.attrs["features"].split("+"))
    assert not result.unscheduled_pods
    per_node = [sum("green" in p.metadata.name for p in s.pods) for s in result.node_status]
    assert sorted(per_node) == [1, 1, 1]
    RECORDER.reset()


# ---------------------------------------------------------------------------
# the megakernel against the XLA scan, step by step
# ---------------------------------------------------------------------------


def test_the_megakernel_chooses_as_the_xla_scan_does_at_every_step(tmp_path, monkeypatch):
    """The weighted sum is taken in `kernels.score_parts`' order in both
    engines. Summed in another order (share before spread, inter-pod last, as
    the megakernel did before ISSUE 29) the scores leave the XLA scan's by an
    ulp and, on this stream at this size and seed, 66 of the 4,400 steps
    choose another node; the counts per (workload, node) come out the same,
    so no replay of an answer shows it."""
    from opensim_tpu.engine import fastpath, simulator
    from opensim_tpu.planner.apply import Applier, Options

    monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")
    driver = drive(tmp_path, dict(CONFIG["tiny"], nodes=2000, init_pods=800, measure_pods=400), 12)
    seen = {}

    class Captured(Exception):
        pass

    def capture(prep, tmpl_ids, pod_valid, forced, **_kw):
        seen.update(prep=prep, stream=(np.asarray(tmpl_ids), np.asarray(pod_valid), np.asarray(forced)))
        raise Captured

    schedule = fastpath.schedule
    monkeypatch.setattr(fastpath, "schedule", capture)
    with pytest.raises(Captured):
        Applier(Options(simon_config=driver.simon_config, output_file=str(tmp_path / "report.txt"),
                        report_pods=True, max_new_nodes=driver.inputs["max_new_nodes"])).run()
    prep, stream = seen["prep"], seen["stream"]
    assert len(stream[0]) == 4400
    kernel = schedule(prep, *stream, interpret=True)[0]
    scan = simulator._xla_scan(prep.ec, prep.st0, *stream, None, features=prep.features)
    assert (kernel >= 0).all()
    assert np.array_equal(kernel, np.asarray(scan.chosen)[:4400])


# ---------------------------------------------------------------------------
# a float32 quotient that is IEEE's on any backend
# ---------------------------------------------------------------------------


def _operands():
    rng = np.random.default_rng(11)
    n = 20000
    return {
        "uniform": (rng.uniform(0, 4000, n), rng.uniform(1, 4000, n)),
        # the shapes the scores divide: milli-CPU over allocatable, bytes over bytes, counts over counts
        "cpu": (rng.integers(0, 4000, n) * 100.0, rng.choice(np.arange(3600, 4001, 50), n)),
        "memory": (rng.integers(0, 60, n) * 524288000.0, (32768 - rng.integers(0, 33, n) * 64) * 1048576.0),
        "counts": (rng.integers(-2000, 2000, n) * 100.0, rng.integers(1, 4000, n)),
    }


@pytest.mark.parametrize("kind", ["uniform", "cpu", "memory", "counts"])
def test_div32_is_the_ieee_quotient_and_repairs_one_that_is_two_ulp_off(kind):
    """On the CPU the hardware quotient is IEEE's and `div32` leaves it as it
    is; a quotient up to 2 ulp off, which is what a TPU gives in a third of
    cases (PERF.md, PR 29), comes back to it."""
    import jax

    from opensim_tpu.ops import kernels

    a, b = (x.astype(np.float32) for x in _operands()[kind])
    want = a / b
    assert np.array_equal(np.asarray(jax.jit(kernels.div32)(a, b)), want)
    corrected = jax.jit(kernels._corrected)
    for ulps in (-2, -1, 1, 2):
        off = want
        for _ in range(abs(ulps)):
            off = np.nextafter(off, np.float32(np.inf if ulps > 0 else -np.inf))
        moved = off != want
        assert moved.any()
        assert np.array_equal(np.asarray(corrected(a, b, off)), want), ulps


def test_div32_inside_the_interpreted_kernel_block_is_the_ieee_quotient():
    from opensim_tpu.ops import pallas_scan

    a, b = (x.astype(np.float32).reshape(4, 5000)[:, :640] for x in _operands()["cpu"])
    rows = [(a[j:j + 1], b[j:j + 1]) for j in range(3)] + [(a[3:4], np.float32(37.0))]
    got = pallas_scan._div_rows([(np.asarray(n), d) for n, d in rows], (1, 640))
    for (n, d), q in zip(rows, got):
        assert np.array_equal(np.asarray(q), n / d)
    # nine quotients take two blocks and come back in order
    nine = [(a[j % 4:j % 4 + 1] + np.float32(j), b[(j + 1) % 4:(j + 1) % 4 + 1]) for j in range(9)]
    for (n, d), q in zip(nine, pallas_scan._div_rows(nine, (1, 640))):
        assert np.array_equal(np.asarray(q), n / d)
    # ISSUE 38: in a packed step each quotient is [8, N], a block of its own;
    # a denominator may be a column, one a scenario
    a8, b8 = np.concatenate([a, a + 1]), np.concatenate([b, b[::-1]])
    col = b8[:, :1] + np.float32(3)
    for (n, d), q in zip([(a8, b8), (a8, col), (a[:1], col)], pallas_scan._div_rows([(a8, b8), (a8, col), (a[:1], col)], (8, 640))):
        assert np.array_equal(np.asarray(q), np.broadcast_to(n / d, (8, 640)))
