"""A masked `simulate` (the planner's prep reuse: one Prepared over the
cluster plus every candidate node, masked down to the count the capacity
search settled on) is a megakernel schedule of one scenario (ISSUE 30). The
kernel runs in the Pallas interpreter here; `OPENSIM_TEST_BACKEND=tpu`
compiles it. Tier-1, unlike the parity matrix of tests/test_fastpath.py."""

import collections
import copy
import os

import numpy as np
import pytest
import yaml

from opensim_tpu.engine import fastpath, reasons, simulator
from opensim_tpu.engine.simulator import AppResource, prepare, simulate
from opensim_tpu.models import ResourceTypes, expand, fixtures as fx
from opensim_tpu.obs import trace as tracing
from opensim_tpu.obs.metrics import RECORDER

_INTERPRET = os.environ.get("OPENSIM_TEST_BACKEND") != "tpu"
ZONE = "topology.kubernetes.io/zone"
N_CANDIDATES = 6


@pytest.fixture(autouse=True)
def _kernel_on(monkeypatch):
    monkeypatch.delenv("OPENSIM_DISABLE_FASTPATH", raising=False)
    if _INTERPRET:
        monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")


def _kernel_off(monkeypatch):
    monkeypatch.delenv("OPENSIM_FASTPATH", raising=False)
    monkeypatch.setenv("OPENSIM_DISABLE_FASTPATH", "1")


def _nodes(template_ssd=True):
    """Six nodes in two zones, four of them the ssd pool; the template opens
    a third zone, so the spread weights differ with the mask."""
    nodes = [
        fx.make_fake_node(
            f"n{i}", "8", "16Gi", "110",
            fx.with_labels({ZONE: f"z{i % 2}", **({"disk": "ssd"} if i < 4 else {})}),
        )
        for i in range(6)
    ]
    labels = {ZONE: "z2", **({"disk": "ssd"} if template_ssd else {})}
    template = fx.make_fake_node("tmpl", "8", "16Gi", "110", fx.with_labels(labels))
    return nodes, template


def _apps(web=24, spread=12, db=8):
    """Two apps, scheduled in this order: `base` (plain pods and a soft zone
    spread), then `db`, pinned to the ssd pool."""
    soft_zone_spread = fx.with_topology_spread([{
        "maxSkew": 1, "topologyKey": ZONE, "whenUnsatisfiable": "ScheduleAnyway",
        "labelSelector": {"matchLabels": {"app": "spread"}},
    }])
    base, pool = ResourceTypes(), ResourceTypes()
    base.deployments.append(fx.make_fake_deployment("web", web, "500m", "1Gi"))
    base.deployments.append(fx.make_fake_deployment("spread", spread, "250m", "512Mi", soft_zone_spread))
    pool.deployments.append(fx.make_fake_deployment("db", db, "2", "2Gi", fx.with_node_selector({"disk": "ssd"})))
    return [AppResource("base", base), AppResource("db", pool)]


class Plan:
    """The planner's inputs after a capacity search: the cluster, the cluster
    with every candidate, and what `simulate(prep=, node_valid=)` wants for a
    count of `k` new nodes."""

    def __init__(self, template_ssd=True, **sizes):
        nodes, self.template = _nodes(template_ssd)
        self.cluster = ResourceTypes()
        self.cluster.nodes = nodes
        self.cluster.daemon_sets.append(fx.make_fake_daemon_set("logger", "100m", "64Mi"))
        self.apps = _apps(**sizes)
        self.candidates = expand.new_fake_nodes(self.template, N_CANDIDATES)
        self.full = copy.copy(self.cluster)
        self.full.nodes = nodes + self.candidates

    def sub(self, k):
        sub = copy.copy(self.cluster)
        sub.nodes = self.cluster.nodes + self.candidates[:k]
        return sub

    def masked(self, k, **asked):
        """A masked simulate over a fresh full Prepared (decode binds its
        pods); returns the result and each stream pod's node, in stream
        order, which two Prepareds of the same inputs share."""
        prep = self.prep = prepare(self.full, self.apps)
        mask = np.zeros(np.asarray(prep.ec_np.node_valid).shape[0], bool)
        mask[: 6 + k] = True
        res = simulate(self.sub(k), self.apps, prep=prep, node_valid=mask, **asked)
        return res, mask, [p.spec.node_name or None for p in prep.ordered]

    def plain(self, **asked):
        """The same over the cluster alone, unmasked: the planner's first pass."""
        prep = self.prep = prepare(self.cluster, self.apps)
        res = simulate(self.cluster, self.apps, prep=prep, **asked)
        return res, [p.spec.node_name or None for p in prep.ordered]


def _per_node_counts(res):
    out = {}
    for ns in res.node_status:
        # a Deployment's pods by its `app` label (the ReplicaSet's name is numbered anew in every expansion)
        c = collections.Counter(
            p.metadata.labels.get("app") or p.metadata.annotations["simon/workload-name"] for p in ns.pods
        )
        out[ns.node.metadata.name] = dict(c)
    return out


def _reasons(res):
    return sorted(u.reason for u in res.unscheduled_pods)


@pytest.mark.parametrize("k", [0, 2, N_CANDIDATES])
def test_a_masked_simulate_runs_on_the_megakernel_and_places_as_the_scan_does(monkeypatch, k):
    plan = Plan()
    entered = []
    schedule = fastpath.schedule

    def spy(*args, **kwargs):
        entered.append(kwargs.get("node_valid"))
        return schedule(*args, **kwargs)

    monkeypatch.setattr(fastpath, "schedule", spy)
    res, mask, nodes_of = plan.masked(k)
    assert len(entered) == 1 and np.array_equal(entered[0], mask)
    assert res.engine.name == "megakernel", res.engine.describe()
    assert "megakernel" not in res.engine.skipped
    assert not res.unscheduled_pods
    if k:  # the new zone is in use, so the spread counted it
        assert any(n in nodes_of for n in (c.metadata.name for c in plan.candidates[:k]))

    _kernel_off(monkeypatch)
    scan, _, scan_nodes_of = plan.masked(k)
    assert scan.engine.name == "xla" and len(entered) == 1
    assert nodes_of == scan_nodes_of
    fresh = simulate(plan.sub(k), plan.apps)
    assert _per_node_counts(res) == _per_node_counts(fresh)
    assert [ns.node.metadata.name for ns in res.node_status] == [n.metadata.name for n in plan.sub(k).nodes]


@pytest.mark.parametrize(
    "sizes,engine",
    [
        # the ssd pool fills last, so nothing binds after the first failure; the candidates are outside
        # the pool, and a reason counted over the unmasked nodes would name eight of them, not three
        (dict(db=20, template_ssd=False), "megakernel"),
        # web overfills every node, spread still binds in what is left, db fails again: the scan re-runs the stream
        (dict(web=120, spread=4, db=2), "xla"),
    ],
    ids=["tail", "midstream"],
)
def test_reasons_under_a_mask_too_small_are_the_scans_string_for_string(monkeypatch, sizes, engine):
    plan = Plan(**sizes)
    res, _, nodes_of = plan.masked(1)
    assert res.engine.name == engine, res.engine.describe()
    assert res.unscheduled_pods
    # seven nodes are valid, and no reason counts one the mask excludes
    assert all(r.startswith("0/7 nodes are available") for r in _reasons(res)), _reasons(res)
    if engine == "megakernel":
        assert "3 node(s) didn't match" in _reasons(res)[0], _reasons(res)[0]

    _kernel_off(monkeypatch)
    scan, _, scan_nodes_of = plan.masked(1)
    assert scan.engine.name == "xla"
    assert _reasons(res) == _reasons(scan)
    assert nodes_of == scan_nodes_of
    assert _reasons(res) == _reasons(simulate(plan.sub(1), plan.apps))


def test_a_masked_schedule_is_its_row_of_a_sweep_under_the_same_masks():
    """What the planner rests on: the count it settles on was found by
    `fastpath.sweep` under the mask that the final pass then schedules."""
    plan = Plan(db=14)
    prep = prepare(plan.full, plan.apps)
    N, P = int(np.asarray(prep.ec_np.node_valid).shape[0]), len(prep.ordered)
    ks = [0, 1, 3, N_CANDIDATES]
    masks = np.zeros((len(ks), N), bool)
    pod_valid = np.ones((len(ks), P), bool)
    ds_target = np.asarray(prep.ds_target)
    for s, k in enumerate(ks):
        masks[s, : 6 + k] = True
        pod_valid[s] = (ds_target < 0) | masks[s][np.maximum(ds_target, 0)]
    forced = np.broadcast_to(prep.forced, (len(ks), P))
    unscheduled, used, chosen, _vg = fastpath.sweep(prep, masks, pod_valid, forced, interpret=_INTERPRET)
    assert unscheduled[0] > 0 and unscheduled[-1] == 0  # both sides of the count
    for s in range(len(ks)):
        got_chosen, got_used, static_fail, *_ = fastpath.schedule(
            prep, prep.tmpl_ids, pod_valid[s], prep.forced, node_valid=masks[s], interpret=_INTERPRET
        )
        assert np.array_equal(got_chosen, chosen[s]), ks[s]
        assert np.array_equal(got_used, used[s]), ks[s]
        assert (got_chosen < 6 + ks[s]).all()
        # db's selector fails on the two nodes outside the pool, a DaemonSet pod's pin on every
        # valid node but its own: no node outside the mask is counted
        assert static_fail[prep.tmpl_ids[-1]].sum() == 2
        assert static_fail[prep.tmpl_ids[0]].sum() == 6 + ks[s] - 1


def _write_plan(tmp_path, plan, new_node=True):
    dirs = {name: tmp_path / name for name in ("cluster", "app", "newnode")}
    for d in dirs.values():
        d.mkdir()
    for n in plan.cluster.nodes:
        (dirs["cluster"] / f"{n.metadata.name}.yaml").write_text(yaml.safe_dump(n.raw))
    (dirs["cluster"] / "logger.yaml").write_text(yaml.safe_dump(plan.cluster.daemon_sets[0].raw))
    for app in plan.apps:
        (dirs["app"] / app.name).mkdir()
        for i, d in enumerate(app.resources.deployments):  # read back in name order: keep the stream's
            (dirs["app"] / app.name / f"{i}-{d.metadata.name}.yaml").write_text(yaml.safe_dump(d.raw))
    (dirs["newnode"] / "node.yaml").write_text(yaml.safe_dump(plan.template.raw))
    cfg = tmp_path / "config.yaml"
    cfg.write_text(yaml.safe_dump({
        "apiVersion": "simon/v1alpha1", "kind": "Config", "metadata": {"name": "short"},
        "spec": {
            "cluster": {"customConfig": str(dirs["cluster"])},
            "appList": [{"name": app.name, "path": str(dirs["app"] / app.name)} for app in plan.apps],
            **({"newNode": str(dirs["newnode"])} if new_node else {}),
        },
    }))
    return str(cfg)


def _pod_table(text):
    """The report's lines with the generated names taken out: a new node's
    random suffix, a pod's counters."""
    import re

    return [re.sub(r"simon-[0-9a-z]{8}|(-[0-9a-f]{10})+(?=\s)", "*", line) for line in text.splitlines()]


def test_a_short_plan_ends_in_one_masked_megakernel_pass_and_reports_the_same(monkeypatch, tmp_path):
    """`plan-short` in small: pods fail mid-stream, and with a `newNode`
    template the first pass is asked for no reasons, so the kernel's result is
    kept (ISSUE 36); the count is searched; the final pass over the masked
    Prepared is the kernel's, and no scan runs at all."""
    import re

    from opensim_tpu.planner.apply import Applier, Options

    config = _write_plan(tmp_path, Plan(web=120, spread=4, db=2))

    def run(name):
        out = tmp_path / name
        tr = tracing.start_trace("apply", force=True)
        with tracing.trace_scope(tr):
            rc = Applier(Options(simon_config=config, output_file=str(out), report_pods=True,
                                 max_new_nodes=N_CANDIDATES)).run()
        tr.finish()
        assert rc == 0
        rungs = [(sp.name, sp.attrs["masked"]) for sp in tr.walk() if sp.name in ("engine.megakernel", "engine.xla")]
        text = out.read_text()
        assert reasons.not_attributed() not in text  # the first pass's failed pods are nobody's to print
        attributions = [sp.attrs["attribution"] for sp in tr.walk() if sp.name == "engine.megakernel"]
        return rungs, int(re.search(r"\(added (\d+) new node\(s\)\)", text).group(1)), _pod_table(text), attributions

    rungs, added, table, attributions = run("on.txt")
    assert rungs == [("engine.megakernel", False), ("engine.megakernel", True)]
    assert attributions == ["not_asked", "none"]
    assert 0 < added < N_CANDIDATES
    assert "Scheduling engine: megakernel" in table

    _kernel_off(monkeypatch)
    rungs, added_off, table_off, _ = run("off.txt")
    assert rungs == [("engine.xla", False), ("engine.xla", True)]
    assert added_off == added
    assert [l for l in table if not l.startswith("Scheduling engine:")] == [
        l for l in table_off if not l.startswith("Scheduling engine:")
    ]


def test_masked_passes_are_counted_by_the_engine_that_answered(monkeypatch):
    RECORDER.reset()
    plan = Plan()
    try:
        plan.masked(1)
        simulate(plan.cluster, plan.apps)  # no mask: not counted
        _kernel_off(monkeypatch)
        plan.masked(2)
        plan.masked(3)
        lines = RECORDER.render_lines()
        assert "# TYPE simon_masked_pass_total counter" in lines
        assert 'simon_masked_pass_total{engine="megakernel"} 1' in lines
        assert 'simon_masked_pass_total{engine="xla"} 2' in lines
    finally:
        RECORDER.reset()


# ---------------------------------------------------------------------------
# ISSUE 36: failure reasons are part of what a caller asks
# ---------------------------------------------------------------------------

MIDSTREAM = dict(web=120, spread=4, db=2)  # web overfills every node, spread still binds, db fails again
TAIL = dict(db=20, template_ssd=False)  # the ssd pool fills last: nothing binds after the first failure


def _unscheduled(res, plan):
    """The unscheduled pods in the result's order, as positions in the stream
    of the plan's last Prepared (names are numbered anew in every expansion)."""
    at = {id(p): i for i, p in enumerate(plan.prep.ordered)}
    return [(at[id(u.pod)], u.pod.metadata.labels.get("app")) for u in res.unscheduled_pods]


@pytest.mark.parametrize("masked", [False, True], ids=["first_pass", "masked"])
def test_a_stream_asked_for_no_reasons_keeps_the_kernels_result_and_places_the_same(masked):
    plan = Plan(**MIDSTREAM)
    run = (lambda **kw: plan.masked(1, **kw)[::2]) if masked else plan.plain
    asked, asked_nodes = run()
    asked_unscheduled = _unscheduled(asked, plan)
    quiet, quiet_nodes = run(reasons=False)
    # asked: today's path, the kernel's result thrown away for the scan's exact strings
    assert asked.engine.name == "xla" and asked.engine.attribution == "rescan"
    assert "mid-stream scheduling failures" in asked.engine.skipped["megakernel"]
    n = 7 if masked else 6
    assert asked.unscheduled_pods and all(r.startswith(f"0/{n} nodes are available: ") for r in _reasons(asked))
    # not asked: the kernel's own result, nothing skipped, pod for pod the same
    assert quiet.engine.name == "megakernel" and quiet.engine.skipped == {}, quiet.engine.describe()
    assert quiet.engine.attribution == "not_asked"
    assert quiet_nodes == asked_nodes
    assert _unscheduled(quiet, plan) == asked_unscheduled
    assert [ns.node.metadata.name for ns in quiet.node_status] == [ns.node.metadata.name for ns in asked.node_status]
    assert _per_node_counts(quiet) == _per_node_counts(asked)
    assert [ns.node.metadata.annotations for ns in quiet.node_status] == [ns.node.metadata.annotations for ns in asked.node_status]
    # and no reason that looks like one: zeros are never decoded into a string
    assert {u.reason for u in quiet.unscheduled_pods} == {reasons.not_attributed()}
    assert "nodes are available" not in reasons.not_attributed()


def test_reasons_are_asked_unless_the_caller_says_otherwise(monkeypatch):
    """The default, at every layer: `Ask`, `simulate` and the `Ask` the ladder
    builds from it; and a run with preemption asks whatever it is told."""
    from opensim_tpu.engine import select

    assert select.Ask().reasons is True
    asks = []
    ladder = select.ladder
    monkeypatch.setattr(select, "ladder", lambda prep, ask, pol=None: asks.append(ask) or ladder(prep, ask, pol))
    plan = Plan(**MIDSTREAM)
    res, _ = plan.plain()
    assert [a.reasons for a in asks] == [True] and res.engine.name == "xla"
    res, _ = plan.plain(reasons=False)
    assert [a.reasons for a in asks] == [True, False] and res.engine.name == "megakernel"
    # preemption reads the carry a failed pod left: it always asks
    res = simulate(plan.cluster, plan.apps, enable_preemption=True, reasons=False)
    assert asks[-1].reasons is True and res.engine.attribution == "rescan"
    assert reasons.not_attributed() not in _reasons(res)


def _apply(config, out):
    from opensim_tpu.planner.apply import Applier, Options

    tr = tracing.start_trace("apply", force=True)
    with tracing.trace_scope(tr):
        rc = Applier(Options(simon_config=config, output_file=str(out), max_new_nodes=N_CANDIDATES)).run()
    tr.finish()
    return tr, rc


def test_a_plan_without_a_template_prints_the_scans_reasons(monkeypatch, tmp_path):
    import re

    config = _write_plan(tmp_path, Plan(**MIDSTREAM), new_node=False)
    tr, rc = _apply(config, tmp_path / "on.txt")
    assert rc == 1
    rungs = [sp.name for sp in tr.walk() if sp.name in ("engine.megakernel", "engine.xla")]
    assert rungs == ["engine.megakernel", "engine.xla"]
    assert [sp.attrs["attribution"] for sp in tr.walk() if sp.name == "engine.megakernel"] == ["rescan"]
    lines = (tmp_path / "on.txt").read_text().splitlines()
    assert lines[0] == "Simulation failed: pods are unschedulable and no newNode is configured:"
    assert len(lines) > 1 and all(": 0/6 nodes are available: " in l for l in lines[1:]), lines[:3]
    _kernel_off(monkeypatch)
    _, rc = _apply(config, tmp_path / "off.txt")
    assert rc == 1
    numbered = lambda text: [re.sub(r"(-[0-9a-f]{10})+", "*", line) for line in text.splitlines()]
    assert numbered((tmp_path / "off.txt").read_text()) == numbered("\n".join(lines))


def test_the_prompt_loop_asks_for_the_reasons_it_shows(tmp_path):
    import io

    from opensim_tpu.planner.apply import Applier, Options

    config = _write_plan(tmp_path, Plan(**MIDSTREAM))  # a template is there, and the prompt still prints why
    applier = Applier(Options(simon_config=config, interactive=True))
    applier.out = io.StringIO()
    script = iter(["show", "exit"])
    applier.input_fn = lambda: next(script)
    tr = tracing.start_trace("apply", force=True)
    with tracing.trace_scope(tr):
        applier.run()
    tr.finish()
    assert [sp.attrs["attribution"] for sp in tr.walk() if sp.name == "engine.megakernel"] == ["rescan"]
    text = applier.out.getvalue()
    assert ": 0/6 nodes are available: " in text and reasons.not_attributed() not in text


def test_a_tail_failure_not_asked_about_evaluates_no_reasons(monkeypatch):
    plan = Plan(**TAIL)
    asked, _, asked_nodes = plan.masked(1)
    asked_unscheduled = _unscheduled(asked, plan)
    assert asked.engine.name == "megakernel" and asked.engine.attribution == "tail"

    def never(*a, **kw):
        raise AssertionError("reasons were evaluated for a caller that reads none")

    monkeypatch.setattr(simulator, "_fast_failure_details", never)
    quiet, _, quiet_nodes = plan.masked(1, reasons=False)
    assert quiet.engine.name == "megakernel" and quiet.engine.attribution == "not_asked"
    assert quiet_nodes == asked_nodes and _unscheduled(quiet, plan) == asked_unscheduled
    assert {u.reason for u in quiet.unscheduled_pods} == {reasons.not_attributed()}


ATTRIBUTIONS = {
    "none": (dict(), dict()),
    "tail": (TAIL, dict()),
    "not_asked": (MIDSTREAM, dict(reasons=False)),
    "rescan": (MIDSTREAM, dict()),
}


@pytest.mark.parametrize("outcome", sorted(ATTRIBUTIONS))
def test_the_kernels_span_and_metrics_say_what_became_of_the_reasons(outcome):
    sizes, asked = ATTRIBUTIONS[outcome]
    plan = Plan(**sizes)
    RECORDER.reset()
    tr = tracing.start_trace("simulate", force=True)
    try:
        with tracing.trace_scope(tr):
            res, _, _ = plan.masked(1, **asked)
        tr.finish()
        assert res.engine.attribution == outcome
        assert [sp.attrs["attribution"] for sp in tr.walk() if sp.name == "engine.megakernel"] == [outcome]
        lines = RECORDER.render_lines()
        assert "# TYPE simon_megakernel_attribution_total counter" in lines
        assert [l for l in lines if l.startswith("simon_megakernel_attribution_total{")] == [
            f'simon_megakernel_attribution_total{{outcome="{outcome}"}} 1'
        ]
        events = [sp for sp in tr.walk() if sp.name in ("placement.reasons", "placement.unschedulable")]
        unschedulable = [l for l in lines if l.startswith("simon_unschedulable_total{")]
        rejects = [l for l in lines if l.startswith("simon_filter_reject_total{")]
        if outcome == "none":
            assert not events and not unschedulable and not rejects
        elif outcome == "not_asked":
            # the count, and nothing built from rows no engine filled
            assert [(sp.name, sp.attrs) for sp in events] == [
                ("placement.reasons", {"unschedulable": len(res.unscheduled_pods), "attribution": "not_asked"})
            ]
            assert unschedulable == [f'simon_unschedulable_total{{reason="not_attributed"}} {len(res.unscheduled_pods)}']
            assert not rejects
        else:
            assert events[0].name == "placement.reasons" and "attribution" not in events[0].attrs
            assert any(k.startswith("reason_") for k in events[0].attrs)
            assert len(events) == 1 + min(len(res.unscheduled_pods), 8)
            assert unschedulable and rejects and not any("not_attributed" in l for l in unschedulable)
    finally:
        RECORDER.reset()
