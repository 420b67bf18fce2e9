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

from opensim_tpu.engine import fastpath
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
    monkeypatch.setenv("OPENSIM_DISABLE_NATIVE", "1")  # as on the chip's machines: below the kernel is the XLA scan
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

    def masked(self, k):
        """A masked simulate over a fresh full Prepared (decode binds its
        pods); returns the result and each stream pod's node, in stream
        order, which two Prepareds of the same inputs share."""
        prep = prepare(self.full, self.apps)
        mask = np.zeros(np.asarray(prep.ec_np.node_valid).shape[0], bool)
        mask[: 6 + k] = True
        res = simulate(self.sub(k), self.apps, prep=prep, node_valid=mask)
        return res, mask, [p.spec.node_name or None for p in prep.ordered]


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


def _write_plan(tmp_path, plan):
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
            "newNode": str(dirs["newnode"]),
        },
    }))
    return str(cfg)


def _pod_table(text):
    """The report's lines with the generated names taken out: a new node's
    random suffix, a pod's counters."""
    import re

    return [re.sub(r"simon-[0-9a-z]{8}|(-[0-9a-f]{10})+(?=\s)", "*", line) for line in text.splitlines()]


def test_a_short_plan_ends_in_one_masked_megakernel_pass_and_reports_the_same(monkeypatch, tmp_path):
    """`plan-short` in small: pods fail mid-stream, so the first pass's kernel
    result is discarded for the scan's; the count is searched; the final pass
    over the masked Prepared is the kernel's, and no second scan runs."""
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
        return rungs, int(re.search(r"\(added (\d+) new node\(s\)\)", text).group(1)), _pod_table(text)

    rungs, added, table = run("on.txt")
    assert rungs == [("engine.megakernel", False), ("engine.xla", False), ("engine.megakernel", True)]
    assert 0 < added < N_CANDIDATES
    assert "Scheduling engine: megakernel" in table

    _kernel_off(monkeypatch)
    rungs, added_off, table_off = run("off.txt")
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
