"""The resident carry (opensim_tpu/engine/resident.py): a request's XLA scan
that starts from the twin's bound pods' carry is held bit-equal to the scan
that replays them — outputs and every leaf of the final state — and the
widened count tensors are held to ``explain.rebuild_counts``. The C++ engine
is kept out the way the other XLA tests do it (OPENSIM_DISABLE_NATIVE)."""

import dataclasses
import json
import logging

import numpy as np
import pytest

from opensim_tpu.engine import explain, prepcache, resident, simulator
from opensim_tpu.engine.simulator import AppResource, prepare, simulate
from opensim_tpu.models import ResourceTypes, fixtures as fx
from opensim_tpu.models.expand import new_fake_nodes
from opensim_tpu.obs import trace as obs
from opensim_tpu.obs.metrics import RECORDER

ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
GPU = {"alibabacloud.com/gpu-mem": "16Gi", "alibabacloud.com/gpu-count": "2"}
LOCAL = dict(
    vgs=[{"name": "pool0", "capacity": 100 * 1024**3}],
    devices=[{"device": "/dev/vdb", "capacity": 40 * 1024**3, "mediaType": "ssd"}],
)
COUNTS = ("port_used", "dom_sel", "dom_anti", "dom_prefw")


@pytest.fixture(autouse=True)
def _xla_only(monkeypatch):
    monkeypatch.setenv("OPENSIM_DISABLE_NATIVE", "1")


def _term(labels, key=HOST):
    return {"labelSelector": {"matchLabels": labels}, "topologyKey": key}


def _spread(labels, key=ZONE, hard=False, skew=1):
    return fx.with_topology_spread([{
        "maxSkew": skew, "topologyKey": key, "labelSelector": {"matchLabels": labels},
        "whenUnsatisfiable": "DoNotSchedule" if hard else "ScheduleAnyway",
    }])


def _twin(*pod_opts, n_nodes=6, per_node=3, node_opts=(), racks=False, pending=0):
    """Nodes in three zones (every other one in a rack when asked), each with
    `per_node` bound pods labelled app=res that carry `pod_opts`."""
    rt = ResourceTypes()
    for i in range(n_nodes):
        labels = {ZONE: f"z{i % 3}"}
        if racks and i % 2:
            labels["rack"] = f"r{i % 4}"
        rt.nodes.append(fx.make_fake_node(f"n{i:03d}", "16", "64Gi", "110", fx.with_labels(labels), *node_opts))
    for i in range(n_nodes):
        for k in range(per_node):
            rt.pods.append(fx.make_fake_pod(
                f"res-{i}-{k}", f"{100 + 50 * k}m", f"{128 * (1 + (i + k) % 3)}Mi",
                fx.with_labels({"app": "res", "tier": f"t{k % 2}"}),
                fx.with_node_name(f"n{i:03d}"), *pod_opts,
            ))
    for k in range(pending):  # unforced, behind the forced run
        rt.pods.append(fx.make_fake_pod(f"pending-{k}", "200m", "256Mi", fx.with_labels({"app": "res"})))
    return rt


def _app(*opts, name="web", replicas=7, labels=None, cpu="500m", annotations=None):
    rt = ResourceTypes()
    d = fx.make_fake_deployment(name, replicas, cpu, "1Gi", fx.with_pod_labels(labels or {"app": name}), *opts)
    if annotations:
        d.template_metadata.annotations.update(annotations)
        d.template_raw.setdefault("metadata", {}).setdefault("annotations", {}).update(annotations)
    rt.deployments.append(d)
    return [AppResource(name, rt)]


def _anti(labels, key=HOST):
    return {"podAntiAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": [_term(labels, key)]}}


def _pref(labels, weight=10, key=ZONE, anti=False):
    kind = "podAntiAffinity" if anti else "podAffinity"
    return {kind: {"preferredDuringSchedulingIgnoredDuringExecution": [
        {"weight": weight, "podAffinityTerm": _term(labels, key)}]}}


# name -> (twin, apps, base_drop indices): what the request brings against
# what the resident pods already hold
CASES = {
    # resident pods with a selector and a key of their own; the request adds its owner selector and the hostname key
    "plain": lambda: (_twin(_spread({"app": "res"})), _app(), ()),
    # the request's selector matches resident pods' labels: dom_sel's new column counts them
    "selector_soft": lambda: (_twin(_spread({"app": "res"})), _app(_spread({"app": "res"}), labels={"app": "res"}), ()),
    "selector_hard": lambda: (
        _twin(_spread({"tier": "t0"})),
        _app(_spread({"app": "res"}, hard=True, skew=2), labels={"app": "res"}, replicas=9), (),
    ),
    # required anti-affinity both ways: the request's term against resident pods (dom_sel), resident pods' terms
    # against the request (dom_anti, kept) — 4 of 7 fit
    "anti": lambda: (
        _twin(fx.with_affinity(_anti({"app": "web"}, ZONE)), per_node=1, n_nodes=4),
        _app(fx.with_affinity(_anti({"tier": "t1"}))) + _app(name="api", replicas=3), (),
    ),
    # preferred terms both ways: dom_prefw kept from the carry, the request's own terms in new columns
    "preferred": lambda: (
        _twin(fx.with_affinity(_pref({"app": "web"}, 20))),
        _app(fx.with_affinity({**_pref({"app": "res"}, 7), **_pref({"tier": "t1"}, 3, HOST, anti=True)})), (),
    ),
    # host ports held by resident pods and asked for by the request (same port: a base column; another: a new one)
    "ports": lambda: (
        _twin(fx.with_host_ports([8080]), per_node=1),
        _app(fx.with_host_ports([8080, 9090]), replicas=8), (),
    ),
    "ports_new_only": lambda: (_twin(), _app(fx.with_host_ports([9090]), replicas=8), ()),
    # a topology key the base never saw: D grows and the trash row, which holds the pods of the nodes that
    # lack the base's key, moves
    "new_topology_key": lambda: (
        _twin(_spread({"app": "res"}, key="rack"), racks=True),
        _app(_spread({"app": "res"}, hard=True, skew=3), labels={"app": "res"}), (),
    ),
    # a resource no node and no resident pod has: R grows (and nothing fits)
    "new_resource": lambda: (_twin(_spread({"app": "res"})), _app(fx.with_requests({"example.com/widget": "1"})), ()),
    # resident GPU-share and open-local pods; the request asks for both
    "gpu_and_local": lambda: (
        _twin(
            fx.with_annotations({"alibabacloud.com/gpu-mem": "3Gi", "alibabacloud.com/gpu-count": "1"}),
            fx.with_pod_local_storage(json.dumps({"volumes": [
                {"size": str(8 * 1024**3), "kind": "LVM", "scName": "open-local-lvm"}]})),
            node_opts=(fx.with_allocatable(GPU), fx.with_node_local_storage(**LOCAL)), per_node=2,
        ),
        _app(annotations={
            "alibabacloud.com/gpu-mem": "5Gi", "alibabacloud.com/gpu-count": "1",
            "simon/pod-local-storage": json.dumps({"volumes": [
                {"size": str(30 * 1024**3), "kind": "SSD", "scName": "open-local-device"}]}),
        }, replicas=5),
        (),
    ),
    # the base has no GPU or storage request at all: the features turn on with the request
    "gpu_feature_turns_on": lambda: (
        _twin(node_opts=(fx.with_allocatable(GPU),)),
        _app(annotations={"alibabacloud.com/gpu-mem": "5Gi", "alibabacloud.com/gpu-count": "1"}), (),
    ),
    # a base with no selector, no term, no port and no key: every axis is max(len, 1) and every feature off
    "bare_base": lambda: (_twin(), _app(_spread({"app": "res"}, hard=True, skew=4), labels={"app": "res"}), ()),
    # unforced pods of the twin behind the forced run are scanned with the request
    "pending_after_forced": lambda: (_twin(_spread({"app": "res"}), pending=3), _app(), ()),
    # twin events dropped resident pods: the carry is built with the entry's mask
    "base_drop": lambda: (_twin(_spread({"app": "res"}), fx.with_host_ports([8080]), per_node=1), _app(fx.with_host_ports([8080])), (1, 4)),
}


def _entry(cluster, dropped=()):
    base = prepare(cluster, [])
    entry = prepcache.CacheEntry("fp|base", base)
    if dropped:
        mask = np.zeros(len(base.ordered), bool)
        mask[list(dropped)] = True
        entry.base_drop = mask
    return entry


def _derive(entry, cluster, apps):
    entry.restore()
    prep = prepcache.derive_with_apps(entry.prep, cluster, apps, base_entry=entry)
    assert prep.resident_base is entry
    valid = np.ones(len(prep.ordered), bool)
    if entry.base_drop is not None:
        valid &= ~prepcache.pad_drop_mask(entry.base_drop, len(prep.ordered))
    return prep, valid


def _ladder(prep, valid, **kw):
    """The XLA rung as simulate() reaches it, with the raw ScheduleOutput."""
    args = dict(segments=None, sched_config=None, extra_plugins=(), tie_seed=None, nv_mask=None, explain=False)
    args.update(kw)
    out, engine, _skips, _rows, _attribution = simulator._run_engine_ladder(
        prep, args["segments"], args["sched_config"], valid, prep.forced, prep.tmpl_ids,
        args["extra_plugins"], args["tie_seed"], args["nv_mask"], prep.ec, prep.st0,
        logging.getLogger("test"), explain=args["explain"],
    )
    assert engine == "xla"
    return out


def _replayed(prep, valid, **kw):
    return _ladder(dataclasses.replace(prep, resident_base=None), valid, **kw)


def _assert_same_run(got, want, n):
    for name in ("chosen", "gpu_take", "fail_counts", "insufficient"):
        a, b = np.asarray(getattr(got, name))[:n], np.asarray(getattr(want, name))[:n]
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert a.tobytes() == b.tobytes(), name
    assert np.asarray(got.static_fail).tobytes() == np.asarray(want.static_fail).tobytes()
    for name, a, b in zip(got.final_state._fields, got.final_state, want.final_state):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert a.tobytes() == b.tobytes(), f"final_state.{name}"


def _outcomes():
    with RECORDER.lock:
        return {k[0]: v for k, v in RECORDER.resident_carry._series.items()}


def _delta(before):
    after = _outcomes()
    return {k: after.get(k, 0) - before.get(k, 0) for k in after if after.get(k, 0) != before.get(k, 0)}


def _assert_counts_match_oracle(prep, valid, head):
    """The widened state's count tensors against the numpy fold over the
    resident binds (``rebuild_counts`` folds every tensor; the scan folds a
    tensor only while its feature is on, and leaves zeros otherwise)."""
    chosen = np.full(len(prep.ordered), -1, np.int32)
    chosen[: head.n_res] = head.carry.chosen
    oracle = dict(zip(COUNTS, explain.rebuild_counts(prep, chosen, upto=head.n_res)))
    f = prep.features
    on = {"port_used": f.ports, "dom_sel": f.sel_counts, "dom_anti": f.interpod, "dom_prefw": f.prefg}
    for name in COUNTS:
        got = np.asarray(getattr(head.state, name))
        want = oracle[name] if on[name] else np.zeros_like(oracle[name])
        np.testing.assert_array_equal(got, want, err_msg=name)


@pytest.mark.parametrize("case", sorted(CASES))
def test_scan_from_the_resident_carry_equals_the_full_replay(case):
    cluster, apps, dropped = CASES[case]()
    entry = _entry(cluster, dropped)
    prep, valid = _derive(entry, cluster, apps)
    n_res = resident.leading_forced(entry.prep.forced)
    assert 0 < n_res <= len(entry.prep.ordered)

    before = _outcomes()
    got = _ladder(prep, valid)
    assert _delta(before) == {"built": 1}, "the case must engage, not pass by declining"
    want = _replayed(prep, valid)
    _assert_same_run(got, want, len(prep.ordered))
    # the case is worth its name: something bound, and the resident pods did
    assert (np.asarray(want.chosen)[:n_res][valid[:n_res]] >= 0).all()

    before = _outcomes()
    head = resident.fetch(prep, valid)
    assert _delta(before) == {"hit": 1} and head.n_res == n_res
    _assert_counts_match_oracle(prep, valid, head)


def test_the_cases_reach_what_they_are_named_for():
    """The traps of encoding/state.py:_assemble, shown in the inputs: a
    phantom column that becomes real, a trash row that moves, a feature the
    base had off, a grown resource axis, pods the scan rejects."""
    entries = {}

    def shapes(case):
        cluster, apps, dropped = CASES[case]()
        entry = _entry(cluster, dropped)
        prep, valid = _derive(entry, cluster, apps)
        entries[id(entry.prep)] = entry
        return entry.prep, prep, _ladder(prep, valid)

    def entry_state(base, name):
        return getattr(entries[id(base)].resident.state, name)

    base, prep, out = shapes("bare_base")
    assert not any(base.encoder.ts.selectors) and base.ec_np.matches_sel.shape[1] == 1
    assert not base.features.sel_counts and prep.features.sel_counts
    assert prep.ec_np.matches_sel[: len(base.ec_np.pin), 0].any()  # column 0 now counts resident pods
    assert np.asarray(out.final_state.dom_sel).sum() > 0

    base, prep, out = shapes("new_topology_key")
    assert prep.ec_np.domain_topo.shape[0] > base.ec_np.domain_topo.shape[0]
    assert np.asarray(entry_state(base, "dom_sel"))[-1].sum() > 0  # the base's trash row holds the rack-less nodes' pods
    assert np.asarray(out.final_state.dom_sel)[-1].sum() > 0  # and so does the new one, further down

    base, prep, out = shapes("new_resource")
    assert prep.ec_np.req.shape[1] == base.ec_np.req.shape[1] + 1
    assert (np.asarray(out.chosen)[len(base.ordered): len(prep.ordered)] < 0).all()

    base, prep, out = shapes("ports_new_only")
    assert not base.features.ports and prep.features.ports and base.ec_np.port_conflict.shape[0] == 1

    base, prep, out = shapes("gpu_feature_turns_on")
    assert not base.features.gpu and prep.features.gpu

    base, prep, out = shapes("anti")
    new = np.asarray(out.chosen)[len(base.ordered): len(prep.ordered)]
    assert (new < 0).any() and (new >= 0).any()  # resident pods' terms and the request's both bite


def test_a_second_request_finds_nothing_left_behind():
    cluster, apps, _ = CASES["preferred"]()
    entry = _entry(cluster)
    first, valid = _derive(entry, cluster, apps)
    _ladder(first, valid)
    kept = [np.asarray(a).copy() for a in entry.resident.state]

    other = _app(fx.with_host_ports([7070]), _spread({"app": "res"}, hard=True, skew=3), name="other", labels={"app": "res"})
    second, valid2 = _derive(entry, cluster, other)
    before = _outcomes()
    got = _ladder(second, valid2)
    assert _delta(before) == {"hit": 1}
    _assert_same_run(got, _replayed(second, valid2), len(second.ordered))
    for a, b in zip(kept, entry.resident.state):
        assert a.tobytes() == np.asarray(b).tobytes()  # read-only: the first request left no trace
    # and the first request again reads what it read before
    _assert_same_run(_ladder(first, valid), _replayed(first, valid), len(first.ordered))


def _traced(fn):
    tr = obs.start_trace("test")
    with obs.trace_scope(tr):
        out = fn()
    tr.finish()
    return tr, out


def _span(tr, name):
    return [sp for sp in tr.walk() if sp.name == name]


def _decline_cases():
    twin = lambda: _twin(_spread({"app": "res"}))

    def derived(**kw):
        cluster = twin()
        entry = _entry(cluster)
        prep, valid = _derive(entry, cluster, _app())
        return lambda: _ladder(prep, valid, **kw), lambda: _replayed(prep, valid, **kw), len(prep.ordered)

    def scale():
        cluster = twin()
        entry = _entry(cluster)
        prep, valid = _derive(entry, cluster, _app())
        valid = valid.copy()
        valid[2] = False  # the request drops a resident pod the carry was built with
        return lambda: _ladder(prep, valid), lambda: _replayed(prep, valid), len(prep.ordered)

    def newnodes():
        cluster = twin()
        entry = _entry(cluster)
        nodes = new_fake_nodes(cluster.nodes[0], 2)
        wider = prepcache.extend_with_nodes(entry.prep, nodes, cluster, [], base_entry=entry)
        grown = ResourceTypes(**{**cluster.__dict__, "nodes": cluster.nodes + nodes})
        prep = prepcache.derive_with_apps(wider, grown, _app(), base_entry=None)
        valid = np.ones(len(prep.ordered), bool)
        return lambda: _ladder(prep, valid), lambda: _replayed(prep, valid), len(prep.ordered)

    def plan():
        cluster = twin()
        cluster.pods = []  # a plan: nothing bound, the whole stream is the question
        entry = _entry(ResourceTypes(nodes=cluster.nodes, deployments=_app()[0].resources.deployments))
        prep, valid = _derive(entry, cluster, _app(name="more"))
        return lambda: _ladder(prep, valid), lambda: _replayed(prep, valid), len(prep.ordered)

    return {
        "scale": (scale, "mask"),
        "newnodes": (newnodes, "no_base"),
        "tie_seed": (lambda: derived(tie_seed=7), "tie_seed"),
        "explain": (lambda: derived(explain=True), "explain"),
        "plan": (plan, "no_resident_pods"),
    }


@pytest.mark.parametrize("case", ["scale", "newnodes", "tie_seed", "explain", "plan"])
def test_a_declined_run_replays_in_full_and_says_why(case):
    make, reason = _decline_cases()[case]
    run, replay, n = make()
    before = _outcomes()
    tr, got = _traced(run)
    assert _delta(before) == {"declined": 1}
    (sp,) = _span(tr, "xla.resident")
    assert sp.attrs["outcome"] == "declined" and sp.attrs["reason"] == reason
    (rung,) = _span(tr, "engine.xla")
    assert rung.attrs["pods"] == rung.attrs["scanned"] == n
    _assert_same_run(got, replay(), n)


def test_an_engaged_run_says_hit_and_how_much_it_scanned():
    cluster, apps, _ = CASES["plain"]()
    entry = _entry(cluster)
    prep, valid = _derive(entry, cluster, apps)
    _ladder(prep, valid)
    tr, _ = _traced(lambda: _ladder(prep, valid))
    (sp,) = _span(tr, "xla.resident")
    assert sp.attrs == {"outcome": "hit", "reason": "", "resident_pods": len(cluster.pods)}
    (rung,) = _span(tr, "engine.xla")
    assert rung.attrs["pods"] == len(prep.ordered) and rung.attrs["scanned"] == 7
    assert [c.name for c in rung.children] == ["xla.resident", "xla.pad", "xla.launch", "xla.wait"]
    assert rung.children[2].attrs["pods"] == 256  # the launch is as long as the request, padded


def test_a_twin_event_builds_a_new_carry_once_and_leaves_the_old_one_alone():
    cluster, apps, _ = CASES["selector_soft"]()
    entry = _entry(cluster)
    prep, valid = _derive(entry, cluster, apps)
    _ladder(prep, valid)
    old = entry.resident

    added = fx.make_fake_pod("late", "300m", "512Mi", fx.with_labels({"app": "res"}), fx.with_node_name("n002"))
    gone = {("default", cluster.pods[4].metadata.name)}
    with entry.lock:
        entry.restore()
        newer = prepcache.twin_pod_delta(entry, "fp2|base", [added], gone)
    assert newer is not None and newer.resident is None and entry.resident is old
    cluster2 = ResourceTypes(**{**cluster.__dict__, "pods": cluster.pods + [added]})

    prep2, valid2 = _derive(newer, cluster2, apps)
    before = _outcomes()
    got = _ladder(prep2, valid2)
    assert _delta(before) == {"built": 1}
    assert newer.resident is not old and newer.resident.n_res == old.n_res + 1
    assert not newer.resident.valid[4] and entry.resident is old
    _assert_same_run(got, _replayed(prep2, valid2), len(prep2.ordered))
    before = _outcomes()
    _ladder(prep2, valid2)
    assert _delta(before) == {"hit": 1}  # built once


def test_simulate_answers_the_same_through_the_carry(monkeypatch):
    """End to end through decode: placements and reasons of a served what-if."""
    cluster, apps, _ = CASES["anti"]()
    entry = _entry(cluster)

    def answer(use_carry):
        prep, _ = _derive(entry, cluster, apps)
        if not use_carry:
            prep = dataclasses.replace(prep, resident_base=None)
        try:
            res = simulate(cluster, apps, prep=prep)
            return (
                [(ns.node.metadata.name, sorted(p.metadata.name.rsplit("-", 2)[0] for p in ns.pods)) for ns in res.node_status],
                sorted(u.reason for u in res.unscheduled_pods),
            )
        finally:
            entry.restore()

    assert answer(True) == answer(False)
    assert _outcomes().get("built", 0) + _outcomes().get("hit", 0) >= 1
