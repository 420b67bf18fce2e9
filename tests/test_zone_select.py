"""The megakernel reads a zone key of at most `kernels.SELECT_DOMAINS`
domains by compare-select over each node's zone id, not through the [K, N, Z]
one-hot blocks, which took most of its VMEM estimate and turned away every
cluster past about 7,000 nodes: the two reads place every pod alike, bit for
bit, in a schedule, a packed sweep and a masked pass, with spread and
inter-pod terms over one to four zone keys; a key of more domains keeps the
blocks; the envelope admits the 15,000 nodes of `gke-15k-50k` and still
turns a 200-domain key away there; the kernel's spans and `/metrics` say
which read each key took. The kernel runs in the Pallas interpreter here;
`OPENSIM_TEST_BACKEND=tpu` compiles it. Tier-1, small shapes."""

import importlib
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from opensim_tpu.engine import fastpath
from opensim_tpu.engine.simulator import AppResource, prepare, simulate
from opensim_tpu.models import ResourceTypes, fixtures as fx
from opensim_tpu.obs import trace as tracing
from opensim_tpu.obs.metrics import RECORDER
from opensim_tpu.ops import kernels

_INTERPRET = os.environ.get("OPENSIM_TEST_BACKEND") != "tpu"
HOST = "kubernetes.io/hostname"
#: zone keys and their domains: three, two, five, and the most a key read by
#: compare-select may have
KEYS = (("topology.kubernetes.io/zone", 3), ("topology.kubernetes.io/region", 2),
        ("example.com/rack", 5), ("example.com/row", kernels.SELECT_DOMAINS))
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _kernel_on(monkeypatch):
    monkeypatch.delenv("OPENSIM_DISABLE_FASTPATH", raising=False)
    if _INTERPRET:  # the interpreter is asked for by name, never inferred
        monkeypatch.setenv("OPENSIM_FASTPATH", "interpret")


def _term(key, app):
    return {"labelSelector": {"matchLabels": {"app": app}}, "topologyKey": key}


def _cluster(n_keys, domains=None, fails=True):
    """16 nodes under the first `n_keys` of KEYS (`domains` in place of the
    first key's count), two nodes without the last key's label; two
    bound pods whose anti-affinity and preferred terms over the zone keys
    count against every pod that matches them (the symmetric terms); with
    `fails`, two pods too large for any node."""
    keys = [(key, domains if k == 0 and domains else d) for k, (key, d) in enumerate(KEYS[:n_keys])]
    cluster = ResourceTypes()
    for i in range(16):
        labels = {key: f"{key.rsplit('/', 1)[1]}-{i % d}" for key, d in keys}
        if i % 7 == 5:
            del labels[keys[-1][0]]
        cluster.nodes.append(fx.make_fake_node(f"n{i:02d}", "8", "16Gi", "110", fx.with_labels(labels)))
    first, last = keys[0][0], keys[-1][0]
    cluster.pods.append(fx.make_fake_pod(
        "guard", "1", "1Gi", fx.with_node_name("n03"), fx.with_labels({"app": "guard"}),
        fx.with_affinity({"podAntiAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": [_term(first, "shy")]}})))
    cluster.pods.append(fx.make_fake_pod(
        "magnet", "1", "1Gi", fx.with_node_name("n06"), fx.with_labels({"app": "magnet"}),
        fx.with_affinity({"podAffinity": {"preferredDuringSchedulingIgnoredDuringExecution": [
            {"weight": 40, "podAffinityTerm": _term(last, "web")}]}})))
    app = ResourceTypes()
    for k, (key, _d) in enumerate(keys):
        spread = fx.with_topology_spread([
            {"maxSkew": 1, "topologyKey": key, "whenUnsatisfiable": "ScheduleAnyway",
             "labelSelector": {"matchLabels": {"app": f"soft{k}"}}}])
        app.deployments.append(fx.make_fake_deployment(f"soft{k}", 7, "300m", "512Mi", spread))
    hard = fx.with_topology_spread([{"maxSkew": 2, "topologyKey": last, "whenUnsatisfiable": "DoNotSchedule",
                                     "labelSelector": {"matchLabels": {"app": "hard"}}}])
    app.deployments.append(fx.make_fake_deployment("hard", 9, "500m", "1Gi", hard))
    app.deployments.append(fx.make_fake_deployment("shy", 4, "200m", "256Mi"))
    if fails:
        app.deployments.append(fx.make_fake_deployment("huge", 2, "9", "1Gi"))
    app.deployments.append(fx.make_fake_deployment("web", 6, "400m", "512Mi", fx.with_affinity({
        "podAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": [_term(first, "magnet")],
                        "preferredDuringSchedulingIgnoredDuringExecution": [
                            {"weight": 60, "podAffinityTerm": _term(last, "web")}]},
        "podAntiAffinity": {"preferredDuringSchedulingIgnoredDuringExecution": [
            {"weight": 30, "podAffinityTerm": _term(first, "hard")}]}})))
    return cluster, app


def _prep(n_keys, domains=None):
    cluster, app = _cluster(n_keys, domains)
    return prepare(cluster, [AppResource("a", app)], node_pad=128)


def _masks(prep, S):
    rng = np.random.RandomState(S)
    n_real = int(np.asarray(prep.ec_np.node_valid).sum())
    N, P = int(np.asarray(prep.ec_np.node_valid).shape[0]), len(prep.ordered)
    nodes = np.zeros((S, N), bool)
    for s in range(S):
        nodes[s, s % 3:s % 3 + 6 + (s * 5) % (n_real - 8)] = True
    return nodes, rng.rand(S, P) > 0.15, np.broadcast_to(prep.forced, (S, P)) & (rng.rand(S, P) > 0.3)


def _run(prep, path):
    P = len(prep.ordered)
    if path == "sweep":
        return fastpath.sweep(prep, *_masks(prep, 9), interpret=_INTERPRET)
    mask = None
    if path == "masked":
        mask = np.zeros(np.asarray(prep.ec_np.node_valid).shape[0], bool)
        mask[2:13] = True
    return fastpath.schedule(prep, prep.tmpl_ids, np.ones(P, bool), prep.forced, node_valid=mask,
                             interpret=_INTERPRET)


@pytest.mark.parametrize("path", ["schedule", "sweep", "masked"])
@pytest.mark.parametrize("n_keys", [1, 2, 3, 4])
def test_the_compare_select_read_places_as_the_one_hot_read_bit_for_bit(monkeypatch, n_keys, path):
    prep = _prep(n_keys)
    assert prep.features.interpod and prep.features.prefg and fastpath.applicable(prep)
    assert fastpath.zone_domains(prep) == max(d for _key, d in KEYS[:n_keys])
    assert fastpath.build_inputs(prep)[0].zone_NZ.shape[1] == 0  # no one-hot block is built
    if path == "sweep":
        assert fastpath.sweep_sublanes(prep, 9) == 8
    selected = _run(prep, path)
    monkeypatch.setattr(fastpath, "zone_domains", lambda prep: 0)
    blocks = _prep(n_keys)
    assert fastpath.build_inputs(blocks)[0].zone_NZ.shape[1] > 0
    onehot = _run(blocks, path)
    assert len(selected) == len(onehot)
    for k, (got, want) in enumerate(zip(selected, onehot)):
        got, want = np.asarray(got), np.asarray(want)
        assert got.dtype == want.dtype and got.shape == want.shape, k
        np.testing.assert_array_equal(got, want, err_msg=str(k))
    chosen = np.asarray(selected[2] if path == "sweep" else selected[0])
    assert (chosen >= 0).any() and (chosen < 0).any()  # the stream both binds and fails


def test_a_key_of_more_domains_than_the_select_reads_keeps_the_one_hot_blocks():
    prep = _prep(2, domains=kernels.SELECT_DOMAINS + 1)
    assert prep.features.count_keys is None and fastpath.applicable(prep)
    assert fastpath.zone_domains(prep) == 0
    assert fastpath.zone_reads(prep) == {"zone_keys_selected": 0, "zone_keys_onehot": 2}
    fi, _meta = fastpath.build_inputs(prep)
    assert fi.zone_NZ.shape == (2, fi.alloc_T.shape[1], 128)
    assert fastpath._kernel_flags(prep)["zone_domains"] == 0


# -- the envelope at the cell's width ------------------------------------------


def _gke_prep(tmp_path):
    """`gke-15k-50k`'s short plan at its `tiny` size, prepared."""
    from benchmarks.drivers import Context
    from opensim_tpu.planner.apply import Applier, Options

    bench = os.path.join(ROOT, "benchmarks")
    with open(os.path.join(bench, "configs", "gke-15k-50k.json")) as f:
        config = json.load(f)
    with open(os.path.join(bench, "traffic", "short-15k.json")) as f:
        traffic = json.load(f)
    ctx = Context(config=config, traffic=traffic, seed=5, scratch=str(tmp_path), rehearse=True, sizes=config["tiny"])
    driver = importlib.import_module("benchmarks.drivers.plan_loop_kernel").Driver(ctx)
    driver.prepare()
    applier = Applier(Options(simon_config=driver.simon_config))
    return prepare(applier.load_cluster(), applier.load_apps())


def _widened(prep, wide, zones):
    """`prep`'s encoding with its node axis widened to `wide`: every node its
    own hostname, the zone key's `zones` domains in turn, nothing padded."""
    ec = prep.ec_np
    n = ec.node_valid.shape[0]
    grow = {f: np.resize(a, (wide,) + a.shape[1:]) for f, a in ec._asdict().items()
            if np.ndim(a) and a.shape[0] == n}
    keys = prep.meta.vocab.topo_keys.items()
    assert sorted(keys) == [HOST, "topology.kubernetes.io/zone"]
    node_domain = np.zeros((wide, 2), np.int32)
    node_domain[:, keys.index(HOST)] = np.arange(wide)
    node_domain[:, 1 - keys.index(HOST)] = wide + np.arange(wide) % zones
    domain_topo = np.zeros((wide + zones + 1,) + np.shape(ec.domain_topo)[1:], np.asarray(ec.domain_topo).dtype)
    grow.update(node_domain=node_domain, node_valid=np.ones(wide, bool), domain_topo=domain_topo)
    ec = ec._replace(**grow)
    return SimpleNamespace(features=prep.features._replace(count_keys=kernels.count_keys_of(ec)),
                           ec_np=ec, ec=None, meta=prep.meta)


def test_the_envelope_admits_fifteen_thousand_nodes_in_four_zones(tmp_path):
    """15,000 nodes (15,104 padded) pass `why_not`, and the short plan's
    sweeps (13,800 nodes and 128 candidates, 13,952 padded) pack eight
    scenarios a step; the one-hot blocks alone took 15.5 MB there."""
    prep = _gke_prep(tmp_path)
    fit, short = _widened(prep, 15104, 4), _widened(prep, 13952, 4)
    assert fastpath.zone_domains(fit) == 4 and fastpath.zone_reads(fit)["zone_keys_selected"] == 1
    assert fastpath.why_not(fit) is None
    assert fastpath.vmem_estimate(fit) <= fastpath._VMEM_BUDGET
    assert fastpath.sweep_sublanes(short, 15) == fastpath.SWEEP_SUBLANES == 8


def test_a_zone_key_of_200_domains_still_declines_at_that_width(tmp_path):
    wide = _widened(_gke_prep(tmp_path), 15104, 200)
    assert fastpath.zone_domains(wide) == 0 and fastpath.zone_reads(wide)["zone_keys_onehot"] == 1
    token, why = fastpath.why_not(wide)
    assert token == "vmem" and "exceeds" in why
    assert fastpath.sweep_sublanes(_widened(_gke_prep(tmp_path), 13952, 200), 15) == 1


# -- the spans and the counter -------------------------------------------------


def _plan_spans(cluster, app):
    tr = tracing.start_trace("test", force=True)
    with tracing.trace_scope(tr):
        result = simulate(cluster, [AppResource("a", app)])
    tr.finish()
    assert result.engine.name == "megakernel"
    reads = ("zone_keys_selected", "zone_keys_onehot")
    return [(sp.name, tuple(sp.attrs[k] for k in reads)) for sp in tr.walk()
            if sp.name in ("mk.inputs", "engine.megakernel")]


@pytest.mark.parametrize("domains,path", [(3, "select"), (kernels.SELECT_DOMAINS + 1, "onehot")])
def test_the_kernels_spans_say_how_its_zone_keys_are_read_and_metrics_counts_them(domains, path):
    RECORDER.reset()
    try:
        spans = _plan_spans(*_cluster(2, domains, fails=False))
        want = (2, 0) if path == "select" else (0, 2)
        assert spans == [("engine.megakernel", want), ("mk.inputs", want)]
        lines = [l for l in RECORDER.render_lines() if l.startswith("simon_megakernel_zone_keys_total{")]
        assert lines == [f'simon_megakernel_zone_keys_total{{path="{path}"}} 2']
        _plan_spans(*_cluster(2, domains, fails=False))  # a second launch adds its keys
        lines = [l for l in RECORDER.render_lines() if l.startswith("simon_megakernel_zone_keys_total{")]
        assert lines == [f'simon_megakernel_zone_keys_total{{path="{path}"}} 4']
    finally:
        RECORDER.reset()
