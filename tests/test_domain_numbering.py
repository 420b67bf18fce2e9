"""Topology domains are numbered key by key (`encoding/state.py`): each key's
nodes in node order, so a key whose every node carries a value of its own (the
hostname) holds node n at its first id + n, and a key of a few values (a zone)
a short block. `kernels.count_keys_of` reads that from the encoding, and the
XLA scan then reads a hostname's counts as a slice and a zone's by
compare-select (`tests/test_count_slab.py` holds those reads to the gather).

The numbering is a permutation of the node-by-node one it replaced, so every
count is the same number under another row. Held here by running the engines
under both, the old numbering rebuilt by `node_major`, on the benchmark's tiny
inputs: the XLA scan, the megakernel (interpreted), `explain.rebuild_counts`
and the resident carry across two served requests place every pod alike."""

import importlib
import json
import os

import numpy as np
import pytest

from benchmarks.drivers import Context
from benchmarks.generators.twin_cluster import deploy_payload
from opensim_tpu.encoding.state import ClusterEncoder
from opensim_tpu.engine import explain, fastpath, prepcache, resident
from opensim_tpu.engine.scheduler import pad_pod_stream, schedule_pods
from opensim_tpu.engine.simulator import AppResource, prepare
from opensim_tpu.models import ResourceTypes, fixtures as fx
from opensim_tpu.models.expand import resources_from_dicts
from opensim_tpu.ops import kernels
from opensim_tpu.planner.apply import Applier, Options

HOST = "kubernetes.io/hostname"
BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")


@pytest.fixture(autouse=True)
def _xla_only(monkeypatch):
    monkeypatch.setenv("OPENSIM_DISABLE_NATIVE", "1")


def node_major(monkeypatch):
    """Number the arenas' domains as the encoder did before: node by node,
    each node's keys in order."""
    build = ClusterEncoder._build_node_arenas

    def renumbered(self):
        ar = build(self)
        order = {}
        for d in ar.node_domain.ravel():
            if d >= 0:
                order.setdefault(int(d), len(order))
        ar.domain_ids = {key: order[d] for key, d in ar.domain_ids.items()}
        ar.node_domain = np.array([[order.get(int(d), -1) for d in row] for row in ar.node_domain], np.int32)
        return ar

    monkeypatch.setattr(ClusterEncoder, "_build_node_arenas", renumbered)


# ---------------------------------------------------------------------------
# inputs: the benchmark's tiny sizes
# ---------------------------------------------------------------------------


def _bench(name: str) -> dict:
    with open(os.path.join(BENCH, name)) as f:
        return json.load(f)


def plan_inputs(config: str, traffic: str, tmp_path):
    """The cluster and apps of a plan cell's first plan at the tiny size."""
    conf, traf = _bench(f"configs/{config}.json"), _bench(f"traffic/{traffic}.json")
    ctx = Context(config=conf, traffic=traf, seed=5, scratch=str(tmp_path), rehearse=True, sizes=conf["tiny"])
    driver = importlib.import_module("benchmarks.drivers." + traf["driver"].replace("-", "_")).Driver(ctx)
    driver.prepare()
    applier = Applier(Options(simon_config=driver.simon_config))
    return applier.load_cluster(), applier.load_apps()


def twin_inputs(tmp_path):
    """The twin of serve-solo at the tiny size, and the Deployments of two
    requests."""
    from benchmarks.generators import twin_cluster

    conf = _bench("configs/twin-3k-30k.json")
    made = twin_cluster.generate(conf["tiny"], 5, str(tmp_path))
    cluster, _ = resources_from_dicts(made["node_docs"] + made["pod_docs"])
    requests = []
    for name, replicas in (("req-a", 30), ("req-b", 60)):
        apps, _ = resources_from_dicts(json.loads(deploy_payload(name, replicas, 250, 512))["deployments"])
        requests.append([AppResource(name, apps)])
    return cluster, requests


PLANS = {"cl2": ("cl2-load-5k", "fit-cl2"), "k8s-short": ("k8s-5k-50k", "short")}


# ---------------------------------------------------------------------------
# the numbering
# ---------------------------------------------------------------------------


def _hostname_column(prep):
    keys = prep.meta.vocab.topo_keys.items()
    return keys.index(HOST)


@pytest.mark.parametrize("inputs", ["cl2", "twin"])
def test_hostname_domains_are_their_nodes_in_order(inputs, tmp_path):
    if inputs == "twin":
        cluster, requests = twin_inputs(tmp_path)
        entry = prepcache.CacheEntry("fp|base", prepare(cluster, []))
        prep = prepcache.derive_with_apps(entry.prep, cluster, requests[0], base_entry=entry)
    else:
        prep = prepare(*plan_inputs(*PLANS[inputs], tmp_path))
    k = _hostname_column(prep)
    n = prep.meta.n_real_nodes
    col = np.asarray(prep.ec_np.node_domain)[:, k]
    base = int(col[0])
    np.testing.assert_array_equal(col[:n], base + np.arange(n))
    assert (col[n:] == len(prep.ec_np.domain_topo) - 1).all()  # pad nodes in the trash domain
    keys = prep.features.count_keys
    assert keys.base[k] == base and keys.nodes == n
    # the other key (the zone: one value at cl2's tiny size, none on the twin) is read by compare-select
    assert keys.paths() == {"slice": 1, "select": 1}
    assert kernels.count_reads(prep.ec_np, prep.features)["count_keys_gathered"] == 0


def _cluster(n_nodes=12, hostnames=None):
    rt = ResourceTypes()
    for i in range(n_nodes):
        rt.nodes.append(fx.make_fake_node(f"n{i}", "8", "16Gi", "110"))
        if hostnames is not None:
            rt.nodes[-1].metadata.labels.pop(HOST)
            if hostnames[i] is not None:
                rt.nodes[-1].metadata.labels[HOST] = hostnames[i]
    apps = ResourceTypes()
    apps.deployments.append(fx.make_fake_deployment("web", 4, "100m", "128Mi"))
    return rt, [AppResource("apps", apps)]


@pytest.mark.parametrize("case", ["own_hostnames", "a_node_without_one", "two_nodes_with_one"])
def test_a_key_is_sliced_only_where_every_node_has_a_domain_of_its_own(case):
    """Twelve nodes: more hostnames than a compare-select reads. A missing
    label or a shared value leaves the key neither node-ordered nor small,
    and every key is then gathered."""
    names = [f"n{i}" for i in range(12)]
    if case == "a_node_without_one":
        names[5] = None
    elif case == "two_nodes_with_one":
        names[7] = names[6]
    prep = prepare(*_cluster(hostnames=names))
    reads = kernels.count_reads(prep.ec_np, prep.features)
    if case == "own_hostnames":
        assert prep.features.count_keys.paths() == {"slice": 1, "select": 1}
        assert (reads["count_keys_sliced"], reads["count_keys_gathered"]) == (1, 0)
    else:
        assert prep.features.count_keys is None
        assert (reads["count_keys_sliced"], reads["count_keys_selected"], reads["count_keys_gathered"]) == (0, 0, 2)


# ---------------------------------------------------------------------------
# every engine places alike under both numberings
# ---------------------------------------------------------------------------


def _engines(prep):
    """Each engine's placements of the prepared stream, and the selector
    counts they leave, as each node reads them under each key."""
    n = len(prep.tmpl_ids)
    valid = np.ones(n, bool)
    tmpl, pv, forced = pad_pod_stream(np.asarray(prep.tmpl_ids, np.int32), valid, np.asarray(prep.forced))
    out = schedule_pods(prep.ec, prep.st0, tmpl, pv, forced, features=prep.features)
    chosen = np.asarray(out.chosen)[:n]
    got = {"xla": chosen}
    if fastpath.why_not(prep) is None:
        got["megakernel"] = np.asarray(fastpath.schedule(prep, prep.tmpl_ids, valid, prep.forced, interpret=True)[0])
    dom_sel = explain.rebuild_counts(prep, chosen)[1]
    got["rebuild_counts"] = dom_sel[np.asarray(prep.ec_np.node_domain)]  # [N, Tk, A]
    got["xla_final_counts"] = np.asarray(out.final_state.dom_sel)[np.asarray(prep.ec_np.node_domain)]
    return got, prep.features.count_keys


def _served(cluster, requests):
    """Two requests against the twin, each scanned from the resident carry."""
    from opensim_tpu.obs.metrics import RECORDER

    entry = prepcache.CacheEntry("fp|base", prepare(cluster, []))
    got = {}
    for i, apps in enumerate(requests):
        entry.restore()
        prep = prepcache.derive_with_apps(entry.prep, cluster, apps, base_entry=entry)
        valid = np.ones(len(prep.ordered), bool)
        before = dict(RECORDER.resident_carry._series)
        head = resident.fetch(prep, valid)
        assert head is not None and dict(RECORDER.resident_carry._series) != before
        n = head.n_res
        tmpl, pv, forced = pad_pod_stream(np.asarray(prep.tmpl_ids[n:], np.int32), valid[n:],
                                          np.asarray(prep.forced[n:]))
        out = schedule_pods(prep.ec, head.state, tmpl, pv, forced, features=prep.features)
        got[f"request{i}"] = np.asarray(out.chosen)[: len(prep.ordered) - n]
        got[f"request{i}_keys"] = prep.features.count_keys
    return got


@pytest.mark.parametrize("inputs", ["cl2", "k8s-short", "twin"])
def test_every_engine_places_alike_under_both_numberings(inputs, tmp_path, monkeypatch):
    def run():
        if inputs == "twin":
            return _served(*twin_inputs(tmp_path))
        got, keys = _engines(prepare(*plan_inputs(*PLANS[inputs], tmp_path)))
        return {**got, "keys": keys}

    after = run()
    node_major(monkeypatch)
    before = run()
    key_fields = [k for k in after if k.endswith("keys")]
    for k in key_fields:
        assert after[k] is not None and after[k].paths()["slice"] == 1, k
        # a plan's hostname is sliced with the new numbering alone; the twin's bound pods carry
        # no topology key, so its keys are numbered after the nodes' arena, a key at a time, either way
        assert inputs == "twin" or before[k] is None or before[k].paths()["slice"] == 0, k
    if inputs == "k8s-short":
        assert "megakernel" in after
    for k in after:
        if k not in key_fields:
            np.testing.assert_array_equal(after[k], before[k], err_msg=k)
    if "megakernel" in after:
        np.testing.assert_array_equal(after["megakernel"], after["xla"])
