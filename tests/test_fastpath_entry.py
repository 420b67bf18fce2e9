"""The megakernel is entered through one jitted function (ISSUE 34):
`run_fast_scan` traces once per signature (argument shapes and the flags that
select the generated kernel) in a process, the compile watch counts those
calls as `megakernel`, `mk.launch` says `entry` = `traced` or `cached`, and
the jitted entry returns what the function it wraps returns. The kernel runs
in the Pallas interpreter here; `OPENSIM_TEST_BACKEND=tpu` compiles it.
Tier-1, small shapes."""

import os

import numpy as np
import pytest

from opensim_tpu.engine import fastpath
from opensim_tpu.engine.simulator import AppResource, prepare
from opensim_tpu.models import ResourceTypes, fixtures as fx
from opensim_tpu.obs import trace as tracing
from opensim_tpu.obs.metrics import parse_metrics
from opensim_tpu.obs.profile import COMPILES
from opensim_tpu.ops.pallas_scan import CHUNK, run_fast_scan

_INTERPRET = os.environ.get("OPENSIM_TEST_BACKEND") != "tpu"
GPU = {"alibabacloud.com/gpu-mem": "32Gi", "alibabacloud.com/gpu-count": "4"}
ANCHORED = fx.with_affinity({"podAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": [
    {"labelSelector": {"matchLabels": {"role": "anchor"}}, "topologyKey": "kubernetes.io/hostname"}
]}})


@pytest.fixture(autouse=True)
def _fresh_entry():
    """The jit's cache and the watch's signatures are the process's: a test
    that counts traces starts from none."""
    run_fast_scan.clear_cache()
    COMPILES.reset()


def _prep(n_nodes=8, replicas=12, kind="plain"):
    cluster = ResourceTypes()
    for i in range(n_nodes):
        gpu = (fx.with_allocatable(GPU),) if kind == "gpushare" else ()
        cluster.nodes.append(fx.make_fake_node(f"n{i:03d}", "16", "32Gi", "110", *gpu))
    app = ResourceTypes()
    app.deployments.append(fx.make_fake_deployment("web", replicas, "500m", "1Gi"))
    app.deployments.append(fx.make_fake_deployment("fat", 3, "20", "8Gi"))  # fits no node
    if kind == "ports":
        app.pods += [fx.make_fake_pod(f"gw{i}", "100m", "64Mi", fx.with_host_ports([31080])) for i in range(2)]
    if kind == "interpod":
        app.pods.append(fx.make_fake_pod("anchor", "100m", "64Mi", fx.with_labels({"role": "anchor"})))
        app.deployments.append(fx.make_fake_deployment("followers", 4, "200m", "128Mi", ANCHORED))
    if kind == "gpushare":
        for j, (mem, count) in enumerate([("4Gi", "1"), ("10Gi", "1"), ("6Gi", "2"), ("8Gi", "3")] * 3):
            share = fx.with_annotations({"alibabacloud.com/gpu-mem": mem, "alibabacloud.com/gpu-count": count})
            app.pods.append(fx.make_fake_pod(f"gpu{j}", "1", "1Gi", share))
    return prepare(cluster, [AppResource("a", app)], node_pad=128)


def _schedule(prep, **kw):
    P = len(prep.ordered)
    return fastpath.schedule(prep, prep.tmpl_ids, np.ones(P, bool), prep.forced, interpret=_INTERPRET, **kw)


def _sweep(prep, S):
    P, N = len(prep.ordered), int(np.asarray(prep.ec_np.node_valid).shape[0])
    masks = np.zeros((S, N), bool)
    for s in range(S):
        masks[s, : 4 + s] = True
    return fastpath.sweep(prep, masks, np.ones((S, P), bool), np.broadcast_to(prep.forced, (S, P)), interpret=_INTERPRET)


def _launched(fn):
    """`fn()` under a trace: the `entry` of each `mk.launch` span it opened."""
    tr = tracing.start_trace("test", force=True)
    with tracing.trace_scope(tr):
        fn()
    tr.finish()
    return [sp.attrs["entry"] for sp in tr.walk() if sp.name == "mk.launch"]


def _counted():
    """What `/metrics` says of the boundary: (simon_compile_total, {cause: n})."""
    total, causes = 0, {}
    for (name, labels), value in parse_metrics("\n".join(COMPILES.metrics_lines())).items():
        labels = dict(labels)
        if labels.get("fn") != "megakernel":
            continue
        if name == "simon_compile_total":
            total = int(value)
        elif name == "simon_compile_cause_total":
            causes[labels["cause"]] = int(value)
    return total, causes


def test_two_schedules_of_equal_shapes_trace_once():
    first, second = _prep(), _prep()  # separately built: no array, closure or cache entry is shared
    assert first is not second and fastpath.build_inputs(first)[0] is not fastpath.build_inputs(second)[0]
    assert _counted() == (0, {})
    assert _launched(lambda: _schedule(first)) == ["traced"]
    assert _counted() == (1, {"first": 1})
    assert _launched(lambda: _schedule(second)) == ["cached"]
    assert _counted() == (1, {"first": 1})
    assert run_fast_scan._cache_size() == 1
    np.testing.assert_array_equal(_schedule(first)[0], _schedule(second)[0])


CHANGES = {
    # what changes between the warm call and the next: (warm, changed, the cause the compile watch names)
    # a sweep packs eight scenarios a block: 2 and 8 are one block, 9 two
    "scenarios": (lambda: _sweep(_prep(), 2), lambda: _sweep(_prep(), 9), "shape"),
    "nodes": (lambda: _schedule(_prep(8)), lambda: _schedule(_prep(130)), "shape"),
    "feature_flag": (lambda: _schedule(_prep()), lambda: _schedule(_prep(kind="ports")), "static"),
    "big_u": (lambda: _schedule(_prep()), lambda: _schedule(_prep(), big_u=True), "static"),
}


@pytest.mark.parametrize("what", sorted(CHANGES))
def test_a_new_signature_traces_once_more_and_the_watch_names_the_cause(what):
    warm, changed, cause = CHANGES[what]
    assert _launched(warm) == ["traced"] and _counted() == (1, {"first": 1})
    assert _launched(changed) == ["traced"]
    assert _counted() == (2, {"first": 1, cause: 1})
    # both signatures stay in the cache: neither traces again, in either order
    assert _launched(warm) == ["cached"] and _launched(changed) == ["cached"]
    assert _counted() == (2, {"first": 1, cause: 1}) and run_fast_scan._cache_size() == 2


@pytest.mark.parametrize("kind,big_u", [("plain", False), ("interpod", False), ("gpushare", True)])
def test_the_jitted_entry_returns_what_the_function_it_wraps_returns(kind, big_u):
    prep = _prep(kind=kind)
    fi, _meta = fastpath.build_inputs(prep)
    fi = fi._replace(node_valid=fi.node_valid[None], key_weight=fi.key_weight[None])
    P = len(prep.ordered)
    tmpl = np.zeros(CHUNK, np.int32)
    tmpl[:P] = np.asarray(prep.tmpl_ids)
    valid = np.zeros((1, CHUNK), bool)
    valid[0, :P] = True
    forced = np.zeros((1, CHUNK), bool)
    forced[0, :P] = np.asarray(prep.forced)
    flags = dict(fastpath._kernel_flags(prep), interpret=_INTERPRET, big_u=big_u)
    assert flags["has_gpu"] == (kind == "gpushare") and flags["has_interpod"] == (kind == "interpod")
    jitted = run_fast_scan(fi, tmpl, valid, forced, **flags)
    plain = run_fast_scan.__wrapped__(fi, tmpl, valid, forced, **flags)
    assert len(jitted) == len(plain) == 6
    for name, got, want in zip(("chosen", "used", "gpu_take", "gpu_free", "vg_free", "dev_free"), jitted, plain):
        got, want = np.asarray(got), np.asarray(want)
        assert got.dtype == want.dtype and got.shape == want.shape, name
        np.testing.assert_array_equal(got, want, err_msg=name)  # bit for bit
    chosen = np.asarray(jitted[0])[0, :P]
    assert (chosen >= 0).any() and (chosen < 0).any()  # the stream both binds and fails
    if kind == "gpushare":
        assert np.asarray(jitted[2]).any()  # devices were taken
