"""The XLA scan reads the selector-count carry (`ScanState.dom_sel`, [D+1, A])
through `kernels.domain_counts`: past one window of `kernels.COUNT_WINDOW`
columns, the pod's own selector columns as a [D+1, C] slab first
(`kernels.selector_columns`: each column from the window that holds it), then
each node's domain row within the slab; a carry of one window as it is. Where
the encoding's `kernels.CountKeys` says every topology key is node-ordered (a
hostname: node n in domain base + n) or small (a few zones), a node's count is
a slice of its term's column or a compare-select over the key's domains, with
no per-node gather. Held here bitwise to what the readers did before, which
this file keeps as its reference: the point gather `counts[dom, cols[None, :]]`
and the column gather `counts[:, cols]`; the helpers alone and their five
readers (spread filter and score, inter-pod anti and affinity filters, the
inter-pod score's incoming terms), in a scan step's form and in a sweep's
vmapped form, over hostname and zone keys, trash-domain and pad nodes, padded
terms and columns in the last, partial window. And a guard on the jaxpr of
`_schedule_pods_jit` at `cl2-load-5k`'s tiny size, its selector axis as it is
and widened to the full size's: the scan body gathers nothing of the carry,
and reads a wide one by windows of whole rows."""

import functools
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from opensim_tpu.engine.simulator import AppResource, prepare
from opensim_tpu.models import ResourceTypes, fixtures as fx
from opensim_tpu.ops import kernels

ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
SCENARIOS = 3


def point_gather(counts, dom, cols, topo=None, keys=None):
    """The reference: one cell of the carry for each (node, term), whatever
    the terms' keys and the encoding's read paths."""
    return counts[dom, cols[None, :]]


def column_gather(counts, cols):
    """The reference: the terms' columns of the carry."""
    return counts[:, cols]


def same_bits(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and a.tobytes() == b.tobytes()


# ---------------------------------------------------------------------------
# the helper against the point gather
# ---------------------------------------------------------------------------


def domains(rng, n_nodes, key):
    """A [N, 2] domain table: column 0 a hostname key (a domain a node) or a
    zone key (three domains), column 1 the other one; every fifth node lacks
    the zone label and sits in the trash row D."""
    host = np.arange(n_nodes)
    zone = n_nodes + rng.integers(0, 3, n_nodes)
    D = n_nodes + 3
    zone[::5] = D
    cols = [host, zone] if key == "hostname" else [zone, host]
    return np.stack(cols, axis=1).astype(np.int32), D


#: selectors -> the terms' columns: one window (A up to 128, read as it is), two
#: windows, the second partial, and many with a column in the last, partial one
#: (5,300 = 41 x 128 + 52)
COLUMNS = {9: [4, -1, 8], 129: [0, 128, -1, 127], 5300: [4, 5299, 130, -1, 5171, 5172]}


@pytest.mark.parametrize("form", ["step", "sweep"])
@pytest.mark.parametrize("key", ["hostname", "zone"])
@pytest.mark.parametrize("A", sorted(COLUMNS))
def test_domain_counts_reads_the_cells_the_point_gather_reads(A, key, form):
    rng = np.random.default_rng(40)
    dom, D = domains(rng, 13, key)
    dom = np.concatenate([dom, dom[:, ::-1]] * 2, axis=1)[:, :len(COLUMNS[A])]
    # terms as the readers pass them: a spread's selector ids (padding is 0) and
    # inter-pod terms clamped from -1 padding to column 0
    cols = np.maximum(np.array(COLUMNS[A], np.int32), 0)
    carries = rng.standard_normal((SCENARIOS, D + 1, A)).astype(np.float32)
    if form == "step":
        got = jax.jit(kernels.domain_counts)(carries[0], dom, cols)
        want = point_gather(carries[0], dom, cols)
        slab = jax.jit(kernels.selector_columns)(carries[0], cols)
        assert same_bits(slab, column_gather(carries[0], cols))
    else:  # the sweep vmaps the scan over the scenarios' carries
        got = jax.jit(jax.vmap(kernels.domain_counts, in_axes=(0, None, None)))(carries, dom, cols)
        want = jax.vmap(point_gather, in_axes=(0, None, None))(carries, dom, cols)
        slab = jax.jit(jax.vmap(kernels.selector_columns, in_axes=(0, None)))(carries, cols)
        assert same_bits(slab, jax.vmap(column_gather, in_axes=(0, None))(carries, cols))
    assert same_bits(got, want)
    # and the reference is numpy's cell by cell
    assert same_bits(want, carries[0][dom, cols[None, :]] if form == "step" else carries[:, dom, cols[None, :]])


def keyed_domains(rng, layout):
    """A key-major [16, 3] domain table of 13 nodes and 3 pad rows (every pad
    row in the trash domain D), as the encoder numbers it, with its
    `CountKeys`: a hostname key (a domain a node, in node order), a zone key
    (three domains, every fifth node without the label) and a rack key of five
    domains; "hostname-first" and "zone-first" order the keys so; "other"
    gives the rack eleven domains, more than a compare-select reads."""
    n_nodes, n_rows = 13, 16
    racks = 11 if layout == "other" else 5
    zone = rng.integers(0, 3, n_nodes)
    zone[::5] = -1
    rack = rng.permutation(np.arange(n_nodes) % racks)
    columns = {"hostname": np.arange(n_nodes), "zone": zone, "rack": rack}
    order = ["zone", "hostname", "rack"] if layout == "zone-first" else ["hostname", "zone", "rack"]
    node_domain, base = np.full((n_rows, 3), -1, np.int32), 0
    for k, name in enumerate(order):  # the encoder's key-major numbering
        ids = {}
        for n, v in enumerate(columns[name]):
            if v >= 0:
                node_domain[n, k] = base + ids.setdefault(int(v), len(ids))
        base += len(ids)
    D = base
    node_domain[node_domain < 0] = D
    ec_np = SimpleNamespace(node_domain=node_domain, domain_topo=np.zeros(D + 1, np.int32),
                            node_valid=np.arange(n_rows) < n_nodes)
    return node_domain, D, order, kernels.count_keys_of(ec_np)


@pytest.mark.parametrize("form", ["step", "sweep"])
@pytest.mark.parametrize("layout", ["hostname-first", "zone-first", "other"])
@pytest.mark.parametrize("A", [9, 5300])
def test_domain_counts_by_key_reads_the_cells_the_point_gather_reads(A, layout, form):
    rng = np.random.default_rng(44)
    node_domain, D, order, keys = keyed_domains(rng, layout)
    if layout == "other":
        assert keys is None  # a key of eleven domains: every key is gathered
    else:
        assert keys.paths() == {"slice": 1, "select": 2}
        assert keys.base[order.index("hostname")] == (3 if layout == "zone-first" else 0)
    # six terms over the three keys, columns in the first, a middle and the last, partial window
    topo = np.array([0, 1, 2, 1, 0, 2], np.int32)
    cols = np.array([c % A for c in (4, A - 1, 130, 0, 5171, 8)], np.int32)
    dom = node_domain[:, topo]
    carries = rng.standard_normal((SCENARIOS, D + 1, A)).astype(np.float32)
    read = functools.partial(kernels.domain_counts, keys=keys)
    if form == "step":
        got = jax.jit(read)(carries[0], dom, cols, topo)
        want = point_gather(carries[0], dom, cols)
    else:
        got = jax.jit(jax.vmap(read, in_axes=(0, None, None, None)))(carries, dom, cols, topo)
        want = jax.vmap(point_gather, in_axes=(0, None, None))(carries, dom, cols)
    assert same_bits(got, want)
    # a gather of the carry or of what is read from it only where a key has no cheap read
    jaxpr = jax.make_jaxpr(read)(carries[0], dom, cols, topo).jaxpr
    gathers = [u for u in uses_of(jaxpr, derived_from(jaxpr, {jaxpr.invars[0]})) if u[0] == "gather"]
    assert bool(gathers) == (layout == "other"), gathers


# ---------------------------------------------------------------------------
# the five readers against the point gather
# ---------------------------------------------------------------------------


def _term(labels, key):
    return {"labelSelector": {"matchLabels": labels}, "topologyKey": key}


def _spread(labels, key, hard):
    return {"maxSkew": 2, "topologyKey": key, "labelSelector": {"matchLabels": labels},
            "whenUnsatisfiable": "DoNotSchedule" if hard else "ScheduleAnyway"}


def _cluster():
    """Eight nodes in three zones, two of them without a zone label (trash
    domain under the zone key); Deployments with soft and hard spread over
    both keys, required anti-affinity and affinity over each key, preferred
    terms, and one with none of them (every term column of its row padded)."""
    rt = ResourceTypes()
    for i in range(8):
        labels = {} if i in (3, 6) else {ZONE: f"z{i % 3}"}
        rt.nodes.append(fx.make_fake_node(f"n{i}", "16", "64Gi", "110", fx.with_labels(labels)))
    apps = ResourceTypes()

    def deploy(name, *opts):
        apps.deployments.append(fx.make_fake_deployment(name, 2, "100m", "128Mi", *opts))

    deploy("web", fx.with_topology_spread([_spread({"app": "web"}, ZONE, False), _spread({"app": "api"}, HOST, True)]))
    deploy("api", fx.with_topology_spread([_spread({"app": "api"}, HOST, False)]))
    deploy("db", fx.with_affinity({
        "podAntiAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": [_term({"app": "db"}, HOST)]},
        "podAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": [_term({"app": "web"}, ZONE)]},
    }))
    deploy("cache", fx.with_affinity({
        "podAntiAffinity": {"requiredDuringSchedulingIgnoredDuringExecution": [
            _term({"app": "web"}, ZONE), _term({"app": "api"}, HOST)]},
        "podAffinity": {"preferredDuringSchedulingIgnoredDuringExecution": [
            {"weight": 7, "podAffinityTerm": _term({"app": "db"}, HOST)},
            {"weight": 3, "podAffinityTerm": _term({"app": "api"}, ZONE)}]},
    }))
    deploy("plain")
    return rt, [AppResource("apps", apps)]


def _readers(ec, stat, st, u, feasible, keys=None):
    return (
        kernels.spread_filter(ec, st, u, stat.aff_mask[u] & ec.node_valid, keys),
        kernels.interpod_filter(ec, st, u, keys),
        kernels.interpod_score(ec, st, u, feasible, keys),
        kernels.spread_score(ec, stat, st, u, feasible, keys),
    )


def _run(ec, stat, carries, feasible, form, keys=None):
    """Each template's outputs of the four reader functions, as a scan step
    runs them (a traced template index) or as a sweep does (vmapped over the
    scenarios' carries), reading by `keys` (a `CountKeys`, or None). A new
    function object each call: nothing is taken from a trace made under
    another `domain_counts`."""
    base = carries[0]

    def one(dom_sel, u):
        return _readers(ec, stat, base._replace(dom_sel=dom_sel), u, feasible, keys)

    if form == "step":
        f = jax.jit(lambda u: one(base.dom_sel, u))
    else:
        f = jax.jit(lambda u: jax.vmap(one, in_axes=(0, None))(
            jnp.stack([c.dom_sel for c in carries]), u))
    return [f(u) for u in range(int(ec.req.shape[0]))]


def _readers_case(monkeypatch, window):
    """The cluster's preparation, its static tables, carries of counts and a
    feasible set; the carry read through windows of `window` columns."""
    monkeypatch.setenv("OPENSIM_DISABLE_NATIVE", "1")
    prep = prepare(*_cluster())
    ec, st0 = prep.ec, prep.st0
    f = prep.features
    assert f.spread_hard and f.spread_soft and f.interpod and f.prefg
    # windows of 4 columns, so that this small carry is read through the slab, the last window partial
    monkeypatch.setattr(kernels, "COUNT_WINDOW", window)
    A = st0.dom_sel.shape[1]
    assert (A > window and A % window) if window == 4 else A <= window
    # both keys, trash-domain nodes under the zone key, -1 padded inter-pod terms
    node_domain = np.asarray(ec.node_domain)
    D = int(ec.domain_topo.shape[0]) - 1
    assert node_domain.shape[1] == 2 and (node_domain == D).any()
    for table in (ec.an_sel, ec.at_sel, ec.pt_sel):
        assert (np.asarray(table) == -1).any() and (np.asarray(table) >= 0).any()
    stat = kernels.precompute_static(ec)
    rng = np.random.default_rng(7)
    # small whole counts, as binds make them, so every filter meets both verdicts
    carries = [st0._replace(dom_sel=jnp.asarray(rng.integers(0, 3, st0.dom_sel.shape).astype(np.float32)))
               for _ in range(SCENARIOS)]
    feasible = jnp.asarray(np.asarray(ec.node_valid) & (rng.random(ec.node_valid.shape) < 0.8))
    return prep, stat, carries, feasible


@pytest.mark.parametrize("form", ["step", "sweep"])
def test_the_five_readers_give_the_point_gathers_bits(form, monkeypatch):
    prep, stat, carries, feasible = _readers_case(monkeypatch, 4)
    _same_as_point_gather(_run(prep.ec, stat, carries, feasible, form), prep.ec, stat, carries, feasible, form,
                          monkeypatch)


@pytest.mark.parametrize("form", ["step", "sweep"])
@pytest.mark.parametrize("window", [4, 128])
def test_the_five_readers_read_by_key_with_the_point_gathers_bits(window, form, monkeypatch):
    """The readers as the XLA scan runs them: hostname read by slice, the
    zone (three domains and the trash row) by compare-select, the carry
    wider than a window and not."""
    prep, stat, carries, feasible = _readers_case(monkeypatch, window)
    keys = prep.features.count_keys
    assert keys is not None and keys.paths() == {"slice": 1, "select": 1}
    _same_as_point_gather(_run(prep.ec, stat, carries, feasible, form, keys), prep.ec, stat, carries, feasible,
                          form, monkeypatch)


def _same_as_point_gather(got, ec, stat, carries, feasible, form, monkeypatch):
    monkeypatch.setattr(kernels, "domain_counts", point_gather)
    monkeypatch.setattr(kernels, "selector_columns", column_gather)
    want = _run(ec, stat, carries, feasible, form)
    verdicts = set()
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            assert same_bits(a, b)
        verdicts.update(bool(x) for x in np.asarray(g[0]).ravel())
        verdicts.update(bool(x) for x in np.asarray(g[1]).ravel())
    assert verdicts == {True, False}


# ---------------------------------------------------------------------------
# the guard: the scan body reads the carry by windows of whole rows
# ---------------------------------------------------------------------------


def _sub_jaxprs(eqn):
    for p in eqn.params.values():
        for j in p if isinstance(p, (tuple, list)) else (p,):
            if isinstance(j, jcore.ClosedJaxpr):
                yield j.jaxpr
            elif isinstance(j, jcore.Jaxpr):
                yield j


#: primitives that pass their operands on to the sub-jaxprs they hold
CALLS = {"cond", "pjit", "jit", "closed_call", "custom_jvp_call", "custom_vjp_call", "checkpoint", "scan", "while"}


def derived_from(jaxpr, tracked):
    """The variables of `jaxpr` and of its sub-jaxprs computed from the
    `tracked` ones, these among them."""
    out = set(tracked)
    for eqn in jaxpr.eqns:
        hit = any(isinstance(v, jcore.Var) and v in out for v in eqn.invars)
        for sub in _sub_jaxprs(eqn):
            args = eqn.invars[len(eqn.invars) - len(sub.invars):]
            inner = {v for v, a in zip(sub.invars, args) if isinstance(a, jcore.Var) and a in out}
            if inner:
                out |= derived_from(sub, inner)
        if hit:
            out.update(eqn.outvars)
    return out


def uses_of(jaxpr, tracked):
    """(primitive, `slice_sizes` or None) of every equation that takes one of
    the `tracked` variables as its first operand, following them into the
    sub-jaxprs they are passed to (a cond's branches take its operands after
    the index; a call or a scan takes them one for one)."""
    out = []
    for eqn in jaxpr.eqns:
        first = eqn.invars[0] if eqn.invars else None
        if eqn.primitive.name not in CALLS and isinstance(first, jcore.Var) and first in tracked:
            sizes = eqn.params.get("slice_sizes")
            out.append((eqn.primitive.name, None if sizes is None else tuple(sizes)))
        for sub in _sub_jaxprs(eqn):
            args = eqn.invars[len(eqn.invars) - len(sub.invars):]
            inner = {v for v, a in zip(sub.invars, args) if isinstance(a, jcore.Var) and a in tracked}
            if inner:
                out += uses_of(sub, inner)
    return out


@pytest.mark.parametrize("selectors", ["tiny", 5300])
def test_the_scan_body_reads_the_count_carry_by_windows_of_whole_rows(selectors, tmp_path, monkeypatch):
    """At the tiny size's 64 selectors the carry is one window; widened to the
    full size's 5,300 (shapes alone: nothing is allocated) it is read by
    windows of whole rows. At both, hostname is read by slice and the zone by
    compare-select, and nothing gathers from the carry or a column of it."""
    import importlib

    from benchmarks.drivers import Context
    from opensim_tpu.engine.scheduler import _schedule_pods_jit, pad_pod_stream
    from opensim_tpu.planner.apply import Applier, Options
    monkeypatch.setenv("OPENSIM_DISABLE_NATIVE", "1")
    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(bench, "configs", "cl2-load-5k.json")) as f:
        config = json.load(f)
    with open(os.path.join(bench, "traffic", "fit-cl2.json")) as f:
        traffic = json.load(f)
    ctx = Context(config=config, traffic=traffic, seed=5, scratch=str(tmp_path), rehearse=True, sizes=config["tiny"])
    driver = importlib.import_module("benchmarks.drivers.plan_loop_kinds").Driver(ctx)
    driver.prepare()
    applier = Applier(Options(simon_config=driver.simon_config))
    prep = prepare(applier.load_cluster(), applier.load_apps())
    assert prep.features.spread_soft  # cl2's pods carry the system-default soft spread
    assert prep.features.count_keys.paths() == {"slice": 1, "select": 1}  # hostname, zone
    ec, st0 = prep.ec_np, prep.st0
    Dp1, A = np.asarray(st0.dom_sel).shape
    assert A <= kernels.COUNT_WINDOW
    if selectors != "tiny":
        # the selector axis is told apart by its width: no other axis of the tiny size is as wide
        assert all(d != A for a in (*ec, *st0) for d in np.shape(a) if d != A) and A not in (Dp1,)
        widen = lambda a: jax.ShapeDtypeStruct(tuple(selectors if d == A else d for d in np.shape(a)), np.asarray(a).dtype)
        ec, st0, A = jax.tree.map(widen, ec), jax.tree.map(widen, st0), selectors
    tmpl, valid, forced = pad_pod_stream(np.asarray(prep.tmpl_ids, np.int32),
                                         np.ones(len(prep.tmpl_ids), bool), np.asarray(prep.forced))
    closed = jax.make_jaxpr(functools.partial(_schedule_pods_jit, features=prep.features))(
        ec, st0, tmpl, valid, forced)

    def scans(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                yield eqn
            for sub in _sub_jaxprs(eqn):
                yield from scans(sub)

    (scan,) = list(scans(closed.jaxpr))
    body = scan.params["jaxpr"].jaxpr
    # the carry less what the scan forwards unchanged (the count tables of features that are off)
    carry = body.invars[scan.params["num_consts"]:scan.params["num_consts"] + scan.params["num_carry"]]
    (dom_sel,) = [v for v in carry if v.aval.shape == (Dp1, A)]
    uses = uses_of(body, {dom_sel})
    # no gather of the carry or of anything read from it: a per-node gather costs by the
    # element, and a gather of a wide carry's columns makes XLA lay it out by columns
    derived = uses_of(body, derived_from(body, {dom_sel}))
    assert not [u for u in derived if u[0] == "gather"], derived
    if selectors == "tiny":  # the one window is read by a lane select; the bind adds one row a topology key
        assert {name for name, _sizes in uses} == {"scatter-add"}, uses
        return
    # a spread term's counts are a window of whole rows
    reads = [sizes for name, sizes in uses if name == "dynamic_slice"]
    assert reads and all(sizes == (Dp1, kernels.COUNT_WINDOW) for sizes in reads), uses
    assert {name for name, _sizes in uses} == {"dynamic_slice", "scatter-add"}, uses
