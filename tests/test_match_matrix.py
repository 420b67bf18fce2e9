"""`TemplateSet.match_matrix` tests a template only against the selectors
that can match it (those of its namespace that name one of its labels, those
with expressions alone, the conjunctions). The double loop it replaced is
kept here as the plain form: the two give the same matrix, bit for bit, on
seeded random templates and selectors, and after templates and selectors are
appended to a set whose matrix was already built."""

import random

import numpy as np
import pytest

from opensim_tpu.encoding.templates import SchedTemplate, TemplateSet, selector_matches
from opensim_tpu.obs import trace as obs

NAMESPACES = ["default", "load-0", "load-1", "load-2"]
KEYS = ["group", "name", "tier", "color"]
VALUES = ["load", "a", "b", "c", ""]
OPERATORS = ["In", "NotIn", "Exists", "DoesNotExist"]


def plain_matrix(ts: TemplateSet) -> np.ndarray:
    """Every template against every selector."""
    m = np.zeros((len(ts.templates), len(ts.selectors)), dtype=bool)
    for u, t in enumerate(ts.templates):
        for a, canon in enumerate(ts.selectors):
            m[u, a] = selector_matches(canon, t.namespace, t.labels)
    return m


def random_labels(rng: random.Random) -> dict:
    return {k: rng.choice(VALUES) for k in rng.sample(KEYS, rng.randrange(0, len(KEYS) + 1))}


def random_selector(rng: random.Random, kind: str):
    if kind == "nil":
        return None
    sel: dict = {}
    if kind in ("labels", "both"):
        sel["matchLabels"] = random_labels(rng) or {"group": "load"}
    if kind in ("expressions", "both"):
        sel["matchExpressions"] = [
            {"key": rng.choice(KEYS), "operator": op,
             "values": rng.sample(VALUES, rng.randrange(1, 3)) if op in ("In", "NotIn") else []}
            for op in rng.sample(OPERATORS, rng.randrange(1, len(OPERATORS) + 1))
        ]
    return sel  # kind "empty": {} matches every pod of its namespaces


def grow(ts: TemplateSet, rng: random.Random, templates: int, selectors: int) -> None:
    for _ in range(templates):
        ts.templates.append(SchedTemplate(namespace=rng.choice(NAMESPACES), labels=random_labels(rng)))
    ids = []
    for _ in range(selectors):
        kind = rng.choice(["labels", "labels", "expressions", "both", "empty", "nil"])
        ns = rng.choice(NAMESPACES) if rng.random() < 0.8 else tuple(rng.sample(NAMESPACES, 2) + [NAMESPACES[0]])
        ids.append(ts.selector_id(ns, random_selector(rng, kind)))
    for _ in range(max(1, selectors // 8)):
        ts.conjunction_id(rng.sample(ids, min(len(ids), rng.randrange(2, 4))))


def one_of_each_operator(ts: TemplateSet) -> None:
    for op in OPERATORS:
        values = ["load", "a"] if op in ("In", "NotIn") else []
        ts.selector_id("load-0", {"matchExpressions": [{"key": "group", "operator": op, "values": values}]})
        ts.selector_id("load-1", {"matchLabels": {"name": "a"},
                                  "matchExpressions": [{"key": "tier", "operator": op, "values": values}]})


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 2147483659])
def test_the_indexed_matrix_is_the_double_loops(seed):
    rng = random.Random(seed)
    ts = TemplateSet()
    one_of_each_operator(ts)
    grow(ts, rng, templates=60, selectors=40)
    got = ts.match_matrix()
    assert got.dtype == np.bool_ and got.shape == (len(ts.templates), len(ts.selectors))
    assert np.array_equal(got, plain_matrix(ts))
    assert got.any() and not got.all()


@pytest.mark.parametrize("seed", [4, 5, 3000000019])
@pytest.mark.parametrize("more_templates,more_selectors", [(25, 0), (0, 15), (25, 15)])
def test_an_incremental_build_is_the_double_loops(seed, more_templates, more_selectors):
    rng = random.Random(seed)
    ts = TemplateSet()
    grow(ts, rng, templates=40, selectors=30)
    first = ts.match_matrix()
    fork = ts.clone()
    grow(fork, rng, more_templates, more_selectors)
    got = fork.match_matrix()
    assert np.array_equal(got, plain_matrix(fork))
    assert np.array_equal(got[: first.shape[0], : first.shape[1]], first)
    assert ts._mm is first  # the base set's matrix is replaced in the fork, never written


def test_a_selector_counts_the_pods_of_its_own_namespace_only():
    """The load test's namespaces repeat every object name and label."""
    ts = TemplateSet()
    labels = {"group": "load", "name": "small-deployment-0"}
    for ns in ("load-0", "load-1"):
        ts.templates.append(SchedTemplate(namespace=ns, labels=dict(labels)))
        ts.selector_id(ns, {"matchLabels": dict(labels)})
    both = ts.selector_id(("load-0", "load-1"), {"matchLabels": {"group": "load"}})
    got = ts.match_matrix()
    assert got[:, :2].tolist() == [[True, False], [False, True]]
    assert got[:, both].all()


def test_the_span_counts_the_tests_made_and_they_are_few():
    """50 namespaces of 100 workloads, each with a selector of its own: every
    template meets its own selector and no other."""
    ts = TemplateSet()
    for n in range(50):
        for w in range(100):
            labels = {"group": "load", "name": f"small-deployment-{w}"}
            ts.templates.append(SchedTemplate(namespace=f"load-{n}", labels=labels))
            ts.selector_id(f"load-{n}", {"matchLabels": labels})
    tr = obs.start_trace("test", force=True)
    with obs.trace_scope(tr):
        got = ts.match_matrix()
    tr.finish()
    assert np.array_equal(got, np.eye(5000, dtype=bool))
    (span,) = [sp for sp in tr.root.children if sp.name == "encode.match"]
    assert span.attrs["templates"] == 5000 and span.attrs["selectors"] == 5000
    # filed under `name`, which one selector a namespace names, not under
    # `group: load`, which all of them do
    assert span.attrs["evaluated"] == 5000
    # nothing new: nothing is tested again
    tr = obs.start_trace("test", force=True)
    with obs.trace_scope(tr):
        ts.match_matrix()
    tr.finish()
    assert tr.root.children[0].attrs["evaluated"] == 0
