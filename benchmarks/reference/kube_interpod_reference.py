"""The plain reference with InterPodAffinity: `kube_reference.Reference` plus
kube-scheduler's inter-pod plugin, one pod at a time.

Independent of the program under test, like the reference it extends: it
imports nothing of `opensim_tpu` and is given the cluster as plain data. A
workload here also carries a namespace and affinity terms (`Term`: selector,
namespaces, topology key, and a weight where the term is preferred); placed
pods are counted per (selector, namespaces, topology domain).

Filter, after `interpodaffinity/filtering.go` of release 1.20:
  - existing pods' required anti-affinity: a node fails if, under a label it
    carries, its domain holds a pod with a required anti-affinity term that
    matches the incoming pod (`satisfyExistingPodsAntiAffinity`);
  - the incoming pod's required anti-affinity: a node fails if it carries the
    term's topology label and its domain holds a pod the term matches;
  - the incoming pod's required affinity: every term's topology label must be
    on the node and its domain must hold a pod that matches *all* the terms
    (`podMatchesAllAffinityTerms`); or, the bootstrap rule, no pod anywhere
    matches all the terms, the pod matches them itself and the node carries
    every label (`satisfyPodAffinity`).
Score, after `scoring.go`: to every node of a domain, +w for each placed pod
there that a preferred affinity term of the incoming pod matches, -w for its
preferred anti-affinity terms; and for each placed pod there whose own term
matches the incoming pod, +w (preferred affinity), -w (preferred
anti-affinity), +1 (required affinity, `HardPodAffinityWeight` 1). A term's
namespaces are its own list or, where it has none, the namespace of the pod
that carries it. The sum is normalised as `NormalizeScore` of that release
does: 100 * (sum - min) / (max - min) over the feasible nodes with min and max
seeded with 0, and 0 everywhere where max = min. Plugin weight 1, added after
least-allocated and balanced and before the spread and the share score.

Departures from the published plugin, all shared with `kube_reference`: the
normalised score is kept unrounded in float32, as the configurations'
guarantee states (kube-scheduler truncates it to int64); label selectors are
`matchLabels` alone; replicas of a workload are identical, so counts per
workload stand for pods. A spread selector counts pods of the incoming pod's
namespace alone, which `kube_reference` has no need to tell apart.

`precision="bfloat16"` is the low-precision control, as there: every operand
and step of a score is rounded to bfloat16 (the counts too: a zone count
past 256 is no longer exact); filters stay exact.

`replay` follows the program pod by pod, in the order the program scheduled
them, where `compare.replay` follows counts per (workload, node). In this
deployment a workload puts at most one pod on most nodes and the red pods
draw each other, so one choice that differs changes every later one: a
replay by counts then reads the whole divergence (0.31 points, from one step
whose two best nodes lay 0.00006 apart: the chip's float32 division is not
IEEE's), a replay by order reads that step.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import numpy as np

from .kube_reference import (
    F32, NEG, SYSTEM_DEFAULT_SPREAD, W_BALANCED, W_LEAST, W_SHARE, W_SPREAD, Cluster,
    Reference as ResourcesReference, Workload, queue_order,
)

W_INTERPOD = 1.0
HARD_POD_AFFINITY_WEIGHT = 1.0

Order = Dict[str, List[str]]  # workload -> the node of each of its pods, in the order they were scheduled


@dataclass(frozen=True)
class Term:
    """One pod-affinity term. `namespaces` empty means the namespace of the
    pod that carries the term; `weight` counts for preferred terms only."""

    match_labels: Tuple[Tuple[str, str], ...]
    topology_key: str
    namespaces: Tuple[str, ...] = ()
    weight: int = 0


def term(match_labels: Dict[str, str], topology_key: str, namespaces=(), weight: int = 0) -> Term:
    return Term(tuple(sorted(match_labels.items())), topology_key, tuple(namespaces), weight)


@dataclass
class PodWorkload(Workload):
    namespace: str = "default"
    affinity: List[Term] = field(default_factory=list)  # required
    anti_affinity: List[Term] = field(default_factory=list)  # required
    preferred_affinity: List[Term] = field(default_factory=list)
    preferred_anti_affinity: List[Term] = field(default_factory=list)


#: what a term selects: (namespaces, matchLabels), the namespaces resolved
Selector = Tuple[Tuple[str, ...], Tuple[Tuple[str, str], ...]]


def selector_of(t: Term, owner: Workload) -> Selector:
    return (t.namespaces or (namespace_of(owner),), t.match_labels)


def namespace_of(w: Workload) -> str:
    return getattr(w, "namespace", "default")


def selects(sel: Selector, w: Workload) -> bool:
    namespaces, labels = sel
    return namespace_of(w) in namespaces and all(w.labels.get(k) == v for k, v in labels)


class Reference(ResourcesReference):
    def __init__(self, cluster: Cluster, precision: str = "float32") -> None:
        super().__init__(cluster, precision)
        #: placed pods that match every selector of the key, per domain of the
        #: topology key: (selectors, topology key) -> float32 [domains]
        self._matching: Dict[Tuple[Tuple[Selector, ...], str], np.ndarray] = {}
        #: placed pods that carry a required anti-affinity term, per domain:
        #: (selector, topology key) -> float32 [domains]
        self._anti_carried: Dict[Tuple[Selector, str], np.ndarray] = {}
        #: signed weights of the scoring terms placed pods carry (preferred
        #: +w and -w, required affinity +1), summed per domain
        self._weight_carried: Dict[Tuple[Selector, str], np.ndarray] = {}
        #: the node of every pod bound so far, per workload, in order
        self._bound: Dict[int, List[int]] = {}
        for w in cluster.workloads:
            for (sel, key), _weight in self._scoring_terms(w):
                self._weight_carried.setdefault((sel, key), np.zeros(self._size(key), F32))
            for t in getattr(w, "anti_affinity", ()):
                self._anti_carried.setdefault((selector_of(t, w), t.topology_key), np.zeros(self._size(t.topology_key), F32))

    def _size(self, key: str) -> int:
        return max(self._domains(key)[1], 1)

    @staticmethod
    def _scoring_terms(w: Workload) -> List[Tuple[Tuple[Selector, str], float]]:
        """The terms a placed pod of `w` scores an incoming pod by."""
        out = []
        for t in getattr(w, "preferred_affinity", ()):
            out.append(((selector_of(t, w), t.topology_key), float(t.weight)))
        for t in getattr(w, "preferred_anti_affinity", ()):
            out.append(((selector_of(t, w), t.topology_key), -float(t.weight)))
        for t in getattr(w, "affinity", ()):
            out.append(((selector_of(t, w), t.topology_key), HARD_POD_AFFINITY_WEIGHT))
        return out

    def _count_map(self, selectors: Tuple[Selector, ...], key: str) -> np.ndarray:
        """Placed pods matching all of `selectors`, per domain of `key`;
        built from what is placed on first use, kept up by `bind`."""
        got = self._matching.get((selectors, key))
        if got is None:
            dom, _size = self._domains(key)
            has = dom >= 0
            got = self._matching[(selectors, key)] = np.zeros(self._size(key), F32)
            for wj, placed in self.placed.items():
                if all(selects(s, self.cluster.workloads[wj]) for s in selectors):
                    np.add.at(got, dom[has], placed[has].astype(F32))
        return got

    def _enter(self, wi: int) -> dict:
        state = super()._enter(wi)
        w = self.cluster.workloads[wi]
        # a spread selector counts pods of the incoming pod's own namespace
        cons = w.spread if w.spread is not None else (
            [(key, skew, dict(w.labels)) for key, skew in SYSTEM_DEFAULT_SPREAD] if w.labels else [])
        for c, (key, _skew, match) in zip(state["spread"], cons):
            sel: Selector = ((namespace_of(w),), tuple(sorted(match.items())))
            c["counts"] = self._count_map((sel,), key)
            c["self"] = False  # `bind` keeps the shared map up for every constraint
        required = tuple(selector_of(t, w) for t in getattr(w, "affinity", ()))
        state["interpod"] = {
            "anti": [self._keyed(t.topology_key, self._count_map((selector_of(t, w),), t.topology_key))
                     for t in getattr(w, "anti_affinity", ())],
            "affinity": [self._keyed(t.topology_key, self._count_map(required, t.topology_key))
                         for t in getattr(w, "affinity", ())],
            "matches_own_affinity": all(selects(s, w) for s in required),
            "preferred": [
                dict(self._keyed(t.topology_key, self._count_map((selector_of(t, w),), t.topology_key)),
                     weight=F32(sign * t.weight))
                for sign, terms in ((1, getattr(w, "preferred_affinity", ())),
                                    (-1, getattr(w, "preferred_anti_affinity", ())))
                for t in terms],
            # what placed pods hold against, or for, a pod like this one
            "existing_anti": [self._keyed(key, carried) for (sel, key), carried in self._anti_carried.items()
                              if selects(sel, w)],
            "existing_weight": [self._keyed(key, carried) for (sel, key), carried in self._weight_carried.items()
                                if selects(sel, w)],
        }
        return state

    def _keyed(self, key: str, counts: np.ndarray) -> dict:
        dom, _size = self._domains(key)
        return {"dom": np.maximum(dom, 0), "has": dom >= 0, "counts": counts}

    # -- one pod ------------------------------------------------------------

    def _interpod_filter(self) -> np.ndarray:
        ip = self._w["interpod"]
        ok = np.ones(self.n, bool)
        for c in ip["existing_anti"] + ip["anti"]:
            ok &= ~(c["has"] & (c["counts"][c["dom"]] > 0))
        if ip["affinity"]:
            labels = np.ones(self.n, bool)
            pods_exist = np.ones(self.n, bool)
            anywhere = 0.0
            for c in ip["affinity"]:
                labels &= c["has"]
                pods_exist &= c["has"] & (c["counts"][c["dom"]] > 0)
                anywhere += float(c["counts"].sum())
            bootstrap = anywhere == 0 and ip["matches_own_affinity"]
            ok &= pods_exist | (labels & bootstrap)
        return ok

    def _interpod_score(self, feasible: np.ndarray) -> np.ndarray:
        ip, q = self._w["interpod"], self.q
        raw = np.zeros(self.n, F32)
        for c in ip["preferred"]:
            raw = q(raw + np.where(c["has"], q(q(c["counts"])[c["dom"]] * c["weight"]), F32(0.0)))
        for c in ip["existing_weight"]:
            raw = q(raw + np.where(c["has"], q(c["counts"])[c["dom"]], F32(0.0)))
        seen = np.where(feasible, raw, F32(0.0))
        hi, lo = max(seen.max(), F32(0.0)), min(seen.min(), F32(0.0))
        rng = q(hi - lo)
        if not rng > 0:
            return np.zeros(self.n, F32)
        return q(q(F32(100.0) * q(raw - lo)) / max(rng, F32(1.0)))

    def step(self) -> Tuple[np.ndarray, np.ndarray]:
        """(feasible [n] bool, weighted score [n] float32) for the next pod
        of the entered workload: `kube_reference`'s step with the inter-pod
        filter beside its filters and the inter-pod score between the
        resource scores and the spread, the order the weighted sum is taken
        in."""
        w, q = self._w, self.q
        cpu, mem = w["cpu"], w["mem"]
        feasible = (
            w["sel"]
            & ~((cpu > 0) & (self.used_cpu + cpu > self.cap_cpu))
            & ~((mem > 0) & (self.used_mem + mem > self.cap_mem))
            & ~(self.used_pods + F32(1.0) > self.cap_pods)
            & self._interpod_filter()
        )
        hundred = F32(100.0)
        rc = q(q(self.used_cpu) + q(cpu if cpu > 0 else F32(100.0)))
        rm = q(q(self.used_mem) + q(mem if mem > 0 else F32(200.0 * 1024 * 1024)))
        cc, cm = q(self.cap_cpu), q(self.cap_mem)
        dc, dm = np.maximum(cc, F32(1.0)), np.maximum(cm, F32(1.0))
        lc = np.where((cc == 0) | (rc > cc), F32(0.0), q(q(q(cc - rc) * hundred) / dc))
        lm = np.where((cm == 0) | (rm > cm), F32(0.0), q(q(q(cm - rm) * hundred) / dm))
        least = q(q(lc + lm) / F32(2.0))
        fc, fm = q(rc / dc), q(rm / dm)
        bal = np.where((fc >= 1.0) | (fm >= 1.0), F32(0.0),
                       q(q(F32(1.0) - np.abs(q(fc - fm))) * hundred))
        score = q(q(F32(W_BALANCED) * bal) + q(F32(W_LEAST) * least))
        score = q(score + q(F32(W_INTERPOD) * self._interpod_score(feasible)))
        if w["spread"]:
            raw = np.zeros(self.n, F32)
            for c in w["spread"]:
                cnt = q(c["counts"])[c["dom"]]
                raw = q(raw + np.where(c["has"], q(q(cnt * q(c["weight"])) + c["skew1"]), F32(0.0)))
            scored = feasible & ~w["ignored"]
            if scored.any():
                mn, mx = raw[scored].min(), raw[scored].max()
            else:
                mn, mx = F32(1e30), F32(-1e30)
            if mx <= 0:
                norm = np.full(self.n, hundred, F32)
            else:
                norm = q(q(hundred * q(q(mx + mn) - raw)) / max(mx, F32(1.0)))
            norm = np.where(feasible & w["ignored"], F32(0.0), norm)
            score = q(score + q(F32(W_SPREAD) * norm))
        sr = w["share_raw"]
        if feasible.any():
            lo, hi = sr[feasible].min(), sr[feasible].max()
            rng = q(hi - lo)
            if rng > 0:
                score = q(score + q(F32(W_SHARE) * q(q(q(sr - lo) * hundred) / rng)))
        return feasible, score.astype(F32)

    def bind(self, node: int) -> None:
        super().bind(node)
        w = self.cluster.workloads[self._w["wi"]]
        self._bound.setdefault(self._w["wi"], []).append(node)
        for (selectors, key), counts in self._matching.items():
            dom, _size = self._domains(key)
            if dom[node] >= 0 and all(selects(s, w) for s in selectors):
                counts[dom[node]] += F32(1.0)
        for t in getattr(w, "anti_affinity", ()):
            dom, _size = self._domains(t.topology_key)
            if dom[node] >= 0:
                self._anti_carried[(selector_of(t, w), t.topology_key)][dom[node]] += F32(1.0)
        for (sel, key), weight in self._scoring_terms(w):
            dom, _size = self._domains(key)
            if dom[node] >= 0:
                self._weight_carried[(sel, key)][dom[node]] += F32(weight)

    def order(self) -> Order:
        """What was bound, in the form `replay` takes: how the control (this
        reference in lower precision) is put in the program's place."""
        nodes, workloads = self.cluster.nodes, self.cluster.workloads
        return {workloads[wi].name: [nodes[i].name for i in seq] for wi, seq in self._bound.items()}


def replay(cluster: Cluster, placed: Order, unscheduled: Dict[str, int],
           precision: str = "float32") -> Dict[str, float]:
    """The program's answer followed pod by pod. At each pod the reference
    computes its own filter and scores from the state built so far; a pod the
    program put elsewhere than the reference's best node is misplaced and the
    score it gave up is recorded; one it put where the filter (a required
    term included) says no is infeasible. Then the reference binds where the
    program did, so each choice is judged in the state the program made it."""
    ref = Reference(cluster, precision)
    by_name = {w.name: i for i, w in enumerate(cluster.workloads)}
    out = {"misplaced_pods": 0, "worst_score_gap": 0.0, "infeasible_pods": 0,
           "unscheduled_diff": 0, "answer_diff": 0}
    out["answer_diff"] += sum(len(seq) for wname, seq in placed.items() if wname not in by_name)
    out["answer_diff"] += sum(k for wname, k in unscheduled.items() if wname not in by_name)
    for wi in queue_order(cluster.workloads):
        w = cluster.workloads[wi]
        said_unsched = int(unscheduled.get(w.name, 0))
        ref._enter(wi)
        followed = 0
        for name in placed.get(w.name, ()):
            node = ref.index.get(name)
            if node is None:
                out["answer_diff"] += 1
                continue
            followed += 1
            feasible, score = ref.step()
            if not feasible[node]:
                out["infeasible_pods"] += 1
            elif node != (best := int(np.argmax(np.where(feasible, score, NEG)))):
                out["misplaced_pods"] += 1
                out["worst_score_gap"] = max(out["worst_score_gap"], float(score[best] - score[node]))
            ref.bind(node)
        out["answer_diff"] += abs(w.replicas - followed - said_unsched)
        if said_unsched and ref.step()[0].any():
            out["unscheduled_diff"] += said_unsched
    return out
