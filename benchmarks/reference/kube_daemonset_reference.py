"""The plain reference for a cluster of namespaces and workload kinds:
`kube_reference.Reference` with DaemonSets, StatefulSets, Jobs and
namespaces, one pod at a time.

Independent of the program under test, like the reference it extends: it
imports nothing of `opensim_tpu` and is given the cluster as plain data. A
workload here carries a namespace and a kind.

  - A DaemonSet is one pod a node, in the order of the node list. The
    controller pins each pod to its node by a required node affinity on
    `metadata.name` (`SetDaemonSetPodNodeNameByNodeAffinity`), so its Filter
    admits that node alone and NodeResourcesFit decides there: a pod whose
    node refuses it is unschedulable and goes nowhere else. The pods of one
    DaemonSet differ (each has a node of its own), so one that fails says
    nothing of the next.
  - PodTopologySpread's system defaults (hostname maxSkew 3, zone maxSkew 5,
    ScheduleAnyway) apply to a pod for which `DefaultSelector` finds a
    selector: one owned by a ReplicaSet (a Deployment's pods) or a
    StatefulSet. It finds none for a Job's or a DaemonSet's pod (with no
    Service selecting it), so those pods carry no spread constraint and the
    plugin scores them nothing.
  - A spread selector counts the pods of the incoming pod's own namespace
    alone: 50 namespaces that repeat every object name and label do not see
    each other.

Departures from the published plugins are `kube_reference`'s: scores kept
unrounded in float32, label selectors `matchLabels` alone (the pod's own
labels stand for its owner's selector), replicas of a workload identical.

`precision="bfloat16"` is the low-precision control, as there: every operand
and step of a score is rounded to bfloat16; filters stay exact.

`replay` follows the program pod by pod, in the order the program scheduled
them, as `kube_interpod_reference.replay` does. A DaemonSet's pods are told
apart by the node each belongs to: the answer names the node of each pod
that was placed, and the reference walks the node list.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

from .kube_reference import (
    F32, NEG, SYSTEM_DEFAULT_SPREAD, Cluster, Reference as ResourcesReference, Workload, queue_order,
)

#: the kinds `DefaultSelector` finds a selector for (a Deployment's pods are a ReplicaSet's)
SPREAD_BY_DEFAULT = ("Deployment", "ReplicaSet", "StatefulSet")

Order = Dict[str, List[str]]  # workload -> the node of each of its pods, in the order they were scheduled


@dataclass
class KindWorkload(Workload):
    namespace: str = "default"
    kind: str = "Deployment"

    def __post_init__(self) -> None:
        if self.spread is None and self.kind not in SPREAD_BY_DEFAULT:
            self.spread = []  # no selector, no default constraint


def selects(namespace: str, match: Dict[str, str], w: Workload) -> bool:
    return w.namespace == namespace and all(w.labels.get(k) == v for k, v in match.items())


class Reference(ResourcesReference):
    def __init__(self, cluster: Cluster, precision: str = "float32") -> None:
        super().__init__(cluster, precision)
        self._by_namespace: Dict[str, List[int]] = {}
        for wi, w in enumerate(cluster.workloads):
            self._by_namespace.setdefault(w.namespace, []).append(wi)
        #: placed pods a selector matches, per domain of a topology key:
        #: (namespace, matchLabels, topology key) -> float32 [domains]
        self._matching: Dict[Tuple[str, Tuple[Tuple[str, str], ...], str], np.ndarray] = {}
        #: workload -> the (topology key, counts) of the maps above that its pods are counted in
        self._counted_in: Dict[int, List[Tuple[str, np.ndarray]]] = {}
        #: the node of every pod bound so far, per workload, in order
        self._bound: Dict[int, List[int]] = {}

    def _count_map(self, namespace: str, match: Dict[str, str], key: str) -> np.ndarray:
        """Placed pods of `namespace` that `match` selects, per domain of
        `key`; built from what is placed on first use, kept up by `bind`."""
        name = (namespace, tuple(sorted(match.items())), key)
        got = self._matching.get(name)
        if got is None:
            dom, size = self._domains(key)
            has = dom >= 0
            got = self._matching[name] = np.zeros(max(size, 1), F32)
            for wj in self._by_namespace[namespace]:
                if selects(namespace, match, self.cluster.workloads[wj]):
                    self._counted_in.setdefault(wj, []).append((key, got))
                    if wj in self.placed:
                        np.add.at(got, dom[has], self.placed[wj][has].astype(F32))
        return got

    def _enter(self, wi: int) -> dict:
        w = self.cluster.workloads[wi]
        # `kube_reference` counts a selector's pods by their labels alone, in
        # every namespace: it is shown no placed pod, and the counts are the
        # maps above, of the pod's own namespace
        everything, self.placed = self.placed, {}
        try:
            state = super()._enter(wi)
        finally:
            everything.setdefault(wi, self.placed[wi])
            self.placed = everything
        cons = w.spread if w.spread is not None else (
            [(key, skew, dict(w.labels)) for key, skew in SYSTEM_DEFAULT_SPREAD] if w.labels else [])
        for c, (key, _skew, match) in zip(state["spread"], cons):
            c["counts"] = self._count_map(w.namespace, match, key)
            c["self"] = False  # `bind` keeps the shared map up
        return state

    def pin(self, node: int) -> None:
        """The next pod of the entered DaemonSet is the one of `node`: its
        node affinity admits that node alone."""
        only = np.zeros(self.n, bool)
        only[node] = True
        self._w["sel"] = only

    def bind(self, node: int) -> None:
        super().bind(node)
        wi = self._w["wi"]
        self._bound.setdefault(wi, []).append(node)
        for key, counts in self._counted_in.get(wi, ()):
            dom, _size = self._domains(key)
            if dom[node] >= 0:
                counts[dom[node]] += F32(1.0)

    def free_run(self, stop_at_unschedulable: bool = False) -> Tuple[Dict[int, np.ndarray], Dict[int, int]]:
        """`kube_reference.Reference.free_run` with the DaemonSets' pods
        pinned, each to its node."""
        unscheduled: Dict[int, int] = {}
        for wi in queue_order(self.cluster.workloads):
            self._enter(wi)
            w = self.cluster.workloads[wi]
            for i in range(w.replicas):
                if w.kind == "DaemonSet":
                    self.pin(i)
                feasible, score = self.step()
                if feasible.any():
                    self.bind(int(np.argmax(np.where(feasible, score, NEG))))
                    continue
                if stop_at_unschedulable:
                    unscheduled[wi] = unscheduled.get(wi, 0) + 1
                    return self.placed, unscheduled
                if w.kind != "DaemonSet":
                    # identical pods and resources only deplete: the rest fail too
                    unscheduled[wi] = w.replicas - i
                    break
                unscheduled[wi] = unscheduled.get(wi, 0) + 1
        return self.placed, unscheduled

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
    score it gave up is recorded; one it put where the filter says no is
    infeasible. Then the reference binds where the program did, so each
    choice is judged in the state the program made it.

    A DaemonSet's answer is a node for each pod placed. The reference walks
    the node list: a node the answer names is that node's own pod, judged by
    the filter there; a node it does not name is a pod the program left
    unschedulable, which counts in `unscheduled_diff` if the reference's
    filter admits it; and a node named more often than once holds pods that
    belong elsewhere, each infeasible."""
    ref = Reference(cluster, precision)
    by_name = {w.name: i for i, w in enumerate(cluster.workloads)}
    out = {"misplaced_pods": 0, "worst_score_gap": 0.0, "infeasible_pods": 0,
           "unscheduled_diff": 0, "answer_diff": 0}
    out["answer_diff"] += sum(len(seq) for wname, seq in placed.items() if wname not in by_name)
    out["answer_diff"] += sum(k for wname, k in unscheduled.items() if wname not in by_name)
    for wi in queue_order(cluster.workloads):
        w = cluster.workloads[wi]
        said_unsched = int(unscheduled.get(w.name, 0))
        answer = [ref.index.get(name) for name in placed.get(w.name, ())]
        out["answer_diff"] += sum(1 for node in answer if node is None)
        answer = [node for node in answer if node is not None]
        ref._enter(wi)
        if w.kind == "DaemonSet":
            times = np.bincount(np.array(answer, np.int64), minlength=ref.n)
            for node in range(ref.n):
                ref.pin(node)
                feasible, _score = ref.step()
                if times[node]:
                    out["infeasible_pods"] += int(not feasible[node])
                    ref.bind(node)
                elif feasible[node]:
                    out["unscheduled_diff"] += 1
            for node in np.nonzero(times > 1)[0]:
                for _ in range(int(times[node]) - 1):
                    out["infeasible_pods"] += 1
                    ref.bind(int(node))
            continue
        for node in answer:
            feasible, score = ref.step()
            if not feasible[node]:
                out["infeasible_pods"] += 1
            elif node != (best := int(np.argmax(np.where(feasible, score, NEG)))):
                out["misplaced_pods"] += 1
                out["worst_score_gap"] = max(out["worst_score_gap"], float(score[best] - score[node]))
            ref.bind(node)
        out["answer_diff"] += abs(w.replicas - len(answer) - said_unsched)
        if said_unsched and ref.step()[0].any():
            out["unscheduled_diff"] += said_unsched
    return out
