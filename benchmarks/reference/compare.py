"""The comparison that decides `correct`: the program's answer replayed
through the plain reference.

An answer is a count of pods per (workload, node) and a count of pods left
unschedulable per workload (replicas of one workload are identical, and their
generated names differ from process to process, so counts are the identity of
an answer). The reference walks the queue; at each pod it computes its own
filter and scores from the state built so far and takes its own best node if
the program still has a pod of that workload to put there. If not, the program
chose otherwise at this or an earlier step: the reference follows the program
to the best-scoring node the program still has a pod for, counts one misplaced
pod and records by how much that node's score lies below the reference's best.
So one rounding tie cannot cascade, and a truly different placement shows as a
score gap.

Numbers compared (each printed beside its limit):
  misplaced_pods     pods the program put elsewhere than the reference's best
  worst_score_gap    largest score deficit of such a pod, in score points
  infeasible_pods    pods the program put where the reference's filter says no
  unscheduled_diff   pods one side schedules and the other reports unschedulable
  answer_diff        pods of the answer on unknown nodes or workloads, or missing
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from .kube_reference import NEG, Cluster, Reference, queue_order

Counts = Dict[str, Dict[str, int]]  # workload -> node name -> pods


def replay(cluster: Cluster, placed: Counts, unscheduled: Dict[str, int],
           precision: str = "float32") -> Dict[str, float]:
    ref = Reference(cluster, precision)
    by_name = {w.name: i for i, w in enumerate(cluster.workloads)}
    out = {"misplaced_pods": 0, "worst_score_gap": 0.0, "infeasible_pods": 0,
           "unscheduled_diff": 0, "answer_diff": 0}
    program: Dict[int, np.ndarray] = {}
    for wname, nodes in placed.items():
        wi = by_name.get(wname)
        if wi is None:
            out["answer_diff"] += sum(nodes.values())
            continue
        arr = program.setdefault(wi, np.zeros(ref.n, np.int64))
        for node, k in nodes.items():
            i = ref.index.get(node)
            if i is None:
                out["answer_diff"] += k
            else:
                arr[i] += k
    for wname in unscheduled:
        if wname not in by_name:
            out["answer_diff"] += unscheduled[wname]
    for wi in queue_order(cluster.workloads):
        w = cluster.workloads[wi]
        remaining = program.get(wi, np.zeros(ref.n, np.int64)).copy()
        said_unsched = int(unscheduled.get(w.name, 0))
        out["answer_diff"] += abs(w.replicas - int(remaining.sum()) - said_unsched)
        ref._enter(wi)
        left = int(remaining.sum())
        while left:
            feasible, score = ref.step()
            cand = feasible & (remaining > 0)
            if not cand.any():
                out["infeasible_pods"] += left
                break
            masked = np.where(feasible, score, NEG)
            best = int(np.argmax(masked))
            if remaining[best] > 0:
                choose = best
            else:
                choose = int(np.argmax(np.where(cand, score, NEG)))
                out["misplaced_pods"] += 1
                out["worst_score_gap"] = max(out["worst_score_gap"], float(masked[best] - score[choose]))
            ref.bind(choose)
            remaining[choose] -= 1
            left -= 1
        if left:
            continue
        feasible, _ = ref.step()
        could = bool(feasible.any())
        if said_unsched and could:
            out["unscheduled_diff"] += said_unsched
    return out


def counts_of(placed: Dict[int, np.ndarray], unscheduled: Dict[int, int],
              cluster: Cluster) -> Tuple[Counts, Dict[str, int]]:
    """A reference run's own answer in the form `replay` takes: how the
    control (the reference in lower precision) is put in the program's place."""
    out: Counts = {}
    for wi, arr in placed.items():
        nodes = {cluster.nodes[i].name: int(arr[i]) for i in np.nonzero(arr)[0]}
        if nodes:
            out[cluster.workloads[wi].name] = nodes
    return out, {cluster.workloads[wi].name: k for wi, k in unscheduled.items()}
