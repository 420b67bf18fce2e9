"""`plan-loop-kinds`: `plan-loop-ref` for a configuration whose workloads are
of several kinds. A StatefulSet's pods are named `<set>-<ordinal>` and carry
no generated suffix, so `plan_loop_ref.scheduling_order` would take each for a
workload of its own: here a pod whose name less its last segment is one of
the configuration's StatefulSets belongs to that set, at that ordinal. The
window, the comparison and the control are `plan_loop_ref.Driver`'s."""

from __future__ import annotations

from typing import Dict, List, Set

from . import canon_pod_ref, plan_loop_ref


def scheduling_order(path: str, stateful: Set[str]) -> Dict[str, List[str]]:
    """`plan_loop_ref.scheduling_order`, with the pods of the StatefulSets
    in `stateful` (`ns/name`) ordered by their ordinals."""
    rows, section = [], ""
    with open(path) as f:
        for line in f:
            line = line.rstrip("\n")
            if line in ("Node Info", "Pod Info", "App Info"):
                section = line
            elif section == "Pod Info" and " | " in line and not line.startswith("Node "):
                node, pod = (c.strip() for c in line.split("|")[:2])
                owner, _, last = pod.rpartition("-")
                if owner in stateful:
                    rows.append((int(last), owner, node))
                else:
                    rows.append((int(last, 16), canon_pod_ref(pod), node))
    order: Dict[str, List[str]] = {}
    for _made, workload, node in sorted(rows):
        order.setdefault(workload, []).append(node)
    return order


class Driver(plan_loop_ref.Driver):
    def after_window(self, window) -> None:
        super().after_window(window)
        cluster = self.inputs["variants"][self.variant]["cluster"]
        stateful = {w.name for w in cluster.workloads if w.kind == "StatefulSet"}
        for it in window.items:
            if it.info.get("report") is not None:
                it.info["report"]["placed"] = scheduling_order(it.answer, stateful)
