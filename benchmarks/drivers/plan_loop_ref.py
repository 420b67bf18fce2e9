"""`plan-loop-ref`: `plan-loop` with the plain reference the configuration
names under `"reference"` (a module of `benchmarks/reference/` with
`Reference` and `replay`), so that a configuration whose pods carry what
`kube_reference` leaves out brings data and a reference, and no driver. The
window is `plan_loop.Driver`'s, unchanged; the comparison and the control take
the other reference, and an answer is the node of every pod in the order the
program scheduled them, not a count per (workload, node): the generated names
of a plan's pods end in a counter that grows as the pods are made, which is
the order of the stream."""

from __future__ import annotations

import importlib
from typing import Dict, List

from benchmarks.window import Window

from . import canon_pod_ref, checks_from, plan_loop


def scheduling_order(path: str) -> Dict[str, List[str]]:
    """The Pod Info table of a report as workload -> the node of each of its
    pods, by the counter their generated names end in."""
    rows, section = [], ""
    with open(path) as f:
        for line in f:
            line = line.rstrip("\n")
            if line in ("Node Info", "Pod Info", "App Info"):
                section = line
            elif section == "Pod Info" and " | " in line and not line.startswith("Node "):
                node, pod = (c.strip() for c in line.split("|")[:2])
                rows.append((int(pod.rsplit("-", 1)[1], 16), canon_pod_ref(pod), node))
    order: Dict[str, List[str]] = {}
    for _made, workload, node in sorted(rows):
        order.setdefault(workload, []).append(node)
    return order


class Driver(plan_loop.Driver):
    def __init__(self, ctx) -> None:
        super().__init__(ctx)
        self.ref = importlib.import_module("benchmarks.reference." + ctx.config["reference"])

    def after_window(self, window) -> None:
        super().after_window(window)
        for it in window.items:
            if it.info.get("report") is not None:
                it.info["report"]["placed"] = scheduling_order(it.answer)

    def compare(self, window, answer=None) -> List[dict]:
        """`plan_loop.Driver.compare`, followed pod by pod through `self.ref`."""
        precision = self.ctx.config["precision"]
        cluster = self.inputs["variants"][self.variant]["cluster"]
        reports = [it.info.get("report") for it in window.items]
        first = answer or next((r for r in reports if r is not None), None)
        values = {"plans_differing": 0, "plans_unanswered": sum(1 for r in reports if r is None)}
        if first is None:
            values.update(misplaced_pods=0, worst_score_gap=0.0, infeasible_pods=0,
                          unscheduled_diff=0, answer_diff=sum(w.replicas for w in cluster.workloads),
                          added_nodes_diff=0)
            return checks_from(values, self.ctx.limits)
        known = {nd.name for nd in cluster.nodes}

        def canon(rep: dict):
            """New nodes carry generated names: number them in the report's order."""
            ren = {n: f"new-{k}" for k, n in enumerate(x for x in rep["node_order"] if x not in known)}
            return rep["added"], {w: [ren.get(n, n) for n in nodes] for w, nodes in rep["placed"].items()}

        added, placed = canon(first)
        if answer is None:
            values["plans_differing"] = sum(1 for r in reports if r is not None and canon(r) != (added, placed))
        values.update(self.ref.replay(cluster.with_new_nodes(added), placed, {}, precision))
        # the count of added nodes is exact: everything schedules with `added`
        # new nodes (the replay above) and something is left over with one fewer
        diff = 0
        if cluster.new_node is not None and added > 0:
            fewer = self.ref.Reference(cluster.with_new_nodes(added - 1), precision)
            _placed, unscheduled = fewer.free_run(stop_at_unschedulable=True)
            diff = 0 if unscheduled else 1
        elif cluster.new_node is None and added:
            diff = added
        values["added_nodes_diff"] = diff
        return checks_from(values, self.ctx.limits)

    def control(self, precision: str) -> List[dict]:
        """`plan_loop.Driver.control`: `self.ref` in a lower precision, put in
        the program's place and judged like a window's answer."""
        base = cluster = self.inputs["variants"][self.variant]["cluster"]
        added = 0
        ref = self.ref.Reference(cluster, precision)
        _placed, unscheduled = ref.free_run()
        if unscheduled and base.new_node is not None:
            added = -(-sum(unscheduled.values()) // base.new_node.pods)
            while True:
                cluster = base.with_new_nodes(added)
                ref = self.ref.Reference(cluster, precision)
                _placed, unscheduled = ref.free_run()
                if not unscheduled:
                    break
                added += 1
        answer = {"success": not unscheduled, "added": added, "engine": f"reference in {precision}",
                  "placed": ref.order(), "node_order": [nd.name for nd in cluster.nodes]}
        return self.compare(Window(opened=0.0, closed=0.0, items=[]), answer=answer)
