"""`roofline.question_work` for a capacity plan over nodes whose GPUs are
shared: what the question "how many nodes must be added so that every task
runs" needs, from shapes alone, the same whatever engine ran.

One pass is `roofline.question_work`'s: for each pod asked for, the node
table with the resource columns this encoding carries
(`roofline_shape.resources`: cpu, memory, pods, gpu-mem, gpu-count), and
beside it the free memory of each of a node's `roofline_shape.gpu_devices`
device cells, read for the filter (how many slots of the pod's size the node
still has) at a column's operations each, and written on the node chosen;
plus one read of the device state at the start.

The passes: one over the stream on the cluster as the plan leaves it, and one
for each scenario of each count sweep the search has to make
(`sweep_counts`: the planner's own geometric ladder and the open bracket
inside it), each over the nodes that scenario has. The program's discarded
pass and its re-scan are not the question's and are not counted: its share
reads smaller for them, never larger."""

from __future__ import annotations

from typing import Dict, List

from benchmarks import roofline


def sweep_counts(added: int, max_new_nodes: int) -> List[int]:
    """The new-node counts a search for the least feasible one probes when
    `added` is the answer: `find_min_nodes_batched`'s ladder {0, 1, 2, 4, ...,
    max} and then every count of the open bracket the answer lies in. None
    where the cluster fits as it is."""
    if added <= 0:
        return []
    coarse = sorted({0, max_new_nodes} | {2 ** i for i in range(max_new_nodes.bit_length()) if 2 ** i <= max_new_nodes})
    hi = min((k for k in coarse if k >= added), default=max_new_nodes)
    lo = max((k for k in coarse if k < hi), default=0)
    return coarse + list(range(lo + 1, hi))


def pass_work(nodes: int, pods: int, shape: Dict[str, int]) -> Dict[str, float]:
    work = roofline.question_work(nodes, pods, 0, shape)
    devices = int(shape.get("gpu_devices", 0))
    work["bytes"] += float(roofline.BYTES_PER_CELL * (pods * nodes * devices + nodes * devices + pods * devices))
    work["ops"] += float(pods * nodes * devices * roofline.OPS_PER_COLUMN)
    return work


def question_work(question: dict, shape: Dict[str, int]) -> Dict[str, float]:
    """`question`: `nodes` (the cluster as the plan leaves it), `pods`, and
    `scenario_nodes`, the node count of every scenario asked."""
    total = {"ops": 0.0, "bytes": 0.0}
    for nodes in [question["nodes"]] + list(question.get("scenario_nodes", ())):
        work = pass_work(nodes, question["pods"], shape)
        total["ops"] += work["ops"]
        total["bytes"] += work["bytes"]
    return total
