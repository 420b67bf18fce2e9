"""`roofline.question_work` for a capacity plan under a bin-packing profile:
what the question "how many nodes must be added so that every pod runs"
needs when RequestedToCapacityRatio scores every node, from shapes alone,
the same whatever engine ran.

One pass is `roofline.question_work`'s, and for each pod and node the
RequestedToCapacityRatio term of each of its `roofline_shape.rtcr_resources`
resources: the utilization (OPS_PER_COLUMN: a subtract, a multiply, a divide
and a select), and the shape's broken-linear function at one compare and one
select a point of its `roofline_shape.shape_points`, plus the weighted sum
over the resources (a multiply-add and a select-add a resource). It reads the
allocatable and used columns the pass already counts: no byte more.

The passes are `roofline_gpushare`'s: one over the stream on the cluster as
the plan leaves it, and one for each scenario of each count sweep the search
has to make (`roofline_gpushare.sweep_counts`), each over the nodes that
scenario has. The program's first pass over the cluster without new nodes is
not the question's: its share reads smaller for it, never larger."""

from __future__ import annotations

from typing import Dict

from benchmarks import roofline

#: the weighted sum over the resources: a multiply-add and a select-add each
OPS_PER_RESOURCE_SUM = 4


def rtcr_ops(shape: Dict[str, int]) -> int:
    """Operations of the RequestedToCapacityRatio term for one pod on one node."""
    points = int(shape.get("shape_points", 0))
    per_resource = roofline.OPS_PER_COLUMN + 2 * points + OPS_PER_RESOURCE_SUM
    return int(shape.get("rtcr_resources", 0)) * per_resource


def pass_work(nodes: int, pods: int, shape: Dict[str, int]) -> Dict[str, float]:
    work = roofline.question_work(nodes, pods, 0, shape)
    work["ops"] += float(pods * nodes * rtcr_ops(shape))
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
