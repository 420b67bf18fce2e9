"""`roofline.question_work` for a configuration whose pods carry inter-pod
terms: the node table has one more column for each distinct (selector,
topology key) map the terms count in (`roofline_shape.interpod_maps`): the
count of matching pods in the node's domain, read once for each pod asked for
and costing a column's operations (a compare for a required term, a multiply
and an add for a preferred one, a select). From shapes alone, the same
whatever engine ran; bound pods are state, not steps."""

from __future__ import annotations

from typing import Dict

from benchmarks import roofline


def question_work(nodes: int, pods_asked: int, resident_pods: int, shape: Dict[str, int]) -> Dict[str, float]:
    work = roofline.question_work(nodes, pods_asked, resident_pods, shape)
    cells = nodes * int(shape.get("interpod_maps", 0))
    work["bytes"] += float(roofline.BYTES_PER_CELL * (pods_asked * cells + cells))
    work["ops"] += float(pods_asked * cells * roofline.OPS_PER_COLUMN)
    return work
