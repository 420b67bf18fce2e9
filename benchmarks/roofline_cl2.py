"""`roofline.question_work` for a configuration with pinned pods
(`roofline_shape.pinned_pods`: a DaemonSet pins one pod to each node). The
question a pinned pod asks is whether its own node takes it: one row of the
node table, not a pass over it. So the work counted is the same whatever
engine answers and however it treats the pin (a scan step over every node
today), and the share cannot pass 100 %. From shapes alone; bound pods are
state, not steps."""

from __future__ import annotations

from typing import Dict

from benchmarks import roofline


def pinned_pods(nodes: int, pods_asked: int, shape: Dict[str, int]) -> int:
    """One pod a node at most is pinned, and no more than were asked for."""
    return min(int(shape.get("pinned_pods", 0)), nodes, pods_asked)


def question_work(nodes: int, pods_asked: int, resident_pods: int, shape: Dict[str, int]) -> Dict[str, float]:
    pinned = pinned_pods(nodes, pods_asked, shape)
    work = roofline.question_work(nodes, pods_asked - pinned, resident_pods, shape)
    cols = roofline.columns(shape)
    work["bytes"] += float(roofline.BYTES_PER_CELL * pinned * cols)
    work["ops"] += float(pinned * (cols * roofline.OPS_PER_COLUMN + roofline.OPS_SELECT))
    return work
