"""`roofline.question_work` for a capacity plan over nodes with open-local
storage: what the question "how many nodes must be added so that every pod
and every claim has its place" needs, from shapes alone, the same whatever
engine ran.

One pass is `roofline.question_work`'s, and beside the node table for each
pod the local rows of each node: each of its `roofline_shape.local_vgs` VGs
(free bytes and capacity: the filter's fit and the score's tightest VG) and
each of its `roofline_shape.local_devices` device cells (free bytes,
capacity and media: the matching and the score's smallest device), read at a
column's operations each, and written on the node chosen (each VG's and
device's free bytes); plus one read of the storage state at the start.

The passes are `roofline_gpushare`'s: one over the stream on the cluster as
the plan leaves it, and one for each scenario of each count sweep the search
has to make (`roofline_gpushare.sweep_counts`), each over the nodes that
scenario has. The program's first pass over the cluster without new nodes is
not the question's: its share reads smaller for it, never larger."""

from __future__ import annotations

from typing import Dict

from benchmarks import roofline

#: cells a VG and a device carry: free bytes and capacity; and the media
VG_CELLS = 2
DEVICE_CELLS = 3


def local_cells(shape: Dict[str, int]) -> int:
    """Local cells of one node, read for every pod."""
    return VG_CELLS * int(shape.get("local_vgs", 0)) + DEVICE_CELLS * int(shape.get("local_devices", 0))


def pass_work(nodes: int, pods: int, shape: Dict[str, int]) -> Dict[str, float]:
    work = roofline.question_work(nodes, pods, 0, shape)
    cells = local_cells(shape)
    written = int(shape.get("local_vgs", 0)) + int(shape.get("local_devices", 0))  # free bytes, on the node chosen
    work["bytes"] += float(roofline.BYTES_PER_CELL * (pods * nodes * cells + nodes * cells + pods * written))
    work["ops"] += float(pods * nodes * cells * roofline.OPS_PER_COLUMN)
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
