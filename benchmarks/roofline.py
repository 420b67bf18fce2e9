"""Operations and bytes that a scheduling *question* needs, from shapes alone.

The question is: place these P pods on these N nodes, given the R pods that
are already bound. The least any engine has to do is, for each pod asked for,
one pass over the node table (for every node, the columns the configuration
has: allocatable and used of each resource, the selector label, the domain of
each spread key), plus one read of the resident state. Pods that are already
bound are state, not steps: an engine that replays them as forced steps does
more work than the question needs, and its share reads smaller for it, never
larger. Nothing here knows which engine ran.

Counted per node and pod: a column is read once (4 bytes, float32 or int32)
and costs OPS_PER_COLUMN arithmetic operations (an add or a compare, a
subtract, a multiply, a divide or a select: the filter and the score of that
column), and choosing the node costs OPS_SELECT (the weighted sum and the
arg-max compare).
"""

from __future__ import annotations

import json
import os
from typing import Dict

BYTES_PER_CELL = 4
OPS_PER_COLUMN = 4
OPS_SELECT = 2


def columns(shape: Dict[str, int]) -> int:
    """Node-table columns of a configuration: `resources` (each has an
    allocatable and a used column), `selector_labels`, `spread_keys`."""
    return 2 * shape["resources"] + shape["selector_labels"] + shape["spread_keys"]


def question_work(nodes: int, pods_asked: int, resident_pods: int, shape: Dict[str, int]) -> Dict[str, float]:
    cols = columns(shape)
    table_cells = nodes * cols
    return {
        # resident pods enter once, as the bytes of their (node, request) rows
        "bytes": float(BYTES_PER_CELL * (pods_asked * table_cells + table_cells + 2 * resident_pods)),
        "ops": float(pods_asked * nodes * (cols * OPS_PER_COLUMN + OPS_SELECT)),
    }


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json: add it with its source")
    return table[device_kind]


def least_seconds(work: Dict[str, float], peaks: dict) -> Dict[str, object]:
    """The least time the chip could take, and which of the two bounds it."""
    by_ops = work["ops"] / peaks["flops_per_s"]
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_ops, by_bytes), "bound": "bytes" if by_bytes >= by_ops else "operations"}
