"""Traffic drivers, looked up by the `driver` name in
`benchmarks/traffic/<traffic>.json` (`plan-loop` -> `plan_loop.py`). A driver
owns the system under test for one run: it makes the inputs from the seed,
warms up through the entry the window drives, runs one item at a time for the
window, and compares what the window produced with the plain reference."""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, List


@dataclass
class Context:
    config: dict  # benchmarks/configs/<config>.json
    traffic: dict  # benchmarks/traffic/<traffic>.json
    sizes: dict  # the configuration's sizes, or its `tiny` ones in a rehearsal
    seed: int
    scratch: str  # a directory of this run's own, inside the checkout
    rehearse: bool
    traced: bool = False  # the window will be a traced one

    @property
    def params(self) -> dict:
        return self.traffic.get("params", {})

    @property
    def limits(self) -> Dict[str, float]:
        return self.traffic["limits"]


def canon_pod_ref(ref: str) -> str:
    """`ns/name` of a pod -> `ns/<owning workload>` (`server/loadgen.py`'s
    `canon_pod_ref`): strips the generated 10-hex segments, which differ from
    process to process."""
    ns, _, name = ref.partition("/")
    parts = name.split("-")
    while len(parts) > 1 and re.fullmatch(r"[0-9a-f]{10}", parts[-1]):
        parts.pop()
    return f"{ns}/{'-'.join(parts)}"


def checks_from(values: Dict[str, float], limits: Dict[str, float]) -> List[dict]:
    """Each number compared beside its limit. A number with no limit in the
    traffic file is an error: nothing is compared against a guess."""
    out = []
    for name in sorted(values):
        if name not in limits:
            raise KeyError(f"no limit for the compared number {name!r} in the traffic file")
        out.append({"name": name, "value": values[name], "limit": limits[name]})
    return out
