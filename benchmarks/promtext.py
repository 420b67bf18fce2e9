"""Just enough of the Prometheus text format to read the server's counters
and histograms from `/metrics` (the arithmetic of `obs/metrics.py`'s
`parse_metrics`/`counter_delta`, kept here so no later PR can move it)."""

from __future__ import annotations

import re
from typing import Dict, Tuple

_SAMPLE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)\s*$")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')

Key = Tuple[str, Tuple[Tuple[str, str], ...]]


def parse(text: str) -> Dict[Key, float]:
    out: Dict[Key, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        if not m:
            continue
        name, body, value = m.groups()
        try:
            out[(name, tuple(sorted(_LABEL.findall(body or ""))))] = float(value)
        except ValueError:
            continue
    return out


def delta(before: Dict[Key, float], after: Dict[Key, float], name: str) -> float:
    """Summed difference of every series of one name."""
    return sum(v - before.get(k, 0.0) for k, v in after.items() if k[0] == name)
