"""Per-layer metric readers, looked up by the `reader` name in
`benchmarks/metrics/<metric>.json`. Each is `read(run, **args)` and returns a
number, or None where it finds nothing to read: the harness then leaves the
metric out of the line."""

from __future__ import annotations

import fnmatch
from typing import Iterator, List


def matching(tree: dict, patterns: List[str]) -> Iterator[dict]:
    """Top-most spans whose name matches one of the patterns: a match's
    descendants are its own time and are not walked again."""
    if any(fnmatch.fnmatchcase(tree["name"], p) for p in patterns):
        yield tree
        return
    for child in tree.get("children", []):
        yield from matching(child, patterns)


def traced(run) -> list:
    return [it for it in run.window.items if it.spans is not None]
