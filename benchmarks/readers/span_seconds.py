"""Seconds per item inside the named spans (`names`: fnmatch patterns), the
spans' children included and overlapping spans counted once: `prepare` with
its `encode` and the `prep.*` spans the prepare cache records beside it;
`schedule` with every `engine.*` rung, and every `sweep.*` pass, whether its
result was kept or discarded."""

from benchmarks.xplane import merge, total

from . import matching, traced


def read(run, names):
    items = traced(run)
    if not items:
        return None
    seconds, found = 0.0, False
    for it in items:
        spans = [(sp["start"], sp["end"]) for sp in matching(it.spans, names)]
        found = found or bool(spans)
        seconds += total(merge(spans))
    return seconds / len(items) if found else None
