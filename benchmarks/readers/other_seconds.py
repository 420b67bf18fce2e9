"""Seconds per item that no named span covers: the item's whole time, on the
benchmark's own clock round the call, less the union of the spans in `names`."""

from benchmarks.xplane import clip, merge, total

from . import matching, traced


def read(run, names):
    items = traced(run)
    if not items:
        return None
    rest = 0.0
    for it in items:
        covered = [(sp["start"], sp["end"]) for sp in matching(it.spans, names)]
        rest += (it.end - it.start) - total(merge(clip(covered, it.start, it.end)))
    return rest / len(items)
