"""The largest value of one attribute of the named spans (`names`: fnmatch
patterns) over the traced window's items, times `scale`: of the spans whose
attributes hold every pair of `where`. None where no such span carries the
attribute: the program opened none, or the cell's driver keeps no span
attributes (`plan-loop-kernel` keeps them)."""

import fnmatch

from . import traced


def spans(tree: dict):
    yield tree
    for child in tree.get("children", []):
        yield from spans(child)


def read(run, names, attr, scale=1.0, where=None):
    values = []
    for it in traced(run):
        for sp in spans(it.spans):
            attrs = sp.get("attrs", {})
            if (
                attr in attrs
                and any(fnmatch.fnmatchcase(sp["name"], p) for p in names)
                and all(attrs.get(k) == v for k, v in (where or {}).items())
            ):
                values.append(float(attrs[attr]))
    return max(values) * scale if values else None
