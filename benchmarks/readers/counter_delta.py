"""The difference of one of the program's counters over the window, from the
driver's parse of `/metrics` before and after it (`promtext`): the series of
`family` whose labels match every `labels` entry and none of `unless`.
None where the program has no series of the family at all (a program that
does not count it), else the difference, 0 a real reading."""

from benchmarks import promtext


def read(run, family, labels=None, unless=None):
    if run.prom is None:
        return None
    after = {k: v for k, v in run.prom["after"].items() if k[0] == family}
    if not after:
        return None

    def wanted(key) -> bool:
        got = dict(key[1])
        return (all(got.get(k) == v for k, v in (labels or {}).items())
                and not any(got.get(k) == v for k, v in (unless or {}).items()))

    before = {k: v for k, v in run.prom["before"].items() if k[0] == family and wanted(k)}
    return promtext.delta(before, {k: v for k, v in after.items() if wanted(k)}, family)
