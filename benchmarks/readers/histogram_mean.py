"""Mean of a server histogram over the window: the difference of its `_sum`
over the difference of its `_count` between the scrape before the window and
the one after it."""

from benchmarks import promtext


def read(run, family):
    if run.prom is None:
        return None
    n = promtext.delta(run.prom["before"], run.prom["after"], family + "_count")
    if n <= 0:
        return None
    return promtext.delta(run.prom["before"], run.prom["after"], family + "_sum") / n
