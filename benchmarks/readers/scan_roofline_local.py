"""`scan_roofline`'s share with `roofline_local`'s count of the work: the
least time for the traced plans' questions (the stream and every scenario of
the count sweeps, the local rows included) over the device time of the
operations whose name matches `ops`. None where no such operation ran, or
where the configuration has no local storage: never 0."""

import re

from benchmarks import roofline, roofline_local


def read(run, ops):
    if run.trace is None or not run.questions:
        return None
    shape = run.config["roofline_shape"]
    if not roofline_local.local_cells(shape):
        return None
    device_s = sum(s for name, s in run.trace["device_ops"] if re.search(ops, name))
    if device_s <= 0:
        return None
    work = {"ops": 0.0, "bytes": 0.0}
    for q in run.questions:
        w = roofline_local.question_work(q, shape)
        work["ops"] += w["ops"]
        work["bytes"] += w["bytes"]
    least = roofline.least_seconds(work, roofline.load_peaks(run.device_kind))
    return 100.0 * least["seconds"] / device_s
