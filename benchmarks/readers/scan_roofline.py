"""The scan's share of its roofline, in percent: the least time the chip
could take for the questions of the traced window's items (`benchmarks/roofline.py`,
from shapes alone) over the device time of the operations whose name matches
`ops` (a regular expression) in the traced window. None where no such
operation ran: never 0."""

import re

from benchmarks import roofline


def read(run, ops):
    if run.trace is None:
        return None
    device_s = sum(s for name, s in run.trace["device_ops"] if re.search(ops, name))
    if device_s <= 0:
        return None
    if not run.questions:
        return None
    shape = run.config["roofline_shape"]
    work = {"ops": 0.0, "bytes": 0.0}
    for q in run.questions:
        w = roofline.question_work(q["nodes"], q["pods"], q["resident"], shape)
        work["ops"] += w["ops"]
        work["bytes"] += w["bytes"]
    least = roofline.least_seconds(work, roofline.load_peaks(run.device_kind))
    return 100.0 * least["seconds"] / device_s
