"""`scan_roofline`'s share with `roofline_interpod`'s count of the work: the
least time for the traced window's questions, the inter-pod count columns
included, over the device time of the operations whose name matches `ops`.
None where no such operation ran, or where the configuration counts no
inter-pod map: never 0."""

import re

from benchmarks import roofline, roofline_interpod


def read(run, ops):
    if run.trace is None or not run.questions:
        return None
    shape = run.config["roofline_shape"]
    if not shape.get("interpod_maps"):
        return None
    device_s = sum(s for name, s in run.trace["device_ops"] if re.search(ops, name))
    if device_s <= 0:
        return None
    work = {"ops": 0.0, "bytes": 0.0}
    for q in run.questions:
        w = roofline_interpod.question_work(q["nodes"], q["pods"], q["resident"], shape)
        work["ops"] += w["ops"]
        work["bytes"] += w["bytes"]
    least = roofline.least_seconds(work, roofline.load_peaks(run.device_kind))
    return 100.0 * least["seconds"] / device_s
