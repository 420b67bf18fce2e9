#!/usr/bin/env python3
"""python benchmarks/control.py --workload <name> --seeds <n> [<n> ...]

The control of a cell's comparison: the plain reference computed in the
nearest precision below the one the configuration states, put in the
program's place at the cell's own size and judged by the same comparison and
the same limits. It has to come out as not correct; its smallest readings
are the upper readings that `PERF.md` sets the limits from. It needs no chip
(the reference is numpy on the host) and no benchmark run makes it;
`benchmarks/tests/test_control.py` keeps it at a size a test run can hold.
One JSON line per seed.
"""

import argparse
import importlib
import json
import os
import shutil
import sys
import time
from typing import Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.drivers import Context  # noqa: E402
from benchmarks.harness import HERE, ROOT, load_json, resolve  # noqa: E402

#: the step down that would tempt a later PR, from the precision a configuration states
LOWER = {"float32": "bfloat16"}


def control(workload: str, seed: int, shrink: Optional[dict] = None, precision: str = "") -> dict:
    """`shrink` overrides some of the configuration's sizes (the test's way to
    a size it can hold); `precision` puts another one in the control's place
    (the test's proof that float32 put in place the same way reads nought)."""
    parts = resolve(load_json(os.path.join(ROOT, "BENCHMARK.json")), workload)
    config, traffic = parts["config"], parts["traffic"]
    precision = precision or LOWER[config["precision"]]
    scratch = os.path.join(HERE, ".cache", f"control-{workload}-{seed}")
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch)
    try:
        ctx = Context(config=config, traffic=traffic, seed=seed, scratch=scratch,
                      rehearse=bool(shrink), sizes=dict(config["sizes"], **(shrink or {})))
        driver = importlib.import_module("benchmarks.drivers." + traffic["driver"].replace("-", "_")).Driver(ctx)
        driver.prepare()
        t0 = time.monotonic()
        checks = driver.control(precision)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    return {"workload": workload, "seed": seed, "control": precision,
            "control_correct": all(c["value"] <= c["limit"] for c in checks),
            "checks": checks, "seconds": round(time.monotonic() - t0, 2)}


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description="the low-precision control of one cell's comparison")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    for seed in args.seeds:
        print(json.dumps(control(args.workload, seed)), flush=True)
