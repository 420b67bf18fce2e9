#!/usr/bin/env python3
"""python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process that holds the chip, one run of one cell of `BENCHMARK.json`. The
last line of standard output is the result; see `benchmarks/README.md`."""

import time

T_PROCESS = time.monotonic()  # as early as this process can read a clock

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmarks.harness import main

    rc = main(sys.argv[1:], T_PROCESS)
    sys.stdout.flush()
    sys.stderr.flush()
    # the result is out and every part is closed: a thread of the program
    # that lingers may not hold the exit (and the chip) any longer
    os._exit(rc)
