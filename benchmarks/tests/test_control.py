"""The control of each cell's comparison, at a size a test run can hold: the
plain reference in bfloat16 (the nearest precision below the float32 the
configurations state), put in the program's place, has to come out as not
correct under the cell's own limits. The plan cells run with their
configuration's counts shrunk (300 nodes, 3,000 pods) and every shape kept;
the twin's control takes seconds at the cell's own size and runs there. The readings at the
cells' own sizes, which the limits were set from, are in `PERF.md`."""

import pytest

from benchmarks.control import control

SHRUNK = {"nodes": 300, "pods": 3000, "short_nodes": 270}
CASES = [("plan-fit", SHRUNK), ("plan-short", SHRUNK), ("serve-solo", None)]


@pytest.mark.parametrize("seed", [5, 2147483659, 3000000019])
@pytest.mark.parametrize("workload,sizes", CASES, ids=[c[0] for c in CASES])
def test_the_low_precision_control_is_not_correct(workload, sizes, seed):
    got = control(workload, seed, sizes)
    assert got["control"] == "bfloat16"
    assert got["control_correct"] is False
    failing = {c["name"] for c in got["checks"] if c["value"] > c["limit"]}
    assert "worst_score_gap" in failing


@pytest.mark.parametrize("workload,sizes", CASES, ids=[c[0] for c in CASES])
def test_the_reference_in_its_own_precision_put_in_the_programs_place_is_correct(workload, sizes):
    """The same path with nothing lowered reads nought everywhere: what the
    control fails on is the precision and not the way it is put in place."""
    got = control(workload, 5, sizes, precision="float32")
    assert got["control_correct"] is True
    assert all(c["value"] == 0 for c in got["checks"])
