"""`plan-mixed` (the configuration `schedperf-mixed-5k`), at sizes a test run
can hold: the bfloat16 control of its comparison reads not correct and
float32 put in the same place reads nought; a pod moved where the answer is
produced, to a node that breaks a required term, reads `infeasible_pods`;
`roofline_interpod` counts no less than `roofline.question_work` and its share
of a synthetic trace never passes 100 %."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmarks import roofline, roofline_interpod
from benchmarks.control import control
from benchmarks.readers import scan_roofline, scan_roofline_interpod
from benchmarks.tests.test_faults import break_answer, failing, run
from benchmarks.window import Item, Window

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(HERE, "configs", "schedperf-mixed-5k.json")) as f:
    CONFIG = json.load(f)
SHAPE = CONFIG["roofline_shape"]
#: every shape kept, the counts shrunk; the zone's count still passes 256,
#: where bfloat16 stops counting
SHRUNK = {"nodes": 300, "init_pods": 300, "measure_pods": 150}
GREEN = "pod-with-pod-anti-affinity"


@pytest.mark.parametrize("seed", [5, 2147483659, 3000000019])
def test_the_low_precision_control_is_not_correct(seed):
    got = control("plan-mixed", seed, SHRUNK)
    assert got["control"] == "bfloat16" and got["control_correct"] is False
    failing_checks = {c["name"] for c in got["checks"] if c["value"] > c["limit"]}
    assert {"worst_score_gap", "misplaced_pods"} <= failing_checks
    # the control's filters are exact: it breaks no required term
    assert {c["name"]: c["value"] for c in got["checks"]}["infeasible_pods"] == 0


def test_the_reference_in_its_own_precision_put_in_the_programs_place_is_correct():
    got = control("plan-mixed", 5, SHRUNK, precision="float32")
    assert got["control_correct"] is True
    assert all(c["value"] == 0 for c in got["checks"])


def test_a_sound_run_is_correct():
    line = run("plan-mixed")
    assert line["correct"] is True and not failing(line) and line["failed"] == 0


def two_green_pods_on_one_node(result):
    """A green pod leaves its node for one that already holds a green pod:
    the required anti-affinity over the hostname no longer holds there."""
    holders = [s for s in result.node_status if any(GREEN in p.metadata.name for p in s.pods)]
    src, dst = holders[0], holders[1]
    pod = next(p for p in src.pods if GREEN in p.metadata.name)
    src.pods.remove(pod)
    dst.pods.append(pod)


def test_one_pod_moved_to_a_node_that_breaks_a_required_term(monkeypatch):
    break_answer(monkeypatch, two_green_pods_on_one_node)
    line = run("plan-mixed")
    assert line["correct"] is False and "infeasible_pods" in failing(line)


# -- the count of the work ---------------------------------------------------


@pytest.mark.parametrize("nodes,pods,resident", [(5000, 11000, 0), (24, 92, 0), (3000, 5, 30000), (1, 1, 0)])
def test_the_interpod_count_is_never_under_the_plain_one(nodes, pods, resident):
    plain = roofline.question_work(nodes, pods, resident, SHAPE)
    mixed = roofline_interpod.question_work(nodes, pods, resident, SHAPE)
    maps = SHAPE["interpod_maps"]
    assert maps == 4  # blue over the zone; green, red and yellow over the hostname
    assert mixed["ops"] - plain["ops"] == pods * nodes * maps * roofline.OPS_PER_COLUMN
    assert mixed["bytes"] - plain["bytes"] == 4 * (pods * nodes * maps + nodes * maps)
    without = dict(SHAPE, interpod_maps=0)
    assert roofline_interpod.question_work(nodes, pods, resident, without) == plain


PATTERN = "^jit_(wrapped|_schedule_pods|.*sweep|.*scan)"
QUESTION = {"nodes": 5000, "pods": 11000, "resident": 0}


def run_with(ops, questions, shape=SHAPE):
    items = [Item(start=0.0, end=1.0, ok=True) for _ in questions]
    return SimpleNamespace(
        trace={"device_ops": ops, "busy_s": sum(s for _n, s in ops), "window_s": 10.0},
        window=Window(opened=0.0, closed=1.0, items=items), questions=questions,
        config={"roofline_shape": shape}, device_kind="TPU v5 lite")


def test_the_share_of_a_synthetic_trace_never_passes_100():
    work = roofline_interpod.question_work(5000, 11000, 0, SHAPE)
    least = roofline.least_seconds(work, roofline.load_peaks("TPU v5 lite"))
    assert least["bound"] == "bytes"
    # a kernel that took exactly the least time the chip could reads 100, and
    # no device can be faster; anything slower reads less, in proportion
    at_peak = scan_roofline_interpod.read(run_with([["jit_wrapped", least["seconds"]]], [QUESTION]), PATTERN)
    assert at_peak == pytest.approx(100.0) and at_peak <= 100.0 + 1e-9
    for slower in (1.5, 10.0, 400.0):
        share = scan_roofline_interpod.read(
            run_with([["jit_wrapped", slower * least["seconds"]]], [QUESTION]), PATTERN)
        assert share == pytest.approx(100.0 / slower) and 0 < share < 100
    # the same device time, whichever engine the trace names, and above the plain share
    mega = scan_roofline_interpod.read(run_with([["jit_wrapped", 0.1]], [QUESTION]), PATTERN)
    xla = scan_roofline_interpod.read(run_with([["jit__schedule_pods_jit", 0.1]], [QUESTION]), PATTERN)
    plain = scan_roofline.read(run_with([["jit_wrapped", 0.1]], [QUESTION]), PATTERN)
    assert mega == xla and plain < mega < 100


def test_nothing_to_read_is_none_not_zero():
    assert scan_roofline_interpod.read(run_with([], [QUESTION]), PATTERN) is None
    assert scan_roofline_interpod.read(run_with([["jit_dynamic_slice", 1.0]], [QUESTION]), PATTERN) is None
    no_maps = run_with([["jit_wrapped", 1.0]], [QUESTION], shape=dict(SHAPE, interpod_maps=0))
    assert scan_roofline_interpod.read(no_maps, PATTERN) is None
    k8s = run_with([["jit_wrapped", 1.0]], [QUESTION], shape={"resources": 3, "selector_labels": 1, "spread_keys": 2})
    assert scan_roofline_interpod.read(k8s, PATTERN) is None
    no_trace = run_with([["jit_wrapped", 1.0]], [QUESTION])
    no_trace.trace = None
    assert scan_roofline_interpod.read(no_trace, PATTERN) is None
