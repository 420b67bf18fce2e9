"""`roofline.py` counts the work the question needs, from shapes alone: the
same count whichever engine ran, and pods already bound are state, not
steps."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmarks import roofline
from benchmarks.readers import scan_roofline
from benchmarks.window import Item, Window

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
K8S = json.load(open(os.path.join(HERE, "configs", "k8s-5k-50k.json")))["roofline_shape"]
TWIN = json.load(open(os.path.join(HERE, "configs", "twin-3k-30k.json")))["roofline_shape"]
PEAKS = roofline.load_peaks("TPU v5 lite")


def test_work_is_pods_asked_times_one_pass_over_the_node_table():
    w = roofline.question_work(nodes=5000, pods_asked=50000, resident_pods=0, shape=K8S)
    cols = 2 * 3 + 1 + 2
    assert w["ops"] == 50000 * 5000 * (cols * 4 + 2)
    assert w["bytes"] == 4 * (50000 * 5000 * cols + 5000 * cols)


def test_resident_pods_are_state_not_steps():
    five = roofline.question_work(3000, 5, 30000, TWIN)
    replayed = roofline.question_work(3000, 30005, 0, TWIN)  # what an engine that replays them does
    assert five["ops"] == 5 * 3000 * (7 * 4 + 2)
    assert replayed["ops"] / five["ops"] == pytest.approx(6001.0)
    bare = roofline.question_work(3000, 5, 0, TWIN)
    assert five["bytes"] - bare["bytes"] == 4 * 2 * 30000  # one read of (node, request) per bound pod


def run_with(ops, questions):
    items = [Item(start=0.0, end=1.0, ok=True) for _ in questions]
    return SimpleNamespace(
        trace={"device_ops": ops, "busy_s": sum(s for _n, s in ops), "window_s": 10.0},
        window=Window(opened=0.0, closed=1.0, items=items), questions=questions,
        config={"roofline_shape": TWIN}, device_kind="TPU v5 lite")


PATTERN = "^jit_(wrapped|_schedule_pods|.*sweep|.*scan)"
QUESTION = {"nodes": 3000, "pods": 100, "resident": 30000}


def test_the_share_depends_on_device_time_and_shapes_not_on_which_engine_ran():
    """The same question answered in the same device time reads the same
    share, whether the trace names the XLA scan or the megakernel."""
    xla = scan_roofline.read(run_with([["jit__schedule_pods_jit", 1.08]], [QUESTION]), PATTERN)
    mega = scan_roofline.read(run_with([["jit_wrapped", 1.08]], [QUESTION]), PATTERN)
    assert xla == mega
    least = roofline.least_seconds(roofline.question_work(3000, 100, 30000, TWIN), PEAKS)
    assert least["bound"] == "bytes"
    assert xla == pytest.approx(100.0 * least["seconds"] / 1.08)
    assert 0 < xla < 0.01  # tiny today, and says so


def test_every_question_of_the_traced_window_counts_and_nothing_to_read_is_none_not_zero():
    both = scan_roofline.read(run_with([["jit_wrapped", 1.0]], [QUESTION, QUESTION]), PATTERN)
    one = scan_roofline.read(run_with([["jit_wrapped", 1.0]], [QUESTION]), PATTERN)
    assert both == pytest.approx(2 * one)
    assert scan_roofline.read(run_with([["jit_dynamic_slice", 1.0]], [QUESTION]), PATTERN) is None
    assert scan_roofline.read(run_with([], [QUESTION]), PATTERN) is None
    no_trace = run_with([["jit_wrapped", 1.0]], [QUESTION])
    no_trace.trace = None
    assert scan_roofline.read(no_trace, PATTERN) is None


def test_an_unknown_device_is_an_error_not_a_default():
    with pytest.raises(KeyError):
        roofline.load_peaks("TPU v9 imaginary")
