"""The rest of a run with the timed path broken underneath: the harness's
look for a chip is skipped (`--rehearse`: tiny sizes, the CPU), everything
else is the run as the chip makes it, and `correct` has to come out false.
One case for each fault these cells can have: an answer altered where it is
produced (one pod moved to another node), half of the answer left out, and
the plan's count of added nodes off by one. A sound run of the same cell
stands beside them and reads true. The last case breaks the harness itself
(the window raises): the last line is still well formed and says so."""

import argparse
import time

import pytest

from benchmarks import harness, lastline


def app_pods(status):
    from opensim_tpu.models.objects import LABEL_APP_NAME

    return [p for p in status.pods if LABEL_APP_NAME in p.metadata.labels]


def move_one_pod(result):
    """The fullest node of the first app's pods gives one to the emptiest."""
    holders = sorted((s for s in result.node_status if app_pods(s)), key=lambda s: len(app_pods(s)))
    src = holders[-1]
    dst = next(s for s in sorted(result.node_status, key=lambda s: len(s.pods)) if s is not src)
    pod = app_pods(src)[0]
    src.pods.remove(pod)
    dst.pods.append(pod)


def drop_half(result):
    """Every second pod of the answer, over all nodes, is left out."""
    k = 0
    for s in result.node_status:
        for pod in app_pods(s):
            if k % 2:
                s.pods.remove(pod)
            k += 1


def break_answer(monkeypatch, how):
    """Both entries build their answer from the `SimulateResult`: the planner
    in `report.report`, the server in `rest._response`."""
    from opensim_tpu.planner import report as report_mod
    from opensim_tpu.server import rest

    real_report, real_response = report_mod.report, rest._response

    def report(result, *a, **kw):
        how(result)
        return real_report(result, *a, **kw)

    def response(result, *a, **kw):
        how(result)
        return real_response(result, *a, **kw)

    monkeypatch.setattr(report_mod, "report", report)
    monkeypatch.setattr(rest, "_response", response)


def one_node_too_many(monkeypatch):
    from opensim_tpu.planner.apply import Applier

    real = Applier.find_min_nodes_batched

    def find(self, prep, n_real):
        k = real(self, prep, n_real)
        return None if k is None else k + 1

    monkeypatch.setattr(Applier, "find_min_nodes_batched", find)


def run(workload: str, seed: int = 7, trace: int = 0) -> dict:
    args = argparse.Namespace(workload=workload, seed=seed, seconds=0.3, trace=trace,
                              rehearse=True)
    lines = []
    try:
        harness.run_cell(args, time.monotonic(), lines.append)
    except Exception:
        pass  # the line is emitted from the `finally` path all the same
    assert len(lines) == 1
    return lines[0]


def failing(line: dict) -> set:
    return {c["name"] for c in line["checks"] if c["value"] > c["limit"]}


@pytest.mark.parametrize("workload", ["plan-fit", "plan-short", "serve-solo"])
def test_a_sound_run_is_correct(workload):
    line = run(workload)
    assert line["correct"] is True and not failing(line) and line["failed"] == 0


@pytest.mark.parametrize("workload", ["plan-fit", "plan-short", "serve-solo"])
def test_one_pod_moved_where_the_answer_is_produced(monkeypatch, workload):
    break_answer(monkeypatch, move_one_pod)
    line = run(workload)
    assert line["correct"] is False
    assert failing(line) & {"worst_score_gap", "infeasible_pods", "misplaced_pods"}


@pytest.mark.parametrize("workload", ["plan-fit", "plan-short", "serve-solo"])
def test_half_of_the_answer_left_out(monkeypatch, workload):
    break_answer(monkeypatch, drop_half)
    line = run(workload)
    assert line["correct"] is False and "answer_diff" in failing(line)


def test_the_count_of_added_nodes_off_by_one(monkeypatch):
    one_node_too_many(monkeypatch)
    line = run("plan-short")
    assert line["correct"] is False and "added_nodes_diff" in failing(line)


@pytest.mark.parametrize("trace", [0, 1])
def test_a_window_that_raises_still_yields_a_well_formed_line(monkeypatch, trace):
    def boom(*a, **kw):
        raise RuntimeError("the window broke")

    monkeypatch.setattr(harness, "run_window", boom)
    line = run("plan-fit", trace=trace)
    lastline.validate(line, traced=bool(trace), expected=[], failed_run=True)
    assert line["correct"] is False and line["metrics"] == {}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_warm_up_reaches_the_driver_by_the_windows_own_call_stack(monkeypatch, trace):
    """A Mosaic kernel's compile-cache key holds the Python call stack it was
    traced under, so a warm-up that came another way would leave the window's
    first plan to compile the kernel again in a checkout's first process."""
    import traceback

    from benchmarks.drivers import plan_loop

    stacks = {}
    real = plan_loop.Driver.one

    def one(self, i, traced):
        stacks.setdefault(i, [(f.filename, f.lineno) for f in traceback.extract_stack()[:-1]])
        return real(self, i, traced)

    monkeypatch.setattr(plan_loop.Driver, "one", one)
    assert run("plan-fit", trace=trace)["correct"] is True
    assert stacks[-1] == stacks[0] and len(stacks[0]) > 3
