"""The one builder of the last line, and the check it passes before it is
printed, against good and bad inputs in both trace modes."""

import copy
import json

import pytest

from benchmarks import lastline

DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 34275840}
TRACED_DEVICE = dict(DEVICE, busy_s=0.197, window_s=8.89)
E2E = {"plan_s": {"value": 8.01, "unit": "s/plan"}, "setup_s": {"value": 22.0, "unit": "s"}}
LAYER = {"load_s.plan": {"value": 5.3, "unit": "s"}, "device_idle_pct.plan": {"value": 97.8, "unit": "%"}}
CHECKS = [{"name": "misplaced_pods", "value": 1, "limit": 50}]
BREAKDOWN = {"device_ops": [["jit_wrapped", 0.197]], "idle_gaps": [["apply", 5.2], ["(no span)", 0.1]]}


def good(traced: bool) -> dict:
    return copy.deepcopy(lastline.build(
        correct=True, attempted=5, failed=0, metrics=LAYER if traced else E2E,
        device=TRACED_DEVICE if traced else DEVICE,
        breakdown=BREAKDOWN if traced else None, checks=CHECKS))


@pytest.mark.parametrize("traced", [False, True])
def test_a_sound_line_passes_and_has_the_contracts_keys_with_checks_last(traced):
    line = good(traced)
    expected = list(LAYER if traced else E2E)
    lastline.validate(line, traced=traced, expected=expected)
    keys = list(json.loads(json.dumps(line)))
    assert keys[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert keys[-1] == "checks"
    assert ("breakdown" in keys) == traced


def bad_lines():
    def mutate(traced, f):
        line = good(traced)
        f(line)
        return traced, line

    yield "missing key", mutate(False, lambda l: l.pop("device"))
    yield "correct not bool", mutate(False, lambda l: l.update(correct="yes"))
    yield "attempted negative", mutate(False, lambda l: l.update(attempted=-1))
    yield "failed over attempted", mutate(False, lambda l: l.update(failed=9))
    yield "metric NaN", mutate(False, lambda l: l["metrics"]["plan_s"].update(value=float("nan")))
    yield "metric a string", mutate(False, lambda l: l["metrics"]["plan_s"].update(value="8.01"))
    yield "metric extra key", mutate(False, lambda l: l["metrics"]["plan_s"].update(why="x"))
    yield "metric lacks unit", mutate(False, lambda l: l["metrics"]["plan_s"].pop("unit"))
    yield "metric of another run", mutate(False, lambda l: l["metrics"].update(LAYER))
    yield "end-to-end metric missing", mutate(False, lambda l: l["metrics"].pop("setup_s"))
    yield "device lacks peak", mutate(False, lambda l: l["device"].pop("memory_peak_bytes"))
    yield "device count zero", mutate(False, lambda l: l["device"].update(count=0))
    yield "traced lacks busy_s", mutate(True, lambda l: l["device"].pop("busy_s"))
    yield "busy_s zero", mutate(True, lambda l: l["device"].update(busy_s=0.0))
    yield "busy over window", mutate(True, lambda l: l["device"].update(busy_s=9.0))
    yield "breakdown key", mutate(True, lambda l: l["breakdown"].update(other=[]))
    yield "breakdown too long", mutate(True, lambda l: l["breakdown"].update(device_ops=[["x", 1.0]] * 11))
    yield "breakdown row", mutate(True, lambda l: l["breakdown"].update(idle_gaps=[["x", "1"]]))
    yield "check without limit", mutate(False, lambda l: l["checks"].append({"name": "x", "value": 1}))
    yield "check infinite", mutate(False, lambda l: l["checks"].append(
        {"name": "x", "value": float("inf"), "limit": 0}))
    yield "not an object", (False, [1, 2])


@pytest.mark.parametrize("name,case", list(bad_lines()), ids=[n for n, _ in bad_lines()])
def test_a_malformed_line_is_refused(name, case):
    traced, line = case
    with pytest.raises(lastline.MalformedLine):
        lastline.validate(line, traced=traced, expected=list(LAYER if traced else E2E))


@pytest.mark.parametrize("traced", [False, True])
def test_a_traced_run_may_lack_a_metric_its_reader_found_nothing_for(traced):
    line = good(traced)
    if traced:
        line["metrics"].pop("load_s.plan")
        lastline.validate(line, traced=True, expected=list(LAYER))
    else:
        line["metrics"].pop("plan_s")
        with pytest.raises(lastline.MalformedLine):
            lastline.validate(line, traced=False, expected=list(E2E))


@pytest.mark.parametrize("traced", [False, True])
def test_the_failure_line_is_well_formed_and_says_so(traced):
    line = lastline.failure(DEVICE, attempted=3)
    lastline.validate(line, traced=traced, expected=list(LAYER if traced else E2E), failed_run=True)
    assert line["correct"] is False and line["failed"] == line["attempted"] == 3
    assert line["metrics"] == {}
    # and before the device was read
    lastline.validate(lastline.failure(None), traced=traced, expected=[], failed_run=True)
