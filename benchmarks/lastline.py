"""The one builder of a run's last line, used by both trace modes and by the
failure path, and the check it has to pass before it is printed."""

from __future__ import annotations

import json
import math
from typing import Dict, List, Optional

KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


class MalformedLine(ValueError):
    pass


def _number(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) and math.isfinite(x)


def build(*, correct: bool, attempted: int, failed: int, metrics: Dict[str, dict],
          device: dict, breakdown: Optional[dict] = None,
          checks: Optional[List[dict]] = None) -> dict:
    """The result object with the contract's keys in order, `checks` (each
    number compared, beside its limit) last."""
    line = {
        "correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
        "metrics": {k: {"value": v["value"], "unit": v["unit"]} for k, v in metrics.items()},
        "device": dict(device),
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = list(checks or [])
    return line


def validate(line: dict, *, traced: bool, expected: Optional[List[str]] = None,
             failed_run: bool = False, device_times: bool = True) -> None:
    """Raise MalformedLine unless `line` is what the driver reads. `expected`
    names the metrics a sound run has to carry; a run that failed may carry
    fewer, never others, and never a number that is not finite. A traced
    run may lack a per-layer metric whose reader found nothing to read; only
    a rehearsal off the chip may lack the device's times (`device_times`)."""
    if not isinstance(line, dict):
        raise MalformedLine("not an object")
    for k in KEYS:
        if k not in line:
            raise MalformedLine(f"missing key {k}")
    if not isinstance(line["correct"], bool):
        raise MalformedLine("correct is not a boolean")
    for k in ("attempted", "failed"):
        if not isinstance(line[k], int) or isinstance(line[k], bool) or line[k] < 0:
            raise MalformedLine(f"{k} is not a count")
    if line["failed"] > line["attempted"]:
        raise MalformedLine("failed exceeds attempted")
    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        raise MalformedLine("metrics is not an object")
    for name, m in metrics.items():
        if not isinstance(m, dict) or set(m) != {"value", "unit"}:
            raise MalformedLine(f"metric {name} is not {{value, unit}}")
        if not _number(m["value"]):
            raise MalformedLine(f"metric {name} has no finite value")
        if not isinstance(m["unit"], str) or not m["unit"]:
            raise MalformedLine(f"metric {name} has no unit")
        if expected is not None and name not in expected:
            raise MalformedLine(f"metric {name} does not belong to this run")
    if expected is not None and not failed_run and not traced:
        missing = [n for n in expected if n not in metrics]
        if missing:
            raise MalformedLine(f"metrics missing: {missing}")
    dev = line["device"]
    if not isinstance(dev, dict):
        raise MalformedLine("device is not an object")
    for k in DEVICE_KEYS:
        if k not in dev:
            raise MalformedLine(f"device lacks {k}")
    if not isinstance(dev["platform"], str) or not isinstance(dev["kind"], str):
        raise MalformedLine("device platform and kind are strings")
    if not isinstance(dev["count"], int) or dev["count"] < 1:
        raise MalformedLine("device count is not a positive whole number")
    if not _number(dev["memory_peak_bytes"]) or dev["memory_peak_bytes"] < 0:
        raise MalformedLine("memory_peak_bytes is not a number")
    if traced and device_times and not failed_run:
        for k in ("busy_s", "window_s"):
            if not _number(dev.get(k)):
                raise MalformedLine(f"a traced run's device lacks {k}")
        if not 0 < dev["busy_s"] <= dev["window_s"]:
            raise MalformedLine("busy_s has to be above 0 and at most window_s")
    if "breakdown" in line:
        bd = line["breakdown"]
        if not isinstance(bd, dict) or set(bd) - {"device_ops", "idle_gaps"}:
            raise MalformedLine("breakdown has other keys than device_ops and idle_gaps")
        for k, rows in bd.items():
            if not isinstance(rows, list) or len(rows) > 10:
                raise MalformedLine(f"breakdown.{k} is not a list of at most 10")
            for row in rows:
                if (not isinstance(row, list) or len(row) != 2
                        or not isinstance(row[0], str) or not _number(row[1])):
                    raise MalformedLine(f"breakdown.{k} holds a row that is not [name, seconds]")
    for c in line.get("checks", []):
        if not isinstance(c, dict) or set(c) != {"name", "value", "limit"}:
            raise MalformedLine("a check is not {name, value, limit}")
        if not _number(c["value"]) or not _number(c["limit"]):
            raise MalformedLine(f"check {c.get('name')} has no finite numbers")
    json.dumps(line)  # and it serialises


def failure(device: Optional[dict], attempted: int = 0, metrics: Optional[Dict[str, dict]] = None,
            checks: Optional[List[dict]] = None) -> dict:
    """The line of a run that raised: well formed, and it says so. Every item
    counts as failed."""
    dev = {"platform": "unknown", "kind": "unknown", "count": 1, "memory_peak_bytes": 0}
    dev.update(device or {})
    return build(correct=False, attempted=attempted, failed=attempted,
                 metrics=metrics or {}, device=dev, checks=checks)
