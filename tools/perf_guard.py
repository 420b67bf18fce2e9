#!/usr/bin/env python
"""Perf-regression sentinel (ISSUE 12): guard bench rows against the
committed baseline manifest.

The bench trajectory (the BENCH_r*.json rows) was append-only JSON no gate
ever read — a perf or memory regression shipped silently. This tool closes the
loop against ``BENCH_BASELINE.json``:

- every baseline entry carries the committed row plus per-metric
  tolerances (``kind: time`` → measured/baseline must stay under
  ``max_ratio``; ``kind: rate`` → must stay above ``min_ratio``;
  ``kind: exact`` → bit-stable counts — placement drift is a correctness
  bug, never noise);
- the default run is the SELF-CHECK: each committed baseline row must
  pass against its own tolerances, and a synthetically slowed copy must
  FAIL — the detector-awake proof (`make tsan` phase 1's pattern), so a
  manifest edit can never silently disarm the guard;
- ``--row FILE --baseline KEY`` guards an externally produced row (a
  fresh bench run on a dev box);
- ``--fresh KEY`` runs the entry's recorded bench command and guards the
  row it prints;
- ``--tolerance-only`` (what ``make verify`` runs): time/rate verdicts
  are REPORTED but only ``exact`` metrics fail the gate — wall-clock on a
  slow shared CI box must not flake the build, while a placement-count
  drift still does. Full enforcement is the default everywhere else.

Output: one human verdict table on stderr, one JSON summary line on
stdout (the repo's bench contract), nonzero exit on failure. See BENCH.md
"Guarding the trajectory" for the manifest format and the re-baselining
workflow.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from dataclasses import asdict, dataclass
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFEST = os.path.join(REPO, "BENCH_BASELINE.json")


@dataclass
class MetricVerdict:
    """One metric's comparison: typed, so the report is machine-usable."""

    metric: str
    kind: str  # time | rate | exact
    baseline: float
    measured: Optional[float]
    ratio: Optional[float]  # measured/baseline (None when unmeasurable)
    limit: Optional[float]  # max_ratio (time) / min_ratio (rate)
    ok: bool
    enforced: bool
    note: str = ""


@dataclass
class GuardReport:
    baseline: str
    source: str
    verdicts: List[MetricVerdict]

    @property
    def failed(self) -> List[MetricVerdict]:
        return [v for v in self.verdicts if not v.ok and v.enforced]

    @property
    def warned(self) -> List[MetricVerdict]:
        return [v for v in self.verdicts if not v.ok and not v.enforced]

    @property
    def ok(self) -> bool:
        return not self.failed


class GuardError(RuntimeError):
    """Typed failure: a malformed manifest/row — distinct from a tolerance
    violation (which is a report, not an exception)."""


def load_manifest(path: str = MANIFEST) -> dict:
    try:
        with open(path) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        raise GuardError(f"cannot load baseline manifest {path}: {e}") from None
    baselines = manifest.get("baselines")
    if not isinstance(baselines, dict) or not baselines:
        raise GuardError(f"{path}: no baselines")
    for key, entry in baselines.items():
        for field in ("row", "metrics", "source"):
            if field not in entry:
                raise GuardError(f"{path}: baseline {key!r} lacks {field!r}")
        for name, spec in entry["metrics"].items():
            kind = spec.get("kind")
            if kind not in ("time", "rate", "exact"):
                raise GuardError(
                    f"{path}: baseline {key!r} metric {name!r} has unknown "
                    f"kind {kind!r} (time|rate|exact)"
                )
            if kind == "time" and not spec.get("max_ratio"):
                raise GuardError(f"{path}: time metric {name!r} needs max_ratio")
            if kind == "rate" and not spec.get("min_ratio"):
                raise GuardError(f"{path}: rate metric {name!r} needs min_ratio")
            if name not in entry["row"]:
                raise GuardError(
                    f"{path}: baseline {key!r} row lacks guarded metric {name!r}"
                )
    return manifest


def compare(row: dict, key: str, entry: dict, tolerance_only: bool = False) -> GuardReport:
    """Compare one fresh bench row against one baseline entry.

    When both rows record ``host_cores`` and they differ, the comparison
    is REFUSED outright (one verdict, no ratios): serving QPS is
    core-count-bound, so a cross-core ratio measures the boxes, not the
    code — re-baseline on a same-core box instead (BENCH.md)."""
    base_cores = entry["row"].get("host_cores")
    row_cores = row.get("host_cores")
    if (
        base_cores is not None
        and row_cores is not None
        and int(base_cores) != int(row_cores)
    ):
        return GuardReport(
            baseline=key,
            source=entry["source"],
            verdicts=[
                MetricVerdict(
                    metric="host_cores", kind="exact",
                    baseline=float(base_cores), measured=float(row_cores),
                    ratio=None, limit=None, ok=False, enforced=True,
                    note=(
                        f"comparison refused: baseline measured on "
                        f"{base_cores} core(s), this row on {row_cores} — "
                        "time/rate ratios are not comparable across core "
                        "counts; re-baseline on a same-core box"
                    ),
                )
            ],
        )
    verdicts: List[MetricVerdict] = []
    for name, spec in entry["metrics"].items():
        kind = spec["kind"]
        base = float(entry["row"][name])
        enforced = (kind == "exact") or not tolerance_only
        if name not in row:
            verdicts.append(
                MetricVerdict(
                    metric=name, kind=kind, baseline=base, measured=None,
                    ratio=None, limit=spec.get("max_ratio") or spec.get("min_ratio"),
                    ok=False, enforced=True,  # a missing metric is never tolerable
                    note="metric missing from the measured row",
                )
            )
            continue
        measured = float(row[name])
        if kind == "exact":
            ok = measured == base
            verdicts.append(
                MetricVerdict(
                    metric=name, kind=kind, baseline=base, measured=measured,
                    ratio=None, limit=None, ok=ok, enforced=True,
                    note="" if ok else "exact metric drifted",
                )
            )
            continue
        ratio = measured / base if base else None
        if kind == "time":
            limit = float(spec["max_ratio"])
            ok = ratio is not None and ratio <= limit
            note = "" if ok else f"slower than {limit}x baseline"
        else:  # rate
            limit = float(spec["min_ratio"])
            ok = ratio is not None and ratio >= limit
            note = "" if ok else f"below {limit}x baseline"
        verdicts.append(
            MetricVerdict(
                metric=name, kind=kind, baseline=base, measured=measured,
                ratio=round(ratio, 4) if ratio is not None else None,
                limit=limit, ok=ok, enforced=enforced, note=note,
            )
        )
    return GuardReport(baseline=key, source=entry["source"], verdicts=verdicts)


def slowed_row(entry: dict, factor: float = 8.0) -> dict:
    """A synthetically degraded copy of the committed row: every time
    metric multiplied, every rate metric divided — the self-check input
    that MUST fail (proves the tolerances actually bite)."""
    row = dict(entry["row"])
    for name, spec in entry["metrics"].items():
        if spec["kind"] == "time":
            row[name] = float(row[name]) * factor
        elif spec["kind"] == "rate":
            row[name] = float(row[name]) / factor
    return row


def render_report(report: GuardReport, out) -> None:
    status = "PASS" if report.ok else "FAIL"
    print(f"[perf-guard] {report.baseline} ({report.source}): {status}", file=out)
    for v in report.verdicts:
        mark = "ok " if v.ok else ("WARN" if not v.enforced else "FAIL")
        ratio = f" ratio={v.ratio}" if v.ratio is not None else ""
        limit = ""
        if v.limit is not None:
            limit = f" limit={'<=' if v.kind == 'time' else '>='}{v.limit}"
        note = f" ({v.note})" if v.note else ""
        print(
            f"  {mark} {v.metric} [{v.kind}] baseline={v.baseline} "
            f"measured={v.measured}{ratio}{limit}{note}",
            file=out,
        )


def self_check(manifest: dict) -> List[GuardReport]:
    """Every committed baseline row passes; every slowed copy fails. Runs
    with enforcement ON regardless of --tolerance-only: the flag only
    relaxes FRESH-row timing (--row/--fresh on a slow box); the detector
    itself must always be provably awake."""
    reports: List[GuardReport] = []
    for key, entry in manifest["baselines"].items():
        clean = compare(entry["row"], key, entry, tolerance_only=False)
        reports.append(clean)
        if not clean.ok:
            continue  # already failing; the report says why
        slow = compare(slowed_row(entry), key, entry, tolerance_only=False)
        if slow.ok:
            # a manifest whose tolerances cannot catch an 8x slowdown is
            # disarmed — fail the self-check loudly
            reports.append(
                GuardReport(
                    baseline=f"{key} (slowed-copy self-test)",
                    source=entry["source"],
                    verdicts=[
                        MetricVerdict(
                            metric="detector-awake", kind="exact", baseline=1.0,
                            measured=0.0, ratio=None, limit=None, ok=False,
                            enforced=True,
                            note="an 8x-degraded row PASSED; tolerances are disarmed",
                        )
                    ],
                )
            )
        else:
            reports.append(
                GuardReport(
                    baseline=f"{key} (slowed-copy self-test)",
                    source=entry["source"],
                    verdicts=[
                        MetricVerdict(
                            metric="detector-awake", kind="exact", baseline=1.0,
                            measured=1.0, ratio=None, limit=None, ok=True,
                            enforced=True,
                            note=f"{len(slow.failed)} metric(s) correctly failed",
                        )
                    ],
                )
            )
    return reports


def run_fresh(entry: dict) -> dict:
    """Run the entry's recorded bench command and parse its one-line JSON
    row (the repo's bench stdout contract)."""
    cmd = entry.get("bench_cmd")
    if not cmd:
        raise GuardError("baseline entry has no bench_cmd; use --row instead")
    proc = subprocess.run(
        cmd, cwd=REPO, capture_output=True, text=True, timeout=1800
    )
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    if proc.returncode != 0 or not lines:
        raise GuardError(
            f"bench command {' '.join(cmd)} failed rc={proc.returncode}: "
            f"{(lines[-1] if lines else proc.stderr.strip()[-400:])!r}"
        )
    try:
        row = json.loads(lines[-1])
    except ValueError as e:
        raise GuardError(f"bench output is not a JSON row: {e}") from None
    if "error" in row:
        raise GuardError(f"bench failed: {row}")
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--manifest", default=MANIFEST, help="baseline manifest path")
    ap.add_argument("--baseline", default="", help="baseline key for --row/--fresh")
    ap.add_argument("--row", default="", metavar="FILE", help="guard a bench row from FILE (or - for stdin)")
    ap.add_argument("--fresh", action="store_true", help="run the baseline's bench command and guard its row")
    ap.add_argument(
        "--tolerance-only", action="store_true",
        help="time/rate violations are reported but only exact metrics fail "
        "(the make verify mode: slow CI boxes must not flake the build)",
    )
    args = ap.parse_args()

    try:
        manifest = load_manifest(args.manifest)
    except GuardError as e:
        print(json.dumps({"error": str(e), "stage": "manifest"}))
        print(f"perf-guard: {e}", file=sys.stderr)
        return 2

    reports: List[GuardReport] = []
    try:
        if args.row or args.fresh:
            key = args.baseline
            if not key:
                if len(manifest["baselines"]) == 1:
                    key = next(iter(manifest["baselines"]))
                else:
                    raise GuardError(
                        "--baseline KEY required (known: "
                        + ", ".join(sorted(manifest["baselines"])) + ")"
                    )
            if key not in manifest["baselines"]:
                raise GuardError(f"unknown baseline {key!r}")
            entry = manifest["baselines"][key]
            if args.fresh:
                row = run_fresh(entry)
            else:
                raw = sys.stdin.read() if args.row == "-" else open(args.row).read()
                row = json.loads(raw)
            reports.append(compare(row, key, entry, tolerance_only=args.tolerance_only))
        else:
            reports = self_check(manifest)
    except (GuardError, OSError, ValueError) as e:
        print(json.dumps({"error": str(e), "stage": "guard"}))
        print(f"perf-guard: {e}", file=sys.stderr)
        return 2

    for report in reports:
        render_report(report, sys.stderr)
    failed = [r for r in reports if not r.ok]
    warned = sum(len(r.warned) for r in reports)
    print(
        json.dumps(
            {
                "metric": "perf-guard",
                "baselines": len(reports),
                "failed": [r.baseline for r in failed],
                "warnings": warned,
                "tolerance_only": args.tolerance_only,
                "ok": not failed,
                "reports": [
                    {"baseline": r.baseline, "verdicts": [asdict(v) for v in r.verdicts]}
                    for r in reports
                ],
            },
            sort_keys=True,
        )
    )
    return 0 if not failed else 1


if __name__ == "__main__":
    sys.exit(main())
