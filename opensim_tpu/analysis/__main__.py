"""CLI entry: ``python -m opensim_tpu.analysis [paths...]``."""

from __future__ import annotations

import argparse
import os
import re
import sys
from typing import Dict, List, Optional

from . import RULES, lint_paths, render_human, render_json, render_sarif


def pyproject_defaults(path: str = "pyproject.toml") -> Dict[str, List[str]]:
    """Defaults from ``[tool.opensim-lint]`` (``paths``/``rules`` string
    arrays). Uses tomllib where available (3.11+); this image runs 3.10,
    so a minimal literal reader covers the two keys we define."""
    if not os.path.isfile(path):
        return {}
    with open(path, "rb") as fh:
        raw = fh.read()
    try:
        import tomllib  # Python 3.11+
    except ImportError:
        tomllib = None
    if tomllib is not None:
        try:
            table = tomllib.loads(raw.decode()).get("tool", {}).get("opensim-lint", {})
            return {k: v for k, v in table.items() if isinstance(v, list)}
        except tomllib.TOMLDecodeError:
            pass  # malformed elsewhere in the file: the minimal reader below
    m = re.search(r"^\[tool\.opensim-lint\]\s*$(.*?)(?=^\[|\Z)", raw.decode(), re.M | re.S)
    if not m:
        return {}
    out: Dict[str, List[str]] = {}
    for key, body in re.findall(r"^(\w[\w-]*)\s*=\s*\[(.*?)\]", m.group(1), re.M | re.S):
        out[key] = re.findall(r'"([^"]+)"', body)
    return out


def _git_changed_files(roots: List[str]) -> Optional[List[str]]:
    """Lintable files with uncommitted changes (``git status
    --porcelain``), scoped to the configured lint roots. Returns None when
    not in a git checkout (caller falls back to a full run)."""
    import subprocess

    try:
        proc = subprocess.run(
            ["git", "status", "--porcelain"],
            capture_output=True, text=True, timeout=30,
        )
    except (OSError, subprocess.SubprocessError):
        return None
    if proc.returncode != 0:
        return None

    def in_scope(path: str) -> bool:
        norm = path.replace(os.sep, "/")
        return any(
            norm == r or norm.startswith(r.rstrip("/") + "/")
            for r in (root.replace(os.sep, "/") for root in roots)
        )

    out: List[str] = []
    for line in proc.stdout.splitlines():
        if len(line) < 4:
            continue
        path = line[3:].strip()
        if " -> " in path:  # rename: lint the new name
            path = path.split(" -> ", 1)[1]
        if not in_scope(path) or not os.path.exists(path):
            continue
        if path.endswith(".py") or os.path.isdir(path):
            out.append(path)
    return sorted(set(out))


def _checked_flag_paths(args):
    """Validate the path-valued flags (registered validators, OSL1603);
    raises ValueError with the usual one-liner text."""
    from ..utils.validate import user_path

    cache_path = None
    if args.cache and not args.no_cache:
        cache_path = user_path(args.cache, label="--cache")
    sarif_out = user_path(args.sarif_out or "", label="--sarif-out", allow_empty=True)
    corpus_dir = user_path(args.corpus or "", label="--corpus", allow_empty=True)
    return cache_path, sarif_out, corpus_dir


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="opensim-lint",
        description="repo-specific AST correctness analyzer (see docs/static-analysis.md)",
    )
    ap.add_argument(
        "paths",
        nargs="*",
        default=None,
        help="files/directories to lint (default: [tool.opensim-lint] paths "
        "in ./pyproject.toml, else opensim_tpu)",
    )
    ap.add_argument("--json", action="store_true", help="emit findings as JSON")
    ap.add_argument(
        "--format",
        choices=("human", "json", "sarif"),
        default=None,
        help="output format (sarif emits SARIF 2.1.0 for CI/editor "
        "annotation; --json is shorthand for --format json)",
    )
    ap.add_argument(
        "--rules",
        help="comma-separated rule names/codes to run (default: all)",
    )
    ap.add_argument("--list-rules", action="store_true", help="list rules and exit")
    ap.add_argument(
        "--cache",
        metavar="PATH",
        default=None,
        help="content-hash result cache (unchanged files skip their rules; "
        "default .lint/cache.json under make lint, off otherwise)",
    )
    ap.add_argument(
        "--no-cache",
        action="store_true",
        help="disable the result cache even if --cache was given",
    )
    ap.add_argument(
        "--sarif-out",
        metavar="PATH",
        default=None,
        help="ALSO write SARIF 2.1.0 to this path (stable artifact for CI "
        "upload), independent of --format",
    )
    ap.add_argument(
        "--corpus",
        metavar="DIR",
        default=None,
        help="after linting, run the detector-awake corpus gate over DIR "
        "(every registered rule must fire on its fixture and stay quiet "
        "on the clean variant)",
    )
    ap.add_argument(
        "--changed",
        action="store_true",
        help="lint only files with uncommitted git changes under the "
        "configured paths — the fast pre-commit loop; whole-program rules "
        "see just this subset and cache in their own project slot",
    )
    ap.add_argument(
        "--jobs",
        type=int,
        default=None,
        metavar="N",
        help="process-pool width for the per-file rule tier on cache "
        "misses (default: auto-size to the machine; 1 forces serial; "
        "results are byte-identical either way)",
    )
    ap.add_argument(
        "--check-typed-core",
        action="store_true",
        help="stdlib typed-core signature check (make mypy fallback)",
    )
    args = ap.parse_args(argv)

    if args.check_typed_core:
        from .typed_core import check_typed_core

        problems = check_typed_core()
        for p in problems:
            print(p)
        print(
            f"typed-core: {len(problems)} problem(s)"
            if problems
            else "typed-core: signatures complete"
        )
        return 1 if problems else 0

    if args.list_rules:
        for name in sorted(RULES):
            r = RULES[name]
            print(f"{r.code}  {r.name:18s} {r.description}")
        return 0

    cfg = pyproject_defaults()
    if args.rules:
        rules: Optional[List[str]] = [r for r in args.rules.split(",") if r]
    else:
        rules = cfg.get("rules") or None
    paths = args.paths or cfg.get("paths") or ["opensim_tpu"]
    fmt = args.format or ("json" if args.json else "human")
    if args.changed:
        changed = _git_changed_files(paths)
        if changed is None:
            print("opensim-lint: --changed needs a git checkout", file=sys.stderr)
            return 2
        if not changed:
            print("opensim-lint: --changed: no modified files under "
                  + ", ".join(paths) + "; nothing to lint")
            return 0
        paths = changed
    try:
        cache_path, sarif_out, corpus_dir = _checked_flag_paths(args)
    except ValueError as e:
        print(f"opensim-lint: {e}", file=sys.stderr)
        return 2
    stats: dict = {}
    findings = lint_paths(
        paths, rules=rules, stats=stats, cache_path=cache_path, jobs=args.jobs
    )
    if sarif_out:
        out_dir = os.path.dirname(sarif_out)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        with open(sarif_out, "w", encoding="utf-8") as fh:
            fh.write(render_sarif(findings))
    if fmt == "json":
        print(render_json(findings))
    elif fmt == "sarif":
        print(render_sarif(findings))
    else:
        # total lint wall time rides the `make lint` output: every file is
        # parsed once and the AST shared across all rules (and, with
        # --cache, unchanged files skip their rules entirely)
        print(render_human(findings, stats=stats))
    rc = 1 if findings else 0
    if corpus_dir:
        from .corpus import check_corpus, corpus_inventory

        problems = check_corpus(corpus_dir)
        if problems:
            for p in problems:
                print(f"lint-corpus: {p}")
            rc = 1
        else:
            inv = corpus_inventory(corpus_dir)
            n_fix = sum(len(v) for e in inv.values() for v in e.values())
            print(
                f"lint-corpus: {len(RULES)} rules, {n_fix} fixtures, "
                "all detectors awake"
            )
    return rc


if __name__ == "__main__":
    sys.exit(main())
