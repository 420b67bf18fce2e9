"""Detector-awake lint corpus — proof every registered rule still fires.

A detector that silently stops firing is worse than no detector: the
perf-guard and tsan gates already self-check their detectors, and this
module extends the pattern to every OSL rule. ``tests/lint_corpus/``
holds, per rule:

- ``<CODE>_fire.py`` — a minimal fixture the rule MUST fire on;
- ``<CODE>_clean.py`` — the paired clean variant it MUST stay quiet on;
- or ``<CODE>_fire/`` / ``<CODE>_clean/`` directories for rules that need
  more than one file (OSL1804 ships a registry and its arena structs).

Because many rules are path-scoped (``paths = ("engine/", ...)``), a
fixture's FIRST line may declare the virtual path it should be linted
under::

    # lint-corpus-path: opensim_tpu/engine/fixture.py

:func:`check_corpus` runs each fixture with ONLY its rule selected and
returns a list of problems (empty == every detector awake):

- a registered rule with no fire fixture (new rule, no corpus entry);
- a fire fixture that does not fire, or a clean fixture that does;
- a fixture naming an unregistered rule code (stale after rule removal);
- a rule with no clean fixture (nothing pins the rule's precision).

Wired into ``make lint`` (``--corpus tests/lint_corpus``) and the tier-1
suite (``tests/test_lint_corpus.py``).
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, Optional, Tuple

from .core import RULES, FileContext, _make_context, _run

__all__ = ["check_corpus", "corpus_inventory", "run_fixture"]

_PATH_RE = re.compile(r"#\s*lint-corpus-path:\s*(\S+)")
_NAME_RE = re.compile(r"^(OSL\d+)_(fire|clean)(?:_[A-Za-z0-9_]+)?(?:\.py)?$")


def _virtual_path(source: str, default: str) -> str:
    first = source.split("\n", 1)[0]
    m = _PATH_RE.search(first)
    return m.group(1) if m else default


def run_fixture(path: str, rule_code: str) -> Tuple[List[str], Optional[str]]:
    """Lint one fixture (file or directory) with only ``rule_code``
    selected. Returns (codes of findings, error string or None)."""
    rule = next((r for r in RULES.values() if r.code == rule_code), None)
    if rule is None:
        return [], f"unknown rule code {rule_code}"
    contexts: List[FileContext] = []
    errors: List[str] = []
    files: List[str] = []
    if os.path.isdir(path):
        for dirpath, dirnames, filenames in os.walk(path):
            dirnames[:] = sorted(dirnames)
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    files.append(os.path.join(dirpath, fn))
    else:
        files.append(path)
    for fpath in files:
        with open(fpath, "r", encoding="utf-8") as fh:
            source = fh.read()
        ctx, err = _make_context(source, _virtual_path(source, fpath))
        if err is not None:
            errors.append(f"{fpath}: does not parse: {err.message}")
        elif ctx is not None:
            contexts.append(ctx)
    if errors:
        return [], "; ".join(errors)
    findings = _run(contexts, [], [rule.name])
    return [f.code for f in findings], None


def corpus_inventory(corpus_dir: str) -> Dict[str, Dict[str, List[str]]]:
    """{rule code: {"fire": [paths], "clean": [paths]}} from the corpus
    directory layout (files and fixture directories both count)."""
    inv: Dict[str, Dict[str, List[str]]] = {}
    for name in sorted(os.listdir(corpus_dir)):
        full = os.path.join(corpus_dir, name)
        if name.startswith((".", "_")) or name == "README.md":
            continue
        m = _NAME_RE.match(name)
        if m is None:
            if name.endswith(".py") or os.path.isdir(full):
                inv.setdefault("<unparsable>", {}).setdefault("fire", []).append(full)
            continue
        code, kind = m.group(1), m.group(2)
        inv.setdefault(code, {}).setdefault(kind, []).append(full)
    return inv


def check_corpus(corpus_dir: str) -> List[str]:
    """Run the full corpus gate; returns problems (empty == pass)."""
    problems: List[str] = []
    if not os.path.isdir(corpus_dir):
        return [f"corpus directory {corpus_dir} does not exist"]
    inv = corpus_inventory(corpus_dir)
    for full in inv.pop("<unparsable>", {}).get("fire", []):
        problems.append(
            f"{full}: fixture name must look like OSL123_fire[.py] / "
            "OSL123_clean[.py]"
        )
    registered = {r.code for r in RULES.values()}
    for code in sorted(registered):
        entry = inv.get(code, {})
        if not entry.get("fire"):
            problems.append(f"{code}: no firing fixture in {corpus_dir} — add "
                            f"{code}_fire.py so the detector stays provably awake")
        if not entry.get("clean"):
            problems.append(f"{code}: no clean fixture in {corpus_dir} — add "
                            f"{code}_clean.py pinning what the rule must NOT flag")
    for code in sorted(inv):
        if code not in registered:
            problems.append(
                f"{code}: corpus fixtures exist but no such rule is registered "
                "(stale fixture after a rule removal?)"
            )
            continue
        for kind in ("fire", "clean"):
            for path in inv[code].get(kind, []):
                codes, err = run_fixture(path, code)
                if err is not None:
                    problems.append(f"{path}: {err}")
                    continue
                fired = code in codes
                stray = sorted({c for c in codes if c not in (code, "OSL000")})
                if stray:
                    problems.append(
                        f"{path}: unexpected findings {stray} from a "
                        f"single-rule run of {code}"
                    )
                if kind == "fire" and not fired:
                    problems.append(
                        f"{path}: detector asleep — {code} did not fire on its "
                        "fire fixture"
                    )
                elif kind == "clean" and fired:
                    problems.append(
                        f"{path}: precision regression — {code} fired on its "
                        "clean fixture"
                    )
    return problems
