"""Parallel per-file lint tier + ``--changed``: the process-pool path
must be byte-identical to serial (including parse errors), cache its
results, degrade to serial when the pool cannot pay for itself, and the
diff-scoped flow must pick the right files out of ``git status``.
Also pins the OSL18xx cache axis: a policy-VALUE-only edit to
``encoding/dtypes.py`` must invalidate the cached project pass."""

import os
import subprocess
import textwrap

from opensim_tpu.analysis import lint_paths
from opensim_tpu.analysis.__main__ import _git_changed_files
from opensim_tpu.analysis.core import _PARALLEL_MIN_MISSES, _resolve_jobs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
UTILS = os.path.join(REPO, "opensim_tpu", "utils")


def _write_tree(root, files):
    for rel, src in files.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as fh:
            fh.write(textwrap.dedent(src))


# -- process-pool tier ------------------------------------------------------


def test_resolve_jobs_degrades_to_serial():
    assert _resolve_jobs(1, 100) == 1
    assert _resolve_jobs(4, _PARALLEL_MIN_MISSES - 1) == 1  # pool can't pay
    assert _resolve_jobs(4, 100) == 4
    assert _resolve_jobs(16, 10) == 10  # never more workers than misses
    assert _resolve_jobs(None, 0) == 1  # warm cache: nothing to fan out


def test_parallel_is_byte_identical_to_serial(tmp_path):
    stats_s, stats_p = {}, {}
    serial = lint_paths([UTILS], stats=stats_s,
                        cache_path=str(tmp_path / "s.json"), jobs=1)
    par = lint_paths([UTILS], stats=stats_p,
                     cache_path=str(tmp_path / "p.json"), jobs=2)
    assert stats_s["jobs"] == 1
    assert stats_p["jobs"] == 2, "pool did not engage on a cold run"
    assert [f.as_dict() for f in serial] == [f.as_dict() for f in par]


def test_parallel_results_are_cached(tmp_path):
    cache = str(tmp_path / "cache.json")
    cold = lint_paths([UTILS], cache_path=cache, jobs=2)
    stats: dict = {}
    warm = lint_paths([UTILS], stats=stats, cache_path=cache, jobs=2)
    assert stats["cache_misses"] == 0 and stats["cache_hits"] > 0
    assert stats["jobs"] == 1  # no misses -> nothing to fan out
    assert [f.as_dict() for f in warm] == [f.as_dict() for f in cold]


def test_parallel_parse_errors_match_serial(tmp_path):
    tree = str(tmp_path / "proj")
    files = {f"m{i}.py": "x = 1\n" for i in range(_PARALLEL_MIN_MISSES)}
    files["broken.py"] = "def oops(:\n"
    _write_tree(tree, files)
    serial = lint_paths([tree], cache_path=str(tmp_path / "s.json"), jobs=1)
    par = lint_paths([tree], cache_path=str(tmp_path / "p.json"), jobs=2)
    assert [f.as_dict() for f in serial] == [f.as_dict() for f in par]
    assert any(f.code == "OSL000" for f in par), "parse error lost in the pool"


# -- --changed file selection ----------------------------------------------


def _git(cwd, *args):
    subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
        cwd=cwd, check=True, capture_output=True,
    )


def test_git_changed_files_scopes_to_lint_roots(tmp_path, monkeypatch):
    repo = str(tmp_path / "repo")
    _write_tree(repo, {
        "pkg/a.py": "a = 1\n",
        "pkg/b.py": "b = 1\n",
        "pkg/engine.cc": "// v1\n",
        "elsewhere/c.py": "c = 1\n",
    })
    _git(repo, "init", "-q")
    _git(repo, "add", ".")
    _git(repo, "commit", "-qm", "seed")
    # modify one .py, one non-Python file, one out-of-scope file; add one
    # untracked .py
    _write_tree(repo, {
        "pkg/a.py": "a = 2\n",
        "pkg/engine.cc": "// v2\n",
        "elsewhere/c.py": "c = 2\n",
        "pkg/new.py": "n = 1\n",
    })
    monkeypatch.chdir(repo)
    changed = _git_changed_files(["pkg"])
    # a.py (modified) and new.py (untracked); b.py (clean), the .cc (not
    # lintable) and elsewhere/ (out of scope) not
    assert changed == ["pkg/a.py", "pkg/new.py"]


def test_git_changed_files_outside_checkout_returns_none(tmp_path, monkeypatch):
    monkeypatch.chdir(str(tmp_path))
    monkeypatch.setenv("GIT_DIR", str(tmp_path / "nope"))
    assert _git_changed_files(["pkg"]) is None


def test_changed_style_scoped_run_keeps_full_project_slot(tmp_path):
    # the point of the 4-slot project cache: a diff-scoped run (different
    # path set -> different project digest) lands in its own slot, and the
    # next full run still reuses the full-repo slot
    tree = str(tmp_path / "proj")
    cache = str(tmp_path / "cache.json")
    _write_tree(tree, {"a/x.py": "x = 1\n", "b/y.py": "y = 2\n"})
    lint_paths([tree], cache_path=cache)
    lint_paths([os.path.join(tree, "a", "x.py")], cache_path=cache)  # scoped
    stats: dict = {}
    lint_paths([tree], stats=stats, cache_path=cache)
    assert stats["project_pass"] == "reused", "scoped run evicted the full slot"
    assert stats["cache_misses"] == 0


# -- OSL18xx cache invalidation on policy-value edits -----------------------

_MINI_DTYPES = """
import numpy as np

FLOAT_DTYPE = np.float32
INT_DTYPE = np.int32

AXIS_ALIASES = {}
ARENA_CONTRACTS = {"alloc": ("FLOAT_DTYPE", ("N", "R"))}
STATE_CONTRACTS = {}
KERNEL_ARG_CONTRACTS = {}
STRUCT_PARAM_NAMES = {}
"""

_MINI_BUILDER = """
import numpy as np

def build(n, r):
    from .state import EncodedCluster
    return EncodedCluster(alloc=np.zeros((n, r)))
"""


def test_policy_value_edit_invalidates_cached_findings(tmp_path):
    tree = str(tmp_path / "proj")
    cache = str(tmp_path / "cache.json")
    _write_tree(tree, {
        "encoding/dtypes.py": _MINI_DTYPES,
        "encoding/builder.py": _MINI_BUILDER,
    })
    rules = ["array-off-policy"]
    cold = lint_paths([tree], rules=rules, cache_path=cache)
    assert [f.code for f in cold] == ["OSL1801"]  # f64 default vs f32 policy
    # warm: same answer from the project slot
    stats: dict = {}
    warm = lint_paths([tree], rules=rules, stats=stats, cache_path=cache)
    assert stats["project_pass"] == "reused"
    assert [f.as_dict() for f in warm] == [f.as_dict() for f in cold]
    # flip ONLY the policy VALUE: the same builder is now on-policy, and
    # the warm cache must notice (dtypes.py content feeds the digest)
    with open(os.path.join(tree, "encoding", "dtypes.py"), "w") as fh:
        fh.write(_MINI_DTYPES.replace("np.float32", "np.float64"))
    after = lint_paths([tree], rules=rules, cache_path=cache)
    assert after == [], "stale project slot survived a policy-value edit"
