"""OSL16xx rule pack (analysis/rules_dataflow.py): each rule fires on its
known-bad fixture, stays quiet on the disciplined twin, honors
suppressions, and the repo itself stays clean — plus the incremental lint
cache's hit/miss/invalidenation behavior."""

import os
import textwrap

from opensim_tpu.analysis import RULES, lint_paths, lint_source

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIX = "opensim_tpu/server/fixture.py"


def _codes(src, path=FIX, rules=None):
    return [f.code for f in lint_source(textwrap.dedent(src), path=path, rules=rules)]


def test_osl16xx_registered():
    by_code = {r.code for r in RULES.values()}
    assert {"OSL1601", "OSL1602", "OSL1603"} <= by_code
    assert {"OSL1801", "OSL1802", "OSL1803", "OSL1804"} <= by_code
    assert "OSL1901" in by_code
    assert len(RULES) == 27


# ---------------------------------------------------------------------------
# OSL1601 jit-impurity
# ---------------------------------------------------------------------------


def test_jit_impurity_fires_across_call_graph_depth():
    src = """
    import time

    import jax

    def helper(c):
        return c * time.time()

    def body(carry, x):
        return helper(carry), x

    def outer(xs):
        return jax.lax.scan(body, 0, xs)
    """
    findings = lint_source(textwrap.dedent(src), path=FIX, rules=["jit-impurity"])
    assert [f.code for f in findings] == ["OSL1601"]
    # the message names the effect, the root, and the call chain
    assert "time.time" in findings[0].message
    assert "body" in findings[0].message and "helper" in findings[0].message


def test_jit_impurity_quiet_on_host_code_and_pure_traced_code():
    src = """
    import time

    import jax

    @jax.jit
    def step(x):
        return x + 1

    def host(xs):
        t0 = time.time()
        return step(xs), time.time() - t0
    """
    assert _codes(src, rules=["jit-impurity"]) == []


def test_jit_impurity_suppression():
    src = """
    import time

    import jax

    @jax.jit
    def step(x):
        return x + time.time()  # opensim-lint: disable=jit-impurity
    """
    assert _codes(src, rules=["jit-impurity"]) == []


def test_jit_impurity_repo_is_clean():
    root = os.path.join(REPO, "opensim_tpu")
    findings = [f for f in lint_paths([root]) if f.code == "OSL1601"]
    assert findings == [], [f"{f.path}:{f.line}: {f.message}" for f in findings]


# ---------------------------------------------------------------------------
# OSL1602 tracer-leak
# ---------------------------------------------------------------------------


def test_tracer_leak_fires_on_outliving_stores():
    src = """
    import jax
    import jax.numpy as jnp

    _HISTORY = []

    class Rec:
        @jax.jit
        def step(self, x):
            y = jnp.sum(x)
            self.last = y
            _HISTORY.append(x)
            return y
    """
    assert _codes(src, rules=["tracer-leak"]) == ["OSL1602", "OSL1602"]


def test_tracer_leak_quiet_on_concrete_and_local_state():
    src = """
    import jax
    import jax.numpy as jnp

    class Rec:
        @jax.jit
        def step(self, x):
            y = jnp.sum(x)
            self.calls = int(3)   # concrete host value: fine
            scratch = [y]
            scratch.append(y)     # local container: fine
            return y
    """
    assert _codes(src, rules=["tracer-leak"]) == []


def test_tracer_leak_repo_is_clean():
    root = os.path.join(REPO, "opensim_tpu")
    findings = [f for f in lint_paths([root]) if f.code == "OSL1602"]
    assert findings == [], [f"{f.path}:{f.line}: {f.message}" for f in findings]


# ---------------------------------------------------------------------------
# OSL1603 input-taint
# ---------------------------------------------------------------------------


def test_input_taint_fires_and_names_the_source():
    src = """
    from urllib.parse import parse_qs

    def handler(q):
        name = parse_qs(q).get("f", [""])[-1]
        return open(name)
    """
    findings = lint_source(textwrap.dedent(src), path=FIX, rules=["input-taint"])
    assert [f.code for f in findings] == ["OSL1603"]
    assert "http-query" in findings[0].message


def test_input_taint_quiet_through_registered_sanitizer():
    src = """
    from urllib.parse import parse_qs

    def sanitizer(fn):
        return fn

    @sanitizer
    def safe_name(raw):
        if not raw.isidentifier():
            raise ValueError(raw)
        return raw

    def handler(q):
        return open(safe_name(parse_qs(q).get("f", [""])[-1]))
    """
    assert _codes(src, rules=["input-taint"]) == []


def test_input_taint_interprocedural_and_cli_sources():
    src = """
    import sys

    def save(path, data):
        with open(path, "w") as fh:
            fh.write(data)

    def main():
        save(sys.argv[1], "hello")
    """
    findings = lint_source(textwrap.dedent(src), path=FIX, rules=["input-taint"])
    assert [f.code for f in findings] == ["OSL1603"]
    assert "cli-arg" in findings[0].message


def test_input_taint_repo_is_clean():
    root = os.path.join(REPO, "opensim_tpu")
    findings = [f for f in lint_paths([root]) if f.code == "OSL1603"]
    assert findings == [], [f"{f.path}:{f.line}: {f.message}" for f in findings]


# ---------------------------------------------------------------------------
# incremental lint cache
# ---------------------------------------------------------------------------


def _write_tree(root, files):
    for rel, src in files.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as fh:
            fh.write(textwrap.dedent(src))


def test_cache_cold_then_warm_then_invalidate(tmp_path):
    tree = str(tmp_path / "proj")
    cache = str(tmp_path / "cache.json")
    _write_tree(
        tree,
        {
            "a.py": """
            def swallow(risky):
                try:
                    risky()
                except Exception:
                    pass
            """,
            "b.py": "x = 1\n",
        },
    )
    stats1: dict = {}
    f1 = lint_paths([tree], stats=stats1, cache_path=cache)
    assert stats1["cache_misses"] == 2 and stats1["cache_hits"] == 0
    assert stats1["project_pass"] == "rebuilt"
    assert [f.code for f in f1] == ["OSL501"]

    stats2: dict = {}
    f2 = lint_paths([tree], stats=stats2, cache_path=cache)
    assert stats2["cache_hits"] == 2 and stats2["cache_misses"] == 0
    assert stats2["project_pass"] == "reused"
    assert [f.as_dict() for f in f2] == [f.as_dict() for f in f1]

    # edit ONE file: that file misses, the other still hits, project rebuilds
    with open(os.path.join(tree, "b.py"), "w") as fh:
        fh.write("y = 2\n")
    stats3: dict = {}
    f3 = lint_paths([tree], stats=stats3, cache_path=cache)
    assert stats3["cache_hits"] == 1 and stats3["cache_misses"] == 1
    assert stats3["project_pass"] == "rebuilt"
    assert [f.code for f in f3] == ["OSL501"]


def test_cache_results_match_uncached_run(tmp_path):
    cache = str(tmp_path / "cache.json")
    root = os.path.join(REPO, "opensim_tpu", "utils")
    plain = [f.as_dict() for f in lint_paths([root])]
    cached_cold = [f.as_dict() for f in lint_paths([root], cache_path=cache)]
    cached_warm = [f.as_dict() for f in lint_paths([root], cache_path=cache)]
    assert plain == cached_cold == cached_warm


def test_cache_scoped_run_does_not_evict_other_entries(tmp_path):
    # review regression: `simon lint <subdir> --cache shared.json` must not
    # wipe the full-run cache (prune only drops entries for DELETED files)
    tree = str(tmp_path / "proj")
    cache = str(tmp_path / "cache.json")
    _write_tree(tree, {"a/x.py": "x = 1\n", "b/y.py": "y = 2\n"})
    stats: dict = {}
    lint_paths([tree], stats=stats, cache_path=cache)
    # scoped run over a/ only
    lint_paths([os.path.join(tree, "a")], cache_path=cache)
    stats2: dict = {}
    lint_paths([tree], stats=stats2, cache_path=cache)
    assert stats2["cache_hits"] == 2, "scoped run evicted the sibling's entry"
    assert stats2["project_pass"] == "reused", "scoped run clobbered the project slot"
