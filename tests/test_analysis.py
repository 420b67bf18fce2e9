"""opensim-lint (opensim_tpu/analysis): each rule fires on a known-bad
fixture, stays silent on the known-good twin, and honors the suppression
syntax — plus the meta-test that the repo itself is lint-clean and the
typed-core signature gate holds."""

import os
import textwrap

from opensim_tpu.analysis import RULES, lint_paths, lint_source, render_human, render_json
from opensim_tpu.analysis.typed_core import check_typed_core

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _codes(src, path="x.py", rules=None):
    return [f.code for f in lint_source(textwrap.dedent(src), path=path, rules=rules)]


# ---------------------------------------------------------------------------
# OSL101 jit-boundary
# ---------------------------------------------------------------------------

JIT_PATH = "opensim_tpu/engine/fixture.py"  # rule is scoped to engine/ops/parallel


def test_jit_boundary_fires_on_host_calls_in_traced_code():
    src = """
    import time, random, jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def step(x):
        t = time.monotonic()          # host clock at trace time
        y = np.asarray(x)             # tracer -> host numpy
        v = x.sum().item()            # device sync
        if jnp.any(x > 0):            # python control flow on tracer
            x = x + 1
        return x
    """
    codes = _codes(src, path=JIT_PATH, rules=["jit-boundary"])
    assert codes == ["OSL101"] * 4


def test_jit_boundary_reaches_through_call_graph_and_lax_entry_points():
    src = """
    import random, jax

    def helper(c):
        return c * random.random()

    def body(carry, x):
        return helper(carry), x

    def outer(xs):
        return jax.lax.scan(body, 0, xs)
    """
    codes = _codes(src, path=JIT_PATH, rules=["jit-boundary"])
    assert codes == ["OSL101"]  # random.random inside helper, via body


def test_jit_boundary_silent_on_host_side_code_and_other_dirs():
    src = """
    import time, jax

    @jax.jit
    def step(x):
        return x + 1

    def host_driver(xs):
        t0 = time.monotonic()        # fine: not traced
        return step(xs), time.monotonic() - t0
    """
    assert _codes(src, path=JIT_PATH, rules=["jit-boundary"]) == []
    bad = """
    import time, jax

    @jax.jit
    def step(x):
        return time.time()
    """
    # same code outside engine/ops/parallel is out of the rule's scope
    assert _codes(bad, path="opensim_tpu/chart/fixture.py", rules=["jit-boundary"]) == []


def test_jit_boundary_suppression():
    src = """
    import time, jax

    @jax.jit
    def step(x):
        t = time.monotonic()  # opensim-lint: disable=jit-boundary
        return x
    """
    assert _codes(src, path=JIT_PATH, rules=["jit-boundary"]) == []


# ---------------------------------------------------------------------------
# OSL201 dtype-drift
# ---------------------------------------------------------------------------

ENC_PATH = "opensim_tpu/encoding/fixture.py"  # rule is scoped to encoding/


def test_dtype_drift_fires_on_float64_and_default_dtype():
    src = """
    import numpy as np

    def build(n):
        a = np.zeros((n,))                       # default dtype
        b = np.arange(n + 1, dtype=np.float64)   # bare float64
        c = np.full((n,), -1)                    # no dtype
        return a, b, c
    """
    codes = _codes(src, path=ENC_PATH, rules=["dtype-drift"])
    assert codes == ["OSL201"] * 3


def test_dtype_drift_silent_on_policy_compliant_arrays():
    src = """
    import numpy as np
    from opensim_tpu.encoding.dtypes import FLOAT_DTYPE, INT_DTYPE, log_size_table

    def build(n, a):
        x = np.zeros((n,), dtype=FLOAT_DTYPE)
        y = np.full((n,), -1, np.int32)          # positional dtype
        z = np.full(a.shape, 0, dtype=a.dtype)   # dtype-preserving growth
        return x, y, z, log_size_table(n)
    """
    assert _codes(src, path=ENC_PATH, rules=["dtype-drift"]) == []
    # out of scope: non-encoding paths may use numpy defaults
    bad = "import numpy as np\na = np.zeros((3,))\n"
    assert _codes(bad, path="opensim_tpu/planner/fixture.py", rules=["dtype-drift"]) == []


def test_dtype_drift_file_level_suppression():
    src = """
    # opensim-lint: disable-file=dtype-drift
    import numpy as np
    a = np.zeros((4,))
    """
    assert _codes(src, path=ENC_PATH, rules=["dtype-drift"]) == []


# ---------------------------------------------------------------------------
# OSL301 determinism
# ---------------------------------------------------------------------------


def test_determinism_fires_on_set_iteration_and_hash_fed_dict_views():
    src = """
    import hashlib

    def fingerprint(d):
        h = hashlib.blake2b()
        for k, v in d.items():        # dict order feeds the hash
            h.update(str((k, v)).encode())
        return h.hexdigest()

    def render(names):
        return ",".join({n for n in names})   # set order into a stream
    """
    codes = _codes(src, rules=["determinism"])
    assert codes == ["OSL301"] * 2


def test_determinism_silent_on_sorted_iteration():
    src = """
    import hashlib

    def fingerprint(d):
        h = hashlib.blake2b()
        for k in sorted(d.items()):
            h.update(str(k).encode())
        return h.hexdigest()

    def render(names):
        return ",".join(sorted(set(names)))

    def count(names):
        return len(set(names))        # cardinality: order irrelevant

    def plain(d):
        return [v for v in d.values()]  # dict order, no hash scope: fine
    """
    assert _codes(src, rules=["determinism"]) == []


def test_determinism_suppression_on_previous_line():
    src = """
    def render(names):
        # opensim-lint: disable=determinism
        return ",".join({n for n in names})
    """
    assert _codes(src, rules=["determinism"]) == []


# ---------------------------------------------------------------------------
# OSL401 cache-mutation
# ---------------------------------------------------------------------------


def test_cache_mutation_fires_on_mutation_after_fingerprint():
    src = """
    from opensim_tpu.engine.prepcache import fingerprint_cluster

    def bad(cluster, extra_pod):
        fp = fingerprint_cluster(cluster)
        cluster.pods.append(extra_pod)          # direct container mutation
        for p in cluster.pods:
            p.phase = "Running"                 # via a loop alias
        return fp
    """
    codes = _codes(src, rules=["cache-mutation"])
    assert codes == ["OSL401"] * 2


def test_cache_mutation_silent_when_invalidated_or_before_fingerprint():
    src = """
    from opensim_tpu.engine.prepcache import fingerprint_cluster

    def fixed(cluster, cache, extra_pod):
        fp = fingerprint_cluster(cluster)
        cluster.pods.append(extra_pod)
        cache.invalidate(cluster)               # the sanctioned escape

    def mutate_then_fingerprint(cluster, extra_pod):
        cluster.pods.append(extra_pod)          # before: content not yet keyed
        return fingerprint_cluster(cluster)

    def unrelated(cluster, other, extra_pod):
        fp = fingerprint_cluster(cluster)
        other.pods.append(extra_pod)            # different object
    """
    assert _codes(src, rules=["cache-mutation"]) == []


def test_cache_mutation_suppression():
    src = """
    from opensim_tpu.engine.prepcache import fingerprint_cluster

    def bad(cluster, extra_pod):
        fp = fingerprint_cluster(cluster)
        cluster.pods.append(extra_pod)  # opensim-lint: disable=cache-mutation
    """
    assert _codes(src, rules=["cache-mutation"]) == []


# ---------------------------------------------------------------------------
# OSL501 exception-swallow
# ---------------------------------------------------------------------------


def test_exception_swallow_fires_on_silent_broad_handlers():
    src = """
    def swallow():
        try:
            risky()
        except Exception:
            pass

    def swallow_bare():
        try:
            risky()
        except:
            return None
    """
    codes = _codes(src, rules=["exception-swallow"])
    assert codes == ["OSL501"] * 2


def test_exception_swallow_silent_on_raise_log_or_narrow():
    src = """
    import logging
    log = logging.getLogger(__name__)

    def translated():
        try:
            risky()
        except Exception as e:
            raise RuntimeError(str(e)) from e

    def logged():
        try:
            risky()
        except Exception as e:
            log.warning("risky failed: %s", e)

    def narrowed():
        try:
            risky()
        except ValueError:
            pass
    """
    assert _codes(src, rules=["exception-swallow"]) == []


def test_exception_swallow_suppression_by_code():
    src = """
    def swallow():
        try:
            risky()
        except Exception:  # opensim-lint: disable=OSL501
            pass
    """
    assert _codes(src, rules=["exception-swallow"]) == []


# ---------------------------------------------------------------------------
# engine plumbing + meta-tests
# ---------------------------------------------------------------------------


def test_unknown_rule_is_an_error():
    import pytest

    with pytest.raises(KeyError):
        lint_source("x = 1", rules=["no-such-rule"])


def test_render_formats():
    findings = lint_source(
        "def f():\n    try:\n        g()\n    except Exception:\n        pass\n",
        path="a.py",
    )
    assert len(findings) == 1
    human = render_human(findings)
    assert "a.py:4" in human and "OSL501" in human
    import json

    data = json.loads(render_json(findings))
    assert data[0]["rule"] == "exception-swallow" and data[0]["line"] == 4


def test_all_five_rules_registered():
    assert {
        "jit-boundary",
        "dtype-drift",
        "determinism",
        "cache-mutation",
        "exception-swallow",
    } <= set(RULES)


def test_repo_is_lint_clean():
    """The acceptance gate: `make lint` exits 0 on the package."""
    findings = lint_paths([os.path.join(REPO, "opensim_tpu")])
    assert findings == [], render_human(findings)


def test_strict_core_has_no_suppressions():
    """engine/prepcache.py and encoding/state.py must be clean WITHOUT
    suppression comments (ISSUE acceptance)."""
    for rel in ("opensim_tpu/engine/prepcache.py", "opensim_tpu/encoding/state.py"):
        with open(os.path.join(REPO, rel)) as fh:
            assert "opensim-lint: disable" not in fh.read(), rel


def test_typed_core_signatures_complete():
    assert check_typed_core(REPO) == []


def test_cli_main():
    from opensim_tpu.analysis.__main__ import main

    assert main(["--list-rules"]) == 0
    assert main([os.path.join(REPO, "opensim_tpu", "analysis")]) == 0


def test_pyproject_defaults_are_read():
    from opensim_tpu.analysis.__main__ import pyproject_defaults

    cfg = pyproject_defaults(os.path.join(REPO, "pyproject.toml"))
    assert cfg.get("paths") == ["opensim_tpu"]
    assert "jit-boundary" in cfg.get("rules", [])


def test_pyproject_rules_list_covers_every_registered_rule():
    # the [tool.opensim-lint] rules array is the default selection for
    # `make lint`: a registered rule missing from it silently never runs
    from opensim_tpu.analysis import RULES
    from opensim_tpu.analysis.__main__ import pyproject_defaults

    cfg = pyproject_defaults(os.path.join(REPO, "pyproject.toml"))
    assert sorted(cfg.get("rules", [])) == sorted(RULES)


def test_cache_mutation_release_is_per_object():
    # review fix: invalidate(cluster) must NOT silence the apps mutation
    src = """
    from opensim_tpu.engine.prepcache import fingerprint_cluster, fingerprint_apps

    def partial_release(cluster, apps, cache, extra):
        fingerprint_cluster(cluster)
        fingerprint_apps(apps)
        cluster.pods.append(extra)
        apps.pods.append(extra)
        cache.invalidate(cluster)      # covers cluster only
    """
    findings = lint_source(textwrap.dedent(src), rules=["cache-mutation"])
    assert len(findings) == 1 and "apps" in findings[0].message


def test_cache_mutation_argless_invalidate_releases_all():
    src = """
    from opensim_tpu.engine.prepcache import fingerprint_cluster, fingerprint_apps

    def full_release(cluster, apps, cache, extra):
        fingerprint_cluster(cluster)
        fingerprint_apps(apps)
        cluster.pods.append(extra)
        apps.pods.append(extra)
        cache.invalidate()             # drops everything
    """
    assert _codes(src, rules=["cache-mutation"]) == []


def test_cache_mutation_touch_on_loop_alias_releases_its_root():
    src = """
    from opensim_tpu.engine.prepcache import fingerprint_cluster

    def touched(cluster):
        fingerprint_cluster(cluster)
        for p in cluster.pods:
            p.phase = "Running"
            p.touch()                  # alias of cluster: releases it
    """
    assert _codes(src, rules=["cache-mutation"]) == []


def test_cache_mutation_nested_function_reports_once():
    src = """
    from opensim_tpu.engine.prepcache import fingerprint_cluster

    def outer():
        def inner(cluster, extra):
            fingerprint_cluster(cluster)
            cluster.pods.append(extra)
        return inner
    """
    assert _codes(src, rules=["cache-mutation"]) == ["OSL401"]


def test_typed_core_catches_multiline_signature_ignore(tmp_path):
    from opensim_tpu.analysis import typed_core

    bad = tmp_path / "mod.py"
    bad.write_text(
        "def f(\n    x: int,\n) -> int:  # type: ignore[override]\n    return x\n"
    )
    orig = typed_core.STRICT_MODULES
    typed_core.STRICT_MODULES = ("mod.py",)
    try:
        problems = typed_core.check_typed_core(str(tmp_path))
    finally:
        typed_core.STRICT_MODULES = orig
    assert len(problems) == 1 and "type: ignore" in problems[0]


def test_determinism_flags_sum_over_float_set():
    src = """
    def total(xs):
        return sum({float(x) for x in xs})   # order-dependent in the last ulp
    """
    assert _codes(src, rules=["determinism"]) == ["OSL301"]


def test_typed_core_catches_one_line_def_ignore(tmp_path):
    from opensim_tpu.analysis import typed_core

    bad = tmp_path / "mod.py"
    bad.write_text("def f(x: int) -> int: return x  # type: ignore\n")
    orig = typed_core.STRICT_MODULES
    typed_core.STRICT_MODULES = ("mod.py",)
    try:
        problems = typed_core.check_typed_core(str(tmp_path))
    finally:
        typed_core.STRICT_MODULES = orig
    assert len(problems) == 1 and "type: ignore" in problems[0]


# ---------------------------------------------------------------------------
# OSL601 unbounded-retry
# ---------------------------------------------------------------------------


def test_unbounded_retry_flags_while_true_around_network_call():
    src = """
    import urllib.request

    def fetch(url):
        while True:
            try:
                return urllib.request.urlopen(url)
            except OSError:
                pass                      # swallow and hammer forever
    """
    assert _codes(src, rules=["unbounded-retry"]) == ["OSL601"]


def test_unbounded_retry_flags_constant_sleep_in_loop():
    src = """
    import time

    def poll(client):
        for _ in range(10):
            if client.ready():
                break
            time.sleep(5)                # constant interval: no backoff
    """
    assert _codes(src, rules=["unbounded-retry"]) == ["OSL601"]


def test_unbounded_retry_accepts_bounded_backoff_and_escaping_handlers():
    src = """
    import time
    import urllib.request

    def fetch(url, attempts=3):
        for k in range(attempts):
            try:
                return urllib.request.urlopen(url)
            except OSError:
                if k == attempts - 1:
                    raise
                time.sleep(0.1 * 2 ** k)   # computed: exponential backoff

    def fail_fast(url):
        while True:
            try:
                return urllib.request.urlopen(url)
            except OSError:
                raise RuntimeError("down")  # handler escapes: not a retry loop

    def prompt_loop(ask):
        while True:                          # no network/device call: fine
            try:
                return int(ask())
            except ValueError:
                pass
    """
    assert _codes(src, rules=["unbounded-retry"]) == []


def test_unbounded_retry_suppression_and_device_calls():
    src = """
    import time, jax

    def hammer(x):
        while True:
            try:
                jax.device_put(x)  # opensim-lint: disable=unbounded-retry
            except RuntimeError:
                continue
    """
    # the loop finding anchors on the `while` line, which has no suppression
    flagged = _codes(src, rules=["unbounded-retry"])
    assert flagged == ["OSL601"]
    src2 = """
    import jax

    def hammer(x):
        # opensim-lint: disable=unbounded-retry
        while True:
            try:
                jax.device_put(x)
            except RuntimeError:
                continue
    """
    assert _codes(src2, rules=["unbounded-retry"]) == []


def test_unbounded_retry_nested_loops_report_sleep_once():
    src = """
    import time

    def poll():
        while running():
            for _ in range(3):
                time.sleep(2)
    """
    # the sleep belongs to its NEAREST enclosing loop only: one finding,
    # not one per enclosing loop level
    assert _codes(src, rules=["unbounded-retry"]) == ["OSL601"]


# ---------------------------------------------------------------------------
# OSL701 deadline-span
# ---------------------------------------------------------------------------


def test_deadline_span_fires_on_uninstrumented_phase_boundary():
    src = """
    from opensim_tpu.resilience.deadline import check_deadline

    def prepare_things(cluster):
        check_deadline("prepare")
        return encode(cluster)
    """
    assert _codes(src, path="opensim_tpu/engine/fixture.py", rules=["deadline-span"]) == ["OSL701"]


def test_deadline_span_fires_on_bare_deadline_scope():
    src = """
    from opensim_tpu.resilience.deadline import deadline_scope

    def handle(req, deadline):
        with deadline_scope(deadline):
            return run(req)
    """
    assert _codes(src, path="opensim_tpu/server/fixture.py", rules=["deadline-span"]) == ["OSL701"]


def test_deadline_span_silent_when_span_present():
    src = """
    from opensim_tpu.obs import trace as obs
    from opensim_tpu.resilience.deadline import check_deadline

    def prepare_things(cluster):
        check_deadline("prepare")
        with obs.span("prepare"):
            return encode(cluster)

    def marked(cluster):
        check_deadline("encode")
        obs.event("encode.skipped")
        return cluster
    """
    assert _codes(src, path="opensim_tpu/engine/fixture.py", rules=["deadline-span"]) == []


def test_deadline_span_nested_def_does_not_credit_outer():
    src = """
    from opensim_tpu.obs import trace as obs
    from opensim_tpu.resilience.deadline import check_deadline

    def outer(cluster):
        check_deadline("snapshot")

        def callback():
            with obs.span("snapshot"):
                pass

        return fetch(cluster, callback)
    """
    # the span lives in the nested function, not at the boundary itself
    assert _codes(src, path="opensim_tpu/engine/fixture.py", rules=["deadline-span"]) == ["OSL701"]


def test_deadline_span_suppression_and_exempt_paths():
    src = """
    from opensim_tpu.resilience.deadline import check_deadline

    def quick(cluster):
        check_deadline("decode")  # opensim-lint: disable=deadline-span
        return decode(cluster)
    """
    assert _codes(src, path="opensim_tpu/engine/fixture.py", rules=["deadline-span"]) == []
    # the deadline module itself (and tests) are exempt by path
    bare = """
    def helper():
        check_deadline("decode")
    """
    assert _codes(bare, path="opensim_tpu/resilience/deadline.py", rules=["deadline-span"]) == []
    assert _codes(bare, path="tests/test_x.py", rules=["deadline-span"]) == []


# ---------------------------------------------------------------------------
# OSL801 unsupervised-watch-loop
# ---------------------------------------------------------------------------


def test_watch_loop_flags_while_true_reconnect():
    src = """
    def follow(client):
        while True:
            try:
                for ev in client.watch("pods", rv):
                    handle(ev)
            except OSError:
                continue                 # reconnect forever, no bound
    """
    assert _codes(src, rules=["unsupervised-watch-loop"]) == ["OSL801"]


def test_watch_loop_flags_bare_stream_loop():
    src = """
    def tail(source):
        while True:
            consume(source.stream())
    """
    assert _codes(src, rules=["unsupervised-watch-loop"]) == ["OSL801"]


def test_watch_loop_accepts_retry_call_and_supervised_loops():
    src = """
    from opensim_tpu.resilience.retry import retry_call

    def follow(client, stop):
        while not stop.is_set():          # supervised condition: fine
            for ev in client.watch("pods", rv):
                handle(ev)

    def follow2(client):
        while True:                       # bounded via retry_call: fine
            stream = retry_call(lambda: client.watch("pods", rv), attempts=5)
            for ev in stream:
                handle(ev)

    def spin():
        while True:                       # no watch/stream call: OSL801 silent
            work()
    """
    assert _codes(src, rules=["unsupervised-watch-loop"]) == []


def test_watch_loop_suppression():
    src = """
    def follow(client):
        # opensim-lint: disable=unsupervised-watch-loop
        while True:
            consume(client.watch("pods"))
    """
    assert _codes(src, rules=["unsupervised-watch-loop"]) == []


# ---------------------------------------------------------------------------
# OSL901 reason-literal
# ---------------------------------------------------------------------------

def test_reason_literal_flags_inline_strings():
    src = """
    def decode(pod, node):
        ups = []
        ups.append(UnscheduledPod(pod, "no nodes matched"))
        ups.append(UnscheduledPod(pod, f'node "{node}" not found'))
        ups.append(UnscheduledPod(pod, reason="0/%d nodes" % 3))
        ups.append(UnscheduledPod(pod, "node {} gone".format(node)))
        return ups
    """
    assert _codes(src, rules=["reason-literal"]) == ["OSL901"] * 4


def test_reason_literal_accepts_registry_helpers_and_variables():
    src = """
    from opensim_tpu.engine import reasons

    def decode(pod, node, msg, custom):
        ups = [
            UnscheduledPod(pod, reasons.node_not_found(node)),
            UnscheduledPod(pod, reasons.preempted("ns", "hi")),
            UnscheduledPod(pod, reasons.render_unschedulable(4, [])),
            UnscheduledPod(pod, msg),
            UnscheduledPod(pod, custom[3]),
        ]
        return ups
    """
    assert _codes(src, rules=["reason-literal"]) == []


def test_reason_literal_repo_is_clean():
    import os

    root = os.path.join(os.path.dirname(__file__), "..", "opensim_tpu")
    findings = [f for f in lint_paths([root]) if f.code == "OSL901"]
    assert findings == [], [f"{f.path}:{f.line}" for f in findings]


# ---------------------------------------------------------------------------
# OSL1001 admission-lock-io (ISSUE 8 satellite)
# ---------------------------------------------------------------------------


def test_admission_lock_io_flags_blocking_calls_under_lock():
    src = """
    import time, urllib.request

    class Controller:
        def submit(self, t):
            with self._cond:
                time.sleep(0.1)
                self._queue.append(t)
                self._cond.notify()

        def drain(self):
            with self.lock:
                urllib.request.urlopen("http://x")

        def join_under_lock(self, fut):
            with self._lock:
                fut.result(timeout=3)
    """
    codes = _codes(src, path="opensim_tpu/server/admission.py",
                   rules=["admission-lock-io"])
    assert codes == ["OSL1001"] * 3


def test_admission_lock_io_allows_cond_wait_and_queue_work():
    src = """
    class Controller:
        def consume(self):
            with self._cond:
                while not self._queue and not self._closed:
                    self._cond.wait()
                item = self._queue.popleft()
                self._cond.notify_all()
            return item

        def other_wait_is_flagged(self, ev):
            with self._cond:
                ev.wait()
    """
    codes = _codes(src, path="opensim_tpu/server/admission.py",
                   rules=["admission-lock-io"])
    # cond.wait() on the held condition is the one legal wait; ev.wait()
    # under the lock is the convoy maker
    assert codes == ["OSL1001"]


def test_admission_lock_io_scoped_to_serving_modules():
    src = """
    import time

    def elsewhere(self):
        with self.lock:
            time.sleep(1)
    """
    assert _codes(src, path="opensim_tpu/engine/simulator.py",
                  rules=["admission-lock-io"]) == []


def test_admission_lock_io_repo_is_clean():
    import os

    root = os.path.join(os.path.dirname(__file__), "..", "opensim_tpu")
    findings = [f for f in lint_paths([root]) if f.code == "OSL1001"]
    assert findings == [], [f"{f.path}:{f.line}" for f in findings]


# ---------------------------------------------------------------------------
# OSL1301 journal-discipline (ISSUE 11)
# ---------------------------------------------------------------------------


def test_journal_discipline_flags_foreign_writes_and_fsync():
    # a journal path opened for writing outside server/journal.py
    assert _codes(
        'f = open("state/journal-00000001.seg", "ab")\n',
        rules=["journal-discipline"],
    ) == ["OSL1301"]
    assert _codes(
        'f = open(self.journal_path, mode="w")\n',
        rules=["journal-discipline"],
    ) == ["OSL1301"]
    # any os.fsync outside the journal module
    assert _codes(
        "import os\nos.fsync(fd)\n", rules=["journal-discipline"]
    ) == ["OSL1301"]


def test_journal_discipline_allows_ordinary_io():
    # read-mode journal opens and unrelated writes stay legal
    assert _codes(
        'f = open("state/journal-00000001.seg", "rb")\n',
        rules=["journal-discipline"],
    ) == []
    assert _codes('f = open("report.txt", "w")\n', rules=["journal-discipline"]) == []
    # tests are excluded: they corrupt journals on purpose
    assert _codes(
        "import os\nos.fsync(3)\n",
        path="tests/test_journal.py",
        rules=["journal-discipline"],
    ) == []


def test_journal_discipline_unchecksummed_write_inside_journal_module():
    src = """
    class Journal:
        def _write_framed(self, payload):
            self._f.write(payload)  # THE framing path: legal

        def _sneaky(self, b):
            self._f.write(b)  # bypasses the crc framing
    """
    assert _codes(
        src, path="opensim_tpu/server/journal.py", rules=["journal-discipline"]
    ) == ["OSL1301"]


def test_journal_discipline_suppression():
    src = 'import os\nos.fsync(fd)  # opensim-lint: disable=journal-discipline\n'
    assert _codes(src, rules=["journal-discipline"]) == []
