"""Unit coverage for the resilience primitives (opensim_tpu/resilience):
deadlines, jittered-backoff retry, circuit breakers, fault injection — plus
the bench.py failure contract (one JSON line, nonzero exit) and the
jit-cache degradation log."""

import json
import os
import random
import subprocess
import sys

import pytest

from opensim_tpu.resilience import breaker as breaker_mod
from opensim_tpu.resilience import faults
from opensim_tpu.resilience.breaker import CircuitBreaker
from opensim_tpu.resilience.deadline import (
    Deadline,
    DeadlineExceeded,
    check_deadline,
    current_deadline,
    deadline_scope,
)
from opensim_tpu.resilience.retry import retry_call

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_resilience_state():
    faults.clear_faults()
    breaker_mod.reset_breakers()
    yield
    faults.clear_faults()
    breaker_mod.reset_breakers()


# ---------------------------------------------------------------------------
# deadline
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def test_deadline_expiry_and_phase():
    clock = FakeClock()
    dl = Deadline.after(5.0, clock=clock)
    assert dl.remaining() == 5.0 and not dl.expired()
    dl.check("prepare")  # plenty of budget: no raise
    clock.t = 6.0
    assert dl.expired()
    with pytest.raises(DeadlineExceeded) as ei:
        dl.check("schedule")
    assert ei.value.phase == "schedule"
    assert "schedule" in str(ei.value) and "budget 5.000s" in str(ei.value)


def test_deadline_scope_is_ambient_and_restores():
    assert current_deadline() is None
    check_deadline("anything")  # no ambient deadline: no-op
    clock = FakeClock()
    dl = Deadline.after(1.0, clock=clock)
    with deadline_scope(dl):
        assert current_deadline() is dl
        clock.t = 2.0
        with pytest.raises(DeadlineExceeded) as ei:
            check_deadline("encode")
        assert ei.value.phase == "encode"
        # deadline_scope(None) keeps the ambient scope (simulate(deadline=
        # None) inside a server-installed scope must still be bounded)
        with deadline_scope(None):
            assert current_deadline() is dl
    assert current_deadline() is None


def test_simulate_honors_deadline_at_prepare_boundary():
    from opensim_tpu.engine.simulator import AppResource, simulate
    from opensim_tpu.models import ResourceTypes, fixtures as fx

    cluster = ResourceTypes()
    cluster.nodes.append(fx.make_fake_node("n1", "8", "16Gi"))
    app = ResourceTypes()
    app.pods.append(fx.make_fake_pod("p1", "500m", "1Gi"))
    clock = FakeClock()
    expired = Deadline.after(1.0, clock=clock)
    clock.t = 2.0
    with pytest.raises(DeadlineExceeded) as ei:
        simulate(cluster, [AppResource("a", app)], deadline=expired)
    assert ei.value.phase == "prepare"
    # and a generous deadline changes nothing
    res = simulate(cluster, [AppResource("a", app)], deadline=Deadline.after(3600.0))
    assert not res.unscheduled_pods


# ---------------------------------------------------------------------------
# retry
# ---------------------------------------------------------------------------


def test_retry_recovers_within_attempts():
    calls = []
    sleeps = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    out = retry_call(
        flaky, attempts=3, base_delay=0.1, max_delay=2.0,
        retry_on=(OSError,), sleep=sleeps.append, rng=random.Random(0),
    )
    assert out == "ok" and len(calls) == 3
    # full-jitter: attempt k sleeps uniform[0, min(max, base*2^k)]
    assert len(sleeps) == 2
    assert 0.0 <= sleeps[0] <= 0.1 and 0.0 <= sleeps[1] <= 0.2


def test_retry_exhaustion_reraises_last_error():
    sleeps = []
    with pytest.raises(OSError, match="always"):
        retry_call(
            lambda: (_ for _ in ()).throw(OSError("always")),
            attempts=4, base_delay=0.05, retry_on=(OSError,),
            sleep=sleeps.append, rng=random.Random(1),
        )
    assert len(sleeps) == 3  # attempts-1 backoffs, bounded


def test_retry_does_not_retry_foreign_exceptions():
    calls = []

    def auth_error():
        calls.append(1)
        raise ValueError("bad kubeconfig")

    with pytest.raises(ValueError):
        retry_call(auth_error, attempts=5, retry_on=(OSError,), sleep=lambda s: None)
    assert len(calls) == 1


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------


def test_breaker_opens_after_threshold_and_half_open_probes():
    clock = FakeClock()
    br = CircuitBreaker("native", threshold=3, cooldown_s=30.0, clock=clock)
    assert br.state() == "closed" and br.allow()
    for _ in range(2):
        br.record_failure(RuntimeError("boom"))
    assert br.state() == "closed" and br.allow() and br.trips_total == 0
    br.record_failure(RuntimeError("boom"))
    assert br.state() == "open" and not br.allow() and br.trips_total == 1
    assert "circuit breaker open" in br.describe_block()
    assert "RuntimeError: boom" in br.describe_block()

    # cooldown elapses: half-open allows exactly one probe
    clock.t = 31.0
    assert br.state() == "half-open"
    assert br.allow()       # the probe
    assert not br.allow()   # concurrent request during the probe: skipped
    br.record_failure(RuntimeError("still broken"))
    assert br.state() == "open" and br.trips_total == 2

    # next probe succeeds: breaker closes fully
    clock.t = 62.0
    assert br.allow()
    br.record_success()
    assert br.state() == "closed" and br.allow() and br.consecutive_failures == 0


def test_breaker_success_resets_consecutive_failures():
    br = CircuitBreaker("x", threshold=3, cooldown_s=1.0, clock=FakeClock())
    br.record_failure(RuntimeError("a"))
    br.record_failure(RuntimeError("b"))
    br.record_success()
    br.record_failure(RuntimeError("c"))
    assert br.state() == "closed" and br.failures_total == 3 and br.trips_total == 0


def test_engine_breaker_registry_env_config(monkeypatch):
    monkeypatch.setenv("OPENSIM_BREAKER_THRESHOLD", "1")
    breaker_mod.reset_breakers()
    br = breaker_mod.engine_breaker("native")
    assert br is breaker_mod.engine_breaker("native")  # one per engine
    br.record_failure(RuntimeError("x"))
    assert br.state() == "open"  # threshold 1 from env
    assert "native" in breaker_mod.all_breakers()


# ---------------------------------------------------------------------------
# fault injection
# ---------------------------------------------------------------------------


def test_fault_point_fires_count_times_then_goes_inert():
    faults.inject("prep.encode", count=2, exc="runtime")
    for _ in range(2):
        with pytest.raises(RuntimeError, match="injected fault at prep.encode"):
            faults.fault_point("prep.encode")
    faults.fault_point("prep.encode")  # armed count exhausted: inert
    assert faults.fault_stats() == {"prep.encode": 2}


def test_fault_env_activation_and_reparse(monkeypatch):
    monkeypatch.setenv("OPENSIM_FAULTS", "engine.compile:1:oserror")
    with pytest.raises(OSError):
        faults.fault_point("engine.compile")
    faults.fault_point("engine.compile")  # consumed
    # changing the env raw value re-arms without any import dance
    monkeypatch.setenv("OPENSIM_FAULTS", "engine.compile:1:timeout")
    with pytest.raises(TimeoutError):
        faults.fault_point("engine.compile")


def test_fault_spec_validation():
    with pytest.raises(ValueError, match="unknown fault point"):
        faults.inject("no.such.point")
    with pytest.raises(ValueError, match="unknown fault exception"):
        faults.inject("cache.stale", exc="nonsense")
    with pytest.raises(ValueError, match="bad fault count"):
        faults.parse_spec("cache.stale:xyz")


def test_fault_stale_exception_is_the_real_type():
    from opensim_tpu.engine.prepcache import StaleFingerprintError

    faults.inject("cache.stale", exc="stale")
    with pytest.raises(StaleFingerprintError):
        faults.fault_point("cache.stale")


# ---------------------------------------------------------------------------
# jit cache degradation
# ---------------------------------------------------------------------------


def test_jitcache_unwritable_dir_logs_and_disables(monkeypatch, caplog, tmp_path):
    import logging

    from opensim_tpu.utils import jitcache

    def deny(path, exist_ok=False):
        raise OSError(13, "Permission denied")

    monkeypatch.setattr(os, "makedirs", deny)
    monkeypatch.setattr(jitcache, "DEFAULT_DIR", str(tmp_path / "blocked" / "jit"))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("OPENSIM_JIT_CACHE", raising=False)
    with caplog.at_level(logging.WARNING, logger="opensim_tpu"):
        assert jitcache.maybe_enable(default=True) is None
    assert any("persistent jit cache disabled" in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# bench.py failure contract (NOTES invariant: exactly one JSON line)
# ---------------------------------------------------------------------------


def test_bench_failure_emits_single_json_line_and_nonzero_exit():
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "OPENSIM_FAULTS": "prep.encode:1:runtime",
        "OPENSIM_JIT_CACHE": "0",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--pods", "20", "--nodes", "4", "--no-warmup"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300,
    )
    assert proc.returncode != 0
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, proc.stdout + proc.stderr
    rec = json.loads(lines[0])
    assert "injected fault at prep.encode" in rec["error"]
    assert rec["stage"] == "measure"
    # no traceback leaked to stdout (stderr is the driver's to ignore)
    assert "Traceback" not in proc.stdout
