"""Per-engine circuit breakers for the scheduling-engine fallback ladder.

Which rungs of the ladder (megakernel → C++ native → XLA scan) may run is
``engine/select.py``'s, before anything runs; this module is the
*runtime*-failure half: when an engine that ``select`` passed fails
while running (Mosaic compile error, ``ScanArgs`` ABI drift, device loss),
``engine/simulator.simulate()`` records the failure here and demotes the
request one rung. After ``threshold`` consecutive failures the breaker opens
— later requests skip the doomed attempt outright (the skip reason lands in
``EngineDecision.skipped``, the trip in ``/metrics``) — and after
``cooldown_s`` it goes half-open: one probe request is allowed through; a
success closes the breaker, a failure re-opens it for another cooldown.

States: ``closed`` (normal), ``open`` (skip), ``half-open`` (probe).
``OPENSIM_REQUIRE_TPU=1`` bypasses breaker gating entirely — "fail hard,
never demote" means a broken megakernel must raise, not be skipped.

Knobs: ``OPENSIM_BREAKER_THRESHOLD`` (default 3 consecutive failures),
``OPENSIM_BREAKER_COOLDOWN_S`` (default 30). The clock is injectable
(``breaker.clock = fake``) so half-open transitions are testable without
sleeping.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Optional

from ..utils import envknobs

__all__ = ["CircuitBreaker", "engine_breaker", "all_breakers", "reset_breakers"]


def _env_int(name: str, default: int) -> int:
    raw = envknobs.raw(name)
    try:
        return int(raw) if raw else default
    except ValueError:
        raise ValueError(f"{name} must be an integer, got {raw!r}") from None


def _env_float(name: str, default: float) -> float:
    raw = envknobs.raw(name)
    try:
        return float(raw) if raw else default
    except ValueError:
        raise ValueError(f"{name} must be a number, got {raw!r}") from None


class CircuitBreaker:
    """Consecutive-failure breaker with half-open probing. Thread-safe."""

    def __init__(
        self,
        name: str,
        threshold: Optional[int] = None,
        cooldown_s: Optional[float] = None,
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        self.name = name
        self.threshold = threshold if threshold is not None else _env_int("OPENSIM_BREAKER_THRESHOLD", 3)
        self.cooldown_s = cooldown_s if cooldown_s is not None else _env_float("OPENSIM_BREAKER_COOLDOWN_S", 30.0)
        self.clock = clock
        self._lock = threading.Lock()
        self.consecutive_failures = 0
        self.failures_total = 0
        self.trips_total = 0
        self.last_error: str = ""
        self._opened_at: Optional[float] = None
        self._probing = False

    # -- state --------------------------------------------------------------

    def state(self) -> str:
        with self._lock:
            return self._state_locked()

    def _state_locked(self) -> str:
        if self._opened_at is None:
            return "closed"
        if self.clock() - self._opened_at >= self.cooldown_s:
            return "half-open"
        return "open"

    def allow(self) -> bool:
        """May the engine be attempted? ``closed`` → yes; ``open`` → no;
        ``half-open`` → yes, once (the probe) — concurrent requests during
        the probe are still skipped so one broken engine can't stall a
        whole burst."""
        with self._lock:
            state = self._state_locked()
            if state == "closed":
                return True
            if state == "open":
                return False
            if self._probing:
                return False
            self._probing = True
            return True

    def describe_block(self) -> str:
        """One-line skip reason for ``EngineDecision.skipped``."""
        with self._lock:
            remaining = 0.0
            if self._opened_at is not None:
                remaining = max(0.0, self.cooldown_s - (self.clock() - self._opened_at))
            return (
                f"circuit breaker {self._state_locked()} after "
                f"{self.consecutive_failures} consecutive failure(s) "
                f"(last: {self.last_error}; retry in {remaining:.1f}s)"
            )

    # -- outcomes -----------------------------------------------------------

    def record_success(self) -> None:
        with self._lock:
            self.consecutive_failures = 0
            self._opened_at = None
            self._probing = False
            self.last_error = ""

    def record_failure(self, exc: BaseException) -> None:
        trip_info = None
        with self._lock:
            self.consecutive_failures += 1
            self.failures_total += 1
            self.last_error = f"{type(exc).__name__}: {exc}"
            was_probe = self._probing
            was_closed = self._opened_at is None
            if was_probe or self.consecutive_failures >= self.threshold:
                # a failed half-open probe re-opens for a fresh cooldown;
                # each closed→open and half-open→open transition is one trip
                self._opened_at = self.clock()
                self._probing = False
                if was_closed or was_probe:
                    self.trips_total += 1
                    # snapshot the state that tripped THIS request while
                    # still locked — a concurrent record_failure/reset must
                    # not rewrite the event's attribution
                    trip_info = (self.consecutive_failures, self.last_error)
        if trip_info is not None:
            # trace event OUTSIDE the breaker lock (the span sink shares one
            # recorder lock with /metrics; never nest the two)
            from ..obs import trace as _obs

            _obs.event(
                "breaker.trip", status="error", engine=self.name,
                failures=trip_info[0], error=trip_info[1],
            )

    def reset(self) -> None:
        with self._lock:
            self.consecutive_failures = 0
            self.failures_total = 0
            self.trips_total = 0
            self.last_error = ""
            self._opened_at = None
            self._probing = False


_BREAKERS: Dict[str, CircuitBreaker] = {}
_REGISTRY_LOCK = threading.Lock()


def engine_breaker(name: str) -> CircuitBreaker:
    """Process-global breaker for engine ``name`` (megakernel/native/xla —
    the XLA scan is the floor of the ladder and never consults its breaker,
    but keeping it registered makes /metrics uniform)."""
    with _REGISTRY_LOCK:
        br = _BREAKERS.get(name)
        if br is None:
            br = _BREAKERS[name] = CircuitBreaker(name)
        return br


def all_breakers() -> Dict[str, CircuitBreaker]:
    with _REGISTRY_LOCK:
        return dict(_BREAKERS)


def reset_breakers() -> None:
    """Test hook: forget all breaker state (and cached env-derived config)."""
    with _REGISTRY_LOCK:
        _BREAKERS.clear()
