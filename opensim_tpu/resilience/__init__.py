"""Resilience layer: request deadlines, engine circuit breakers, retry with
jittered backoff, and deterministic fault injection.

The REST server is the production surface (ROADMAP north star: heavy traffic
from millions of users); before this package a mid-request failure — snapshot
fetch error, engine compile failure, device loss, stale prepare-cache entry —
either crashed the request with a raw 500 or hung it indefinitely. The four
modules here make the serving path survive faults:

- ``deadline``  — request-scoped :class:`Deadline` propagated via a context
  variable from ``server/rest.py`` into ``engine/simulator.simulate()``,
  enforced at phase boundaries (snapshot, prepare, encode, schedule, decode)
  so an exhausted budget becomes a typed 504, not a hang;
- ``breaker``   — per-engine :class:`CircuitBreaker` behind the megakernel →
  XLA scan fallback ladder: a *runtime* engine failure demotes
  the request and counts against the engine; repeated failures open the
  breaker (skip the doomed attempt), with half-open probing after a cooldown;
- ``retry``     — :func:`retry_call`, bounded attempts with jittered
  exponential backoff (the snapshot fetch path);
- ``faults``    — deterministic fault injection at named points
  (``OPENSIM_FAULTS=point:count:exc`` or the test API), so every failure
  mode above is provable on CPU (docs/resilience.md).
"""

from .breaker import (  # noqa: F401
    CircuitBreaker,
    all_breakers,
    engine_breaker,
    reset_breakers,
)
from .deadline import (  # noqa: F401
    Deadline,
    DeadlineExceeded,
    check_deadline,
    current_deadline,
    deadline_scope,
)
from .faults import FaultError, clear_faults, fault_point, fault_stats, inject  # noqa: F401
from .retry import retry_call  # noqa: F401
