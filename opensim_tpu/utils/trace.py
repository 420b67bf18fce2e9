"""Host-prepare attribution (``PREP_STATS``) and the JAX profiler server.

Latency tracing lives in ``obs/trace.py``, the one span API: the reference's
``k8s.io/utils/trace`` spans (``Simulate`` at ``pkg/simulator/core.go:72-73``,
the snapshot, per-pod scheduling) are the ``prepare`` / ``schedule`` /
``decode`` spans of a request's tree there. For device-side profiling the
reference exposes pprof on its HTTP server (``pkg/server/server.go:152``);
the analogue here is the JAX profiler: ``start_profiler()`` serves the
TensorBoard-compatible capture endpoint, and a capture shows the program's
spans in the host plane beside the device operations.
"""

from __future__ import annotations

import time
import threading
from typing import Optional, Tuple

from ..obs import trace as _obs


class _PrepScope:
    """``with PREP_STATS.timed(kind) as t:`` — see :meth:`PrepStats.timed`."""

    __slots__ = ("stats", "kind", "_scope", "_t0", "_declined")

    def __init__(self, stats: "PrepStats", kind: str) -> None:
        self.stats = stats
        self.kind = kind
        self._declined = False

    def declined(self) -> None:
        """The delta handed the work back (it returns None and the caller
        prepares in full): its seconds stay out of the stats."""
        self._declined = True

    def __enter__(self) -> "_PrepScope":
        self._t0 = time.monotonic()
        self._scope = _obs.span("prep." + self.kind, kind=self.kind)
        self._scope.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._scope.__exit__(exc_type, exc, tb)
        if exc_type is None and not self._declined:
            self.stats.record(self.kind, time.monotonic() - self._t0)
        return False


class PrepStats:
    """Host-side prepare attribution (incremental-prepare observability).

    Every way a simulation can obtain its ``Prepared`` records here:
      ``full``        — a cold expand+encode of the whole cluster
      ``delta_apps``  — delta re-encode: pods appended to a cached base
      ``delta_nodes`` — delta re-encode: nodes added to a cached base
      ``twin_delta``  — live-twin watch events folded into the warm base
                        (pod insert / drop-mask flip, server/watch.py)
      ``hit``         — encode-cache hit (fingerprint + bind-state restore)

    ``bench.py`` emits these as ``host_prep_s``; the REST server exports
    them as ``simon_prepare_seconds_total``; tests use ``last`` to assert a
    request skipped re-encoding. Each also stands in the request's span
    tree as a real ``prep.<kind>`` span round the work (:meth:`timed`)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        self.seconds: dict = {}
        self.counts: dict = {}
        self.last: Optional[Tuple[str, float]] = None

    def record(self, kind: str, seconds: float) -> None:
        with self._lock:
            self.seconds[kind] = self.seconds.get(kind, 0.0) + seconds
            self.counts[kind] = self.counts.get(kind, 0) + 1
            self.last = (kind, seconds)

    def timed(self, kind: str) -> _PrepScope:
        """One prepare of ``kind``: opens ``obs.span("prep.<kind>")`` round
        the body (a no-op without an ambient trace) and records the body's
        seconds on a clean exit."""
        return _PrepScope(self, kind)

    def total_seconds(self) -> float:
        with self._lock:
            return sum(self.seconds.values())

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "seconds": dict(self.seconds),
                "counts": dict(self.counts),
                "last": self.last,
            }


PREP_STATS = PrepStats()


_profiler_active = False


def start_profiler(port: int = 9999) -> Optional[int]:
    """Start the JAX profiler server (TensorBoard trace viewer endpoint) —
    the pprof analogue for the XLA side."""
    global _profiler_active
    if _profiler_active:
        return port
    import jax

    jax.profiler.start_server(port)
    _profiler_active = True
    return port
