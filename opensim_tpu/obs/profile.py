"""Compile telemetry + cumulative phase profiles (ISSUE 12).

Two blind spots closed:

**Compile telemetry.** The jit cache is the difference between a 50 ms
warm request and a multi-second stall, yet nothing counted compiles or
said WHY a signature recompiled. :class:`CompileWatch` instruments the
repo's jit boundaries (``engine/scheduler.schedule_pods``, the scenario
sweeps) — each call builds the abstract signature (leaf shapes/dtypes +
static flags), detects a compile by the jitted function's cache-size
growth, and attributes the recompile cause by diffing against the
previous signature: ``static`` (a static flag changed), ``dtype`` (same
shapes, different dtypes — the classic policy leak), ``shape`` (bucket
padding failed to hold the signature), ``new``/``first`` otherwise.
Backend-wide compile seconds and the persistent compilation cache's
monitoring events come from ``jax.monitoring`` listeners, and the
persistent cache directory's file/byte footprint from
``utils/jitcache.cache_stats``. The same listeners count what the compile
PATH costs when nothing compiles: calls and seconds per stage (``trace``,
``lower``, ``backend``, ``cache_retrieval``), the stages a signature's
first call walks even where the persistent cache answers (ISSUE 27).

**Cumulative phase profiles.** The flight recorder answers "why was THAT
request slow"; capacity questions need "where do requests spend time in
aggregate". :class:`PhaseProfile` folds every recorded trace's span tree
into per-span-name accumulators — call count, inclusive seconds,
EXCLUSIVE seconds (children subtracted, so `prepare` minus its `encode`
child is visible), and a fixed-bucket histogram that serves p50/p99 — fed
from the same :meth:`FlightRecorder.record` sink the debug endpoints
read, so one query replaces walking N traces.

Surfaces: ``GET /api/debug/profile``, ``simon profile``, and the
``simon_compile_*`` / ``simon_phase_profile_*`` ``/metrics`` families
(registered in ``obs/metrics.py`` FAMILIES, conformance-gated). See
docs/observability.md "Memory & profiles".
"""

from __future__ import annotations

import logging
import math
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from .metrics import DEFAULT_BUCKETS, escape_label_value, family_header
from .trace import NOOP_SPAN, _SpanScope, current_trace

log = logging.getLogger("opensim_tpu.obs")

__all__ = [
    "COMPILES",
    "PROFILE",
    "CompileWatch",
    "PhaseProfile",
    "device_stamp",
    "launch_span",
    "observed_jit_call",
]

#: signature-table bound per boundary: past it new signatures fold into an
#: "overflow" row instead of growing without limit (a runaway shape
#: churn is exactly what the telemetry should surface, not amplify)
_MAX_SIGNATURES = 256

_BUCKETS: Tuple[float, ...] = tuple(DEFAULT_BUCKETS) + (math.inf,)

#: ``jax.monitoring`` duration events (by leaf name) -> compile-path stage
_STAGES = {
    "jaxpr_trace_duration": "trace",
    "jaxpr_to_mlir_module_duration": "lower",
    "backend_compile_duration": "backend",
    "cache_retrieval_time_sec": "cache_retrieval",
}


def _quantile(counts: List[int], total: int, q: float) -> float:
    """histogram_quantile-style linear interpolation over the fixed
    buckets (the same math ``server/loadgen.py`` applies to scrapes)."""
    if total <= 0:
        return 0.0
    rank = q * total
    cum = 0
    lo = 0.0
    for count, hi in zip(counts, _BUCKETS):
        if count:
            if cum + count >= rank:
                if math.isinf(hi):
                    return lo
                frac = (rank - cum) / count
                return lo + (hi - lo) * frac
            cum += count
        lo = 0.0 if math.isinf(hi) else hi
    return lo


# ---------------------------------------------------------------------------
# compile telemetry
# ---------------------------------------------------------------------------


def _leaf_sig(leaves: List[Any]) -> Tuple[Tuple[tuple, str], ...]:
    out = []
    for leaf in leaves:
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
        out.append((shape, dtype))
    return tuple(out)


def _attribute_cause(prev: Optional[dict], sig: dict) -> str:
    """Why did this signature compile? Diffed against the PREVIOUS call's
    signature at the same boundary — the question an operator asks is
    "what changed since the warm call", not "which cache line missed"."""
    if prev is None:
        return "first"
    if prev["static"] != sig["static"]:
        return "static"
    shapes = [s for s, _ in sig["leaves"]]
    dtypes = [d for _, d in sig["leaves"]]
    prev_shapes = [s for s, _ in prev["leaves"]]
    prev_dtypes = [d for _, d in prev["leaves"]]
    if shapes == prev_shapes and dtypes != prev_dtypes:
        return "dtype"
    if shapes != prev_shapes:
        return "shape"
    return "new"


class CompileWatch:
    """Per-boundary compile accounting plus process-wide jax monitoring."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # name -> {"compiles", "seconds", "causes": {cause: n},
        #          "signatures": {sig_key: {"count", "seconds"}}, "last_sig"}
        self._fns: Dict[str, dict] = {}  # guarded-by: _lock
        # stage -> [calls, seconds]; "backend" counts every entry into the
        # backend compiler, those the persistent cache answered included
        self._stages: Dict[str, list] = {st: [0, 0.0] for st in _STAGES.values()}  # guarded-by: _lock
        self._cache_events: Dict[str, int] = {}  # guarded-by: _lock
        self._installed = False  # guarded-by: _lock

    # -- jax.monitoring (process-wide) --------------------------------------

    def install(self) -> None:
        """Register the jax monitoring listeners (idempotent). Captures
        every backend compile in the process — including boundaries this
        module does not wrap — and the compilation-cache event stream."""
        with self._lock:
            if self._installed:
                return
            self._installed = True
        try:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(self._on_duration)
            jax.monitoring.register_event_listener(self._on_event)
        except (ImportError, AttributeError) as e:
            log.debug("jax monitoring unavailable: %s", e)

    def _on_duration(self, name: str, duration: float, **_kw) -> None:
        stage = _STAGES.get(name.rsplit("/", 1)[-1])
        if stage is not None:
            with self._lock:
                rec = self._stages[stage]
                rec[0] += 1
                rec[1] += float(duration)

    def _on_event(self, name: str, **_kw) -> None:
        if "/compilation_cache/" in name:
            leaf = name.rsplit("/", 1)[-1]
            with self._lock:
                self._cache_events[leaf] = self._cache_events.get(leaf, 0) + 1

    # -- instrumented boundaries --------------------------------------------

    def _fn_locked(self, name: str) -> dict:
        return self._fns.setdefault(
            name,
            {"compiles": 0, "seconds": 0.0, "causes": {}, "signatures": {},
             "claimed": set(), "last_sig": None},
        )

    def claim(self, name: str, sig: dict) -> Optional[str]:
        """Atomically observe one call's signature: updates the boundary's
        last-seen signature (cause attribution diffs against the previous
        CALL, compiled or not) and claims the signature for measurement if
        it is NEW at this boundary. Returns the attributed cause for the
        claimant, None for everyone else — under concurrency only ONE
        thread measures a given signature, so two workers racing into the
        same cold signature cannot double-count the compile or bill the
        loser's lock-wait as compile seconds."""
        key = (sig["leaves"], sig["static"])
        with self._lock:
            fn = self._fn_locked(name)
            cause = _attribute_cause(fn["last_sig"], sig)
            fn["last_sig"] = sig
            if key in fn["claimed"]:
                return None
            if len(fn["claimed"]) >= _MAX_SIGNATURES:
                return None  # bounded: runaway signature churn stops recording
            fn["claimed"].add(key)
            return cause

    def record(self, name: str, sig: dict, seconds: float,
               cause: Optional[str] = None) -> None:
        key = (sig["leaves"], sig["static"])
        with self._lock:
            fn = self._fn_locked(name)
            if cause is None:
                cause = _attribute_cause(fn["last_sig"], sig)
            fn["compiles"] += 1
            fn["seconds"] += seconds
            fn["causes"][cause] = fn["causes"].get(cause, 0) + 1
            sigs = fn["signatures"]
            if key not in sigs and len(sigs) >= _MAX_SIGNATURES:
                key = "overflow"
            rec = sigs.setdefault(key, {"count": 0, "seconds": 0.0})
            rec["count"] += 1
            rec["seconds"] += seconds

    # -- views ---------------------------------------------------------------

    def counts(self) -> Tuple[int, int]:
        """(entries into the backend compiler, persistent-cache hits) so far:
        cheap enough to read at both ends of a span (``launch_span``), where
        ``snapshot()`` would walk the cache directory."""
        with self._lock:
            return self._stages["backend"][0], self._cache_events.get("cache_hits", 0)

    def traced(self, name: str) -> int:
        """Calls at boundary ``name`` that traced and compiled so far (what
        ``simon_compile_total{fn=}`` renders)."""
        with self._lock:
            fn = self._fns.get(name)
            return fn["compiles"] if fn else 0

    def snapshot(self) -> dict:
        from ..utils import jitcache

        with self._lock:
            fns = {
                name: {
                    "compiles": fn["compiles"],
                    "seconds": round(fn["seconds"], 6),
                    "causes": dict(fn["causes"]),
                    "distinct_signatures": len(fn["signatures"]),
                }
                for name, fn in sorted(self._fns.items())
            }
            out = {
                "boundaries": fns,
                "backend": {
                    "compiles": self._stages["backend"][0],
                    "seconds": round(self._stages["backend"][1], 6),
                },
                "stages": {
                    st: {"count": n, "seconds": round(secs, 6)}
                    for st, (n, secs) in self._stages.items()
                },
                "cache_events": dict(sorted(self._cache_events.items())),
            }
        out["persistent_cache"] = jitcache.cache_stats()
        return out

    def metrics_lines(self) -> List[str]:
        from ..utils import jitcache

        esc = escape_label_value
        lines: List[str] = []
        with self._lock:
            if self._fns:
                lines += family_header("simon_compile_total")
                lines += [
                    f'simon_compile_total{{fn="{esc(n)}"}} {fn["compiles"]}'
                    for n, fn in sorted(self._fns.items())
                ]
                lines += family_header("simon_compile_seconds_total")
                lines += [
                    f'simon_compile_seconds_total{{fn="{esc(n)}"}} {fn["seconds"]:.6f}'
                    for n, fn in sorted(self._fns.items())
                ]
                cause_lines = [
                    f'simon_compile_cause_total{{cause="{esc(c)}",fn="{esc(n)}"}} {k}'
                    for n, fn in sorted(self._fns.items())
                    for c, k in sorted(fn["causes"].items())
                ]
                if cause_lines:
                    lines += family_header("simon_compile_cause_total")
                    lines += cause_lines
            lines += [
                *family_header("simon_backend_compile_total"),
                f"simon_backend_compile_total {self._stages['backend'][0]}",
                *family_header("simon_backend_compile_seconds_total"),
                f"simon_backend_compile_seconds_total {self._stages['backend'][1]:.6f}",
                *family_header("simon_compile_stage_seconds_total"),
                *[
                    f'simon_compile_stage_seconds_total{{stage="{st}"}} {secs:.6f}'
                    for st, (_n, secs) in sorted(self._stages.items())
                ],
            ]
            if self._cache_events:
                lines += family_header("simon_jitcache_events_total")
                lines += [
                    f'simon_jitcache_events_total{{event="{esc(ev)}"}} {n}'
                    for ev, n in sorted(self._cache_events.items())
                ]
        lines += _device_info_lines()
        stats = jitcache.cache_stats()
        if stats is not None:
            lines += [
                *family_header("simon_jitcache_persistent_files"),
                f"simon_jitcache_persistent_files {stats['files']}",
                *family_header("simon_jitcache_persistent_bytes"),
                f"simon_jitcache_persistent_bytes {stats['bytes']}",
            ]
        return lines

    def reset(self) -> None:
        with self._lock:
            self._fns.clear()
            for rec in self._stages.values():
                rec[:] = [0, 0.0]
            self._cache_events.clear()


def device_stamp() -> Dict[str, Any]:
    """platform / device_kind / device count of the backend JAX selected in
    THIS process — the one shape every consumer stamps (bench rows, the
    `apply ran on …` line, ``simon_device_info``). Initializes the backend."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }


def _device_info_lines() -> List[str]:
    """``simon_device_info``: which backend this process's numbers come from
    (a load generator stamps its rows from this, never from its own JAX)."""
    try:
        dev = device_stamp()
    except RuntimeError as e:  # no backend could initialize: say so once per scrape
        log.debug("device info unavailable: %s", e)
        return []
    esc = escape_label_value
    return [
        *family_header("simon_device_info"),
        f'simon_device_info{{device_kind="{esc(dev["device_kind"])}",'
        f'platform="{esc(dev["platform"])}"}} {dev["device_count"]}',
    ]


COMPILES = CompileWatch()


class _LaunchScope(_SpanScope):
    """A span that also counts, as attributes, the entries into the backend
    compiler and the persistent-cache hits inside it, and for a call through
    ``observed_jit_call`` at boundary ``watch`` says as ``entry`` whether the
    call ``traced`` or was served from the jit's cache (``cached``)."""

    __slots__ = ("_before", "_watch")

    def __init__(self, tr, name: str, attrs: dict, watch: Optional[str]) -> None:
        super().__init__(tr, name, attrs)
        self._watch = watch

    def _counts(self) -> Tuple[int, int, int]:
        return (*COMPILES.counts(), COMPILES.traced(self._watch) if self._watch else 0)

    def __enter__(self):
        self._before = self._counts()
        return super().__enter__()

    def __exit__(self, exc_type, exc, tb) -> bool:
        compiles, hits, traced = self._counts()
        self.span.attrs.update(
            backend_compiles=compiles - self._before[0], cache_hits=hits - self._before[1]
        )
        if self._watch and exc_type is None:
            self.span.attrs["entry"] = "traced" if traced > self._before[2] else "cached"
        return super().__exit__(exc_type, exc, tb)


def launch_span(name: str, watch: Optional[str] = None, **attrs: Any):
    """``obs.span`` for a call that may enter the compile path (the
    megakernel's ``mk.launch``, the scans' ``xla.launch``): the span says
    whether its seconds held a compile, a cache hit or neither, and with
    ``watch`` (an ``observed_jit_call`` boundary) whether the jitted entry
    traced. The shared no-op without an ambient trace, like every
    instrumentation point."""
    tr = current_trace()
    return NOOP_SPAN if tr is None else _LaunchScope(tr, name, attrs, watch)


def observed_jit_call(name: str, fn, args: tuple, static: Optional[dict] = None):
    """Call a jitted function through the compile watch: build the
    abstract signature, time the call, and record a compile when the
    function's jit cache grew. Transparent under tracing (an inner
    ``vmap``/``jit`` caller passes tracers — the call goes straight
    through) and when the cache size is unreadable."""
    import jax

    static = static or {}
    leaves = jax.tree_util.tree_leaves(args)
    if any(isinstance(leaf, jax.core.Tracer) for leaf in leaves):
        return fn(*args, **static)
    COMPILES.install()
    sig = {
        "leaves": _leaf_sig(leaves),
        "static": tuple(sorted((k, repr(v)) for k, v in static.items())),
    }
    # one atomic observation: last-sig update + new-signature claim. Only
    # the claimant measures — a repeat signature returns None and the call
    # goes straight through (the warm path pays one lock + dict lookup).
    cause = COMPILES.claim(name, sig)
    if cause is None:
        return fn(*args, **static)
    try:
        # private-but-stable jit API: absence degrades to no per-boundary
        # count (the jax.monitoring backend listener still sees the compile)
        before = fn._cache_size()
    except (AttributeError, TypeError):
        before = None
    t0 = time.monotonic()
    try:
        return fn(*args, **static)
    finally:
        if before is not None:
            try:
                grew = fn._cache_size() > before
            except (AttributeError, TypeError):
                grew = False
            if grew:
                COMPILES.record(name, sig, time.monotonic() - t0, cause=cause)


# ---------------------------------------------------------------------------
# cumulative phase profiles
# ---------------------------------------------------------------------------


class _Agg:
    __slots__ = ("count", "incl", "excl", "max_s", "buckets")

    def __init__(self) -> None:
        self.count = 0
        self.incl = 0.0
        self.excl = 0.0
        self.max_s = 0.0
        self.buckets = [0] * len(_BUCKETS)

    def add(self, incl: float, excl: float) -> None:
        self.count += 1
        self.incl += incl
        self.excl += excl
        self.max_s = max(self.max_s, incl)
        for i, hi in enumerate(_BUCKETS):
            if incl <= hi:
                self.buckets[i] += 1
                break

    def clone(self) -> "_Agg":
        """Copy taken under the profile lock: snapshot() reads fields after
        releasing it, and a concurrent add() must not tear count vs buckets
        (a mismatch would push _quantile's rank past the histogram)."""
        out = _Agg()
        out.count = self.count
        out.incl = self.incl
        out.excl = self.excl
        out.max_s = self.max_s
        out.buckets = list(self.buckets)
        return out

    def to_dict(self) -> dict:
        return {
            "count": self.count,
            "seconds": round(self.incl, 6),
            "exclusive_seconds": round(self.excl, 6),
            "mean_s": round(self.incl / self.count, 6) if self.count else 0.0,
            "p50_s": round(_quantile(self.buckets, self.count, 0.50), 6),
            "p99_s": round(_quantile(self.buckets, self.count, 0.99), 6),
            "max_s": round(self.max_s, 6),
        }


class PhaseProfile:
    """Cumulative span profiles keyed ``(endpoint, span name)``, fed from
    the flight-recorder sink (every finished request trace)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._agg: Dict[Tuple[str, str], _Agg] = {}  # guarded-by: _lock
        self._traces = 0  # guarded-by: _lock

    def observe_trace(self, trace) -> None:
        rows: List[Tuple[str, float, float]] = []
        endpoint = trace.endpoint
        for sp in trace.walk():
            incl = sp.duration_s
            excl = incl - sum(c.duration_s for c in sp.children)
            rows.append((sp.name, incl, max(0.0, excl)))
        with self._lock:
            self._traces += 1
            for name, incl, excl in rows:
                agg = self._agg.get((endpoint, name))
                if agg is None:
                    agg = self._agg[(endpoint, name)] = _Agg()
                agg.add(incl, excl)

    def snapshot(self) -> dict:
        """The ``/api/debug/profile`` phases body: per span name (summed
        over endpoints) and the per-endpoint breakdown."""
        with self._lock:
            items = [(ep, name, agg.clone()) for (ep, name), agg in self._agg.items()]
            traces = self._traces
        by_span: Dict[str, _Agg] = {}
        for _ep, name, agg in items:
            tot = by_span.get(name)
            if tot is None:
                tot = by_span[name] = _Agg()
            tot.count += agg.count
            tot.incl += agg.incl
            tot.excl += agg.excl
            tot.max_s = max(tot.max_s, agg.max_s)
            tot.buckets = [a + b for a, b in zip(tot.buckets, agg.buckets)]
        return {
            "traces": traces,
            "spans": {
                name: agg.to_dict()
                for name, agg in sorted(by_span.items(), key=lambda kv: -kv[1].incl)
            },
            "endpoints": {
                ep: {
                    name: agg.to_dict()
                    for (e2, name, agg) in sorted(items, key=lambda r: -r[2].incl)
                    if e2 == ep
                }
                for ep in sorted({ep for ep, _n, _a in items})
            },
        }

    def metrics_lines(self) -> List[str]:
        esc = escape_label_value
        snap = self.snapshot()
        if not snap["spans"]:
            return []
        lines = [*family_header("simon_phase_profile_calls_total")]
        lines += [
            f'simon_phase_profile_calls_total{{span="{esc(name)}"}} {d["count"]}'
            for name, d in sorted(snap["spans"].items())
        ]
        lines += family_header("simon_phase_profile_seconds_total")
        lines += [
            f'simon_phase_profile_seconds_total{{span="{esc(name)}"}} {d["seconds"]:.6f}'
            for name, d in sorted(snap["spans"].items())
        ]
        lines += family_header("simon_phase_profile_exclusive_seconds_total")
        lines += [
            f'simon_phase_profile_exclusive_seconds_total{{span="{esc(name)}"}} '
            f'{d["exclusive_seconds"]:.6f}'
            for name, d in sorted(snap["spans"].items())
        ]
        return lines

    def reset(self) -> None:
        with self._lock:
            self._agg.clear()
            self._traces = 0


PROFILE = PhaseProfile()

# arm the process-wide jax.monitoring listeners as soon as anything touches
# the obs surface: backend compiles that happen before the first
# instrumented boundary call (encode-time device ops, fastpath builds)
# must still be counted
COMPILES.install()


def debug_payload() -> dict:
    """The ``GET /api/debug/profile`` body (also what ``simon profile``
    renders): the cumulative phase profiles plus the compile telemetry."""
    return {
        "generated_unix": round(time.time(), 3),
        "phases": PROFILE.snapshot(),
        "compiles": COMPILES.snapshot(),
    }
