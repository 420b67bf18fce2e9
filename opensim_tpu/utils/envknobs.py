"""Typed registry of every ``OPENSIM_*`` environment knob (ISSUE 12).

The knob surface grew organically to ~45 variables scattered across ~25
modules, each with its own ad-hoc ``os.environ.get`` + parse + default.
That made three things impossible:

- an operator could not discover the surface (``docs/env.md`` is now
  GENERATED from this registry — ``make docs`` / ``python -m
  opensim_tpu.utils.envknobs``);
- a typo'd knob name silently read as unset (every read now routes through
  :func:`raw`, which fails loudly on an UNREGISTERED name — the analogue of
  the metric-family registry in ``obs/metrics.py``);
- nothing type-checked the documented default against the parser (every
  registered validator is exercised against its default by
  tests/test_envknobs.py).

Contract (lint rule OSL1401, ``analysis/rules_env.py``): no module outside
this one reads an ``OPENSIM_*`` variable from ``os.environ`` directly.
Reads go through :func:`raw` (the registered passthrough — call sites keep
their site-specific parse/degrade semantics) or :func:`value` (parse with
the registered validator). Writes (``os.environ["OPENSIM_X"] = ...``) stay
legal — the CLI's ``--backend`` plumbing and tests set knobs for child
code; governance is about undeclared READS.

Error-handling conventions carried by ``on_error`` (and enforced at the
call sites that own the parse):

- ``"raise"`` — an operator typo must surface at startup, not during an
  incident (watch/journal policy, headroom profiles, scan unroll);
- ``"default"`` — debug/observability knobs degrade to the default with a
  warning, never taking down library use (flight recorder, capacity topk).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple

__all__ = ["Knob", "KNOBS", "register", "raw", "value", "is_set", "render_markdown"]


@dataclass(frozen=True)
class Knob:
    """One registered environment knob: the name, a human type tag, the
    documented default (raw string form, ``""`` = unset), the doc line that
    becomes its ``docs/env.md`` row, and an optional validator mapping the
    raw string to a parsed value (raising ``ValueError`` on garbage)."""

    name: str
    type: str  # int | float | flag | enum | str | path | spec
    default: str
    doc: str
    validator: Optional[Callable[[str], object]] = None
    choices: Tuple[str, ...] = ()
    on_error: str = "default"  # "default" (warn + fall back) or "raise"
    section: str = "general"


KNOBS: Dict[str, Knob] = {}


def register(knob: Knob) -> Knob:
    if not knob.name.startswith("OPENSIM_"):
        raise ValueError(f"env knob {knob.name!r} must be OPENSIM_-prefixed")
    if knob.name in KNOBS:
        raise ValueError(f"env knob {knob.name!r} registered twice")
    KNOBS[knob.name] = knob
    return knob


def _registered(name: str) -> Knob:
    knob = KNOBS.get(name)
    if knob is None:
        raise KeyError(
            f"env knob {name!r} is not registered in utils/envknobs.py; "
            "register it there (name, type, default, doc) so docs/env.md "
            "and the OSL1401 governance cover it"
        )
    return knob


def raw(name: str, default: str = "") -> str:
    """The ONE read path for ``OPENSIM_*`` variables: ``os.environ.get``
    for a REGISTERED knob. An unregistered name is a programming error —
    the knob ships undocumented and invisible to ``docs/env.md`` — and
    fails loudly here instead. A caller-supplied ``default`` must MATCH
    the registered one (tests/test_envknobs.py sweeps call sites for
    drift) — it exists so sites keep their unset-vs-empty semantics,
    not to fork the documented default."""
    _registered(name)
    return os.environ.get(name, default)


def is_set(name: str) -> bool:
    """Registered-knob presence check (``name in os.environ``)."""
    _registered(name)
    return name in os.environ


def value(name: str):
    """Parse the knob through its registered validator. Unset → the
    default is parsed instead. ``on_error="raise"`` knobs propagate the
    ``ValueError``; ``"default"`` knobs warn and return the parsed
    default (the degrade-don't-crash contract debug knobs follow)."""
    knob = _registered(name)
    if knob.validator is None:
        return raw(name, knob.default)
    text = os.environ.get(name, "")
    if text == "":
        text = knob.default
    try:
        return knob.validator(text)
    except ValueError:
        if knob.on_error == "raise":
            raise
        import logging

        logging.getLogger("opensim_tpu").warning(
            "ignoring unparseable %s=%r (using %r)", name, text, knob.default
        )
        return knob.validator(knob.default)


# ---------------------------------------------------------------------------
# validator combinators
# ---------------------------------------------------------------------------


def _int(lo: Optional[int] = None) -> Callable[[str], int]:
    def parse(text: str) -> int:
        v = int(text)
        if lo is not None and v < lo:
            raise ValueError(f"must be >= {lo}, got {v}")
        return v

    return parse


def _float(lo: Optional[float] = None, exclusive: bool = False) -> Callable[[str], float]:
    def parse(text: str) -> float:
        v = float(text)
        if lo is not None and (v <= lo if exclusive else v < lo):
            raise ValueError(f"must be {'>' if exclusive else '>='} {lo}, got {v}")
        return v

    return parse


def _flag(text: str) -> bool:
    return text.strip().lower() in ("1", "on", "true", "yes")


def _enum(*choices: str) -> Callable[[str], str]:
    def parse(text: str) -> str:
        v = text.strip().lower()
        if v not in choices:
            raise ValueError(f"must be one of {'|'.join(choices)}, got {text!r}")
        return v

    return parse


def _str(text: str) -> str:
    return text


# ---------------------------------------------------------------------------
# the registry — grouped the way docs/env.md renders it
# ---------------------------------------------------------------------------

_ENGINE = [
    Knob("OPENSIM_DISABLE_FASTPATH", "flag", "", "Any non-empty value disables the Pallas megakernel fast path (`--backend xla` sets it).", None, section="engine"),
    Knob("OPENSIM_FASTPATH", "enum", "", "Megakernel mode override; `interpret` runs the Pallas kernels in interpret mode (CI parity without a TPU). Never inferred from the backend; `--backend tpu` refuses it.", None, choices=("", "interpret"), section="engine"),
    Knob("OPENSIM_REQUIRE_TPU", "flag", "", "`1` fails hard instead of falling back when the TPU engine cannot run (exact value; `--backend tpu` sets it).", None, section="engine"),
    Knob("OPENSIM_SCAN_UNROLL", "int", "1", "XLA scan unroll factor (accelerator tuning; resolved outside jit so it keys the jit cache).", _int(lo=1), on_error="raise", section="engine"),
    Knob("OPENSIM_JIT_CACHE", "flag", "", "`0` turns the persistent XLA compile cache off; bench/CLI/tests default it on. The directory is `JAX_COMPILATION_CACHE_DIR` when set, else the git-ignored `.jit_cache/` in the checkout (utils/jitcache.py).", None, section="engine"),
]

_RESILIENCE = [
    Knob("OPENSIM_REQUEST_TIMEOUT_S", "float", "", "Default per-request deadline in seconds (the `X-Simon-Timeout-S` header wins; unset/0 = unbounded).", None, section="resilience"),
    Knob("OPENSIM_BREAKER_THRESHOLD", "int", "3", "Consecutive engine failures before that engine's circuit breaker opens.", _int(lo=1), on_error="raise", section="resilience"),
    Knob("OPENSIM_BREAKER_COOLDOWN_S", "float", "30", "Seconds an open engine breaker waits before a half-open probe.", _float(lo=0.0), on_error="raise", section="resilience"),
    Knob("OPENSIM_FAULTS", "spec", "", "Deterministic fault injection: `point:count:exc[,point:count:exc...]` (docs/resilience.md fault table).", None, section="resilience"),
    Knob("OPENSIM_SNAPSHOT_TIMEOUT_S", "float", "60", "Per-endpoint timeout for cluster snapshot list calls.", _float(lo=0.0, exclusive=True), on_error="raise", section="resilience"),
    Knob("OPENSIM_SNAPSHOT_RETRIES", "int", "3", "Snapshot fetch attempts before degrading to a stale snapshot / typed 503.", _int(lo=1), on_error="raise", section="resilience"),
    Knob("OPENSIM_SNAPSHOT_BACKOFF_S", "float", "0.1", "Full-jitter backoff base between snapshot fetch retries.", _float(lo=0.0), on_error="raise", section="resilience"),
]

_SERVER = [
    Knob("OPENSIM_ADMISSION", "enum", "on", "`on` routes requests through the admission queue + batcher; `off` restores the single-flight TryLock path.", None, choices=("on", "off"), section="server"),
    Knob("OPENSIM_PREP_CACHE", "flag", "1", "`0` disables the encode cache (per-request full prepare).", None, section="server"),
    Knob("OPENSIM_QUEUE_BOUND", "int", "64", "Admission queue bound; past it requests shed typed 503 + Retry-After.", _int(lo=1), section="server"),
    Knob("OPENSIM_BATCH_WINDOW_MS", "float", "5", "Admission coalescing window in ms, measured from the first waiter.", _float(lo=0.0), section="server"),
    Knob("OPENSIM_BATCH_MAX", "int", "16", "Max requests folded into one batched schedule dispatch.", _int(lo=1), section="server"),
    Knob("OPENSIM_WORKERS", "int", "", "Worker-pool size for unbatchable requests (default: a small CPU-derived bound).", None, section="server"),
    Knob("OPENSIM_WORKERS_MODE", "enum", "auto", "Worker pool mode: `auto`/`thread` (default) or `process` (opt-in fork+probe).", _enum("auto", "thread", "process"), section="server"),
    Knob("OPENSIM_ACCESS_LOG", "flag", "", "`1` emits one JSON access-log line per request on the `opensim_tpu.access` logger (exact value; `--access-log` sets it).", None, section="server"),
    Knob("OPENSIM_WATCH_STALE_S", "float", "30", "No watch event/bookmark for this long → the stream is stale and the twin degrades.", _float(lo=0.0, exclusive=True), on_error="raise", section="server"),
    Knob("OPENSIM_WATCH_RESYNC_S", "float", "300", "Anti-entropy relist-and-diff interval (0 disables).", _float(lo=0.0), on_error="raise", section="server"),
    Knob("OPENSIM_WATCH_RECONNECTS", "int", "5", "Bounded watch reconnect attempts per incident.", _int(lo=1), on_error="raise", section="server"),
    Knob("OPENSIM_WATCH_BACKOFF_S", "float", "0.2", "Full-jitter backoff base between watch reconnects.", _float(lo=0.0), on_error="raise", section="server"),
    Knob("OPENSIM_JOURNAL_FSYNC", "enum", "interval", "Journal fsync policy: `always`, `interval`, or `off`.", _enum("always", "interval", "off"), on_error="raise", section="server"),
    Knob("OPENSIM_JOURNAL_FSYNC_S", "float", "1.0", "Journal `interval` fsync cadence in seconds.", _float(lo=0.0, exclusive=True), on_error="raise", section="server"),
    Knob("OPENSIM_JOURNAL_SEGMENT_MB", "float", "64", "Journal segment rotation size bound in MB.", _float(lo=0.0, exclusive=True), on_error="raise", section="server"),
    Knob("OPENSIM_JOURNAL_CHECKPOINT_EVERY", "int", "4096", "Event records between journal cadence checkpoints.", _int(lo=1), on_error="raise", section="server"),
    Knob("OPENSIM_JOURNAL_KEEP", "int", "2", "Checkpoint segments retained by journal pruning.", _int(lo=1), on_error="raise", section="server"),
    Knob("OPENSIM_JOURNAL_QUEUE", "int", "65536", "Journal writer queue bound; past it records drop (counted) and the next checkpoint re-anchors.", _int(lo=1), on_error="raise", section="server"),
    # multi-process serving fleet (server/fleet.py, docs/serving.md
    # "Scaling past one process")
    Knob("OPENSIM_WORKERS_FLEET", "int", "", "Fleet worker processes for `simon server` (the `--workers` flag wins; unset/0/1 = single process).", None, section="server"),
    Knob("OPENSIM_FLEET_PUBLISH_MS", "float", "50", "Twin-owner publish cadence: how often the owner checks the twin generation and republishes arena deltas over shared memory.", _float(lo=1.0), section="server"),
    Knob("OPENSIM_FLEET_ATTACH_RETRIES", "int", "16", "Seqlock attach retries before a worker declares the publication torn (counted in simon_fleet_attach_retries_exhausted_total).", _int(lo=1), section="server"),
    Knob("OPENSIM_FLEET_ADMIN_PORT", "int", "", "Fleet admin port (aggregated /metrics, /healthz, /api/fleet/status). Default: public port + 1.", None, section="server"),
    Knob("OPENSIM_FLEET_ATTACH", "str", "", "INTERNAL: shared-memory control-block name a fleet worker attaches to (set by the fleet supervisor, never by operators).", None, section="server"),
    Knob("OPENSIM_FLEET_INTERNAL_PORT", "int", "", "INTERNAL: per-worker loopback listener port the fleet supervisor scrapes for /metrics aggregation (set by the supervisor).", None, section="server"),
    # HA control plane (server/fleet.py, docs/serving.md "Surviving owner
    # loss & rolling upgrades")
    Knob("OPENSIM_HA", "flag", "", "`1` enables the HA control plane: the fleet owner holds a fenced lease next to the journal and a `simon server --standby` process tails the journal, ready to take over.", None, section="server"),
    Knob("OPENSIM_HA_LEASE_S", "float", "5", "HA lease duration in seconds: an owner that has not renewed within this window is considered dead and the standby takes over (renewal cadence is a third of it).", _float(lo=0.0, exclusive=True), on_error="raise", section="server"),
    Knob("OPENSIM_HA_TAIL_POLL_MS", "float", "50", "Standby journal tail-follow poll cadence in ms (also the lease-expiry check cadence).", _float(lo=1.0), on_error="raise", section="server"),
    Knob("OPENSIM_HA_HANDOVER_TIMEOUT_S", "float", "30", "Bound on an explicit handover drain (rolling upgrade): past it the requesting standby falls back to lease-expiry takeover.", _float(lo=0.0, exclusive=True), on_error="raise", section="server"),
    Knob("OPENSIM_FLEET_LEASE", "str", "", "INTERNAL: HA lease file path a fleet worker follows to re-resolve the owner's control block after a failover (set by the fleet supervisor, never by operators).", None, section="server"),
    # pipelined admission + priority lanes (server/admission.py,
    # docs/serving.md "Continuous batching & priority lanes")
    Knob("OPENSIM_PIPELINE", "enum", "on", "`on` overlaps batch k+1 host prep with batch k engine dispatch (staged pipeline); `off` restores the serial single-batch-in-flight loop.", None, choices=("on", "off"), section="server"),
    Knob("OPENSIM_PRIORITY_LANES", "enum", "on", "`on` splits the admission queue into interactive/bulk lanes with weighted pickup; `off` restores strict FIFO.", None, choices=("on", "off"), section="server"),
    Knob("OPENSIM_LANE_INTERACTIVE_PODS", "int", "8", "Requests expanding to at most this many pods ride the interactive lane (explain requests always do).", _int(lo=0), section="server"),
    Knob("OPENSIM_LANE_WEIGHT", "int", "4", "Interactive-lane pickups per bulk pickup when both lanes are non-empty (weighted round-robin ratio).", _int(lo=1), section="server"),
    Knob("OPENSIM_LANE_STARVATION_S", "float", "0.5", "Starvation bound: a bulk request waiting longer than this is picked next regardless of lane weight.", _float(lo=0.0), section="server"),
    Knob("OPENSIM_EXPAND_CACHE", "flag", "1", "`0` disables the workload-expansion template cache (per-request full template clone + validation).", None, section="server"),
]

_OBSERVABILITY = [
    Knob("OPENSIM_TRACE", "flag", "1", "`0` disables request tracing (dormant cost: one contextvar read per instrumentation point).", None, section="observability"),
    Knob("OPENSIM_FLIGHT_RECORDER_N", "int", "64", "Flight-recorder ring capacity (last N request traces).", _int(lo=1), section="observability"),
    Knob("OPENSIM_EXPLAIN_STORE_N", "int", "512", "Per-trace cap on stored placement explanations (`?explain=1` audits).", _int(lo=1), section="observability"),
    Knob("OPENSIM_CAPACITY_TOPK", "int", "10", "Per-node series cap for `simon_cluster_node_utilization` (cardinality governor).", _int(lo=0), section="observability"),
    Knob("OPENSIM_CAPACITY_TIMELINE_N", "int", "512", "Capacity timeline ring capacity (generation-keyed samples).", _int(lo=1), section="observability"),
    Knob("OPENSIM_HEADROOM_PROFILES", "spec", "small=500m:1Gi,large=4:8Gi", "Registered headroom probe profiles: `name=cpu:mem[:max_replicas],...` (validated loudly).", None, on_error="raise", section="observability"),
    Knob("OPENSIM_MEM_TICKER_S", "float", "10", "Low-rate memory watermark sampling cadence in seconds (0 disables the ticker).", _float(lo=0.0), section="observability"),
    # time-series ring + SLO engine (obs/timeseries.py, obs/slo.py,
    # docs/observability.md "Watching the fleet")
    Knob("OPENSIM_TS_INTERVAL_S", "float", "5", "Time-series ring sampling cadence: every registered metric family is sampled into the on-disk ring at this interval.", _float(lo=0.0, exclusive=True), on_error="raise", section="observability"),
    Knob("OPENSIM_TS_WINDOWS", "int", "48", "Time-series ring bound: sealed delta-encoded windows kept on disk (oldest evicted first).", _int(lo=2), on_error="raise", section="observability"),
    Knob("OPENSIM_TS_WINDOW_SAMPLES", "int", "60", "Samples per time-series window before it seals to disk (windows × window_samples × interval = retention).", _int(lo=2), on_error="raise", section="observability"),
    Knob("OPENSIM_TS_DIR", "path", "", "Time-series ring directory (persists across restarts and is re-adopted on boot). Default: a private tempdir removed on shutdown.", None, section="observability"),
    Knob("OPENSIM_SLO", "spec", "availability:99.9,latency_p99:99:2.5,freshness:99:30", "Declarative SLOs: `name:target_pct[:threshold_s],...` with kinds availability/latency_p99/freshness (validated loudly).", None, on_error="raise", section="observability"),
    Knob("OPENSIM_SLO_WINDOWS", "spec", "5m,1h", "SLO burn-rate evaluation windows: `<number><s|m|h|d>,...` (multi-window burn-rate alerting).", None, on_error="raise", section="observability"),
]

_PLANNER = [
    Knob("OPENSIM_CAMPAIGN_EXEC", "enum", "warm", "Campaign execution mode (docs/campaigns.md): `warm` = one full prepare + prepcache deltas; `cold` = per-step full prepare (the verification mode the delta-equality gate compares against).", _enum("warm", "cold"), on_error="raise", section="planner"),
    Knob("OPENSIM_CAMPAIGN_MAX_STEPS", "int", "256", "Campaign spec safety bound: specs with more steps are rejected at parse time.", _int(lo=1), on_error="raise", section="planner"),
    Knob("OPENSIM_CAMPAIGN_MAX_WAVES", "int", "64", "Drain-wave runaway bound: cordon/evict/reschedule passes per drain step (blocked-eviction retries included).", _int(lo=1), on_error="raise", section="planner"),
]

_DEBUG = [
    Knob("OPENSIM_LOCKWATCH", "flag", "", "`1`/`on`/`true` enables the runtime lock-order sanitizer (`make tsan` arms it in-process).", _flag, section="debug"),
    Knob("OPENSIM_LOCKWATCH_HOLD_MS", "float", "500", "Lockwatch hold-time outlier threshold in ms (floor 1; a typo degrades to the default with a warning).", _float(lo=1.0), section="debug"),
    Knob("OPENSIM_LOCKWATCH_HOLD_EXEMPT", "spec", "", "Comma-separated site substrings exempt from lockwatch hold-time checks (inversions are never exempt).", None, section="debug"),
    Knob("OPENSIM_NO_PROGRESS", "flag", "", "Any non-empty value suppresses interactive progress spinners.", None, section="debug"),
]

for _knob in _ENGINE + _RESILIENCE + _SERVER + _OBSERVABILITY + _PLANNER + _DEBUG:
    register(_knob)


# ---------------------------------------------------------------------------
# docs generation (docs/env.md)
# ---------------------------------------------------------------------------

_SECTIONS = (
    ("engine", "Engine selection & tuning"),
    ("resilience", "Resilience (deadlines, breakers, faults, snapshot retry)"),
    ("server", "Serving (admission, workers, live twin, journal)"),
    ("observability", "Observability (tracing, capacity, memory)"),
    ("planner", "Planner (campaigns)"),
    ("debug", "Debug & development"),
)


def render_markdown() -> str:
    """The generated ``docs/env.md`` body — one table row per registered
    knob, grouped by section. Regenerate with ``make docs`` (sync is gated
    by tests/test_envknobs.py)."""
    lines = [
        "# Environment knobs",
        "",
        "Every `OPENSIM_*` variable the system reads, generated from the",
        "typed registry in `opensim_tpu/utils/envknobs.py` (`make docs`).",
        "Do not edit by hand. Raw `os.environ` reads of `OPENSIM_*` outside",
        "the registry are banned by lint rule OSL1401",
        "(docs/static-analysis.md).",
        "",
    ]
    for section, title in _SECTIONS:
        knobs = sorted((k for k in KNOBS.values() if k.section == section), key=lambda k: k.name)
        if not knobs:
            continue
        lines += [f"## {title}", "", "| Knob | Type | Default | Description |", "|---|---|---|---|"]
        for k in knobs:
            default = f"`{k.default}`" if k.default != "" else "unset"
            kind = k.type if not k.choices else "enum"
            lines.append(f"| `{k.name}` | {kind} | {default} | {k.doc} |")
        lines.append("")
    return "\n".join(lines)


def main() -> int:
    import sys

    sys.stdout.write(render_markdown())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
