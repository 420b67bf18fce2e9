"""Latency histograms + Prometheus text-format hardening (ISSUE 5).

The serving path used to export only hand-maintained ``*_seconds_total``
counters — totals hide tail behavior entirely. This module adds fixed-bucket
latency *histograms* computed from the same spans the tracer records
(``simon_phase_seconds_bucket{phase=,endpoint=}`` and
``simon_request_seconds_bucket{endpoint=}``), rendered in the Prometheus
exposition format at ``/metrics``.

It also owns the ONE recording lock for the whole metrics surface: the REST
layer's ``_Metrics`` counters, these histograms, and the span sink all
record under :data:`RECORDER`'s RLock, closing the cross-thread bump races
the old per-object locking left open (counters were bumped both from
``_handle`` and from snapshot-retry callbacks).

Label values are escaped per the exposition format (``\\`` → ``\\\\``,
``"`` → ``\\"``, newline → ``\\n``) — a hostile endpoint/path string cannot
corrupt a scrape.
"""

from __future__ import annotations

import math
import re
import threading
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "DEFAULT_BUCKETS",
    "FAMILIES",
    "CounterVec",
    "HistogramVec",
    "MetricKey",
    "MetricsRecorder",
    "RECORDER",
    "bucket_deltas",
    "counter_delta",
    "escape_label_value",
    "family_header",
    "histogram_quantile",
    "make_counter",
    "make_histogram",
    "parse_metrics",
    "scrape_metrics",
]

# fixed bucket upper bounds in seconds (the +Inf bucket is implicit):
# sub-ms cache hits through multi-second cold 50k-pod plans
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0,
)

#: batch sizes are small integers; the latency bucket ladder would waste
#: every bucket past 32 — count buckets instead (server/admission.py)
BATCH_SIZE_BUCKETS: Tuple[float, ...] = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0)

#: watch-event application is µs-scale dict surgery; the request bucket
#: ladder would collapse the whole distribution into its first bucket
WATCH_APPLY_BUCKETS: Tuple[float, ...] = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.5,
)

#: per-node utilization is a ratio in [0, 1+] (requests can legitimately
#: exceed allocatable on over-committed nodes) — capacity-shaped buckets
UTILIZATION_BUCKETS: Tuple[float, ...] = (
    0.1, 0.25, 0.5, 0.625, 0.75, 0.875, 0.95, 1.0,
)

#: event-to-servable freshness (obs/fleetobs.py): the publish loop alone
#: adds up to OPENSIM_FLEET_PUBLISH_MS, so the ladder starts at ms scale
#: and reaches the minutes a wedged worker would show
FRESHNESS_BUCKETS: Tuple[float, ...] = (
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
    10.0, 30.0, 60.0,
)

#: THE metric-family registry: ``name -> (help, type)`` for every family
#: the process can render. Family registration — names, help text, types,
#: and therefore cardinality governance — lives HERE and nowhere else
#: (opensim-lint OSL1101 bans ``CounterVec``/``HistogramVec`` construction
#: and ``exposition_headers`` calls outside this module); other modules
#: render their series through :func:`family_header` /
#: :func:`make_counter` / :func:`make_histogram`.
FAMILIES: Dict[str, Tuple[str, str]] = {
    # serving counters (server/rest.py)
    "simon_requests_total": ("Requests served by endpoint", "counter"),
    "simon_simulations_total": ("Successful simulations", "counter"),
    "simon_pods_scheduled_total": ("Pods placed across all simulations", "counter"),
    "simon_pods_unscheduled_total": ("Pods left unschedulable", "counter"),
    "simon_simulate_seconds_total": ("Wall seconds in successful simulations", "counter"),
    "simon_prepare_seconds_total": ("Host-side expand+encode seconds", "counter"),
    "simon_prep_cache_hits_total": ("Encode-cache hits", "counter"),
    "simon_prep_cache_misses_total": ("Encode-cache misses", "counter"),
    "simon_prep_cache_invalidations_total": ("Encode-cache invalidations", "counter"),
    # resilience (docs/resilience.md)
    "simon_request_timeouts_total": ("Requests 504ed at a deadline boundary", "counter"),
    "simon_snapshot_fetch_retries_total": ("Snapshot fetch retry attempts", "counter"),
    "simon_snapshot_stale_served_total": ("Requests served from a stale snapshot", "counter"),
    "simon_stale_prep_retries_total": ("Stale prep-cache internal retries", "counter"),
    "simon_engine_breaker_trips_total": ("Engine circuit-breaker trips", "counter"),
    "simon_engine_breaker_open": ("Engine breaker open (1) or closed (0)", "gauge"),
    "simon_faults_injected_total": ("Chaos faults injected by point", "counter"),
    # live twin (server/watch.py, docs/live-twin.md)
    "simon_watch_state": ("Live-twin state machine (one-hot)", "gauge"),
    "simon_watch_events_total": ("Watch events consumed by kind and resource", "counter"),
    "simon_watch_reconnects_total": ("Watch stream reconnect attempts", "counter"),
    "simon_watch_relists_total": ("Full relists (bootstrap/410/anti-entropy)", "counter"),
    "simon_watch_gone_total": ("410 Gone resourceVersion expiries", "counter"),
    "simon_twin_drift_total": ("Drifted objects repaired, by resource", "counter"),
    "simon_twin_resyncs_total": ("Anti-entropy passes that found drift", "counter"),
    "simon_twin_generation": ("Live-twin generation (bumps on every applied event)", "gauge"),
    "simon_watch_apply_seconds": ("Watch-pipeline latency: event receipt to twin applied", "histogram"),
    # admission / batching (server/admission.py, docs/serving.md)
    "simon_admission_queue_depth": ("Requests waiting in the admission queue", "gauge"),
    "simon_batches_total": ("Batched schedule dispatches", "counter"),
    "simon_shed_total": ("Requests shed at the admission queue by reason", "counter"),
    "simon_batch_size": ("Requests folded into one batched schedule dispatch", "histogram"),
    "simon_queue_wait_seconds": ("Real time-in-queue from admission to execution start", "histogram"),
    # pipelined admission + priority lanes (server/admission.py,
    # docs/serving.md "Continuous batching & priority lanes") —
    # cardinality contract: stage ∈ {prep, dispatch, decode};
    # lane ∈ {interactive, bulk}; reason reuses the typed shed reasons
    "simon_pipeline_stage_seconds": ("Per-batch pipeline stage latency by stage (prep/dispatch/decode)", "histogram"),
    "simon_pipeline_prep_overlap_seconds_total": (
        "Engine-dispatch-busy seconds observed while a later batch's host prep ran (the measured overlap)", "counter",
    ),
    "simon_pipeline_overlapped_batches_total": (
        "Batches whose host prep overlapped another batch's engine dispatch", "counter",
    ),
    "simon_lane_depth": ("Admission queue depth by priority lane", "gauge"),
    "simon_lane_admitted_total": ("Requests admitted by priority lane", "counter"),
    "simon_lane_shed_total": ("Requests shed at the admission queue by lane and reason", "counter"),
    "simon_lane_starvation_promotions_total": (
        "Bulk requests promoted past the lane weight by the starvation bound", "counter",
    ),
    # multi-process serving fleet (server/fleet.py, docs/serving.md
    # "Scaling past one process") — owner-side families are label-free;
    # worker-side attach counters are label-free too
    "simon_fleet_workers": ("Fleet worker processes currently alive", "gauge"),
    "simon_fleet_workers_target": ("Fleet worker processes configured", "gauge"),
    "simon_fleet_respawns_total": ("Fleet worker respawns after a crash", "counter"),
    "simon_fleet_publishes_total": ("Twin publications over shared memory", "counter"),
    "simon_fleet_generation": ("Last twin generation published over shared memory", "gauge"),
    "simon_fleet_shm_segments": ("Live shared-memory segments the publisher owns", "gauge"),
    "simon_fleet_shm_bytes": ("Bytes across live shared-memory segments", "gauge"),
    "simon_fleet_publish_seconds": ("Twin publication latency (delta segments + control swap)", "histogram"),
    "simon_fleet_attaches_total": ("Worker attaches to a published generation", "counter"),
    "simon_fleet_attach_retries_total": ("Seqlock retries during worker attach (torn reads)", "counter"),
    "simon_fleet_attach_retries_exhausted_total": (
        "Worker attaches abandoned after exhausting seqlock retries", "counter",
    ),
    "simon_fleet_attach_generation": ("Twin generation this worker last attached", "gauge"),
    "simon_fleet_segment_reuse_total": ("Segments reused across generations at attach (content-keyed delta hits)", "counter"),
    # HA control plane (server/fleet.py, docs/serving.md "Surviving owner
    # loss & rolling upgrades") — reason ∈ {expired, handover}
    "simon_fleet_takeovers_total": ("Standby-to-owner takeovers by reason (expired/handover)", "counter"),
    "simon_fleet_standby_tail_lag_records": ("Journal records the standby drained at its last tail poll (how far it had fallen behind)", "gauge"),
    "simon_fleet_lease_age_seconds": ("Seconds since the HA lease was last renewed", "gauge"),
    "simon_fleet_fenced_writes_total": ("Publishes refused because the lease epoch moved (a deposed owner fenced out)", "counter"),
    # latency + decision audit (this module's RECORDER)
    "simon_phase_seconds": ("Per-phase latency from the request span trees", "histogram"),
    "simon_request_seconds": ("Whole-request latency by endpoint and outcome", "histogram"),
    "simon_filter_reject_total": (
        "Nodes rejected per filter plugin while attributing unschedulable pods", "counter",
    ),
    "simon_unschedulable_total": ("Unschedulable pods by primary (most-rejecting) reason code", "counter"),
    # outcome: hit | built | declined
    "simon_resident_carry_total": (
        "XLA scans by outcome of the resident carry: started from it, built it first, or replayed in full", "counter",
    ),
    # engine: megakernel | xla; features: the active
    # feature flags joined by "+" (ops/kernels.py Features), or "none"
    "simon_engine_features_total": (
        "Scheduled streams by the engine that answered and the feature set that chose its kernels", "counter",
    ),
    # engine: megakernel | xla
    "simon_masked_pass_total": (
        "Simulations over a masked node set (the planner's prep reuse) by the engine that answered", "counter",
    ),
    # outcome: none | tail | not_asked | rescan
    "simon_megakernel_attribution_total": (
        "Clean megakernel runs of a stream by what became of their failure reasons: no pod failed, exact from the final "
        "carry, kept without reasons (the caller asked for none), or discarded for a scan that attributes", "counter",
    ),
    # sublanes: 1 | 8, the scenarios a kernel step holds
    "simon_megakernel_sweep_blocks_total": (
        "Scenario blocks the megakernel's sweeps walked the pod stream for, by the scenarios a step holds: a packed sweep "
        "of S scenarios is ceil(S/8) blocks of 8 sublanes, an unpacked one S blocks of 1", "counter",
    ),
    # path: select | onehot (engine/fastpath.py zone_reads)
    "simon_megakernel_zone_keys_total": (
        "Zone keys (topology keys but the hostname) of the megakernel's launches by how a step reads a key's counts: a "
        "compare-select over the key's few domains, or a dot with the key's one-hot [N, Z] block", "counter",
    ),
    # engine: megakernel | xla; profile: default | weights | rtcr
    # (engine/schedconfig.py profile_of)
    "simon_engine_profile_total": (
        "Scans of a stream or a sweep by the engine that ran them and the score profile of their scheduler config", "counter",
    ),
    # engine: megakernel, the rung that turned the run away; reason:
    # a row of select.DECLINES, or the envelope's token (U, A, R, vmem, topo_keys, ...)
    "simon_engine_declined_total": (
        "Streams and sweeps a faster engine turned away, by that engine and the short token of its reason", "counter",
    ),
    # engine: megakernel | xla; kind: fraction | whole | multi
    "simon_gpushare_pods_total": (
        "Pods placed with a gpu-share request by the engine that answered and by kind: a fraction of a device, one whole device, several slots",
        "counter",
    ),
    # engine: megakernel | xla; kind: lvm | ssd | hdd
    "simon_local_volumes_total": (
        "open-local claims of the placed pods by the engine that answered and by kind: an LVM volume, a whole ssd or hdd device",
        "counter",
    ),
    # path: slice | select | gather (ops/kernels.py count_reads)
    "simon_count_read_keys_total": (
        "Topology keys of the XLA scans' selector-count reads by how a step reads a key's counts: a slice of a key whose "
        "domains are its nodes in order, a compare-select over a key's few domains, or a per-node gather", "counter",
    ),
    # loader: c | python (models/expand.py: libyaml where PyYAML has it)
    "simon_yaml_documents_total": (
        "YAML documents read from files and rendered charts by the parser that read them", "counter",
    ),
    # capacity observatory (obs/capacity.py, docs/observability.md) —
    # cardinality contract: every family below is label-free or bounded
    # (resource ∈ {cpu, memory, pods}; profile = registered headroom
    # profiles; node series are capped at the top-K hottest nodes)
    "simon_cluster_utilization": ("Per-node utilization distribution by resource", "histogram"),
    "simon_cluster_node_utilization": (
        "Top-K hottest node utilization by resource (cardinality-capped)", "gauge",
    ),
    "simon_cluster_utilization_ratio": ("Aggregate requested/allocatable by resource", "gauge"),
    "simon_cluster_allocatable": ("Cluster-wide allocatable by resource", "gauge"),
    "simon_cluster_requested": ("Cluster-wide requests of counted pods by resource", "gauge"),
    "simon_cluster_spread": ("Allocation spread: stddev/mean of per-node utilization", "gauge"),
    "simon_cluster_fragmentation": (
        "Free-capacity fragmentation: 1 - largest free node / total free", "gauge",
    ),
    "simon_cluster_headroom": (
        "Max additional replicas of a registered workload profile that still fit", "gauge",
    ),
    "simon_cluster_nodes": ("Nodes in the observed cluster", "gauge"),
    "simon_cluster_pods_bound": ("Counted pods bound to a node", "gauge"),
    "simon_cluster_pods_pending": ("Counted pods with no node (unschedulable pressure)", "gauge"),
    # watch-event journal (server/journal.py, docs/live-twin.md) — type ∈
    # {ev, rb, ck}; outcome ∈ {restored, empty, corrupt}
    "simon_journal_records_total": ("Journal records written by type (ev/rb/ck)", "counter"),
    "simon_journal_bytes_total": ("Journal bytes written (framing included)", "counter"),
    "simon_journal_dropped_total": ("Records dropped at the bounded writer queue", "counter"),
    "simon_journal_fsync_seconds": ("Journal fsync latency", "histogram"),
    "simon_journal_recoveries_total": ("Journal recovery attempts by outcome", "counter"),
    # memory observatory (obs/footprint.py, ISSUE 12) — cardinality
    # contract: dtype ∈ the encoder policy set (encoding/dtypes.py) plus
    # "other"; ring ∈ {flight_recorder, capacity_timeline, journal_queue};
    # device series are one per local accelerator, kind ∈ {in_use, peak}
    "simon_mem_rss_bytes": ("Process resident set size", "gauge"),
    "simon_mem_rss_peak_bytes": ("Process RSS high watermark (VmHWM)", "gauge"),
    "simon_mem_device_bytes": ("Per-device accelerator memory by kind (in_use/peak)", "gauge"),
    "simon_mem_prepcache_bytes": ("Prep-cache host arena bytes (shared leaves counted once)", "gauge"),
    "simon_mem_prepcache_entries": ("Prep-cache entries resident", "gauge"),
    "simon_mem_prepcache_evictions_total": ("Prep-cache LRU evictions", "counter"),
    "simon_mem_prepcache_compactions_total": (
        "Twin-delta refusals at the drop-mask density threshold (full rebuild follows)", "counter",
    ),
    "simon_mem_arena_bytes": ("Prep-cache host arena bytes by encoder-policy dtype", "gauge"),
    "simon_mem_ring_entries": ("Bounded-ring occupancy by ring", "gauge"),
    "simon_mem_ring_capacity": ("Bounded-ring capacity by ring", "gauge"),
    # compile telemetry (obs/profile.py, ISSUE 12) — fn is a fixed set of
    # instrumented jit boundaries; cause ∈ {first, shape, dtype, static,
    # new}; event is the jax compilation-cache event leaf name
    "simon_compile_total": ("JIT compiles observed at instrumented boundaries", "counter"),
    "simon_compile_seconds_total": ("Wall seconds inside observed JIT compiles", "counter"),
    "simon_compile_cause_total": ("Recompiles by attributed cause (shape/dtype/static/new)", "counter"),
    "simon_backend_compile_seconds_total": (
        "Backend (XLA) compile seconds from jax monitoring, all call sites", "counter",
    ),
    "simon_backend_compile_total": ("Backend (XLA) compiles from jax monitoring", "counter"),
    "simon_compile_stage_seconds_total": (
        "Seconds on the compile path by stage (trace/lower/backend/cache_retrieval), compiled or not", "counter",
    ),
    "simon_device_info": (
        "The JAX backend this process computes on, by platform and device_kind (value = device count)", "gauge",
    ),
    "simon_jitcache_persistent_files": ("Entries in the persistent XLA compile cache dir", "gauge"),
    "simon_jitcache_persistent_bytes": ("Bytes in the persistent XLA compile cache dir", "gauge"),
    "simon_jitcache_events_total": ("jax compilation-cache monitoring events by leaf name", "counter"),
    # aggregate phase profiles (obs/profile.py) — span names are the fixed
    # instrumentation vocabulary (phases, engine rungs, their sub-phases)
    "simon_phase_profile_calls_total": ("Spans folded into the cumulative profile, by span name", "counter"),
    "simon_phase_profile_seconds_total": ("Cumulative inclusive span seconds by span name", "counter"),
    "simon_phase_profile_exclusive_seconds_total": (
        "Cumulative exclusive span seconds (children subtracted) by span name", "counter",
    ),
    # fleet-wide observability (ISSUE 20, obs/fleetobs.py): the event-to-
    # servable freshness pipeline — stage ∈ {journaled, published,
    # attached, served}, each measured from watch-event acceptance on the
    # owner's wall clock (owner and workers share a host)
    "simon_fleet_freshness_seconds": (
        "Event-to-servable latency by pipeline stage, from watch-event acceptance", "histogram",
    ),
    # time-series ring (obs/timeseries.py): sampling liveness + disk bound
    "simon_ts_samples_total": ("Time-series ring samples recorded", "counter"),
    "simon_ts_window_bytes": ("Bytes held by the on-disk time-series ring", "gauge"),
    "simon_ts_windows": ("Delta-encoded windows resident in the time-series ring", "gauge"),
    # SLO engine (obs/slo.py): burn rate = observed bad fraction over the
    # window divided by the objective's error budget (1.0 = burning budget
    # exactly at the sustainable rate); slo/window are a fixed small set
    "simon_slo_burn_rate": ("SLO burn rate by objective and evaluation window", "gauge"),
}


def exposition_headers(name: str, help_text: str, kind: str = "counter") -> List[str]:
    """The ``# HELP``/``# TYPE`` header pair every rendered family carries
    (exposition-format conformance, ISSUE 7 satellite) — the one place the
    header layout lives. Prefer :func:`family_header`, which also forces the
    family through the registry above."""
    return [f"# HELP {name} {help_text}", f"# TYPE {name} {kind}"]


def family_header(name: str) -> List[str]:
    """``# HELP``/``# TYPE`` for a REGISTERED family — the only way modules
    outside this file emit headers (OSL1101), so an unregistered family
    fails loudly at render time instead of silently forking the registry."""
    try:
        help_text, kind = FAMILIES[name]
    except KeyError:
        raise KeyError(
            f"metric family {name!r} is not registered in obs/metrics.py "
            "FAMILIES; register it there (cardinality governance)"
        ) from None
    return exposition_headers(name, help_text, kind)


def make_counter(name: str, label_names: Sequence[str]) -> "CounterVec":
    """A :class:`CounterVec` for a registered family (help text comes from
    the registry)."""
    help_text, kind = FAMILIES[name]  # KeyError = unregistered family
    if kind != "counter":
        raise ValueError(f"{name} is registered as {kind}, not counter")
    return CounterVec(name, label_names, help=help_text)


def make_histogram(
    name: str,
    label_names: Sequence[str],
    buckets: Sequence[float] = DEFAULT_BUCKETS,
) -> "HistogramVec":
    """A :class:`HistogramVec` for a registered family."""
    help_text, kind = FAMILIES[name]  # KeyError = unregistered family
    if kind != "histogram":
        raise ValueError(f"{name} is registered as {kind}, not histogram")
    return HistogramVec(name, label_names, buckets=buckets, help=help_text)


def escape_label_value(value: str) -> str:
    """Prometheus exposition-format label escaping (text format §label
    values): backslash, double quote, and line feed."""
    return (
        str(value)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _fmt_le(bound: float) -> str:
    if math.isinf(bound):
        return "+Inf"
    s = f"{bound:g}"
    return s


# ---------------------------------------------------------------------------
# Prometheus text-format READING (stdlib only) — the inverse of the render
# path above, shared by the loadgen harness (server/loadgen.py), the fleet
# aggregator (server/fleet.py), and the time-series ring (obs/timeseries.py)
# so per-worker histograms are merged once, correctly, in one place
# (ISSUE 20 satellite; this code started life inside loadgen).
# ---------------------------------------------------------------------------

_SAMPLE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+([0-9eE+.\-]+|\+Inf|NaN)$"
)
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')

MetricKey = Tuple[str, Tuple[Tuple[str, str], ...]]


def parse_metrics(text: str) -> Dict[MetricKey, float]:
    """Exposition text → ``{(name, sorted label items): value}``."""
    out: Dict[MetricKey, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        if not m:
            continue
        name, labels_body, value = m.groups()
        labels = tuple(sorted(
            (k, v.replace('\\"', '"').replace("\\\\", "\\"))
            for k, v in _LABEL.findall(labels_body or "")
        ))
        out[(name, labels)] = float(value)
    return out


def scrape_metrics(url: str, timeout_s: float = 10.0) -> Dict[MetricKey, float]:
    import urllib.request

    with urllib.request.urlopen(f"{url}/metrics", timeout=timeout_s) as resp:
        return parse_metrics(resp.read().decode())


def _series_delta(after_v: float, before_v: float) -> float:
    """Cumulative-series delta with counter-reset handling (the PromQL
    ``rate()`` convention): a decrease means the process restarted and the
    counter began again at zero, so the post-reset value IS the delta —
    without this a worker restart mid-measurement reports a negative
    count and poisons every merged quantile."""
    d = after_v - before_v
    return after_v if d < 0 else d


def bucket_deltas(
    before: Dict[MetricKey, float],
    after: Dict[MetricKey, float],
    family: str,
    match: Dict[str, str],
) -> List[Tuple[float, float]]:
    """Sorted ``(le, cumulative delta)`` for one histogram family,
    aggregated over every series whose labels are a superset of ``match``
    (summing cumulative bucket counts across series is legal — they share
    the bucket ladder). A series absent from ``before`` (a worker that
    joined mid-measurement, or an empty first scrape) contributes its full
    ``after`` value; a series that DECREASED is a counter reset and
    contributes its post-reset value."""
    sums: Dict[float, float] = {}
    for (name, labels), v in after.items():
        if name != f"{family}_bucket":
            continue
        ld = dict(labels)
        if any(ld.get(k) != want for k, want in match.items()):
            continue
        le = math.inf if ld.get("le") == "+Inf" else float(ld.get("le", "inf"))
        sums[le] = sums.get(le, 0.0) + _series_delta(v, before.get((name, labels), 0.0))
    return sorted(sums.items())


def histogram_quantile(
    before: Dict[MetricKey, float],
    after: Dict[MetricKey, float],
    family: str,
    q: float,
    match: Optional[Dict[str, str]] = None,
) -> Optional[float]:
    """PromQL ``histogram_quantile`` over the scrape DELTA (so a long-lived
    server's history does not pollute the run's distribution): linear
    interpolation inside the target bucket. None when the delta is empty."""
    buckets = bucket_deltas(before, after, family, match or {})
    if not buckets:
        return None
    total = buckets[-1][1]
    if total <= 0:
        return None
    target = q * total
    prev_le, prev_cum = 0.0, 0.0
    for le, cum in buckets:
        if cum >= target:
            if math.isinf(le):
                return prev_le  # tail bucket: the lower bound is the honest answer
            if cum == prev_cum:
                return le
            return prev_le + (le - prev_le) * (target - prev_cum) / (cum - prev_cum)
        prev_le, prev_cum = le, cum
    return buckets[-1][0]


def counter_delta(
    before: Dict[MetricKey, float],
    after: Dict[MetricKey, float],
    name: str,
    match: Optional[Dict[str, str]] = None,
) -> float:
    """Summed counter delta across matching series, reset-safe (see
    :func:`bucket_deltas`)."""
    total = 0.0
    for (n, labels), v in after.items():
        if n != name:
            continue
        ld = dict(labels)
        if match and any(ld.get(k) != want for k, want in match.items()):
            continue
        total += _series_delta(v, before.get((n, labels), 0.0))
    return total


class CounterVec:
    """One counter family over a fixed label set, rendered with its
    ``# HELP``/``# TYPE`` header. Not self-locking — mutations happen under
    the owning :class:`MetricsRecorder`'s lock like everything else."""

    def __init__(self, name: str, label_names: Sequence[str], help: str = "") -> None:
        self.name = name
        self.label_names = tuple(label_names)
        self.help = help
        self._series: Dict[Tuple[str, ...], int] = {}  # guarded-by: RECORDER.lock

    def inc(self, labels: Tuple[str, ...], n: int = 1) -> None:
        self._series[labels] = self._series.get(labels, 0) + n

    def render_lines(self) -> List[str]:
        if not self._series:
            return []
        lines = []
        if self.help:
            lines.append(f"# HELP {self.name} {self.help}")
        lines.append(f"# TYPE {self.name} counter")
        for labels in sorted(self._series):
            base = ",".join(
                f'{k}="{escape_label_value(v)}"'
                for k, v in zip(self.label_names, labels)
            )
            lines.append(f"{self.name}{{{base}}} {self._series[labels]}")
        return lines

    def reset(self) -> None:
        self._series.clear()


class HistogramVec:
    """One histogram family over a fixed label set. Not self-locking: every
    mutation/read happens under the owning :class:`MetricsRecorder`'s lock
    (the one-lock design is the point — see module docstring)."""

    def __init__(
        self,
        name: str,
        label_names: Sequence[str],
        buckets: Sequence[float] = DEFAULT_BUCKETS,
        help: str = "",
    ) -> None:
        self.name = name
        self.label_names = tuple(label_names)
        self.buckets = tuple(buckets) + (math.inf,)
        self.help = help
        # label-values tuple -> [per-bucket counts..., count, sum]
        self._series: Dict[Tuple[str, ...], list] = {}  # guarded-by: RECORDER.lock

    def observe(self, seconds: float, labels: Tuple[str, ...]) -> None:
        series = self._series.get(labels)
        if series is None:
            series = self._series[labels] = [0] * len(self.buckets) + [0, 0.0]
        for i, bound in enumerate(self.buckets):
            if seconds <= bound:
                series[i] += 1
                break
        series[-2] += 1
        series[-1] += seconds

    def render_lines(self) -> List[str]:
        if not self._series:
            return []
        lines = []
        if self.help:
            lines.append(f"# HELP {self.name} {self.help}")
        lines.append(f"# TYPE {self.name} histogram")
        for labels in sorted(self._series):
            series = self._series[labels]
            base = ",".join(
                f'{k}="{escape_label_value(v)}"'
                for k, v in zip(self.label_names, labels)
            )
            sep = "," if base else ""
            # label-less histograms (e.g. simon_batch_size) must not render
            # empty `{}` braces — the exposition grammar rejects them
            wrap = f"{{{base}}}" if base else ""
            cum = 0
            for i, bound in enumerate(self.buckets):
                cum += series[i]
                lines.append(
                    f'{self.name}_bucket{{{base}{sep}le="{_fmt_le(bound)}"}} {cum}'
                )
            lines.append(f"{self.name}_sum{wrap} {series[-1]:.6f}")
            lines.append(f"{self.name}_count{wrap} {series[-2]}")
        return lines

    def reset(self) -> None:
        self._series.clear()


class MetricsRecorder:
    """The locked recorder every metrics mutation routes through: phase and
    request latency histograms fed from trace spans, plus the shared RLock
    the REST counters borrow."""

    def __init__(self) -> None:
        self.lock = threading.RLock()
        self.phase_seconds = make_histogram("simon_phase_seconds", ("phase", "endpoint"))
        self.request_seconds = make_histogram("simon_request_seconds", ("endpoint", "status"))
        # decision audit (ISSUE 7): per-filter node rejects from the
        # failure attribution, and unschedulable pods by primary reason —
        # bumped by every simulate() regardless of explain mode
        self.filter_rejects = make_counter("simon_filter_reject_total", ("filter",))
        self.unschedulable = make_counter("simon_unschedulable_total", ("reason",))
        # XLA scans by what became of the resident carry (engine/resident.py)
        self.resident_carry = make_counter("simon_resident_carry_total", ("outcome",))
        # streams by answering engine and feature set (engine/simulator.py's ladder)
        self.engine_features = make_counter("simon_engine_features_total", ("engine", "features"))
        # masked simulations by answering engine; megakernel over all is
        # how often the planner's final pass engages the kernel
        self.masked_pass = make_counter("simon_masked_pass_total", ("engine",))
        # clean kernel runs by what became of their failure reasons; rescan over
        # all is how often a stream pays the kernel and a whole scan after it
        self.megakernel_attribution = make_counter("simon_megakernel_attribution_total", ("outcome",))
        # scenario blocks of megakernel sweeps by sublanes a step; blocks of
        # 1 mean a sweep that did not pack (fastpath.sweep_sublanes)
        self.megakernel_sweep_blocks = make_counter("simon_megakernel_sweep_blocks_total", ("sublanes",))
        # zone keys of every kernel launch by the read of their counts; onehot
        # on a cluster of a few zones means the compare-select did not engage
        self.megakernel_zone_keys = make_counter("simon_megakernel_zone_keys_total", ("path",))
        # runs that reached a slower rung because a faster one declined them
        # (engine/select.py turned_away): the rung and its reason's token
        self.engine_declined = make_counter("simon_engine_declined_total", ("engine", "reason"))
        # every scan by the engine that ran it and its config's score profile:
        # a profile other than default on the XLA scan is one the kernel lost
        self.engine_profile = make_counter("simon_engine_profile_total", ("engine", "profile"))
        # pods placed with a gpu-share request, by answering engine and kind
        # (fraction of a device, one whole device, several slots)
        self.gpushare_pods = make_counter("simon_gpushare_pods_total", ("engine", "kind"))
        # open-local claims of the placed pods, by answering engine and kind
        self.local_volumes = make_counter("simon_local_volumes_total", ("engine", "kind"))
        # topology keys of every XLA scan by the path its count reads take;
        # gather in a cluster with hostname labels means the slice did not engage
        self.count_read_keys = make_counter("simon_count_read_keys_total", ("path",))
        # documents by the YAML parser that read them (models/expand.py);
        # "python" on a host whose PyYAML has libyaml means the fast parser is not engaged
        self.yaml_documents = make_counter("simon_yaml_documents_total", ("loader",))
        # watch-pipeline latency (ISSUE 9 satellite): event receipt → twin
        # applied, fed from the supervisor's dispatch (server/watch.py)
        self.watch_apply = make_histogram(
            "simon_watch_apply_seconds", (), buckets=WATCH_APPLY_BUCKETS
        )

    def observe_request(self, endpoint: str, seconds: float, status: str = "ok") -> None:
        """Whole-request latency — recorded for every outcome (labeled with
        the trace status, so errors/timeouts have their own series), with or
        without tracing enabled (the histogram must not go dark when
        ``OPENSIM_TRACE=0``)."""
        with self.lock:
            self.request_seconds.observe(seconds, (endpoint, status))

    def observe_phase(self, phase: str, endpoint: str, seconds: float) -> None:
        with self.lock:
            self.phase_seconds.observe(seconds, (phase, endpoint))

    def observe_watch_apply(self, seconds: float) -> None:
        """One watch event's receipt→applied latency (server/watch.py
        dispatch — includes the injected-fault bookkeeping and the twin's
        rv-monotonic store surgery, not the network read)."""
        with self.lock:
            self.watch_apply.observe(seconds, ())

    def observe_trace(self, trace) -> None:
        """The span sink: fold a finished trace's phase spans into the
        per-phase histograms. One recording path — the histograms and the
        flight-recorder tree are computed from the SAME span objects."""
        from .trace import PHASES

        phases = set(PHASES)
        with self.lock:
            for sp in trace.walk():
                if sp.name in phases:
                    self.phase_seconds.observe(sp.duration_s, (sp.name, trace.endpoint))

    def simulate_seconds_total(self) -> float:
        """Continuity shim for the pre-histogram ``simon_simulate_seconds_total``
        counter, derived from the one recording path instead of
        hand-maintained. Sums the ``status="ok"`` series only — the old
        counter accumulated successful simulations exclusively, and a
        dashboard dividing it by ``simon_simulations_total`` (also
        success-only) must not spike during an outage."""
        with self.lock:
            return sum(
                s[-1]
                for labels, s in self.request_seconds._series.items()
                if labels[1] == "ok"
            )

    def count_filter_rejects(self, by_filter: Dict[str, int]) -> None:
        with self.lock:
            for name, n in by_filter.items():
                self.filter_rejects.inc((name,), int(n))

    def count_unschedulable(self, by_reason: Dict[str, int]) -> None:
        with self.lock:
            for name, n in by_reason.items():
                self.unschedulable.inc((name,), int(n))

    def count_resident_carry(self, outcome: str) -> None:
        with self.lock:
            self.resident_carry.inc((outcome,))

    def count_engine_features(self, engine: str, features: str) -> None:
        with self.lock:
            self.engine_features.inc((engine, features))

    def count_masked_pass(self, engine: str) -> None:
        with self.lock:
            self.masked_pass.inc((engine,))

    def count_megakernel_attribution(self, outcome: str) -> None:
        with self.lock:
            self.megakernel_attribution.inc((outcome,))

    def count_megakernel_sweep_blocks(self, sublanes: int, blocks: int) -> None:
        with self.lock:
            self.megakernel_sweep_blocks.inc((str(sublanes),), blocks)

    def count_megakernel_zone_keys(self, selected: int, onehot: int) -> None:
        with self.lock:
            for path, n in (("select", selected), ("onehot", onehot)):
                if n:
                    self.megakernel_zone_keys.inc((path,), n)

    def count_engine_declined(self, engine: str, reason: str) -> None:
        with self.lock:
            self.engine_declined.inc((engine, reason))

    def count_engine_profile(self, engine: str, profile: str) -> None:
        with self.lock:
            self.engine_profile.inc((engine, profile))

    def count_gpushare_pods(self, engine: str, by_kind: Dict[str, int]) -> None:
        with self.lock:
            for kind, n in by_kind.items():
                if n:
                    self.gpushare_pods.inc((engine, kind), int(n))

    def count_local_volumes(self, engine: str, by_kind: Dict[str, int]) -> None:
        with self.lock:
            for kind, n in by_kind.items():
                if n:
                    self.local_volumes.inc((engine, kind), int(n))

    def count_read_keys_by_path(self, by_path: Dict[str, int]) -> None:
        with self.lock:
            for path, n in by_path.items():
                self.count_read_keys.inc((path,), int(n))

    def count_yaml_documents(self, loader: str, n: int) -> None:
        with self.lock:
            self.yaml_documents.inc((loader,), n)

    def render_lines(self) -> List[str]:
        with self.lock:
            return (
                self.filter_rejects.render_lines()
                + self.unschedulable.render_lines()
                + self.resident_carry.render_lines()
                + self.engine_features.render_lines()
                + self.masked_pass.render_lines()
                + self.megakernel_attribution.render_lines()
                + self.megakernel_sweep_blocks.render_lines()
                + self.megakernel_zone_keys.render_lines()
                + self.engine_declined.render_lines()
                + self.engine_profile.render_lines()
                + self.gpushare_pods.render_lines()
                + self.local_volumes.render_lines()
                + self.count_read_keys.render_lines()
                + self.yaml_documents.render_lines()
                + self.phase_seconds.render_lines()
                + self.request_seconds.render_lines()
                + self.watch_apply.render_lines()
            )

    def reset(self) -> None:
        with self.lock:
            self.phase_seconds.reset()
            self.request_seconds.reset()
            self.filter_rejects.reset()
            self.unschedulable.reset()
            self.resident_carry.reset()
            self.engine_features.reset()
            self.masked_pass.reset()
            self.megakernel_attribution.reset()
            self.megakernel_sweep_blocks.reset()
            self.megakernel_zone_keys.reset()
            self.engine_declined.reset()
            self.engine_profile.reset()
            self.gpushare_pods.reset()
            self.local_volumes.reset()
            self.count_read_keys.reset()
            self.yaml_documents.reset()
            self.watch_apply.reset()


RECORDER = MetricsRecorder()
