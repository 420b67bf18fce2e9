"""REST server — parity with ``pkg/server/server.go``: ``GET /healthz``,
``POST /api/deploy-apps``, ``POST /api/scale-apps`` with the exact request/
response DTOs (``server.go:48-93``) so existing clients can switch backends.

Implementation notes vs the reference:
- stdlib ``http.server`` replaces gin (no third-party web framework in the
  image); single-flight busy rejection mirrors the TryLock 503 behavior
  (``server.go:167,:234``).
- The live-cluster informer snapshot is taken per request via the
  Kubernetes Python client when a kubeconfig is configured; without one, the
  server can still serve simulations whose requests carry their own nodes
  (useful for testing and air-gapped use — a divergence the reference
  doesn't offer).
"""

from __future__ import annotations

import hashlib
import json
import logging
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import TYPE_CHECKING, List, Optional

if TYPE_CHECKING:
    from ..engine.prepcache import PrepareCache

from ..engine.simulator import AppResource, SimulateResult, simulate
from ..models.objects import LABEL_APP_NAME, Node, ResourceTypes, object_from_dict
from ..obs import trace as tracing
from ..obs.metrics import RECORDER, escape_label_value, family_header
from ..obs.recorder import FLIGHT_RECORDER
from ..resilience import breaker as breaker_mod
from ..resilience import faults
from ..resilience.deadline import Deadline, DeadlineExceeded, check_deadline, deadline_scope
from ..resilience.retry import retry_call
from ..utils import envknobs
from .snapshot import (
    SnapshotFetchError,
    SnapshotUnavailable,
    cluster_from_kubeconfig,
    snapshot_retry_policy,
)

log = logging.getLogger("opensim_tpu.server")
# structured access log (OPENSIM_ACCESS_LOG=1): one JSON object per line
_ACCESS_LOG = logging.getLogger("opensim_tpu.access")

_deploy_lock = threading.Lock()  # lockwatch: hold-exempt — single-flight, spans engine work incl. first XLA compile
_scale_lock = threading.Lock()  # lockwatch: hold-exempt — single-flight, spans engine work incl. first XLA compile

# per-request state (one HTTP request = one handler thread): whether THIS
# request's result was computed from a stale snapshot. Reading the shared
# SimonServer flag at send time would mis-tag responses when a concurrent
# request's refresh flips it mid-flight.
_REQUEST_STATE = threading.local()


def _mark_request_snapshot(stale: bool) -> None:
    _REQUEST_STATE.snapshot_stale = stale


def request_served_stale() -> bool:
    """Did the current thread's request get served from a stale snapshot?"""
    return getattr(_REQUEST_STATE, "snapshot_stale", False)


def response_extra_headers() -> dict:
    """Extra response headers the current thread's request accumulated
    (e.g. ``Retry-After`` on an admission shed) — reset per request by the
    handler, merged into ``_send``."""
    return getattr(_REQUEST_STATE, "extra_headers", {}) or {}


def last_request_id() -> str:
    """The request id assigned to the current thread's request (honored from
    ``X-Simon-Request-Id`` if the client sent one, generated otherwise) —
    echoed back in the response header by the handler."""
    return getattr(_REQUEST_STATE, "request_id", "")


class _Metrics:
    """Process-local counters exposed at /metrics in Prometheus text format
    (the reference's vendored scheduler metrics exist but are never exposed;
    SURVEY.md §5 — this closes that gap).

    Locking (ISSUE 5 bugfix): every mutation routes through the ONE
    recorder RLock shared with the span sink and latency histograms
    (``obs.metrics.RECORDER``) — counters are bumped both from ``_handle``
    and from snapshot-retry callbacks on other code paths, and the old
    per-object lock left render() assembling a scrape interleaved with
    recordings. Label values are escaped per the exposition format so a
    hostile endpoint/path string cannot corrupt the scrape."""

    def __init__(self) -> None:
        self.lock = RECORDER.lock  # the one metrics lock (an RLock)
        self.requests = {"deploy-apps": 0, "scale-apps": 0}
        self.simulations = 0
        self.pods_scheduled = 0
        self.pods_unscheduled = 0
        # resilience counters (docs/resilience.md): deadline 504s, snapshot
        # fetch retries/degradations, stale-prep-cache internal retries
        self.request_timeouts = 0
        self.snapshot_retries = 0
        self.snapshot_stale_served = 0
        self.stale_prep_retries = 0

    def record(self, endpoint: str, result: SimulateResult) -> None:
        # simulate wall time is no longer hand-summed here: the request
        # latency histogram (RECORDER.observe_request, one recording path)
        # carries both the distribution and the total
        with self.lock:
            self.requests[endpoint] = self.requests.get(endpoint, 0) + 1
            self.simulations += 1
            self.pods_scheduled += sum(len(ns.pods) for ns in result.node_status)
            self.pods_unscheduled += len(result.unscheduled_pods)

    def bump(self, counter: str, n: int = 1) -> None:
        with self.lock:
            setattr(self, counter, getattr(self, counter) + n)

    def render(self, prep_cache=None, watch=None, admission=None, capacity=None,
               journal=None, memory=None) -> str:
        from ..utils.trace import PREP_STATS

        esc = escape_label_value
        hdr = family_header  # every family comes from the obs/metrics.py registry

        with self.lock:
            lines = [
                *hdr("simon_requests_total"),
                *(
                    f'simon_requests_total{{endpoint="{esc(ep)}"}} {n}'
                    for ep, n in sorted(self.requests.items())
                ),
                *hdr("simon_simulations_total"),
                f"simon_simulations_total {self.simulations}",
                *hdr("simon_pods_scheduled_total"),
                f"simon_pods_scheduled_total {self.pods_scheduled}",
                *hdr("simon_pods_unscheduled_total"),
                f"simon_pods_unscheduled_total {self.pods_unscheduled}",
                *hdr("simon_simulate_seconds_total"),
                f"simon_simulate_seconds_total {RECORDER.simulate_seconds_total():.6f}",
            ]
        # host-side prepare attribution (incremental prepare): total seconds
        # spent producing Prepared inputs, and the encode-cache counters
        lines += [
            *hdr("simon_prepare_seconds_total"),
            f"simon_prepare_seconds_total {PREP_STATS.total_seconds():.6f}",
        ]
        if prep_cache is not None:
            st = prep_cache.stats
            lines += [
                *hdr("simon_prep_cache_hits_total"),
                f"simon_prep_cache_hits_total {st.hits}",
                *hdr("simon_prep_cache_misses_total"),
                f"simon_prep_cache_misses_total {st.misses}",
                *hdr("simon_prep_cache_invalidations_total"),
                f"simon_prep_cache_invalidations_total {st.invalidations}",
            ]
        # resilience layer: deadline 504s, snapshot degradation, engine
        # breaker state, fault injections (docs/resilience.md)
        with self.lock:
            lines += [
                *hdr("simon_request_timeouts_total"),
                f"simon_request_timeouts_total {self.request_timeouts}",
                *hdr("simon_snapshot_fetch_retries_total"),
                f"simon_snapshot_fetch_retries_total {self.snapshot_retries}",
                *hdr("simon_snapshot_stale_served_total"),
                f"simon_snapshot_stale_served_total {self.snapshot_stale_served}",
                *hdr("simon_stale_prep_retries_total"),
                f"simon_stale_prep_retries_total {self.stale_prep_retries}",
            ]
        breakers = sorted(breaker_mod.all_breakers().items())
        lines += hdr("simon_engine_breaker_trips_total")
        lines += [
            f'simon_engine_breaker_trips_total{{engine="{esc(name)}"}} {br.trips_total}'
            for name, br in breakers
        ]
        lines += hdr("simon_engine_breaker_open")
        lines += [
            f'simon_engine_breaker_open{{engine="{esc(name)}"}} '
            f'{int(br.state() != "closed")}'
            for name, br in breakers
        ]
        fired = sorted(faults.fault_stats().items())
        if fired:
            lines += hdr("simon_faults_injected_total")
            lines += [
                f'simon_faults_injected_total{{point="{esc(point)}"}} {n}'
                for point, n in fired
            ]
        # live-twin state machine + event/drift counters (server/watch.py):
        # simon_watch_state one-hot, events by kind, reconnects, drift
        if watch is not None:
            lines += watch.metrics_lines()
        # admission queue / batching / shedding telemetry (ISSUE 8,
        # server/admission.py): queue depth gauge, batch-size histogram,
        # shed counters, real time-in-queue
        if admission is not None:
            lines += admission.metrics_lines()
        # capacity observatory (ISSUE 9, obs/capacity.py): per-node
        # utilization distribution, top-K hottest nodes, spread/
        # fragmentation gauges, headroom per registered profile
        if capacity is not None:
            lines += capacity.metrics_lines()
        # watch-event journal (ISSUE 11, server/journal.py): records/bytes
        # written, writer-queue drops, fsync latency, recovery outcomes
        if journal is not None:
            lines += journal.metrics_lines()
        # memory observatory (ISSUE 12, obs/footprint.py): RSS/device
        # watermarks, prep-cache arena bytes, ring occupancy
        if memory is not None:
            lines += memory.metrics_lines()
        # compile telemetry + cumulative phase profiles (ISSUE 12,
        # obs/profile.py) — process singletons, rendered on every scrape
        from ..obs.profile import COMPILES, PROFILE

        lines += COMPILES.metrics_lines()
        lines += PROFILE.metrics_lines()
        # per-phase / per-endpoint latency histograms, computed from the
        # same spans the flight recorder serves (obs/metrics.py)
        lines += RECORDER.render_lines()
        return "\n".join(lines) + "\n"


METRICS = _Metrics()


def _decode_app(payload: dict) -> ResourceTypes:
    rt = ResourceTypes()
    kind_map = {
        "pods": "Pod",
        "deployments": "Deployment",
        "daemonsets": "DaemonSet",
        "DaemonSets": "DaemonSet",
        "statefulsets": "StatefulSet",
        "StatefulSets": "StatefulSet",
        "Jobs": "Job",
        "jobs": "Job",
        "ConfigMaps": "ConfigMap",
        "configmaps": "ConfigMap",
        "Deployments": "Deployment",
        "Pods": "Pod",
    }
    for key, kind in kind_map.items():
        for obj in payload.get(key) or []:
            obj = dict(obj)
            obj.setdefault("kind", kind)
            decoded = object_from_dict(obj)
            if decoded is not None:
                rt.add(decoded)
    return rt


def _decode_new_nodes(payload: dict) -> List[Node]:
    """Requested nodes become fake nodes exactly like the apply path
    (server.go:187-194 → NewFakeNode): fresh simon-<rand> name, hostname
    label rewritten, simon/new-node marker."""
    from ..models.expand import new_fake_nodes

    nodes = []
    for obj in payload.get("newnodes") or payload.get("NewNodes") or []:
        obj = dict(obj)
        obj.setdefault("kind", "Node")
        nodes.extend(new_fake_nodes(Node.from_dict(obj), 1))
    return nodes


def _response(result: SimulateResult, explain: bool = False) -> dict:
    """getSimulateResponse (server.go:446-470): names only; node entries only
    for nodes holding app pods. ``explain=1`` (ISSUE 7) upgrades each
    unscheduled entry with its typed reason breakdown and adds the
    per-filter reject totals — additive, so existing clients are
    unaffected."""
    expl_by_pod = {}
    engine = result.engine
    if explain and engine is not None and engine.explanations:
        expl_by_pod = {e.pod: e for e in engine.explanations}
    out = {"unscheduledPods": [], "nodeStatus": []}
    for up in result.unscheduled_pods:
        name = f"{up.pod.metadata.namespace}/{up.pod.metadata.name}"
        entry = {"pod": name, "reason": up.reason}
        e = expl_by_pod.get(name)
        if e is not None:
            entry["explanation"] = e.to_dict()
        out["unscheduledPods"].append(entry)
    for ns in result.node_status:
        pods = [
            f"{p.metadata.namespace}/{p.metadata.name}"
            for p in ns.pods
            if LABEL_APP_NAME in p.metadata.labels
        ]
        if pods:
            out["nodeStatus"].append({"node": ns.node.metadata.name, "pods": pods})
    if explain and engine is not None and engine.filter_rejects is not None:
        out["filterRejects"] = engine.filter_rejects
    return out


# flight-recorder storage cap for explain-mode placement audits: the ring
# holds N traces, and a 50k-pod audit would pin ~50k dicts per trace. A
# typo'd knob degrades to the default with a warning (same contract as
# OPENSIM_FLIGHT_RECORDER_N), never a startup crash.
def _explain_store_n() -> int:
    raw = envknobs.raw("OPENSIM_EXPLAIN_STORE_N")
    try:
        return max(1, int(raw)) if raw else 512
    except ValueError:
        log.warning("ignoring unparseable OPENSIM_EXPLAIN_STORE_N=%r (using 512)", raw)
        return 512


_EXPLAIN_STORE_N = _explain_store_n()


def _placements_payload(rid: str, result: SimulateResult) -> dict:
    """The serialized decision audit stored on the request's trace for
    ``GET /api/debug/placements/<request-id>``: unschedulable records first
    (they are what the endpoint exists for), scheduled records filling the
    remaining cap."""
    engine = result.engine
    explanations = engine.explanations or []
    ranked = sorted(explanations, key=lambda e: e.status == "scheduled")
    kept = ranked[:_EXPLAIN_STORE_N]
    return {
        "request_id": rid,
        "engine": engine.describe(),
        "filter_rejects": engine.filter_rejects or {},
        "pods_total": len(explanations),
        "truncated": max(0, len(explanations) - len(kept)),
        "explanations": [e.to_dict() for e in kept],
    }


class _BatchUnroutable(Exception):
    """Internal: the drained batch cannot run as one shared-prep batched
    schedule (empty base prep, delta re-encode declined) — the group
    degrades to solo execution, it does not fail."""


class _BatchState:
    """In-flight batch handed between the pipeline stages (prep →
    dispatch → decode). Everything the engine stage touches lives in
    ``derived``/``items`` — derived prep arrays that are immutable after
    the prep stage releases the base-entry lock (generation swaps build
    NEW cache entries, prepcache.twin_pod_delta)."""

    __slots__ = (
        "tickets", "base", "derived", "items", "stale",
        "prep_s", "dispatch", "dispatch_s",
    )

    def __init__(self, tickets, base, derived, items, stale, prep_s):
        self.tickets = tickets
        self.base = base
        self.derived = derived
        self.items = items
        self.stale = stale
        self.prep_s = prep_s
        self.dispatch = None
        self.dispatch_s = 0.0


class SimonServer:
    def __init__(
        self,
        kubeconfig: str = "",
        master: str = "",
        base_cluster: Optional[ResourceTypes] = None,
        snapshot_ttl_s: float = 30.0,
        prep_cache: Optional["PrepareCache"] = None,  # False disables
        watch=None,
        admission=None,
        capacity=None,
        journal=None,
    ):
        self.kubeconfig = kubeconfig
        self.master = master
        self.base_cluster = base_cluster
        # live twin (server/watch.py, ISSUE 6): when a WatchSupervisor is
        # attached AND synced, requests serve from its event-maintained twin
        # (tagged stale while degraded); until then — and whenever watch
        # mode is off or its bootstrap keeps failing — the polling snapshot
        # below is the graceful fallback, so watch mode has no regression
        # path
        self.watch = watch
        # live-cluster snapshots are cached between requests (the reference
        # serves every request from its always-warm informer cache,
        # pkg/server/server.go:97-137, instead of re-listing the cluster);
        # snapshot_ttl_s bounds staleness, ≤0 disables caching
        self.snapshot_ttl_s = snapshot_ttl_s
        self._snapshot: Optional[ResourceTypes] = None
        self._snapshot_at = 0.0
        self._snapshot_fp: Optional[str] = None
        # polling-snapshot state is mutated from pool-worker AND dispatcher
        # threads under the admission path (the endpoint TryLocks that used
        # to serialize it only guard the OPENSIM_ADMISSION=off path) — an
        # RLock keeps (snapshot, fingerprint) pairs coherent and collapses
        # concurrent refreshes into one apiserver fetch
        self._snapshot_lock = threading.RLock()
        # degradation state: when the apiserver stays down through every
        # retry, requests are served from the last good snapshot and tagged
        # with an X-Simon-Snapshot: stale response header
        self.snapshot_stale = False
        self._snapshot_fetched_at = 0.0
        # encode cache (incremental prepare): the snapshot's expanded+encoded
        # cluster is cached across requests keyed by content fingerprint, so
        # a request pays O(its own app) host work, not O(cluster). Opt out
        # with OPENSIM_PREP_CACHE=0 (restores per-request full prepare).
        if prep_cache is None and envknobs.raw("OPENSIM_PREP_CACHE", "1") != "0":
            from ..engine.prepcache import PrepareCache

            prep_cache = PrepareCache()
        self.prep_cache = prep_cache if prep_cache is not False else None
        # concurrent serving core (ISSUE 8, server/admission.py): admission
        # queue + cross-request batching + bounded worker pool. ``None``
        # defers to OPENSIM_ADMISSION (default on); ``False`` restores the
        # single-flight TryLock path; an AdmissionController instance is
        # used as-is (tests inject tiny windows/bounds).
        from . import admission as admission_mod

        if admission is None:
            admission = admission_mod.admission_enabled()
        if admission is True:
            admission = admission_mod.AdmissionController(
                solo_fn=self._admitted_solo, batch_fn=self._admitted_batch,
                # staged executors (ISSUE 16): when OPENSIM_PIPELINE=on the
                # controller runs these as a prep/dispatch/decode pipeline,
                # overlapping batch k+1's host prep with batch k's engine
                # dispatch; batch_fn above remains the serial fallback
                prep_fn=self._batch_prep, dispatch_fn=self._batch_dispatch,
                decode_fn=self._batch_decode,
            )
        self.admission = admission or None
        # serializes headroom probes (they are expensive scans) and guards
        # the published-generation watermark below
        self._headroom_lock = threading.Lock()  # lockwatch: hold-exempt — probes span engine scans by design
        self._headroom_pub_gen = -1
        # capacity observatory (ISSUE 9, obs/capacity.py): always on —
        # ``None`` builds the default engine, ``False`` disables. With a
        # live twin the watch supervisor bootstraps and event-feeds it; on
        # the polling/custom-cluster paths /api/cluster/report bootstraps
        # it per snapshot key instead.
        if capacity is None:
            from ..obs.capacity import CapacityEngine

            capacity = CapacityEngine()
        self.capacity = capacity or None
        if self.watch is not None and self.capacity is not None:
            self.watch.capacity = self.capacity
        # watch-event journal (ISSUE 11, server/journal.py): attached to the
        # watch supervisor, which restores the twin from its newest
        # checkpoint + suffix replay at start() and records every accepted
        # event after — crash-safe instead of merely self-healing. Kept on
        # the server too for /metrics and the shutdown flush.
        self.journal = journal
        if journal is not None and self.watch is not None:
            self.watch.attach_journal(journal)
        self._headroom_key: Optional[str] = None
        # campaign engine (ISSUE 13): one campaign at a time PER SERVER —
        # each builds its own prep lineage; an instance lock keeps
        # unrelated servers (tests, smokes) from serializing each other
        self._campaign_lock = threading.Lock()  # lockwatch: hold-exempt — a campaign spans many engine scans by design
        # memory observatory (ISSUE 12, obs/footprint.py): arena/cache
        # footprint accounting + RSS/device watermarks over the structures
        # THIS server owns. Always on — every view is computed on demand;
        # only serve() starts the low-rate watermark ticker.
        from ..obs.footprint import MemoryObservatory

        self.memory = MemoryObservatory(
            prep_cache=self.prep_cache,
            timeline=self.capacity.timeline if self.capacity is not None else None,
            journal=journal,
        )
        # time-series ring + SLO engine (ISSUE 20, obs/timeseries.py /
        # obs/slo.py): like the memory ticker, only serve() starts them —
        # tests construct SimonServer freely without a sampler thread
        self.timeseries = None
        self.slo = None
        self._ts_sampler = None

    def metrics_text(self) -> str:
        """THE /metrics body (handler + time-series sampler share it):
        the request-layer families plus, when the ring is running, its
        own telemetry and the SLO burn-rate gauges."""
        text = METRICS.render(
            prep_cache=self.prep_cache, watch=self.watch,
            admission=self.admission, capacity=self.capacity,
            journal=self.journal, memory=self.memory,
        )
        extra: List[str] = []
        if self.timeseries is not None:
            extra += self.timeseries.metrics_lines()
        if self.slo is not None:
            extra += self.slo.metrics_lines()
        return text + ("\n".join(extra) + "\n" if extra else "")

    def start_timeseries(self) -> None:
        """Boot the on-disk time-series ring, the self-scrape sampler and
        the SLO engine (idempotent; serve() calls this)."""
        from ..obs.slo import SLOEngine
        from ..obs.timeseries import TimeSeriesRing, TimeSeriesSampler

        if self.timeseries is not None:
            return
        ts_dir = str(envknobs.value("OPENSIM_TS_DIR") or "") or None
        self.timeseries = TimeSeriesRing(directory=ts_dir)
        self.slo = SLOEngine(self.timeseries)
        self._ts_sampler = TimeSeriesSampler(self.timeseries, self.metrics_text)
        self._ts_sampler.start()

    def _stamp_fleet_trace(self, tr) -> None:
        """Cross-process stitching (ISSUE 20): when serving from a fleet
        twin client, stamp the serving generation and the owner's
        publication span ids onto the request trace. Free with tracing
        off (``tr is None``) and on non-fleet servers (no ``stitch_info``
        on the watch object) — the fast path is two attribute reads."""
        if tr is None:
            return
        stitch = getattr(self.watch, "stitch_info", None)
        if stitch is None:
            return
        try:
            gen, pub = stitch()
        except Exception as e:  # a torn reader mid-swap must not fail the request
            log.debug("fleet stitch skipped: %s: %s", type(e).__name__, e)
            return
        if gen is None:
            return
        tr.serving_generation = gen  # the flight recorder keys the graft on this
        attrs = {"serving_generation": gen}
        if isinstance(pub, dict):
            if pub.get("span"):
                attrs["fleet_publication"] = pub["span"]
            events = [e[0] for e in pub.get("events") or []]
            if events:
                # comma-joined, not a list: span attrs are primitives so
                # they survive the tree's JSON export verbatim
                attrs["fleet_events"] = ",".join(events)
        tr.root.set(**attrs)

    def close(self) -> None:
        """Graceful teardown (docs/serving.md "Shutting down"): stop the
        admission dispatcher + worker pool (the in-flight batch completes,
        queued tickets shed typed 503 ``shutting_down``), then flush, fsync
        and close the journal so the on-disk history is complete up to the
        last accepted event. Idempotent."""
        if self.admission is not None:
            self.admission.stop()
        if self._ts_sampler is not None:
            self._ts_sampler.stop()
        if self.timeseries is not None:
            self.timeseries.close()
        if self.journal is not None:
            self.journal.close()
        self.memory.stop()

    def _twin_snapshot(self) -> Optional[tuple]:
        """(cluster, cache key) from the synced live twin, or None when the
        polling path must serve (no watch, or not yet synced). Tags the
        request stale when the twin is degraded/resyncing."""
        if self.watch is None:
            return None
        check_deadline("snapshot")
        with tracing.span("snapshot", source="twin") as sp:
            got = self.watch.serving_snapshot()
            if got is None:
                sp.set(synced=False)
                return None
            cluster, key, stale = got
            sp.set(key=key, stale=stale, state=self.watch.state())
            _mark_request_snapshot(stale)
            if stale:
                METRICS.bump("snapshot_stale_served")
        return cluster, key

    def current_cluster(self) -> ResourceTypes:
        if self.base_cluster is not None:
            return self.base_cluster
        got = self._twin_snapshot()
        if got is not None:
            import copy as _copy

            # the legacy (cache-off) path mutates the cluster in place —
            # the twin's objects must stay pristine
            return _copy.deepcopy(got[0])
        if self.kubeconfig:
            import copy as _copy

            self._refresh_snapshot()
            # hand each request its own copy: simulate() mutates pods/nodes
            # in place (bind writes nodeName/phase/annotations), and the
            # cached snapshot must stay pristine across requests
            return _copy.deepcopy(self._snapshot)
        return ResourceTypes()

    def _refresh_snapshot(self) -> None:
        with self._snapshot_lock:
            self._refresh_snapshot_locked()

    def _refresh_snapshot_locked(self) -> None:
        import time as _time

        now = _time.monotonic()
        if self._snapshot is not None and not (
            self.snapshot_ttl_s <= 0 or now - self._snapshot_at > self.snapshot_ttl_s
        ):
            # within the TTL window after a degrade the cached snapshot is
            # still the stale one: this request must be tagged too
            _mark_request_snapshot(self.snapshot_stale)
            return
        check_deadline("snapshot")
        attempts, base_delay = snapshot_retry_policy()

        def _fetch() -> ResourceTypes:
            faults.fault_point("snapshot.http")
            return cluster_from_kubeconfig(self.kubeconfig, self.master)

        def _note_retry(attempt: int, exc: BaseException, delay: float) -> None:
            # the trace event comes from retry_call itself (trace_name below)
            METRICS.bump("snapshot_retries")
            log.warning(
                "snapshot fetch attempt %d failed (%s: %s); retrying in %.3fs",
                attempt + 1, type(exc).__name__, exc, delay,
            )

        with tracing.span("snapshot") as snap_span:
            try:
                # the ONE retry layer for the snapshot fetch (the per-endpoint
                # code raises typed single-attempt failures). Only the transient
                # class retries — a missing kubeconfig or auth misconfiguration
                # (plain OSError/RuntimeError) will not heal and surfaces now.
                self._snapshot = retry_call(
                    _fetch,
                    attempts=attempts,
                    base_delay=base_delay,
                    retry_on=(SnapshotFetchError, TimeoutError),
                    on_retry=_note_retry,
                    trace_name="snapshot.retry",
                )
            except (SnapshotFetchError, TimeoutError) as e:
                if self._snapshot is not None:
                    # degrade: serve the last good snapshot, tagged stale, and
                    # re-arm the TTL so a down apiserver is probed once per TTL
                    # window instead of hammered on every request
                    self.snapshot_stale = True
                    _mark_request_snapshot(True)
                    self._snapshot_at = now
                    METRICS.bump("snapshot_stale_served")
                    snap_span.mark(
                        "demoted",
                        reason="stale snapshot served",
                        age_s=round(now - self._snapshot_fetched_at, 3),
                        error=f"{type(e).__name__}: {e}",
                    )
                    log.warning(
                        "snapshot refresh failed after %d attempt(s) (%s: %s); "
                        "serving stale snapshot (age %.1fs)",
                        attempts, type(e).__name__, e, now - self._snapshot_fetched_at,
                    )
                    return
                raise SnapshotUnavailable(
                    f"cluster snapshot unavailable after {attempts} attempt(s): {e}"
                ) from e
        self._snapshot_at = now
        self._snapshot_fetched_at = now
        self.snapshot_stale = False
        _mark_request_snapshot(False)
        self._snapshot_fp = None  # re-fingerprint lazily

    def _snapshot_for_cache(self) -> tuple:
        """(cluster, content fingerprint) for the encode-cache path — no
        defensive deepcopy: the cached Prepared owns sanitized pod copies
        and its bind state is restored after every use, so the snapshot
        objects are never mutated. A fingerprint change (snapshot refresh
        picked up cluster changes) invalidates the stale entries."""
        from ..engine.prepcache import fingerprint_cluster

        if self.base_cluster is not None:
            with self._snapshot_lock:
                if self._snapshot_fp is None:
                    self._snapshot_fp = fingerprint_cluster(self.base_cluster)
                return self.base_cluster, self._snapshot_fp
        got = self._twin_snapshot()
        if got is not None:
            # generation-keyed, not content-fingerprinted: every applied
            # event bumps the twin's generation, and the watch supervisor —
            # not this request path — owns base-entry invalidation (it
            # replaces the base by O(changes) delta instead)
            return got
        if self.kubeconfig:
            # fetch + fingerprint + invalidation under ONE lock: a
            # concurrent refresh swapping self._snapshot between the two
            # reads would cache a prepare under the wrong fingerprint
            with self._snapshot_lock:
                old_fp = self._snapshot_fp
                self._refresh_snapshot_locked()
                if self._snapshot_fp is None:
                    self._snapshot_fp = fingerprint_cluster(self._snapshot)
                    if old_fp is not None and old_fp != self._snapshot_fp:
                        self.prep_cache.invalidate(old_fp)
                return self._snapshot, self._snapshot_fp
        return ResourceTypes(), "empty"

    # -- capacity observatory (ISSUE 9) -------------------------------------

    def _observed_cluster(self) -> tuple:
        """(cluster, stable key) for the capacity view — the cache path's
        (snapshot, fingerprint-or-generation) pair, or a content
        fingerprint on the legacy cache-off path."""
        if self.prep_cache is not None:
            return self._snapshot_for_cache()
        from ..engine.prepcache import fingerprint_cluster

        cluster = self.current_cluster()
        return cluster, fingerprint_cluster(cluster)

    def _probe_headroom(self, cluster: ResourceTypes, key: str) -> dict:
        """Headroom per registered profile, probed through the warm base
        prep (one delta re-encode + batched mask-prefix scans — zero full
        prepares once the base exists; creating a missing base IS the
        serving path's bootstrap prepare). Keyed by the snapshot key: one
        probe set per observed cluster state."""
        from ..engine import prepcache
        from ..obs import capacity as capacity_mod

        if self.capacity is None:
            return {}
        # serialized: concurrent reports must not probe the same state
        # twice, and a slow probe for an OLDER snapshot must not overwrite
        # a newer probe's published gauges (the generation watermark below)
        with self._headroom_lock:
            if self._headroom_key == key:
                return self.capacity.headroom()
            gen0 = self.capacity.generation
            profiles = capacity_mod.headroom_profiles()
            base = None
            if self.prep_cache is not None:
                from ..engine.simulator import prepare

                base_key = f"{key}|base"
                base = self.prep_cache.get(base_key)
                if base is None:
                    watch = prepcache.watch_snapshot(cluster, [])  # before the build
                    base = self.prep_cache.put(
                        base_key,
                        prepcache.CacheEntry(base_key, prepare(cluster, []), watch=watch),
                    )
                self.prep_cache.check_fresh(base)
                if base.prep is None:
                    base = None  # no schedulable pods cached; probe prepares fresh
            out = {}
            for profile in profiles:
                out[profile.name] = capacity_mod.headroom_probe(
                    cluster, profile, base=base,
                    kmax=self.capacity.fit_upper_bound(profile),
                )
            if gen0 >= self._headroom_pub_gen:
                self.capacity.set_headroom(out)
                self._headroom_key = key
                self._headroom_pub_gen = gen0
            return out

    def cluster_report(
        self, extended: Optional[List[str]] = None, probe_headroom: bool = True,
        include_memory: bool = False,
    ) -> dict:
        """The ``GET /api/cluster/report`` body: the capacity sample plus
        the same table rows the text renderer prints
        (``obs/capacity.build_report`` — one computation path, gated by the
        report-parity test). ``include_memory`` (``?mem=1``) adds the
        memory observatory block — summary plus the SAME rows ``simon top
        --mem`` renders (``obs/footprint.memory_rows``, byte-equal parity
        like every other report table)."""
        from ..obs import capacity as capacity_mod

        if self.capacity is None:
            raise RuntimeError("capacity observatory disabled (capacity=False)")
        cluster, key = self._observed_cluster()
        self.capacity.ensure_bootstrap(cluster, key)
        if probe_headroom:
            self._probe_headroom(cluster, key)
        state = self.watch.state() if self.watch is not None else "polling"
        report = capacity_mod.build_report(
            self.capacity, cluster, extended_resources=extended, state=state
        )
        if include_memory:
            from ..obs.footprint import memory_rows

            summary = self.memory.summary()
            report["memory"] = {"summary": summary, "rows": memory_rows(summary)}
        return report

    # -- campaign engine (ISSUE 13) -----------------------------------------

    def run_campaign(self, payload: dict, deadline: Optional[Deadline] = None) -> tuple:
        """``POST /api/campaign`` (docs/campaigns.md): evaluate a
        lifecycle campaign — the request body's ``steps`` list, the same
        shape as a campaign file's ``spec.steps`` — against the observed
        cluster (the live twin when synced, the polling snapshot
        otherwise). Campaigns are serialized: each builds its own prep
        lineage (exactly one full prepare) and never mutates the snapshot
        objects. Returns ``(status, body)``."""
        from ..planner import campaign as campaign_mod

        try:
            with campaign_mod.remote_spec_context():
                steps = campaign_mod.parse_steps(payload.get("steps"))
        except campaign_mod.CampaignError as e:
            return 400, {"error": str(e), "step": e.step, "field": e.field}
        name = str(payload.get("name") or "campaign")
        mode = payload.get("mode") or None
        try:
            with deadline_scope(deadline):
                with self._campaign_lock:
                    with tracing.span("campaign", steps=len(steps)):
                        cluster, _key = self._observed_cluster()
                        # remote spec: step run() must not dereference
                        # server-side paths either (deploy _load at run
                        # time, from-journal reads) — the same gate holds
                        # for the whole evaluation
                        with campaign_mod.remote_spec_context():
                            result = campaign_mod.run_campaign(
                                cluster, steps, mode=mode, name=name
                            )
            return 200, result.to_dict()
        except DeadlineExceeded as e:
            return 504, {"error": str(e), "phase": e.phase, "retryable": True}
        except SnapshotUnavailable as e:
            return 503, {"error": str(e), "retryable": True}
        except campaign_mod.CampaignError as e:
            return 400, {"error": str(e), "step": e.step, "field": e.field}

    # -- handlers -----------------------------------------------------------

    def _simulate_request(self, kind: str, payload: dict,
                          explain: bool = False) -> SimulateResult:
        """`_simulate_request_once` plus stale-entry recovery: a
        ``StaleFingerprintError`` hit means a fingerprinted object was
        ``touch()``ed behind the cache's back — ``PrepareCache.check_fresh``
        already evicted everything the object taints, so ONE internal retry
        re-prepares from the live objects. A REST client has no way to call
        ``invalidate(obj)``; without this the client would eat a 500 for a
        purely server-side cache condition. A second stale failure in the
        same request propagates (typed 500) rather than looping."""
        from ..engine.prepcache import StaleFingerprintError

        try:
            return self._simulate_request_once(kind, payload, explain=explain)
        except StaleFingerprintError as e:
            METRICS.bump("stale_prep_retries")
            log.warning("stale prepare-cache entry (%s); retrying once after eviction", e)
            return self._simulate_request_once(kind, payload, explain=explain)

    def _simulate_request_once(self, kind: str, payload: dict,
                               explain: bool = False) -> SimulateResult:
        """Shared deploy/scale simulation through the encode cache:

        1. identical repeated request → full-key hit: restore + simulate,
           zero re-encoding;
        2. known snapshot → base-entry hit: delta re-encode (append the
           request's app pods; extend nodes from the request's templates;
           flip valid-mask bits for scaled-away pods);
        3. cold → one full prepare of the snapshot, cached for 1+2.
        """
        from ..engine import prepcache
        from ..utils.trace import PREP_STATS

        with tracing.span("http.parse"):  # the JSON body to node and app objects
            new_nodes = _decode_new_nodes(payload)
            app = _decode_app(payload)
        apps = [AppResource(kind, app)]
        scaled: set = set()
        if kind == "scale":
            scaled = {
                (w.kind, w.metadata.namespace, w.metadata.name)
                for w in app.deployments + app.daemon_sets + app.stateful_sets
            }

        if self.prep_cache is None:
            # legacy path: per-request snapshot copy + full prepare
            cluster = _with_new_nodes(self.current_cluster(), new_nodes)
            if scaled:
                cluster.pods = [p for p in cluster.pods if not _owned_by(p, scaled)]
            return simulate(cluster, apps, explain=explain)

        cluster0, fp = self._snapshot_for_cache()
        cluster = _with_new_nodes(cluster0, new_nodes)

        def _filtered() -> ResourceTypes:
            # only the cold full-prepare fallbacks need the scaled pods
            # actually removed from the input; the cached paths express the
            # removal as a drop mask over the prepared stream instead, so
            # the O(all pods) owner scan is skipped on the hot path
            if not scaled:
                return cluster
            out = _with_new_nodes(cluster0, new_nodes)
            out.pods = [p for p in cluster0.pods if not _owned_by(p, scaled)]
            return out

        payload_fp = hashlib.blake2b(
            json.dumps(payload, sort_keys=True, default=str).encode(), digest_size=16
        ).hexdigest()
        full_key = f"{fp}|{kind}|{payload_fp}"
        # full-key reuse only without newnodes: fake-node names are freshly
        # randomized per request, and a cached derived prep would replay the
        # first request's names into later responses
        entry = self.prep_cache.get(full_key) if not new_nodes else None
        if entry is not None and entry.prep is not None:
            self.prep_cache.check_fresh(entry)
            with entry.lock:
                with PREP_STATS.timed("hit"):
                    entry.restore()
                try:
                    return simulate(
                        cluster, apps, prep=entry.prep,
                        drop_pods=getattr(entry, "drop_mask", None),
                        explain=explain,
                    )
                finally:
                    entry.restore()

        base_key = f"{fp}|base"
        base = self.prep_cache.get(base_key)
        if base is None:
            from ..engine.simulator import prepare

            watch = prepcache.watch_snapshot(cluster0, [])  # before the build
            base = self.prep_cache.put(
                base_key,
                prepcache.CacheEntry(base_key, prepare(cluster0, []), watch=watch),
            )
        if base.prep is None:
            # snapshot with no schedulable pods: nothing worth caching
            return simulate(_filtered(), apps, explain=explain)
        self.prep_cache.check_fresh(base)
        with base.lock:
            base.restore()
            base_prep = base.prep
            if new_nodes:
                base_prep = prepcache.extend_with_nodes(
                    base_prep, new_nodes, cluster0, [], base_entry=base
                )
            derived = (
                prepcache.derive_with_apps(
                    base_prep, cluster, apps,
                    base_entry=base if not new_nodes else None,
                )
                if base_prep is not None
                else None
            )
            if derived is None:
                return simulate(_filtered(), apps, explain=explain)
            # the simulate drop mask composes the scale request's removals
            # with the live twin's event-deleted pods (CacheEntry.base_drop:
            # watch DELETEDs stay in the cached stream, mask-flipped)
            drop = prepcache.union_drop_masks(
                base.base_drop,
                prepcache.drop_mask_for_scaled(derived, _owned_by, scaled)
                if scaled
                else None,
                len(derived.ordered),
            )
            entry = prepcache.CacheEntry(full_key, derived, base=base)
            entry.drop_mask = drop
            if not new_nodes:
                self.prep_cache.put(full_key, entry)
            try:
                return simulate(cluster, apps, prep=derived, drop_pods=drop,
                                explain=explain)
            finally:
                entry.restore()

    # -- admission-path executors (ISSUE 8) --------------------------------
    #
    # Both run on dispatcher/worker-pool threads, never on the HTTP handler
    # thread: they communicate exclusively through the ticket (result or
    # error + the stale flag observed on the executing thread, since
    # _REQUEST_STATE is thread-local and would not survive the handoff).

    def _admitted_solo(self, ticket) -> None:
        """Full-fidelity solo execution: the exact `_simulate_request` path
        (engine ladder, prep cache, one stale retry), with the request's
        deadline and trace installed on this worker thread so phase spans
        and 504s land exactly as on the legacy path."""
        _mark_request_snapshot(False)
        _REQUEST_STATE.request_id = ticket.request_id
        try:
            with deadline_scope(ticket.deadline), tracing.trace_scope(ticket.trace):
                result = self._simulate_request(
                    ticket.kind, ticket.payload, explain=ticket.explain
                )
            ticket.resolve(result=result, stale=request_served_stale())
        except BaseException as e:
            # transported: the REST thread re-raises this into its typed
            # failure ladder (504/503/500) and logs it there
            log.debug("solo execution failed: %s: %s", type(e).__name__, e)
            ticket.resolve(error=e, stale=request_served_stale())

    def _admitted_batch(self, tickets) -> None:
        """Batched execution with the solo path's stale-entry contract (one
        internal retry after eviction) and a solo fallback when the stream
        cannot batch (empty base prep, delta declined)."""
        from ..engine.prepcache import StaleFingerprintError

        try:
            try:
                self._run_batch_once(tickets)
            except StaleFingerprintError as e:
                METRICS.bump("stale_prep_retries")
                log.warning(
                    "stale prepare-cache entry in batch (%s); retrying once "
                    "after eviction", e,
                )
                self._run_batch_once(tickets)
        except _BatchUnroutable as e:
            # the stream cannot batch (no schedulable base pods, delta
            # declined): degrade to full-fidelity solo runs, never an error
            log.info("batch of %d unroutable (%s); running solo", len(tickets), e)
            for t in tickets:
                self._admitted_solo(t)
        except BaseException as e:
            # one failure fails the whole group with the same typed error a
            # solo run would surface — never a partial result
            log.warning(
                "batch of %d failed (%s: %s); failing the group",
                len(tickets), type(e).__name__, e,
            )
            for t in tickets:
                if not t.done.is_set():
                    t.resolve(error=e)

    def _run_batch_once(self, tickets) -> None:
        """Fold N compatible requests onto one shared warm prep and run ONE
        request-axis batched schedule (engine/reqbatch.py), demultiplexing
        a per-request SimulateResult that is bit-identical to a solo run
        (gated by tests/test_admission.py).

        Composed from the same three stage executors the pipelined path
        runs (prep → dispatch → decode), so serial and pipelined modes
        share ONE implementation and cannot drift."""
        state = self._batch_prep_once(tickets)
        if state is None:
            return  # every rider already resolved (payload decode failures)
        self._batch_decode(self._batch_dispatch(state))

    def _batch_prep_once(self, tickets) -> Optional[_BatchState]:
        """Pipeline stage 1 — host prep, under the base entry's lock:
        snapshot/fingerprint, per-rider payload decode, shared
        derive-with-slices, per-rider drop masks. Releases the lock before
        returning: the derived prep it hands the dispatch stage is
        immutable from here on (twin generation swaps build NEW entries —
        prepcache.twin_pod_delta — so a swap mid-flight never mutates
        these arrays)."""
        import time as _time

        import numpy as np

        from ..engine import prepcache, reqbatch
        from ..engine.simulator import prepare

        _mark_request_snapshot(False)
        t0 = _time.monotonic()
        cluster0, fp = self._snapshot_for_cache()
        stale = request_served_stale()
        apps: List[AppResource] = []
        scaled_sets: List[set] = []
        kept: List = []
        for t in tickets:
            # per-ticket decode: ONE malformed payload must fail only its
            # own request (typed 500), never poison the whole batch
            try:
                app = _decode_app(t.payload)
            except Exception as e:
                log.warning(
                    "batch rider payload failed to decode (%s: %s)",
                    type(e).__name__, e,
                )
                t.resolve(error=e, stale=stale)
                continue
            kept.append(t)
            apps.append(AppResource(t.kind, app))
            scaled_sets.append(
                {
                    (w.kind, w.metadata.namespace, w.metadata.name)
                    for w in app.deployments + app.daemon_sets + app.stateful_sets
                }
                if t.kind == "scale"
                else set()
            )
        tickets = kept
        if not tickets:
            return None
        base_key = f"{fp}|base"
        base = self.prep_cache.get(base_key)
        if base is None:
            watch = prepcache.watch_snapshot(cluster0, [])  # before the build
            base = self.prep_cache.put(
                base_key,
                prepcache.CacheEntry(base_key, prepare(cluster0, []), watch=watch),
            )
        self.prep_cache.check_fresh(base)
        with base.lock:
            base.restore()
            if base.prep is not None:
                got = prepcache.derive_with_app_slices(
                    base.prep, cluster0, apps, base_entry=base
                )
                if got is None:
                    raise _BatchUnroutable("delta re-encode declined the stream")
                derived, slices = got
            else:
                # snapshot with no schedulable pods: nothing cached to
                # derive from — one fresh prepare of ALL the batch's apps
                # still beats N solo full prepares (prepare() records the
                # per-app stream slices for exactly this demultiplexing)
                derived = prepare(cluster0, apps)
                if derived is None or derived.app_slices is None:
                    raise _BatchUnroutable("batch expanded to an empty stream")
                slices = derived.app_slices
            items = []
            for s in range(len(tickets)):
                drop = prepcache.union_drop_masks(
                    base.base_drop,
                    prepcache.drop_mask_for_scaled(derived, _owned_by, scaled_sets[s])
                    if scaled_sets[s]
                    else None,
                    len(derived.ordered),
                )
                drops = set(int(i) for i in np.nonzero(drop)[0]) if drop is not None else set()
                items.append(
                    reqbatch.BatchItem(
                        cluster=cluster0, apps=[apps[s]],
                        lo=slices[s][0], hi=slices[s][1], drops=drops,
                        # batched explain (ISSUE 15 satellite): the rider's
                        # audit is built from its own count_all fail rows
                        # over the shared derive
                        explain=tickets[s].explain,
                        # pre-dispatch shedding (ISSUE 9 satellite): a rider
                        # expired before the dispatch schedules nothing
                        deadline=tickets[s].deadline,
                    )
                )
        prep_s = _time.monotonic() - t0
        return _BatchState(tickets, base, derived, items, stale, prep_s)

    def _batch_prep(self, tickets) -> Optional[_BatchState]:
        """The pipelined controller's ``prep_fn``: `_batch_prep_once` with
        the serial path's stale-entry contract (one internal retry after
        eviction — a twin generation swap mid-prep lands here) and the
        `_BatchUnroutable` → None degradation (the controller pools the
        still-unresolved riders to full-fidelity solo runs)."""
        from ..engine.prepcache import StaleFingerprintError

        try:
            try:
                return self._batch_prep_once(tickets)
            except StaleFingerprintError as e:
                METRICS.bump("stale_prep_retries")
                log.warning(
                    "stale prepare-cache entry in batch (%s); retrying once "
                    "after eviction", e,
                )
                return self._batch_prep_once(tickets)
        except _BatchUnroutable as e:
            log.info(
                "batch of %d unroutable (%s); degrading to solo", len(tickets), e
            )
            return None

    def _batch_dispatch(self, state: _BatchState) -> _BatchState:
        """Pipeline stage 2 — the engine dispatch. Runs WITHOUT the base
        entry's lock: it touches only the stage-1 derived prep (immutable)
        and device buffers, and the engines release the GIL, so the NEXT
        batch's host prep overlaps this wall-clock (the tentpole win)."""
        import time as _time

        from ..engine import reqbatch

        t0 = _time.monotonic()
        state.dispatch = reqbatch.dispatch_request_batch(state.derived, state.items)
        state.dispatch_s = _time.monotonic() - t0
        return state

    def _batch_decode(self, state: _BatchState) -> None:
        """Pipeline stage 3 — demultiplex per-rider results under the base
        entry's lock (decode mutates the shared pod objects' bind state;
        the restore discipline hands the next holder pristine state)."""
        import time as _time

        from ..engine import reqbatch

        tickets, base, stale = state.tickets, state.base, state.stale
        t1 = _time.monotonic()
        with base.lock:
            base.restore()
            try:
                results = reqbatch.decode_request_batch(
                    state.derived, state.items, state.dispatch
                )
            finally:
                base.restore()
        run_s = state.dispatch_s + (_time.monotonic() - t1)
        for t, res in zip(tickets, results):
            if isinstance(res, BaseException):
                # a rider shed from the batch (deadline expired before
                # the dispatch): transported like any executor error — the REST
                # thread re-raises into its typed ladder (504 phase=schedule)
                t.resolve(error=res, stale=stale)
                continue
            tr = t.trace
            if tr is not None:
                # synthetic phase spans: the shared batch work, attributed
                # to every rider so per-phase histograms stay live for
                # batched traffic (child_from_seconds exists for this)
                tr.root.child_from_seconds(
                    "prepare", state.prep_s, batched=True, batch=len(tickets)
                )
                tr.root.child_from_seconds(
                    "schedule", run_s, batched=True, batch=len(tickets)
                )
            t.resolve(result=res, stale=stale, batch_size=len(tickets))

    def _handle_admitted(self, endpoint: str, kind: str, payload: dict,
                         deadline: Optional[Deadline] = None,
                         request_id: Optional[str] = None,
                         explain: bool = False) -> tuple:
        """The admission-path endpoint shell: same typed failure ladder as
        the legacy `_handle`, plus two shed outcomes —

        - 503 + reason=queue_full + ``Retry-After``: the admission queue is
          at its bound (load-shedding, server/admission.py);
        - 504 + phase=queue: the request's deadline expired while queued.

        Every outcome records the REAL elapsed time in the request
        histogram (the ISSUE 8 satellite: rejected traffic must carry its
        actual latency, not a fake 0.0)."""
        import math
        import time

        from . import admission as admission_mod

        rid = tracing.sanitize_request_id(request_id) or tracing.new_request_id()
        _REQUEST_STATE.request_id = rid
        _REQUEST_STATE.extra_headers = {}
        _mark_request_snapshot(False)
        tr = tracing.start_trace(endpoint, request_id=rid)
        t0 = time.monotonic()
        status = "error"
        code, body = 500, {"error": "unhandled"}
        result: Optional[SimulateResult] = None
        ticket = None
        try:
            has_new_nodes = bool(payload.get("newnodes") or payload.get("NewNodes"))
            ticket = admission_mod.Ticket(
                kind=kind, payload=payload, explain=explain, deadline=deadline,
                trace=tr, request_id=rid,
                # with the cache off every request takes the legacy
                # full-prepare path: solo through the pool, never batched
                has_new_nodes=has_new_nodes or self.prep_cache is None,
            )
            self.admission.submit(ticket)
            self.admission.wait(ticket)
            result = ticket.result
            _mark_request_snapshot(ticket.stale)
            status = "ok"
            if result.engine is not None:
                result.engine.request_id = rid
                if tr is not None:
                    tr.root.set(engine=result.engine.describe())
                    if ticket.batch_size:
                        tr.root.set(batch_size=ticket.batch_size)
            # the trace's scope was the worker's: install it here for the
            # answer (the bytes and the socket write follow the root's end)
            with tracing.trace_scope(tr), tracing.span("http.respond"):
                code, body = 200, _response(result, explain=explain)
            if explain and tr is not None and result.engine is not None:
                tr.placements = _placements_payload(rid, result)
        except admission_mod.QueueFull as e:
            status = "shed"
            log.warning("%s shed: %s", endpoint, e)
            _REQUEST_STATE.extra_headers = {
                "Retry-After": str(max(1, int(math.ceil(e.retry_after_s))))
            }
            # reason distinguishes overload (queue_full) from graceful
            # shutdown (shutting_down) — a client should retry the former
            # against this replica and the latter against another
            code, body = 503, {
                "error": str(e),
                "reason": getattr(e, "reason", "queue_full"),
                "retryable": True,
            }
        except DeadlineExceeded as e:
            status = "deadline-exceeded"
            METRICS.bump("request_timeouts")
            log.warning("%s timed out: %s", endpoint, e)
            code, body = 504, {"error": str(e), "phase": e.phase}
        except SnapshotUnavailable as e:
            log.warning("%s snapshot unavailable: %s", endpoint, e)
            code, body = 503, {"error": str(e), "retryable": True}
        except Exception as e:
            log.warning("%s failed: %s: %s", endpoint, type(e).__name__, e)
            code, body = 500, {"error": str(e), "type": type(e).__name__}
        finally:
            seconds = time.monotonic() - t0
            with RECORDER.lock:
                if status == "ok" and result is not None:
                    METRICS.record(endpoint, result)
                RECORDER.observe_request(endpoint, seconds, status=status)
            if tr is not None:
                if ticket is not None and ticket.queue_s:
                    # real time-in-queue on the span tree, where the ticket's
                    # own stamps put it (also histogrammed as
                    # simon_queue_wait_seconds by the controller)
                    tr.root.child_at(
                        "queue", ticket.enqueued, ticket.enqueued + ticket.queue_s
                    )
                self._stamp_fleet_trace(tr)
                tr.finish(status=status, http_status=code)
                FLIGHT_RECORDER.record(tr)
                RECORDER.observe_trace(tr)
        return code, body

    def _handle(self, endpoint: str, kind: str, lock: threading.Lock,
                payload: dict, deadline: Optional[Deadline] = None,
                request_id: Optional[str] = None, explain: bool = False) -> tuple:
        """Shared endpoint shell: single-flight busy rejection, deadline
        scope, request-scoped trace, and the failure-mode ladder
        (docs/resilience.md) — every outcome is a typed JSON body, never a
        hang or a raw traceback:

        - 200: simulation result
        - 503 busy: TryLock rejection (server.go:167,:234)
        - 504 + phase: request deadline exhausted at a phase boundary
        - 503 + retryable: apiserver down through every retry, no snapshot
          to degrade to
        - 500 + type: everything else (engine/encoding failure after the
          fallback ladder is exhausted)

        Observability (ISSUE 5): every request gets an id (the client's
        ``X-Simon-Request-Id`` honored when supplied, generated otherwise —
        read it back via :func:`last_request_id`) and, when tracing is
        enabled, a span tree recorded into the flight recorder and folded
        into the /metrics latency histograms on the way out.

        With the admission queue enabled (the default — OPENSIM_ADMISSION,
        ISSUE 8), requests route through ``_handle_admitted`` instead:
        cross-request batching + bounded worker pool + load-shedding; this
        single-flight shell remains the ``OPENSIM_ADMISSION=off`` path.
        """
        import time

        if self.admission is not None:
            return self._handle_admitted(
                endpoint, kind, payload, deadline, request_id, explain=explain
            )
        t0 = time.monotonic()
        rid = tracing.sanitize_request_id(request_id) or tracing.new_request_id()
        _REQUEST_STATE.request_id = rid
        if not lock.acquire(blocking=False):
            # rejected traffic must still be visible in the histograms —
            # overload is exactly what a latency dashboard is watching for.
            # Record the REAL elapsed time (ISSUE 8 satellite): a fake 0.0
            # skewed every dashboard's busy-series percentiles.
            RECORDER.observe_request(
                endpoint, time.monotonic() - t0, status="busy"
            )
            return 503, {"error": "the server is busy now, please try again later"}
        _mark_request_snapshot(False)  # until a refresh says otherwise
        tr = tracing.start_trace(endpoint, request_id=rid)
        t0 = time.monotonic()
        status = "error"
        code, body = 500, {"error": "unhandled"}
        result: Optional[SimulateResult] = None
        try:
            with deadline_scope(deadline), tracing.trace_scope(tr):
                result = self._simulate_request(kind, payload, explain=explain)
            status = "ok"
            if result.engine is not None:
                result.engine.request_id = rid
                if tr is not None:
                    tr.root.set(engine=result.engine.describe())
            with tracing.trace_scope(tr), tracing.span("http.respond"):
                code, body = 200, _response(result, explain=explain)
            if explain and tr is not None and result.engine is not None:
                # the decision audit joins the flight recorder: served later
                # at GET /api/debug/placements/<request-id> (serialized and
                # capped — the ctx holding the full Prepared is dropped).
                # engine is None when the snapshot held no schedulable pods
                tr.placements = _placements_payload(rid, result)
        except DeadlineExceeded as e:
            status = "deadline-exceeded"
            METRICS.bump("request_timeouts")
            log.warning("%s timed out: %s", endpoint, e)
            code, body = 504, {"error": str(e), "phase": e.phase}
        except SnapshotUnavailable as e:
            log.warning("%s snapshot unavailable: %s", endpoint, e)
            code, body = 503, {"error": str(e), "retryable": True}
        except Exception as e:  # surface as 500 like gin's error handler
            log.warning("%s failed: %s: %s", endpoint, type(e).__name__, e)
            code, body = 500, {"error": str(e), "type": type(e).__name__}
        finally:
            try:
                seconds = time.monotonic() - t0
                # one recording path for request latency, ONE critical
                # section: the success counters and the histogram land
                # atomically, so a scrape never sees simulations_total
                # bumped with simulate_seconds_total still short a request
                with RECORDER.lock:
                    if status == "ok" and result is not None:
                        METRICS.record(endpoint, result)
                    RECORDER.observe_request(endpoint, seconds, status=status)
                if tr is not None:
                    self._stamp_fleet_trace(tr)
                    tr.finish(status=status, http_status=code)
                    FLIGHT_RECORDER.record(tr)
                    RECORDER.observe_trace(tr)
            finally:
                # the single-flight lock must be released even if telemetry
                # recording throws — a leaked lock would 503 the endpoint
                # until restart
                lock.release()
        return code, body

    def deploy_apps(self, payload: dict, deadline: Optional[Deadline] = None,
                    request_id: Optional[str] = None, explain: bool = False) -> tuple:
        return self._handle("deploy-apps", "deploy", _deploy_lock, payload,
                            deadline, request_id, explain=explain)

    def scale_apps(self, payload: dict, deadline: Optional[Deadline] = None,
                   request_id: Optional[str] = None, explain: bool = False) -> tuple:
        """scale-apps (server.go:233-312): remove the workload's existing
        pods from the cluster snapshot, then re-simulate at the new scale —
        on the cached path the removal is a valid-mask flip over the
        snapshot's cached encoding, not a re-encode."""
        return self._handle("scale-apps", "scale", _scale_lock, payload,
                            deadline, request_id, explain=explain)


def _owned_by(pod, scaled: set) -> bool:
    for ref in pod.metadata.owner_references:
        key = (ref.kind, pod.metadata.namespace, ref.name)
        if key in scaled:
            return True
        # deployment pods are owned via a generated ReplicaSet name prefix
        if ref.kind == "ReplicaSet" and any(
            k == "Deployment" and ns == pod.metadata.namespace and ref.name.startswith(name + "-")
            for k, ns, name in scaled
        ):
            return True
    return False


def _with_new_nodes(cluster: ResourceTypes, nodes: List[Node]) -> ResourceTypes:
    import copy

    out = copy.copy(cluster)
    out.nodes = list(cluster.nodes) + nodes
    return out


def request_deadline(headers) -> Optional[Deadline]:
    """Per-request deadline: the ``X-Simon-Timeout-S`` header wins, else
    ``OPENSIM_REQUEST_TIMEOUT_S`` (unset/0 = no deadline — existing clients
    keep today's unbounded behavior unless they or the operator opt in)."""
    raw = headers.get("X-Simon-Timeout-S") if headers is not None else None
    if raw is None:
        raw = envknobs.raw("OPENSIM_REQUEST_TIMEOUT_S")
    if not raw:
        return None
    try:
        budget = float(raw)
    except ValueError:
        log.warning("ignoring unparseable request timeout %r", raw)
        return None
    return Deadline.after(budget) if budget > 0 else None


def make_handler(server: SimonServer):
    class Handler(BaseHTTPRequestHandler):
        # keep-alive (ISSUE 8): every response carries Content-Length, so
        # HTTP/1.1 persistent connections are safe — a closed-loop client
        # pays one TCP connect + one handler thread per WORKER instead of
        # per request (the per-request connection churn dominated serving
        # latency under load)
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):  # quiet by default
            pass

        def _begin_request(self) -> None:
            # duration is request-scoped, stamped at dispatch: measuring
            # from connection setup() would bill keep-alive idle and slow
            # client uploads to the server. EVERY request — GETs and 4xx
            # paths included — gets an id here (the client's
            # X-Simon-Request-Id honored, generated otherwise), so an
            # access-log line always joins against the flight recorder and
            # can never inherit the id of an earlier request served on the
            # same thread (ISSUE 7 satellite).
            import time

            self._t0 = time.monotonic()
            _REQUEST_STATE.request_id = (
                tracing.sanitize_request_id(self.headers.get("X-Simon-Request-Id"))
                or tracing.new_request_id()
            )
            _REQUEST_STATE.extra_headers = {}

        def _access_log(self, code: int) -> None:
            """Opt-in structured access logging (``OPENSIM_ACCESS_LOG=1``):
            one JSON object per request on the ``opensim_tpu.access``
            logger — request id, endpoint, status, duration — keeping the
            quiet-by-default behavior when unset (ISSUE 5 satellite)."""
            if envknobs.raw("OPENSIM_ACCESS_LOG") != "1":
                return
            import time

            _ACCESS_LOG.info(
                "%s",
                json.dumps(
                    {
                        "ts": round(time.time(), 3),
                        "request_id": last_request_id(),
                        "method": self.command,
                        "endpoint": self.path,
                        "status": code,
                        "duration_s": round(
                            time.monotonic() - getattr(self, "_t0", time.monotonic()), 6
                        ),
                        "remote": self.client_address[0],
                    },
                    sort_keys=True,
                ),
            )

        def _send(self, code: int, body: dict, extra_headers: Optional[dict] = None) -> None:
            data = json.dumps(body).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            # every response names its request id — GETs and error paths
            # included — so any response joins the access log + recorder
            if last_request_id() and "X-Simon-Request-Id" not in (extra_headers or {}):
                self.send_header("X-Simon-Request-Id", last_request_id())
            for k, v in (extra_headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(data)
            self._access_log(code)

        def do_GET(self):
            self._begin_request()
            if self.path == "/healthz":
                self._send(200, {"status": "ok"})
            elif self.path == "/metrics":
                data = server.metrics_text().encode()
                self.send_response(200)
                self.send_header("Content-Type", "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(data)))
                if last_request_id():
                    self.send_header("X-Simon-Request-Id", last_request_id())
                self.end_headers()
                self.wfile.write(data)
                self._access_log(200)
            elif self.path.split("?", 1)[0] == "/api/cluster/report":
                # capacity observatory (ISSUE 9, docs/observability.md):
                # the live capacity report — SAME rows as the text renderer
                from urllib.parse import parse_qs

                q = parse_qs(self.path.partition("?")[2])
                extended = [
                    e for e in q.get("extended", [""])[-1].split(",") if e
                ]
                probe = q.get("headroom", ["1"])[-1] not in ("0", "false")
                mem = q.get("mem", ["0"])[-1] not in ("", "0", "false")
                try:
                    self._send(
                        200,
                        server.cluster_report(
                            extended=extended, probe_headroom=probe,
                            include_memory=mem,
                        ),
                    )
                except SnapshotUnavailable as e:
                    self._send(503, {"error": str(e), "retryable": True})
                except Exception as e:
                    log.warning(
                        "cluster report failed: %s: %s", type(e).__name__, e
                    )
                    self._send(500, {"error": str(e), "type": type(e).__name__})
            elif self.path.split("?", 1)[0] == "/api/debug/capacity":
                # the capacity timeline ring (obs/timeline.py): trend
                # samples per twin generation for charting
                if server.capacity is None:
                    self._send(404, {"error": "capacity observatory disabled"})
                else:
                    server.capacity.sample()  # fold in the latest generation
                    self._send(
                        200,
                        {
                            "capacity": server.capacity.timeline.capacity,
                            "samples": [
                                s.to_dict()
                                for s in server.capacity.timeline.snapshot()
                            ],
                        },
                    )
            elif self.path.split("?", 1)[0] == "/api/debug/memory":
                # memory observatory (ISSUE 12, docs/observability.md
                # "Memory & profiles"): per-entry arena byte attribution,
                # ring occupancy, RSS/device watermarks. ?fields=0 drops
                # the per-field breakdown for cheap polling.
                from urllib.parse import parse_qs as _parse_qs

                q = _parse_qs(self.path.partition("?")[2])
                fields = q.get("fields", ["1"])[-1] not in ("0", "false")
                try:
                    self._send(200, server.memory.debug_payload(include_fields=fields))
                except Exception as e:
                    log.warning("memory debug failed: %s: %s", type(e).__name__, e)
                    self._send(500, {"error": str(e), "type": type(e).__name__})
            elif self.path.split("?", 1)[0] == "/api/debug/profile":
                # compile telemetry + cumulative phase profiles (ISSUE 12)
                from ..obs import profile as profile_mod

                try:
                    payload = profile_mod.debug_payload()
                    adm = server.admission
                    if adm is not None:
                        # pipelined-admission stage aggregates (ISSUE 16):
                        # the `simon profile` pipeline table reads this
                        payload["pipeline"] = adm.pipeline_snapshot()
                    self._send(200, payload)
                except Exception as e:
                    log.warning("profile debug failed: %s: %s", type(e).__name__, e)
                    self._send(500, {"error": str(e), "type": type(e).__name__})
            elif self.path == "/api/debug/requests":
                # flight recorder (docs/observability.md): newest-first
                # summaries of the last N request traces
                self._send(200, {"requests": FLIGHT_RECORDER.summaries()})
            elif self.path.startswith("/api/debug/requests/"):
                # drop any query string before extracting the id segment
                rid = tracing.sanitize_request_id(
                    self.path.split("?", 1)[0].rsplit("/", 1)[1]
                )
                tr = FLIGHT_RECORDER.get(rid)
                if tr is None:
                    self._send(404, {"error": f"no recorded trace for request id {rid!r}"})
                else:
                    body = tr.tree()
                    # stitched fleet trace (ISSUE 20): graft the owner-side
                    # publication subtree under the worker-side tree
                    gen = getattr(tr, "serving_generation", None)
                    if gen is not None:
                        from ..obs.fleetobs import publication_tree

                        fleet_node = publication_tree(gen)
                        if fleet_node is not None:
                            body["fleet"] = fleet_node
                    self._send(200, body)
            elif self.path.split("?", 1)[0] == "/api/debug/timeseries":
                # the on-disk time-series ring (ISSUE 20): serve() starts
                # it; bare SimonServer constructions answer 503
                if server.timeseries is None:
                    self._send(503, {"error": "time-series ring not running"})
                else:
                    from urllib.parse import parse_qs

                    from ..obs.timeseries import parse_duration_s

                    q = parse_qs(self.path.partition("?")[2])
                    try:
                        range_s = parse_duration_s(q.get("range", [""])[-1])
                    except ValueError as e:
                        self._send(400, {"error": str(e)})
                    else:
                        self._send(200, {
                            "stats": server.timeseries.stats(),
                            "samples": server.timeseries.query(
                                family=q.get("family", [""])[-1],
                                range_s=range_s,
                            ),
                        })
            elif self.path.split("?", 1)[0] == "/api/fleet/slo":
                # SLO burn rates (ISSUE 20, obs/slo.py) — same surface the
                # fleet admin endpoint serves
                if server.slo is None:
                    self._send(503, {"error": "SLO engine not running"})
                else:
                    self._send(200, server.slo.evaluate())
            elif self.path.startswith("/api/debug/placements/"):
                # decision audit (ISSUE 7): the per-pod placement
                # explanations of an explain=1 request, keyed by request id
                rid = tracing.sanitize_request_id(
                    self.path.split("?", 1)[0].rsplit("/", 1)[1]
                )
                tr = FLIGHT_RECORDER.get(rid)
                placements = getattr(tr, "placements", None) if tr is not None else None
                if placements is None:
                    self._send(
                        404,
                        {
                            "error": f"no recorded placements for request id {rid!r}",
                            "hint": "POST /api/deploy-apps?explain=1 records them",
                        },
                    )
                else:
                    self._send(200, placements)
            elif self.path.startswith("/debug/profiler"):
                # pprof analogue (the reference registers pprof on gin,
                # server.go:152): start the JAX profiler server and report
                # where TensorBoard can connect
                from ..utils.trace import start_profiler

                try:
                    port = start_profiler()
                    self._send(200, {"profiler": "running", "port": port, "ui": "tensorboard --logdir ... (trace viewer)"})
                except Exception as e:
                    log.warning("profiler start failed: %s: %s", type(e).__name__, e)
                    self._send(500, {"error": str(e)})
            else:
                self._send(404, {"error": "not found"})

        def do_POST(self):
            self._begin_request()
            length = int(self.headers.get("Content-Length", 0))
            try:
                payload = json.loads(self.rfile.read(length) or b"{}")
            except ValueError:
                self._send(400, {"error": "invalid JSON body"})
                return
            deadline = request_deadline(self.headers)
            # request-id propagation (ISSUE 5): _begin_request honored the
            # client's X-Simon-Request-Id (sanitized) or generated one; the
            # id is echoed by _send and keys the flight-recorder trace
            request_id = last_request_id()
            path, _, query = self.path.partition("?")
            # explain=1 (decision audit, ISSUE 7): attach per-pod placement
            # explanations to the response and the flight recorder
            from urllib.parse import parse_qs

            explain = parse_qs(query).get("explain", ["0"])[-1] not in ("", "0", "false")
            if path == "/api/deploy-apps":
                code, body = server.deploy_apps(
                    payload, deadline=deadline, request_id=request_id, explain=explain
                )
            elif path == "/api/scale-apps":
                code, body = server.scale_apps(
                    payload, deadline=deadline, request_id=request_id, explain=explain
                )
            elif path == "/api/campaign":
                # campaign engine (ISSUE 13, docs/campaigns.md): a what-if
                # analysis like the cluster report — runs inline on the
                # handler thread, serialized across requests
                code, body = server.run_campaign(payload, deadline=deadline)
            else:
                code, body = 404, {"error": "not found"}
            # degraded-mode transparency: a result computed from a stale
            # snapshot (apiserver down through every retry) says so. Read
            # per-request (thread-local), not off the shared server flag —
            # a concurrent refresh must not mis-tag this response.
            extra = dict(response_extra_headers())  # e.g. Retry-After on shed
            if request_served_stale():
                extra["X-Simon-Snapshot"] = "stale"
            self._send(code, body, extra_headers=extra or None)

    return Handler


class SimonHTTPServer(ThreadingHTTPServer):
    """The serving listener: stdlib ``ThreadingHTTPServer`` with a backlog
    sized for hundreds of concurrent keep-alive clients — the default
    backlog of 5 resets most of a 500-client connect storm before a
    single request is read (ISSUE 15; the fleet's SO_REUSEPORT listener
    subclasses this sizing in server/fleet.py)."""

    request_queue_size = 512


def build_twin(kubeconfig: str, master: str, watch: str, journal: str):
    """(watch supervisor or None, journal or None) for a serving process —
    shared by the single-process :func:`serve` and the fleet owner
    (``server/fleet.serve_fleet``). Raises ``ValueError`` on operator
    errors (both callers print the message and exit 1). Paths must
    already be validated."""
    if watch == "on" and not kubeconfig:
        # "require a synced twin" with nothing to sync FROM is an operator
        # error that must not silently degrade to an empty polling server
        raise ValueError("--watch on requires --kubeconfig")
    supervisor = None
    if kubeconfig and watch != "off":
        from .watch import source_from_kubeconfig, watch_policy, WatchSupervisor

        policy = watch_policy()
        supervisor = WatchSupervisor(
            source_from_kubeconfig(
                kubeconfig, master or None, read_timeout_s=policy["stale_s"]
            ),
            policy=policy,
        )
    jrnl = None
    if journal:
        if supervisor is None:
            # a journal with no event stream to record is an operator
            # mistake worth failing on, not silently ignoring
            raise ValueError(
                "--journal requires the live twin (--kubeconfig and "
                "--watch auto|on)"
            )
        from .journal import Journal, JournalError

        try:
            jrnl = Journal(journal)
        except JournalError as e:
            raise ValueError(str(e)) from e
    return supervisor, jrnl


def fleet_workers(flag: int = 0) -> int:
    """Resolve the fleet size: the ``--workers`` flag wins, else
    ``OPENSIM_WORKERS_FLEET``; 0/1 means single-process serving."""
    if flag:
        return flag
    raw = envknobs.raw("OPENSIM_WORKERS_FLEET")
    if not raw:
        return 0
    try:
        return max(0, int(raw))
    except ValueError:
        log.warning("ignoring unparseable OPENSIM_WORKERS_FLEET=%r", raw)
        return 0


def serve(
    kubeconfig: str = "", master: str = "", port: int = 8080,
    watch: str = "auto", journal: str = "", workers: int = 0,
    standby: bool = False, ha_handover: bool = False,
) -> int:
    """Start the REST server. ``watch`` selects the snapshot strategy when a
    kubeconfig is configured (docs/live-twin.md):

    - ``auto`` (default): start the live twin in the background and serve
      from it once synced; until then — and if its bootstrap keeps
      failing — requests fall back to the polling snapshot path;
    - ``on``: require the twin to sync before accepting traffic (fail the
      process if it cannot);
    - ``off``: today's polling behavior only.

    ``journal`` names a directory for the crash-safe watch-event journal
    (docs/live-twin.md "Durability & replay"): the twin restores from its
    newest checkpoint + suffix replay at startup and every accepted event
    is recorded after. Requires the live twin (ignored, loudly, with
    ``--watch off`` or no kubeconfig).

    SIGTERM/SIGINT shut down gracefully: the listener stops, the admission
    queue drains (in-flight batch completes, queued requests shed typed
    503 ``shutting_down``), the reflectors stop, the journal is flushed +
    fsynced, and the process exits 0.

    ``workers`` ≥ 2 (or ``OPENSIM_WORKERS_FLEET``) serves through the
    multi-process fleet instead (docs/serving.md "Scaling past one
    process"): a twin-owner process publishing arena deltas over shared
    memory plus N worker processes sharing the port via SO_REUSEPORT.
    """
    import signal

    from ..utils import validate

    # registered validators (OSL1603): the CLI hands these straight from
    # argv; reject control characters before they reach open()/makedirs
    kubeconfig = validate.user_path(kubeconfig, label="--kubeconfig", allow_empty=True)
    journal = validate.user_path(journal, label="--journal", allow_empty=True)

    if envknobs.raw("OPENSIM_FLEET_ATTACH"):
        # this process IS a fleet worker (the supervisor set the knob):
        # attach the owner's publication instead of building a twin
        from .fleet import run_worker

        return run_worker(port)
    n_fleet = fleet_workers(workers)
    from .pool import OneProcessPerChip, one_process_per_chip

    if standby or n_fleet >= 2:
        # one process per chip: the fleet (and a standby, a fleet owner in
        # waiting) spawns N engine processes with no device assigned
        refusal = one_process_per_chip("--standby" if standby else f"--workers {n_fleet}")
        if refusal is not None:
            print(f"simon server: {refusal}", flush=True)
            return 1
    if standby:
        # HA hot standby (docs/serving.md "Surviving owner loss & rolling
        # upgrades"): tail the owner's journal, take over on lease expiry
        # or handover — with --handover, request the handover itself
        from .fleet import serve_standby

        return serve_standby(
            kubeconfig, master, port, watch, journal,
            n_fleet or 2, handover=ha_handover,
        )
    if n_fleet >= 2:
        from .fleet import serve_fleet

        return serve_fleet(kubeconfig, master, port, watch, journal, n_fleet)

    try:
        supervisor, jrnl = build_twin(kubeconfig, master, watch, journal)
    except ValueError as e:
        print(f"simon server: {e}", flush=True)
        return 1
    try:
        server = SimonServer(
            kubeconfig=kubeconfig, master=master, watch=supervisor, journal=jrnl
        )
    except OneProcessPerChip as e:  # OPENSIM_WORKERS_MODE=process on a TPU
        print(f"simon server: {e}", flush=True)
        return 1
    # low-rate RSS/device watermark sampler (OPENSIM_MEM_TICKER_S): only
    # the long-lived server process runs it — library/test constructions
    # of SimonServer sample on demand instead
    server.memory.start_ticker()
    # time-series ring + SLO engine (ISSUE 20): long-lived servers only,
    # same rationale as the ticker
    server.start_timeseries()
    if supervisor is not None:
        supervisor.prep_cache = server.prep_cache
        if watch == "on":
            if not supervisor.start(wait_s=60.0):
                print("simon server: --watch on but the twin could not sync", flush=True)
                supervisor.stop()
                return 1
        else:
            supervisor.start()
    httpd = SimonHTTPServer(("0.0.0.0", port), make_handler(server))
    # graceful shutdown (ISSUE 11 satellite): the handler only nudges the
    # serve loop from a helper thread (httpd.shutdown() deadlocks when
    # called from the thread running serve_forever) — the drain sequence
    # itself runs in the one finally block below, signal or not
    def _graceful(signum, frame):
        log.info(
            "received %s; draining and shutting down",
            signal.Signals(signum).name,
        )
        threading.Thread(
            target=httpd.shutdown, name="simon-shutdown", daemon=True
        ).start()

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, _graceful)
        except ValueError:
            # not the main thread (embedded/test use): skip the handlers;
            # the finally-block drain still runs on loop exit
            break
    mode = "admission queue" if server.admission is not None else "single-flight"
    print(
        f"simon server listening on :{port} [{mode}]"
        + (" (live twin)" if supervisor else "")
        + (f" [journal {journal}]" if jrnl is not None else ""),
        flush=True,
    )
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        # drain order matters: stop admitting first (queued work sheds
        # typed 503s, the in-flight batch completes), then the reflectors
        # (no new events), then flush+fsync+close the journal (server
        # .close()) so the recorded history is complete to the last event
        if server.admission is not None:
            server.admission.stop()
        if supervisor is not None:
            supervisor.stop()
        server.close()
        print("simon server: shutdown complete", flush=True)
    return 0
