"""Incremental prepare: content-keyed encode cache + delta re-encoding.

Repeated simulations against one cluster used to pay the full host-side
``prepare()`` cost — workload expansion plus cluster encoding, the dominant
host cost at 50k-pod scale (NOTES.md round-5 #5) — on every call: every REST
request re-encoded the snapshot, every planner sweep re-prepared its
candidate cluster. This module makes the host path pay O(changes) instead of
O(cluster):

- ``PrepareCache``: an LRU of ``prepare()`` outputs keyed by a cluster/app
  content fingerprint, with per-entry locks and pristine bind-state
  snapshots (``simulate``'s decode mutates the prepared pods; entries are
  restored after every use so a cache hit is indistinguishable from a fresh
  prepare).
- Delta re-encoders over a cached base ``Prepared``:
    * ``derive_with_apps``  — append an app's expanded pods to the stream
      (new templates re-assemble against the cached O(N) node arenas);
    * ``extend_with_nodes`` — add nodes cloned from a template (the planner
      case), splicing per-node DaemonSet pods in at exactly the positions a
      fresh expansion would produce them;
    * ``drop_mask_for_scaled`` — flip valid-mask bits for pods a scale
      request removed, instead of re-encoding the shrunk cluster.

Correctness bar (tests/test_prepcache.py): placements byte-identical to a
full re-encode on every path. The delta stream preserves the exact pod
order a fresh ``prepare()`` would produce; template/domain/vocab ids may be
numbered differently (they are opaque to the engines).
"""

from __future__ import annotations

import hashlib
import json
import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple, Union

import jax.numpy as jnp
import numpy as np

from ..encoding.state import ClusterEncoder, EncodedCluster, ScanState
from ..models import expand
from ..obs import trace as obs
from ..models.objects import (
    ANNO_WORKLOAD_KIND,
    LABEL_APP_NAME,
    Pod,
    ResourceTypes,
    touch_epoch,
)
from ..utils.trace import PREP_STATS
from . import queues
from .simulator import (
    AppResource,
    Prepared,
    SimulateResult,
    _owner_selector,
    _tmpl_hint,
    pinned_node_name,
    prepare,
    restore_bind_state,
    simulate,
    snapshot_bind_state,
)
from ..ops import kernels

# ---------------------------------------------------------------------------
# content fingerprints
# ---------------------------------------------------------------------------


def _meta_rv(obj: object) -> str:
    raw = getattr(obj, "raw", None) or {}
    return str((raw.get("metadata") or {}).get("resourceVersion", ""))


def fingerprint_cluster(cluster: ResourceTypes) -> str:
    """Content key for a cluster snapshot. Hashes object identity + version
    (name/uid/resourceVersion) plus the node fields that feed the encoder
    directly, so hand-built clusters (no uid/rv) still key on node content.
    In-place mutation of an already-fingerprinted object is NOT detected —
    callers that edit objects must invalidate explicitly (the REST server
    re-fingerprints on every snapshot refresh)."""
    h = hashlib.blake2b(digest_size=16)
    for n in cluster.nodes:
        h.update(
            "|".join(
                (
                    "n",
                    n.metadata.name,
                    n.metadata.uid or "",
                    _meta_rv(n),
                    "1" if n.unschedulable else "0",
                    json.dumps(sorted(n.metadata.labels.items())),
                    json.dumps(sorted((t.key, t.value, t.effect) for t in n.taints)),
                    json.dumps(sorted(n.allocatable.items())),
                    n.metadata.annotations.get("simon/node-local-storage", ""),
                )
            ).encode()
        )
    for p in cluster.pods:
        m = p.metadata
        h.update(
            f"p|{m.namespace}|{m.name}|{m.uid}|{_meta_rv(p)}|{p.spec.node_name}|{p.phase}".encode()
        )
    for kind, objs in (
        ("dep", cluster.deployments),
        ("rs", cluster.replica_sets),
        ("sts", cluster.stateful_sets),
        ("ds", cluster.daemon_sets),
        ("job", cluster.jobs),
        ("cj", cluster.cron_jobs),
    ):
        for w in objs:
            h.update(
                f"{kind}|{w.metadata.namespace}|{w.metadata.name}|{w.metadata.uid}|{_meta_rv(w)}|{w.replicas}".encode()
            )
    return h.hexdigest()


def fingerprint_apps(apps: List[AppResource]) -> str:
    """Content key for an app list: hashes each object's raw dict when
    present (request payloads round-trip exactly), identity otherwise."""
    h = hashlib.blake2b(digest_size=16)
    for app in apps:
        h.update(f"a|{app.name}".encode())
        rt = app.resources
        for objs in (
            rt.pods, rt.deployments, rt.replica_sets, rt.stateful_sets,
            rt.daemon_sets, rt.jobs, rt.cron_jobs,
        ):
            for o in objs:
                raw = getattr(o, "raw", None)
                if raw:
                    h.update(json.dumps(raw, sort_keys=True, default=str).encode())
                else:
                    h.update(
                        f"{type(o).__name__}|{o.metadata.namespace}|{o.metadata.name}|{o.metadata.uid}".encode()
                    )
    return h.hexdigest()


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------


class StaleFingerprintError(RuntimeError):
    """A cache hit landed on an entry whose watched object was ``touch()``ed
    after the entry was fingerprinted — the cached encoding no longer
    matches the object's content. Fix: ``cache.invalidate(obj)`` after the
    mutation (see models.objects.VersionedObject and
    docs/static-analysis.md#cache-mutation). ``obj`` carries the offending
    object so the cache can evict everything it taints."""

    def __init__(self, message: str, obj: Optional[object] = None) -> None:
        super().__init__(message)
        self.obj = obj


def _watched_objects(cluster: ResourceTypes, apps: List[AppResource]) -> List[object]:
    """Every model object a (cluster, apps) fingerprint covers — the set
    the stale-entry guard watches for version bumps."""
    out: List[object] = []
    rts = [cluster] + [a.resources for a in apps]
    for rt in rts:
        out.extend(rt.nodes)
        out.extend(rt.pods)
        out.extend(rt.deployments)
        out.extend(rt.replica_sets)
        out.extend(rt.stateful_sets)
        out.extend(rt.daemon_sets)
        out.extend(rt.jobs)
        out.extend(rt.cron_jobs)
        # RawObject kinds are versioned too: they don't enter the content
        # fingerprint, but the touch()/invalidate(obj) protocol must hold
        # uniformly for every model object a cluster carries
        out.extend(rt.services)
        out.extend(rt.pdbs)
        out.extend(rt.storage_classes)
        out.extend(rt.pvcs)
        out.extend(rt.config_maps)
    return out


#: (watched (object, version) pairs, touch epoch) — both captured at
#: FINGERPRINT time, i.e. before the (possibly seconds-long) prepare runs,
#: so a touch()+invalidate() landing during the build is not lost: the
#: entry records pre-build versions and an epoch older than the touch,
#: forcing the next check_fresh to scan and catch it.
WatchSnapshot = Tuple[List[Tuple[object, int]], int]


def watch_snapshot(cluster: ResourceTypes, apps: List[AppResource]) -> WatchSnapshot:
    """Capture the stale-guard baseline for a (cluster, apps) pair. The
    epoch is read BEFORE the versions: a touch interleaving between the
    two reads then leaves the entry's epoch behind the global one, which
    forces a full version scan on the next check_fresh."""
    epoch = touch_epoch()
    pairs = [(o, getattr(o, "_local_version", 0)) for o in _watched_objects(cluster, apps)]
    return pairs, epoch


@dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    invalidations: int = 0
    evictions: int = 0

    def as_dict(self) -> dict:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "invalidations": self.invalidations,
            "evictions": self.evictions,
        }


# drop-mask compaction telemetry (ISSUE 12, obs/footprint.py): how many
# times twin_pod_delta REFUSED a delta because the accumulated masked-row
# density crossed the threshold, forcing the caller's full rebuild — the
# event that re-compacts the stream. A process-global counter because the
# refusal site has no cache handle (the caller owns the rebuild).
_compaction_lock = threading.Lock()
_compactions = 0  # guarded-by: _compaction_lock


def note_compaction() -> None:
    global _compactions
    with _compaction_lock:
        _compactions += 1


def compactions_total() -> int:
    with _compaction_lock:
        return _compactions


class CacheEntry:
    """One cached ``Prepared`` plus everything reuse needs: a pristine
    bind-state snapshot, a lock serializing uses of the (shared) pod
    objects, and a numpy→device map so delta builds re-upload only changed
    tensors. Entries derived from a base share the base's lock — their pod
    streams alias the same objects."""

    def __init__(
        self,
        key: str,
        prep: Optional[Prepared],
        base: Optional["CacheEntry"] = None,
        watch: Optional[WatchSnapshot] = None,
    ) -> None:
        self.key = key
        self.prep = prep
        self.base = base
        self.lock = base.lock if base is not None else threading.RLock()  # lockwatch: hold-exempt — per-entry lock spans derive/encode by design
        self.bind_snap = snapshot_bind_state(prep) if prep is not None else []
        self._dev_map: Optional[dict] = None  # guarded-by: lock
        # live-twin delta state (server/watch.py): pods DELETED by watch
        # events stay in the cached stream with their valid-mask bit flipped
        # here instead of forcing a full re-encode; the REST layer unions
        # this into every simulate() drop mask derived from the entry
        self.base_drop: Optional[np.ndarray] = None  # guarded-by: lock
        # the scan state after this entry's leading run of bound pods, built
        # by the first XLA scan over a stream derived from it and read by
        # every later one (engine/resident.py). A twin event makes a new
        # entry (twin_pod_delta), which builds its own.
        self.resident = None  # guarded-by: lock
        # (object, local_version at fingerprint time) — the stale-entry
        # guard; see VersionedObject (models/objects.py) and
        # watch_snapshot(). Derived entries share the base's list: their
        # stream aliases the same objects, and the base was proven fresh
        # before the delta was built.
        if watch is None and base is not None:
            self.watched: List[Tuple[object, int]] = base.watched
            self._touch_epoch = base._touch_epoch
        elif watch is not None:
            self.watched, self._touch_epoch = watch
        else:
            self.watched, self._touch_epoch = [], touch_epoch()

    def restore(self) -> None:
        if self.prep is not None:
            restore_bind_state(self.prep, self.bind_snap)

    def watches(self, obj: object) -> bool:
        return any(o is obj for o, _ in self.watched)

    def check_fresh(self) -> None:
        """Raise StaleFingerprintError if any watched object was touched
        since this entry was fingerprinted.

        Fast path: ``touch()`` bumps a process-global epoch, so when no
        object anywhere was touched since this entry (the steady state)
        this is one integer compare, not an O(watched) scan. A clean scan
        re-arms the fast path at the current epoch."""
        epoch = touch_epoch()
        if epoch == self._touch_epoch:
            return
        for obj, v0 in self.watched:
            v1 = getattr(obj, "_local_version", 0)
            if v1 != v0:
                kind = getattr(obj, "kind", type(obj).__name__)
                meta = getattr(obj, "metadata", None)
                name = getattr(meta, "name", "?") if meta is not None else "?"
                raise StaleFingerprintError(
                    f"cached prepare is stale: {kind} {name!r} was touch()ed "
                    f"(version {v1} vs {v0} at fingerprint time) without cache "
                    "invalidation; call cache.invalidate(obj) after mutating "
                    "a fingerprinted object (docs/static-analysis.md#cache-mutation)",
                    obj=obj,
                )
        self._touch_epoch = epoch

    def dev_map(self) -> dict:
        """{id(numpy leaf): device leaf} over the entry's EncodedCluster —
        delta assemblies reuse the already-uploaded tensors for every leaf
        the delta did not touch."""
        # a locked accessor: delta builders call this while already inside
        # the entry lock (RLock — free re-entry), but the planner's
        # lock-free extend_with_nodes path reaches here too
        with self.lock:
            if self._dev_map is None:
                self._dev_map = {
                    id(np_leaf): dev_leaf
                    for np_leaf, dev_leaf in zip(self.prep.ec_np, self.prep.ec)
                }
            return self._dev_map


class PrepareCache:
    """Thread-safe LRU of CacheEntry keyed by content fingerprint."""

    def __init__(self, capacity: int = 8) -> None:
        self.capacity = capacity
        self._lock = threading.Lock()
        self._entries: "OrderedDict[str, CacheEntry]" = OrderedDict()  # guarded-by: _lock
        self.stats = CacheStats()

    def get(self, key: str) -> Optional[CacheEntry]:
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                self.stats.misses += 1
                return None
            self._entries.move_to_end(key)
            self.stats.hits += 1
            return entry

    def put(self, key: str, entry: CacheEntry) -> CacheEntry:
        with self._lock:
            existing = self._entries.get(key)
            if existing is not None:
                return existing  # racing builders: first one wins
            self._entries[key] = entry
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self.stats.evictions += 1
            return entry

    def invalidate(self, target: Union[str, object] = "") -> int:
        """Drop cache entries; returns the number dropped.

        - ``invalidate()`` — everything;
        - ``invalidate(prefix)`` — entries whose key starts with ``prefix``
          (the REST server's path when the live snapshot's fingerprint
          changes);
        - ``invalidate(obj)`` — entries whose fingerprint covered the model
          object ``obj`` (by identity): THE call to make after mutating an
          already-fingerprinted Pod/Node/Workload in place, closing the
          NOTES.md in-place-mutation envelope. Pair with ``obj.touch()`` so
          a forgotten invalidation fails loudly (StaleFingerprintError)
          instead of serving stale placements."""
        with self._lock:
            if isinstance(target, str):
                doomed = [k for k in self._entries if k.startswith(target)]
            else:
                doomed = [k for k, e in self._entries.items() if e.watches(target)]
            for k in doomed:
                del self._entries[k]
            self.stats.invalidations += len(doomed)
        if doomed:
            # trace event outside the cache lock (the span sink shares the
            # metrics recorder lock; never hold both)
            obs.event("prepcache.invalidate", dropped=len(doomed))
        return len(doomed)

    def check_fresh(self, entry: CacheEntry) -> None:
        """Entry freshness check that also EVICTS on staleness: once an
        entry is proven stale it can never become fresh again, so leaving
        it cached would turn every later hit on its key into the same
        error (a REST client has no way to call invalidate(obj)). Eviction
        is by the offending OBJECT, dropping every entry it taints (e.g. a
        REST base entry and its derived full-key entries share one watch
        list) — recovery costs one failed request, not one per entry."""
        from ..resilience import faults

        try:
            # chaos injection point: a fault here (exc name ``stale``) lands
            # exactly like a mid-flight touch() on a watched object
            faults.fault_point("cache.stale")
            entry.check_fresh()
        except StaleFingerprintError as e:
            obs.event("prepcache.stale", status="error", key=entry.key)
            if e.obj is not None:
                self.invalidate(e.obj)
            self.invalidate(entry.key)
            raise

    def entries_snapshot(self) -> List[CacheEntry]:
        """Point-in-time list of resident entries, LRU-oldest first — the
        memory observatory's walk (obs/footprint.py). The list is a copy;
        per-entry reads still take each entry's own lock."""
        with self._lock:
            return list(self._entries.values())

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


# ---------------------------------------------------------------------------
# delta assembly
# ---------------------------------------------------------------------------


def _to_device_reusing(
    ec_np: EncodedCluster, st0_np: ScanState, base_entry: Optional[CacheEntry]
) -> Tuple[EncodedCluster, ScanState]:
    """``scheduler.to_device`` with leaf reuse: tensors the delta shares
    with the cached base keep their device copies (no re-upload)."""
    dev_map = base_entry.dev_map() if base_entry is not None and base_entry.prep is not None else {}
    ec = EncodedCluster(
        *[dev_map[id(a)] if id(a) in dev_map else jnp.asarray(a) for a in ec_np]
    )
    st0 = ScanState(*[jnp.asarray(a) for a in st0_np])
    return ec, st0


def _assemble_delta(
    base_entry: Optional[CacheEntry],
    enc: "ClusterEncoder",
    ordered: List[Pod],
    tmpl_parts: List[object],
    forced_parts: List[object],
    n_cluster: int,
    n_bare: int,
    ds_group_sizes: List[int],
) -> Prepared:
    # prep.assemble: the arenas rebuilt, what changed sent to the device,
    # and the list and array concatenations over every base pod
    with obs.span("prep.assemble", pods=len(ordered)):
        ec_np, st0_np, meta = enc.build()
        features = kernels.features_of(ec_np)
        ec, st0 = _to_device_reusing(ec_np, st0_np, base_entry)
        tmpl_ids = np.concatenate(
            [np.asarray(p, dtype=np.int32) for p in tmpl_parts]
        ) if tmpl_parts else np.zeros((0,), np.int32)
        forced = np.concatenate(
            [np.asarray(p, dtype=bool) for p in forced_parts]
        ) if forced_parts else np.zeros((0,), bool)
        node_idx = {name: i for i, name in enumerate(meta.node_names)}
        ds_target = [
            node_idx.get(pinned_node_name(p), -1)
            if p.metadata.annotations.get(ANNO_WORKLOAD_KIND) == "DaemonSet"
            else -1
            for p in ordered
        ]
        return Prepared(
            ec=ec,
            st0=st0,
            meta=meta,
            ordered=ordered,
            tmpl_ids=tmpl_ids,
            forced=forced,
            ds_target=ds_target,
            features=features,
            ec_np=ec_np,
            encoder=enc,
            n_cluster=n_cluster,
            n_bare=n_bare,
            ds_group_sizes=ds_group_sizes,
        )


def _expand_app(cluster: ResourceTypes, app: AppResource, use_greed: bool) -> List[Pod]:
    """The exact app expansion pipeline of ``simulator._prepare_inner``."""
    app_pods = expand.generate_pods_from_resources(app.resources, cluster.nodes)
    for p in app_pods:
        p.metadata.labels.setdefault(LABEL_APP_NAME, app.name)
    app_pods = queues.toleration_sort(queues.affinity_sort(app_pods))
    if use_greed:
        app_pods = queues.greed_sort(cluster.nodes, app_pods)
    return app_pods


def derive_with_apps(
    base: Prepared,
    cluster: ResourceTypes,
    apps: List[AppResource],
    use_greed: bool = False,
    base_entry: Optional[CacheEntry] = None,
) -> Optional[Prepared]:
    """Delta re-encode: the cached base's stream plus `apps` appended —
    exactly the stream ``prepare(cluster, apps)`` would produce when the
    base was prepared from the same cluster with no apps. `base_entry`
    (when `base` is its prep) enables device-tensor reuse for unchanged
    leaves. Returns None when the result would be empty."""
    got = derive_with_app_slices(
        base, cluster, apps, use_greed=use_greed, base_entry=base_entry
    )
    return None if got is None else got[0]


def derive_with_app_slices(
    base: Prepared,
    cluster: ResourceTypes,
    apps: List[AppResource],
    use_greed: bool = False,
    base_entry: Optional[CacheEntry] = None,
) -> Optional[Tuple[Prepared, List[Tuple[int, int]]]]:
    """:func:`derive_with_apps` that also reports per-app stream slices.

    Returns ``(prep, slices)`` where ``slices[k] = (lo, hi)`` is the
    half-open index range app ``k``'s expanded pods occupy in
    ``prep.ordered``. This is the share-safe handoff the request-axis
    batcher (``engine/reqbatch.py``) builds on: N requests' apps are
    appended onto ONE fork of the cached base arenas, and each request's
    scenario mask enables exactly the base region plus its own slice —
    masked foreign pods never touch engine state, so per-request
    placements are bit-identical to a solo ``derive_with_apps`` of that
    app alone (gated by tests/test_admission.py)."""
    if isinstance(base, CacheEntry):  # convenience: entry accepted directly
        base_entry, base = base, base.prep
    with PREP_STATS.timed("delta_apps") as timed:
        enc = base.encoder.fork()
        new_pods: List = []
        forced_new: List[bool] = []
        slices: List[Tuple[int, int]] = []
        n_base = len(base.ordered)
        with obs.span("prep.expand"):
            for app in apps:
                lo = n_base + len(new_pods)
                for p in _expand_app(cluster, app, use_greed):
                    new_pods.append(p)
                    forced_new.append(bool(p.spec.node_name))
                slices.append((lo, n_base + len(new_pods)))
        if not new_pods and not base.ordered:
            timed.declined()
            return None
        tmpl_new = [
            enc.add_pod(p, (lambda p=p: _owner_selector(p)), hint=_tmpl_hint(p))
            for p in new_pods
        ]
        prep = _assemble_delta(
            base_entry,
            enc,
            ordered=list(base.ordered) + new_pods,
            tmpl_parts=[base.tmpl_ids, tmpl_new] if len(base.tmpl_ids) else [tmpl_new],
            forced_parts=[base.forced, forced_new] if len(base.forced) else [forced_new],
            n_cluster=base.n_cluster,
            n_bare=base.n_bare,
            ds_group_sizes=list(base.ds_group_sizes or []),
        )
        if base_entry is not None and base_entry.prep is base:
            prep.resident_base = base_entry
    return prep, slices


def extend_with_nodes(
    base_prep: Prepared,
    new_nodes: List,
    cluster: ResourceTypes,
    apps: List[AppResource],
    use_greed: bool = False,
    base_entry: Optional[CacheEntry] = None,
) -> Optional[Prepared]:
    """Delta re-encode for node addition (the planner's candidate sweep):
    encode the new nodes into the cached arenas and splice their DaemonSet
    pods in at the exact stream positions a fresh full expansion would
    produce. Returns None when the delta cannot reproduce a fresh prepare:

    - greedy sort orders app pods by node TOTALS, which the added nodes
      change — the whole stream may reorder;
    - app DaemonSets expand one pod per node inside the app's sorted
      region — splicing there is not order-preserving in general.
    """
    if use_greed:
        return None
    if any(a.resources.daemon_sets for a in apps):
        return None
    if base_prep is None or base_prep.encoder is None or base_prep.ds_group_sizes is None:
        return None
    with PREP_STATS.timed("delta_nodes") as timed:
        enc = base_prep.encoder.fork()
        enc.extend_nodes(new_nodes)

        # per-DaemonSet pods for the new nodes, in cluster.daemon_sets order —
        # the same expansion order _cluster_pods uses
        groups_new = expand.pods_from_daemon_sets(cluster.daemon_sets, new_nodes)
        if len(groups_new) != len(base_prep.ds_group_sizes):
            timed.declined()
            return None  # cluster's DS set changed vs the base prep: not a pure node delta

        b = base_prep.n_cluster - sum(base_prep.ds_group_sizes)
        ordered: List = list(base_prep.ordered[:b])
        tmpl_parts: List = [base_prep.tmpl_ids[:b]]
        forced_parts: List = [base_prep.forced[:b]]
        ds_group_sizes: List[int] = []
        off = b
        for size, pods_k in zip(base_prep.ds_group_sizes, groups_new):
            ordered.extend(base_prep.ordered[off : off + size])
            tmpl_parts.append(base_prep.tmpl_ids[off : off + size])
            forced_parts.append(base_prep.forced[off : off + size])
            off += size
            ids = [
                enc.add_pod(p, (lambda p=p: _owner_selector(p)), hint=_tmpl_hint(p))
                for p in pods_k
            ]
            ordered.extend(pods_k)
            tmpl_parts.append(ids)
            forced_parts.append([bool(p.spec.node_name) for p in pods_k])
            ds_group_sizes.append(size + len(pods_k))
        # the app region rides along unchanged (apps have no DaemonSets here)
        ordered.extend(base_prep.ordered[base_prep.n_cluster :])
        tmpl_parts.append(base_prep.tmpl_ids[base_prep.n_cluster :])
        forced_parts.append(base_prep.forced[base_prep.n_cluster :])

        prep = _assemble_delta(
            base_entry,
            enc,
            ordered=ordered,
            tmpl_parts=[p for p in tmpl_parts if len(p)],
            forced_parts=[p for p in forced_parts if len(p)],
            n_cluster=base_prep.n_cluster + sum(len(g) for g in groups_new),
            n_bare=base_prep.n_bare,
            ds_group_sizes=ds_group_sizes,
        )
    return prep


def drop_mask_for_scaled(
    prep: Prepared, owned_by: Callable[[Pod, set], bool], scaled: set
) -> np.ndarray:
    """Valid-mask flip for a scale request: mark the BARE cluster pods owned
    by the scaled workloads (the pods ``scale-apps`` removes from the
    snapshot before re-simulating). Only the bare prefix is eligible — the
    fresh path filters ``cluster.pods``, never workload expansions."""
    mask = np.zeros((len(prep.ordered),), dtype=bool)
    for i in range(prep.n_bare):
        if owned_by(prep.ordered[i], scaled):
            mask[i] = True
    return mask


def pad_drop_mask(mask: Optional[np.ndarray], n: int) -> Optional[np.ndarray]:
    """Extend a base-entry drop mask to a longer derived stream. Safe for
    every derive path in use: ``derive_with_apps`` appends at the end and
    ``extend_with_nodes`` splices only above the bare-pod prefix, while twin
    drop masks only ever flag bare pods — set bits never move."""
    if mask is None:
        return None
    if len(mask) >= n:
        return mask[:n]
    out = np.zeros((n,), dtype=bool)
    out[: len(mask)] = mask
    return out


def union_drop_masks(
    a: Optional[np.ndarray], b: Optional[np.ndarray], n: int
) -> Optional[np.ndarray]:
    """Union of two (optional) drop masks, padded to stream length ``n``."""
    a = pad_drop_mask(a, n)
    b = pad_drop_mask(b, n)
    if a is None:
        return b
    if b is None:
        return a
    return a | b


def twin_pod_delta(
    base_entry: CacheEntry,
    key: str,
    added: List[Pod],
    removed_keys: set,
    watch: Optional[WatchSnapshot] = None,
) -> Optional[CacheEntry]:
    """O(changes) base-entry maintenance for the live twin (server/watch.py):
    derive a new base CacheEntry from the current one after a batch of pod
    ADDED/DELETED watch events, without re-expanding or re-encoding the
    cluster.

    - ``added`` pods are encoded into a fork of the cached arenas and
      inserted at the END OF THE BARE REGION — exactly where a fresh
      ``prepare()`` of the re-listed cluster puts them (the twin appends new
      pods to its pod list, mirroring event order).
    - ``removed_keys`` — ``(namespace, name)`` pairs — become valid-mask
      flips recorded in ``CacheEntry.base_drop``; the pods stay in the
      stream but every engine skips them (the scale-apps drop-mask path,
      proven placement-identical to re-encoding the shrunk cluster).

    Returns None when the entry cannot express the delta (no encoder
    provenance, a removed pod outside the bare region, or the accumulated
    masked-row density past the compaction threshold below) — the caller
    falls back to a full rebuild. MUST be called with ``base_entry.lock``
    held and bind state restored."""
    prep = base_entry.prep
    if prep is None or prep.encoder is None or prep.ds_group_sizes is None:
        return None
    with PREP_STATS.timed("twin_delta") as timed:
        nb = prep.n_bare
        drop = (
            np.array(base_entry.base_drop, dtype=bool, copy=True)
            if base_entry.base_drop is not None
            else np.zeros((len(prep.ordered),), dtype=bool)
        )
        if removed_keys:
            found = set()
            for i in range(nb):
                p = prep.ordered[i]
                k = (p.metadata.namespace, p.metadata.name)
                if k in removed_keys:
                    drop[i] = True
                    found.add(k)
            missing = removed_keys - found
            if missing:
                # a deletion we cannot locate in the bare prefix (e.g. the pod
                # was never admissible, or it lives in a workload expansion) —
                # only the full rebuild knows how to express it
                timed.declined()
                return None
        if added:
            enc = prep.encoder.fork()
            ids_new = [
                enc.add_pod(p, (lambda p=p: _owner_selector(p)), hint=_tmpl_hint(p))
                for p in added
            ]
            new_prep = _assemble_delta(
                base_entry,
                enc,
                ordered=list(prep.ordered[:nb]) + list(added) + list(prep.ordered[nb:]),
                tmpl_parts=[
                    prep.tmpl_ids[:nb],
                    np.asarray(ids_new, dtype=np.int32),
                    prep.tmpl_ids[nb:],
                ],
                forced_parts=[
                    prep.forced[:nb],
                    np.asarray([bool(p.spec.node_name) for p in added], dtype=bool),
                    prep.forced[nb:],
                ],
                n_cluster=prep.n_cluster + len(added),
                n_bare=nb + len(added),
                ds_group_sizes=list(prep.ds_group_sizes),
            )
            drop = np.concatenate([drop[:nb], np.zeros((len(added),), bool), drop[nb:]])
        else:
            new_prep = prep  # drops alone never re-encode: the mask is the delta
        # compaction threshold: deleted pods stay in the stream as masked rows,
        # so pure add/delete churn would otherwise grow the stream (and every
        # engine pass over it) without bound. Past the threshold the delta is
        # refused and the caller's full rebuild re-prepares the compacted
        # cluster — amortized O(cluster / threshold) per churned pod.
        n_dropped = int(drop.sum())
        if n_dropped > max(64, len(drop) // 4):
            note_compaction()
            timed.declined()
            return None
        entry = CacheEntry(key, new_prep, base=base_entry, watch=watch)
        entry.base_drop = drop if n_dropped else None
    return entry


# ---------------------------------------------------------------------------
# attach-from-shm (multi-process serving fleet, server/fleet.py)
# ---------------------------------------------------------------------------


def publication_parts(entry: CacheEntry) -> Optional[dict]:
    """The host-side pieces of a warm base entry a twin owner publishes
    over shared memory (server/fleet.py): everything a worker process
    needs to rebuild an equivalent :class:`CacheEntry` EXCEPT the device
    tensors (each attaching process re-uploads once per generation) and
    the per-entry lock (locks are process-local by definition). MUST be
    called with ``entry.lock`` held and bind state restored, like every
    other reader of the shared pod objects. Returns None for a no-prep
    entry (a cluster with no schedulable pods — nothing to publish)."""
    prep = entry.prep
    if prep is None:
        return None
    st0_np = ScanState(*[np.asarray(a) for a in prep.st0])
    return {
        "ec_np": prep.ec_np,
        "st0_np": st0_np,
        "meta": prep.meta,
        "ordered": prep.ordered,
        "tmpl_ids": prep.tmpl_ids,
        "forced": prep.forced,
        "ds_target": prep.ds_target,
        "features": prep.features,
        "encoder": prep.encoder,
        "n_cluster": prep.n_cluster,
        "n_bare": prep.n_bare,
        "ds_group_sizes": prep.ds_group_sizes,
        "base_drop": entry.base_drop,
    }


def entry_from_publication(key: str, parts: dict) -> CacheEntry:
    """Rebuild a warm base :class:`CacheEntry` from published parts — the
    worker-process half of the fleet's attach-from-shm path. The numpy
    leaves in ``parts`` may be zero-copy read-only views over shared
    memory; nothing here (or on any serving path over the entry) writes
    through them — deltas fork the encoder and drop masks are copied
    before mutation. The one per-attach cost is the device upload of the
    encoded cluster (each process owns its device buffers; later derives
    reuse them leaf-by-leaf through ``CacheEntry.dev_map``)."""
    ec_np: EncodedCluster = parts["ec_np"]
    st0_np: ScanState = parts["st0_np"]
    ec = EncodedCluster(*[jnp.asarray(a) for a in ec_np])
    st0 = ScanState(*[jnp.asarray(a) for a in st0_np])
    prep = Prepared(
        ec=ec,
        st0=st0,
        meta=parts["meta"],
        ordered=parts["ordered"],
        tmpl_ids=parts["tmpl_ids"],
        forced=parts["forced"],
        ds_target=parts["ds_target"],
        features=parts["features"],
        ec_np=ec_np,
        encoder=parts["encoder"],
        n_cluster=parts["n_cluster"],
        n_bare=parts["n_bare"],
        ds_group_sizes=parts["ds_group_sizes"],
    )
    entry = CacheEntry(key, prep)
    with entry.lock:  # fresh and unpublished, but base_drop is guarded-by it
        entry.base_drop = parts.get("base_drop")
    return entry


# ---------------------------------------------------------------------------
# steady-state entry point
# ---------------------------------------------------------------------------


def simulate_cached(
    cluster: ResourceTypes,
    apps: List[AppResource],
    cache: PrepareCache,
    *,
    use_greed: bool = False,
    node_pad: int = 128,
    sched_config: Optional[object] = None,
    extra_plugins: tuple = (),
    tie_seed: Optional[int] = None,
    key: Optional[str] = None,
) -> "SimulateResult":
    """One full simulation through the encode cache: the first call for a
    (cluster, apps) content key pays the full prepare; every later call
    reuses the cached Prepared (fingerprint + bind-state restore — O(pods)
    pointer work, no expansion, no encode). The steady-state path bench.py
    --config steady measures."""
    full_key = key or (
        fingerprint_cluster(cluster)
        + "|" + fingerprint_apps(apps)
        + f"|g{int(use_greed)}|p{node_pad}"
    )
    entry = cache.get(full_key)
    if entry is None:
        # baseline captured BEFORE the build: a touch()+invalidate() racing
        # the prepare leaves this entry provably stale, not silently fresh
        watch = watch_snapshot(cluster, apps)
        prep = prepare(cluster, apps, use_greed=use_greed, node_pad=node_pad)
        entry = cache.put(full_key, CacheEntry(full_key, prep, watch=watch))
    else:
        with PREP_STATS.timed("hit"):
            cache.check_fresh(entry)
            with entry.lock:
                entry.restore()
    if entry.prep is None:
        return simulate(
            cluster, apps, use_greed=use_greed, node_pad=node_pad,
            sched_config=sched_config, extra_plugins=extra_plugins, tie_seed=tie_seed,
        )
    with entry.lock:
        try:
            return simulate(
                cluster, apps, sched_config=sched_config,
                extra_plugins=extra_plugins, tie_seed=tie_seed, prep=entry.prep,
            )
        finally:
            entry.restore()
