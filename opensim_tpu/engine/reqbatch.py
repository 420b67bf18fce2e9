"""Request-axis batched simulation — the engine half of the concurrent
serving core (ISSUE 8).

The per-scenario sweep machinery (``parallel/scenarios.py``,
``fastpath.sweep``) proved the shape: S what-ifs over one ``Prepared``
differ only in boolean masks, so the whole batch is one vmapped dispatch.
This module lifts that batching from the *scenario* axis to the *request*
axis: N compatible REST simulate requests, folded onto one shared warm
prep (``prepcache.derive_with_app_slices`` appends every request's app
onto ONE fork of the cached base arenas), run as a single batched schedule
where request ``s``'s mask enables the base cluster region plus its own
app slice. Foreign pods are mask-invalid and never touch engine state, so
each demultiplexed result is bit-identical to running that request alone —
the same mask-flip argument ``drop_pods`` and the scenario sweeps rest on,
and gated end-to-end by ``tests/test_admission.py``.

The batch runs on the vmapped XLA scan: one compiled dispatch for the whole
batch, request axis prepended by ``jax.vmap`` (the megakernel declines the
request axis, ``select.DECLINES``). The decode demultiplexes through the same
``simulator.finish_decode`` tail the solo path uses, restoring bind state
between requests so shared pod objects never leak one request's binds into
another's report.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Union

import jax
import numpy as np

from ..encoding.state import EncodedCluster, ScanState
from ..models.objects import ResourceTypes
from ..obs import trace as obs
from ..resilience.deadline import Deadline, DeadlineExceeded
from . import select
from .scheduler import (
    ScheduleOutput,
    _schedule_pods_jit as _schedule_pods_traced,
    pad_pod_stream,
    scan_unroll,
)
from .simulator import (
    AppResource,
    EngineDecision,
    Prepared,
    SimulateResult,
    finish_decode,
    restore_bind_state,
    snapshot_bind_state,
)

__all__ = [
    "BatchItem",
    "BatchDispatch",
    "run_request_batch",
    "dispatch_request_batch",
    "decode_request_batch",
]

# request-axis pad buckets: the batch size participates in the jit
# signature, so S is padded up to a small fixed set of shapes (padded
# scenarios are all-invalid and never bind) — the same reasoning as
# pad_pod_stream's 256-pod buckets
_S_BUCKETS = (1, 2, 4, 8, 16, 32)


@dataclass
class BatchItem:
    """One request's view of the shared batch stream."""

    cluster: ResourceTypes  # the cluster this request simulates against
    apps: List[AppResource]  # its own apps (already appended to the stream)
    lo: int  # its app slice in prep.ordered (half-open)
    hi: int
    # report-level drops: scale-removed pods + the twin's event-deleted
    # pods (CacheEntry.base_drop), as indices over the BATCH stream
    drops: set = field(default_factory=set)
    explain: bool = False
    # the rider's request deadline (NOTES.md rough edge, ISSUE 9
    # satellite): a rider expired before the dispatch is shed with the
    # typed 504 (the vmapped XLA scan is one atomic dispatch, so
    # enforcement stops at the queue boundary)
    deadline: Optional[Deadline] = None


@functools.partial(jax.jit, static_argnames=("features", "unroll", "explain"))
def _batched_schedule(ec: EncodedCluster, st0: ScanState, tmpl_ids,
                      pod_valid_masks, forced, features, unroll,
                      explain=False):
    """ALL requests in ONE compiled dispatch: ``jax.vmap`` over the
    per-request pod-validity masks prepends a request axis to the scan
    (shared EncodedCluster/ScanState operands are not duplicated). Module
    level + jitted so repeat batch shapes hit the jit cache.

    ``explain`` (batched decision audit, ISSUE 15 satellite) runs the
    count_all scan variant so every rider's per-pod fail rows are filled
    — the shared carry is untouched, so non-explain riders' placements
    are unchanged and each explain rider's rows are bit-identical to its
    solo count_all run.

    The vmapped body calls the raw jit entry, not the observed
    ``schedule_pods`` wrapper: inside this trace the compile watch's
    host-side bookkeeping (locks, clocks, signature dicts) must not run —
    OSL1601 gates that statically. THIS boundary is the one the compile
    watch instruments instead (the ``observed_jit_call`` at the dispatch
    site below)."""
    return jax.vmap(
        lambda pv: _schedule_pods_traced(
            ec, st0, tmpl_ids, pv, forced, features=features, unroll=unroll,
            explain=explain,
        )
    )(pod_valid_masks)


def _pad_batch(pod_valid: np.ndarray) -> np.ndarray:
    """Pad the request axis up to the next shape bucket with all-invalid
    rows (they schedule nothing and are sliced off after the dispatch)."""
    S = pod_valid.shape[0]
    for b in _S_BUCKETS:
        if S <= b:
            pad = b - S
            break
    else:
        pad = (-S) % _S_BUCKETS[-1]
    if pad == 0:
        return pod_valid
    return np.concatenate([pod_valid, np.zeros((pad, pod_valid.shape[1]), bool)])


def _request_masks(prep: Prepared, items: List[BatchItem]) -> np.ndarray:
    """[S, P] bool: request s sees the base region plus its own app slice,
    minus its report-level drops."""
    P = len(prep.ordered)
    n_base = min(i.lo for i in items) if items else P
    valid = np.zeros((len(items), P), dtype=bool)
    for s, it in enumerate(items):
        valid[s, :n_base] = True
        valid[s, it.lo : it.hi] = True
        for i in it.drops:
            valid[s, i] = False
    return valid


def _slice_outputs(batched: ScheduleOutput, S: int, P: int) -> List[ScheduleOutput]:
    """Every request's host-side view of the batched outputs in ONE
    device→host pass per field: the per-rider ``np.asarray`` calls this
    replaced each re-materialized the FULL batched array (N transfers of
    the whole [S, P] tensor, the hottest decode-side span in
    ``obs/profile.py``); converting once and slicing numpy views is the
    vectorized path."""
    chosen = np.asarray(batched.chosen)
    fail_counts = np.asarray(batched.fail_counts)
    insufficient = np.asarray(batched.insufficient)
    gpu_take = np.asarray(batched.gpu_take)
    static_fail = np.asarray(batched.static_fail)
    fs = batched.final_state
    leaves = [np.asarray(leaf) for leaf in fs]
    state_type = type(fs)
    return [
        ScheduleOutput(
            chosen=chosen[s, :P],
            fail_counts=fail_counts[s, :P],
            insufficient=insufficient[s, :P],
            gpu_take=gpu_take[s, :P],
            static_fail=static_fail[s],
            final_state=state_type(*[leaf[s] for leaf in leaves]),
        )
        for s in range(S)
    ]


@dataclass
class BatchDispatch:
    """The engine half's outputs, handed from the dispatch stage to the
    decode stage (server/admission.py pipeline). Everything in here is
    host-side numpy (or a typed shed) — the decode stage never touches a
    device buffer."""

    outs: List[Optional[ScheduleOutput]]
    shed: Dict[int, BaseException]
    engine_name: str
    skips: Dict[str, str]
    pod_valid: np.ndarray


def run_request_batch(
    prep: Prepared, items: List[BatchItem]
) -> List[Union[SimulateResult, BaseException]]:
    """Schedule N requests' shared stream in one batched pass and
    demultiplex one :class:`SimulateResult` per request —
    :func:`dispatch_request_batch` followed by
    :func:`decode_request_batch` (the staged pipeline calls the halves
    separately so batch k+1's host prep can overlap batch k's dispatch).

    The caller (``server/rest.py``) owns the base entry lock and the
    derived prep; this function only reads ``prep`` and restores the bind
    state it mutates. Results are bit-identical to solo runs of each
    request (mask-invalid foreign pods never touch engine state).

    Deadline shedding (ISSUE 9 satellite + ISSUE 15 satellite): riders
    already expired BEFORE the dispatch are dropped from the request mask
    (their lane schedules nothing) and come back as a typed
    :class:`DeadlineExceeded` (``phase="schedule"``), so one slow queue
    wait can never ride a whole batch; a batch already IN FLIGHT stays
    atomic by design — the vmapped scan is one compiled dispatch with no
    host boundary to shed at.

    Batched explain (ISSUE 15 satellite): a rider with ``explain=True``
    rides the shared dispatch like any other — the batch runs the
    count_all scan variant so its per-pod fail rows exist, and only that
    rider's decode pays the audit build."""
    return decode_request_batch(prep, items, dispatch_request_batch(prep, items))


def dispatch_request_batch(prep: Prepared, items: List[BatchItem]) -> BatchDispatch:
    """The ENGINE stage: mask build + one batched schedule dispatch, no
    decode. Lock contract (the pipeline's overlap hinges on it): this
    function touches ONLY the derived prep's arrays and device buffers —
    never the shared pod objects, never the base entry's bind state — so
    the caller runs it WITHOUT the base-entry lock while the next batch's
    prep (which does hold it) overlaps. The XLA scan releases the GIL
    inside."""
    P = len(prep.ordered)
    pod_valid = _request_masks(prep, items)
    engine_name = "xla"
    skips = {"megakernel": select.ladder(prep, select.Ask(shape="batch"))["megakernel"]}

    def _shed_rider(s: int, dl: Deadline) -> DeadlineExceeded:
        obs.event(
            "batch.rider_shed", status="deadline-exceeded",
            rider=s, over_by_s=round(-dl.remaining(), 6),
        )
        return DeadlineExceeded(
            "request deadline exceeded at the 'schedule' phase "
            f"(shed between batched rider scans, over by "
            f"{-dl.remaining():.3f}s)",
            phase="schedule",
        )

    shed: Dict[int, BaseException] = {}
    # pre-dispatch deadline shedding (ISSUE 15 satellite): an already-
    # expired rider never enters the compiled dispatch — its lane's
    # mask is all-invalid (it schedules nothing and cannot perturb the
    # others, whose masks never included its pods anyway). Once the
    # dispatch is running the batch is atomic by design: the vmapped
    # scan has no host boundary to shed at.
    for s, it in enumerate(items):
        dl = it.deadline
        if dl is not None and dl.expired():
            shed[s] = _shed_rider(s, dl)
            pod_valid[s, :] = False
    # computed AFTER shedding: a shed rider's audit has no consumer,
    # and the count_all variant is its own jit cache entry — an
    # expired explain rider must not force that compile on the batch
    explain_any = any(
        it.explain for s, it in enumerate(items) if s not in shed
    )
    tmpl_p, _pv0, forced_p = pad_pod_stream(
        prep.tmpl_ids, pod_valid[0], prep.forced
    )
    pv_all = np.zeros((pod_valid.shape[0], len(tmpl_p)), dtype=bool)
    pv_all[:, :P] = pod_valid
    pv_all = _pad_batch(pv_all)
    with obs.span("engine.xla", requests=len(items), pods=P):
        import jax.numpy as jnp

        from ..obs.profile import launch_span, observed_jit_call

        # the batch dispatch is the outer jit boundary: the compile
        # watch observes it HERE, on the host, never under the trace
        with launch_span("xla.launch", requests=len(items), pods=len(tmpl_p)):
            batched = observed_jit_call(
                "batched_schedule",
                _batched_schedule,
                args=(
                    prep.ec, prep.st0, jnp.asarray(tmpl_p), jnp.asarray(pv_all),
                    jnp.asarray(forced_p),
                ),
                static={
                    "features": prep.features, "unroll": scan_unroll(),
                    "explain": explain_any,
                },
            )
        with obs.span("xla.wait"):
            jax.block_until_ready(batched.chosen)
    # ONE device→host conversion per output field for the whole batch
    # (N redundant full-tensor transfers before — the vectorized path)
    outs = list(_slice_outputs(batched, len(items), P))
    for s in shed:
        outs[s] = None
    return BatchDispatch(
        outs=outs, shed=shed, engine_name=engine_name, skips=skips,
        pod_valid=pod_valid,
    )


def decode_request_batch(
    prep: Prepared, items: List[BatchItem], dispatch: BatchDispatch
) -> List[Union[SimulateResult, BaseException]]:
    """The DECODE stage: demultiplex one :class:`SimulateResult` (or typed
    shed) per rider from the dispatch outputs. Mutates shared pod objects
    (binds, GPU annotations) through ``finish_decode`` and restores bind
    state between riders and on exit — the caller MUST hold the base-entry
    lock, exactly like the serial path."""
    P = len(prep.ordered)
    outs, shed = dispatch.outs, dispatch.shed
    pod_valid = dispatch.pod_valid
    sf_rows = prep.tmpl_ids
    snap = snapshot_bind_state(prep)
    results: List[Union[SimulateResult, BaseException]] = []
    with obs.span("decode", pods=P, requests=len(items)):
        for s, it in enumerate(items):
            if s in shed:
                results.append(shed[s])
                continue
            out = outs[s]
            engine = EngineDecision(name=dispatch.engine_name, skipped=dict(dispatch.skips))
            # the drop mask by slice assignment (vectorized): foreign
            # riders' app ranges + this rider's own report-level drops —
            # the old per-rider `set(drops) | _foreign(...)` built and
            # unioned index sets spanning most of the stream
            dropm = np.zeros(P, dtype=bool)
            for k, other in enumerate(items):
                if k != s:
                    dropm[other.lo : other.hi] = True
            if it.drops:
                for i in it.drops:
                    dropm[i] = True
            try:
                unsched, statuses = finish_decode(
                    prep, out, it.cluster,
                    np.asarray(out.chosen), np.asarray(out.gpu_take),
                    np.asarray(out.fail_counts), np.asarray(out.insufficient),
                    np.asarray(out.static_fail), sf_rows,
                    pod_valid[s], np.asarray(prep.forced, dtype=bool),
                    {}, {}, dropm,
                    None, None, None, (), engine, dispatch.engine_name,
                    it.explain,
                )
                results.append(
                    SimulateResult(
                        unscheduled_pods=unsched, node_status=statuses, engine=engine
                    )
                )
            finally:
                # shared pod objects: request s's binds must not leak into
                # request s+1's decode (or the cached entry's pristine state)
                restore_bind_state(prep, snap)
    return results


