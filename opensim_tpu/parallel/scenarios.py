"""Scenario-parallel what-if evaluation.

The reference's capacity planner re-runs the whole simulation once per
candidate node count, interactively (``pkg/apply/apply.go:203-259``). Here a
*batch* of scenarios — node counts, drain plans — evaluates in one jitted,
sharded computation: every scenario shares the same EncodedCluster tensors
and differs only in its ``node_valid`` / ``pod_valid`` masks, so the whole
sweep is one ``vmap`` over masks, sharded across TPU cores over ICI with a
``jax.sharding.Mesh``. This is §2.3 of SURVEY.md: the distributed backend of
this framework is XLA collectives over the scenario axis, not NCCL/MPI.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..encoding.state import EncodedCluster, ScanState
from ..engine import select
# the sweep bodies run UNDER tracing (vmapped inside the jitted sweeps):
# they call the raw jit entry, never the observed schedule_pods wrapper —
# the compile watch's host bookkeeping must stay outside the trace (OSL1601)
from ..engine.scheduler import _schedule_pods_jit as _schedule_pods_traced, scan_unroll
from ..ops import kernels


class SweepResult(NamedTuple):
    unscheduled: jnp.ndarray  # [S] i32 — unscheduled pod count per scenario
    used: jnp.ndarray  # [S, N, R] f32 — final per-node usage
    chosen: jnp.ndarray  # [S, P] i32
    vg_used: jnp.ndarray  # [S] f32 — total VG bytes allocated


def _one_scenario(ec: EncodedCluster, st0: ScanState, tmpl_ids, forced, node_valid, pod_valid, features, config, unroll):  # opensim-lint: jit-region
    out = _schedule_pods_traced(
        ec._replace(node_valid=node_valid),
        st0,
        tmpl_ids,
        pod_valid,
        forced,
        features=features,
        config=config,
        unroll=unroll,
    )
    unscheduled = jnp.sum(pod_valid & (out.chosen < 0))
    vg_used = jnp.sum(
        jnp.where(node_valid[:, None], st0.vg_free - out.final_state.vg_free, 0.0)
    )
    return unscheduled.astype(jnp.int32), out.final_state.used, out.chosen, vg_used


@functools.partial(jax.jit, static_argnames=("features", "config", "unroll"))
def _sweep_impl(
    ec, st0, tmpl_ids, node_valid_masks, pod_valid_masks, forced_masks, features, config=None, unroll=1
):
    """Module-level jitted sweep so repeat invocations hit the jit cache
    (a fresh closure per call would retrace every time)."""
    return jax.vmap(
        lambda nv, pv, fm: _one_scenario(ec, st0, tmpl_ids, fm, nv, pv, features, config, unroll)
    )(node_valid_masks, pod_valid_masks, forced_masks)


def sweep_counts(
    prep, n_real: int, ks, config=None
) -> "tuple[SweepResult, np.ndarray]":
    """Candidate new-node count sweep directly over a prepared (possibly
    cached/delta-derived) arena: scenario s enables the first ``n_real +
    ks[s]`` nodes of the prepared node axis, and DaemonSet pods pinned to
    disabled candidate nodes are masked out of that scenario (a smaller
    expansion would never have created them). This is the mask-flip
    materialization of the planner's sweep — the encoded tensors are built
    once (or delta re-encoded from a cached base) and every probe is just a
    pair of boolean masks. Returns (SweepResult, node_valid_masks)."""
    N = int(np.asarray(prep.ec_np.node_valid).shape[0])
    P = len(prep.ordered)
    S = len(ks)
    node_valid = np.zeros((S, N), dtype=bool)
    for s, k in enumerate(ks):
        node_valid[s, : n_real + k] = True
    pod_valid = np.ones((S, P), dtype=bool)
    for p, target in enumerate(prep.ds_target):
        if target >= n_real:  # DaemonSet pod pinned to a candidate node
            pod_valid[:, p] = node_valid[:, target]
    return sweep_auto(prep, node_valid, pod_valid, config=config), node_valid


def sweep_auto(
    prep,
    node_valid_masks: np.ndarray,
    pod_valid_masks: np.ndarray,
    forced_masks: Optional[np.ndarray] = None,
    config=None,
) -> SweepResult:
    """A scenario sweep on the rung ``select`` gives it: sequential C++ scans,
    ALL scenarios in one batched Pallas dispatch (the scenario axis rides the
    kernel grid), or the vmapped XLA scan sharded across the devices."""
    S = node_valid_masks.shape[0]
    if forced_masks is None:
        forced_masks = np.broadcast_to(prep.forced, (S, len(prep.forced)))
    if config is not None:
        # multi-profile config: same routing as simulate() — unknown-profile
        # pods are masked out of every scenario (they can never schedule, so
        # capacity sweeps must not count them). DIFFERING profiles used to
        # raise here (the NOTES.md rough edge); they now route through
        # per-segment scans sharing the scheduling carry (ISSUE 8
        # satellite), exactly like simulate()'s segmented path — so the
        # request-axis batcher and the planner can sweep mixed-profile
        # streams.
        from ..engine.schedconfig import DEFAULT_CONFIG, resolve_profile_segments

        segs, invalid = resolve_profile_segments(
            config, prep.ordered, prep.meta.resource_names, forced=prep.forced
        )
        if invalid:
            pod_valid_masks = np.array(pod_valid_masks, copy=True)
            for i in invalid:
                pod_valid_masks[:, i] = False
        distinct = {c for c, _, _ in segs if c is not None and c != DEFAULT_CONFIG}
        if len(segs) > 1 and distinct:
            return sweep_segmented(
                prep, segs, node_valid_masks, pod_valid_masks,
                np.asarray(forced_masks, dtype=bool),
            )
        config = distinct.pop() if distinct else None
    from ..engine.schedconfig import profile_of
    from ..obs import trace as obs
    from ..obs.metrics import RECORDER

    pol, ask = select.policy(), select.Ask(shape="sweep", sched_config=config)
    rungs = select.ladder(prep, ask, pol)
    profile = profile_of(config)
    if rungs["native"] is None:
        from ..engine import nativepath

        # no XLA scan compile; the incremental template cache makes each
        # scenario ms-scale on small configs
        RECORDER.count_engine_profile("native", profile)
        with obs.span("sweep.native", scenarios=S):
            unscheduled, used, chosen, vg_used = nativepath.sweep(
                prep, node_valid_masks, pod_valid_masks, forced_masks, config=config
            )
        return SweepResult(
            unscheduled=jnp.asarray(unscheduled), used=jnp.asarray(used),
            chosen=jnp.asarray(chosen), vg_used=jnp.asarray(vg_used),
        )
    if rungs["megakernel"] is None:
        from ..engine import fastpath

        try:
            with obs.span("sweep.megakernel", scenarios=S, profile=profile):
                unscheduled, used, chosen, vg_used = fastpath.sweep(
                    prep, node_valid_masks, pod_valid_masks, forced_masks, config=config
                )
            RECORDER.count_engine_profile("megakernel", profile)
            return SweepResult(
                unscheduled=unscheduled, used=used, chosen=chosen, vg_used=vg_used
            )
        except Exception as e:  # opensim-lint: disable=exception-swallow (kernel_failed raises or logs)
            select.kernel_failed(e, "sweep")  # demoted: the XLA sweep below computes the same
    from ..obs.profile import launch_span

    away = select.turned_away(prep, ask, pol, rungs)
    if away is not None:
        RECORDER.count_engine_declined(*away)
    RECORDER.count_engine_profile("xla", profile)
    with obs.span("sweep.xla", scenarios=S, devices=len(jax.devices()), profile=profile,
                  **kernels.count_reads(prep.ec, prep.features), **select.decline_attrs(prep, away)):
        with launch_span("xla.launch", scenarios=S, pods=len(prep.tmpl_ids)):
            res = sweep(
                prep.ec,
                prep.st0,
                prep.tmpl_ids,
                prep.forced,
                node_valid_masks,
                pod_valid_masks,
                mesh=default_mesh(),
                features=prep.features,
                forced_masks=np.asarray(forced_masks),
                config=config,
            )
        with obs.span("xla.wait"):
            jax.block_until_ready(res.chosen)  # dispatch is async; trace real device time
    return res


@functools.partial(jax.jit, static_argnames=("features", "config", "unroll"))
def _sweep_segment_impl(
    ec, st_batch, tmpl_ids, node_valid_masks, pod_valid_masks, forced_masks,
    features, config=None, unroll=1,
):
    """One segment of a segmented sweep: vmap over scenarios with a
    PER-SCENARIO carry (st_batch has a leading scenario axis — segment k's
    final states seed segment k+1)."""

    def one(st, nv, pv, fm):
        out = _schedule_pods_traced(
            ec._replace(node_valid=nv), st, tmpl_ids, pv, fm,
            features=features, config=config, unroll=unroll,
        )
        return out.chosen, out.final_state

    return jax.vmap(one)(st_batch, node_valid_masks, pod_valid_masks, forced_masks)


def sweep_segmented(
    prep,
    segments,
    node_valid_masks: np.ndarray,
    pod_valid_masks: np.ndarray,
    forced_masks: np.ndarray,
) -> SweepResult:
    """Scenario sweep over a MIXED-PROFILE stream: consecutive scans per
    contiguous same-profile segment, sharing each scenario's scheduling
    carry — ``simulate()``'s segmented path (``utils.go:304-381``) lifted
    to the scenario axis. Out-of-segment pods are mask-invalid per scan, so
    binds happen in exact stream order and placements per scenario equal a
    solo segmented simulate of that scenario (gated by
    tests/test_parallel.py). Sequential C++ scans where ``select`` gives
    every segment that rung (chaining ``st0`` between segments), the
    vmapped XLA scan with a batched carry otherwise."""
    from ..engine import nativepath
    from ..engine.schedconfig import DEFAULT_CONFIG

    S = node_valid_masks.shape[0]
    P = len(prep.ordered)
    segments = [
        (None if c == DEFAULT_CONFIG else c, lo, hi) for c, lo, hi in segments
    ]
    chosen = np.full((S, P), -1, dtype=np.int32)
    ask = select.Ask(shape="sweep", segments=len(segments))
    use_native = all(
        select.ladder(prep, ask._replace(sched_config=cfg))["native"] is None for cfg, _, _ in segments
    )
    vg0 = np.asarray(prep.st0.vg_free)
    nv_np = np.asarray(node_valid_masks, dtype=bool)
    from ..engine.schedconfig import profile_of
    from ..obs.metrics import RECORDER

    for cfg, _lo, _hi in segments:
        RECORDER.count_engine_profile("native" if use_native else "xla", profile_of(cfg))
    if use_native:
        used = np.zeros((S,) + np.asarray(prep.st0.used).shape, np.float32)
        vg_used = np.zeros((S,), np.float32)
        for s in range(S):
            st = prep.st0
            pv_s = np.asarray(pod_valid_masks[s], dtype=bool)
            for cfg, lo, hi in segments:
                seg_valid = np.zeros((P,), dtype=bool)
                seg_valid[lo:hi] = pv_s[lo:hi]
                out = nativepath.schedule(
                    prep, seg_valid, config=cfg, node_valid=nv_np[s],
                    forced=np.asarray(forced_masks[s], bool), st0=st,
                )
                chosen[s, lo:hi] = np.asarray(out.chosen)[lo:hi]
                st = out.final_state
            used[s] = np.asarray(st.used)
            vg_used[s] = float(
                ((vg0 - np.asarray(st.vg_free)) * nv_np[s][:, None]).sum()
            )
        unscheduled = (
            (chosen < 0) & np.asarray(pod_valid_masks, bool)
        ).sum(axis=1).astype(np.int32)
        return SweepResult(
            unscheduled=jnp.asarray(unscheduled), used=jnp.asarray(used),
            chosen=jnp.asarray(chosen), vg_used=jnp.asarray(vg_used),
        )
    # XLA path: batched carry across segments (each segment is one vmapped
    # dispatch; S scenarios advance in lockstep through the profile chain)
    st_batch = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(jnp.asarray(a), (S,) + jnp.asarray(a).shape),
        prep.st0,
    )
    nv_dev = jnp.asarray(nv_np)
    fm_dev = jnp.asarray(np.asarray(forced_masks, dtype=bool))
    final = None
    for cfg, lo, hi in segments:
        seg = np.zeros((S, P), dtype=bool)
        seg[:, lo:hi] = np.asarray(pod_valid_masks, bool)[:, lo:hi]
        from ..obs.profile import observed_jit_call

        seg_chosen, st_batch = observed_jit_call(
            "sweep_segment",
            _sweep_segment_impl,
            args=(
                prep.ec, st_batch, jnp.asarray(prep.tmpl_ids), nv_dev,
                jnp.asarray(seg), fm_dev,
            ),
            static={"features": prep.features, "config": cfg, "unroll": scan_unroll()},
        )
        chosen[:, lo:hi] = np.asarray(seg_chosen)[:, lo:hi]
        final = st_batch
    unscheduled = (
        (chosen < 0) & np.asarray(pod_valid_masks, bool)
    ).sum(axis=1).astype(np.int32)
    used = np.asarray(final.used)
    vg_used = (
        (vg0[None] - np.asarray(final.vg_free)) * nv_np[:, :, None]
    ).sum(axis=(1, 2)).astype(np.float32)
    return SweepResult(
        unscheduled=jnp.asarray(unscheduled), used=jnp.asarray(used),
        chosen=jnp.asarray(chosen), vg_used=jnp.asarray(vg_used),
    )


def sweep(
    ec: EncodedCluster,
    st0: ScanState,
    tmpl_ids: np.ndarray,
    forced: np.ndarray,
    node_valid_masks: np.ndarray,  # [S, N]
    pod_valid_masks: np.ndarray,  # [S, P]
    mesh: Optional[Mesh] = None,
    features=None,
    forced_masks: Optional[np.ndarray] = None,  # [S, P] — per-scenario override
    config=None,
) -> SweepResult:
    """Evaluate S scenarios in one compiled computation. With a mesh, the
    scenario axis is sharded across devices (pad S to a device multiple).
    `forced_masks` lets each scenario choose which pods stay pre-bound
    (defragmentation: a drained node's pods become schedulable again)."""
    from ..ops.kernels import ALL_FEATURES

    features = features or ALL_FEATURES
    S = node_valid_masks.shape[0]
    if forced_masks is None:
        forced_masks = np.broadcast_to(np.asarray(forced, dtype=bool), (S, len(forced))).copy()
    arrays = (node_valid_masks, pod_valid_masks, forced_masks)
    if mesh is not None:
        n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        pad = (-S) % n_dev
        if pad:
            arrays = tuple(np.concatenate([a, a[-1:].repeat(pad, 0)]) for a in arrays)
        shard = NamedSharding(mesh, P(mesh.axis_names[0]))
        if jax.process_count() > 1:
            # DCN path: the mesh spans processes, so scenario shards must be
            # assembled from each host's addressable slice (every host holds
            # the same full mask arrays — the planner builds them
            # deterministically) and the small per-scenario summaries are
            # gathered back to every host afterwards.
            arrays = tuple(
                jax.make_array_from_callback(
                    a.shape, shard, lambda idx, a=a: np.asarray(a)[idx]
                )
                for a in arrays
            )
            rep = NamedSharding(mesh, P())

            def _replicate(a):
                a = np.asarray(a)
                return jax.make_array_from_callback(a.shape, rep, lambda idx, a=a: a[idx])

            out = _sweep_impl(
                type(ec)(*[_replicate(x) for x in ec]),
                type(st0)(*[_replicate(x) for x in st0]),
                _replicate(np.asarray(tmpl_ids)),
                *arrays,
                features=features,
                config=config,
                unroll=scan_unroll(),
            )
            from jax.experimental import multihost_utils

            out = multihost_utils.process_allgather(out, tiled=True)
        else:
            arrays = tuple(jax.device_put(jnp.asarray(a), shard) for a in arrays)
            out = _sweep_impl(
                ec, st0, jnp.asarray(tmpl_ids), *arrays,
                features=features, config=config, unroll=scan_unroll(),
            )
        out = jax.tree_util.tree_map(lambda a: a[:S], out)
    else:
        from ..obs.profile import observed_jit_call

        out = observed_jit_call(
            "sweep",
            _sweep_impl,
            args=(ec, st0, jnp.asarray(tmpl_ids), *(jnp.asarray(a) for a in arrays)),
            static={"features": features, "config": config, "unroll": scan_unroll()},
        )
    return SweepResult(*out)


def default_mesh() -> Optional[Mesh]:
    """One-axis mesh over all local devices (scenario data parallelism)."""
    devices = jax.devices()
    if len(devices) <= 1:
        return None
    return Mesh(np.array(devices), ("s",))
