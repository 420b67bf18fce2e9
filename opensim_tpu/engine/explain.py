"""Decision-audit evaluators (ISSUE 7): normalize engine outputs into
:class:`~opensim_tpu.engine.reasons.PlacementExplanation` records and, on
demand, reconstruct one pod's full scoring decision.

Two tiers, priced differently:

- **Bulk** (``simulate(..., explain=True)``): every pod gets a record built
  from data the engines already produced — status, winning node, and for
  unschedulable pods the per-filter rejection counts the failure
  attribution computed. O(pods) host work, no per-node evaluation.
- **Deep** (:func:`explain_pod`, behind ``simon explain <pod>``): replay
  the scheduling state to the instant *before* the pod's step from the
  recorded placements, then re-evaluate the score pipeline through the
  SAME kernel functions the XLA scan runs (``kernels.score_parts`` is the
  scan's own accumulation order), yielding the per-plugin breakdown on the
  winning node and the margin over the runner-up. O(nodes) for one pod.

The engine-computed ``chosen`` stays authoritative throughout: the replayed
state is exact up to float summation order (``np.add.at`` accumulates in
index order where the scan accumulated in bind order), so the breakdown is
reported *about* the engine's winner, never used to re-decide it.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from ..ops import kernels
from . import reasons
from .reasons import PlacementExplanation, Reason

_precompute_static = jax.jit(kernels.precompute_static, static_argnames=("cfg",))


def rebuild_counts(prep, chosen: np.ndarray, upto: Optional[int] = None):
    """Host-side reconstruction of the ScanState count tensors (port_used,
    dom_sel, dom_anti, dom_prefw) from placements — the numpy mirror of
    ``kernels.bind_update``'s count updates. ``upto`` restricts to binds
    strictly before that stream index (deep-explain replay); None folds in
    every bind (the megakernel failure path)."""
    ec = prep.ec_np
    st0 = prep.st0
    chosen = np.asarray(chosen)
    bound = chosen >= 0
    if upto is not None:
        bound = bound.copy()
        bound[upto:] = False
    us = prep.tmpl_ids[: len(bound)][bound]
    cs = chosen[bound].astype(np.int64)

    port_used = np.array(np.asarray(st0.port_used), dtype=np.float32, copy=True)
    ports = np.asarray(ec.ports)[us]  # [B, Hp]
    pv = ports >= 0
    if pv.any():
        rows = np.repeat(cs, ports.shape[1])[pv.ravel()]
        np.add.at(port_used, (rows, ports.ravel()[pv.ravel()]), 1.0)

    dom_sel = np.array(np.asarray(st0.dom_sel), dtype=np.float32, copy=True)
    matches = np.asarray(ec.matches_sel)[us].astype(np.float32)  # [B, A]
    node_domain = np.asarray(ec.node_domain)
    for tk in range(node_domain.shape[1]):
        np.add.at(dom_sel, node_domain[cs, tk], matches)

    dom_anti = np.array(np.asarray(st0.dom_anti), dtype=np.float32, copy=True)
    anti_g_topo = np.asarray(ec.anti_g_topo)
    anti_g = np.asarray(ec.anti_g)[us].astype(np.float32)
    for g in range(anti_g_topo.shape[0]):
        np.add.at(dom_anti[:, g], node_domain[cs, anti_g_topo[g]], anti_g[:, g])

    dom_prefw = np.array(np.asarray(st0.dom_prefw), dtype=np.float32, copy=True)
    prefg_topo = np.asarray(ec.prefg_topo)
    prefg_w = np.asarray(ec.prefg_w)[us]
    for g in range(prefg_topo.shape[0]):
        np.add.at(dom_prefw[:, g], node_domain[cs, prefg_topo[g]], prefg_w[:, g])

    return port_used, dom_sel, dom_anti, dom_prefw


def replay_state(prep, chosen: np.ndarray, gpu_take: np.ndarray, upto: int):
    """The ScanState the scheduler saw right before stream index ``upto``,
    rebuilt from the recorded placements (chosen node + GPU slot packing per
    pod). used/ports/domain counts are pure sums; vg/dev state replays the
    deterministic tightest-fit packing sequentially over the (rare)
    local-storage binds."""
    from ..encoding.state import ScanState

    ec = prep.ec_np
    st0 = prep.st0
    chosen = np.asarray(chosen)
    bound = chosen >= 0
    bound = bound.copy()
    bound[upto:] = False
    us = prep.tmpl_ids[: len(bound)][bound]
    cs = chosen[bound].astype(np.int64)

    used = np.array(np.asarray(st0.used), dtype=np.float32, copy=True)
    np.add.at(used, cs, np.asarray(ec.req)[us])

    port_used, dom_sel, dom_anti, dom_prefw = rebuild_counts(prep, chosen, upto=upto)

    gpu_free = np.array(np.asarray(st0.gpu_free), dtype=np.float32, copy=True)
    if prep.features.gpu and len(cs):
        take = np.asarray(gpu_take)[: len(bound)][bound].astype(np.float32)  # [B, Gd]
        mem = np.asarray(ec.gpu_mem)[us].astype(np.float32)  # [B]
        np.add.at(gpu_free, cs, -(take * mem[:, None]))

    vg_free = np.array(np.asarray(st0.vg_free), dtype=np.float32, copy=True)
    dev_free = np.array(np.asarray(st0.dev_free), dtype=np.float32, copy=True)
    if prep.features.local:
        big = np.float32(1e30)
        lvm_req = np.asarray(ec.lvm_req)
        dev_req_sizes = np.asarray(ec.dev_req_sizes)
        node_dev_media = np.asarray(ec.node_dev_media)
        node_dev_cap = np.asarray(ec.node_dev_cap)
        Mv = dev_req_sizes.shape[2]
        for j in np.nonzero(bound)[0]:
            u = int(prep.tmpl_ids[j])
            node = int(chosen[j])
            lvm = float(lvm_req[u])
            vf = vg_free[node]
            if vf.shape[0]:
                fits = vf >= lvm
                if fits.any():
                    vf[np.argmin(np.where(fits, vf, big))] -= max(lvm, 0.0)
            df = dev_free[node]
            taken = np.zeros_like(df, dtype=bool)
            for media in (0, 1):
                for k in reversed(range(Mv)):  # ascending sizes; 0-pads skipped
                    size = float(dev_req_sizes[u, media, k])
                    if size <= 0.0:
                        continue
                    cand = (
                        (node_dev_media[node] == media) & (df >= size) & (df > 0) & ~taken
                    )
                    if cand.any():
                        taken[np.argmin(np.where(cand, node_dev_cap[node], big))] = True
            df[taken] = 0.0

    return ScanState(
        used=used, port_used=port_used, dom_sel=dom_sel, dom_anti=dom_anti,
        dom_prefw=dom_prefw, gpu_free=gpu_free, vg_free=vg_free, dev_free=dev_free,
    )


@dataclass
class ExplainContext:
    """Everything an on-demand deep explanation needs, captured by
    ``simulate(..., explain=True)`` and attached to ``EngineDecision``.
    Holds a reference to the (large) Prepared — meant for library/CLI
    callers; the REST layer serializes explanations and drops this."""

    prep: object
    chosen: np.ndarray
    gpu_take: np.ndarray
    static_fail: np.ndarray  # [U,4] or per-pod [P,4] (segments)
    sf_rows: np.ndarray      # pod index -> static_fail row
    fail_counts: np.ndarray  # [P, NUM_FILTERS-4]
    insufficient: np.ndarray  # [P, R]
    n_nodes: int
    node_names: Sequence[str]
    resource_names: Sequence[str]
    config: object = None
    segments: Optional[list] = None  # [(config_or_None, lo, hi)]
    extra_plugins: tuple = ()
    engine: str = ""
    # node mask of a masked re-simulation (planner prep reuse): the deep
    # audit must score exactly the node set the engine considered
    node_valid: Optional[np.ndarray] = None

    def config_for(self, i: int):
        if self.segments:
            for cfg, lo, hi in self.segments:
                if lo <= i < hi:
                    return cfg
        return self.config

    def index_of(self, pod_name: str) -> Optional[int]:
        """Stream index of ``ns/name`` or bare ``name``. Expanded pods carry
        generated uid suffixes (``web-00a3…-00a4…``), so a query that exactly
        matches no pod falls back to a workload-prefix match: the first pod
        whose name starts with ``<query>-`` wins when every such pod shares
        that prefix (one workload); distinct workloads raise ambiguity."""
        hit = None
        for i, p in enumerate(self.prep.ordered):
            full = f"{p.metadata.namespace}/{p.metadata.name}"
            if full == pod_name or p.metadata.name == pod_name:
                if hit is not None and p.metadata.name == pod_name:
                    raise ValueError(
                        f"pod name {pod_name!r} is ambiguous; use namespace/name"
                    )
                hit = i
                if full == pod_name:
                    return i
        if hit is not None:
            return hit
        import re

        bare = pod_name.rsplit("/", 1)[-1]
        ns = pod_name.rsplit("/", 1)[0] if "/" in pod_name else None
        # exactly <bare> plus generated uid segments: "web" matches
        # "web-00a3…-00a4…" but NOT another workload "web-frontend-…"
        gen = re.compile(re.escape(bare) + r"(-[0-9a-f]{10})+$")
        matches = [
            i
            for i, p in enumerate(self.prep.ordered)
            if gen.fullmatch(p.metadata.name)
            and (ns is None or p.metadata.namespace == ns)
        ]
        # first match in stream order — pods of one workload share a
        # template, so any member's explanation stands in for the workload
        return matches[0] if matches else None

    def reason_counts(self, i: int) -> List[reasons.ReasonCount]:
        return reasons.counts_from_rows(
            np.asarray(self.static_fail)[int(self.sf_rows[i])],
            self.fail_counts[i],
            self.insufficient[i],
            self.resource_names,
        )


def audit_rejects(static_fail, sf_rows, fail_counts, mask) -> np.ndarray:
    """Aggregate 11-slot per-filter reject totals (kernel filter-index
    order) from per-pod attribution rows. ``mask`` selects the audited pods
    (valid, unforced)."""
    rej = np.zeros(kernels.NUM_FILTERS, np.int64)
    mask = np.asarray(mask, dtype=bool)
    if mask.any():
        static_rows = np.asarray(static_fail)[np.asarray(sf_rows)[mask]]
        rej[: kernels.F_PORTS] = static_rows.sum(axis=0, dtype=np.int64)
        rej[kernels.F_PORTS:] = np.asarray(fail_counts)[mask].sum(axis=0, dtype=np.int64)
    return rej


def primary_reason_histogram(
    static_fail, sf_rows, fail_counts, failed_idx
) -> Dict[str, int]:
    """``{reason_name: pod count}`` over the unschedulable pods, each pod
    attributed to its dominant filter (max rejected nodes, ties by filter
    precedence — the argmax over the merged row takes the lowest index)."""
    out: Dict[str, int] = {}
    failed_idx = np.asarray(failed_idx)
    if not len(failed_idx):
        return out
    merged = np.concatenate(
        [
            np.asarray(static_fail)[np.asarray(sf_rows)[failed_idx]],
            np.asarray(fail_counts)[failed_idx],
        ],
        axis=1,
    )
    primary = np.argmax(merged, axis=1)
    # a pod with all-zero rows (e.g. no attribution ran) falls to slot 0;
    # report those as unattributed rather than inventing a hostname mismatch
    has_any = merged.max(axis=1) > 0
    for k in primary[has_any]:
        name = Reason(int(k)).name.lower()
        out[name] = out.get(name, 0) + 1
    n_unattr = int((~has_any).sum())
    if n_unattr:
        out["unattributed"] = n_unattr
    return out


def explain_pod(ctx: ExplainContext, i: int) -> PlacementExplanation:
    """Deep decision audit for one stream index: the bulk record plus — for
    scheduled pods — the per-plugin score breakdown on the winning node and
    the margin over the runner-up, evaluated against the replayed pre-bind
    state through the scan's own kernels."""
    import jax.numpy as jnp

    from ..encoding.state import ScanState

    prep = ctx.prep
    pod = prep.ordered[i]
    name = f"{pod.metadata.namespace}/{pod.metadata.name}"
    c = int(ctx.chosen[i])
    forced = bool(prep.forced[i])

    if forced:
        if c < 0:
            return PlacementExplanation(
                pod=name, status="unschedulable", nodes_total=ctx.n_nodes,
                forced=True, message=reasons.node_not_found(pod.spec.node_name),
            )
        return PlacementExplanation(
            pod=name, status="scheduled", nodes_total=ctx.n_nodes,
            node=str(ctx.node_names[c]), forced=True,
            message="pre-bound (spec.nodeName set); bypassed the scheduler",
        )

    if c < 0:
        counts = ctx.reason_counts(i)
        return PlacementExplanation(
            pod=name, status="unschedulable", nodes_total=ctx.n_nodes,
            reasons=counts,
            message=reasons.render_unschedulable(ctx.n_nodes, counts),
        )

    # scheduled: replay the pre-bind state and re-run the score pipeline
    u = int(prep.tmpl_ids[i])
    cfg = ctx.config_for(i)
    st = replay_state(prep, ctx.chosen, ctx.gpu_take, upto=i)
    st_dev = ScanState(*[jnp.asarray(a) for a in st])
    ec = prep.ec_np
    if ctx.node_valid is not None:
        ec = ec._replace(node_valid=np.ascontiguousarray(ctx.node_valid, dtype=bool))
    # the XLA scan's own static tables, jit-cached per shape and config
    stat = _precompute_static(ec, cfg)
    res = kernels.pod_step(
        ec, stat, st_dev, u, feat=prep.features, cfg=cfg,
        extra=ctx.extra_plugins,
    )
    parts = kernels.score_parts(
        ec, stat, st_dev, u, res.feasible, prep.features, cfg,
        ctx.extra_plugins,
    )
    score = np.asarray(res.score)
    feasible = np.asarray(res.feasible)
    scores = {k: round(float(np.asarray(v)[c]), 4) for k, v in parts.items()}
    total = round(float(score[c]), 4)
    runner_up = margin = None
    others = feasible.copy()
    others[c] = False
    if others.any():
        masked = np.where(others, score, -np.inf)
        ru = int(np.argmax(masked))
        runner_up = str(ctx.node_names[ru])
        margin = round(float(score[c] - score[ru]), 4)
    return PlacementExplanation(
        pod=name, status="scheduled", nodes_total=ctx.n_nodes,
        node=str(ctx.node_names[c]), scores=scores, score=total,
        runner_up=runner_up, margin=margin,
        message=f"scheduled on {ctx.node_names[c]} "
        f"(score {total}"
        + (f", margin {margin} over {runner_up}" if runner_up is not None else "")
        + ")",
    )


def build_explanations(
    ctx: ExplainContext,
    custom_reasons: Dict[int, str],
    victims_of: Dict[int, int],
    drops=(),
) -> List[PlacementExplanation]:
    """Bulk tier: one record per pod in the stream (dropped pods excluded),
    from data the engines already produced — no per-node work."""
    out: List[PlacementExplanation] = []
    ordered = ctx.prep.ordered
    forced = ctx.prep.forced
    for i, pod in enumerate(ordered):
        if i in drops:
            continue
        name = f"{pod.metadata.namespace}/{pod.metadata.name}"
        c = int(ctx.chosen[i])
        if forced[i] and c < 0:
            out.append(
                PlacementExplanation(
                    pod=name, status="unschedulable", nodes_total=ctx.n_nodes,
                    forced=True,
                    message=reasons.node_not_found(pod.spec.node_name),
                )
            )
        elif c >= 0:
            out.append(
                PlacementExplanation(
                    pod=name, status="scheduled", nodes_total=ctx.n_nodes,
                    node=str(ctx.node_names[c]), forced=bool(forced[i]),
                )
            )
        elif i in custom_reasons:
            out.append(
                PlacementExplanation(
                    pod=name, status="unschedulable", nodes_total=ctx.n_nodes,
                    message=custom_reasons[i],
                )
            )
        elif i in victims_of:
            p = ordered[victims_of[i]]
            out.append(
                PlacementExplanation(
                    pod=name, status="preempted", nodes_total=ctx.n_nodes,
                    message=reasons.preempted(p.metadata.namespace, p.metadata.name),
                )
            )
        else:
            counts = ctx.reason_counts(i)
            out.append(
                PlacementExplanation(
                    pod=name, status="unschedulable", nodes_total=ctx.n_nodes,
                    reasons=counts,
                    message=reasons.render_unschedulable(ctx.n_nodes, counts),
                )
            )
    return out
