"""Filter / score kernels — the vectorized scheduler plugin pipeline.

Each kernel computes over the FULL node axis at once, replacing the
reference's goroutine fan-out (``parallelize.Until`` with 16 workers,
``vendor/.../internal/parallelize/parallelism.go:56``) with data
parallelism on the TPU vector units. One ``pod_step`` = one pod through
Filter → Score → selectHost, exactly the pipeline of
``generic_scheduler.Schedule`` (``vendor/.../core/generic_scheduler.go:131-180``)
with ``PercentageOfNodesToScore = 100`` (``pkg/simulator/utils.go:370``).

Kernel ↔ reference-plugin parity map (score weights from
``algorithmprovider/registry.go:119-132``):
  filter: NodeName, NodeUnschedulable, TaintToleration, NodeAffinity,
          NodePorts, NodeResourcesFit, PodTopologySpread, InterPodAffinity,
          GpuShare (open-gpu-share.go:51-81), OpenLocal (open-local.go:51-92)
  score:  BalancedAllocation (w1), ImageLocality (w1, 0 — no images in sim),
          InterPodAffinity (w1), LeastAllocated (w1), NodeAffinity (w1),
          NodePreferAvoidPods (w10000, annotation table), PodTopologySpread (w2),
          TaintToleration (w1), Simon share (w1, plugin/simon.go:45-101),
          GpuShare share (w1), OpenLocal (w1); RequestedToCapacityRatio
          (off unless a profile enables it: the bin-packing score)

All functions take the EncodedCluster (`ec`), the scan carry (`st`) and a
traced template index `u`; shapes are static.
"""

from __future__ import annotations

import itertools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..encoding import vocab as V
from ..encoding.state import EncodedCluster, ScanState

MAX_NODE_SCORE = 100.0

# Filter kernel ids (order = reason-attribution precedence, roughly the
# order the default profile runs them).
F_NODE_PIN = 0  # NodeName
F_UNSCHEDULABLE = 1
F_TAINT = 2
F_AFFINITY = 3  # NodeAffinity + nodeSelector
F_PORTS = 4
F_FIT = 5  # NodeResourcesFit
F_SPREAD = 6
F_INTERPOD = 7
F_GPU = 8
F_LOCAL = 9
F_EXTRA = 10  # out-of-tree plugins registered via extra_plugins
NUM_FILTERS = 11

# the registered reason-code table (engine/reasons.py, ISSUE 7): one copy
# of the kube FitError phrasings shared by every engine and report surface
from ..engine.reasons import FILTER_MESSAGES as FILTER_REASONS  # noqa: E402


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _gather_label(label_arr, keys):
    """label_arr [N, K], keys [...]-shaped int32 (may be -1) →
    values [N, ...]; -1 keys yield -1/NaN."""
    safe = jnp.maximum(keys, 0)
    vals = label_arr[:, safe]  # [N, ...]
    return vals


def _requirements_match(ec, keys, ops, vals, nums):
    """Evaluate node-selector requirements against all nodes.

    keys/ops/nums: [...]; vals: [..., Vv]. Returns bool [N, ...] — True
    where the requirement holds (padding requirements are vacuously True).
    """
    node_val = _gather_label(ec.label_val, keys)  # [N, ...]
    node_num = _gather_label(ec.label_num, keys)  # [N, ...]
    present = node_val >= 0
    in_set = jnp.any(node_val[..., None] == vals[None, ...], axis=-1)  # [N, ...]
    ops_b = ops[None, ...]
    result = jnp.ones_like(present)
    result = jnp.where(ops_b == V.OP_IN, present & in_set, result)
    result = jnp.where(ops_b == V.OP_NOT_IN, ~(present & in_set), result)
    result = jnp.where(ops_b == V.OP_EXISTS, present, result)
    result = jnp.where(ops_b == V.OP_DOES_NOT_EXIST, ~present, result)
    result = jnp.where(ops_b == V.OP_GT, node_num > nums[None, ...], result)
    result = jnp.where(ops_b == V.OP_LT, node_num < nums[None, ...], result)
    return result


def _split(x):
    """Veltkamp's split of a float32 into two halves of 12 bits whose
    products are exact."""
    c = x * 4097.0
    hi = c - (c - x)
    return hi, x - hi


def div32(a, b):
    """``a / b`` rounded as IEEE float32 division rounds it, on any backend.

    A TPU's float32 quotient (XLA's and Mosaic's alike) is up to 2 ulp off
    in a third of cases; the scores are compared with a float32 reference
    down to the last bit, and one node chosen otherwise changes the pods
    after it. The hardware quotient is corrected once by its own residual,
    which Dekker's product gives exactly without a fused multiply-add. On a
    backend whose quotient is already IEEE's the correction changes nothing."""
    return _corrected(a, b, a / b)


def _corrected(a, b, q):
    """``q``, a quotient of ``a / b`` within a few ulp, moved to the float32
    nearest ``a / b``: q + (a - q*b) / b with the residual taken exactly."""
    qh, ql = _split(q)
    bh, bl = _split(b)
    p = q * b
    e = ((qh * bh - p) + qh * bl + ql * bh) + ql * bl
    return q + ((a - p) - e) / b


def floor_div32(a, b):
    """``floor(a / b)`` exactly, on any backend, for the device arithmetic of
    gpu-share: how many requests of ``b`` fit what a device has free. A
    TPU's float32 quotient is up to 2 ulp off (see ``div32``), so a device
    with exactly two halves free can read 1.9999999 and floor to one. Shared
    by the XLA scan and the megakernel so the two stay op for op."""
    return _floor_corrected(a, b, a / b)


def _floor_corrected(a, b, q):
    """The floor of ``q``, a quotient of ``a / b`` within a few ulp, moved to
    the floor of the true quotient by the residual it leaves. Operands are
    whole numbers of Mi under 2**24 Mi, so ``floor(q) * b`` and the residual
    are exact in float32."""
    f = jnp.floor(q)
    r = a - f * b
    return jnp.where(r < 0, f - 1.0, jnp.where(r >= b, f + 1.0, f))


def _minmax_normalize(scores, feasible):
    """SimonPlugin.NormalizeScore (plugin/simon.go:76-101): min-max over the
    feasible set to [0, 100]; degenerate range → 0."""
    big = jnp.float32(1e30)
    lo = jnp.min(jnp.where(feasible, scores, big))
    hi = jnp.max(jnp.where(feasible, scores, -big))
    rng = hi - lo
    return jnp.where(rng > 0, div32((scores - lo) * MAX_NODE_SCORE, rng), 0.0)


# ---------------------------------------------------------------------------
# filter kernels
# ---------------------------------------------------------------------------

def taint_filter(ec, u):
    """TaintToleration: every NoSchedule/NoExecute taint must be tolerated."""
    t_key = ec.taint_key  # [N, Tt]
    t_val = ec.taint_val
    t_eff = ec.taint_effect
    tol_valid = ec.tol_valid[u]  # [Tl]
    tol_key = ec.tol_key[u]
    tol_op = ec.tol_op[u]
    tol_val = ec.tol_val[u]
    tol_eff = ec.tol_effect[u]

    # [N, Tt, Tl]: does toleration l tolerate taint t?
    key_ok = (tol_key[None, None, :] == -1) | (tol_key[None, None, :] == t_key[:, :, None])
    eff_ok = (tol_eff[None, None, :] == -1) | (tol_eff[None, None, :] == t_eff[:, :, None])
    val_ok = jnp.where(
        tol_op[None, None, :] == V.TOL_EXISTS, True, tol_val[None, None, :] == t_val[:, :, None]
    )
    # empty-key (-1) tolerations require operator Exists to match all
    empty_key_bad = (tol_key[None, None, :] == -1) & (tol_op[None, None, :] != V.TOL_EXISTS)
    tolerated = key_ok & eff_ok & val_ok & ~empty_key_bad & tol_valid[None, None, :]
    taint_tolerated = jnp.any(tolerated, axis=-1)  # [N, Tt]
    taint_blocking = (t_eff == V.EFFECT_NO_SCHEDULE) | (t_eff == V.EFFECT_NO_EXECUTE)
    return ~jnp.any(taint_blocking & ~taint_tolerated, axis=-1)


def node_affinity_filter(ec, u):
    """NodeAffinity plugin: nodeSelector map AND required node affinity
    (OR over terms, AND over requirements)."""
    # nodeSelector map: each (key, val) must match exactly.
    ns_key = ec.ns_key[u]  # [Qs]
    ns_val = ec.ns_val[u]
    node_val = _gather_label(ec.label_val, ns_key)  # [N, Qs]
    sel_ok = jnp.all((ns_key[None, :] < 0) | (node_val == ns_val[None, :]), axis=-1)

    req_ok = _requirements_match(ec, ec.aff_key[u], ec.aff_op[u], ec.aff_val[u], ec.aff_num[u])
    term_ok = jnp.all(req_ok, axis=-1)  # [N, T] AND over requirements
    term_valid = ec.aff_term_valid[u]  # [T]
    any_term = jnp.any(term_ok & term_valid[None, :], axis=-1)
    aff_ok = jnp.where(ec.has_req_aff[u], any_term, True)
    return sel_ok & aff_ok


def ports_filter(ec, st, u):
    """NodePorts: requested host ports must be free on the node. A request
    conflicts with any in-use port its conflict row overlaps — wildcard
    0.0.0.0 overlaps every specific hostIP on the same port/protocol
    (nodeports.go ckConflict)."""
    ports = ec.ports[u]  # [Hp]
    safe = jnp.maximum(ports, 0)
    conf = ec.port_conflict[safe].astype(jnp.float32)  # [Hp, Hports]
    hits = st.port_used @ conf.T  # [N, Hp] — weighted count of conflicting uses
    conflict = (ports[None, :] >= 0) & (hits > 0)
    return ~jnp.any(conflict, axis=-1)


def gc_row_of(ec) -> int:
    """Host-side resource-axis row of alibabacloud.com/gpu-count, -1 when
    absent. The single source for the engines' static `gc_row` parameter —
    keep fastpath/preemption in lockstep through this."""
    import numpy as np

    mask = np.asarray(ec.gc_mask)
    return int(np.argmax(mask)) if mask.any() else -1


def gc_dynamic_alloc(ec, st):
    """The gpushare Reserve rewrite (open-gpu-share.go:177-182 →
    ExportGpuNodeInfoAsNodeGpuInfo, gpunodeinfo.go:354-369): a device-bearing
    node's ``gpu-count`` allocatable is the count of devices that are not
    fully used. Returns (dyn [N] f32, has_dev [N] bool)."""
    valid_dev = ec.node_gpu_mem > 0
    dyn = jnp.sum(valid_dev & (st.gpu_free > 0), axis=-1).astype(jnp.float32)
    return dyn, jnp.any(valid_dev, axis=-1)


def effective_alloc(ec, st):
    """Allocatable with the dynamic gpu-count column substituted on
    device-bearing nodes (all other columns — and device-less nodes, whose
    fake-client objects the reference never updates — stay static)."""
    dyn, has_dev = gc_dynamic_alloc(ec, st)
    return jnp.where(ec.gc_mask[None, :] & has_dev[:, None], dyn[:, None], ec.alloc)


def fit_filter(ec, st, u, alloc=None, ignored_cols: tuple = ()):
    """NodeResourcesFit (noderesources/fit.go:195-260): requested resources
    must fit allocatable - used. Returns (mask, insufficient [N, R]).
    `alloc` overrides ec.alloc (the Features.gc_dyn dynamic-allocatable
    path); `ignored_cols` are static resource columns the filter skips
    (NodeResourcesFitArgs.ignoredResources, fit.go podutil filtering)."""
    alloc = ec.alloc if alloc is None else alloc
    req = ec.req[u]  # [R]
    insufficient = (req[None, :] > 0) & (st.used + req[None, :] > alloc)
    for c in ignored_cols:
        insufficient = insufficient.at[:, c].set(False)
    return ~jnp.any(insufficient, axis=-1), insufficient


def spread_filter(ec, st, u, node_aff_mask, keys=None):
    """PodTopologySpread DoNotSchedule constraints
    (podtopologyspread/filtering.go:276): for each hard constraint,
    matchCount(domain) + selfMatch - minMatch(eligible domains) <= maxSkew."""
    topo = ec.spr_topo[u]  # [Cs] topo-key idx, -1 pad
    sel = ec.spr_sel[u]
    skew = ec.spr_skew[u]
    hard = ec.spr_hard[u]
    active = (topo >= 0) & hard

    dom = ec.node_domain[:, jnp.maximum(topo, 0)]  # [N, Cs]
    has_label = dom < ec.domain_topo.shape[0] - 1  # trash row = missing label
    cnt = domain_counts(st.dom_sel, dom, sel, jnp.maximum(topo, 0), keys)  # [N, Cs]
    self_match = ec.matches_sel[u, sel]  # [Cs]

    # min matchNum over eligible domains: nodes passing node affinity with the
    # label present (k8s filtering.go calPreFilterState node filter).
    eligible = node_aff_mask[:, None] & has_label & ec.node_valid[:, None]
    big = jnp.float32(1e30)
    min_cnt = jnp.min(jnp.where(eligible, cnt, big), axis=0)  # [Cs]
    ok = cnt + self_match[None, :].astype(jnp.float32) - min_cnt <= skew[None, :].astype(jnp.float32)
    ok = ok & has_label  # nodes missing the topology label fail the constraint
    return jnp.all(ok | ~active[None, :], axis=-1)


def interpod_filter(ec, st, u, keys=None):
    """InterPodAffinity filter (interpodaffinity/filtering.go:378):
    1) incoming pod's required anti-affinity: no existing pod in the
       candidate's topology domain may match;
    2) existing pods' anti-affinity terms must not match the incoming pod;
    3) incoming pod's required affinity: some domain pod matches (with the
       self-match bootstrap rule)."""
    D_trash = ec.domain_topo.shape[0] - 1

    # (1) incoming anti terms
    an_sel = ec.an_sel[u]  # [Tn]
    an_topo = ec.an_topo[u]
    an_active = an_sel >= 0
    dom = ec.node_domain[:, an_topo]  # [N, Tn]
    anti_cnt = domain_counts(st.dom_sel, dom, jnp.maximum(an_sel, 0), an_topo, keys)  # [N, Tn]
    # k8s: a node missing the topology label forms no topology pair, so the
    # anti-affinity term is vacuously satisfied there.
    has_label = dom < D_trash
    anti_ok = jnp.all(~an_active[None, :] | ~has_label | (anti_cnt == 0), axis=-1)

    # (2) existing pods' anti terms (symmetric check); label-less candidate
    # nodes can't be in any violating domain
    g_topo = ec.anti_g_topo  # [G]
    g_sel = ec.anti_g_sel
    dom_g = ec.node_domain[:, g_topo]  # [N, G]
    has_label_g = dom_g < D_trash
    exist_cnt = st.dom_anti[dom_g, jnp.arange(g_topo.shape[0])[None, :]]  # [N, G]
    incoming_matches = ec.matches_sel[u, g_sel]  # [G]
    sym_ok = jnp.all(~(has_label_g & (exist_cnt > 0) & incoming_matches[None, :]), axis=-1)

    # (3) incoming required affinity terms. All of a template's terms share
    # one conjunction selector id (templates.py), so `aff_cnt` counts pods
    # matching ALL terms — k8s's topologyToMatchedAffinityTerms basis
    # (filtering.go:113-127). satisfyPodAffinity (filtering.go:347-374):
    # every term's topology label must exist on the node; the first-pod
    # bootstrap needs the GLOBAL count map empty AND a full self-match, and
    # still requires the labels.
    at_sel = ec.at_sel[u]  # [Ti]
    at_topo = ec.at_topo[u]
    at_active = at_sel >= 0
    dom_a = ec.node_domain[:, at_topo]  # [N, Ti]
    aff_cnt = domain_counts(st.dom_sel, dom_a, jnp.maximum(at_sel, 0), at_topo, keys)  # [N, Ti]
    has_label_a = dom_a < D_trash
    dom_is_key = ec.domain_topo[None, :] == at_topo[:, None]  # [Ti, D+1]
    total = jnp.sum(jnp.where(dom_is_key, selector_columns(st.dom_sel, jnp.maximum(at_sel, 0)).T, 0.0), axis=-1)
    map_empty = jnp.sum(jnp.where(at_active, total, 0.0)) == 0
    self_match = ec.matches_sel[u, jnp.maximum(at_sel, 0)]  # [Ti]
    bootstrap = map_empty & jnp.all(~at_active | self_match) & jnp.any(at_active)
    per_term_ok = ~at_active[None, :] | (has_label_a & (aff_cnt > 0))
    labels_ok = ~at_active[None, :] | has_label_a
    aff_ok = jnp.all(per_term_ok, axis=-1) | (jnp.all(labels_ok, axis=-1) & bootstrap)

    return anti_ok & sym_ok & aff_ok


def gpu_filter(ec, st, u):
    """Open-Gpu-Share filter (open-gpu-share.go:51-81 + AllocateGpuId,
    gpunodeinfo.go:232-290): per-GPU memory × count must be packable. The
    greedy multi-GPU packing with device reuse is equivalent to
    sum_d floor(free_d / mem) >= count."""
    mem = ec.gpu_mem[u]
    cnt = ec.gpu_count[u].astype(jnp.float32)
    chunks = jnp.sum(floor_div32(st.gpu_free, jnp.maximum(mem, 1.0)), axis=-1)  # [N]
    ok = (chunks >= cnt) & (cnt > 0)
    return jnp.where(mem > 0, ok, True)


def local_filter(ec, st, u):
    """Open-Local filter (open-local.go:51-92): LVM request fits the best
    VG; exclusive-device volumes must admit a one-device-per-volume
    matching (CheckExclusiveResourceMeetsPVCSize, common.go:290-349).
    With volume sizes sorted descending, a matching exists iff the i-th
    largest volume has at least i free fitting devices (Hall's condition
    on the nested fit sets)."""
    lvm = ec.lvm_req[u]
    lvm_ok = jnp.max(st.vg_free, axis=-1) >= lvm
    ok = jnp.where(lvm > 0, lvm_ok, True)
    for media in (0, 1):
        sizes = ec.dev_req_sizes[u, media]  # [Mv] descending, 0 pad
        free = st.dev_free  # [N, Dv]
        fitting = (
            (ec.node_dev_media[:, None, :] == media)
            & (free[:, None, :] >= sizes[None, :, None])
            & (free[:, None, :] > 0)
        )  # [N, Mv, Dv]
        fit_cnt = jnp.sum(fitting, axis=-1)  # [N, Mv]
        rank = jnp.arange(sizes.shape[0]) + 1  # [Mv]
        ok = ok & jnp.all((sizes[None, :] <= 0) | (fit_cnt >= rank[None, :]), axis=-1)
    return ok


# ---------------------------------------------------------------------------
# score kernels
# ---------------------------------------------------------------------------

def _nonzero_req(ec, u):
    """GetNonzeroRequests defaults: 100m CPU / 200Mi memory when a pod
    declares no request (used by Least/BalancedAllocation)."""
    cpu = ec.req[u, V.RES_CPU]
    mem = ec.req[u, V.RES_MEMORY]
    return jnp.where(cpu > 0, cpu, 100.0), jnp.where(mem > 0, mem, 200.0 * 1024 * 1024)


def least_allocated_score(ec, st, u):
    """NodeResourcesLeastAllocated (least_allocated.go:93-117)."""
    cpu_req, mem_req = _nonzero_req(ec, u)
    cpu_score = _least_requested(st.used[:, V.RES_CPU] + cpu_req, ec.alloc[:, V.RES_CPU])
    mem_score = _least_requested(st.used[:, V.RES_MEMORY] + mem_req, ec.alloc[:, V.RES_MEMORY])
    return (cpu_score + mem_score) / 2.0


def _least_requested(requested, capacity):
    score = div32((capacity - requested) * MAX_NODE_SCORE, jnp.maximum(capacity, 1.0))
    return jnp.where((capacity == 0) | (requested > capacity), 0.0, score)


def rtcr_utilization(q, requested, capacity):
    """RequestedToCapacityRatio's utilization of one resource from ``q``,
    LeastAllocated's quotient ``(capacity - requested) * 100 / capacity``
    (``div32``): ``100 - q``, or 100 where the capacity is 0 or exceeded
    (requested_to_capacity_ratio.go reads the shape at maxUtilization there)."""
    return jnp.where((capacity == 0) | (requested > capacity), MAX_NODE_SCORE, MAX_NODE_SCORE - q)


def rtcr_shape_score(util, shape):
    """kube's broken-linear function of a shape (helper/shape_score.go) in
    float32 and unrounded: ``shape[0]``'s score up to its utilization, the
    last score beyond the last, else on the segment i that holds ``util``
    ``s[i-1] + (s[i] - s[i-1]) * (util - u[i-1]) / (u[i] - u[i-1])``, the
    quotient by ``div32``. A segment whose slope is exactly 1 is folded at
    trace time to ``s[i-1] + (util - u[i-1])``, as the plain reference folds
    it. The XLA scan and the megakernel both call this on their own rows."""
    out = jnp.full_like(util, shape[-1][1])
    for i in range(len(shape) - 1, -1, -1):
        u_i, s_i = shape[i]
        if i == 0:
            val = s_i
        else:
            u_p, s_p = shape[i - 1]
            if s_i - s_p == u_i - u_p:
                val = s_p + (util - u_p)
            else:
                val = s_p + div32((s_i - s_p) * (util - u_p), jnp.full_like(util, u_i - u_p))
        out = jnp.where(util <= u_i, val, out)
    return out


def _power_of_two(x: float) -> bool:
    return x >= 1 and float(x).is_integer() and (int(x) & (int(x) - 1)) == 0


def rtcr_mean(weighted):
    """The node's RequestedToCapacityRatio score from ((weight, f), ...) in
    the profile's resource order: ``sum w*f / sum w`` over the resources whose
    ``f`` is above 0, 0 where none is (the kube 1.21 rule). The sums run in
    the given order. Where every sum of weights the rule can leave is a power
    of two (cpu and memory at weight 1: 1 or 2) the quotient is exact as a
    product and is taken as one, by a reciprocal chosen per node; else by
    ``div32``."""
    num = den = None
    for w, f in weighted:
        term = f if w == 1.0 else w * f
        num = term if num is None else num + term
        d = jnp.where(f > 0, w, 0.0)
        den = d if den is None else den + d
    weights = [w for w, _f in weighted]
    sums = {sum(c) for k in range(1, len(weights) + 1) for c in itertools.combinations(weights, k)}
    if all(_power_of_two(x) for x in sums):
        recip = None
        for x in sorted(sums - {1.0}):
            recip = jnp.where(den == x, 1.0 / x, 1.0 if recip is None else recip)
        return num if recip is None else num * recip
    return div32(num, jnp.maximum(den, 1.0))


def rtcr_score(ec, st, u, cfg):
    """RequestedToCapacityRatio (requested_to_capacity_ratio.go, kube 1.21)
    over ``cfg.rtcr_resources``: for cpu and memory the requested amount is
    LeastAllocated's (the node's total plus the pod's non-zero request), for
    another resource the plain request; a column of -1 is a resource the
    cluster does not have (capacity 0, read at utilization 100)."""
    cpu_req, mem_req = _nonzero_req(ec, u)
    weighted = []
    for col, w in cfg.rtcr_resources:
        if col < 0:
            util = jnp.full(ec.alloc.shape[:1], MAX_NODE_SCORE, jnp.float32)
        else:
            req = cpu_req if col == V.RES_CPU else mem_req if col == V.RES_MEMORY else ec.req[u, col]
            requested, capacity = st.used[:, col] + req, ec.alloc[:, col]
            q = div32((capacity - requested) * MAX_NODE_SCORE, jnp.maximum(capacity, 1.0))
            util = rtcr_utilization(q, requested, capacity)
        weighted.append((w, rtcr_shape_score(util, cfg.rtcr_shape)))
    return rtcr_mean(weighted)


def balanced_allocation_score(ec, st, u):
    """NodeResourcesBalancedAllocation (balanced_allocation.go:82-112)."""
    cpu_req, mem_req = _nonzero_req(ec, u)
    cpu_frac = div32(st.used[:, V.RES_CPU] + cpu_req, jnp.maximum(ec.alloc[:, V.RES_CPU], 1.0))
    mem_frac = div32(st.used[:, V.RES_MEMORY] + mem_req, jnp.maximum(ec.alloc[:, V.RES_MEMORY], 1.0))
    score = (1.0 - jnp.abs(cpu_frac - mem_frac)) * MAX_NODE_SCORE
    return jnp.where((cpu_frac >= 1.0) | (mem_frac >= 1.0), 0.0, score)


def node_affinity_raw(ec, u):
    """NodeAffinity score (pre-normalization): sum of matching
    preferred-term weights; DefaultNormalizeScore (max → 100) is applied in
    pod_step over the feasible set."""
    req_ok = _requirements_match(ec, ec.pna_key[u], ec.pna_op[u], ec.pna_val[u], ec.pna_num[u])
    term_ok = jnp.all(req_ok, axis=-1)  # [N, Pp]
    weights = ec.pna_weight[u]  # [Pp]
    return jnp.sum(jnp.where(term_ok, weights[None, :], 0.0), axis=-1)


def taint_toleration_raw(ec, u):
    """TaintToleration score input: count of intolerable PreferNoSchedule
    taints; reverse DefaultNormalizeScore is applied in pod_step."""
    t_key, t_val, t_eff = ec.taint_key, ec.taint_val, ec.taint_effect
    tol_valid = ec.tol_valid[u]
    tol_key, tol_op, tol_val, tol_eff = ec.tol_key[u], ec.tol_op[u], ec.tol_val[u], ec.tol_effect[u]
    key_ok = (tol_key[None, None, :] == -1) | (tol_key[None, None, :] == t_key[:, :, None])
    eff_ok = (tol_eff[None, None, :] == -1) | (tol_eff[None, None, :] == t_eff[:, :, None])
    val_ok = jnp.where(
        tol_op[None, None, :] == V.TOL_EXISTS, True, tol_val[None, None, :] == t_val[:, :, None]
    )
    empty_key_bad = (tol_key[None, None, :] == -1) & (tol_op[None, None, :] != V.TOL_EXISTS)
    tolerated = jnp.any(key_ok & eff_ok & val_ok & ~empty_key_bad & tol_valid[None, None, :], axis=-1)
    return jnp.sum((t_eff == V.EFFECT_PREFER_NO_SCHEDULE) & ~tolerated, axis=-1).astype(jnp.float32)


def interpod_score(ec, st, u, feasible, keys=None):
    """InterPodAffinity score (interpodaffinity/scoring.go): incoming
    preferred terms against existing pods + existing pods' symmetric
    preferred/hard-affinity terms against the incoming pod, min-max
    normalized over the feasible set (min/max seeded with 0 per k8s)."""
    D_trash = ec.domain_topo.shape[0] - 1
    # incoming side: pt terms gather dom_sel counts; nodes missing the
    # topology label form no pair (k8s: no contribution, not trash-row reads)
    pt_sel = ec.pt_sel[u]  # [Tpp]
    pt_topo = ec.pt_topo[u]
    pt_w = ec.pt_w[u]
    dom = ec.node_domain[:, pt_topo]  # [N, Tpp]
    has_label = dom < D_trash
    cnt = domain_counts(st.dom_sel, dom, jnp.maximum(pt_sel, 0), pt_topo, keys)
    incoming = jnp.sum(
        jnp.where((pt_sel[None, :] >= 0) & has_label, cnt * pt_w[None, :], 0.0), axis=-1
    )

    # symmetric side: existing pods' terms whose selector matches the pod
    g_topo = ec.prefg_topo  # [Gp]
    g_sel = ec.prefg_sel
    dom_g = ec.node_domain[:, g_topo]  # [N, Gp]
    has_label_g = dom_g < D_trash
    w_sum = st.dom_prefw[dom_g, jnp.arange(g_topo.shape[0])[None, :]]  # [N, Gp]
    matches = ec.matches_sel[u, g_sel].astype(jnp.float32)  # [Gp]
    symmetric = jnp.sum(jnp.where(has_label_g, w_sum * matches[None, :], 0.0), axis=-1)

    raw = incoming + symmetric
    masked = jnp.where(feasible, raw, 0.0)
    hi = jnp.maximum(jnp.max(masked), 0.0)
    lo = jnp.minimum(jnp.min(masked), 0.0)
    rng = hi - lo
    return jnp.where(rng > 0, div32(MAX_NODE_SCORE * (raw - lo), jnp.maximum(rng, 1.0)), 0.0)


def spread_score(ec, stat: StaticTables, st, u, feasible, keys=None):
    """PodTopologySpread score (podtopologyspread/scoring.go:175-248):
    ScheduleAnyway constraints; score_n = Σ_c cnt*log-weight + (maxSkew-1),
    inverted-normalized so spreading wins. The log(size+2) normalizing
    weight uses the statically precomputed per-key domain count."""
    topo = ec.spr_topo[u]  # [Cs]
    sel = ec.spr_sel[u]
    skew = ec.spr_skew[u].astype(jnp.float32)
    soft = (topo >= 0) & ~ec.spr_hard[u]
    any_soft = jnp.any(soft)

    D_trash = ec.domain_topo.shape[0] - 1
    dom = ec.node_domain[:, jnp.maximum(topo, 0)]  # [N, Cs]
    has_label = dom < D_trash
    cnt = domain_counts(st.dom_sel, dom, sel, jnp.maximum(topo, 0), keys)  # [N, Cs]

    ignored = feasible & ~jnp.all(has_label | ~soft[None, :], axis=-1)  # [N]
    scored = feasible & ~ignored
    weight = stat.spread_weight[jnp.maximum(topo, 0)]  # [Cs]

    contrib = jnp.where(soft[None, :] & has_label, cnt * weight[None, :] + (skew[None, :] - 1.0), 0.0)
    raw = jnp.sum(contrib, axis=-1)  # [N]

    big = jnp.float32(1e30)
    mn = jnp.min(jnp.where(scored, raw, big))
    mx = jnp.max(jnp.where(scored, raw, -big))
    norm = jnp.where(
        mx <= 0, MAX_NODE_SCORE, div32(MAX_NODE_SCORE * (mx + mn - raw), jnp.maximum(mx, 1.0))
    )
    norm = jnp.where(ignored, 0.0, norm)
    return jnp.where(any_soft, norm, 0.0)


def share_raw(ec, u):
    """Simon / Open-Gpu-Share share score (plugin/simon.go:45-74 +
    algo.Share, pkg/algo/greed.go:70-83), pre-normalization: max over
    node-allocatable resources of req/(allocatable - req). Allocatable is
    static — the fake client's node objects are never decremented — EXCEPT
    the gpu-count column on device-bearing nodes, which the gpushare
    Reserve rewrites (open-gpu-share.go:177-182): that column is excluded
    here and re-added per step by gc_share_dyn when Features.gc_dyn."""
    req = ec.req[u].at[V.RES_PODS].set(0.0)  # 'pods' request is not in PodRequestsAndLimits
    avail = ec.alloc - req[None, :]
    share = jnp.where(
        avail == 0, jnp.where(req[None, :] == 0, 0.0, 1.0), div32(jnp.broadcast_to(req[None, :], avail.shape), avail)
    )
    # only resources the node actually declares participate; negative shares
    # (req > allocatable) floor at 0 like the Go accumulator starting at 0
    share = jnp.where(ec.alloc > 0, share, 0.0)
    # the gpu-count column is DYNAMIC on device-bearing nodes (the gpushare
    # Reserve rewrite, open-gpu-share.go:177-182): its static contribution is
    # excluded here and pod_step adds the usage-dependent term per step
    # (gc_share_dyn). The exclusion MUST mirror Features.gc_dyn exactly —
    # some template must carry a gpushare annotation (else devices never
    # fill and no add-back runs) and some template must request gpu-count
    # (else the column is 0 anyway). Device-less nodes keep the static
    # column in all cases.
    has_dev = jnp.any(ec.node_gpu_mem > 0, axis=-1)  # [N]
    dyn_active = jnp.any(ec.gpu_mem > 0) & jnp.any(
        jnp.where(ec.gc_mask[None, :], ec.req, 0.0) > 0
    )
    share = jnp.where(
        ec.gc_mask[None, :] & has_dev[:, None] & dyn_active, 0.0, share
    )
    raw = jnp.maximum(jnp.max(share, axis=-1), 0.0) * MAX_NODE_SCORE
    # pods with no requests score MaxNodeScore on every node
    return jnp.where(jnp.any(req > 0), raw, MAX_NODE_SCORE)


def gc_share_dyn(ec, st, u):
    """Per-step share term for the dynamic gpu-count allocatable
    (algo.Share over the Reserve-updated value, open-gpu-share.go:94-106):
    req / (dyn_alloc - req), 1 when the denominator is 0, negative floored
    at 0 (the Go accumulator starts at 0). Zero on device-less nodes (their
    static column stays in share_raw) and for templates not requesting
    gpu-count."""
    gc_req = jnp.sum(jnp.where(ec.gc_mask, ec.req[u], 0.0))
    dyn, has_dev = gc_dynamic_alloc(ec, st)
    declared = jnp.sum(jnp.where(ec.gc_mask[None, :], ec.alloc, 0.0), axis=-1) > 0
    avail = dyn - gc_req
    share = jnp.where(avail == 0, jnp.where(gc_req == 0, 0.0, 1.0), div32(gc_req, avail))
    share = jnp.where(declared & has_dev, jnp.maximum(share, 0.0), 0.0)
    return jnp.where(gc_req > 0, share * MAX_NODE_SCORE, 0.0)


class StaticTables(NamedTuple):
    """Per-(template, node) quantities that never change during a scan —
    precomputed once with a vmap over the template axis, so the scan body
    only runs the usage-dependent kernels. This is the TPU answer to the
    reference re-running every plugin per pod (generic_scheduler.go:270-345):
    pods sharing a template share all topology-independent work."""

    static_pass: jnp.ndarray  # [U, N] bool — AND of the four static filters
    aff_mask: jnp.ndarray  # [U, N] bool (NodeAffinity + nodeSelector, for spread eligibility)
    static_fail: jnp.ndarray  # [U, 4] i32 first-fail counts for pin/unsched/taint/affinity
    na_raw: jnp.ndarray  # [U, N] f32 preferred-node-affinity weights
    tt_raw: jnp.ndarray  # [U, N] f32 intolerable PreferNoSchedule counts
    share_raw: jnp.ndarray  # [U, N] f32 Simon/GpuShare share × 100
    spread_weight: jnp.ndarray  # [Tk] f32 log(domain count + 2) per topology key


def precompute_static(ec: EncodedCluster, cfg=None) -> StaticTables:  # opensim-lint: jit-region
    """NodeName pinning is handled by the forced-bind path in the scan step
    (pods with spec.nodeName never reach the scheduler, reference
    simulator.go:329-331), so the pin filter is NOT part of static_pass —
    a defrag scenario that un-forces a drained node's pods lets them
    reschedule anywhere. Its static_fail column stays zero."""
    from ..engine.schedconfig import DEFAULT_CONFIG

    cfg = cfg or DEFAULT_CONFIG
    U = ec.req.shape[0]
    us = jnp.arange(U)
    taint = jax.vmap(lambda u: taint_filter(ec, u))(us)
    aff = jax.vmap(lambda u: node_affinity_filter(ec, u))(us)
    unsched = jnp.broadcast_to(~ec.unschedulable[None, :], taint.shape)
    true_m = jnp.ones_like(taint)
    pin = true_m
    valid = ec.node_valid[None, :]
    fails = []
    passed = jnp.broadcast_to(valid, taint.shape)
    for m, enabled in (
        (pin, True),
        (unsched, cfg.f_unschedulable),
        (taint, cfg.f_taints),
        (aff, cfg.f_node_affinity),
    ):
        m = m if enabled else true_m
        fails.append(jnp.sum(passed & ~m, axis=-1))
        passed = passed & m

    # topology-spread normalizing weight log(size+2): size = distinct
    # domains per key over valid nodes. k8s computes it over the per-pod
    # filtered set (scoring.go:96-104); using the valid set instead keeps
    # the weight out of the scan (a documented fidelity trade: it only
    # blends the spread score, never feasibility).
    Dp1 = ec.domain_topo.shape[0]
    Tk = ec.node_domain.shape[1]
    dom_present = jnp.zeros((Dp1,), jnp.float32).at[
        jnp.where(ec.node_valid[:, None], ec.node_domain, Dp1 - 1)
    ].max(1.0)
    sizes = jnp.stack(
        [jnp.sum(jnp.where(ec.domain_topo[: Dp1 - 1] == tk, dom_present[: Dp1 - 1], 0.0)) for tk in range(Tk)]
    )

    return StaticTables(
        static_pass=passed,
        aff_mask=aff,
        static_fail=jnp.stack(fails, axis=-1).astype(jnp.int32),
        na_raw=jax.vmap(lambda u: node_affinity_raw(ec, u))(us),
        tt_raw=jax.vmap(lambda u: taint_toleration_raw(ec, u))(us),
        share_raw=jax.vmap(lambda u: share_raw(ec, u))(us),
        # gather, not jnp.log: every engine must read the SAME f32 weights
        # (see EncodedCluster.log_sizes)
        spread_weight=ec.log_sizes[
            jnp.clip(sizes.astype(jnp.int32), 0, ec.log_sizes.shape[0] - 1)
        ],
    )


def local_score(ec, st, u):
    """Open-Local score (open-local.go:94-138 → ScoreLVMVolume/ScoreDevice
    Volume, vendored common.go:487-509,:660-690, StrategyBinpack default,
    types.go:142): mean over allocated units of used/capacity × MaxScore(10).
    The LVM unit lands on the tightest-fitting VG (ascending free-size
    first-fit, common.go:111-116); min-max normalization happens with the
    other score plugins in pod_step."""
    lvm = ec.lvm_req[u]
    big = jnp.float32(1e30)
    fits = st.vg_free >= lvm  # [N, Vg]
    tight_free = jnp.min(jnp.where(fits, st.vg_free, big), axis=-1)  # [N]
    # capacity of the chosen VG: gather via argmin over masked free
    choice = jnp.argmin(jnp.where(fits, st.vg_free, big), axis=-1)  # [N]
    vg_cap = jnp.take_along_axis(ec.node_vg_cap, choice[:, None], axis=-1)[:, 0]
    lvm_part = jnp.where((lvm > 0) & (tight_free < big), div32(lvm, jnp.maximum(vg_cap, 1.0)), 0.0)

    parts = lvm_part
    count = (lvm > 0).astype(jnp.float32)
    for media in (0, 1):
        size = ec.dev_req[u, media]
        n_dev = ec.dev_req_count[u, media].astype(jnp.float32)
        fitting = (ec.node_dev_media == media) & (st.dev_free >= size) & (st.dev_free > 0)
        dev_cap = jnp.where(fitting, ec.node_dev_cap, big)
        first_cap = jnp.min(dev_cap, axis=-1)  # first-fit proxy: smallest fitting device
        parts = parts + jnp.where(size > 0, div32(n_dev * size, jnp.maximum(first_cap, 1.0)), 0.0)
        count = count + jnp.where(size > 0, n_dev, 0.0)

    raw = jnp.where(count > 0, div32(parts, jnp.maximum(count, 1.0)) * 10.0, 0.0)
    return raw


class Features(NamedTuple):
    """Static (trace-time) feature flags of the whole workload set: any
    kernel whose inputs are empty across every template is eliminated from
    the compiled scan entirely. Computed host-side at encode time."""

    ports: bool
    gpu: bool
    local: bool
    interpod: bool  # any required pod affinity/anti-affinity term
    prefg: bool  # any preferred/symmetric inter-pod score term
    spread_hard: bool
    spread_soft: bool
    pref_node_affinity: bool
    prefer_taints: bool
    prefer_avoid: bool
    # some template requests alibabacloud.com/gpu-count as a SPEC resource
    # while gpushare devices exist: the allocatable column follows the device
    # state (Reserve rewrite) instead of the static table
    gc_dyn: bool = False
    # how the XLA scan reads each topology key's selector counts (not a flag:
    # the encoding's domain layout, count_keys_of); None reads by gather
    count_keys: "CountKeys | None" = None

    @property
    def sel_counts(self) -> bool:
        return self.interpod or self.spread_hard or self.spread_soft


ALL_FEATURES = Features(*([True] * 11))


def features_of(ec_np) -> Features:
    """Derive feature flags from the (host-side numpy) encoded cluster."""
    import numpy as np

    return Features(
        ports=bool((np.asarray(ec_np.ports) >= 0).any()),
        gpu=bool((np.asarray(ec_np.gpu_mem) > 0).any()),
        local=bool(
            (np.asarray(ec_np.lvm_req) > 0).any() or (np.asarray(ec_np.dev_req) > 0).any()
        ),
        interpod=bool(
            (np.asarray(ec_np.at_sel) >= 0).any() or (np.asarray(ec_np.an_sel) >= 0).any()
        ),
        prefg=bool((np.asarray(ec_np.prefg_w) != 0).any()),
        spread_hard=bool(
            ((np.asarray(ec_np.spr_topo) >= 0) & np.asarray(ec_np.spr_hard)).any()
        ),
        spread_soft=bool(
            ((np.asarray(ec_np.spr_topo) >= 0) & ~np.asarray(ec_np.spr_hard)).any()
        ),
        pref_node_affinity=bool((np.asarray(ec_np.pna_weight) != 0).any()),
        prefer_taints=bool(
            (np.asarray(ec_np.taint_effect) == V.EFFECT_PREFER_NO_SCHEDULE).any()
        ),
        prefer_avoid=bool((np.asarray(ec_np.avoid_score) < 100.0).any()),
        gc_dyn=bool(
            (np.asarray(ec_np.gpu_mem) > 0).any()
            and np.asarray(ec_np.gc_mask).any()
            and (np.asarray(ec_np.req)[:, np.asarray(ec_np.gc_mask)] > 0).any()
        ),
        count_keys=count_keys_of(ec_np),
    )


class StepResult(NamedTuple):
    feasible: jnp.ndarray  # [N] bool
    score: jnp.ndarray  # [N] f32 weighted total
    chosen: jnp.ndarray  # scalar i32 node index (-1 infeasible)
    fail_counts: jnp.ndarray  # [NUM_FILTERS] i32 first-fail node counts
    insufficient: jnp.ndarray  # [R] i32 nodes short of each resource


def score_parts(
    ec, stat: "StaticTables", st, u, feasible, feat: Features = ALL_FEATURES,
    cfg=None, extra: tuple = (),
):
    """Per-plugin weighted score contributions for one pod over the node
    axis, keyed by the kube plugin name, in the exact accumulation order of
    ``pod_step``'s selectHost sum (insertion-ordered dict — summing the
    values reproduces the engine's score bit-for-bit). This is the single
    scoring source shared by the scan and the decision audit's per-plugin
    breakdown (``simon explain``), so the two can never drift."""
    from ..engine.schedconfig import DEFAULT_CONFIG

    cfg = cfg or DEFAULT_CONFIG
    parts = {}
    if cfg.w_balanced:
        parts["NodeResourcesBalancedAllocation"] = (
            cfg.w_balanced * balanced_allocation_score(ec, st, u)
        )
    if cfg.w_least:
        parts["NodeResourcesLeastAllocated"] = (
            cfg.w_least * least_allocated_score(ec, st, u)
        )
    if cfg.w_rtcr:
        parts["RequestedToCapacityRatio"] = cfg.w_rtcr * rtcr_score(ec, st, u, cfg)
    if feat.pref_node_affinity and cfg.w_node_affinity:
        na_raw = stat.na_raw[u]
        na_max = jnp.max(jnp.where(feasible, na_raw, 0.0))
        parts["NodeAffinity"] = cfg.w_node_affinity * jnp.where(
            na_max > 0, div32(na_raw * MAX_NODE_SCORE, jnp.maximum(na_max, 1.0)), na_raw
        )
    if feat.prefer_taints and cfg.w_taint_toleration:
        tt_raw = stat.tt_raw[u]
        tt_max = jnp.max(jnp.where(feasible, tt_raw, 0.0))
        parts["TaintToleration"] = cfg.w_taint_toleration * jnp.where(
            tt_max > 0,
            MAX_NODE_SCORE - div32(tt_raw * MAX_NODE_SCORE, jnp.maximum(tt_max, 1.0)),
            MAX_NODE_SCORE,
        )
    if (feat.prefg or feat.interpod) and cfg.w_interpod:
        parts["InterPodAffinity"] = cfg.w_interpod * interpod_score(ec, st, u, feasible, feat.count_keys)
    if feat.spread_soft and cfg.w_spread:
        parts["PodTopologySpread"] = cfg.w_spread * spread_score(ec, stat, st, u, feasible, feat.count_keys)
    if cfg.w_simon + cfg.w_gpu_share:
        # Simon + Open-Gpu-Share share the same formula and normalization
        share_row = stat.share_raw[u]
        if feat.gc_dyn:
            # add back the gpu-count column with the Reserve-updated value
            # (share_raw zeroed it on device-bearing nodes); max mirrors the
            # Go accumulator taking the largest per-resource share
            share_row = jnp.maximum(share_row, gc_share_dyn(ec, st, u))
        parts["Simon/GpuShare"] = (cfg.w_simon + cfg.w_gpu_share) * _minmax_normalize(
            share_row, feasible
        )
    if feat.local and cfg.w_local:
        parts["OpenLocal"] = cfg.w_local * _minmax_normalize(
            local_score(ec, st, u), feasible
        )
    if feat.prefer_avoid and cfg.w_prefer_avoid:
        # NodePreferAvoidPods (w=10000, no NormalizeScore): raw 0/100 table
        parts["NodePreferAvoidPods"] = cfg.w_prefer_avoid * ec.avoid_score[u]
    for k, entry in enumerate(extra):
        if entry[0] == "score":
            parts[f"Extra[{k}]"] = float(entry[2]) * entry[1](ec, st, u, feasible)
    return parts


def pod_step(  # opensim-lint: jit-region
    ec: EncodedCluster, stat: StaticTables, st: ScanState, u,
    feat: Features = ALL_FEATURES, cfg=None, extra: tuple = (),
    count_all: bool = False,
) -> StepResult:
    """One pod through the full pipeline. Mirrors scheduleOne
    (vendor/.../scheduler/scheduler.go:441) minus the bind goroutine.
    The four static filters are a single precomputed-row gather; only
    usage-dependent kernels the workload actually exercises evaluate per
    step (see Features). `cfg` (SchedulerConfig) adjusts plugin weights and
    disables, mirroring --default-scheduler-config.

    `extra` is the WithExtraRegistry equivalent (simulator.go:190-200,
    :471-500): out-of-tree plugins as jittable callables. Each entry is
    ("filter", fn) where fn(ec, st, u) -> bool [N], or ("score", fn, weight)
    where fn(ec, st, u, feasible) -> f32 [N] (already 0-100 scaled)."""
    from ..engine.schedconfig import DEFAULT_CONFIG

    cfg = cfg or DEFAULT_CONFIG
    valid = ec.node_valid
    aff_mask = stat.aff_mask[u]
    static_pass = stat.static_pass[u]  # valid already folded in
    true_mask = jnp.ones_like(static_pass)
    masks = [ports_filter(ec, st, u) if feat.ports and cfg.f_ports else true_mask]
    alloc_eff = effective_alloc(ec, st) if feat.gc_dyn else None
    if cfg.f_fit:
        fit_mask, insufficient = fit_filter(
            ec, st, u, alloc=alloc_eff, ignored_cols=cfg.fit_ignored_cols
        )
    else:
        fit_mask, insufficient = true_mask, jnp.zeros_like(ec.alloc, dtype=bool)
    masks.append(fit_mask)
    masks.append(
        spread_filter(ec, st, u, aff_mask & valid, feat.count_keys)
        if feat.spread_hard and cfg.f_spread
        else true_mask
    )
    masks.append(interpod_filter(ec, st, u, feat.count_keys) if feat.interpod and cfg.f_interpod else true_mask)
    masks.append(gpu_filter(ec, st, u) if feat.gpu and cfg.f_gpu else true_mask)
    masks.append(local_filter(ec, st, u) if feat.local and cfg.f_local else true_mask)
    extra_filter = true_mask
    for entry in extra:
        if entry[0] == "filter":
            extra_filter = extra_filter & entry[1](ec, st, u)
    masks.append(extra_filter)  # dedicated F_EXTRA reason slot

    passed_list = []
    passed_so_far = static_pass
    insufficient_attributed = None
    for i, m in enumerate(masks):
        passed_list.append(passed_so_far)
        if i == F_FIT - F_PORTS:
            # per-resource counts attribute only nodes that reached the fit
            # filter (k8s reports each node under its first failing plugin)
            insufficient_attributed = insufficient & passed_so_far[:, None]
        passed_so_far = passed_so_far & m
    feasible = passed_so_far

    # Failure accounting (several reductions) only runs on the rare
    # unschedulable step — lax.cond skips it on every successful bind.
    def count_fails(_):
        counts = jnp.stack(
            [jnp.sum(p & ~m) for p, m in zip(passed_list, masks)]
        ).astype(jnp.int32)
        per_res = jnp.sum(insufficient_attributed & valid[:, None], axis=0).astype(jnp.int32)
        return counts, per_res

    def no_fails(_):
        return (
            jnp.zeros((len(masks),), jnp.int32),
            jnp.zeros((insufficient.shape[1],), jnp.int32),
        )

    any_feasible = jnp.any(feasible)
    if count_all:
        # explain mode (ISSUE 7): per-filter reject counts for EVERY step,
        # not just failures — the decision-audit aggregate needs to see
        # filter pressure on successful binds too. Trace-time flag, so the
        # default compile keeps the cond-skipped accounting below.
        fail_counts, per_res_insufficient = count_fails(None)
    else:
        fail_counts, per_res_insufficient = jax.lax.cond(
            any_feasible, no_fails, count_fails, None
        )

    # score plugins × weights (registry.go:119-132 + the three sim plugins):
    # accumulated in score_parts order — the per-plugin breakdown IS the
    # scoring code path, so the decision audit (engine/explain.py) reports
    # exactly the terms selectHost summed. Normalization runs over the
    # feasible set, matching the framework normalizing the filtered-node
    # score list (framework.go:635).
    score = jnp.zeros_like(stat.share_raw[u])
    for term in score_parts(ec, stat, st, u, feasible, feat, cfg, extra).values():
        score = score + term
    # ImageLocality: 0 (no images in sim)

    neg = jnp.float32(-1e30)
    best = jnp.argmax(jnp.where(feasible, score, neg))
    chosen = jnp.where(any_feasible, best, -1).astype(jnp.int32)
    return StepResult(
        feasible=feasible,
        score=score,
        chosen=chosen,
        fail_counts=fail_counts,
        insufficient=per_res_insufficient,
    )


def bind_update(ec: EncodedCluster, st: ScanState, u, node, apply,
                feat: Features = ALL_FEATURES):  # opensim-lint: jit-region
    """State transition on bind — the tensorized equivalent of the Reserve +
    Bind plugin chain writing back into the fake clientset
    (plugin/simon.go:104-126, open-gpu-share.go:147-245, open-local.go:175-254).

    `apply` (bool scalar) gates the whole update so the scan body needs no
    state-select afterwards. Every update is a single-ROW
    dynamic-update-slice (``.at[row]``): with the scan carry donated, XLA
    performs them in place, so per-step HBM traffic is O(row), not O(state)
    — the difference between 50k binds costing ~50 MB vs ~50 GB of writes.

    Returns (new_state, gpu_take[Gd]) — gpu_take is the number of requested
    GPU slots packed onto each device (the reference's devId annotation)."""
    applyf = apply.astype(jnp.float32)

    used = st.used.at[node].add(ec.req[u] * applyf)

    # host-port counts: one row, multi-hot over the template's ports
    port_used = st.port_used
    if feat.ports:
        ports = ec.ports[u]  # [Hp]
        Hports = st.port_used.shape[1]
        port_hot = jnp.sum(
            (jnp.arange(Hports)[None, :] == ports[:, None]) & (ports[:, None] >= 0), axis=0
        ).astype(jnp.float32)  # [Hports]
        port_used = st.port_used.at[node].add(port_hot * applyf)

    # domain selector counts: one row per topology key (Tk is tiny, the
    # Python loop unrolls into Tk dynamic-update-slices)
    dom_sel = st.dom_sel
    if feat.sel_counts:
        doms = ec.node_domain[node]  # [Tk]
        matches = ec.matches_sel[u].astype(jnp.float32) * applyf  # [A]
        for tk in range(int(ec.node_domain.shape[1])):
            dom_sel = dom_sel.at[doms[tk]].add(matches)

    # existing-anti / symmetric-preferred term counts: element updates
    dom_anti = st.dom_anti
    if feat.interpod:
        g_doms = ec.node_domain[node, ec.anti_g_topo]  # [G]
        anti_vals = ec.anti_g[u].astype(jnp.float32) * applyf
        for g in range(int(ec.anti_g_topo.shape[0])):
            dom_anti = dom_anti.at[g_doms[g], g].add(anti_vals[g])

    dom_prefw = st.dom_prefw
    if feat.prefg:
        p_doms = ec.node_domain[node, ec.prefg_topo]  # [Gp]
        pref_vals = ec.prefg_w[u] * applyf
        for g in range(int(ec.prefg_topo.shape[0])):
            dom_prefw = dom_prefw.at[p_doms[g], g].add(pref_vals[g])

    # gpu-share packing (AllocateGpuId, gpunodeinfo.go:232-290): single-GPU
    # pods take the tightest-fitting device; multi-GPU pods use the greedy
    # two-pointer packing with device reuse.
    gpu_free = st.gpu_free
    take = jnp.zeros_like(st.gpu_free[0])
    if feat.gpu:
        mem = ec.gpu_mem[u]
        cnt = ec.gpu_count[u].astype(jnp.float32)
        free = st.gpu_free[node]  # [Gd]
        chunks = floor_div32(free, jnp.maximum(mem, 1.0))
        cum = jnp.cumsum(chunks)
        take_greedy = jnp.clip(cnt - (cum - chunks), 0.0, chunks)
        big = jnp.float32(1e30)
        fits = free >= mem
        tight = jnp.argmin(jnp.where(fits, free, big))
        # a force-bound pod can land on a node where nothing fits — take 0
        # rather than driving gpu_free negative
        take_tight = ((jnp.arange(free.shape[0]) == tight) & jnp.any(fits)).astype(jnp.float32)
        take = jnp.where(cnt == 1, take_tight, take_greedy)
        take = jnp.where(mem > 0, take, 0.0)
        gpu_free = st.gpu_free.at[node].add(-(take * mem) * applyf)

    vg_free = st.vg_free
    dev_free = st.dev_free
    if feat.local:
        # open-local LVM: tightest-fitting VG (ascending free-size first-fit,
        # vendored common.go:111-116); a force-bound pod that fits nowhere
        # takes nothing rather than driving vg_free negative
        lvm = ec.lvm_req[u]
        vg_free_n = st.vg_free[node]
        big = jnp.float32(1e30)
        vg_fits = vg_free_n >= lvm
        vg_choice = jnp.argmin(jnp.where(vg_fits, vg_free_n, big))
        vg_hot = ((jnp.arange(st.vg_free.shape[1]) == vg_choice) & jnp.any(vg_fits)).astype(jnp.float32)
        vg_free = st.vg_free.at[node].add(-(vg_hot * jnp.maximum(lvm, 0.0)) * applyf)

        # open-local exclusive devices: one device per volume, smallest
        # volume first onto the smallest-capacity fitting free device
        # (CheckExclusiveResourceMeetsPVCSize, common.go:290-349; ties by
        # lowest device index)
        dev_free_n = st.dev_free[node]  # [Dv]
        dev_cap_n = ec.node_dev_cap[node]
        dev_taken = jnp.zeros_like(dev_free_n)
        big = jnp.float32(1e30)
        Mv = ec.dev_req_sizes.shape[2]
        for media in (0, 1):
            for i in reversed(range(Mv)):  # ascending sizes; 0-pads skipped
                size = ec.dev_req_sizes[u, media, i]
                cand = (
                    (ec.node_dev_media[node] == media)
                    & (dev_free_n >= size)
                    & (dev_free_n > 0)
                    & (dev_taken == 0)
                )
                choice = jnp.argmin(jnp.where(cand, dev_cap_n, big))
                hot = (jnp.arange(dev_free_n.shape[0]) == choice) & jnp.any(cand) & (size > 0)
                dev_taken = jnp.maximum(dev_taken, hot.astype(jnp.float32))
        dev_free = st.dev_free.at[node].set(
            jnp.where((dev_taken > 0) & apply, 0.0, dev_free_n)
        )

    return (
        st._replace(
            used=used,
            port_used=port_used,
            dom_sel=dom_sel,
            dom_anti=dom_anti,
            dom_prefw=dom_prefw,
            gpu_free=gpu_free,
            vg_free=vg_free,
            dev_free=dev_free,
        ),
        take * applyf,
    )


# The readers of the selector-count carry below sit after every function the
# megakernel traces, so that adding them moved none of those lines (a Mosaic
# compile key holds the call stack).

#: columns of the selector-count carry in one window: one lane tile
COUNT_WINDOW = 128

#: domains a topology key may have, beside the trash row, for a step to read
#: its counts by compare-select (``CountKeys``)
SELECT_DOMAINS = 8


def _count_column(counts, col):
    """``counts[:, col]``, the [D+1] counts of one selector ``col`` (a traced
    scalar, at least 0) of the per-domain selector counts ``counts`` [D+1, A]:
    a select over the lanes of the window of ``COUNT_WINDOW`` columns that
    holds it (past one window a dynamic slice of whole rows) and a sum of one
    count and zeros, so the same float32 count."""
    A, lanes = counts.shape[1], COUNT_WINDOW
    if A > lanes:
        start = jnp.clip(col - col % lanes, 0, A - lanes)
        counts, col = jax.lax.dynamic_slice_in_dim(counts, start, lanes, axis=1), col - start
    return jnp.sum(jnp.where(jnp.arange(counts.shape[1]) == col, counts, 0.0), axis=1)


def selector_columns(counts, cols):
    """``counts[:, cols]``: the [D+1, C] slab of the per-domain selector
    counts ``counts`` [D+1, A] under the terms' selectors ``cols`` [C] (each
    at least 0). Past one window, each column is :func:`_count_column`'s. A
    gather of the columns makes XLA lay the carry out by columns, and the
    bind's row update then walks every tile of a row (35 us more a step at
    [5,002, 5,300] on a v5e); a window keeps it by rows. A carry of one window
    is read as it is."""
    if counts.shape[1] <= COUNT_WINDOW:
        return counts[:, cols]
    return jnp.stack([_count_column(counts, cols[c]) for c in range(cols.shape[0])], axis=1)


class CountKeys(NamedTuple):
    """How an XLA step reads each topology key's counts, from the domain
    numbering of an encoding (:func:`count_keys_of`); a trace-time constant of
    the scan, in :class:`Features`. Key k is *node-ordered* where ``base[k]``
    is at least 0: node n below ``nodes`` is in domain ``base[k] + n`` and
    every later node in the trash domain, so a node's counts are a slice of
    the column. Else it is *small*: ``ids[k]``, at most ``SELECT_DOMAINS``
    domains, holds every node that is not in the trash domain, so a node's
    counts are a select over those domains' counts."""

    nodes: int
    base: tuple  # [Tk] i32, -1 for a small key
    ids: tuple  # [Tk] tuples of domain ids, None for a node-ordered key

    def paths(self) -> dict:
        """Keys by the read each takes: ``slice`` and ``select``."""
        sliced = sum(b >= 0 for b in self.base)
        return {"slice": sliced, "select": len(self.base) - sliced}


def count_keys_of(ec_np) -> "CountKeys | None":
    """The :class:`CountKeys` of a (host-side numpy) encoded cluster; None
    where a key is neither node-ordered nor small (a rack label of hundreds of
    values, a node without a hostname label, two nodes with one hostname),
    and every key is then read by gather (:func:`domain_counts`)."""
    import numpy as np

    node_domain = np.asarray(ec_np.node_domain)
    trash = int(np.asarray(ec_np.domain_topo).shape[0]) - 1
    nodes = int(np.asarray(ec_np.node_valid).sum())
    base, ids = [], []
    for col in node_domain.T:
        first = int(col[0]) if nodes else trash
        if (
            first + nodes <= trash
            and (col[:nodes] == first + np.arange(nodes)).all()
            and (col[nodes:] == trash).all()
        ):
            base.append(first)
            ids.append(None)
            continue
        domains = np.unique(col[col != trash])
        if len(domains) > SELECT_DOMAINS:
            return None
        base.append(-1)
        ids.append(tuple(int(d) for d in domains))
    return CountKeys(nodes=nodes, base=tuple(base), ids=tuple(ids))


def _keyed_counts(counts, dom, cols, topo, keys: CountKeys):
    """:func:`domain_counts` with no per-node read of the counts: each term's
    [D+1] column (:func:`_count_column`), then for every node a slice of it
    under a node-ordered key, or under a small key a select over the key's
    domains, whose counts are read one by one. A term's key ``topo`` [C] is
    traced, so where the encoding has keys of both kinds both reads are made
    and a select keeps its key's. Nodes in the trash domain, pad nodes among
    them, read its count, as the gather does."""
    import numpy as np

    trash, n_nodes = counts.shape[0] - 1, dom.shape[0]
    sliced = np.array([b >= 0 for b in keys.base])
    width = max([len(d) for d in keys.ids if d is not None], default=0)
    table = np.full((len(keys.ids), width), trash, np.int32)
    for k, d in enumerate(keys.ids):
        if d:
            table[k, : len(d)] = d
    base = jnp.asarray(np.maximum(np.array(keys.base, np.int32), 0))[topo]  # [C]
    key_sliced = jnp.asarray(sliced)[topo]  # [C]
    ids = jnp.asarray(table)[topo]  # [C, W]
    out = []
    for c in range(cols.shape[0]):
        col = _count_column(counts, cols[c])  # [D+1]
        fill = jnp.broadcast_to(col[trash], (n_nodes,))
        read = fill
        for j in range(width):
            count = jax.lax.dynamic_index_in_dim(col, ids[c, j], keepdims=False)
            read = jnp.where(dom[:, c] == ids[c, j], count, read)
        if sliced.any():
            ordered = jnp.concatenate(
                [jax.lax.dynamic_slice_in_dim(col, base[c], keys.nodes), fill[keys.nodes:]]
            )
            read = jnp.where(key_sliced[c], ordered, read) if not sliced.all() else ordered
        out.append(read)
    return jnp.stack(out, axis=1)


def domain_counts(counts, dom, cols, topo=None, keys=None):
    """``counts[dom, cols[None, :]]``: each node's count in its own domain
    under each term's topology key (``dom`` [N, C] the nodes' domains, ``topo``
    [C] the terms' keys). Where the encoding's :class:`CountKeys` ``keys``
    says every key is node-ordered or small, read by slice and select
    (:func:`_keyed_counts`): the per-node gather below costs by the element
    on a v5e, 87.6 us of a 159 us step at plan-cl2's 5,120 nodes and two
    terms. Else, past one window, gathered node by node within
    :func:`selector_columns`' [D+1, C] slab: a point gather of ``counts``
    itself reads N·C cells scattered over the whole carry at every step,
    [5,002, 5,300] (106 MB) for 5,300 spread selectors, 188 us of a 239 us
    step on a v5e. A carry of one window is gathered from directly: a slab
    would hold all of it, and on a v5e the served what-if's scan (A = 1) took
    45 % more device time through one."""
    if keys is not None:
        return _keyed_counts(counts, dom, cols, topo, keys)
    if counts.shape[1] <= COUNT_WINDOW:
        return counts[dom, cols[None, :]]
    return jnp.take_along_axis(selector_columns(counts, cols), dom, axis=0, mode="promise_in_bounds")


def count_reads(ec, feat: Features) -> dict:
    """How an XLA step reads the selector-count carry, for the spans of the
    rungs that run the XLA scan: ``count_columns``, the columns of
    ``dom_sel`` one step reads through :func:`selector_columns` for the
    features that are on (the spread constraints' Cs, one slab for filter and
    score; the inter-pod anti and affinity terms' Tn + Ti; the incoming
    preferred terms' Tpp); ``count_table_bytes``, the carry's (D+1)·A·4; and
    ``count_keys_sliced``, ``count_keys_selected`` and
    ``count_keys_gathered``, the topology keys a step reads by each path of
    :func:`domain_counts` (none where no column is read). Called once a scan
    as its span opens, it adds the keys to ``simon_count_read_keys_total``."""
    from ..obs.metrics import RECORDER

    spread = ec.spr_topo.shape[1] if feat.spread_hard or feat.spread_soft else 0
    required = ec.an_sel.shape[1] + ec.at_sel.shape[1] if feat.interpod else 0
    preferred = ec.pt_sel.shape[1] if feat.interpod or feat.prefg else 0
    columns = int(spread + required + preferred)
    paths = {"slice": 0, "select": 0, "gather": 0}
    if columns and feat.count_keys is not None:
        paths.update(feat.count_keys.paths())
    elif columns:
        paths["gather"] = int(ec.node_domain.shape[1])
    RECORDER.count_read_keys_by_path({k: n for k, n in paths.items() if n})
    return {
        "count_columns": columns,
        "count_table_bytes": int(ec.domain_topo.shape[0] * ec.matches_sel.shape[1] * 4),
        "count_keys_sliced": paths["slice"],
        "count_keys_selected": paths["select"],
        "count_keys_gathered": paths["gather"],
    }
