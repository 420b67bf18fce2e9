"""The plain reference for a scheduler profile that packs:
`kube_reference.Reference` with the profile's score weights and kube's
RequestedToCapacityRatio plugin, one pod at a time.

Independent of the program under test, like the reference it extends: it
imports nothing of `opensim_tpu` and is given the cluster and the profile as
plain data by the generator (`Profile`: what the scheduler-config file says,
the shape's scores in the file's 0..10).

RequestedToCapacityRatio, as kube 1.21's `requested_to_capacity_ratio.go` and
`helper/shape_score.go` have it (written from memory):

  - The arguments are converted: each shape point's score is multiplied by
    MaxNodeScore / MaxCustomPriorityScore = 10.
  - For cpu and memory the requested amount is what LeastAllocated reads
    here: the node's requested total plus the pod's non-zero request (100m
    and 200Mi where it sets none).
  - Per resource, the utilization is `100 - (capacity - requested) * 100 /
    capacity`, or 100 where the capacity is 0 or the request exceeds it. The
    shape's broken-linear function of it: the first point's score up to the
    first utilization; on the segment i that holds it `s[i-1] + (s[i] -
    s[i-1]) * (u - u[i-1]) / (u[i] - u[i-1])`; the last point's score beyond
    the last utilization.
  - The node's score is `sum w_r * f_r / sum w_r` over the resources whose
    `f_r` is above 0, and 0 where none is (the 1.21 rule as remembered).
  - It is weighted by the plugin's weight and added after LeastAllocated, in
    the order of the program's score sum: BalancedAllocation,
    LeastAllocated, RequestedToCapacityRatio, PodTopologySpread, the share.

Departures, in `kube_reference`'s convention for LeastAllocated: float32 and
unrounded where kube divides int64s and rounds with `math.Round`; the sums over
resources run in the profile's order. A segment whose slope `(s[i] - s[i-1])
/ (u[i] - u[i-1])` is exactly 1 is folded to `s[i-1] + (u - u[i-1])`, as the
program folds it; any other segment multiplies and then divides. Only cpu and
memory can be named: the configurations give no node another resource.

`precision="bfloat16"` is the low-precision control, as there: every operand
and step of a score is rounded to bfloat16; filters stay exact.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from .kube_reference import F32, Cluster, Reference as ResourcesReference

Order = Dict[str, List[str]]  # workload -> the node of each of its pods, in the order they were scheduled
#: kube's MaxNodeScore / MaxCustomPriorityScore
SCORE_SCALE = 10.0


@dataclass
class Profile:
    """A scheduler profile's score side: the weight of each plugin (0 is
    off), RequestedToCapacityRatio's shape as (utilization, score 0..10)
    points and its resources as (name, weight)."""

    balanced: float = 1.0
    least: float = 1.0
    rtcr: float = 0.0
    spread: float = 2.0
    share: float = 2.0
    shape: Tuple[Tuple[int, int], ...] = ()
    resources: Tuple[Tuple[str, int], ...] = (("cpu", 1), ("memory", 1))


@dataclass
class ProfiledCluster(Cluster):
    profile: Profile = field(default_factory=Profile)

    def with_new_nodes(self, k: int) -> "ProfiledCluster":
        grown = super().with_new_nodes(k)
        return ProfiledCluster(grown.nodes, grown.bound, grown.workloads, grown.new_node, self.profile)


class Reference(ResourcesReference):
    def __init__(self, cluster: ProfiledCluster, precision: str = "float32") -> None:
        super().__init__(cluster, precision)
        self.profile = cluster.profile
        for name, _w in self.profile.resources:
            if name not in ("cpu", "memory"):
                raise ValueError(f"the reference has no resource {name!r}")
        self.shape = [(F32(u), F32(s * SCORE_SCALE)) for u, s in self.profile.shape]
        self._bound: Dict[int, List[int]] = {}

    def _shape_score(self, util: np.ndarray) -> np.ndarray:
        q = self.q
        out = np.full(self.n, self.shape[-1][1], F32)
        for i in range(len(self.shape) - 1, -1, -1):
            u_i, s_i = self.shape[i]
            if i == 0:
                val = np.full(self.n, s_i, F32)
            else:
                u_p, s_p = self.shape[i - 1]
                if s_i - s_p == u_i - u_p:
                    val = q(s_p + q(util - u_p))
                else:
                    val = q(s_p + q(q(q(s_i - s_p) * q(util - u_p)) / q(u_i - u_p)))
            out = np.where(util <= u_i, val, out)
        return out.astype(F32)

    def _rtcr(self, requested: Dict[str, np.ndarray], capacity: Dict[str, np.ndarray]) -> np.ndarray:
        q, hundred = self.q, F32(100.0)
        num = den = None
        for name, weight in self.profile.resources:
            r, c = requested[name], capacity[name]
            util = q(hundred - q(q(q(c - r) * hundred) / np.maximum(c, F32(1.0))))
            util = np.where((c == 0) | (r > c), hundred, util)
            f = self._shape_score(util)
            term = q(F32(weight) * f)
            num = term if num is None else q(num + term)
            d = np.where(f > 0, F32(weight), F32(0.0))
            den = d if den is None else den + d
        with np.errstate(divide="ignore", invalid="ignore"):
            return np.where(den > 0, q(num / den), F32(0.0)).astype(F32)

    def step(self) -> Tuple[np.ndarray, np.ndarray]:
        """`kube_reference.Reference.step` under the profile: (feasible [n]
        bool, weighted score [n] float32) for the next pod of the entered
        workload."""
        w, q, p = self._w, self.q, self.profile
        cpu, mem = w["cpu"], w["mem"]
        feasible = (
            w["sel"]
            & ~((cpu > 0) & (self.used_cpu + cpu > self.cap_cpu))
            & ~((mem > 0) & (self.used_mem + mem > self.cap_mem))
            & ~(self.used_pods + F32(1.0) > self.cap_pods)
        )
        hundred = F32(100.0)
        rc = q(q(self.used_cpu) + q(cpu if cpu > 0 else F32(100.0)))
        rm = q(q(self.used_mem) + q(mem if mem > 0 else F32(200.0 * 1024 * 1024)))
        cc, cm = q(self.cap_cpu), q(self.cap_mem)
        dc, dm = np.maximum(cc, F32(1.0)), np.maximum(cm, F32(1.0))
        score = np.zeros(self.n, F32)
        if p.balanced:
            fc, fm = q(rc / dc), q(rm / dm)
            bal = np.where((fc >= 1.0) | (fm >= 1.0), F32(0.0),
                           q(q(F32(1.0) - np.abs(q(fc - fm))) * hundred))
            score = q(score + q(F32(p.balanced) * bal))
        if p.least:
            lc = np.where((cc == 0) | (rc > cc), F32(0.0), q(q(q(cc - rc) * hundred) / dc))
            lm = np.where((cm == 0) | (rm > cm), F32(0.0), q(q(q(cm - rm) * hundred) / dm))
            score = q(score + q(F32(p.least) * q(q(lc + lm) / F32(2.0))))
        if p.rtcr:
            rtcr = self._rtcr({"cpu": rc, "memory": rm}, {"cpu": cc, "memory": cm})
            score = q(score + q(F32(p.rtcr) * rtcr))
        if w["spread"] and p.spread:
            raw = np.zeros(self.n, F32)
            for c in w["spread"]:
                cnt = q(c["counts"])[c["dom"]]
                raw = q(raw + np.where(c["has"], q(q(cnt * q(c["weight"])) + c["skew1"]), F32(0.0)))
            scored = feasible & ~w["ignored"]
            if scored.any():
                mn, mx = raw[scored].min(), raw[scored].max()
            else:
                mn, mx = F32(1e30), F32(-1e30)
            if mx <= 0:
                norm = np.full(self.n, hundred, F32)
            else:
                norm = q(q(hundred * q(q(mx + mn) - raw)) / max(mx, F32(1.0)))
            norm = np.where(feasible & w["ignored"], F32(0.0), norm)
            score = q(score + q(F32(p.spread) * norm))
        sr = w["share_raw"]
        if feasible.any() and p.share:
            lo, hi = sr[feasible].min(), sr[feasible].max()
            rng = q(hi - lo)
            if rng > 0:
                score = q(score + q(F32(p.share) * q(q(q(sr - lo) * hundred) / rng)))
        return feasible, score.astype(F32)

    def bind(self, node: int) -> None:
        super().bind(node)
        self._bound.setdefault(self._w["wi"], []).append(node)

    def order(self) -> Order:
        """What was bound, in the form `replay` takes: how the control (this
        reference in lower precision) is put in the program's place."""
        nodes, workloads = self.cluster.nodes, self.cluster.workloads
        return {workloads[wi].name: [nodes[i].name for i in seq] for wi, seq in self._bound.items()}


def replay(cluster: ProfiledCluster, placed: Order, unscheduled: Dict[str, int],
           precision: str = "float32") -> Dict[str, float]:
    """The program's answer followed pod by pod, as
    `kube_interpod_reference.replay` follows it: at each pod the reference
    computes its own filter and scores from the state built so far; a pod the
    program put elsewhere than the reference's best node is misplaced and the
    score it gave up is recorded, one it put where the filter says no is
    infeasible; then the reference binds where the program did."""
    from .kube_reference import NEG, queue_order

    ref = Reference(cluster, precision)
    by_name = {w.name: i for i, w in enumerate(cluster.workloads)}
    out = {"misplaced_pods": 0, "worst_score_gap": 0.0, "infeasible_pods": 0,
           "unscheduled_diff": 0, "answer_diff": 0}
    out["answer_diff"] += sum(len(seq) for wname, seq in placed.items() if wname not in by_name)
    out["answer_diff"] += sum(k for wname, k in unscheduled.items() if wname not in by_name)
    for wi in queue_order(cluster.workloads):
        w = cluster.workloads[wi]
        said_unsched = int(unscheduled.get(w.name, 0))
        ref._enter(wi)
        followed = 0
        for name in placed.get(w.name, ()):
            node: Optional[int] = ref.index.get(name)
            if node is None:
                out["answer_diff"] += 1
                continue
            followed += 1
            feasible, score = ref.step()
            if not feasible[node]:
                out["infeasible_pods"] += 1
            elif node != (best := int(np.argmax(np.where(feasible, score, NEG)))):
                out["misplaced_pods"] += 1
                out["worst_score_gap"] = max(out["worst_score_gap"], float(score[best] - score[node]))
            ref.bind(node)
        out["answer_diff"] += abs(w.replicas - followed - said_unsched)
        if said_unsched and ref.step()[0].any():
            out["unscheduled_diff"] += said_unsched
    return out
