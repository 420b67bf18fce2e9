"""The plain reference: kube-scheduler's default plugins, one pod at a time.

Independent of the program under test: it imports nothing of `opensim_tpu`
and is given the cluster as plain data by the generator, never an encoding, a
table or an answer of the program. For each pod, in queue order: Filter
(NodeResourcesFit on cpu, memory and pods; node selector), Score
(NodeResourcesLeastAllocated w1, NodeResourcesBalancedAllocation w1,
PodTopologySpread w2 over the pod's ScheduleAnyway constraints or the system
defaults, the Simon share score w2, min-max normalised), and bind to the best
node, the lowest index among equals: the tie-break the configurations state.
Plugins whose inputs these configurations never carry (taints, host ports,
inter-pod affinity, GPU share, local volumes, prefer-avoid) are constant over
the nodes and left out.

Scores are computed unrounded in the precision the configuration states,
float32. `precision="bfloat16"` is the low-precision control: the operands of
every score and each arithmetic step are rounded to bfloat16; filters stay
exact.

Queue order is the simulator's: within an app, pods with a node selector
first, then the rest (a stable partition); replicas of one workload are
consecutive and identical, so a placement is a count per (workload, node).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

F32 = np.float32
HOSTNAME = "kubernetes.io/hostname"
ZONE = "topology.kubernetes.io/zone"
#: DefaultPodTopologySpread (kube 1.21): soft, for pods owned by a workload
SYSTEM_DEFAULT_SPREAD = ((HOSTNAME, 3), (ZONE, 5))
W_LEAST, W_BALANCED, W_SPREAD, W_SHARE = 1.0, 1.0, 2.0, 2.0
NEG = F32(-1e30)


@dataclass
class NodeSpec:
    name: str
    cpu_m: int
    mem_bytes: int
    pods: int
    labels: Dict[str, str]


@dataclass
class Workload:
    """`replicas` identical pods. `spread` lists explicit ScheduleAnyway
    constraints as (topology key, maxSkew, matchLabels); None means the
    system defaults apply, matching on the pods' own labels."""

    name: str
    replicas: int
    cpu_m: int
    mem_bytes: int
    labels: Dict[str, str]
    node_selector: Dict[str, str] = field(default_factory=dict)
    spread: Optional[List[Tuple[str, int, Dict[str, str]]]] = None


@dataclass
class Cluster:
    nodes: List[NodeSpec]
    #: pods already bound, unlabelled: (node name, count, cpu_m each, mem_bytes each)
    bound: List[Tuple[str, int, int, int]]
    workloads: List[Workload]
    new_node: Optional[NodeSpec] = None

    def with_new_nodes(self, k: int) -> "Cluster":
        if not k:
            return self
        t = self.new_node
        extra = [
            NodeSpec(f"new-{i}", t.cpu_m, t.mem_bytes, t.pods,
                     dict(t.labels, **{HOSTNAME: f"new-{i}"}))
            for i in range(k)
        ]
        return Cluster(self.nodes + extra, self.bound, self.workloads, self.new_node)


def queue_order(workloads: List[Workload]) -> List[int]:
    with_sel = [i for i, w in enumerate(workloads) if w.node_selector]
    return with_sel + [i for i, w in enumerate(workloads) if not w.node_selector]


def round_bf16(x):
    """float32 -> nearest bfloat16 (ties to even), kept in a float32."""
    a = np.ascontiguousarray(x, dtype=F32)
    u = a.view(np.uint32)
    r = (u + (((u >> 16) & 1) + np.uint32(0x7FFF))) & np.uint32(0xFFFF0000)
    return r.view(F32)


class Reference:
    def __init__(self, cluster: Cluster, precision: str = "float32") -> None:
        if precision not in ("float32", "bfloat16"):
            raise ValueError(f"no such precision: {precision}")
        self.low = precision == "bfloat16"
        self.q = round_bf16 if self.low else (lambda x: x)
        self.cluster = cluster
        nodes = cluster.nodes
        self.n = len(nodes)
        self.index = {nd.name: i for i, nd in enumerate(nodes)}
        self.cap_cpu = np.array([nd.cpu_m for nd in nodes], F32)
        self.cap_mem = np.array([nd.mem_bytes for nd in nodes], F32)
        self.cap_pods = np.array([nd.pods for nd in nodes], F32)
        self.used_cpu = np.zeros(self.n, F32)
        self.used_mem = np.zeros(self.n, F32)
        self.used_pods = np.zeros(self.n, F32)
        for name, count, cpu_m, mem in cluster.bound:
            i = self.index[name]
            self.used_cpu[i] += F32(count * cpu_m)
            self.used_mem[i] += F32(count * mem)
            self.used_pods[i] += F32(count)
        #: pods placed so far, per workload: int32 [n]
        self.placed: Dict[int, np.ndarray] = {}
        self._topo: Dict[str, Tuple[np.ndarray, int]] = {}
        self._w: Optional[dict] = None

    # -- static facts -------------------------------------------------------

    def _domains(self, key: str) -> Tuple[np.ndarray, int]:
        """(domain id per node or -1, number of domains) of a topology key."""
        got = self._topo.get(key)
        if got is None:
            ids: Dict[str, int] = {}
            dom = np.full(self.n, -1, np.int64)
            for i, nd in enumerate(self.cluster.nodes):
                v = nd.labels.get(key)
                if v is not None:
                    dom[i] = ids.setdefault(v, len(ids))
            got = self._topo[key] = (dom, len(ids))
        return got

    def _enter(self, wi: int) -> dict:
        """Per-workload constants and the spread counts as they stand."""
        w = self.cluster.workloads[wi]
        q = self.q
        sel = np.array(
            [all(nd.labels.get(k) == v for k, v in w.node_selector.items())
             for nd in self.cluster.nodes], bool,
        ) if w.node_selector else np.ones(self.n, bool)
        cons = w.spread if w.spread is not None else (
            [(key, skew, dict(w.labels)) for key, skew in SYSTEM_DEFAULT_SPREAD] if w.labels else []
        )
        spread = []
        for key, skew, match in cons:
            dom, size = self._domains(key)
            counts = np.zeros(max(size, 1), F32)
            self_match = all(w.labels.get(k) == v for k, v in match.items())
            for wj, placed in self.placed.items():
                other = self.cluster.workloads[wj]
                if wj != wi and all(other.labels.get(k) == v for k, v in match.items()):
                    has = dom >= 0
                    np.add.at(counts, dom[has], placed[has].astype(F32))
            spread.append({
                "dom": np.maximum(dom, 0), "has": dom >= 0, "counts": counts,
                "weight": F32(math.log(size + 2.0)), "skew1": F32(skew - 1.0),
                "self": self_match,
            })
        cpu, mem = F32(w.cpu_m), F32(w.mem_bytes)
        # Simon share: max over the node's resources of req / (allocatable - req)
        shares = []
        for req, cap in ((cpu, self.cap_cpu), (mem, self.cap_mem)):
            avail = q(q(cap) - q(req))
            with np.errstate(divide="ignore", invalid="ignore"):
                s = np.where(avail == 0, F32(1.0 if req else 0.0), q(q(req) / avail))
            shares.append(np.where(cap > 0, s, F32(0.0)))
        share_raw = q(np.maximum(np.maximum(shares[0], shares[1]), F32(0.0)) * F32(100.0))
        ignored_any = np.zeros(self.n, bool)
        for c in spread:
            ignored_any |= ~c["has"]
        self._w = {"wi": wi, "sel": sel, "cpu": cpu, "mem": mem, "spread": spread,
                   "share_raw": share_raw.astype(F32), "ignored": ignored_any}
        self.placed.setdefault(wi, np.zeros(self.n, np.int32))
        return self._w

    # -- one pod ------------------------------------------------------------

    def step(self) -> Tuple[np.ndarray, np.ndarray]:
        """(feasible [n] bool, weighted score [n] float32) for the next pod
        of the entered workload."""
        w, q = self._w, self.q
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
        # NodeResourcesLeastAllocated: mean over cpu and memory of free/capacity
        lc = np.where((cc == 0) | (rc > cc), F32(0.0), q(q(q(cc - rc) * hundred) / dc))
        lm = np.where((cm == 0) | (rm > cm), F32(0.0), q(q(q(cm - rm) * hundred) / dm))
        least = q(q(lc + lm) / F32(2.0))
        # NodeResourcesBalancedAllocation: 1 - |cpu fraction - memory fraction|
        fc, fm = q(rc / dc), q(rm / dm)
        bal = np.where((fc >= 1.0) | (fm >= 1.0), F32(0.0),
                       q(q(F32(1.0) - np.abs(q(fc - fm))) * hundred))
        score = q(q(F32(W_BALANCED) * bal) + q(F32(W_LEAST) * least))
        # PodTopologySpread, ScheduleAnyway: fewer matching pods in the node's
        # domains is better; nodes missing a topology label score 0
        if w["spread"]:
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
            score = q(score + q(F32(W_SPREAD) * norm))
        # Simon share, min-max normalised over the feasible nodes
        sr = w["share_raw"]
        if feasible.any():
            lo, hi = sr[feasible].min(), sr[feasible].max()
            rng = q(hi - lo)
            if rng > 0:
                score = q(score + q(F32(W_SHARE) * q(q(q(sr - lo) * hundred) / rng)))
        return feasible, score.astype(F32)

    def bind(self, node: int) -> None:
        w = self._w
        self.used_cpu[node] += w["cpu"]
        self.used_mem[node] += w["mem"]
        self.used_pods[node] += F32(1.0)
        self.placed[w["wi"]][node] += 1
        for c in w["spread"]:
            if c["self"] and c["has"][node]:
                c["counts"][c["dom"][node]] += F32(1.0)

    # -- whole runs ---------------------------------------------------------

    def free_run(self, stop_at_unschedulable: bool = False) -> Tuple[Dict[int, np.ndarray], Dict[int, int]]:
        """Schedule everything. (pods per node of each workload, pods of each
        workload left unschedulable). `stop_at_unschedulable` ends the run at
        the first pod that fits nowhere: enough to show that something does."""
        unscheduled: Dict[int, int] = {}
        for wi in queue_order(self.cluster.workloads):
            self._enter(wi)
            reps = self.cluster.workloads[wi].replicas
            for i in range(reps):
                feasible, score = self.step()
                if not feasible.any():
                    # identical pods and resources only deplete: the rest fail too
                    unscheduled[wi] = reps - i
                    if stop_at_unschedulable:
                        return self.placed, unscheduled
                    break
                self.bind(int(np.argmax(np.where(feasible, score, NEG))))
        return self.placed, unscheduled
