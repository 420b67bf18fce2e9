"""The plain reference with Open-Gpu-Share: `kube_reference.Reference` plus
open-simulator's GPU-sharing plugin, one pod at a time.

Independent of the program under test, like the reference it extends: it
imports nothing of `opensim_tpu` and is given the cluster as plain data. A
node here also carries a count of GPU devices and the memory of each; a
workload the memory it asks of each GPU and the number of GPUs (the pod
annotations `alibabacloud.com/gpu-mem` and `alibabacloud.com/gpu-count`).
The state is the free memory of every device of every node, whole bytes in
int64, so that no quotient is ever rounded.

Written from `SURVEY.md`'s account of `open-gpu-share.go:51-81` (Filter,
Score) and `gpunodeinfo.go:232-290` (`AllocateGpuId`):

Filter: a pod that asks GPU memory needs `sum_d (free_d // mem) >= count`
  over the node's devices, in integers, and a count above 0; a node with no
  device has no slot. A pod that asks none passes.
Score: Open-Gpu-Share scores by the Simon share formula and its min-max
  normalisation, so the two plugins are one score at weight 2 (`W_SHARE`).
  The share is the largest, over the resources the node declares, of
  request / (allocatable - request); `gpu-mem` and `gpu-count` are declared
  in allocatable and take part as any resource does, with the pod's *spec*
  request for them (the annotations are no spec request, so a pod that
  states its GPUs by annotation alone adds 0 there).
Bind: one GPU, the fitting device with the least free memory, the lowest
  index among equals; several, the devices in index order, each giving
  `min(free_d // mem, left)` slots of `mem` until none is left, so one device
  can hold several of a pod's slots.

Departures from the Go source that could not be checked against it here (no
network, no checkout): (1) the Go `AllocateGpuId` is said to walk its devices
with two pointers; the greedy walk above is what `SURVEY.md` and the program
both describe, and where they differ the reference sides with the survey;
(2) Reserve rewrites a device-bearing node's `gpu-count` allocatable to the
number of devices not fully used, which moves the share only for a pod that
requests `gpu-count` in its spec: no pod of these configurations does, and
the reference keeps the column static; (3) the score is kept unrounded in
float32, the tie goes to the lowest node index, and replicas of a workload
are identical, all as in `kube_reference`.

`precision="bfloat16"` is the low-precision control, as there: the scores
are rounded at every step; filters and the device arithmetic stay exact.

`replay` follows the program pod by pod in the order it scheduled them, as
`kube_interpod_reference.replay` does and for its reason: where a device
fits or not decides which nodes are feasible for every later pod, so one
choice that differs changes the trajectory, and a replay by counts would
read the whole divergence where a replay by order reads that step.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from .kube_reference import F32, HOSTNAME, NEG, Cluster, NodeSpec, Reference as ResourcesReference, Workload, queue_order

Order = Dict[str, List[str]]  # workload -> the node of each of its pods, in the order they were scheduled
Devices = Dict[Tuple[str, int], int]  # (node, device index) -> bytes in use


@dataclass
class GpuNodeSpec(NodeSpec):
    gpus: int = 0  # devices, `alibabacloud.com/gpu-count`
    gpu_mem: int = 0  # bytes of each device: `alibabacloud.com/gpu-mem` over the count


@dataclass
class GpuWorkload(Workload):
    gpu_mem: int = 0  # bytes asked of each GPU (annotation)
    gpu_count: int = 0  # GPUs asked (annotation)
    #: spec-level requests of the two extended resources; the annotations are
    #: none, so these stay 0 unless a container names the resource
    spec_gpu_mem: int = 0
    spec_gpu_count: int = 0


@dataclass
class GpuCluster(Cluster):
    def with_new_nodes(self, k: int) -> "GpuCluster":
        if not k:
            return self
        t = self.new_node
        extra = [
            GpuNodeSpec(f"new-{i}", t.cpu_m, t.mem_bytes, t.pods, dict(t.labels, **{HOSTNAME: f"new-{i}"}),
                        gpus=getattr(t, "gpus", 0), gpu_mem=getattr(t, "gpu_mem", 0))
            for i in range(k)
        ]
        return GpuCluster(self.nodes + extra, self.bound, self.workloads, self.new_node)


class Reference(ResourcesReference):
    def __init__(self, cluster: Cluster, precision: str = "float32") -> None:
        super().__init__(cluster, precision)
        gpus = np.array([getattr(nd, "gpus", 0) for nd in cluster.nodes], np.int64)
        each = np.array([getattr(nd, "gpu_mem", 0) for nd in cluster.nodes], np.int64)
        width = max(int(gpus.max()) if self.n else 0, 1)
        #: bytes of every device, 0 where the node has none: int64 [n, width]
        self.gpu_total = np.where(np.arange(width)[None, :] < gpus[:, None], each[:, None], 0)
        self.gpu_free = self.gpu_total.copy()
        #: the two columns of allocatable a GPU node declares, as the share score reads them
        self.cap_gpu_mem = (gpus * each).astype(F32)
        self.cap_gpu_count = gpus.astype(F32)
        #: the node of every pod bound so far, per workload, in order
        self._bound: Dict[int, List[int]] = {}

    # -- one workload -------------------------------------------------------

    def _enter(self, wi: int) -> dict:
        state = super()._enter(wi)
        w, q = self.cluster.workloads[wi], self.q
        # the share over the declared extended resources, beside cpu and memory
        for req, cap in ((F32(getattr(w, "spec_gpu_mem", 0)), self.cap_gpu_mem),
                         (F32(getattr(w, "spec_gpu_count", 0)), self.cap_gpu_count)):
            avail = q(q(cap) - q(req))
            with np.errstate(divide="ignore", invalid="ignore"):
                s = np.where(avail == 0, F32(1.0 if req else 0.0), q(q(req) / avail))
            share = np.where(cap > 0, np.maximum(s, F32(0.0)), F32(0.0))
            state["share_raw"] = np.maximum(state["share_raw"], q(share * F32(100.0))).astype(F32)
        state["selector"] = state["sel"]
        state["gpu_mem"] = int(getattr(w, "gpu_mem", 0))
        state["gpu_count"] = int(getattr(w, "gpu_count", 0))
        return state

    # -- one pod ------------------------------------------------------------

    def gpu_slots(self) -> np.ndarray:
        """Slots of the entered workload's size every node still has: int64 [n]."""
        return (self.gpu_free // max(self._w["gpu_mem"], 1)).sum(axis=1)

    def gpu_filter(self) -> np.ndarray:
        w = self._w
        if not w["gpu_mem"]:
            return np.ones(self.n, bool)
        return (self.gpu_slots() >= w["gpu_count"]) & (w["gpu_count"] > 0)

    def step(self) -> Tuple[np.ndarray, np.ndarray]:
        """`kube_reference`'s step with the Open-Gpu-Share filter beside its
        filters: the mask joins the node selector's, the one static mask that
        step takes, so the feasible set every normalisation runs over is the
        filtered one."""
        self._w["sel"] = self._w["selector"] & self.gpu_filter()
        return super().step()

    def allocate(self, node: int) -> np.ndarray:
        """Slots the entered workload's next pod takes of each device of
        `node`: int64 [width]. All 0 where it asks no GPU, or where nothing
        fits (a pod the program put where the filter says no)."""
        mem, count = self._w["gpu_mem"], self._w["gpu_count"]
        free = self.gpu_free[node]
        take = np.zeros_like(free)
        if not mem or count <= 0:
            return take
        if count == 1:
            fits = np.nonzero(free >= mem)[0]
            if fits.size:
                take[fits[np.argmin(free[fits])]] = 1  # argmin: the first among equals
            return take
        left = count
        for d in range(free.shape[0]):
            take[d] = min(int(free[d] // mem), left)
            left -= take[d]
        return take

    def bind(self, node: int) -> None:
        take = self.allocate(node)
        super().bind(node)
        self.gpu_free[node] -= take * self._w["gpu_mem"]
        self._bound.setdefault(self._w["wi"], []).append(node)

    # -- answers ------------------------------------------------------------

    def order(self) -> Order:
        """What was bound, in the form `replay` takes: how the control (this
        reference in lower precision) is put in the program's place."""
        nodes, workloads = self.cluster.nodes, self.cluster.workloads
        return {workloads[wi].name: [nodes[i].name for i in seq] for wi, seq in self._bound.items()}

    def devices(self) -> Devices:
        """Bytes in use on every device that exists."""
        used = self.gpu_total - self.gpu_free
        return {(self.cluster.nodes[i].name, int(d)): int(used[i, d])
                for i, d in zip(*np.nonzero(self.gpu_total))}


def replay(cluster: Cluster, placed: Order, unscheduled: Dict[str, int],
           precision: str = "float32") -> Dict[str, float]:
    """The program's answer followed pod by pod. At each pod the reference
    computes its own filter and scores from the state built so far; a pod the
    program put elsewhere than the reference's best node is misplaced and the
    score it gave up is recorded; one it put where the filter (the device
    filter included) says no is infeasible. Then the reference binds where
    the program did, so each choice is judged in the state the program made
    it."""
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
            node = ref.index.get(name)
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


def device_diff(cluster: Cluster, placed: Order, devices: Optional[Devices]) -> int:
    """Devices whose memory in use, at the end of the plan, is not what the
    reference's own allocation gives when every pod sits on the node the
    answer names: the program's choice of device, pod by pod, against
    `allocate`. `devices` is the answer's table, (node, device) -> bytes; a
    device it leaves out is in use for 0 bytes, one it names that does not
    exist differs. None (an answer that carries no table) is judged on the
    order alone and reads 0."""
    ref = Reference(cluster)
    for wi in queue_order(cluster.workloads):
        ref._enter(wi)
        for name in placed.get(cluster.workloads[wi].name, ()):
            if name in ref.index:
                ref.bind(ref.index[name])
    if devices is None:
        return 0
    own = ref.devices()
    return sum(1 for key in set(own) | set(devices) if own.get(key, 0) != devices.get(key, 0))
