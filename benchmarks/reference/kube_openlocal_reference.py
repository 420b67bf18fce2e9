"""The plain reference with Open-Local: `kube_reference.Reference` plus
open-simulator's local-storage plugin, one pod at a time.

Independent of the program under test, like the reference it extends: it
imports nothing of `opensim_tpu` and is given the cluster as plain data. A
node here also carries its volume groups (name, bytes) and its exclusive
devices (name, bytes, media), what the `NodeLocalStorage` of open-local
reports; a workload its claims: the bytes of each `open-local-lvm` claim and
the bytes and media of each device claim (`open-local-device-ssd`,
`open-local-device-hdd`) of its volumeClaimTemplates. The state is the free
bytes of every VG and device of every node, whole bytes in int64, so that no
sum or comparison is ever rounded.

Written from what `PARITY.md` (#3, #4) and the open-local oracles of
`tests/test_k8s_oracle.py` cite of open-local's `common.go` (binpack, the
strategy the simulator's plugin runs):

Filter: the pod's LVM claims, summed, fit the free bytes of some VG of the
  node; and its device claims admit a matching of one whole free device of
  their media each, a device being free while no claim holds it.
Score (ScoreLVM / ScoreDevice, MaxScore 10): the mean over the pod's units of
  requested / capacity: one unit for its LVM claims together, over the
  capacity of the VG they would be bound to; one unit per device claim, over
  the capacity of the device it would take. Min-max normalised over the
  feasible nodes to 0..100, at weight 1, added after the share score.
Bind: the LVM claims, summed, go to the tightest VG that fits (least free
  bytes, the lowest index among equals); the device claims of each media, the
  smallest first, each take the smallest free device of that media that
  fits (the lowest index among equals), whole.

Departures, each kept where the program keeps it: (1) the score is kept
unrounded in float32 where open-local truncates `int64(score * MaxScore)`,
as `kube_reference` keeps kube's scores; (2) a pod's claims of one media are
scored as that many claims of the largest, over the smallest device that fits
it, and not device by device: this is exact for every workload of these
configurations, whose claims of one media are equal and whose devices of one
media on a node are equal; (3) the filter asks for a true matching where the
vendored `CheckExclusiveResourceMeetsPVCSize` can pass a node with a claim
left over (`PARITY.md` #3: devices [10, 20] against claims [15, 25] is
infeasible here); (4) several LVM claims of a pod are one allocation in one
VG (`PARITY.md` #4), exact here with one VG a node.

`precision="bfloat16"` is the low-precision control, as there: the operands
of every score and each arithmetic step are rounded to bfloat16; filters and
the storage arithmetic stay exact.

`replay` follows the program pod by pod in the order it scheduled them, as
`kube_gpushare_reference.replay` does: where a volume fits or not decides
which nodes are feasible for every later pod.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

from .kube_reference import F32, HOSTNAME, NEG, Cluster, NodeSpec, Reference as ResourcesReference, Workload, queue_order

Order = Dict[str, List[str]]  # workload -> the node of each of its pods, in the order they were scheduled
MEDIA = ("ssd", "hdd")
W_LOCAL = 1.0
MAX_SCORE = 10.0


@dataclass
class LocalNodeSpec(NodeSpec):
    vgs: Tuple[Tuple[str, int], ...] = ()  # (name, bytes)
    devices: Tuple[Tuple[str, int, str], ...] = ()  # (name, bytes, media)


@dataclass
class LocalWorkload(Workload):
    kind: str = "Deployment"
    lvm: Tuple[int, ...] = ()  # bytes of each LVM claim
    devices: Tuple[Tuple[int, str], ...] = ()  # (bytes, media) of each device claim


@dataclass
class LocalCluster(Cluster):
    def with_new_nodes(self, k: int) -> "LocalCluster":
        if not k:
            return self
        t = self.new_node
        extra = [
            LocalNodeSpec(f"new-{i}", t.cpu_m, t.mem_bytes, t.pods, dict(t.labels, **{HOSTNAME: f"new-{i}"}),
                          vgs=getattr(t, "vgs", ()), devices=getattr(t, "devices", ()))
            for i in range(k)
        ]
        return LocalCluster(self.nodes + extra, self.bound, self.workloads, self.new_node)


class Reference(ResourcesReference):
    def __init__(self, cluster: Cluster, precision: str = "float32") -> None:
        super().__init__(cluster, precision)
        nodes = cluster.nodes
        n_vg = max([len(getattr(nd, "vgs", ())) for nd in nodes] + [1])
        n_dev = max([len(getattr(nd, "devices", ())) for nd in nodes] + [1])
        #: bytes of every VG and device, 0 where the node has none: int64 [n, width]
        self.vg_cap = np.zeros((self.n, n_vg), np.int64)
        self.dev_cap = np.zeros((self.n, n_dev), np.int64)
        #: media of every device, an index of MEDIA, -1 where there is none
        self.dev_media = np.full((self.n, n_dev), -1, np.int64)
        for i, nd in enumerate(nodes):
            for j, (_name, size) in enumerate(getattr(nd, "vgs", ())):
                self.vg_cap[i, j] = size
            for j, (_name, size, media) in enumerate(getattr(nd, "devices", ())):
                self.dev_cap[i, j] = size
                self.dev_media[i, j] = MEDIA.index(media)
        self.vg_free = self.vg_cap.copy()
        #: whether a claim holds the device: bool [n, width]
        self.dev_held = np.zeros((self.n, n_dev), bool)
        self._bound: Dict[int, List[int]] = {}

    # -- one workload -------------------------------------------------------

    def _enter(self, wi: int) -> dict:
        state = super()._enter(wi)
        w = self.cluster.workloads[wi]
        state["selector"] = state["sel"]
        state["lvm"] = int(sum(getattr(w, "lvm", ())))
        # each media's claims, the smallest first
        claims = getattr(w, "devices", ())
        state["claims"] = [sorted(size for size, media in claims if media == m) for m in MEDIA]
        return state

    # -- one pod ------------------------------------------------------------

    def match(self, nodes: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """The entered workload's device claims on each of `nodes` (ssd, then
        hdd, each the smallest first, onto the smallest free device of its
        media that holds it): (whether every claim found a device: bool [k],
        the devices they took: bool [k, width])."""
        cap, media = self.dev_cap[nodes], self.dev_media[nodes]
        taken = np.zeros(cap.shape, bool)
        ok = np.ones(len(nodes), bool)
        rows = np.arange(len(nodes))
        for m, sizes in enumerate(self._w["claims"]):
            for size in sizes:
                fits = (media == m) & ~self.dev_held[nodes] & ~taken & (cap >= size)
                found = fits.any(axis=1)
                d = np.argmin(np.where(fits, cap, np.iinfo(np.int64).max), axis=1)  # the first among equals
                taken[rows[found], d[found]] = True
                ok &= found
        return ok, taken

    def vg_of(self, nodes: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """The tightest VG of each of `nodes` that holds the entered
        workload's LVM claims: (whether one does: bool [k], its index: [k])."""
        free = self.vg_free[nodes]
        fits = free >= self._w["lvm"]
        return fits.any(axis=1), np.argmin(np.where(fits, free, np.iinfo(np.int64).max), axis=1)

    def local_filter(self) -> np.ndarray:
        w, every = self._w, np.arange(self.n)
        ok = np.ones(self.n, bool)
        if w["lvm"]:
            ok &= self.vg_of(every)[0]
        if any(w["claims"]):
            ok &= self.match(every)[0]
        return ok

    def local_raw(self) -> np.ndarray:
        """The binpack score before normalisation, 0..10 a node: float32 [n].
        Read on the nodes the filter passes, where every claim has its VG and
        its device."""
        w, q = self._w, self.q
        lvm, claims = w["lvm"], w["claims"]
        parts = np.zeros(self.n, F32)
        if lvm:
            fits, v = self.vg_of(np.arange(self.n))
            cap = np.maximum(self.vg_cap[np.arange(self.n), v], 1).astype(F32)
            parts = np.where(fits, q(F32(lvm) / cap), F32(0.0)).astype(F32)
        for m, sizes in enumerate(claims):
            if not sizes:
                continue
            size = sizes[-1]  # departure (2): the largest claim of the media, as many times as it has claims
            fits = (self.dev_media == m) & ~self.dev_held & (self.dev_cap >= size)
            smallest = np.where(fits, self.dev_cap, np.iinfo(np.int64).max).min(axis=1)
            term = q(q(F32(len(sizes)) * F32(size)) / np.maximum(smallest, 1).astype(F32))
            parts = np.where(fits.any(axis=1), q(parts + term), parts).astype(F32)
        units = (1 if lvm else 0) + sum(len(c) for c in claims)
        return q(q(parts / F32(units)) * F32(MAX_SCORE)).astype(F32)

    def step(self) -> Tuple[np.ndarray, np.ndarray]:
        """`kube_reference`'s step with the Open-Local filter joined to the
        node selector's mask, and the Open-Local score after the others."""
        self._w["sel"] = self._w["selector"] & self.local_filter()
        feasible, score = super().step()
        if (self._w["lvm"] or any(self._w["claims"])) and feasible.any():
            q, hundred = self.q, F32(100.0)
            raw = self.local_raw()
            lo, hi = raw[feasible].min(), raw[feasible].max()
            rng = q(hi - lo)
            if rng > 0:
                norm = q(q(q(raw - lo) * hundred) / rng)
                score = q(score + q(F32(W_LOCAL) * norm))
        return feasible, score.astype(F32)

    def bind(self, node: int) -> None:
        """The claims go where `vg_of` and `match` say; a pod the program put
        where they do not fit takes nothing (its placement is already read as
        infeasible)."""
        w, at = self._w, np.array([node])
        if w["lvm"]:
            fits, v = self.vg_of(at)
            if fits[0]:
                self.vg_free[node, v[0]] -= w["lvm"]
        if any(w["claims"]):
            ok, taken = self.match(at)
            if ok[0]:
                self.dev_held[node] |= taken[0]
        super().bind(node)
        self._bound.setdefault(w["wi"], []).append(node)

    # -- answers ------------------------------------------------------------

    def order(self) -> Order:
        """What was bound, in the form `replay` takes: how the control (this
        reference in lower precision) is put in the program's place."""
        nodes, workloads = self.cluster.nodes, self.cluster.workloads
        return {workloads[wi].name: [nodes[i].name for i in seq] for wi, seq in self._bound.items()}

    def storage(self) -> Dict[Tuple[str, str], Tuple[str, int, int]]:
        """Every VG and device that exists, as (node, name) -> ("VG", bytes
        requested, capacity) or ("Device", 1 where a claim holds it else 0,
        capacity)."""
        out: Dict[Tuple[str, str], Tuple[str, int, int]] = {}
        for i, nd in enumerate(self.cluster.nodes):
            for j, (name, size) in enumerate(getattr(nd, "vgs", ())):
                out[(nd.name, name)] = ("VG", int(size - self.vg_free[i, j]), int(size))
            for j, (name, size, _media) in enumerate(getattr(nd, "devices", ())):
                out[(nd.name, name)] = ("Device", int(self.dev_held[i, j]), int(size))
        return out


def follow(cluster: Cluster, placed: Order) -> Reference:
    """The reference with every pod bound where the answer put it, by its
    own choice of VG and device: what `storage` reads at the end of the plan."""
    ref = Reference(cluster)
    for wi in queue_order(cluster.workloads):
        ref._enter(wi)
        for name in placed.get(cluster.workloads[wi].name, ()):
            if name in ref.index:
                ref.bind(ref.index[name])
    return ref


def replay(cluster: Cluster, placed: Order, unscheduled: Dict[str, int],
           precision: str = "float32") -> Dict[str, float]:
    """The program's answer followed pod by pod. At each pod the reference
    computes its own filter and scores from the state built so far; a pod the
    program put elsewhere than the reference's best node is misplaced and the
    score it gave up is recorded; one it put where the filter (the storage
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
