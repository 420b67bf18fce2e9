#!/usr/bin/env python
"""Measured serial baseline: a faithful object-at-a-time re-implementation
of the reference's scheduling loop, timed on the BASELINE.md configs.

The Go reference has no published numbers and no Go toolchain exists in
this environment, so BENCH.md carries a modeled Go cost bracket
(tools/go_baseline_proxy.py). This tool adds a MEASURED floor: the exact
serial pipeline the reference runs —

    for each pod:                      # simulator.go:309-348
        filter all nodes               # generic_scheduler.go:131-180
        score the feasible set         # framework.RunScorePlugins
        bind the best                  # lowest index on ties (see below)

— implemented object-at-a-time over Pod/Node objects with kube's own
incremental NodeInfo/PreFilter design (scheduler framework types.go
NodeInfo; interpodaffinity/filtering.go PreFilter maps), never touching
the tensor encodings or JAX. Semantics match the independent kube oracle
(tests/test_k8s_oracle.py) and the engines: the default plugin set with
registry.go:119-132 weights plus Simon/Open-Local/Open-Gpu-Share, the
Reserve-updated gpu-count allocatable, and the deterministic lowest-index
tie-break (the engines' documented divergence from reservoir sampling).

Honesty note, stated plainly: this floor is measured in *Python*, which is
slower than the reference's Go per operation — so the speedups computed
against it OVERSTATE nothing: the vectorized engines' advantage vs real Go
is smaller than vs this floor by roughly the Go-vs-Python constant, which
the modeled brackets in BENCH.md estimate. Conversely kube's 16-goroutine
parallelism is absent here, as it is in the serial loop timed above.

Usage:
  python tools/serial_baseline.py --config all            # the 5 configs
  python tools/serial_baseline.py --config plan           # 50k/5k headline
  python tools/serial_baseline.py --config synthetic --pods 1000 --nodes 100

Each run prints one JSON line per config and (with --out, default
BASELINE_MEASURED.json) merges results into a file bench.py reads to
report `vs_serial`.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from opensim_tpu.models import selectors  # noqa: E402
from opensim_tpu.models.objects import Node, Pod  # noqa: E402
from opensim_tpu.models.quantity import parse_quantity  # noqa: E402

HOSTNAME = "kubernetes.io/hostname"
GPU_MEM = "alibabacloud.com/gpu-mem"
GPU_COUNT = "alibabacloud.com/gpu-count"
NONZERO_CPU = 0.1
NONZERO_MEM = 200.0 * 1024 * 1024

W_BALANCED = 1.0
W_LEAST = 1.0
W_NODE_AFFINITY = 1.0
W_TAINT = 1.0
W_INTERPOD = 1.0
W_SPREAD = 2.0
W_SHARE = 2.0  # Simon (1) + Open-Gpu-Share (1): same formula and norm
W_LOCAL = 1.0
W_AVOID = 10000.0


def _sel_key(sel) -> str:
    return json.dumps(sel, sort_keys=True) if sel is not None else "null"


def _term_sig(term: dict, owner_ns: str):
    ns = tuple(sorted(term.get("namespaces") or [owner_ns]))
    return (ns, _sel_key(term.get("labelSelector")), term.get("topologyKey", ""))


def _sig_matches(sig, pod: Pod) -> bool:
    ns, sel_key, _key = sig
    if pod.metadata.namespace not in ns:
        return False
    sel = json.loads(sel_key)
    if sel is None:
        return False
    return selectors.match_label_selector(sel, pod.metadata.labels)


def _terms(pod: Pod, kind: str, mode: str):
    aff = (pod.spec.affinity or {}).get(kind) or {}
    return aff.get(f"{mode}DuringSchedulingIgnoredDuringExecution") or []


def _pod_gpu(pod: Pod):
    return pod.gpu_mem_request(), (
        pod.gpu_count_request() if pod.gpu_mem_request() > 0 else 0
    )


def _pod_local(pod: Pod):
    lvm, devs = 0.0, []
    for v in pod.local_volumes():
        kind = str(v.get("kind", ""))
        try:
            size = float(parse_quantity(v.get("size", 0)))
        except ValueError:
            continue
        if kind == "LVM":
            lvm += size
        elif kind in ("SSD", "HDD"):
            devs.append((size, kind))
    return lvm, devs


class CarrierCounts:
    """Per-(term signature) domain tallies contributed by BOUND pods that
    CARRY the term — kube's topologyToMatchedExistingAntiAffinityTerms and
    the symmetric preferred/hard-affinity weight maps (scoring.go
    processExistingPod), memoized by signature so one workload's identical
    pods share an entry."""

    def __init__(self):
        self.entries = {}  # sig -> {val: weight}

    def add(self, sig, node_val, w: float):
        if node_val is None:
            return
        m = self.entries.get(sig)
        if m is None:
            m = self.entries[sig] = {}
        m[node_val] = m.get(node_val, 0.0) + w

    def matching(self, pod: Pod):
        """[(topology key, {val: weight})] for sigs whose term matches."""
        out = []
        for sig, m in self.entries.items():
            if m and _sig_matches(sig, pod):
                out.append((sig[2], m))
        return out


class MatchCounts:
    """Per-(term-set signature) counts of bound pods MATCHING the terms,
    per topology value — kube's PreFilter count maps
    (interpodaffinity/filtering.go:113-127 podsMatchingAllTerms;
    podtopologyspread calPreFilterState). Registered lazily on first
    sight (one backfill scan over bound pods), then maintained
    incrementally at every bind."""

    def __init__(self, scheduler: "SerialScheduler"):
        self.sched = scheduler
        self.entries = {}  # sigset -> {"maps": [dict], "total": float}

    def get(self, terms, owner_ns):
        sigset = tuple(_term_sig(t, owner_ns) for t in terms)
        e = self.entries.get(sigset)
        if e is None:
            maps = [{} for _ in sigset]
            total = 0.0
            for q, ni in self.sched.bound:
                if all(_sig_matches(s, q) for s in sigset):
                    for s, m in zip(sigset, maps):
                        val = ni.labels.get(s[2])
                        if val is not None:
                            m[val] = m.get(val, 0.0) + 1.0
                            total += 1.0
            e = self.entries[sigset] = {"maps": maps, "total": total}
        return e

    def on_bind(self, pod: Pod, ni: "NodeInfo"):
        for sigset, e in self.entries.items():
            if all(_sig_matches(s, pod) for s in sigset):
                for s, m in zip(sigset, e["maps"]):
                    val = ni.labels.get(s[2])
                    if val is not None:
                        m[val] = m.get(val, 0.0) + 1.0
                        e["total"] += 1.0


class NodeInfo:
    """Cached per-node aggregates — framework.NodeInfo (types.go): the
    serial loop's answer to not rescanning every bound pod per decision."""

    __slots__ = (
        "node", "idx", "name", "labels", "alloc", "taints", "unschedulable",
        "used", "nz_cpu", "nz_mem", "ports", "n_pods", "gpu_free", "has_dev",
        "vgs", "devs", "avoid", "prefer_taints",
    )

    def __init__(self, node: Node, idx: int):
        self.node = node
        self.idx = idx
        self.name = node.metadata.name
        self.labels = node.metadata.labels
        self.alloc = dict(node.allocatable)
        self.taints = node.taints
        self.unschedulable = node.unschedulable
        self.used = {}
        self.nz_cpu = 0.0
        self.nz_mem = 0.0
        self.ports = []  # ContainerPort of bound pods
        self.n_pods = 0
        total = node.allocatable.get(GPU_MEM, 0.0)
        cnt = int(node.allocatable.get(GPU_COUNT, 0))
        self.gpu_free = [total / cnt] * cnt if cnt > 0 and total > 0 else []
        self.has_dev = bool(self.gpu_free)
        self.vgs, self.devs = [], []
        raw = node.metadata.annotations.get("simon/node-local-storage")
        if raw:
            try:
                data = json.loads(raw)
            except ValueError:
                data = {}
            for vg in data.get("vgs") or []:
                cap = float(parse_quantity(vg.get("capacity", 0)))
                self.vgs.append([cap, cap])  # [free, cap]
            for d in data.get("devices") or []:
                cap = float(parse_quantity(d.get("capacity", 0)))
                media = "SSD" if str(d.get("mediaType", "")).lower() == "ssd" else "HDD"
                self.devs.append([cap, media, cap])  # [free, media, cap]
        self.avoid = set()
        anno = node.metadata.annotations.get(
            "scheduler.alpha.kubernetes.io/preferAvoidPods"
        )
        if anno:
            try:
                entries = json.loads(anno).get("preferAvoidPods") or []
            except (ValueError, AttributeError):
                entries = []
            for e in entries:
                pc = ((e.get("podSignature") or {}).get("podController") or {})
                self.avoid.add((str(pc.get("kind", "")), str(pc.get("uid", ""))))
        self.prefer_taints = any(t.effect == "PreferNoSchedule" for t in node.taints)

    def alloc_view(self) -> dict:
        """Reserve-updated allocatable (open-gpu-share.go:147-188): on
        device-bearing nodes gpu-count = count of not-fully-used devices."""
        if not self.has_dev:
            return self.alloc
        a = dict(self.alloc)
        a[GPU_COUNT] = float(sum(1 for f in self.gpu_free if f > 0))
        return a


class SerialScheduler:
    def __init__(self, nodes):
        self.nodes = [NodeInfo(n, i) for i, n in enumerate(nodes)]
        self.by_name = {ni.name: ni for ni in self.nodes}
        self.bound = []  # (pod, NodeInfo)
        self.exist_anti = CarrierCounts()
        self.sym_pref = CarrierCounts()
        self.match_counts = MatchCounts(self)
        # static topology facts
        self.key_vals = {}  # key -> set of values over all nodes
        for ni in self.nodes:
            for k, v in ni.labels.items():
                self.key_vals.setdefault(k, set()).add(v)
        self.any_prefer_taints = any(ni.prefer_taints for ni in self.nodes)
        self.any_avoid = any(ni.avoid for ni in self.nodes)
        self._eligible_cache = {}

    # -- filters -------------------------------------------------------------

    def _static_ok(self, pod: Pod, ni: NodeInfo) -> bool:
        if ni.unschedulable:
            return False
        if pod.spec.node_name and pod.spec.node_name != ni.name:
            return False
        if not selectors.pod_matches_node_selector_and_affinity(pod, ni.node):
            return False
        if ni.taints and selectors.find_untolerated_taint(
            ni.taints, pod.spec.tolerations
        ):
            return False
        return True

    def _fit_ok(self, req: dict, ni: NodeInfo) -> bool:
        alloc = ni.alloc_view()
        used = ni.used
        for k, v in req.items():
            if v > 0 and used.get(k, 0.0) + v > alloc.get(k, 0.0):
                return False
        return True

    def _ports_ok(self, mine, ni: NodeInfo) -> bool:
        for theirs in ni.ports:
            for m in mine:
                if m.protocol != theirs.protocol or m.host_port != theirs.host_port:
                    continue
                ia = "" if m.host_ip in ("", "0.0.0.0") else m.host_ip
                ib = "" if theirs.host_ip in ("", "0.0.0.0") else theirs.host_ip
                if ia == ib or ia == "" or ib == "":
                    return False
        return True

    def _gpu_ok(self, mem, cnt, ni: NodeInfo) -> bool:
        if mem <= 0:
            return True
        return cnt > 0 and sum(int(f // mem) for f in ni.gpu_free) >= cnt

    def _local_ok(self, lvm, devs, ni: NodeInfo) -> bool:
        if lvm > 0 and not any(free >= lvm for free, _cap in ni.vgs):
            return False
        taken = set()
        for media in ("SSD", "HDD"):
            for size, _m in sorted(v for v in devs if v[1] == media):
                pick, pick_cap = None, None
                for idx, (free, m, cap) in enumerate(ni.devs):
                    if idx in taken or m != media or free < size or free <= 0:
                        continue
                    if pick is None or cap < pick_cap:
                        pick, pick_cap = idx, cap
                if pick is None:
                    return False
                taken.add(pick)
        return True

    def _eligible_vals(self, pod: Pod, key: str):
        """Values of `key` over nodes passing the pod's node affinity —
        the PreFilter's eligible-domain set, cached by the pod's static
        node-affinity signature (pods of one workload share it)."""
        sig = (
            tuple(sorted(pod.spec.node_selector.items())),
            _sel_key((pod.spec.affinity or {}).get("nodeAffinity")),
            key,
        )
        vals = self._eligible_cache.get(sig)
        if vals is None:
            vals = {
                ni.labels[key]
                for ni in self.nodes
                if key in ni.labels
                and selectors.pod_matches_node_selector_and_affinity(pod, ni.node)
            }
            self._eligible_cache[sig] = vals
        return vals

    # -- one pod through the pipeline ----------------------------------------

    def schedule_one(self, pod: Pod):
        """Filter -> Score -> select (generic_scheduler.go:131-180 with
        PercentageOfNodesToScore=100). Returns the chosen NodeInfo or None."""
        ns = pod.metadata.namespace
        req = dict(pod.resource_requests())
        req["pods"] = req.get("pods", 0.0) + 1
        mine_ports = pod.host_ports()
        gpu_mem, gpu_cnt = _pod_gpu(pod)
        lvm, dev_vols = _pod_local(pod)

        # PreFilter: incoming interpod terms and spread constraints
        anti_terms = _terms(pod, "podAntiAffinity", "required")
        aff_terms = _terms(pod, "podAffinity", "required")
        anti_entries = [
            (t.get("topologyKey", ""), self.match_counts.get([t], ns))
            for t in anti_terms
        ]
        aff_entry = self.match_counts.get(aff_terms, ns) if aff_terms else None
        exist_anti_hits = self.exist_anti.matching(pod)

        hard_spread, soft_spread = [], []
        explicit = pod.spec.topology_spread_constraints
        if explicit:
            for c in explicit:
                lst = (
                    hard_spread
                    if c.get("whenUnsatisfiable", "DoNotSchedule") == "DoNotSchedule"
                    else soft_spread
                )
                lst.append(c)
        else:
            owner = self._owner_selector(pod)
            if owner is not None:
                soft_spread = [
                    {"topologyKey": HOSTNAME, "maxSkew": 3, "labelSelector": owner},
                    {"topologyKey": "topology.kubernetes.io/zone", "maxSkew": 5,
                     "labelSelector": owner},
                ]
        spread_pre = []
        for c in hard_spread:
            key = c.get("topologyKey", "")
            entry = self.match_counts.get(
                [{"labelSelector": c.get("labelSelector"), "topologyKey": key,
                  "namespaces": [ns]}], ns)
            elig = self._eligible_vals(pod, key)
            cnts = entry["maps"][0]
            min_cnt = min((cnts.get(v, 0.0) for v in elig), default=None)
            self_match = (
                1.0
                if c.get("labelSelector") is not None
                and selectors.match_label_selector(
                    c.get("labelSelector"), pod.metadata.labels)
                else 0.0
            )
            spread_pre.append((key, cnts, min_cnt, float(c.get("maxSkew", 1)),
                               self_match))

        # -- Filter over all nodes
        feasible = []
        for ni in self.nodes:
            if not self._static_ok(pod, ni):
                continue
            if not self._fit_ok(req, ni):
                continue
            if mine_ports and not self._ports_ok(mine_ports, ni):
                continue
            # spread hard (filtering.go:276)
            ok = True
            for key, cnts, min_cnt, skew, self_match in spread_pre:
                val = ni.labels.get(key)
                if val is None or min_cnt is None:
                    ok = False
                    break
                if cnts.get(val, 0.0) + self_match - min_cnt > skew:
                    ok = False
                    break
            if not ok:
                continue
            # existing pods' required anti-affinity vs this pod
            for key, m in exist_anti_hits:
                val = ni.labels.get(key)
                if val is not None and m.get(val, 0.0) > 0:
                    ok = False
                    break
            if not ok:
                continue
            # incoming required anti terms (node missing key: vacuous)
            for t, (key, entry) in zip(anti_terms, anti_entries):
                val = ni.labels.get(key)
                if val is not None and entry["maps"][0].get(val, 0.0) > 0:
                    ok = False
                    break
            if not ok:
                continue
            # incoming required affinity (satisfyPodAffinity + bootstrap)
            if aff_terms:
                labels_ok = all(
                    ni.labels.get(t.get("topologyKey", "")) is not None
                    for t in aff_terms
                )
                per_term = labels_ok and all(
                    m.get(ni.labels.get(s[2]), 0.0) > 0
                    for s, m in zip(
                        (tuple(_term_sig(t, ns) for t in aff_terms)),
                        aff_entry["maps"],
                    )
                )
                if not per_term:
                    bootstrap = (
                        labels_ok
                        and aff_entry["total"] == 0.0
                        and all(
                            selectors.affinity_term_matches_pod(t, ns, pod)
                            for t in aff_terms
                        )
                    )
                    if not bootstrap:
                        continue
            if gpu_mem > 0 and not self._gpu_ok(gpu_mem, gpu_cnt, ni):
                continue
            if (lvm > 0 or dev_vols) and not self._local_ok(lvm, dev_vols, ni):
                continue
            feasible.append(ni)

        if not feasible:
            return None

        # -- Score (per-plugin normalization over the feasible list)
        scores = [0.0] * len(feasible)
        cpu_req = req.get("cpu") or NONZERO_CPU
        mem_req = req.get("memory") or NONZERO_MEM
        for i, ni in enumerate(feasible):
            ac = ni.alloc.get("cpu", 0.0)
            am = ni.alloc.get("memory", 0.0)
            rc = ni.nz_cpu + cpu_req
            rm = ni.nz_mem + mem_req
            ls = 0.0 if (ac == 0 or rc > ac) else (ac - rc) * 100.0 / ac
            ms = 0.0 if (am == 0 or rm > am) else (am - rm) * 100.0 / am
            scores[i] += W_LEAST * (ls + ms) / 2.0
            cf = rc / ac if ac else 0.0
            mf = rm / am if am else 0.0
            bal = 0.0 if (cf >= 1 or mf >= 1) else (1.0 - abs(cf - mf)) * 100.0
            scores[i] += W_BALANCED * bal

        pna = (pod.spec.affinity or {}).get("nodeAffinity") or {}
        if pna.get("preferredDuringSchedulingIgnoredDuringExecution"):
            raw = [float(selectors.node_affinity_preferred_score(pod, ni.node))
                   for ni in feasible]
            mx = max(raw, default=0.0)
            for i, v in enumerate(raw):
                scores[i] += W_NODE_AFFINITY * (v * 100.0 / mx if mx > 0 else v)

        if self.any_prefer_taints:
            raw = [
                float(selectors.count_intolerable_prefer_no_schedule(pod, ni.node))
                if ni.prefer_taints else 0.0
                for ni in feasible
            ]
            mx = max(raw, default=0.0)
            for i, v in enumerate(raw):
                scores[i] += W_TAINT * (100.0 - v * 100.0 / mx if mx > 0 else 100.0)

        self._interpod_score(pod, ns, feasible, scores)
        self._spread_score(pod, ns, soft_spread, feasible, scores)
        self._share_score(pod, feasible, scores)
        if lvm > 0 or dev_vols:
            self._local_score(lvm, dev_vols, feasible, scores)
        if self.any_avoid:
            ctrl = None
            for ref in pod.metadata.owner_references:
                if ref.controller and ref.kind in ("ReplicaSet",
                                                   "ReplicationController"):
                    ctrl = (ref.kind, ref.uid)
                    break
            for i, ni in enumerate(feasible):
                avoided = ctrl is not None and ctrl in ni.avoid
                scores[i] += W_AVOID * (0.0 if avoided else 100.0)

        best_i = 0
        for i in range(1, len(feasible)):
            if scores[i] > scores[best_i]:
                best_i = i
        return feasible[best_i]

    def _interpod_score(self, pod, ns, feasible, scores):
        # incoming preferred terms + symmetric carried terms (scoring.go)
        parts = []
        for tw in _terms(pod, "podAffinity", "preferred"):
            t = tw.get("podAffinityTerm") or {}
            e = self.match_counts.get([t], ns)
            parts.append((float(tw.get("weight", 0)), t.get("topologyKey", ""),
                          e["maps"][0]))
        for tw in _terms(pod, "podAntiAffinity", "preferred"):
            t = tw.get("podAffinityTerm") or {}
            e = self.match_counts.get([t], ns)
            parts.append((-float(tw.get("weight", 0)), t.get("topologyKey", ""),
                          e["maps"][0]))
        sym = self.sym_pref.matching(pod)
        if not parts and not sym:
            return
        raw = []
        for ni in feasible:
            s = 0.0
            for w, key, m in parts:
                val = ni.labels.get(key)
                if val is not None:
                    s += w * m.get(val, 0.0)
            for key, m in sym:
                val = ni.labels.get(key)
                if val is not None:
                    s += m.get(val, 0.0)
            raw.append(s)
        hi = max(max(raw), 0.0)
        lo = min(min(raw), 0.0)
        rng = hi - lo
        if rng > 0:
            for i, v in enumerate(raw):
                scores[i] += W_INTERPOD * 100.0 * (v - lo) / rng

    def _spread_score(self, pod, ns, soft, feasible, scores):
        if not soft:
            return
        pre = []
        for c in soft:
            key = c.get("topologyKey", "")
            e = self.match_counts.get(
                [{"labelSelector": c.get("labelSelector"), "topologyKey": key,
                  "namespaces": [ns]}], ns)
            size = len(self.key_vals.get(key, ()))
            pre.append((key, e["maps"][0], math.log(size + 2.0),
                        float(c.get("maxSkew", 1))))
        raw, ignored = [], []
        for ni in feasible:
            s, ig = 0.0, False
            for key, cnts, w, skew in pre:
                val = ni.labels.get(key)
                if val is None:
                    ig = True
                    continue
                s += cnts.get(val, 0.0) * w + (skew - 1.0)
            raw.append(s)
            ignored.append(ig)
        scored = [v for v, ig in zip(raw, ignored) if not ig]
        mx = max(scored, default=0.0)
        mn = min(scored, default=0.0)
        for i, (v, ig) in enumerate(zip(raw, ignored)):
            if ig:
                continue
            scores[i] += W_SPREAD * (100.0 if mx <= 0 else 100.0 * (mx + mn - v) / mx)

    def _share_score(self, pod, feasible, scores):
        req = pod.resource_requests()
        raw = []
        for ni in feasible:
            if not req:
                raw.append(100.0)
                continue
            best = 0.0
            for r, alloc in ni.alloc_view().items():
                pr = req.get(r, 0.0)
                avail = alloc - pr
                share = (1.0 if pr else 0.0) if avail == 0 else pr / avail
                if share > best:
                    best = share
            raw.append(best * 100.0)
        hi, lo = max(raw), min(raw)
        rng = hi - lo
        if rng > 0:
            for i, v in enumerate(raw):
                scores[i] += W_SHARE * (v - lo) * 100.0 / rng

    def _local_score(self, lvm, devs, feasible, scores):
        raw = []
        for ni in feasible:
            parts, count = 0.0, 0
            if lvm > 0:
                cands = [v for v in ni.vgs if v[0] >= lvm]
                if cands:
                    choice = min(cands, key=lambda v: v[0])
                    parts += lvm / choice[1]
                count += 1
            for media in ("SSD", "HDD"):
                sizes = [s for s, m in devs if m == media]
                if not sizes:
                    continue
                size = max(sizes)
                fitting = [d for d in ni.devs
                           if d[1] == media and d[0] >= size and d[0] > 0]
                if fitting:
                    parts += len(sizes) * size / min(d[2] for d in fitting)
                count += len(sizes)
            raw.append(parts / count * 10.0 if count else 0.0)
        hi, lo = max(raw), min(raw)
        rng = hi - lo
        if rng > 0:
            for i, v in enumerate(raw):
                scores[i] += W_LOCAL * (v - lo) * 100.0 / rng

    @staticmethod
    def _owner_selector(pod: Pod):
        # kube's DefaultSelector finds a selector for the pods of a ReplicaSet
        # (a Deployment's), an RC or a StatefulSet; a Job's or a DaemonSet's have none
        kind = pod.metadata.annotations.get("simon/workload-kind")
        if kind in ("ReplicaSet", "ReplicationController", "StatefulSet") and pod.metadata.labels:
            return {"matchLabels": dict(pod.metadata.labels)}
        return None

    # -- bind ----------------------------------------------------------------

    def bind(self, pod: Pod, ni: NodeInfo):
        self.bound.append((pod, ni))
        used = ni.used
        for k, v in pod.resource_requests().items():
            used[k] = used.get(k, 0.0) + v
        used["pods"] = used.get("pods", 0.0) + 1
        req = pod.resource_requests()
        ni.nz_cpu += req.get("cpu") or NONZERO_CPU
        ni.nz_mem += req.get("memory") or NONZERO_MEM
        ni.ports.extend(pod.host_ports())
        ni.n_pods += 1

        ns = pod.metadata.namespace
        for t in _terms(pod, "podAntiAffinity", "required"):
            key = t.get("topologyKey", "")
            self.exist_anti.add(_term_sig(t, ns), ni.labels.get(key), 1.0)
        for tw in _terms(pod, "podAffinity", "preferred"):
            t = tw.get("podAffinityTerm") or {}
            self.sym_pref.add(_term_sig(t, ns), ni.labels.get(t.get("topologyKey", "")),
                              float(tw.get("weight", 0)))
        for tw in _terms(pod, "podAntiAffinity", "preferred"):
            t = tw.get("podAffinityTerm") or {}
            self.sym_pref.add(_term_sig(t, ns), ni.labels.get(t.get("topologyKey", "")),
                              -float(tw.get("weight", 0)))
        for t in _terms(pod, "podAffinity", "required"):
            # HardPodAffinityWeight = 1 symmetric score contribution
            self.sym_pref.add(_term_sig(t, ns), ni.labels.get(t.get("topologyKey", "")),
                              1.0)
        self.match_counts.on_bind(pod, ni)

        mem, cnt = _pod_gpu(pod)
        if mem > 0 and cnt > 0 and ni.gpu_free:
            free = ni.gpu_free
            if cnt == 1:
                fitting = [i for i, f in enumerate(free) if f >= mem]
                if fitting:
                    tight = min(fitting, key=lambda i: (free[i], i))
                    free[tight] -= mem
            else:
                left = cnt
                for i, f in enumerate(free):
                    take = min(int(f // mem), left)
                    free[i] -= take * mem
                    left -= take
                    if left == 0:
                        break
        lvm, devs = _pod_local(pod)
        if lvm > 0:
            cands = [v for v in ni.vgs if v[0] >= lvm]
            if cands:
                min(cands, key=lambda v: v[0])[0] -= lvm
        if devs:
            taken = set()
            for media in ("SSD", "HDD"):
                for size, _m in sorted(v for v in devs if v[1] == media):
                    pick, pick_cap = None, None
                    for idx, (free, m, cap) in enumerate(ni.devs):
                        if idx in taken or m != media or free < size or free <= 0:
                            continue
                        if pick is None or cap < pick_cap:
                            pick, pick_cap = idx, cap
                    if pick is not None:
                        taken.add(pick)
                        ni.devs[pick][0] = 0.0


def run_serial(cluster, apps, progress=False):
    """Expand (reusing the package's expansion + ordering) then schedule
    the whole stream serially. Returns (n_scheduled, n_unscheduled,
    expand_s, schedule_s, chosen_names)."""
    from opensim_tpu.engine import queues
    from opensim_tpu.engine.simulator import _cluster_pods
    from opensim_tpu.models import expand
    from opensim_tpu.models.objects import LABEL_APP_NAME

    t0 = time.time()
    stream = []
    cluster_pods, _n_bare, _ds_sizes = _cluster_pods(cluster)
    for p in cluster_pods:
        stream.append((p, bool(p.spec.node_name)))
    for app in apps:
        pods = expand.generate_pods_from_resources(app.resources, cluster.nodes)
        for p in pods:
            p.metadata.labels.setdefault(LABEL_APP_NAME, app.name)
        pods = queues.toleration_sort(queues.affinity_sort(pods))
        stream.extend((p, bool(p.spec.node_name)) for p in pods)
    expand_s = time.time() - t0

    sched = SerialScheduler(cluster.nodes)
    scheduled = unscheduled = 0
    chosen = []
    t0 = time.time()
    for i, (pod, forced) in enumerate(stream):
        if progress and i and i % 5000 == 0:
            print(f"  ... {i}/{len(stream)} pods, {time.time() - t0:.1f}s",
                  file=sys.stderr)
        if forced:
            ni = sched.by_name.get(pod.spec.node_name)
            if ni is not None:
                sched.bind(pod, ni)
                scheduled += 1
                chosen.append(ni.name)
            else:
                unscheduled += 1
                chosen.append(None)
            continue
        ni = sched.schedule_one(pod)
        if ni is None:
            unscheduled += 1
            chosen.append(None)
        else:
            sched.bind(pod, ni)
            scheduled += 1
            chosen.append(ni.name)
    schedule_s = time.time() - t0
    return scheduled, unscheduled, expand_s, schedule_s, chosen


# ---------------------------------------------------------------------------
# the BASELINE.md configs
# ---------------------------------------------------------------------------

def _bench():
    import bench

    return bench


def _example(config_path: str):
    from opensim_tpu.planner.apply import Applier, Options

    a = Applier(Options(simon_config=config_path))
    return a.load_cluster(), a.load_apps()


def _runner(args):
    """--impl python → this module's run_serial; --impl c++ → the compiled
    serial engine (opensim_tpu/native/serial_engine.cc), the same pipeline
    in C++ — the measured stand-in for the Go reference's constant factor
    (placement parity asserted by tests/test_serial_baseline.py)."""
    if getattr(args, "impl", "python") == "c++":
        from opensim_tpu.native.serial import run_serial_native

        return run_serial_native, "c++-serial (same NodeInfo/PreFilter pipeline compiled -O3; see native/serial_engine.cc)"
    return run_serial, "python-serial (kube NodeInfo/PreFilter design; see module docstring)"


def run_config(name: str, args):
    from opensim_tpu.engine.simulator import AppResource

    bench = _bench()
    if name in ("example", "gpushare"):
        path = os.path.join(
            _REPO,
            "example/simon-config.yaml" if name == "example"
            else "example/simon-gpushare-config.yaml",
        )
        cluster, apps = _example(path)
        pods_n, nodes_n = None, len(cluster.nodes)
    elif name == "synthetic":
        pods_n, nodes_n = args.pods or 10000, args.nodes or 1000
        cluster = bench.synthetic_cluster(nodes_n)
        apps = [AppResource("bench", bench.synthetic_apps(pods_n))]
    elif name == "affinity":
        pods_n, nodes_n = args.pods or 5000, args.nodes or 500
        cluster = bench.synthetic_cluster(nodes_n)
        apps = [AppResource("bench", bench.affinity_apps(pods_n))]
    elif name == "plan":
        pods_n, nodes_n = args.pods or 50000, args.nodes or 5000
        cluster = bench.synthetic_cluster(nodes_n)
        apps = [AppResource("bench", bench.synthetic_apps(pods_n))]
    elif name == "defrag":
        return run_defrag(args)
    else:
        raise SystemExit(f"unknown config {name}")

    run, impl = _runner(args)
    scheduled, unscheduled, expand_s, schedule_s, _ = run(
        cluster, apps, progress=True
    )
    total = scheduled + unscheduled
    rec = {
        "config": name,
        "pods": total,
        "nodes": len(cluster.nodes),
        "expand_s": round(expand_s, 3),
        "schedule_s": round(schedule_s, 3),
        "pods_per_sec": round(total / schedule_s, 1) if schedule_s else None,
        "scheduled": scheduled,
        "unscheduled": unscheduled,
        "impl": impl,
    }
    print(json.dumps(rec))
    return rec


def run_defrag(args):
    """BASELINE config 5 floor: K drain what-ifs, each a full serial
    re-simulation with the candidate node removed (the vectorized sweep
    runs these as scenarios in one dispatch)."""
    from opensim_tpu.engine.simulator import AppResource

    bench = _bench()
    run, impl = _runner(args)
    pods_n, nodes_n = args.pods or 10000, args.nodes or 1000
    k = args.scenarios or 3
    cluster = bench.synthetic_cluster(nodes_n)
    apps = [AppResource("bench", bench.synthetic_apps(pods_n))]
    t0 = time.time()
    for c in range(k):
        import copy

        sub = copy.copy(cluster)
        sub.nodes = [n for i, n in enumerate(cluster.nodes) if i != c]
        run(sub, apps)
    dt = time.time() - t0
    rec = {
        "config": "defrag",
        "pods": pods_n,
        "nodes": nodes_n,
        "scenarios": k,
        "wall_s": round(dt, 3),
        "scenarios_per_sec": round(k / dt, 4),
        "impl": f"{impl.split(' ')[0]}, one full re-simulation per drain scenario",
    }
    print(json.dumps(rec))
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--config", default="all",
        choices=["all", "example", "gpushare", "synthetic", "affinity",
                 "defrag", "plan"],
    )
    ap.add_argument("--pods", type=int, default=None)
    ap.add_argument("--nodes", type=int, default=None)
    ap.add_argument("--scenarios", type=int, default=None)
    ap.add_argument(
        "--impl", default="python", choices=["python", "c++"],
        help="c++ runs the compiled serial engine (the measured Go-cost "
        "stand-in); results are stored under '<config>-cxx' keys",
    )
    ap.add_argument(
        "--out", default=os.path.join(_REPO, "BASELINE_MEASURED.json"),
        help="merge results into this JSON file ('' disables)",
    )
    args = ap.parse_args()

    names = (
        ["example", "gpushare", "synthetic", "affinity", "defrag"]
        if args.config == "all" else [args.config]
    )
    suffix = "-cxx" if args.impl == "c++" else ""
    existing = {}
    if args.out:
        try:
            with open(args.out) as f:
                existing = json.load(f)
        except (OSError, ValueError):
            pass
    results = {}
    for name in names:
        rec = run_config(name, args)
        # the canonical key updates in place when the shape matches (or no
        # canonical record exists yet); only a genuinely different shape
        # gets its own suffixed key, so canonical rows never go stale
        key = name + suffix
        canon = existing.get(key)
        if (
            isinstance(canon, dict)
            and (canon.get("pods"), canon.get("nodes")) != (rec.get("pods"), rec.get("nodes"))
        ):
            key = f"{name}-{rec.get('pods')}p-{rec.get('nodes')}n{suffix}"
        results[key] = rec

    if args.out:
        merged = {}
        try:
            with open(args.out) as f:
                merged = json.load(f)
        except (OSError, ValueError):
            pass
        merged.update(results)
        with open(args.out, "w") as f:
            json.dump(merged, f, indent=1, sort_keys=True)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
