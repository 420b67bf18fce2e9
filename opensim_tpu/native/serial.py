"""C++ serial baseline — marshalling + bindings.

VERDICT r4 #2: BENCH.md's python→Go conversion bracket was a *model*; this
module replaces it with a *measurement*. ``serial_engine.cc`` is the same
object-at-a-time NodeInfo/PreFilter pipeline as ``tools/serial_baseline.py``
— per pod: filter every node, score the feasible set, bind the best — built
on hash-maps over strings and incremental per-node aggregates, the memory
model of the reference's Go scheduler (vendored
``generic_scheduler.go:131-180``), never the tensor encodings. Compiled
C++ with that design is a defensible stand-in for the Go constant factor,
so ``impl: "c++-serial"`` rows in BASELINE_MEASURED.json anchor the true
vs-Go speedup claims.

The marshaller serializes the object model (nodes + deduped pod templates +
the pod stream) into one byte buffer; the C++ side parses it (untimed) and
times only the scheduling loop, exactly like the python tool's
``schedule_s``. Placement parity with the python serial baseline is
asserted by tests/test_serial_baseline.py.
"""

from __future__ import annotations

import ctypes
import json
import struct
import time
from pathlib import Path
from typing import List, Optional, Tuple

from ..models.objects import Pod
from ..models.quantity import parse_quantity

_HERE = Path(__file__).resolve().parent
_SRC = _HERE / "serial_engine.cc"
_CXX_FLAGS = ["-O3", "-std=c++17", "-shared", "-fPIC"]

_LABEL_OPS = {"In": 0, "NotIn": 1, "Exists": 2, "DoesNotExist": 3}
_NODE_OPS = {**_LABEL_OPS, "Gt": 4, "Lt": 5}

HOSTNAME = "kubernetes.io/hostname"
ZONE = "topology.kubernetes.io/zone"

#: wire-format tag ("SRL1", version 1) — machine-readable anchors the
#: OSL1604 abi-parity pass checks against serial_engine.cc's header guards
WIRE_MAGIC = 0x53524C31
WIRE_VERSION = 1


class _Buf:
    def __init__(self):
        self.parts: List[bytes] = []

    def u8(self, v: int):
        self.parts.append(struct.pack("<B", v))

    def u32(self, v: int):
        self.parts.append(struct.pack("<I", v))

    def f64(self, v: float):
        self.parts.append(struct.pack("<d", float(v)))

    def s(self, v: str):
        b = str(v).encode("utf-8")
        self.parts.append(struct.pack("<I", len(b)) + b)

    def strmap(self, d: dict):
        items = list((d or {}).items())
        self.u32(len(items))
        for k, v in items:
            self.s(k)
            self.s(v)

    def bytes(self) -> bytes:
        return b"".join(self.parts)


def _sel_key(sel) -> str:
    return json.dumps(sel, sort_keys=True) if sel is not None else "null"


def _term_sig(term: dict, owner_ns: str) -> str:
    ns = sorted([str(n) for n in (term.get("namespaces") or [])] or [owner_ns])
    return "\x01".join(["|".join(ns), _sel_key(term.get("labelSelector")), term.get("topologyKey", "") or ""])


def _put_selector(b: _Buf, sel: Optional[dict]):
    if sel is None:
        b.u8(0)
        return
    b.u8(1)
    b.strmap(sel.get("matchLabels") or {})
    exprs = sel.get("matchExpressions") or []
    b.u32(len(exprs))
    for e in exprs:
        op = e.get("operator", "")
        if op not in _LABEL_OPS:
            raise ValueError(f"unknown label selector operator: {op}")
        b.s(e.get("key", ""))
        b.u8(_LABEL_OPS[op])
        vals = [str(v) for v in (e.get("values") or [])]
        b.u32(len(vals))
        for v in vals:
            b.s(v)


def _put_node_term(b: _Buf, term: dict):
    for part in ("matchExpressions", "matchFields"):
        exprs = term.get(part) or []
        b.u32(len(exprs))
        for e in exprs:
            op = e.get("operator", "")
            if op not in _NODE_OPS:
                raise ValueError(f"unknown node selector operator: {op}")
            b.s(e.get("key", ""))
            b.u8(_NODE_OPS[op])
            vals = [str(v) for v in (e.get("values") or [])]
            b.u32(len(vals))
            for v in vals:
                b.s(v)


def _put_terms(b: _Buf, terms: list, ns: str, weights: Optional[list]):
    b.u32(len(terms))
    for i, t in enumerate(terms):
        b.s(_term_sig(t, ns))
        nss = [str(n) for n in (t.get("namespaces") or [])] or [ns]
        b.u32(len(nss))
        for n in nss:
            b.s(n)
        _put_selector(b, t.get("labelSelector"))
        b.s(t.get("topologyKey", "") or "")
        b.f64(weights[i] if weights is not None else 0.0)


def _terms(pod: Pod, kind: str, mode: str):
    aff = (pod.spec.affinity or {}).get(kind) or {}
    return aff.get(f"{mode}DuringSchedulingIgnoredDuringExecution") or []


def _put_template(b: _Buf, pod: Pod):
    ns = pod.metadata.namespace
    b.s(ns)
    b.strmap(pod.metadata.labels)
    req = pod.resource_requests()
    b.u32(len(req))
    for k, v in req.items():
        b.s(k)
        b.f64(v)
    b.strmap({k: str(v) for k, v in pod.spec.node_selector.items()})

    aff = (pod.spec.affinity or {}).get("nodeAffinity") or {}
    required = aff.get("requiredDuringSchedulingIgnoredDuringExecution")
    if required is None:
        b.u8(0)
    else:
        b.u8(1)
        terms = required.get("nodeSelectorTerms") or []
        b.u32(len(terms))
        for t in terms:
            _put_node_term(b, t)
    preferred = aff.get("preferredDuringSchedulingIgnoredDuringExecution") or []
    b.u32(len(preferred))
    for p in preferred:
        b.f64(float(p.get("weight", 0)))
        _put_node_term(b, p.get("preference") or {})

    tols = pod.spec.tolerations
    b.u32(len(tols))
    for t in tols:
        b.s(t.key)
        op = t.operator
        b.u8(1 if op == "Exists" else (0 if op in ("Equal", "") else 2))
        b.s(t.value)
        b.s(t.effect)

    ports = pod.host_ports()
    b.u32(len(ports))
    for p in ports:
        b.s(p.protocol)
        b.s(p.host_ip)
        b.u32(int(p.host_port))

    aff_req = _terms(pod, "podAffinity", "required")
    anti_req = _terms(pod, "podAntiAffinity", "required")
    aff_pref_w = _terms(pod, "podAffinity", "preferred")
    anti_pref_w = _terms(pod, "podAntiAffinity", "preferred")
    _put_terms(b, aff_req, ns, None)
    _put_terms(b, anti_req, ns, None)
    _put_terms(
        b, [tw.get("podAffinityTerm") or {} for tw in aff_pref_w], ns,
        [float(tw.get("weight", 0)) for tw in aff_pref_w],
    )
    _put_terms(
        b, [tw.get("podAffinityTerm") or {} for tw in anti_pref_w], ns,
        [float(tw.get("weight", 0)) for tw in anti_pref_w],
    )

    explicit = pod.spec.topology_spread_constraints or []
    b.u32(len(explicit))
    for c in explicit:
        key = c.get("topologyKey", "") or ""
        sel = c.get("labelSelector")
        b.s(_term_sig({"labelSelector": sel, "topologyKey": key, "namespaces": [ns]}, ns))
        b.s(key)
        b.f64(float(c.get("maxSkew", 1)))
        b.u8(1 if c.get("whenUnsatisfiable", "DoNotSchedule") == "DoNotSchedule" else 0)
        _put_selector(b, sel)

    from ..engine.simulator import _owner_selector

    owner = _owner_selector(pod)
    if owner is None:
        b.u8(0)
    else:
        b.u8(1)
        _put_selector(b, owner)
        for key in (HOSTNAME, ZONE):
            b.s(_term_sig({"labelSelector": owner, "topologyKey": key, "namespaces": [ns]}, ns))

    gpu_mem = pod.gpu_mem_request()
    b.f64(gpu_mem)
    b.u32(int(pod.gpu_count_request()) if gpu_mem > 0 else 0)

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
    b.f64(lvm)
    b.u32(len(devs))
    for size, kind in devs:
        b.f64(size)
        b.u8(0 if kind == "SSD" else 1)

    ctrl = None
    for ref in pod.metadata.owner_references:
        if ref.controller and ref.kind in ("ReplicaSet", "ReplicationController"):
            ctrl = (ref.kind, ref.uid)
            break
    if ctrl is None:
        b.u8(0)
    else:
        b.u8(1)
        b.s(ctrl[0])
        b.s(ctrl[1])


def _put_node(b: _Buf, node):
    b.s(node.metadata.name)
    b.strmap(node.metadata.labels)
    alloc = node.allocatable
    b.u32(len(alloc))
    for k, v in alloc.items():
        b.s(k)
        b.f64(v)
    b.u32(len(node.taints))
    for t in node.taints:
        b.s(t.key)
        b.s(t.value)
        b.s(t.effect)
    b.u8(1 if node.unschedulable else 0)
    total = alloc.get("alibabacloud.com/gpu-mem", 0.0)
    cnt = int(alloc.get("alibabacloud.com/gpu-count", 0))
    if not (cnt > 0 and total > 0):
        total, cnt = 0.0, 0
    b.f64(total)
    b.u32(cnt)
    vgs, devs = [], []
    raw = node.metadata.annotations.get("simon/node-local-storage")
    if raw:
        try:
            data = json.loads(raw)
        except ValueError:
            data = {}
        for vg in data.get("vgs") or []:
            vgs.append(float(parse_quantity(vg.get("capacity", 0))))
        for d in data.get("devices") or []:
            cap = float(parse_quantity(d.get("capacity", 0)))
            media = 0 if str(d.get("mediaType", "")).lower() == "ssd" else 1
            devs.append((cap, media))
    b.u32(len(vgs))
    for cap in vgs:
        b.f64(cap)
    b.u32(len(devs))
    for cap, media in devs:
        b.f64(cap)
        b.u8(media)
    avoid = []
    anno = node.metadata.annotations.get("scheduler.alpha.kubernetes.io/preferAvoidPods")
    if anno:
        try:
            entries = json.loads(anno).get("preferAvoidPods") or []
        except (ValueError, AttributeError):
            entries = []
        for e in entries:
            pc = (e.get("podSignature") or {}).get("podController") or {}
            avoid.append((str(pc.get("kind", "")), str(pc.get("uid", ""))))
    b.u32(len(avoid))
    for kind, uid in avoid:
        b.s(kind)
        b.s(uid)


def marshal(nodes, stream: List[Tuple[Pod, bool]]) -> bytes:
    """Serialize nodes + the ordered pod stream (pod, forced) into the
    engine's byte format. Pods are deduped into templates by scheduling
    spec (same hint as simulator._tmpl_hint, else full spec identity)."""
    from ..engine.simulator import _tmpl_hint

    b = _Buf()
    b.u32(WIRE_MAGIC)  # "SRL1"
    b.u32(WIRE_VERSION)
    b.u32(len(nodes))
    for n in nodes:
        _put_node(b, n)

    tmpl_idx: dict = {}
    tmpl_of: List[int] = []
    tmpl_pods: List[Pod] = []
    for pod, _forced in stream:
        hint = _tmpl_hint(pod)
        key = hint if hint is not None else ("__uniq__", len(tmpl_pods))
        idx = tmpl_idx.get(key)
        if idx is None:
            idx = tmpl_idx[key] = len(tmpl_pods)
            tmpl_pods.append(pod)
        tmpl_of.append(idx)
    b.u32(len(tmpl_pods))
    for pod in tmpl_pods:
        _put_template(b, pod)
    b.u32(len(stream))
    for (pod, forced), ti in zip(stream, tmpl_of):
        b.u32(ti)
        b.u8(1 if forced else 0)
        b.s(pod.spec.node_name if forced else "")
    return b.bytes()


# -- build + bindings (loader shared with scan_engine: native.build_cached) --

_lib = None
_lib_error: Optional[str] = None


def load() -> Optional[ctypes.CDLL]:
    global _lib, _lib_error
    if _lib is not None:
        return _lib
    if _lib_error is not None:
        return None
    from . import build_cached

    out, err = build_cached(_SRC, "_serial_engine_", _CXX_FLAGS)
    if out is None:
        _lib_error = err
        return None
    try:
        lib = ctypes.CDLL(str(out))
    except OSError as e:
        _lib_error = f"dlopen failed: {e}"
        return None
    lib.opensim_serial_abi.restype = ctypes.c_int64
    if lib.opensim_serial_abi() != 1:
        _lib_error = f"serial engine ABI {lib.opensim_serial_abi()} != 1"
        return None
    lib.opensim_serial_run.restype = ctypes.c_int
    lib.opensim_serial_run.argtypes = [
        ctypes.c_char_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_double),
    ]
    _lib = lib
    return lib


def available() -> bool:
    return load() is not None


def load_error() -> Optional[str]:
    return _lib_error


def run_serial_native(cluster, apps, progress: bool = False):
    """Expand (shared with the python tool), marshal, run the C++ serial
    engine. Returns (scheduled, unscheduled, expand_s, schedule_s,
    chosen_names) — the same shape as tools/serial_baseline.run_serial,
    with schedule_s timed INSIDE the C++ loop (marshal/parse excluded)."""
    import numpy as np

    from ..engine import queues
    from ..engine.simulator import _cluster_pods
    from ..models import expand
    from ..models.objects import LABEL_APP_NAME

    from ..utils.gcpause import gc_paused

    lib = load()
    if lib is None:
        raise RuntimeError(f"serial engine unavailable: {_lib_error}")

    t0 = time.time()
    stream: List[Tuple[Pod, bool]] = []
    with gc_paused():
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

    buf = marshal(cluster.nodes, stream)
    chosen = np.full((len(stream),), -1, dtype=np.int32)
    sched_s = ctypes.c_double(0.0)
    rc = lib.opensim_serial_run(
        buf, len(buf),
        chosen.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.byref(sched_s),
    )
    if rc != 0:
        raise RuntimeError(f"serial engine failed with code {rc}")
    names = [cluster.nodes[c].metadata.name if c >= 0 else None for c in chosen]
    scheduled = int((chosen >= 0).sum())
    return scheduled, len(stream) - scheduled, expand_s, float(sched_s.value), names
