"""Inputs of `openb-gpushare-*`: Alibaba's GPU-sharing cluster of
`cluster-trace-gpu-v2023` (`openb_node_list_all_node.csv`,
`openb_pod_list_default.csv`), as a cluster directory, one app directory of
bare pods, a `newNode` template and a simon Config, made from the seed.

The trace is not read (there is no network, and the files are not here):
nodes and tasks are drawn to its marginals, which the configuration states
under `source_sizes` and, where they could not be confirmed, `assumed`.

Nodes. `sizes.node_classes` lists the classes (GPUs a node, CPU cores,
memory, card model, count); the counts are the same for every seed and meet
the source's totals of nodes, GPU nodes, GPUs and CPU cores. A node declares
`alibabacloud.com/gpu-count` and `alibabacloud.com/gpu-mem` in capacity and
allocatable, upstream's protocol (`example/cluster/gpushare`). The trace
counts a GPU in thousandths, so every device is `gpu_mem_mi` = 1000Mi and a
task's `gpu_milli` is that many Mi: the fractions are the trace's own, and
every value is a multiple of 2**20 that float32 holds exactly. The seed
draws which position of the node list (the tie-break index) carries which
node, and a system reservation per node that lowers its allocatable CPU and
memory below capacity, as kube-reserved does: without it equal nodes tie by
index and no precision could be told from another.

Tasks. Each is one bare `Pod` with the annotations
`alibabacloud.com/gpu-mem` (per GPU) and `alibabacloud.com/gpu-count`, no
labels and no owner, all in one file in arrival order. Their number is the
least with which the GPUs asked for reach `load_pct` % of the cluster's GPUs
(the paper's "arrived workload") when the six GPU classes hold their shares
by task count; the number of tasks of each class, and within the fractional
class of each `gpu_milli`, is apportioned by largest remainder and is the
same for every seed, and so is the number with each (`cpu_milli`,
`memory_mib`) pair of its class's two lists (the product of their weights,
largest remainders again): the request shapes, which are the program's
templates and decide the shapes its kernels compile for, do not move with
the seed. The seed draws the order of arrival.

The same description is returned as plain data (`GpuCluster` of
`GpuWorkload`, one workload of one replica a task) for the reference, which
never sees the files.
"""

from __future__ import annotations

import math
import os
import random
from typing import Dict, List, Sequence, Tuple

from benchmarks.generators.k8s_cluster import HOSTNAME, MI, write_docs
from benchmarks.reference.kube_gpushare_reference import GpuCluster, GpuNodeSpec, GpuWorkload

GPU_MEM = "alibabacloud.com/gpu-mem"
GPU_COUNT = "alibabacloud.com/gpu-count"
CARD_MODEL = "alibabacloud.com/gpu-card-model"
NAMESPACE = "openb"
#: the six GPU classes of a task, in the order the shares are listed
CLASSES = ("none", "fraction", "one", "two", "four", "eight")
WHOLE_GPUS = {"none": 0, "one": 1, "two": 2, "four": 4, "eight": 8}


def apportion(total: int, weights: Sequence[float], least: int = 0) -> List[int]:
    """`total` split by `weights` with the largest remainders rounded up,
    ties to the earlier entry; no share under `least`."""
    scale = total / float(sum(weights))
    exact = [w * scale for w in weights]
    counts = [max(int(math.floor(x)), least) for x in exact]
    by_remainder = sorted(range(len(weights)), key=lambda i: (-(exact[i] - math.floor(exact[i])), i))
    k = 0
    while sum(counts) < total:
        counts[by_remainder[k % len(weights)]] += 1
        k += 1
    while sum(counts) > total:  # the floor of `least` overshot: take from the largest
        counts[max(range(len(counts)), key=lambda i: counts[i])] -= 1
    return counts


def task_counts(sizes: dict) -> Dict[str, object]:
    """How many tasks of each GPU class, and of each `gpu_milli` among the
    fractional ones: from the shares and `load_pct` alone, the same for every
    seed."""
    gpus = sum(c["gpus"] * c["count"] for c in sizes["node_classes"])
    shares = [sizes["task_classes"][c]["share_pct"] for c in CLASSES]
    fractions = sizes["task_classes"]["fraction"]["gpu_milli"]  # [[gpu_milli, weight], ...]
    mean_fraction = sum(m * w for m, w in fractions) / (1000.0 * sum(w for _m, w in fractions))
    per_task = sum(s / 100.0 * (mean_fraction if c == "fraction" else WHOLE_GPUS[c]) for c, s in zip(CLASSES, shares))
    target = sizes["load_pct"] / 100.0 * gpus
    tasks = math.floor(target / per_task)  # near; then one task more until the GPUs asked for reach the target
    while True:
        by_class = dict(zip(CLASSES, apportion(tasks, shares, least=1)))
        by_milli = apportion(by_class["fraction"], [w for _m, w in fractions])
        asked = sum(m * k for (m, _w), k in zip(fractions, by_milli)) / 1000.0 + sum(
            WHOLE_GPUS[c] * k for c, k in by_class.items() if c != "fraction")
        if asked >= target:
            break
        tasks += 1
    return {"tasks": tasks, "gpus": gpus, "asked_gpus": asked, "by_class": by_class,
            "by_milli": [(m, k) for (m, _w), k in zip(fractions, by_milli)]}


def node_doc(name: str, model: str, capacity: Dict[str, str], allocatable: Dict[str, str]) -> dict:
    labels = {HOSTNAME: name}
    if model:
        labels[CARD_MODEL] = model
    return {"apiVersion": "v1", "kind": "Node", "metadata": {"name": name, "labels": labels},
            "status": {"allocatable": allocatable, "capacity": capacity}}


def pod_doc(name: str, cpu_m: int, mem_mi: int, gpu_milli: int, gpus: int) -> dict:
    meta: dict = {"name": name, "namespace": NAMESPACE}
    if gpus:
        meta["annotations"] = {GPU_MEM: f"{gpu_milli}Mi", GPU_COUNT: str(gpus)}
    requests = {"cpu": f"{cpu_m}m", "memory": f"{mem_mi}Mi"}
    return {"apiVersion": "v1", "kind": "Pod", "metadata": meta,
            "spec": {"containers": [{"name": "main", "image": "registry.example.com/openb:1",
                                     "resources": {"requests": requests, "limits": dict(requests)}}]}}


def requests_of(cls: dict, count: int) -> List[Tuple[int, int]]:
    """`count` (cpu_milli, memory_mib) pairs of one GPU class: the pairs of
    its two lists, weighted by the product of their weights, apportioned by
    largest remainder. The same for every seed, so that the request shapes
    (the program's templates, and with them the shapes its kernels compile
    for) do not move with the seed."""
    pairs = [(c, m, wc * wm) for c, wc in cls["cpu_milli"] for m, wm in cls["memory_mib"]]
    counts = apportion(count, [w for _c, _m, w in pairs])
    return [(c, m) for (c, m, _w), k in zip(pairs, counts) for _ in range(k)]


def task_list(sizes: dict, counts: Dict[str, object] = None) -> List[Tuple[int, int, int, int]]:
    """Every task as (cpu_milli, memory_mib, gpu_milli per GPU, GPUs), class
    by class: what arrives is the same for every seed, the seed draws the
    order."""
    counts = counts or task_counts(sizes)
    tasks: List[Tuple[int, int, int, int]] = []
    for cname in CLASSES:
        cls = sizes["task_classes"][cname]
        groups = counts["by_milli"] if cname == "fraction" else [
            (1000 if WHOLE_GPUS[cname] else 0, counts["by_class"][cname])]
        gpus = 1 if cname == "fraction" else WHOLE_GPUS[cname]
        for milli, k in groups:
            tasks += [(cpu_m, mem_mi, milli, gpus) for cpu_m, mem_mi in requests_of(cls, k)]
    return tasks


def node_of(name: str, cls: dict, sizes: dict, res_c: int = 0, res_m: int = 0) -> Tuple[dict, GpuNodeSpec]:
    """A node of class `cls` as the document the program reads and as plain
    data for the reference: capacity the class's, allocatable lower by the
    reservation (`res_c` milli-CPU, `res_m` Mi)."""
    pods, gpus = sizes["node_pods"], cls["gpus"]
    cpu_m, mem_mi = cls["cpu"] * 1000 - res_c, cls["memory_gi"] * 1024 - res_m
    cap = {"cpu": str(cls["cpu"]), "memory": f"{cls['memory_gi']}Gi", "pods": str(pods)}
    alloc = {"cpu": f"{cpu_m}m", "memory": f"{mem_mi}Mi", "pods": str(pods)}
    if gpus:
        for d in (cap, alloc):
            d[GPU_COUNT] = str(gpus)
            d[GPU_MEM] = f"{gpus * sizes['gpu_mem_mi']}Mi"
    doc = node_doc(name, cls["model"], cap, alloc)
    spec = GpuNodeSpec(name=name, cpu_m=cpu_m, mem_bytes=mem_mi * MI, pods=pods, labels=dict(doc["metadata"]["labels"]),
                       gpus=gpus, gpu_mem=sizes["gpu_mem_mi"] * MI if gpus else 0)
    return doc, spec


def generate(sizes: dict, seed: int, out: str) -> dict:
    """Write the inputs under `out`; return the simon Config path of the one
    variant (`short`) and its `GpuCluster`."""
    rng = random.Random(seed)
    classes = sizes["node_classes"]
    logical = [ci for ci, cls in enumerate(classes) for _ in range(cls["count"])]
    n = len(logical)
    order = list(range(n))
    rng.shuffle(order)  # position in the file (the tie-break index) -> logical node
    lo_c, hi_c, step_c = sizes["reserved_cpu_m"]
    lo_m, hi_m, step_m = sizes["reserved_memory_mi"]
    reserved = [(rng.randrange(lo_c, hi_c + 1, step_c), rng.randrange(lo_m, hi_m + 1, step_m)) for _ in range(n)]
    docs, specs = zip(*(node_of(f"openb-node-{j:04d}", classes[logical[j]], sizes, *reserved[j]) for j in order))

    # the tasks: what arrives is held, the order of arrival is the seed's
    counts = task_counts(sizes)
    tasks = task_list(sizes, counts)
    rng.shuffle(tasks)
    pods, workloads = [], []
    for i, (cpu_m, mem_mi, milli, gpus) in enumerate(tasks):
        name = f"openb-pod-{i:05d}"
        pods.append(pod_doc(name, cpu_m, mem_mi, milli, gpus))
        workloads.append(GpuWorkload(
            name=f"{NAMESPACE}/{name}", replicas=1, cpu_m=cpu_m, mem_bytes=mem_mi * MI, labels={},
            gpu_mem=milli * MI if gpus else 0, gpu_count=gpus))

    new_doc, new_spec = node_of("new-gpu", classes[sizes["new_node_class"]], sizes)

    root = os.path.join(out, "plan")
    write_docs(os.path.join(root, "cluster", "nodes.yaml"), list(docs))
    write_docs(os.path.join(root, "newnode", "node.yaml"), [new_doc])
    write_docs(os.path.join(root, "tasks", "pods.yaml"), pods)
    path = os.path.join(root, "simon-short.yaml")
    with open(path, "w") as f:
        f.write(
            "apiVersion: simon/v1alpha1\nkind: Config\nmetadata:\n  name: benchmark\n"
            "spec:\n  cluster:\n    customConfig: cluster\n"
            "  appList:\n  - name: openb\n    path: tasks\n  newNode: newnode\n"
        )
    cluster = GpuCluster(nodes=list(specs), bound=[], workloads=workloads, new_node=new_spec)
    return {"variants": {"short": {"simon_config": path, "cluster": cluster}},
            "max_new_nodes": sizes["max_new_nodes"], "shapes": len(set(tasks)), "counts": counts}
