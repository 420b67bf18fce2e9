"""The registered reason-code enum + placement explanations (ISSUE 7).

Every unschedulable-reason string in the repo comes from this module — the
kube-scheduler FitError phrasings for the 11 filter plugins, plus the
non-filter outcomes (missing pinned node, unknown scheduler profile,
preemption victim). ``opensim-lint`` rule OSL901 enforces the registration:
an inline reason literal at an ``UnscheduledPod(...)`` construction site is
a lint error, so the XLA scan, the megakernel, and every report/endpoint
render byte-identical diagnostics from one table.

:class:`PlacementExplanation` is the typed per-pod decision-audit record the
engines normalize into (engine/explain.py): scheduled → winning node (and,
on demand, the per-plugin score breakdown + runner-up margin);
unschedulable → per-filter rejection counts over nodes rendered in kube's
``0/N nodes are available: …`` phrasing.

This module deliberately imports nothing from :mod:`..ops` — it is the leaf
the kernel layer's ``FILTER_REASONS`` table is built FROM (ops/kernels.py
imports it), so the registry stays a single definition with no cycle.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence


class Reason(enum.Enum):
    """Registered reason codes. Filter members carry their kernel filter
    index as the value (asserted against ``ops.kernels.F_*`` by the tests);
    non-filter outcomes live at 100+."""

    # --- filter plugins (value == ops.kernels filter index) ---------------
    NODE_PIN = 0          # NodeName
    UNSCHEDULABLE = 1     # NodeUnschedulable
    TAINT = 2             # TaintToleration
    AFFINITY = 3          # NodeAffinity + nodeSelector
    PORTS = 4             # NodePorts
    FIT = 5               # NodeResourcesFit
    SPREAD = 6            # PodTopologySpread
    INTERPOD = 7          # InterPodAffinity
    GPU = 8               # GpuShare
    LOCAL = 9             # OpenLocal
    EXTRA = 10            # out-of-tree extra_plugins
    # --- non-filter outcomes ----------------------------------------------
    NODE_NOT_FOUND = 100   # forced pod whose spec.nodeName matches no node
    UNKNOWN_PROFILE = 101  # spec.schedulerName matches no profile
    PREEMPTED = 102        # evicted by a higher-priority pod

    @property
    def message(self) -> str:
        return _MESSAGES[self]

    @property
    def is_filter(self) -> bool:
        return self.value < 100


# kube-scheduler FitError phrasings (vendor/.../framework/types.go +
# the sim plugins' Filter status messages) — the ONE copy in the repo.
_MESSAGES: Dict[Reason, str] = {
    Reason.NODE_PIN: "node(s) didn't match the requested hostname",
    Reason.UNSCHEDULABLE: "node(s) were unschedulable",
    Reason.TAINT: "node(s) had taints that the pod didn't tolerate",
    Reason.AFFINITY: "node(s) didn't match Pod's node affinity",
    Reason.PORTS: "node(s) didn't have free ports for the requested pod ports",
    Reason.FIT: "Insufficient resources",
    Reason.SPREAD: "node(s) didn't match pod topology spread constraints",
    Reason.INTERPOD: "node(s) didn't satisfy inter-pod affinity rules",
    Reason.GPU: "Insufficient GPU memory in 1 GPU device",
    Reason.LOCAL: "node(s) didn't have enough local storage",
    Reason.EXTRA: "node(s) were rejected by an out-of-tree plugin",
    Reason.NODE_NOT_FOUND: 'node "{node}" not found',
    Reason.UNKNOWN_PROFILE: (
        "no scheduler profile named {profile!r} "
        "(pod never enters any profile's scheduling queue)"
    ),
    Reason.PREEMPTED: "preempted by higher-priority pod {pod}",
}

# the 11 filter messages in kernel filter-index order — ops/kernels.py
# re-exports this as FILTER_REASONS (single registered table, no drift)
FILTER_MESSAGES: List[str] = [
    _MESSAGES[r] for r in sorted((r for r in Reason if r.is_filter), key=lambda r: r.value)
]

N_STATIC_FILTERS = 4  # NODE_PIN..AFFINITY — template-static, precomputed


def node_not_found(node_name: str) -> str:
    return Reason.NODE_NOT_FOUND.message.format(node=node_name)


def unknown_profile(profile_name: str) -> str:
    return Reason.UNKNOWN_PROFILE.message.format(profile=profile_name)


def preempted(namespace: str, name: str) -> str:
    return Reason.PREEMPTED.message.format(pod=f"{namespace}/{name}")


# the capacity observatory's cluster report (obs/capacity.py) lists pods
# OBSERVED pending — no simulation ran, so there is no FitError breakdown
# to render; the registered phrasing keeps OSL901's one-registry contract
PENDING_OBSERVED = "pod is pending in the observed cluster (no node assigned)"


def pending_observed() -> str:
    return PENDING_OBSERVED


# a pod the megakernel left unplaced in a run whose caller asked for no
# reasons (``simulate(reasons=False)``: the planner's first pass with a
# newNode template): nothing counted its filters, so nothing is rendered
NOT_ATTRIBUTED = "unschedulable (failure reasons were not asked of this simulation)"


def not_attributed() -> str:
    return NOT_ATTRIBUTED


@dataclass
class ReasonCount:
    """One line of a FitError breakdown: ``count`` nodes rejected for
    ``code``; ``resource`` names the short resource for FIT rejections
    (kube reports each resource class on its own line)."""

    code: Reason
    count: int
    resource: str = ""

    @property
    def label(self) -> str:
        if self.code is Reason.FIT and self.resource:
            return f"Insufficient {self.resource}"
        return self.code.message

    def to_dict(self) -> dict:
        out = {"code": self.code.name.lower(), "count": int(self.count)}
        if self.resource:
            out["resource"] = self.resource
        return out


def render_unschedulable(n_nodes: int, counts: Sequence[ReasonCount]) -> str:
    """The kube FitError headline: ``0/N nodes are available: 3 node(s) had
    taints that the pod didn't tolerate, 1 Insufficient cpu.`` — parts
    sorted by label like the reference's sorted reason map."""
    parts = [(c.count, c.label) for c in counts if c.count > 0]
    if not parts:
        return f"0/{n_nodes} nodes are available."
    body = ", ".join(f"{cnt} {msg}" for cnt, msg in sorted(parts, key=lambda x: x[1]))
    return f"0/{n_nodes} nodes are available: {body}."


def counts_from_rows(
    static_fail_row,
    fail_counts_row,
    insufficient_row,
    resource_names: Sequence[str],
) -> List[ReasonCount]:
    """Normalize one pod's engine failure-attribution rows into typed
    reason counts. ``static_fail_row`` covers the 4 template-static filters,
    ``fail_counts_row`` the dynamic ones (PORTS..EXTRA); FIT expands into
    per-resource lines from ``insufficient_row`` (kube reports Insufficient
    per resource, not per plugin)."""
    merged = list(static_fail_row) + list(fail_counts_row)
    out: List[ReasonCount] = []
    for code in sorted((r for r in Reason if r.is_filter), key=lambda r: r.value):
        cnt = int(merged[code.value])
        if cnt <= 0:
            continue
        if code is Reason.FIT:
            for r, rname in enumerate(resource_names):
                rcnt = int(insufficient_row[r])
                if rcnt > 0:
                    out.append(ReasonCount(code, rcnt, resource=str(rname)))
        else:
            out.append(ReasonCount(code, cnt))
    return out


@dataclass
class PlacementExplanation:
    """The per-pod decision-audit record (the tentpole's typed output).

    ``status``:
      - ``scheduled``     — landed on ``node`` (``forced`` marks pre-bound
        pods that bypassed the scheduler, simulator.go:329-331);
      - ``unschedulable`` — ``reasons`` carries the per-filter rejection
        counts and ``message`` their kube FitError rendering;
      - ``preempted``     — evicted post-bind by a preemption pass.

    The score fields (``scores`` per-plugin weighted contributions on the
    winner, ``runner_up``/``margin`` vs the second-best node) are filled by
    the on-demand deep evaluator (engine/explain.py:explain_pod) — never on
    the bulk path, where they would cost O(nodes) per pod."""

    pod: str
    status: str
    nodes_total: int = 0
    node: Optional[str] = None
    forced: bool = False
    reasons: List[ReasonCount] = field(default_factory=list)
    message: str = ""
    # deep (on-demand) fields
    scores: Optional[Dict[str, float]] = None
    score: Optional[float] = None
    runner_up: Optional[str] = None
    margin: Optional[float] = None

    def to_dict(self) -> dict:
        out: dict = {"pod": self.pod, "status": self.status}
        if self.node is not None:
            out["node"] = self.node
        if self.forced:
            out["forced"] = True
        if self.reasons:
            out["reasons"] = [c.to_dict() for c in self.reasons]
        if self.message:
            out["message"] = self.message
        for k in ("scores", "score", "runner_up", "margin"):
            v = getattr(self, k)
            if v is not None:
                out[k] = v
        return out


def format_rejects(rejects: Dict[str, int]) -> str:
    """One-line human rendering of a per-filter reject-total dict — shared
    by ``simon explain``, ``simon apply --explain``, and any future report
    surface so the wording cannot drift."""
    return ", ".join(f"{k}={v}" for k, v in sorted(rejects.items()))


def count_lines(counts: Sequence[ReasonCount]) -> List[str]:
    """The per-reason breakdown lines (`` <n> × <label>``) under a kube
    FitError headline, shared by every text surface."""
    return [f"{c.count:5d} × {c.label}" for c in counts]


def primary_code(counts: Sequence[ReasonCount]) -> Optional[Reason]:
    """The dominant rejection reason of one unschedulable pod: the filter
    rejecting the most nodes, ties broken by filter precedence (lowest
    index — the order the default profile runs them)."""
    best: Optional[ReasonCount] = None
    for c in counts:
        if best is None or c.count > best.count or (
            c.count == best.count and c.code.value < best.code.value
        ):
            best = c
    return best.code if best is not None else None


def rejects_dict(vec) -> Dict[str, int]:
    """An 11-slot per-filter reject vector (kernel filter-index order) as a
    ``{reason_name: count}`` dict, zero slots dropped."""
    out: Dict[str, int] = {}
    for code in sorted((r for r in Reason if r.is_filter), key=lambda r: r.value):
        n = int(vec[code.value])
        if n:
            out[code.name.lower()] = n
    return out
