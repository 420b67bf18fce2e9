"""The C++ scan engine's envelope and marshalling.

``why_not()`` says what the C++ loop lacks for a run and whether its ``.so``
is built; whether the engine is tried at all is ``engine/select.py``'s (the
megakernel owns the TPU, this engine owns hosts without an accelerator, and
``OPENSIM_NATIVE=1`` asks for it by name). ``schedule()`` marshals the encoded
cluster into the flat-buffer ABI of ``opensim_tpu/native`` and returns a full
``ScheduleOutput`` — including a completely populated final ``ScanState``
and exact per-pod failure attribution, so no XLA re-scan is ever needed.
"""

from __future__ import annotations

import functools

import numpy as np

from ..encoding import vocab as V
from ..encoding.state import ScanState
from ..ops import kernels
from . import select
from .schedconfig import DEFAULT_CONFIG


@functools.lru_cache(maxsize=None)
def _warn_native_unavailable() -> None:
    import logging

    from .. import native

    logging.getLogger("opensim_tpu").warning(
        "OPENSIM_NATIVE=1 but the native engine is unavailable "
        "(falling back to the XLA scan): %s",
        native.load_error() or "engine not built",
    )


def applicable(prep, config=None, extra_plugins: tuple = ()) -> bool:
    return select.policy().off["native"] is None and why_not(prep, config, extra_plugins) is None


def why_not(prep, config=None, extra_plugins: tuple = (), tie_seed=None):
    """What the C++ engine lacks for this run, None when it can take it, else
    a one-line reason (engine attribution). tie_seed is accepted: the engine
    implements the seeded sampled tie-break."""
    if extra_plugins:
        return "out-of-tree extra_plugins are jittable callables (XLA scan only)"
    if config is not None and getattr(config, "w_rtcr", 0.0):
        return "RequestedToCapacityRatio runs on the megakernel or the XLA scan"
    if config is not None and getattr(config, "fit_ignored_cols", ()):
        # NodeResourcesFitArgs ignored columns are an XLA-scan feature; the
        # C++ fit loop has no per-column skip (rare config — not worth ABI)
        return "NodeResourcesFitArgs ignoredResources need the XLA scan's per-column skip"
    from .. import native

    if not native.available():
        if select.policy().forced_native:
            _warn_native_unavailable()
        return f"engine not built: {native.load_error() or 'unknown'}"
    return None


def _stat_np(prep, config, node_valid=None):
    """Static tables via the numpy mirror (kernels.precompute_static_np):
    bitwise-equal to the jitted tables with ZERO XLA compiles, keeping
    `--backend native` ms-scale cold. `node_valid` overrides the encoder's
    mask — only the valid-set-dependent fold (static_pass, static_fail,
    spread weights) recomputes per scenario; the expensive per-template
    core is computed once per Prepared and cached on it."""
    ec = prep.ec_np
    core = getattr(prep, "_np_core", None)
    if core is None:
        core = kernels.precompute_core_np(ec)
        try:
            prep._np_core = core
        except AttributeError:
            pass
    if node_valid is not None:
        # scenario sweeps: every mask is distinct — caching the [U, N]-scale
        # fold per mask would trade unbounded memory for nothing
        ec = ec._replace(node_valid=np.ascontiguousarray(node_valid, dtype=bool))
        return kernels.precompute_static_np(ec, config, core=core)
    # per-config fold cache: segmented multi-profile runs revisit the same
    # few configs once per segment; identical folds are reused
    cache = getattr(prep, "_np_stat_cache", None)
    if cache is None:
        cache = {}
        try:
            prep._np_stat_cache = cache
        except AttributeError:
            pass
    stat = cache.get(config)
    if stat is None:
        stat = cache[config] = kernels.precompute_static_np(ec, config, core=core)
    return stat


def schedule(prep, pod_valid: np.ndarray, config=None, node_valid=None, forced=None,
             tie_seed=None, st0=None, explain=False):
    """Run the whole pod stream through the C++ engine. Returns a
    ``ScheduleOutput`` (numpy arrays throughout). `node_valid`/`forced`
    override the prepared masks (scenario sweeps). `tie_seed` switches
    selection to seeded uniform sampling over the score maxima (the
    reference's selectHost reservoir distribution). `st0` overrides the
    initial carry (segmented multi-profile runs chain scans). `explain`
    (decision audit, ISSUE 7) forces the generic path, fills the per-pod
    fail rows for every step, and accumulates the 11-slot per-filter
    reject totals in-engine (ScanArgs.filter_rejects, abi v4)."""
    from .. import native
    from ..resilience import faults
    from .scheduler import ScheduleOutput

    _LAST_PROFILE[0] = None  # never inherit a previous run's timings
    # runtime-failure injection (chaos suite): a fault here stands in for
    # ABI drift / a .so crash; simulate()'s ladder demotes to the XLA scan
    faults.fault_point("engine.compile")

    cfg = config or DEFAULT_CONFIG
    ec = prep.ec_np
    if st0 is None:
        st0 = prep.st0
    feat = prep.features
    stat = _stat_np(prep, config, node_valid=node_valid)
    node_valid_arr = ec.node_valid if node_valid is None else node_valid
    forced_arr = prep.forced if forced is None else forced

    def f32(x):
        return np.ascontiguousarray(x, dtype=np.float32)

    def i32(x):
        return np.ascontiguousarray(x, dtype=np.int32)

    def u8(x):
        return np.ascontiguousarray(x, dtype=np.uint8)

    N, R = ec.alloc.shape
    U = ec.req.shape[0]
    P = len(prep.tmpl_ids)
    Gd = ec.node_gpu_mem.shape[1]

    state = {
        "used": f32(np.array(st0.used, copy=True)),
        "port_used": f32(np.array(st0.port_used, copy=True)),
        "dom_sel": f32(np.array(st0.dom_sel, copy=True)),
        "dom_anti": f32(np.array(st0.dom_anti, copy=True)),
        "dom_prefw": f32(np.array(st0.dom_prefw, copy=True)),
        "gpu_free": f32(np.array(st0.gpu_free, copy=True)),
        "vg_free": f32(np.array(st0.vg_free, copy=True)),
        "dev_free": f32(np.array(st0.dev_free, copy=True)),
    }
    outputs = {
        "chosen": np.zeros(P, np.int32),
        "fail_counts": np.zeros((P, kernels.NUM_FILTERS - kernels.F_PORTS), np.int32),
        "insufficient": np.zeros((P, R), np.int32),
        "gpu_take": np.zeros((P, Gd), np.float32),
        # path attribution + OPENSIM_NATIVE_PROFILE phase timings
        "path_counts": np.zeros(3, np.int32),
        "profile_out": np.zeros(12, np.float64),
        # decision audit (explain=1): per-filter reject totals, kernel
        # filter-index order (always marshalled; only written under explain)
        "filter_rejects": np.zeros(kernels.NUM_FILTERS, np.int64),
        # incremental-carry attribution (abi v5): why the envelope
        # disengaged (_BAIL_REASONS order) + which carry classes served
        # incremental steps (_CARRY_CLASSES order)
        "bail_out": np.zeros(len(_BAIL_REASONS), np.int64),
        "class_steps": np.zeros(len(_CARRY_CLASSES), np.int64),
    }

    dims = {
        "N": N, "R": R, "U": U, "P": P,
        "Tk": ec.node_domain.shape[1], "Dp1": ec.domain_topo.shape[0],
        "A": ec.matches_sel.shape[1], "Hp": ec.ports.shape[1],
        "Hports": st0.port_used.shape[1], "Cs": ec.spr_topo.shape[1],
        "Ti": ec.at_sel.shape[1], "Tn": ec.an_sel.shape[1],
        "Tpp": ec.pt_sel.shape[1], "G": ec.anti_g_sel.shape[0],
        "Gp": ec.prefg_sel.shape[0], "Gd": Gd,
        "Vg": ec.node_vg_cap.shape[1], "Dv": ec.node_dev_cap.shape[1],
        "Mv": ec.dev_req_sizes.shape[2],
        "res_cpu": V.RES_CPU, "res_mem": V.RES_MEMORY,
        "res_gc": kernels.gc_row_of(ec),
        "ft_ports": feat.ports, "ft_gpu": feat.gpu, "ft_local": feat.local,
        "ft_interpod": feat.interpod, "ft_prefg": feat.prefg,
        "ft_spread_hard": feat.spread_hard, "ft_spread_soft": feat.spread_soft,
        "ft_pref_na": feat.pref_node_affinity,
        "ft_pref_taints": feat.prefer_taints,
        "ft_prefer_avoid": feat.prefer_avoid,
        "ft_gc_dyn": feat.gc_dyn,
        "cf_ports": cfg.f_ports, "cf_fit": cfg.f_fit, "cf_spread": cfg.f_spread,
        "cf_interpod": cfg.f_interpod, "cf_gpu": cfg.f_gpu, "cf_local": cfg.f_local,
        "tie_sample": tie_seed is not None, "tie_seed": tie_seed or 0,
        "explain": bool(explain),
    }
    weights = {k: getattr(cfg, k) for k in (
        "w_balanced", "w_least", "w_node_affinity", "w_taint_toleration",
        "w_interpod", "w_spread", "w_prefer_avoid", "w_simon", "w_gpu_share",
        "w_local",
    )}
    buffers = {
        "node_valid": u8(node_valid_arr), "alloc": f32(ec.alloc),
        "node_domain": i32(ec.node_domain), "domain_topo": i32(ec.domain_topo),
        "req": f32(ec.req), "ports": i32(ec.ports),
        "port_conflict": u8(ec.port_conflict),
        "spr_topo": i32(ec.spr_topo), "spr_sel": i32(ec.spr_sel),
        "spr_skew": i32(ec.spr_skew), "spr_hard": u8(ec.spr_hard),
        "at_sel": i32(ec.at_sel), "at_topo": i32(ec.at_topo),
        "an_sel": i32(ec.an_sel), "an_topo": i32(ec.an_topo),
        "pt_sel": i32(ec.pt_sel), "pt_topo": i32(ec.pt_topo), "pt_w": f32(ec.pt_w),
        "matches_sel": u8(ec.matches_sel), "anti_g": u8(ec.anti_g),
        "anti_g_sel": i32(ec.anti_g_sel), "anti_g_topo": i32(ec.anti_g_topo),
        "prefg_w": f32(ec.prefg_w), "prefg_sel": i32(ec.prefg_sel),
        "prefg_topo": i32(ec.prefg_topo),
        "gpu_mem": f32(ec.gpu_mem), "gpu_count": i32(ec.gpu_count),
        "node_gpu_cap": f32(ec.node_gpu_mem),
        "avoid_score": f32(ec.avoid_score),
        "lvm_req": f32(ec.lvm_req), "dev_req": f32(ec.dev_req),
        "dev_req_count": i32(ec.dev_req_count),
        "dev_req_sizes": f32(ec.dev_req_sizes),
        "node_vg_cap": f32(ec.node_vg_cap), "node_dev_cap": f32(ec.node_dev_cap),
        "node_dev_media": i32(ec.node_dev_media), "pin": i32(ec.pin),
        "static_pass": u8(stat.static_pass), "aff_mask": u8(stat.aff_mask),
        "na_raw": f32(stat.na_raw), "tt_raw": f32(stat.tt_raw),
        "share_raw": f32(stat.share_raw), "spread_weight": f32(stat.spread_weight),
        "tmpl_ids": i32(prep.tmpl_ids), "forced": u8(forced_arr),
        "pod_valid": u8(pod_valid),
        "static_fail": i32(stat.static_fail),
        **state,
        **outputs,
    }
    native.run_scan(dims, weights, buffers)

    stats = _path_stats(outputs["path_counts"], outputs["profile_out"],
                        outputs["bail_out"], outputs["class_steps"])
    _attach_profile_spans(stats, P)
    return ScheduleOutput(
        chosen=outputs["chosen"],
        fail_counts=outputs["fail_counts"],
        insufficient=outputs["insufficient"],
        gpu_take=outputs["gpu_take"],
        static_fail=np.asarray(stat.static_fail),
        final_state=ScanState(**state),
        native_stats=stats,
        filter_rejects=outputs["filter_rejects"] if explain else None,
    )


def _attach_profile_spans(stats: dict, n_pods: int) -> None:
    """OPENSIM_NATIVE_PROFILE phase timings as child spans of the ambient
    engine span (ISSUE 5): the C++ scan's internal time lands in the same
    request tree as host prep. The .so measures durations, not timestamps,
    so the children are laid out sequentially from the span's start.

    Only attaches when the ambient span IS an engine span: sweep callers
    (``nativepath.sweep``, one schedule() per scenario) run with the trace
    root ambient, and stamping hundreds of per-scenario stats/children onto
    the root would mis-attribute the whole run to the last scenario."""
    from ..obs import trace as obs

    cur = obs.current_span()
    if not getattr(cur, "name", "").startswith("engine."):
        return
    cur.set(
        native_path=stats["path"],
        steps_incremental=stats["steps"]["incremental"],
        steps_generic=stats["steps"]["generic"],
        pods=int(n_pods),
    )
    for phase, rec in (stats.get("profile") or {}).items():
        cur.child_from_seconds(
            f"native.{phase}", rec["seconds"], steps=rec["steps"]
        )


_PROFILE_PHASES = ("delta", "full_eval", "argmax", "bind", "fail", "generic")

# scan_engine.cc `enum Bail` slot order (abi v5): the three whole-scan
# envelope gates, then the per-delta bail classes. A nonzero count names
# exactly which gate closed the incremental path for a workload.
_BAIL_REASONS = (
    "force_generic", "explain", "cs",
    "ports", "gpu", "local", "gc_dyn", "fit", "spread", "interpod", "pending",
)

# ScanArgs.class_steps slot order: incremental steps served with each
# resource-class carry active (score = dynamic share and/or local score)
_CARRY_CLASSES = ("ports", "gpu", "local", "score")

# most recent scan's per-phase timings (OPENSIM_NATIVE_PROFILE only) — read
# by bench.py to put a structured `native_profile` field on its JSON line.
# Cleared at the start of every schedule() call so a run that never reached
# the C++ engine can't inherit a previous run's numbers; a segmented
# multi-profile run leaves the LAST segment's scan here.
_LAST_PROFILE: list = [None]


def last_profile():
    """Per-phase {seconds, steps} of the most recent C++ engine scan in
    this process, or None when OPENSIM_NATIVE_PROFILE was not set or no
    native scan has run since the last schedule() attempt."""
    return _LAST_PROFILE[0]


def _path_stats(path_counts: np.ndarray, profile_out: np.ndarray,
                bail_out: np.ndarray = None, class_steps: np.ndarray = None) -> dict:
    """Engine path attribution (ISSUE 4 satellite: a silent incremental-cache
    disengage must be visible): which evaluation path served the scheduled
    steps, plus the per-phase OPENSIM_NATIVE_PROFILE timings when enabled.
    abi v5 adds *why* attribution: nonzero bail-reason counts under
    ``steps["bails"]`` and per-carry-class engagement under
    ``steps["classes"]`` (additive keys — rest._Metrics.record() only reads
    the incremental/generic pair, so older consumers are unaffected)."""
    inc, gen, full = (int(x) for x in path_counts)
    if inc and gen:
        path = "mixed"
    elif inc:
        path = "incremental"
    elif gen:
        path = "generic"
    else:
        path = "none"  # every pod forced/invalid: no scheduling step ran
    stats = {
        "path": path,
        "steps": {"incremental": inc, "generic": gen, "full_evals": full},
    }
    if bail_out is not None:
        bails = {_BAIL_REASONS[k]: int(v) for k, v in enumerate(bail_out) if v}
        if bails:
            stats["steps"]["bails"] = bails
    if class_steps is not None:
        classes = {_CARRY_CLASSES[k]: int(v) for k, v in enumerate(class_steps) if v}
        if classes:
            stats["steps"]["classes"] = classes
    if profile_out.any():
        stats["profile"] = {
            _PROFILE_PHASES[k]: {
                "seconds": round(float(profile_out[2 * k]), 6),
                "steps": int(profile_out[2 * k + 1]),
            }
            for k in range(len(_PROFILE_PHASES))
            if profile_out[2 * k + 1] > 0
        }
        _LAST_PROFILE[0] = stats["profile"]
    return stats


def sweep(prep, node_valid_masks, pod_valid_masks, forced_masks, config=None):
    """Scenario sweep on the C++ engine: one sequential scan per scenario
    — the accelerator-less counterpart of the batched Pallas/XLA sweeps, so
    `simon apply`/`simon defrag` under --backend native never touch an XLA
    scan compile (the reference's capacity loop is ms-scale serial re-runs,
    apply.go:203-259). Returns (unscheduled [S], used [S,N,R], chosen
    [S,P], vg_used [S]) matching parallel.scenarios.SweepResult."""
    S = node_valid_masks.shape[0]
    vg0 = np.asarray(prep.st0.vg_free)
    unscheduled = np.zeros((S,), np.int32)
    used, chosen, vg_used = [], [], np.zeros((S,), np.float32)
    for s in range(S):
        nv = np.asarray(node_valid_masks[s], bool)
        pv = np.asarray(pod_valid_masks[s], bool)
        out = schedule(
            prep, pv, config=config, node_valid=nv,
            forced=np.asarray(forced_masks[s], bool),
        )
        ch = np.asarray(out.chosen)
        chosen.append(ch)
        unscheduled[s] = int((pv & (ch < 0)).sum())
        used.append(np.asarray(out.final_state.used))
        vg_used[s] = float(
            ((vg0 - np.asarray(out.final_state.vg_free)) * nv[:, None]).sum()
        )
    return unscheduled, np.stack(used), np.stack(chosen), vg_used
