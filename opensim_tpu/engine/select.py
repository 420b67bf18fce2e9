"""Which engine runs, in what order, and why not the others.

The one module under ``opensim_tpu/`` that reads the six engine knobs and asks
JAX for the platform and the device count in order to choose an engine. It
holds the policy (:func:`policy`), the table of what each engine declines
(:data:`DECLINES`) and the functions from a ``Prepared`` and an :class:`Ask` to
the answer (:func:`ladder`, :func:`carry`, :func:`batch`), with
:func:`kernel_failed` for a kernel that raised. Callers keep the running:
spans, breakers, the ``try`` round the kernel. The envelopes stay with their
engines (``fastpath.why_not``, ``nativepath.why_not``) and are asked last, so a
run the kernel cannot take never imports Pallas (~1 s).
"""

from __future__ import annotations

import logging
import re
from typing import Dict, NamedTuple, Optional, Set, Tuple

import jax

from ..utils import envknobs
from .schedconfig import DEFAULT_CONFIG, kernel_gap

log = logging.getLogger("opensim_tpu")


class Policy(NamedTuple):
    platform: str
    devices: int
    interpret: bool  # OPENSIM_FASTPATH=interpret: the Pallas interpreter, asked for by name
    strict: bool  # OPENSIM_REQUIRE_TPU=1 (--backend tpu): a kernel failure is fatal
    forced_native: bool  # OPENSIM_NATIVE=1 (--backend native)
    off: Dict[str, Optional[str]]  # rung -> the words of what switched it off


def policy() -> Policy:
    """Read afresh on every call: tests change the environment and substitute
    ``jax.default_backend``."""
    platform = jax.default_backend()
    interpret = envknobs.raw("OPENSIM_FASTPATH") == "interpret"
    forced_native = envknobs.raw("OPENSIM_NATIVE") == "1"
    megakernel = native = None
    if platform != "tpu" and not interpret:
        # the kernel compiles for a TPU only; its interpreter is slower than the XLA scan
        megakernel = f"no TPU backend (jax.default_backend()={platform!r})"
    elif envknobs.raw("OPENSIM_DISABLE_FASTPATH"):
        megakernel = "disabled by --backend xla (OPENSIM_DISABLE_FASTPATH)"
    elif forced_native:
        megakernel = "disabled by --backend native (OPENSIM_NATIVE=1)"
    if envknobs.raw("OPENSIM_DISABLE_NATIVE"):
        native = "disabled by --backend xla (OPENSIM_DISABLE_NATIVE)"
    elif platform == "tpu" and not forced_native:
        native = "TPU backend present (the megakernel/XLA scan own the accelerator)"
    return Policy(
        platform, len(jax.devices()), interpret, envknobs.raw("OPENSIM_REQUIRE_TPU") == "1",
        forced_native, {"megakernel": megakernel, "native": native},
    )


class Ask(NamedTuple):
    """What a caller asks of the engines beyond a plain stream of pods."""

    shape: str = "stream"  # or "sweep" (a scenario axis), "batch" (a request axis)
    segments: Optional[int] = None  # profile segments of a multi-profile stream
    explain: bool = False
    sched_config: object = None
    extra_plugins: tuple = ()
    tie_seed: Optional[int] = None
    node_mask: bool = False
    start_state: bool = False  # the stream starts from the caller's ScanState, not prep.st0
    # the caller reads why a pod failed. No rung is chosen by it: the ladder's one
    # question is whether a kernel result with a mid-stream failure is re-scanned
    reasons: bool = True


_SHARDED = "{devices} devices: the sweep is sharded across them on the XLA scan"
# What each engine declines, as (ask, reason) in the order its reasons take
# precedence; what is not listed is served, and the XLA scan serves everything.
# The C++ scan's other two (extra_plugins, a config with fit_ignored_cols) are
# in nativepath.why_not, with the engine.
DECLINES = {
    "megakernel": (
        ("batch", "request-axis batches run on the vmapped XLA scan (or sequential C++ scans)"),
        ("many_devices", _SHARDED),
        ("segments", "segmented multi-profile stream ({segments} segments)"),
        # only the C++ generic path and the XLA count_all scan emit per-filter verdicts
        ("explain", "explain mode audits per-filter verdicts (C++/XLA engines)"),
        # the profile's weights and its RequestedToCapacityRatio are served
        ("sched_config", "a scheduler config the kernel cannot compute ({gap})"),
        ("extra_plugins", "out-of-tree extra_plugins run on the XLA scan"),
        ("tie_seed", "sampled tie-break runs on the C++ engine or XLA scan"),
        ("start_state", "a stream from the caller's scan state runs on the C++ engine or XLA scan"),
        # a node mask is served: validity is a runtime row of the kernel
    ),
    "native": (("many_devices", _SHARDED),),
    # the resident carry's reasons are the tokens of the xla.resident span and its counter
    "resident": (
        ("segments", "segments"),
        ("no_base", "no_base"),  # a plain prepare, or a base extended with new nodes
        ("node_mask", "node_mask"),
        ("tie_seed", "tie_seed"),  # a key rides the carry and is split every step
        ("explain", "explain"),  # every step emits its rows
        ("config", "sched_config"),  # any config but the default one
        ("extra_plugins", "extra_plugins"),
    ),
}


def _asked(prep, ask: Ask, devices: int = 1) -> Set[str]:
    facts = {
        "batch": ask.shape == "batch",
        "many_devices": ask.shape == "sweep" and devices != 1,
        "segments": ask.segments is not None,
        "explain": ask.explain,
        "sched_config": kernel_gap(ask.sched_config) is not None,
        "config": ask.sched_config is not None and ask.sched_config != DEFAULT_CONFIG,
        "extra_plugins": bool(ask.extra_plugins),
        "tie_seed": ask.tie_seed is not None,
        "node_mask": ask.node_mask,
        "start_state": ask.start_state,
        "no_base": prep.resident_base is None,
    }
    return {name for name, on in facts.items() if on}


def _declined(engine: str, asked: Set[str], **words) -> Optional[str]:
    return next((why.format(**words) for name, why in DECLINES[engine] if name in asked), None)


def ladder(prep, ask: Ask = Ask(), pol: Optional[Policy] = None) -> Dict[str, Optional[str]]:
    """For each rung (``megakernel``, ``native``, ``xla``): None, or the reason
    it cannot run what is asked. The table first, then the policy, then the
    engine's own envelope."""
    pol = pol or policy()
    asked = _asked(prep, ask, pol.devices)
    words = {"segments": ask.segments, "devices": pol.devices, "gap": kernel_gap(ask.sched_config)}
    megakernel = _declined("megakernel", asked, **words) or pol.off["megakernel"]
    if megakernel is None:
        from . import fastpath

        megakernel = fastpath.why_not(prep)
        if megakernel is not None:
            log.info("megakernel envelope miss (%s): %s", ask.shape, megakernel)
    native = _declined("native", asked, **words) or pol.off["native"]
    if native is None:
        from . import nativepath

        native = nativepath.why_not(prep, ask.sched_config, ask.extra_plugins, ask.tie_seed)
    return {"megakernel": megakernel, "native": native, "xla": None}


_OVER = re.compile(r"\b([RUA])=\d+ > \d+ supported")


def _envelope_token(engine: str, reason: str) -> str:
    """An envelope's reason as a short token. The kernel's: the table axes
    over their caps joined by ``+`` (``U``, ``A``, ``R``), ``vmem``,
    ``topo_keys``, or ``features`` for a feature's table it has no rows for.
    The C++ scan's: ``extra_plugins``, ``fit_ignored_cols``, ``rtcr``,
    ``not_built``."""
    if engine == "megakernel":
        over = _OVER.findall(reason)
        if over:
            return "+".join(over)
        if reason.startswith("VMEM estimate"):
            return "vmem"
        return "topo_keys" if "topology keys" in reason else "features"
    if reason.startswith("engine not built"):
        return "not_built"
    if "RequestedToCapacityRatio" in reason:
        return "rtcr"
    return "extra_plugins" if "extra_plugins" in reason else "fit_ignored_cols"


def turned_away(prep, ask: Ask, pol: Policy, rungs: Dict[str, Optional[str]]) -> Optional[Tuple[str, str]]:
    """The first rung the policy left on that declined the run, with its
    reason as a short token: the row's name in :data:`DECLINES` (the
    ``sched_config`` row's with what the kernel cannot compute,
    ``sched_config:disabled_filter``), else the token of the engine's envelope. None when that rung serves the run; a
    rung the policy switched off turned nothing away."""
    asked = _asked(prep, ask, pol.devices)
    for engine in ("megakernel", "native"):
        if pol.off[engine] is not None:
            continue
        if rungs[engine] is None:
            return None
        row = next((name for name, _why in DECLINES[engine] if name in asked), None)
        if row == "sched_config":  # which part of the config: a token, not the prose of the reason
            row = f"sched_config:{kernel_gap(ask.sched_config)}"
        return engine, row or _envelope_token(engine, rungs[engine])
    return None


def decline_attrs(prep, away: Optional[Tuple[str, str]]) -> Dict[str, object]:
    """What the span of the rung that runs instead says of a run that
    :func:`turned_away` names: ``declined`` (``<rung>:<token>``) and the sizes
    the envelopes look at, the templates, the selectors and the pods pinned to
    a node (each DaemonSet pod is a template of its own). Empty for None."""
    if away is None:
        return {}
    ec = prep.ec_np if prep.ec_np is not None else prep.ec
    return {
        "declined": ":".join(away),
        "templates": int(ec.req.shape[0]),
        "selectors": int(ec.matches_sel.shape[1]),
        "pinned_pods": sum(1 for t in prep.ds_target if t >= 0),
    }


def carry(prep, ask: Ask = Ask()) -> Optional[str]:
    """The token under which the resident carry declines what is asked, None
    when it serves it (``resident.fetch`` then looks at its own data)."""
    return _declined("resident", _asked(prep, ask))


def batch(prep) -> Tuple[str, Dict[str, str]]:
    """A request batch's engine and the skip map its riders report. Under
    ``OPENSIM_BATCH_ENGINE=auto`` sequential C++ scans where they can run and
    win (one device, or ``--backend native``: ms-scale a request, no XLA
    compile), else one vmapped XLA dispatch; ``xla`` / ``native`` force one."""
    mode = envknobs.raw("OPENSIM_BATCH_ENGINE", "auto").strip().lower() or "auto"
    if mode not in ("auto", "xla", "native"):
        raise ValueError(f"OPENSIM_BATCH_ENGINE must be auto|xla|native, got {mode!r}")
    pol = policy()
    rungs = ladder(prep, Ask(shape="batch"), pol)
    skips = {"megakernel": rungs["megakernel"]}
    if mode == "native" and rungs["native"] is not None:
        raise RuntimeError(
            f"OPENSIM_BATCH_ENGINE=native but the C++ engine cannot run this stream: {rungs['native']}"
        )
    if mode == "native" or (
        mode == "auto" and rungs["native"] is None and (pol.forced_native or pol.devices == 1)
    ):
        skips["xla"] = "OPENSIM_BATCH_ENGINE routed the batch to the C++ engine"
        return "native", skips
    if rungs["native"] is None:
        skips["native"] = "request-axis batching dispatches ONE vmapped scan"
    return "xla", skips


_KERNEL = {  # the call's shape -> (what failed, what it falls back to)
    "stream": ("the Pallas megakernel failed to compile/run", "a slower engine"),
    "sweep": ("the batched megakernel sweep failed", "the XLA sweep"),
}


def kernel_failed(e: Exception, shape: str, breaker=None) -> str:
    """The rule for a kernel that raised. Under interpret the error stands: a
    broken kernel contract must fail, not validate the fallback engine. Under
    strict it is fatal: a fallback measured as ``--backend tpu`` would be a
    lie. Otherwise it is logged, counted against ``breaker`` and returned as
    the rung's skip reason."""
    pol = policy()
    if pol.interpret:
        raise e
    what, fallback = _KERNEL[shape]
    if pol.strict:
        raise RuntimeError(
            f"--backend tpu: {what} ({type(e).__name__}: {e}); refusing to "
            f"silently fall back to {fallback}"
        ) from e
    if breaker is not None:
        breaker.record_failure(e)
    log.warning("%s (%s: %s); falling back to %s", what, type(e).__name__, e, fallback)
    return f"{type(e).__name__}: {e}"
