"""Which engine runs, in what order, and why not the others.

The one module under ``opensim_tpu/`` that reads the engine knobs and asks
JAX for the platform and the device count in order to choose an engine. The
ladder has two rungs, the Pallas megakernel and the XLA scan, which serves
everything. This module holds the policy (:func:`policy`), the table of what
each engine declines (:data:`DECLINES`) and the functions from a ``Prepared``
and an :class:`Ask` to the answer (:func:`ladder`, :func:`carry`), with
:func:`kernel_failed` for a kernel that raised. Callers keep the running:
spans, breakers, the ``try`` round the kernel. The kernel's envelope stays
with it (``fastpath.why_not``) and is asked last, so a run the kernel cannot
take never imports Pallas (~1 s).
"""

from __future__ import annotations

import logging
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
    off: Dict[str, Optional[str]]  # rung -> the words of what switched it off


def policy() -> Policy:
    """Read afresh on every call: tests change the environment and substitute
    ``jax.default_backend``."""
    platform = jax.default_backend()
    interpret = envknobs.raw("OPENSIM_FASTPATH") == "interpret"
    megakernel = None
    if platform != "tpu" and not interpret:
        # the kernel compiles for a TPU only; its interpreter is slower than the XLA scan
        megakernel = f"no TPU backend (jax.default_backend()={platform!r})"
    elif envknobs.raw("OPENSIM_DISABLE_FASTPATH"):
        megakernel = "disabled by --backend xla (OPENSIM_DISABLE_FASTPATH)"
    return Policy(
        platform, len(jax.devices()), interpret, envknobs.raw("OPENSIM_REQUIRE_TPU") == "1",
        {"megakernel": megakernel},
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
DECLINES = {
    "megakernel": (
        ("batch", "request-axis batches run on the vmapped XLA scan"),
        ("many_devices", _SHARDED),
        ("segments", "segmented multi-profile stream ({segments} segments)"),
        # only the XLA count_all scan emits per-filter verdicts
        ("explain", "explain mode audits per-filter verdicts (XLA scan)"),
        # the profile's weights and its RequestedToCapacityRatio are served
        ("sched_config", "a scheduler config the kernel cannot compute ({gap})"),
        ("extra_plugins", "out-of-tree extra_plugins run on the XLA scan"),
        ("tie_seed", "sampled tie-break runs on the XLA scan"),
        ("start_state", "a stream from the caller's scan state runs on the XLA scan"),
        # a node mask is served: validity is a runtime row of the kernel
    ),
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
    """For each rung (``megakernel``, ``xla``): None, or the reason it cannot
    run what is asked. The table first, then the policy, then the kernel's
    own envelope."""
    pol = pol or policy()
    asked = _asked(prep, ask, pol.devices)
    words = {"segments": ask.segments, "devices": pol.devices, "gap": kernel_gap(ask.sched_config)}
    megakernel = _declined("megakernel", asked, **words) or pol.off["megakernel"]
    if megakernel is None:
        from . import fastpath

        miss = fastpath.why_not(prep)
        if miss is not None:
            megakernel = miss[1]
            log.info("megakernel envelope miss (%s): %s", ask.shape, megakernel)
    return {"megakernel": megakernel, "xla": None}


def turned_away(prep, ask: Ask, pol: Policy, rungs: Dict[str, Optional[str]]) -> Optional[Tuple[str, str]]:
    """The megakernel, when the policy left it on and it declined the run,
    with its reason as a short token: the row's name in :data:`DECLINES` (the
    ``sched_config`` row's with what the kernel cannot compute,
    ``sched_config:disabled_filter``), else the token of the kernel's envelope
    (``fastpath.why_not``). None when the kernel serves the run; a kernel the
    policy switched off turned nothing away."""
    if pol.off["megakernel"] is not None or rungs["megakernel"] is None:
        return None
    row = next((name for name, _why in DECLINES["megakernel"] if name in _asked(prep, ask, pol.devices)), None)
    if row == "sched_config":  # which part of the config: a token, not the prose of the reason
        row = f"sched_config:{kernel_gap(ask.sched_config)}"
    if row is None:
        from . import fastpath

        row = fastpath.why_not(prep)[0]
    return "megakernel", row


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
