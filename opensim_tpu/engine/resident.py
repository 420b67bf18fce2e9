"""The resident carry: a live twin's bound pods folded into the scan's
initial state once per twin state, so a served what-if scans its own pods.

A ``lax.scan`` over a stream equals the scan over its prefix followed by the
scan over its suffix from the prefix's final state, bit for bit. The prefix
here is the longest leading run of *forced* pods of a base ``CacheEntry``'s
stream (``rest.py``'s ``…|base`` entry: the twin's snapshot prepared with no
apps). Forced steps take no decision (``chosen = pin[u]``), so the state after
them depends on nothing a later request appends; :func:`_build` runs the
existing scan over them once and keeps the final ``ScanState`` on the entry.

A request's derived ``Prepared`` (``prepcache.derive_with_app_slices``) is
encoded against a fork of the base's encoder: more templates, maybe more
selectors, terms, ports, topology keys, domains and resources. :func:`_widen`
re-expresses the kept state under that encoding, exactly:

- ``used``, ``gpu_free``, ``vg_free``, ``dev_free`` are node-side and
  order-dependent: taken from the carry (a new resource column is zero);
- ``port_used``, ``dom_sel``, ``dom_anti``, ``dom_prefw`` are sums of small
  integers, exact in float32 in any order. The block the base's scan
  maintained (its real domain rows and the trash row, its columns, its
  topology keys) comes from the carry; everything else — columns and topology
  keys the request brought, and whole tensors whose feature the base had off
  — is counted in closed form over the resident pods against the derived
  encoding (:func:`_count_new`; ``explain.rebuild_counts`` is the oracle).

The encoder's vocab, selector and term tables are append-only with the base's
entries first (``ClusterEncoder.fork``), so a base column keeps its index; an
axis of ``max(len, 1)`` has a phantom column that means nothing, which is why
the kept block is sized by the base's *features*, never by shape alone.

:func:`fetch` declines (the scan then replays in full, as before) on anything
this does not cover, and says why in the ``xla.resident`` span and in
``simon_resident_carry_total{outcome=}``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..encoding.state import ScanState
from ..ops import kernels
from . import select
from .scheduler import pad_pod_stream, scan_unroll, schedule_pods

# float32 holds every integer below 2**24: the bound under which the count
# tensors' sums are exact in any order
_EXACT = float(2 ** 24)


@dataclass
class ResidentCarry:
    """What one base entry keeps of its resident run. Read-only once built."""

    n_res: int  # length of the leading forced run
    valid: np.ndarray  # [n_res] bool — the pod_valid mask the run was scanned with
    state: ScanState  # device — the carry after the run, under the base's encoding
    chosen: np.ndarray  # [n_res] i32 host — the run's outputs decode needs
    gpu_take: np.ndarray  # [n_res, Gd] f32 host
    weight: np.ndarray  # [U_base] f32 — bound resident pods per template


class Head(NamedTuple):
    """A request's view of the carry: the state its scan starts from (the
    carry's, under the request's own encoding), and the carry, whose outputs
    stand in front of the scan's."""

    carry: ResidentCarry
    state: ScanState

    @property
    def n_res(self) -> int:
        return self.carry.n_res

    def in_front_of(self, out):
        """``out`` (the suffix scan's) at full stream length. Forced steps
        report no filter failures, so those rows are zeros; ``static_fail``
        and ``final_state`` are the suffix scan's and already whole."""
        fc, ins = np.asarray(out.fail_counts), np.asarray(out.insufficient)
        return out._replace(
            chosen=np.concatenate([self.carry.chosen, np.asarray(out.chosen)]),
            fail_counts=np.concatenate([np.zeros((self.n_res,) + fc.shape[1:], fc.dtype), fc]),
            insufficient=np.concatenate([np.zeros((self.n_res,) + ins.shape[1:], ins.dtype), ins]),
            gpu_take=np.concatenate([self.carry.gpu_take, np.asarray(out.gpu_take)]),
        )


class _Declined(Exception):
    """The widening met something it does not handle; the message is the reason."""


def leading_forced(forced: np.ndarray) -> int:
    forced = np.asarray(forced, dtype=bool)
    return len(forced) if forced.all() else int(np.argmin(forced))


def _build(entry, n_res: int, valid: np.ndarray) -> ResidentCarry:
    """One scan over the base stream's resident run, under the base's own
    encoding and features. The stream is padded to its bucket like any other
    (pad steps are invalid and bind nothing), so a twin that gains a pod
    reuses the compiled scan."""
    prep = entry.prep
    tmpl_p, valid_p, forced_p = pad_pod_stream(
        prep.tmpl_ids[:n_res], valid, prep.forced[:n_res]
    )
    out = schedule_pods(
        prep.ec, prep.st0, tmpl_p, valid_p, forced_p,
        features=prep.features, unroll=scan_unroll(),
    )
    chosen = np.asarray(out.chosen)[:n_res]
    return ResidentCarry(
        n_res=n_res,
        valid=valid,
        state=out.final_state,
        chosen=chosen,
        gpu_take=np.asarray(out.gpu_take)[:n_res],
        weight=np.bincount(
            prep.tmpl_ids[:n_res][chosen >= 0], minlength=len(prep.ec_np.pin)
        ).astype(np.float32),
    )


def _count_new(carry: ResidentCarry, ec, feat: kernels.Features, cols: dict, keys: int):
    """The count tensors' cells outside the block the base's scan maintained,
    in closed form over the resident pods against the derived encoding ``ec``
    (numpy): a forced pod binds to ``pin[u]``, so the fold runs over templates
    weighted by their bound pods. The carry already covers ``cols[name]``
    columns of each tensor and, of ``dom_sel``, the first ``keys`` topology
    keys. Returns ``{name: array}`` for the tensors something was added to
    (the derived ``st0``'s zeros serve for the others)."""
    got = {}
    us = np.nonzero(carry.weight)[0]
    if not len(us):
        return got
    w = carry.weight[us][:, None]
    nodes = ec.pin[us]
    doms = ec.node_domain[nodes]  # [B, Tk]
    n_rows = len(ec.domain_topo)

    if feat.ports:
        ports = ec.ports[us]  # [B, Hp]
        new = ports >= cols["port_used"]
        if new.any():
            got["port_used"] = np.zeros((len(ec.node_valid), len(ec.port_conflict)), np.float32)
            rows = np.broadcast_to(nodes[:, None], ports.shape)
            np.add.at(got["port_used"], (rows[new], ports[new]), np.broadcast_to(w, ports.shape)[new])

    if feat.sel_counts:
        for tk in range(doms.shape[1]):
            lo = cols["dom_sel"] if tk < keys else 0
            vals = ec.matches_sel[us, lo:] * w
            if vals.any():
                out = got.setdefault("dom_sel", np.zeros((n_rows, ec.matches_sel.shape[1]), np.float32))
                np.add.at(out[:, lo:], doms[:, tk], vals)

    for name, on, carried, topo in (
        ("dom_anti", feat.interpod, ec.anti_g, ec.anti_g_topo),
        ("dom_prefw", feat.prefg, ec.prefg_w, ec.prefg_topo),
    ):
        lo = cols[name]
        vals = carried[us, lo:].astype(np.float32) * w  # [B, G - lo]
        if not on or not vals.any():
            continue
        if (vals != np.round(vals)).any():
            raise _Declined("fractional_weight")
        got[name] = np.zeros((n_rows, carried.shape[1]), np.float32)
        for g in range(vals.shape[1]):
            np.add.at(got[name][:, lo + g], doms[:, topo[lo + g]], vals[:, g])
    return got


@functools.partial(jax.jit, static_argnames=("cols", "n_dom"))
def _widen_carry(kept: ScanState, base: ScanState, cols, n_dom):
    """``base`` (the derived ``st0`` with the closed-form counts added) plus
    the carry's block. ``cols``: the columns kept of ``port_used``,
    ``dom_sel``, ``dom_anti``, ``dom_prefw``, which keep their index. Of a
    domain table the carry's first ``n_dom`` rows stay where they are and its
    trash row (its last) moves to the new trash row."""

    def dom(old, new, c):
        if not c:
            return new
        new = new.at[:n_dom, :c].add(old[:n_dom, :c])
        return new.at[-1, :c].add(old[-1, :c])

    h, a, g, gp = cols
    return ScanState(
        used=base.used.at[:, : kept.used.shape[1]].set(kept.used),
        port_used=base.port_used.at[:, :h].add(kept.port_used[:, :h]) if h else base.port_used,
        dom_sel=dom(kept.dom_sel, base.dom_sel, a),
        dom_anti=dom(kept.dom_anti, base.dom_anti, g),
        dom_prefw=dom(kept.dom_prefw, base.dom_prefw, gp),
        gpu_free=kept.gpu_free,
        vg_free=kept.vg_free,
        dev_free=kept.dev_free,
    )


def _widen(carry: ResidentCarry, base_prep, prep) -> ScanState:
    """The carry's state under ``prep``'s encoding (module docstring)."""
    ec0, ec = base_prep.ec_np, prep.ec_np
    f0, f = base_prep.features, prep.features
    n_tmpl = len(carry.weight)
    for a, b, why in (
        (prep.tmpl_ids[: carry.n_res], base_prep.tmpl_ids[: carry.n_res], "stream"),
        (ec.pin[:n_tmpl], ec0.pin, "pins"),
        (ec.node_valid, ec0.node_valid, "nodes"),
    ):
        if not np.array_equal(a, b):
            raise _Declined(why)
    if any(
        getattr(prep.st0, k).shape != getattr(carry.state, k).shape
        for k in ("gpu_free", "vg_free", "dev_free")
    ) or prep.st0.used.shape[1] < carry.state.used.shape[1]:
        raise _Declined("node_state_shape")

    # what the base's scan maintained: a tensor's columns when its feature
    # was on there, nothing otherwise (a phantom column of a max(len, 1) axis
    # is never kept: a feature that is on has a real one)
    cols = {
        "port_used": len(ec0.port_conflict) if f0.ports and f.ports else 0,
        "dom_sel": ec0.matches_sel.shape[1] if f0.sel_counts and f.sel_counts else 0,
        "dom_anti": len(ec0.anti_g_topo) if f0.interpod and f.interpod else 0,
        "dom_prefw": len(ec0.prefg_topo) if f0.prefg and f.prefg else 0,
    }
    # the base's real domains keep their rows and its topology keys their
    # columns of node_domain; absent labels point at the trash row, which moved
    keys = ec0.node_domain.shape[1]
    n_dom = int((ec0.domain_topo[:-1] >= 0).sum())
    if cols["dom_sel"] or cols["dom_anti"] or cols["dom_prefw"]:
        trash0, trash = len(ec0.domain_topo) - 1, len(ec.domain_topo) - 1
        moved = np.where(ec0.node_domain == trash0, trash, ec0.node_domain)
        if ec.node_domain.shape[1] < keys or not np.array_equal(moved, ec.node_domain[:, :keys]):
            raise _Declined("domains")
    bound = carry.n_res * ec.node_domain.shape[1]
    if f.prefg:
        bound *= max(1.0, float(np.abs(ec.prefg_w[:n_tmpl]).max(initial=0.0)))
    if bound >= _EXACT:
        raise _Declined("count_range")

    new = _count_new(carry, ec, f, cols, keys if cols["dom_sel"] else 0)
    base = prep.st0._replace(**{k: jnp.asarray(v) for k, v in new.items()})
    return _widen_carry(carry.state, base, cols=tuple(cols.values()), n_dom=n_dom)


def fetch(prep, pod_valid, ask: select.Ask = select.Ask()) -> Optional[Head]:
    """The resident carry for this run of ``prep``, built if its base entry
    has none yet, widened to ``prep``'s encoding; None when the run has to
    replay in full. Either way the ``xla.resident`` span and the counter say
    which (``hit``, ``built``, ``declined``) and why."""
    from ..obs import trace as obs
    from ..obs.metrics import RECORDER

    with obs.span("xla.resident") as sp:
        head, outcome, n_res = None, "declined", 0
        reason = select.carry(prep, ask)
        if reason is None:
            entry = prep.resident_base
            with entry.lock:
                carry = entry.resident
                n_res = carry.n_res if carry is not None else leading_forced(entry.prep.forced)
                want = (
                    carry.valid if carry is not None
                    else np.ones(n_res, bool) if entry.base_drop is None
                    else ~np.asarray(entry.base_drop[:n_res], dtype=bool)
                )
                if n_res == 0:
                    reason = "no_resident_pods"
                elif not np.array_equal(np.asarray(pod_valid[:n_res], dtype=bool), want):
                    reason = "mask"  # a scale request drops resident pods
                else:
                    outcome = "hit"
                    if carry is None:
                        carry = entry.resident = _build(entry, n_res, want)
                        outcome = "built"
                    try:
                        head = Head(carry, _widen(carry, entry.prep, prep))
                    except _Declined as e:
                        outcome, reason = "declined", f"widen:{e}"
        sp.set(outcome=outcome, reason=reason or "", resident_pods=n_res)
    RECORDER.count_resident_carry(outcome)
    return head
