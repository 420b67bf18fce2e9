"""Pallas megakernel for the bind scan (fast path).

The XLA scan pays ~5 µs of per-op overhead for each of the ~30 HLO ops in
a scheduling step. This kernel fuses the entire step — static-filter gather,
resource fit, Least/BalancedAllocation, Simon share, PodTopologySpread
(hard + soft), inter-pod affinity (required / anti / preferred, incoming and
symmetric), selectHost, and the bind state update — into ONE Pallas program
whose cluster state lives in VMEM for the whole scan: a bind costs
VMEM-bandwidth, not kernel launches.

Scope: every scheduler feature — resource fit, topology spread, inter-pod
affinity, GPU-share devices, open-local storage, host ports, preferred node
affinity, PreferNoSchedule and NodePreferAvoidPods scoring — bounded by
table-size caps and at most five topology keys (hostname + four zone-like
keys, stacked per-key count blocks); `engine/fastpath.py`
gates applicability and guarantees identical placements to the XLA scan
(tests + randomized differential fuzzing assert equality). Past 512
templates the kernel switches to big-U mode: the [U, N]/[X, U] template
tables stay in HBM and each pod step DMAs its row/column into VMEM scratch,
so VMEM no longer scales with U (cap 2048, bounded by SMEM scalars). The kernel is
generated per feature-flag combination so absent features cost nothing, and
node validity is a runtime row: a scenario sweep is the same kernel over
scenarios, each reading its own mask, spread weights and pod streams
(`run_fast_scan`; a plain schedule is one scenario). A scheduler config's
score profile (its weights and RequestedToCapacityRatio) is a trace-time
constant of the generated kernel too; the default profile is the kernel
without one.

Scenarios sit on the sublane axis. A step works on [SB, N] rows, one
scenario a sublane: SB = 1 for a schedule, where a [1, N] row fills one of
the eight sublanes of each vector register it lies in, and SB = 8 for a
packed sweep, where eight scenarios fill them all. The grid is
(S / SB scenario blocks, pod chunks); a block's scenarios walk the shared
pod stream together, reading the same template row, static row and request
once a step. `run_fast_scan` is the one entry and a module-level `jax.jit`:
a process traces and lowers the kernel once per signature (argument shapes ×
feature flags × SB) and enters it from the jit's cache after that.

Layouts (N = padded node axis, lanes; rows padded to sublane multiples). A
per-scenario table of X rows is [X*SB, N]: row x of scenario s is row
x*SB + s, so row x of a block is the aligned [SB, N] slice at x*SB (with
SB = 1 the table is the plain [X, N]):
  alloc_T     [R, N]       f32  allocatable per resource row (shared)
  used        [R*SB, N]    f32  scratch, persistent across a block's chunks
  static_pass [U, N]       f32  0/1 from kernels.precompute_static (shared)
  node_valid  [SB, N]      f32  the block's validity rows
  node_cnt    [A*SB, N]    f32  scratch — per-hostname-domain selector counts
  zone_cnt    [K*A*SB, Z]  f32  scratch — per-(zone-key, selector) counts
  zone_id     [K, N]       f32  each node's domain under each zone key, -1 none
  anti_node   [G*SB, N]    f32  scratch — existing-pod anti-affinity terms
  prefw_node  [Gp*SB, N]   f32  scratch — symmetric preferred-term weights
  matches_AU  [A, U]       f32  selector-match matrix (column = template)
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..encoding import vocab as V
from .kernels import div32, floor_div32, rtcr_mean, rtcr_shape_score, rtcr_utilization

NEG = -1e30
MAX_SCORE = 100.0
# pod-stream block: 1-D SMEM windows of CHUNK int32 (compiles for v5e on the
# installed JAX/libtpu; the grid's chunk axis steps through them)
CHUNK = 1024
# the scoped-VMEM limit the kernel is compiled under: half of a v5e core's
# 128 MiB, stated instead of left to the 16 MiB default. fastpath.why_not
# admits a shape when its resident-row ESTIMATE is under
# fastpath._VMEM_BUDGET (10 MB); the compiler then needs about twice that —
# double-buffered per-scenario blocks plus the operand splits of the exact
# (fp32-contract) matmuls: the affinity plan at 5,120 lanes takes 21.6 MB.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _dot(a, b):
    """Every contraction in the kernel is a gather or a count-weighted sum
    written as a matmul (one-hot selectors × counts or weights), so it has to
    be EXACT. The MXU's default for f32 operands is a reduced-precision pass
    that cannot hold a count past 256; at 50k pods the zone counts pass it and
    the compiled kernel's placements left the XLA scan's (found on the chip,
    PR 21). fp32 contract precision is the setting that keeps them identical."""
    return jnp.dot(
        a, b, preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST
    )


def _div_rows(pairs, shape):
    """Exactly rounded quotients (kernels.div32) of `shape` = [SB, N] rows,
    a whole vector register's eight sublanes at a time: with SB = 1 a row
    uses one sublane of each register it lies in, so the quotients of a step
    are stacked into [8, N] blocks and eight of them cost one division; with
    SB = 8 each quotient fills its block alone. Either operand may broadcast
    to `shape`. Returned in the order given."""
    per = 8 // shape[0]
    out = []
    for at in range(0, len(pairs), per):
        group = pairs[at:at + per]
        nums = [jnp.broadcast_to(a, shape) for a, _ in group]
        dens = [jnp.broadcast_to(b, shape) for _, b in group]
        if per == 1:
            out.append(div32(nums[0], dens[0]))
            continue
        fill = per - len(group)
        q = div32(
            jnp.concatenate(nums + nums[:1] * fill, axis=0),
            jnp.concatenate(dens + dens[:1] * fill, axis=0),
        )
        out += [q[j:j + 1, :] for j in range(len(group))]
    return out


class FastInputs(NamedTuple):
    """Host-prepared tensors for the kernel (see engine/fastpath.py)."""

    alloc_T: np.ndarray  # [R, N]
    used0_T: np.ndarray  # [R, N]
    static_pass: np.ndarray  # [U, N]
    aff_mask: np.ndarray  # [U, N]
    share_raw: np.ndarray  # [U, N]
    # per-zone-key one-hot blocks (lane offset 0 per key); empty, [K, 0, Z]
    # and [K*Z, 0], where the kernel reads the zone keys by compare-select
    zone_NZ: np.ndarray  # [K, N, Z]
    zone_ZN: np.ndarray  # [K*Z, N]
    has_zone: np.ndarray  # [K, N] f32 — node has key k's label
    zone_id: np.ndarray  # [K, N] f32 — node's domain index under key k, -1 where it has no label
    matches_AU: np.ndarray  # [A, U]
    node_valid: np.ndarray  # [1, N] f32 (run_fast_scan takes [S, 1, N])
    # SMEM scalar tables
    req: np.ndarray  # [U, R] f32
    cpu_nz: np.ndarray  # [U] f32 nonzero-default cpu (milli)
    mem_nz: np.ndarray  # [U] f32 nonzero-default memory
    pin: np.ndarray  # [U] i32
    # spread constraints, [U, Cs] each
    spr_active: np.ndarray  # i32 0/1
    spr_key: np.ndarray  # i32 topology key index: 0 = hostname, 1..K = zone keys
    spr_sel: np.ndarray  # i32 selector id
    spr_skew: np.ndarray  # f32
    spr_hard: np.ndarray  # i32 0/1
    spr_self: np.ndarray  # f32 0/1 template matches own selector
    # [K+1] f32 log(domain count + 2) of each topology key index (0 =
    # hostname, 1..K zone keys): a spread constraint's weight is its key's
    # (run_fast_scan takes [S, K+1])
    key_weight: np.ndarray
    # inter-pod affinity (all zero-shaped semantics when has_interpod=False)
    at_active: np.ndarray  # [U, Ti] i32 — incoming required affinity terms
    at_key: np.ndarray  # [U, Ti] i32 key index (0 = hostname, 1..K = zone)
    at_sel: np.ndarray  # [U, Ti] i32
    at_self: np.ndarray  # [U, Ti] f32 — bootstrap self-match
    an_active: np.ndarray  # [U, Tn] i32 — incoming anti terms
    an_key: np.ndarray  # [U, Tn] i32
    an_sel: np.ndarray  # [U, Tn] i32
    pt_active: np.ndarray  # [U, Tp] i32 — incoming preferred terms
    pt_key: np.ndarray  # [U, Tp] i32
    pt_sel: np.ndarray  # [U, Tp] i32
    pt_w: np.ndarray  # [U, Tp] f32 signed weights
    anti_g_key: np.ndarray  # [G] i32 — global existing-anti term key indices
    prefg_key: np.ndarray  # [Gp] i32 — global symmetric-preferred term key indices
    antig_GU: np.ndarray  # [G, U] f32 — template carries term g
    gmatch_GU: np.ndarray  # [G, U] f32 — template matches term g's selector
    prefg_GU: np.ndarray  # [Gp, U] f32 — carried symmetric weights
    pmatch_GU: np.ndarray  # [Gp, U] f32 — template matches pref term's selector
    # gpu-share (zero-shaped semantics when has_gpu=False)
    gpu_mem: np.ndarray  # [U] f32 per-GPU memory request
    gpu_cnt: np.ndarray  # [U] f32 requested GPU count
    gpu0_DN: np.ndarray  # [Gd, N] f32 initial per-device free memory
    # open-local storage (inert when has_local=False)
    lvm_req: np.ndarray  # [U] f32 total LVM bytes
    dev_req: np.ndarray  # [U, 2] f32 exclusive-device max size by media (score)
    dev_need: np.ndarray  # [U, 2] f32 device count by media
    dev_sizes: np.ndarray  # [U, 2*Mv] f32 per-volume sizes desc (ssd rows then hdd)
    vg_cap_VN: np.ndarray  # [Vg, N] f32 VG capacities
    vg0_VN: np.ndarray  # [Vg, N] f32 initial VG free
    dev_cap_DN: np.ndarray  # [Dv, N] f32 device capacities
    dev0_DN: np.ndarray  # [Dv, N] f32 initial device free
    dev_media_DN: np.ndarray  # [2*Dv, N] f32 media one-hots (ssd rows then hdd rows)
    # host ports (inert when has_ports=False)
    port_HU: np.ndarray  # [Hp, U] f32 — template uses port row h (bind marks)
    port_conf_HU: np.ndarray  # [Hp, U] f32 — template conflicts with row h (filter)
    # static score tables (inert when the matching feature flag is off)
    na_raw: np.ndarray  # [U, N] f32 preferred-node-affinity weights
    tt_raw: np.ndarray  # [U, N] f32 intolerable PreferNoSchedule counts
    avoid_raw: np.ndarray  # [U, N] f32 NodePreferAvoidPods raw score (0 or 100)


def _input_layout(
    has_interpod: bool,
    has_gpu: bool,
    has_local: bool,
    has_ports: bool,
    has_na: bool,
    has_tt: bool,
    has_avoid: bool,
    big_u: bool,
    packed: bool = False,
    zone_select: bool = False,
):
    """Ordered (name, kind) list of kernel inputs for one feature-flag
    combination; kind ∈ {stream, smem, vmem, any}. The pallas_call signature
    is generated from this, so a workload with a feature off pays ZERO
    VMEM/SMEM for that feature's tables — the buffers don't exist. A kernel
    that reads its zone keys by compare-select (`zone_select`) reads
    `zone_id`, each node's zone under each key, in place of the one-hot
    blocks; a packed kernel (SB > 1) reads it beside them too."""
    ut = "any" if big_u else "vmem"  # U-scaled tables move to HBM in big-U mode
    L = [
        ("tmpl", "stream"), ("valid", "stream"), ("forced", "stream"),
        ("req", "smem"), ("cpu_nz", "smem"), ("mem_nz", "smem"), ("pin", "smem"),
        ("spr_active", "smem"), ("spr_key", "smem"), ("spr_sel", "smem"),
        ("spr_skew", "smem"), ("spr_hard", "smem"), ("spr_self", "smem"),
        ("key_weight", "smem"),
    ]
    if has_interpod:
        L += [
            ("at_active", "smem"), ("at_key", "smem"), ("at_sel", "smem"),
            ("at_self", "smem"),
            ("an_active", "smem"), ("an_key", "smem"), ("an_sel", "smem"),
            ("pt_active", "smem"), ("pt_key", "smem"), ("pt_sel", "smem"),
            ("pt_w", "smem"),
            ("anti_g_key", "smem"), ("prefg_key", "smem"),
        ]
    if has_gpu:
        L += [("gpu_mem", "smem"), ("gpu_cnt", "smem")]
    if has_local:
        L += [("lvm_req", "smem"), ("dev_req", "smem"), ("dev_need", "smem"),
              ("dev_sizes", "smem")]
    L += [
        ("alloc_T", "vmem"), ("used0_T", "vmem"),
        ("static_pass", ut), ("aff_mask", ut), ("share_raw", ut),
    ]
    if not zone_select:
        L += [("zone_NZ", "vmem"), ("zone_ZN", "vmem")]
    L += [("has_zone", "vmem"), ("matches_AU", ut), ("node_valid", "vmem")]
    if packed or zone_select:
        L += [("zone_id", "vmem")]
    if has_interpod:
        L += [("antig_GU", ut), ("gmatch_GU", ut), ("prefg_GU", ut), ("pmatch_GU", ut)]
    if has_gpu:
        L += [("gpu0_DN", "vmem")]
    if has_local:
        L += [("vg_cap_VN", "vmem"), ("vg0_VN", "vmem"), ("dev_cap_DN", "vmem"),
              ("dev0_DN", "vmem"), ("dev_media_DN", "vmem")]
    if has_ports:
        L += [("port_HU", ut), ("port_conf_HU", ut)]
    if has_na:
        L += [("na_raw", ut)]
    if has_tt:
        L += [("tt_raw", ut)]
    if has_avoid:
        L += [("avoid_raw", ut)]
    return L


def _scratch_names(has_interpod, has_gpu, has_local, has_ports):
    names = ["used", "node_cnt", "zone_cnt"]
    if has_interpod:
        names += ["anti_node", "anti_zone", "prefw_node", "prefw_zone"]
    if has_gpu:
        names += ["gpu_free"]
    if has_local:
        names += ["vg_free", "dev_free"]
    if has_ports:
        names += ["port_used"]
    return names


def _make_kernel(
    has_interpod: bool,
    has_gpu: bool,
    has_local: bool,
    has_ports: bool,
    has_na: bool,
    has_tt: bool,
    has_avoid: bool,
    n_anti: int,
    n_pref: int,
    n_gpu: int,
    n_vg: int,
    n_dev: int,
    n_dvol: int,
    big_u: bool = False,
    n_zkeys: int = 1,
    gc_row: int = -1,
    sublanes: int = 1,
    config=None,
    n_vg_real=None,
    n_dev_real=None,
    zone_domains: int = 0,
):
    from ..engine.schedconfig import DEFAULT_CONFIG

    SB = sublanes
    # the open-local loops walk the VG and device rows a node really has; the
    # tables are padded to n_vg / n_dev rows, and a padding row (free and
    # capacity 0, media neither) fits no claim and takes no bind
    n_vg_real = n_vg if n_vg_real is None else n_vg_real
    n_dev_real = n_dev if n_dev_real is None else n_dev_real
    assert n_vg_real <= n_vg and n_dev_real <= n_dev, (n_vg_real, n_vg, n_dev_real, n_dev)
    # the score profile (a SchedulerConfig whose filters are all on; select
    # declines the others): its weights are trace-time constants, a weight of
    # one multiplies nothing and the RequestedToCapacityRatio term exists only
    # where its weight is not 0, so the default profile is the kernel it was
    cfg = config or DEFAULT_CONFIG
    rtcr_cols = [col for col, _w in cfg.rtcr_resources] if cfg.w_rtcr else []
    # the RequestedToCapacityRatio columns with a quotient of their own (cpu
    # and memory reuse LeastAllocated's)
    rtcr_extra = [col for col in rtcr_cols if col >= 0 and col not in (V.RES_CPU, V.RES_MEMORY)]

    def weighted(w, term):
        return term if w == 1.0 else w * term
    layout = _input_layout(
        has_interpod, has_gpu, has_local, has_ports, has_na, has_tt, has_avoid, big_u, SB > 1, zone_domains > 0
    )
    in_names = [n for n, _ in layout]
    out_names = ["chosen", "used_out"]
    if has_gpu:
        # a packed sweep reads no device takes: only a schedule writes them
        out_names += ["gpu_take", "gpu_out"] if SB == 1 else ["gpu_out"]
    if has_local:
        out_names += ["vg_out", "dev_out"]
    scratch_names = _scratch_names(has_interpod, has_gpu, has_local, has_ports)

    def kernel(*refs):
        Rd = dict(zip(in_names + out_names + scratch_names, refs))
        u_scratch = refs[len(in_names) + len(out_names) + len(scratch_names):]
        # SMEM streams + tables
        tmpl_ref, valid_ref, forced_ref = Rd["tmpl"], Rd["valid"], Rd["forced"]
        req_ref, cpu_nz_ref, mem_nz_ref, pin_ref = (
            Rd["req"], Rd["cpu_nz"], Rd["mem_nz"], Rd["pin"])
        sa_ref, sh_ref, ss_ref, sk_ref, shard_ref, sself_ref, kw_ref = (
            Rd["spr_active"], Rd["spr_key"], Rd["spr_sel"], Rd["spr_skew"],
            Rd["spr_hard"], Rd["spr_self"], Rd["key_weight"])
        if has_interpod:
            ata_ref, ath_ref, ats_ref, atf_ref = (
                Rd["at_active"], Rd["at_key"], Rd["at_sel"], Rd["at_self"])
            ana_ref, anh_ref, ans_ref = Rd["an_active"], Rd["an_key"], Rd["an_sel"]
            pta_ref, pth_ref, pts_ref, ptw_ref = (
                Rd["pt_active"], Rd["pt_key"], Rd["pt_sel"], Rd["pt_w"])
            agh_ref, pgh_ref = Rd["anti_g_key"], Rd["prefg_key"]
            antig_ref, gmatch_ref = Rd["antig_GU"], Rd["gmatch_GU"]
            prefg_ref, pmatch_ref = Rd["prefg_GU"], Rd["pmatch_GU"]
            anti_node_ref, anti_zone_ref = Rd["anti_node"], Rd["anti_zone"]
            prefw_node_ref, prefw_zone_ref = Rd["prefw_node"], Rd["prefw_zone"]
        if has_gpu:
            gmem_ref, gcnt_ref = Rd["gpu_mem"], Rd["gpu_cnt"]
            gpu0_ref, gpu_free_ref = Rd["gpu0_DN"], Rd["gpu_free"]
            gpu_take_ref, gpu_out_ref = Rd.get("gpu_take"), Rd["gpu_out"]
        if has_local:
            lvm_ref, dreq_ref, dneed_ref, dsz_ref = (
                Rd["lvm_req"], Rd["dev_req"], Rd["dev_need"], Rd["dev_sizes"])
            vgcap_ref, vg0_ref = Rd["vg_cap_VN"], Rd["vg0_VN"]
            devcap_ref, dev0_ref, media_ref = (
                Rd["dev_cap_DN"], Rd["dev0_DN"], Rd["dev_media_DN"])
            vg_free_ref, dev_free_ref = Rd["vg_free"], Rd["dev_free"]
            vg_out_ref, dev_out_ref = Rd["vg_out"], Rd["dev_out"]
        if has_ports:
            port_hu_ref, port_conf_hu_ref = Rd["port_HU"], Rd["port_conf_HU"]
            port_used_ref = Rd["port_used"]
        if has_na:
            na_ref = Rd["na_raw"]
        if has_tt:
            tt_ref = Rd["tt_raw"]
        if has_avoid:
            av_ref = Rd["avoid_raw"]
        alloc_ref, used0_ref = Rd["alloc_T"], Rd["used0_T"]
        static_ref, affm_ref, shraw_ref = (
            Rd["static_pass"], Rd["aff_mask"], Rd["share_raw"])
        # the one-hot blocks, or (zone_domains > 0) none
        zone_nz_ref, zone_zn_ref = Rd.get("zone_NZ"), Rd.get("zone_ZN")
        has_zone_ref = Rd["has_zone"]
        matches_ref, nodevalid_ref = Rd["matches_AU"], Rd["node_valid"]
        zone_id_ref = Rd.get("zone_id")  # [K, N] f32: a packed kernel's, or a compare-select one's
        chosen_ref, used_out_ref = Rd["chosen"], Rd["used_out"]
        used_ref, node_cnt_ref, zone_cnt_ref = (
            Rd["used"], Rd["node_cnt"], Rd["zone_cnt"])
        R, N = alloc_ref.shape
        U = static_ref.shape[0]
        Cs = sa_ref.shape[0]
        if has_interpod:
            Ti = ata_ref.shape[0]
            Tn = ana_ref.shape[0]
            Tp = pta_ref.shape[0]

        # --- the sublane axis. A per-scenario table of X rows is [X*SB, N]
        # (row x of scenario s at x*SB + s); with SB = 1 every helper below
        # is the plain [X, N] form, so a schedule compiles the scalar kernel
        def srows(ref, x):
            """Row x of a per-scenario table, for every scenario: [SB, W]."""
            if SB == 1:
                return ref[pl.ds(x, 1), :]
            start = x * SB if isinstance(x, int) else pl.multiple_of(x * SB, SB)
            return ref[pl.ds(start, SB), :]

        def set_srows(ref, x, value):
            ref[pl.ds(x * SB, SB), :] = value

        def add_outer(ref, col, rows, base=None):
            """Rows base..base+X (the whole table without a base) of a
            per-scenario table += col [X, 1] ⊗ rows [SB, W]: row x of
            scenario s gains col[x] * rows[s]."""
            X = col.shape[0]
            if SB == 1:
                at = slice(None) if base is None else pl.ds(base, X)
                ref[at, :] = ref[at, :] + col * rows
                return
            for x in range(X):
                at = pl.ds(((base or 0) + x) * SB, SB)
                # over the sublanes first, then the lanes: Mosaic broadcasts
                # a [1, 1] slice along one of the two at a time
                cx = jnp.zeros((SB, 1), jnp.float32) + col[x:x + 1, :]
                ref[at, :] = ref[at, :] + cx * rows

        def fill(dst, src):
            """Every scenario of the block starts from the shared table src."""
            if SB == 1:
                dst[:] = src[:]
                return
            for x in range(src.shape[0]):
                set_srows(dst, x, jnp.broadcast_to(src[pl.ds(x, 1), :], (SB, src.shape[1])))

        # reductions over the node axis: one per scenario, [SB, 1] (a scalar
        # when SB = 1). max/min/sum of counts are exact in any order
        if SB == 1:
            vmin, vmax, vsum = jnp.min, jnp.max, jnp.sum
        else:
            vmin = functools.partial(jnp.min, axis=-1, keepdims=True)
            vmax = functools.partial(jnp.max, axis=-1, keepdims=True)
            vsum = functools.partial(jnp.sum, axis=-1, keepdims=True)
            s_iota = jax.lax.broadcasted_iota(jnp.int32, (SB, 1), 0)
            lane_chunk = jax.lax.broadcasted_iota(jnp.int32, (SB, CHUNK), 1)

            def column(get, dtype):
                """[SB, 1] column of the block's SMEM scalars get(s)."""
                col = jnp.zeros((SB, 1), dtype)
                for s in range(SB):
                    col = jnp.where(s_iota == s, get(s).astype(dtype), col)
                return col

        def kron_row(m):
            """[1, X] -> [SB, X*SB], m[x] at (s, x*SB + s): a shared row of
            term weights against a per-scenario [X*SB, W] table in one
            exact dot. The identity when SB = 1."""
            if SB == 1:
                return m
            X = m.shape[1]
            lane = jax.lax.broadcasted_iota(jnp.int32, (X, X * SB), 1)
            row = jax.lax.broadcasted_iota(jnp.int32, (X, X * SB), 0)
            spread = ((lane >= row * SB) & (lane < row * SB + SB)).astype(jnp.float32)
            rep = _dot(m, spread)  # [1, X*SB]: m[x] on lanes x*SB .. x*SB+SB-1
            lane = jax.lax.broadcasted_iota(jnp.int32, (SB, X * SB), 1)
            sub = jax.lax.broadcasted_iota(jnp.int32, (SB, X * SB), 0)
            return jnp.where((lane & (SB - 1)) == sub, rep, 0.0)

        # grid = (scenario block, chunk): the carried state lives in scratch
        # that persists across the whole grid, so every block re-initializes
        # it at its first chunk
        @pl.when(pl.program_id(1) == 0)
        def _init():
            fill(used_ref, used0_ref)
            node_cnt_ref[:] = jnp.zeros_like(node_cnt_ref)
            zone_cnt_ref[:] = jnp.zeros_like(zone_cnt_ref)
            if has_interpod:
                anti_node_ref[:] = jnp.zeros_like(anti_node_ref)
                anti_zone_ref[:] = jnp.zeros_like(anti_zone_ref)
                prefw_node_ref[:] = jnp.zeros_like(prefw_node_ref)
                prefw_zone_ref[:] = jnp.zeros_like(prefw_zone_ref)
            if has_gpu:
                fill(gpu_free_ref, gpu0_ref)
            if has_local:
                fill(vg_free_ref, vg0_ref)
                fill(dev_free_ref, dev0_ref)
            if has_ports:
                port_used_ref[:] = jnp.zeros_like(port_used_ref)

        iota_n = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)
        iota_u = jax.lax.broadcasted_iota(jnp.int32, (U, 1), 0)
        valid_row = nodevalid_ref[:]  # [SB, N]
        ones_1n = jnp.ones((1, N), jnp.float32)

        A_rows = node_cnt_ref.shape[0] // SB
        Zk = zone_cnt_ref.shape[1]

        # a spread constraint's weight is its topology key's, per scenario
        if SB == 1:
            def key_weight(key):
                return kw_ref[0, key]
        else:
            kw_cols = [column(lambda s, k=k: kw_ref[s, k], jnp.float32) for k in range(n_zkeys + 1)]

            def key_weight(key):
                w = kw_cols[0]
                for k in range(1, n_zkeys + 1):
                    w = jnp.where(key == k, kw_cols[k], w)
                return w

        def pod_flag(ref, i):
            """This step's entry of a per-scenario pod stream (the block's
            SB streams lie CHUNK apart in its window)."""
            if SB == 1:
                return ref[i]
            return column(lambda s: ref[s * CHUNK + i], jnp.int32)

        def on_claim(claimed, part, value):
            """part(value) for a pod with the claim `claimed` tests, value as
            it is for one without: the open-local parts where the pod has
            nothing to place leave every value as it was. `claimed` reads
            the template's SMEM scalars, the same for every scenario of the
            block, so the branch is taken once a step for all of them."""
            return jax.lax.cond(claimed, part, lambda v: v, value)

        def _flag_row(flag_ref, n_rows):
            """Expand an SMEM int-flag table into a [1, n_rows] f32 vector
            (loop-invariant: built once, outside the pod loop)."""
            row = jnp.zeros((1, n_rows), jnp.float32)
            r_iota = jax.lax.broadcasted_iota(jnp.int32, (1, n_rows), 1)
            for g in range(n_rows):
                row = jnp.where(r_iota == g, jnp.float32(flag_ref[g]), row)
            return row

        def _flag_col(flag_ref, n_rows):
            col = jnp.zeros((n_rows, 1), jnp.float32)
            c_iota = jax.lax.broadcasted_iota(jnp.int32, (n_rows, 1), 0)
            for g in range(n_rows):
                col = jnp.where(c_iota == g, jnp.float32(flag_ref[g]), col)
            return col

        if has_interpod:
            g_key_row = _flag_row(agh_ref, n_anti)
            p_key_row = _flag_row(pgh_ref, n_pref)
            g_key_col = _flag_col(agh_ref, n_anti)
            p_key_col = _flag_col(pgh_ref, n_pref)

        def zone_read(k, counts):
            """Each node's count in its own domain under zone key k (0-based),
            [SB, N], from `counts` [SB, Zk] by domain. A key of at most
            `zone_domains` domains is read by compare-select over the nodes'
            `zone_id` row, each domain's count taken out of its lane by an
            exact sum; else by an exact dot with the key's one-hot block. A
            node without the key's label reads 0 either way."""
            if not zone_domains:
                return _dot(counts, zone_zn_ref[pl.ds(k * Zk, Zk), :])
            zid = zone_id_ref[pl.ds(k, 1), :]  # [1, N]
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, Zk), 1)
            out = jnp.zeros((SB, N), jnp.float32)
            for d in range(zone_domains):
                count = vsum(jnp.where(lane == d, counts, 0.0))  # [SB, 1] (a scalar when SB = 1)
                out = jnp.where(zid == d, jnp.broadcast_to(count, (SB, N)), out)
            return out

        def sel_cnt(sel, key):
            """Count of bound pods matching selector `sel` in the candidate
            node's domain under topology key index `key` (0 = hostname,
            1..K = zone keys; zone counts live in per-key row blocks)."""
            host_cnt = srows(node_cnt_ref, sel)  # [SB, N]
            k = jnp.maximum(key - 1, 0)
            zrow = srows(zone_cnt_ref, k * A_rows + sel)  # [SB, Zk]
            zone_gather = zone_read(k, zrow)
            has = has_zone_ref[pl.ds(k, 1), :]
            return jnp.where(key == 0, host_cnt, zone_gather), jnp.where(
                key == 0, ones_1n, has
            )

        def body(i, _):
            u = tmpl_ref[i]
            if big_u:
                # template tables live in HBM: DMA this step's row (for
                # [U, N] tables) / 128-lane column block (for [X, U] tables
                # — the aligned block containing column u is copied and the
                # single column extracted in VMEM by a one-hot dot) — all
                # copies in flight together, one wait. VMEM stays
                # independent of U. Both kinds are stored 3-D with the
                # indexed axis LEADING ([U, 1, N] rows, [U/128, X, 128]
                # column blocks): a DMA may index a leading axis freely,
                # while a 1-row or lane-offset slice of a 2-D table breaks
                # the (8, 128) HBM tiling and Mosaic refuses it.
                sems = u_scratch[-1]
                bufs = list(u_scratch[:-1])
                dma_state = {"k": 0}
                copies = []
                u_blk = (u // 128) * 128

                def _dma(ref, col):
                    k = dma_state["k"]
                    dma_state["k"] = k + 1
                    scratch = bufs[k]
                    src = ref.at[u // 128] if col else ref.at[u]
                    cp = pltpu.make_async_copy(src, scratch, sems.at[k])
                    cp.start()
                    copies.append(cp)
                    return scratch

                s_static = _dma(static_ref, False)
                s_aff = _dma(affm_ref, False)
                s_share = _dma(shraw_ref, False)
                s_match = _dma(matches_ref, True)
                s_na = _dma(na_ref, False) if has_na else None
                s_tt = _dma(tt_ref, False) if has_tt else None
                s_av = _dma(av_ref, False) if has_avoid else None
                if has_ports:
                    s_port = _dma(port_hu_ref, True)
                    s_portc = _dma(port_conf_hu_ref, True)
                if has_interpod:
                    s_antig = _dma(antig_ref, True)
                    s_gmatch = _dma(gmatch_ref, True)
                    s_prefg = _dma(prefg_ref, True)
                    s_pmatch = _dma(pmatch_ref, True)
                for cp in copies:
                    cp.wait()
                lane_oh = (
                    jax.lax.broadcasted_iota(jnp.int32, (128, 1), 0) == (u - u_blk)
                ).astype(jnp.float32)

                def col_of(scratch):  # [X, 128] block -> [X, 1] column u
                    return _dot(scratch[:], lane_oh)

                static_row = s_static[:]
            else:
                static_row = static_ref[pl.ds(u, 1), :]  # [1, N] (validity applied separately)
            if has_gpu and SB == 1:
                for d in range(n_gpu):  # SMEM outputs have no default value
                    gpu_take_ref[d, i] = jnp.float32(0.0)

            # --- NodeResourcesFit
            # dynamic gpu-count allocatable (Features.gc_dyn; the gpushare
            # Reserve rewrite, open-gpu-share.go:177-182): on device-bearing
            # nodes the gc_row alloc is the count of not-fully-used devices
            use_gc = has_gpu and gc_row >= 0
            if use_gc:
                gc_dyn_row = jnp.zeros((1, N), jnp.float32)
                gc_has_dev = jnp.zeros((1, N), jnp.float32)
                for d in range(n_gpu):
                    valid_d = (gpu0_ref[pl.ds(d, 1), :] > 0).astype(jnp.float32)
                    free_d = (srows(gpu_free_ref, d) > 0).astype(jnp.float32)
                    gc_dyn_row = gc_dyn_row + valid_d * free_d
                    gc_has_dev = jnp.maximum(gc_has_dev, valid_d)
            fit = ones_1n
            for r in range(R):
                req_r = req_ref[r, u]
                alloc_r = alloc_ref[pl.ds(r, 1), :]
                if use_gc and r == gc_row:
                    alloc_r = jnp.where(gc_has_dev > 0, gc_dyn_row, alloc_r)
                over = (srows(used_ref, r) + req_r > alloc_r).astype(jnp.float32)
                fit = fit * jnp.where(req_r > 0, 1.0 - over, 1.0)
            # node validity is a runtime row (NOT folded into static_pass) so
            # scenario sweeps can vary it without re-marshalling the tables
            feasible = static_row * fit * valid_row

            if has_ports:
                # NodePorts: any CONFLICTING port already used on the node
                # (wildcard-expanded template rows via one-hot matvec, or the
                # DMA'd column in big-U mode)
                if big_u:
                    my_ports = col_of(s_portc)  # [Hp, 1]
                else:
                    onehot_u_p = (iota_u == u).astype(jnp.float32)
                    my_ports = _dot(port_conf_hu_ref[:], onehot_u_p)  # [Hp, 1]
                conflicts = _dot(
                    kron_row(my_ports.reshape(1, -1)), (port_used_ref[:] > 0).astype(jnp.float32)
                )  # [SB, N]
                feasible = feasible * (conflicts == 0).astype(jnp.float32)

            if has_gpu:
                # Open-Gpu-Share filter: sum_d floor(free_d / mem) >= count
                gmem = gmem_ref[u]
                gcnt = gcnt_ref[u]
                # slots of this pod's size on every device, one [Gd, N] block:
                # the bind below packs from the same rows (nothing writes
                # gpu_free between the filter and the bind of a step)
                gpu_chunks = floor_div32(gpu_free_ref[:], jnp.maximum(gmem, 1.0))
                chunks_sum = jnp.zeros((1, N), jnp.float32)
                for d in range(n_gpu):
                    chunks_sum = chunks_sum + gpu_chunks[d * SB:(d + 1) * SB, :]
                gpu_ok = ((chunks_sum >= gcnt) & (gcnt > 0)).astype(jnp.float32)
                feasible = jnp.where(gmem > 0, feasible * gpu_ok, feasible)

            if has_local:
                # Open-Local filter: LVM fits the best VG; enough exclusive
                # devices of each media type
                lvm = lvm_ref[u]

                def vg_fits(feasible):
                    best_vg_free = jnp.full((1, N), -1e30, jnp.float32)
                    for v in range(n_vg_real):
                        best_vg_free = jnp.maximum(best_vg_free, srows(vg_free_ref, v))
                    return feasible * (best_vg_free >= lvm).astype(jnp.float32)

                feasible = on_claim(lvm > 0, vg_fits, feasible)
                # one-device-per-volume matching: the i-th largest volume
                # needs ≥ i+1 free fitting devices (common.go:290-349)
                for m in range(2):
                    for vi in range(n_dvol):
                        size = dsz_ref[m * n_dvol + vi, u]

                        def devs_fit(feasible):
                            cnt_fit = jnp.zeros((1, N), jnp.float32)
                            for d in range(n_dev_real):
                                free_d = srows(dev_free_ref, d)
                                media_d = media_ref[pl.ds(m * n_dev + d, 1), :]
                                cnt_fit = cnt_fit + media_d * ((free_d >= size) & (free_d > 0)).astype(jnp.float32)
                            return feasible * (cnt_fit >= (vi + 1)).astype(jnp.float32)

                        feasible = on_claim(size > 0, devs_fit, feasible)

            # --- PodTopologySpread
            aff_row = (s_aff[:] if big_u else affm_ref[pl.ds(u, 1), :]) * valid_row
            soft_raw = jnp.zeros((1, N), jnp.float32)
            ignored = jnp.zeros((1, N), jnp.float32)
            any_soft = jnp.float32(0.0)
            for c in range(Cs):
                active = sa_ref[c, u]
                skew = sk_ref[c, u]
                cnt, has_label = sel_cnt(ss_ref[c, u], sh_ref[c, u])
                activef = active == 1
                hardf = activef & (shard_ref[c, u] == 1)
                softf = activef & (shard_ref[c, u] == 0)

                elig = aff_row * has_label
                masked = jnp.where(elig > 0, cnt, jnp.float32(1e30))
                min_cnt = vmin(masked)
                ok = (cnt + sself_ref[c, u] - min_cnt <= skew) & (has_label > 0)
                feasible = jnp.where(hardf, feasible * ok.astype(jnp.float32), feasible)

                contrib = jnp.where(has_label > 0, cnt * key_weight(sh_ref[c, u]) + (skew - 1.0), 0.0)
                soft_raw = soft_raw + jnp.where(softf, contrib, 0.0)
                ignored = jnp.maximum(ignored, jnp.where(softf, 1.0 - has_label, 0.0))
                any_soft = jnp.maximum(any_soft, jnp.where(softf, 1.0, 0.0))

            ip_raw = jnp.zeros((1, N), jnp.float32)
            if has_interpod:
                if not big_u:
                    onehot_u_col = (iota_u == u).astype(jnp.float32)  # [U, 1]
                # incoming required anti-affinity: no matching pod in domain
                for t in range(Tn):
                    cnt, has_label = sel_cnt(ans_ref[t, u], anh_ref[t, u])
                    violated = (cnt > 0) & (has_label > 0)
                    feasible = jnp.where(
                        ana_ref[t, u] == 1, feasible * (1.0 - violated.astype(jnp.float32)), feasible
                    )
                # incoming required affinity: counts use the all-terms
                # conjunction selector (filtering.go:113-127). A node passes
                # when every term's topology label exists and every term's
                # domain count is positive, or via the bootstrap — global
                # count map empty AND full self-match AND labels present
                # (satisfyPodAffinity, filtering.go:347-374).
                at_all_ok = jnp.ones((1, N), jnp.float32)
                at_labels_ok = jnp.ones((1, N), jnp.float32)
                at_map_total = jnp.float32(0.0)
                at_self_all = jnp.float32(1.0)
                for t in range(Ti):
                    cnt, has_label = sel_cnt(ats_ref[t, u], ath_ref[t, u])
                    total_host = vsum(srows(node_cnt_ref, ats_ref[t, u]))
                    at_k = jnp.maximum(ath_ref[t, u] - 1, 0)
                    total_zone = vsum(srows(zone_cnt_ref, at_k * A_rows + ats_ref[t, u]))
                    total = jnp.where(ath_ref[t, u] == 0, total_host, total_zone)
                    activef = ata_ref[t, u] == 1
                    term_ok = ((cnt > 0) & (has_label > 0)).astype(jnp.float32)
                    at_all_ok = jnp.where(activef, at_all_ok * term_ok, at_all_ok)
                    at_labels_ok = jnp.where(
                        activef, at_labels_ok * (has_label > 0).astype(jnp.float32), at_labels_ok
                    )
                    at_map_total = at_map_total + jnp.where(activef, total, 0.0)
                    at_self_all = at_self_all * jnp.where(
                        activef, (atf_ref[t, u] > 0).astype(jnp.float32), 1.0
                    )
                at_bootstrap = ((at_map_total == 0.0) & (at_self_all > 0)).astype(jnp.float32)
                feasible = feasible * jnp.maximum(at_all_ok, at_labels_ok * at_bootstrap)
                # symmetric: existing pods' anti terms vs the incoming pod.
                # counts are non-negative, so "any matching term has pods in
                # my domain" == "match-weighted count sum > 0" — three dots
                # instead of per-term loops. Host-key domains always have
                # the label (applicable() enforces hostname-identity); zone
                # gathers give 0 on label-less nodes via the one-hot.
                if big_u:
                    my_gmatch = col_of(s_gmatch)
                else:
                    my_gmatch = _dot(gmatch_ref[:], onehot_u_col)
                m_row = my_gmatch.reshape(1, n_anti)
                m_host = m_row * (g_key_row == 0).astype(jnp.float32)
                sym_cnt = _dot(kron_row(m_host), anti_node_ref[:])
                for zk in range(n_zkeys):
                    m_k = m_row * (g_key_row == zk + 1).astype(jnp.float32)
                    sym_cnt = sym_cnt + zone_read(zk, _dot(kron_row(m_k), anti_zone_ref[:]))
                feasible = feasible * (1.0 - (sym_cnt > 0).astype(jnp.float32))
                # score: incoming preferred terms
                for t in range(Tp):
                    cnt, has_label = sel_cnt(pts_ref[t, u], pth_ref[t, u])
                    ip_raw = ip_raw + jnp.where(
                        pta_ref[t, u] == 1, cnt * ptw_ref[t, u] * has_label, 0.0
                    )
                # score: symmetric preferred/hard-affinity weights — same
                # three-dot contraction over the term axis
                if big_u:
                    my_pmatch = col_of(s_pmatch)
                else:
                    my_pmatch = _dot(pmatch_ref[:], onehot_u_col)
                pm_row = my_pmatch.reshape(1, n_pref)
                pm_host = pm_row * (p_key_row == 0).astype(jnp.float32)
                ip_raw = ip_raw + _dot(kron_row(pm_host), prefw_node_ref[:])
                for zk in range(n_zkeys):
                    pm_k = pm_row * (p_key_row == zk + 1).astype(jnp.float32)
                    ip_raw = ip_raw + zone_read(zk, _dot(kron_row(pm_k), prefw_zone_ref[:]))

            # --- scores
            cpu_req = cpu_nz_ref[u]
            mem_req = mem_nz_ref[u]
            alloc_cpu = alloc_ref[pl.ds(V.RES_CPU, 1), :]
            alloc_mem = alloc_ref[pl.ds(V.RES_MEMORY, 1), :]
            used_cpu = srows(used_ref, V.RES_CPU) + cpu_req
            used_mem = srows(used_ref, V.RES_MEMORY) + mem_req
            # every quotient of the step is taken in one stacked block
            # below (_div_rows): its operands are gathered here first
            cap_cpu = jnp.maximum(alloc_cpu, 1.0)
            cap_mem = jnp.maximum(alloc_mem, 1.0)
            quotients = [
                ((alloc_cpu - used_cpu) * MAX_SCORE, cap_cpu),
                ((alloc_mem - used_mem) * MAX_SCORE, cap_mem),
                (used_cpu, cap_cpu),
                (used_mem, cap_mem),
            ]

            share_row = s_share[:] if big_u else shraw_ref[pl.ds(u, 1), :]
            if use_gc:
                # add back the gpu-count share with the Reserve-updated
                # value (share_raw zeroed that column on device-bearing
                # nodes; algo.Share semantics, greed.go:70-83)
                gc_req = req_ref[gc_row, u]
                declared = (alloc_ref[pl.ds(gc_row, 1), :] > 0).astype(jnp.float32)
                avail = gc_dyn_row - gc_req
                sh = jnp.where(
                    avail == 0,
                    jnp.where(gc_req == 0, 0.0, 1.0),
                    div32(gc_req, jnp.where(avail == 0, 1.0, avail)),
                )
                sh = jnp.where(
                    (declared > 0) & (gc_has_dev > 0), jnp.maximum(sh, 0.0), 0.0
                ) * MAX_SCORE
                share_row = jnp.maximum(share_row, jnp.where(gc_req > 0, sh, 0.0))
            feas_b = feasible > 0
            lo = vmin(jnp.where(feas_b, share_row, jnp.float32(1e30)))
            hi = vmax(jnp.where(feas_b, share_row, jnp.float32(-1e30)))
            rng = hi - lo
            quotients.append(((share_row - lo) * MAX_SCORE, rng))

            scored = feas_b & (ignored == 0)
            smn = vmin(jnp.where(scored, soft_raw, jnp.float32(1e30)))
            smx = vmax(jnp.where(scored, soft_raw, jnp.float32(-1e30)))
            quotients.append((MAX_SCORE * (smx + smn - soft_raw), jnp.maximum(smx, 1.0)))
            if has_interpod:
                # interpod_score normalization: min/max seeded with 0
                ip_masked = jnp.where(feas_b, ip_raw, 0.0)
                ip_hi = jnp.maximum(vmax(ip_masked), 0.0)
                ip_lo = jnp.minimum(vmin(ip_masked), 0.0)
                ip_rng = ip_hi - ip_lo
                quotients.append((MAX_SCORE * (ip_raw - ip_lo), jnp.maximum(ip_rng, 1.0)))
            if has_na:
                # NodeAffinity preferred-term weights, max-normalized over
                # the feasible set (DefaultNormalizeScore)
                na_row = s_na[:] if big_u else na_ref[pl.ds(u, 1), :]
                na_max = vmax(jnp.where(feas_b, na_row, 0.0))
                quotients.append((na_row * MAX_SCORE, jnp.maximum(na_max, 1.0)))
            if has_tt:
                # TaintToleration: intolerable PreferNoSchedule counts,
                # reverse-normalized
                tt_row = s_tt[:] if big_u else tt_ref[pl.ds(u, 1), :]
                tt_max = vmax(jnp.where(feas_b, tt_row, 0.0))
                quotients.append((tt_row * MAX_SCORE, jnp.maximum(tt_max, 1.0)))
            rtcr_req = {}
            for col in rtcr_extra:
                requested = srows(used_ref, col) + req_ref[col, u]
                alloc_c = alloc_ref[pl.ds(col, 1), :]
                rtcr_req[col] = (requested, alloc_c)
                quotients.append(((alloc_c - requested) * MAX_SCORE, jnp.maximum(alloc_c, 1.0)))
            quotients = _div_rows(quotients, (SB, N))
            rtcr_q = dict(zip(rtcr_extra, quotients[len(quotients) - len(rtcr_extra):]))
            quotients = iter(quotients[:len(quotients) - len(rtcr_extra)])

            q_cpu, q_mem = next(quotients), next(quotients)
            l_cpu = jnp.where((alloc_cpu == 0) | (used_cpu > alloc_cpu), 0.0, q_cpu)
            l_mem = jnp.where((alloc_mem == 0) | (used_mem > alloc_mem), 0.0, q_mem)
            least = (l_cpu + l_mem) / 2.0
            cpu_frac = next(quotients)
            mem_frac = next(quotients)
            balanced = jnp.where(
                (cpu_frac >= 1.0) | (mem_frac >= 1.0),
                0.0,
                (1.0 - jnp.abs(cpu_frac - mem_frac)) * MAX_SCORE,
            )
            share_norm = jnp.where(rng > 0, next(quotients), 0.0)
            spread_norm = jnp.where(smx <= 0, MAX_SCORE, next(quotients))
            spread_norm = jnp.where(ignored > 0, 0.0, spread_norm)
            spread_norm = jnp.where(any_soft > 0, spread_norm, 0.0)
            if has_interpod:
                ip_norm = jnp.where(ip_rng > 0, next(quotients), 0.0)

            # the weighted sum is taken in kernels.score_parts' order
            # (balanced, least, requested-to-capacity, node affinity, taints,
            # inter-pod, spread, share, open-local, prefer-avoid): float32
            # addition does not associate, and a sum taken in another order
            # leaves the XLA scan's by an ulp wherever three terms differ over
            # the nodes. It commutes: least + balanced is the scan's
            # 0 + balanced + least
            if cfg.w_least and cfg.w_balanced:
                score = weighted(cfg.w_least, least) + weighted(cfg.w_balanced, balanced)
            elif cfg.w_least or cfg.w_balanced:
                score = weighted(cfg.w_least, least) if cfg.w_least else weighted(cfg.w_balanced, balanced)
            else:
                score = jnp.zeros((SB, N), jnp.float32)
            if rtcr_cols:
                per_resource = []
                for col, w in cfg.rtcr_resources:
                    if col == V.RES_CPU:
                        util = rtcr_utilization(q_cpu, used_cpu, alloc_cpu)
                    elif col == V.RES_MEMORY:
                        util = rtcr_utilization(q_mem, used_mem, alloc_mem)
                    elif col >= 0:
                        util = rtcr_utilization(rtcr_q[col], *rtcr_req[col])
                    else:
                        util = jnp.full((SB, N), MAX_SCORE, jnp.float32)
                    per_resource.append((w, rtcr_shape_score(util, cfg.rtcr_shape)))
                score = score + weighted(cfg.w_rtcr, rtcr_mean(per_resource))
            if has_na:
                na_q = next(quotients)
                if cfg.w_node_affinity:
                    score = score + weighted(cfg.w_node_affinity, jnp.where(na_max > 0, na_q, na_row))
            if has_tt:
                tt_q = next(quotients)
                if cfg.w_taint_toleration:
                    score = score + weighted(
                        cfg.w_taint_toleration, jnp.where(tt_max > 0, MAX_SCORE - tt_q, MAX_SCORE)
                    )
            if has_interpod and cfg.w_interpod:
                score = score + weighted(cfg.w_interpod, ip_norm)
            if cfg.w_spread:
                score = score + weighted(cfg.w_spread, spread_norm)
            if cfg.w_simon + cfg.w_gpu_share:
                score = score + weighted(cfg.w_simon + cfg.w_gpu_share, share_norm)
            if has_local and cfg.w_local:
                # Open-Local binpack score (local_score in kernels.py):
                # mean over units of used/capacity × 10, min-max normalized.
                # A pod with no claim scores 0 on every node, so its range is
                # 0 and the term adds nothing
                lvm = lvm_ref[u]
                big_f = jnp.float32(1e30)

                def vg_part(parts):
                    best_free = jnp.full((1, N), big_f, jnp.float32)
                    best_cap = jnp.zeros((1, N), jnp.float32)
                    for v in range(n_vg_real):
                        free_v = srows(vg_free_ref, v)
                        fits_v = free_v >= lvm
                        better = fits_v & (free_v < best_free)
                        best_free = jnp.where(better, free_v, best_free)
                        best_cap = jnp.where(better, vgcap_ref[pl.ds(v, 1), :], best_cap)
                    return parts + jnp.where(best_free < big_f, div32(lvm, jnp.maximum(best_cap, 1.0)), 0.0)

                def local_term(score):
                    parts = on_claim(lvm > 0, vg_part, jnp.zeros((SB, N), jnp.float32))
                    count = jnp.where(lvm > 0, 1.0, 0.0)
                    for m in range(2):
                        size = dreq_ref[m, u]
                        need = dneed_ref[m, u]

                        def dev_part(parts):
                            first_cap = jnp.full((1, N), big_f, jnp.float32)
                            for d in range(n_dev_real):
                                free_d = srows(dev_free_ref, d)
                                media_d = media_ref[pl.ds(m * n_dev + d, 1), :]
                                fitting = (media_d > 0) & (free_d >= size) & (free_d > 0)
                                first_cap = jnp.where(
                                    fitting, jnp.minimum(first_cap, devcap_ref[pl.ds(d, 1), :]), first_cap
                                )
                            return parts + div32(need * size, jnp.maximum(first_cap, 1.0))

                        parts = on_claim(size > 0, dev_part, parts)
                        count = count + jnp.where(size > 0, need, 0.0)
                    local_raw = jnp.where(count > 0, div32(parts, jnp.maximum(count, 1.0)) * 10.0, 0.0)
                    l_lo = vmin(jnp.where(feas_b, local_raw, big_f))
                    l_hi = vmax(jnp.where(feas_b, local_raw, -big_f))
                    l_rng = l_hi - l_lo
                    return score + weighted(
                        cfg.w_local, jnp.where(l_rng > 0, div32((local_raw - l_lo) * MAX_SCORE, l_rng), 0.0)
                    )

                any_claim = (lvm > 0) | (dreq_ref[0, u] > 0) | (dreq_ref[1, u] > 0)
                score = on_claim(any_claim, local_term, score)
            if has_avoid and cfg.w_prefer_avoid:
                # NodePreferAvoidPods (w=10000, no NormalizeScore): raw
                # 0/100 static table, same shape class as na_raw
                av_row = s_av[:] if big_u else av_ref[pl.ds(u, 1), :]
                score = score + weighted(cfg.w_prefer_avoid, av_row)

            # --- selectHost: lowest index among maxima — Mosaic's argmax
            # breaks ties by HIGHEST index, diverging from the XLA scan
            masked_score = jnp.where(feas_b, score, jnp.float32(NEG))
            mx_score = vmax(masked_score)
            best = vmin(jnp.where(masked_score == mx_score, iota_n, N)).astype(jnp.int32)
            any_feasible = vmax(feasible) > 0
            sel_choice = jnp.where(any_feasible, best, jnp.int32(-1))
            is_forced = pod_flag(forced_ref, i) == 1
            pin_u = pin_ref[u]
            choice = jnp.where(is_forced, jnp.where(pin_u >= 0, pin_u, -1), sel_choice)
            do_bind = (pod_flag(valid_ref, i) == 1) & (choice >= 0)
            if SB == 1:
                chosen_ref[i] = jnp.where(do_bind, choice, -1)
            else:  # the block's [SB, CHUNK] window in VMEM: column i
                chosen_ref[:] = jnp.where(lane_chunk == i, jnp.where(do_bind, choice, -1), chosen_ref[:])

            # --- bind update
            def _bind(onehot, zone_rows):
                """The chosen node's one-hot [SB, N] (a zero row where a
                scenario binds nothing) and zone_rows(k), the [SB, Z]
                one-hot of its zone under key k."""
                if SB == 1:
                    iota_r = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
                    req_col = jnp.zeros((R, 1), jnp.float32)
                    for r in range(R):  # static unroll; .at[] would lower to scatter
                        req_col = jnp.where(iota_r == r, req_ref[r, u], req_col)
                    used_ref[:] = used_ref[:] + req_col * onehot
                else:
                    for r in range(R):
                        set_srows(used_ref, r, srows(used_ref, r) + req_ref[r, u] * onehot)

                if big_u:
                    m_col = col_of(s_match)  # [A, 1]
                else:
                    onehot_u = (iota_u == u).astype(jnp.float32)  # [U, 1]
                    m_col = _dot(matches_ref[:], onehot_u)
                zrow_k = [zone_rows(zk) for zk in range(n_zkeys)]
                add_outer(node_cnt_ref, m_col, onehot)
                for zk in range(n_zkeys):
                    add_outer(zone_cnt_ref, m_col, zrow_k[zk], base=zk * A_rows)
                if has_ports:
                    p_col = col_of(s_port) if big_u else _dot(port_hu_ref[:], onehot_u)
                    add_outer(port_used_ref, p_col, onehot)
                if has_gpu:
                    # device packing on the chosen node (computed for all
                    # nodes, applied via the one-hot): single-GPU tightest
                    # fit, multi-GPU greedy with reuse (gpunodeinfo.go)
                    gmem = gmem_ref[u]
                    gcnt = gcnt_ref[u]
                    best_free = jnp.full((1, N), 1e30, jnp.float32)
                    for d in range(n_gpu):
                        free_d = srows(gpu_free_ref, d)
                        best_free = jnp.where(free_d >= gmem, jnp.minimum(best_free, free_d), best_free)
                    assigned = jnp.zeros((1, N), jnp.float32)
                    cum = jnp.zeros((1, N), jnp.float32)
                    for d in range(n_gpu):
                        free_d = srows(gpu_free_ref, d)
                        fits_d = (free_d >= gmem).astype(jnp.float32)
                        take_tight = fits_d * (free_d == best_free).astype(jnp.float32) * (1.0 - jnp.minimum(assigned, 1.0))
                        assigned = assigned + take_tight
                        chunks_d = gpu_chunks[d * SB:(d + 1) * SB, :]
                        take_greedy = jnp.clip(gcnt - cum, 0.0, chunks_d)
                        cum = cum + chunks_d
                        take_d = jnp.where(gcnt == 1, take_tight, take_greedy)
                        take_d = jnp.where(gmem > 0, take_d, 0.0)
                        set_srows(gpu_free_ref, d, free_d - take_d * gmem * onehot)
                        if SB == 1:
                            gpu_take_ref[d, i] = jnp.sum(take_d * onehot)
                if has_local:
                    lvm = lvm_ref[u]

                    @pl.when(lvm > 0)
                    def _():
                        # LVM: tightest-fitting VG (first among equals)
                        best_free = jnp.full((1, N), jnp.float32(1e30), jnp.float32)
                        for v in range(n_vg_real):
                            free_v = srows(vg_free_ref, v)
                            best_free = jnp.where(free_v >= lvm, jnp.minimum(best_free, free_v), best_free)
                        taken_vg = jnp.zeros((1, N), jnp.float32)
                        for v in range(n_vg_real):
                            free_v = srows(vg_free_ref, v)
                            take_v = (
                                (free_v >= lvm) & (free_v == best_free)
                            ).astype(jnp.float32) * (1.0 - jnp.minimum(taken_vg, 1.0))
                            taken_vg = taken_vg + take_v
                            set_srows(vg_free_ref, v, free_v - lvm * take_v * onehot)

                    # exclusive devices: one device per volume, smallest
                    # volume onto the smallest-capacity fitting free device
                    # (common.go:290-349; ties by lowest device index) —
                    # must mirror the XLA bind exactly. A device a smaller
                    # volume took in this step is free 0 on the chosen node
                    # after its write, which fits no volume, so it is not
                    # taken twice there; elsewhere the one-hot writes nothing
                    for m in range(2):
                        for vi in reversed(range(n_dvol)):  # ascending sizes
                            size = dsz_ref[m * n_dvol + vi, u]

                            @pl.when(size > 0)
                            def _():
                                def cand(d):
                                    free_d = srows(dev_free_ref, d)
                                    media_d = media_ref[pl.ds(m * n_dev + d, 1), :]
                                    return free_d, (media_d > 0) & (free_d >= size) & (free_d > 0)

                                best_cap = jnp.full((1, N), jnp.float32(1e30), jnp.float32)
                                for d in range(n_dev_real):
                                    best_cap = jnp.where(
                                        cand(d)[1], jnp.minimum(best_cap, devcap_ref[pl.ds(d, 1), :]), best_cap
                                    )
                                assigned = jnp.zeros((1, N), jnp.float32)
                                for d in range(n_dev_real):
                                    free_d, cand_d = cand(d)
                                    take_d = (
                                        cand_d & (devcap_ref[pl.ds(d, 1), :] == best_cap)
                                    ).astype(jnp.float32) * (1.0 - jnp.minimum(assigned, 1.0))
                                    assigned = assigned + take_d
                                    set_srows(dev_free_ref, d, free_d * (1.0 - take_d * onehot))
                if has_interpod:
                    a_col = col_of(s_antig) if big_u else _dot(antig_ref[:], onehot_u)
                    add_outer(anti_node_ref, a_col, onehot)
                    for zk in range(n_zkeys):
                        key_mask = (g_key_col == zk + 1).astype(jnp.float32)
                        add_outer(anti_zone_ref, a_col * key_mask, zrow_k[zk])
                    p_col = col_of(s_prefg) if big_u else _dot(prefg_ref[:], onehot_u)
                    add_outer(prefw_node_ref, p_col, onehot)
                    for zk in range(n_zkeys):
                        key_mask = (p_key_col == zk + 1).astype(jnp.float32)
                        add_outer(prefw_zone_ref, p_col * key_mask, zrow_k[zk])

            def zone_rows_of(onehot):
                """zone_rows(k) of the chosen nodes' one-hot [SB, N]: the
                one-hot of each chosen node's zone id (-1: none, or no bind),
                read by an exact reduction over the node lanes."""
                iota_z = jax.lax.broadcasted_iota(jnp.int32, (1, Zk), 1)

                def zone_rows(zk):
                    zid = vsum(onehot * (zone_id_ref[pl.ds(zk, 1), :] + 1.0)) - 1.0  # [SB, 1]
                    return (iota_z.astype(jnp.float32) == zid).astype(jnp.float32)

                return zone_rows

            if SB == 1:
                @pl.when(do_bind)
                def _():
                    c = jnp.maximum(choice, 0)
                    onehot = (iota_n == c).astype(jnp.float32)
                    if zone_domains:
                        _bind(onehot, zone_rows_of(onehot))
                        return
                    # the chosen node's [1, Zk] zone one-hot under each key —
                    # read from the 3-D [K, N, Z] table so every key's row
                    # sits at lane offset 0 (a lane-offset slice can't
                    # broadcast)
                    _bind(onehot, lambda zk: zone_nz_ref[zk, pl.ds(c, 1), :])
            else:
                # every scenario's bind at once: a scenario that binds
                # nothing has a zero one-hot row, and every update adds its
                # product (or, for a device, scales by 1 - it)
                onehot = ((iota_n == choice) & do_bind).astype(jnp.float32)  # [SB, N]
                _bind(onehot, zone_rows_of(onehot))
            return 0

        jax.lax.fori_loop(0, tmpl_ref.shape[0], body, 0)
        used_out_ref[:] = used_ref[:]
        if has_gpu:
            gpu_out_ref[:] = gpu_free_ref[:]
        if has_local:
            vg_out_ref[:] = vg_free_ref[:]
            dev_out_ref[:] = dev_free_ref[:]

    return kernel


# what selects the generated kernel; everything else run_fast_scan reads
# comes from the shapes of its traced arguments
_STATIC = ("has_interpod", "has_gpu", "has_local", "has_ports", "has_na", "has_tt",
           "has_avoid", "interpret", "big_u", "gc_row", "sublanes", "config", "n_vg_real", "n_dev_real",
           "zone_domains")


@functools.partial(jax.jit, static_argnames=_STATIC)
def run_fast_scan(
    fi: FastInputs,
    tmpl_ids,
    pod_valid,
    forced,
    has_interpod: bool,
    has_gpu: bool,
    has_local: bool = False,
    has_ports: bool = False,
    has_na: bool = False,
    has_tt: bool = False,
    has_avoid: bool = False,
    interpret: bool = False,
    big_u: bool = False,
    gc_row: int = -1,
    sublanes: int = 1,
    config=None,
    n_vg_real: Optional[int] = None,
    n_dev_real: Optional[int] = None,
    zone_domains: int = 0,
):
    """Execute the megakernel over S scenarios in ONE dispatch: tmpl_ids is
    [P] (P a multiple of CHUNK, shared), pod_valid/forced are [S, P],
    ``fi.node_valid`` is [S, 1, N] and ``fi.key_weight`` [S, K+1]; every
    other table is shared. A plain schedule is S = 1. `sublanes` (SB, 1 or
    8) scenarios share each kernel step, one a sublane: the grid is
    (S / SB, P / CHUNK), and S must be a multiple of SB (fastpath.sweep pads
    a packed sweep with scenarios that have no valid node and no valid pod).
    Returns (chosen [S, P] i32, used_final [S, R, N], gpu_take [S, P, Gd],
    gpu_final [S, Gd, N], vg_final [S, Vg, N], dev_final [S, Dv, N]); a
    packed run (SB > 1) writes no device takes and returns None for
    gpu_take.

    `big_u` keeps the [U, N] / [X, U] template tables in HBM and DMAs one
    row/column per pod step into VMEM scratch — VMEM use then no longer
    scales with U, lifting the template cap (fastpath.applicable). One DMA
    a step serves every scenario of a block.

    This is the one entry to the kernel and it is jitted (the XLA module is
    `jit_run_fast_scan`): `fi` and the three pod streams are traced, the
    flags of `_STATIC` are static, so a process traces and lowers the kernel
    once per signature (argument shapes × flags) and every later call of
    that signature is a cache lookup, a transfer of the streams and an
    enqueue. The casts and layout changes below and the normalisation of the
    outputs are part of the same program. `run_fast_scan.__wrapped__` is the
    plain function (the tests compare the two).

    `n_vg_real` / `n_dev_real` are the VG and device rows a node really has
    (None: every row of `fi.vg0_VN` / `fi.dev0_DN`); the open-local block of
    the `has_local` variant walks those alone, the tables stay padded.

    `zone_domains` (at most kernels.SELECT_DOMAINS) is the most domains a
    zone key of the cluster has where the kernel reads each node's zone
    counts by compare-select over ``fi.zone_id`` (the one-hot blocks
    ``fi.zone_NZ`` / ``fi.zone_ZN`` are then not read, and may be empty);
    0 reads them through the one-hot blocks."""
    SB = sublanes
    assert SB in (1, 8), SB
    P = tmpl_ids.shape[0]
    assert P % CHUNK == 0, P
    S = pod_valid.shape[0]
    assert pod_valid.shape == forced.shape == (S, P), (pod_valid.shape, forced.shape)
    assert S % SB == 0, (S, SB)
    B = S // SB
    R, N = fi.alloc_T.shape
    assert fi.node_valid.shape == (S, 1, N), fi.node_valid.shape
    A = fi.matches_AU.shape[0]
    K = fi.has_zone.shape[0]  # number of non-hostname topology keys (>= 1)
    assert fi.key_weight.shape == (S, K + 1), fi.key_weight.shape
    Z = fi.zone_NZ.shape[2]
    G = fi.antig_GU.shape[0]
    Gp = fi.prefg_GU.shape[0]
    Gd = fi.gpu0_DN.shape[0]
    Vg = fi.vg0_VN.shape[0]
    Dv = fi.dev0_DN.shape[0]
    Hp = fi.port_HU.shape[0]
    n_chunks = P // CHUNK
    grid = (B, n_chunks)

    smem = lambda: pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = lambda: pl.BlockSpec(memory_space=pltpu.VMEM)
    # big-U tables are pinned to HBM (not ANY): the point of the mode is
    # that they stay out of VMEM even when one happens to fit
    anyspace = lambda: pl.BlockSpec(memory_space=pltpu.HBM)
    # per-scenario pod streams are FLAT with 1-D blocks of SB·CHUNK, a
    # block's SB streams CHUNK apart: a [S, P] array blocked (1, CHUNK)
    # breaks the TPU rule that a block's second-minor dim is a multiple of 8
    # or the whole axis (which is why jax.vmap over the pallas_call never
    # lowered)
    shared_stream = lambda: pl.BlockSpec((CHUNK,), lambda b, i: (i,), memory_space=pltpu.SMEM)
    scen_stream = lambda: pl.BlockSpec(
        (SB * CHUNK,), lambda b, i: (b * n_chunks + i,), memory_space=pltpu.SMEM
    )

    def flat_streams(arr):  # [S, P] -> [B, chunk, SB, CHUNK], flat
        if SB == 1:
            return arr.reshape(S * P)
        return arr.reshape(B, SB, n_chunks, CHUNK).transpose(0, 2, 1, 3).reshape(S * P)

    def per_block(space, *tail):  # [B, *tail] → one block's [*tail]
        return pl.BlockSpec(
            (pl.Squeezed(), *tail), lambda b, i: (b,) + (0,) * len(tail), memory_space=space
        )

    def unpack(out, X):  # [B, X*SB, N] (row x*SB + s) → [S, X, N]
        if SB == 1:
            return out
        return out.reshape(B, X, SB, N).transpose(0, 2, 1, 3).reshape(S, X, N)

    _I32 = {"tmpl", "valid", "forced", "pin", "spr_active", "spr_key", "spr_sel",
            "spr_hard", "at_active", "at_key", "at_sel", "an_active", "an_key",
            "an_sel", "pt_active", "pt_key", "pt_sel", "anti_g_key", "prefg_key"}
    # [X, U] tables whose big-U DMA copies an aligned 128-lane column block
    # (U padded to a 128 multiple so the last block is whole)
    _COL_TABLES = {"matches_AU", "port_HU", "port_conf_HU",
                   "antig_GU", "gmatch_GU", "prefg_GU", "pmatch_GU"}
    # 2-D SMEM scalar tables are stored TRANSPOSED ([X, U], U minor): an
    # SMEM array's minor dim pads to 128 lanes, so the natural [U, X] layout
    # with X ≤ 8 would cost 128/X× the memory — fatal at big U (a [2048, 2]
    # table would pad to 1 MB, the whole SMEM)
    _SMEM_T = {"req", "spr_active", "spr_key", "spr_sel", "spr_skew",
               "spr_hard", "spr_self",
               "at_active", "at_key", "at_sel", "at_self",
               "an_active", "an_key", "an_sel",
               "pt_active", "pt_key", "pt_sel", "pt_w",
               "dev_req", "dev_need", "dev_sizes"}
    layout = _input_layout(
        has_interpod, has_gpu, has_local, has_ports, has_na, has_tt, has_avoid, big_u, SB > 1, zone_domains > 0
    )
    in_specs, args = [], []
    for name, kind in layout:
        if kind == "stream":
            src = {"tmpl": tmpl_ids, "valid": pod_valid, "forced": forced}[name]
        elif name == "zone_id" and not zone_domains:
            # each node's zone index under each key, -1 where it has no label
            # (derived from the blocks here, so a packed kernel that reads
            # them lowers as it did before `fi.zone_id`)
            zone_NZ = jnp.asarray(fi.zone_NZ, jnp.float32)
            src = jnp.sum(zone_NZ * jnp.arange(1, Z + 1, dtype=jnp.float32), axis=-1) - 1.0
        else:
            src = getattr(fi, name)
        arr = jnp.asarray(src, jnp.int32 if name in _I32 else jnp.float32)
        if name in _SMEM_T:
            arr = jnp.swapaxes(arr, -1, -2)
        if kind == "any" and name in _COL_TABLES:
            # [X, U] → [U/128, X, 128]: the DMA indexes the leading block axis
            arr = jnp.pad(arr, ((0, 0), (0, (-arr.shape[1]) % 128)))
            arr = arr.reshape(arr.shape[0], -1, 128).transpose(1, 0, 2)
        elif kind == "any":
            arr = arr[:, None, :]  # [U, N] → [U, 1, N]: the DMA indexes the row axis
        if name == "tmpl":
            spec = shared_stream()
        elif kind == "stream":
            arr, spec = flat_streams(arr), scen_stream()
        elif name in ("node_valid", "key_weight"):  # the per-scenario tables
            arr = arr.reshape(B, SB, arr.shape[-1])
            spec = per_block({"smem": pltpu.SMEM, "vmem": pltpu.VMEM}[kind], SB, arr.shape[-1])
        else:
            spec = {"smem": smem, "vmem": vmem, "any": anyspace}[kind]()
        in_specs.append(spec)
        args.append(arr)

    # outputs: feature-gated, like the inputs. gpu_take is [Gd, S·P] (device
    # rows × pod lanes): an SMEM window's minor dim pads to 128 lanes, so the
    # natural [P, Gd] layout would burn 1 MB of the chip's 1 MB SMEM on
    # 8-lane rows — transposed, the window is [Gd, CHUNK] = 32 KB. A packed
    # block writes its chosen nodes as [SB, CHUNK] VMEM windows, a column a
    # step, and no gpu_take (fastpath.sweep reads neither takes nor devices)
    if SB == 1:
        out_shape = [jax.ShapeDtypeStruct((S * P,), jnp.int32)]
        out_specs = [scen_stream()]
    else:
        out_shape = [jax.ShapeDtypeStruct((S, P), jnp.int32)]
        out_specs = [pl.BlockSpec((SB, CHUNK), lambda b, i: (b, i), memory_space=pltpu.VMEM)]
    out_shape += [jax.ShapeDtypeStruct((B, R * SB, N), jnp.float32)]
    out_specs += [per_block(pltpu.VMEM, R * SB, N)]
    if has_gpu:
        if SB == 1:
            out_shape += [jax.ShapeDtypeStruct((Gd, S * P), jnp.float32)]
            out_specs += [pl.BlockSpec((Gd, CHUNK), lambda b, i: (0, b * n_chunks + i),
                                       memory_space=pltpu.SMEM)]
        out_shape += [jax.ShapeDtypeStruct((B, Gd * SB, N), jnp.float32)]
        out_specs += [per_block(pltpu.VMEM, Gd * SB, N)]
    if has_local:
        out_shape += [jax.ShapeDtypeStruct((B, Vg * SB, N), jnp.float32),
                      jax.ShapeDtypeStruct((B, Dv * SB, N), jnp.float32)]
        out_specs += [per_block(pltpu.VMEM, Vg * SB, N), per_block(pltpu.VMEM, Dv * SB, N)]

    scratch = [pltpu.VMEM((R * SB, N), jnp.float32),
               pltpu.VMEM((A * SB, N), jnp.float32),
               pltpu.VMEM((K * A * SB, Z), jnp.float32)]
    if has_interpod:
        scratch += [pltpu.VMEM((G * SB, N), jnp.float32),
                    pltpu.VMEM((G * SB, Z), jnp.float32),
                    pltpu.VMEM((Gp * SB, N), jnp.float32),
                    pltpu.VMEM((Gp * SB, Z), jnp.float32)]
    if has_gpu:
        scratch += [pltpu.VMEM((Gd * SB, N), jnp.float32)]
    if has_local:
        scratch += [pltpu.VMEM((Vg * SB, N), jnp.float32),
                    pltpu.VMEM((Dv * SB, N), jnp.float32)]
    if has_ports:
        scratch += [pltpu.VMEM((Hp * SB, N), jnp.float32)]

    if big_u:
        # per-step scratch: rows [1, N] for the [U, N] tables, 128-lane
        # column blocks [X, 128] for the [X, U] tables — order must match
        # the kernel's _dma calls
        u_scratch = [pltpu.VMEM((1, N), jnp.float32)] * 3  # static, affm, shraw
        u_scratch.append(pltpu.VMEM((A, 128), jnp.float32))  # matches block
        if has_na:
            u_scratch.append(pltpu.VMEM((1, N), jnp.float32))
        if has_tt:
            u_scratch.append(pltpu.VMEM((1, N), jnp.float32))
        if has_avoid:
            u_scratch.append(pltpu.VMEM((1, N), jnp.float32))
        if has_ports:
            u_scratch += [pltpu.VMEM((Hp, 128), jnp.float32)] * 2
        if has_interpod:
            u_scratch += [
                pltpu.VMEM((G, 128), jnp.float32),
                pltpu.VMEM((G, 128), jnp.float32),
                pltpu.VMEM((Gp, 128), jnp.float32),
                pltpu.VMEM((Gp, 128), jnp.float32),
            ]
        u_scratch.append(pltpu.SemaphoreType.DMA((len(u_scratch),)))
        scratch += u_scratch

    out = pl.pallas_call(
        _make_kernel(
            has_interpod, has_gpu, has_local, has_ports, has_na, has_tt, has_avoid,
            G, Gp, Gd, Vg, Dv, fi.dev_sizes.shape[1] // 2, big_u, K, gc_row, SB, config,
            n_vg_real, n_dev_real, zone_domains,
        ),
        grid=grid,
        out_shape=tuple(out_shape),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        scratch_shapes=scratch,
        # both axes carry state through scratch: strictly sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(*args)

    # normalize to the fixed 6-tuple callers expect — absent features report
    # their initial state / zero takes
    res = list(out)
    chosen, used_T = res[0].reshape(S, P), unpack(res[1], R)
    idx = 2
    if has_gpu:
        gpu_take = None
        if SB == 1:
            gpu_take = res[idx].reshape(Gd, S, P).transpose(1, 2, 0)
            idx += 1
        gpu_T = unpack(res[idx], Gd)
        idx += 1
    else:
        gpu_take = jnp.zeros((S, P, Gd), jnp.float32) if SB == 1 else None
        gpu_T = jnp.broadcast_to(jnp.asarray(fi.gpu0_DN, jnp.float32), (S, Gd, N))
    if has_local:
        vg_T = unpack(res[idx], Vg)
        dev_T = unpack(res[idx + 1], Dv)
    else:
        vg_T = jnp.broadcast_to(jnp.asarray(fi.vg0_VN, jnp.float32), (S, Vg, N))
        dev_T = jnp.broadcast_to(jnp.asarray(fi.dev0_DN, jnp.float32), (S, Dv, N))
    return chosen, used_T, gpu_take, gpu_T, vg_T, dev_T
