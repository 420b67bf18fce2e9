"""The Pallas megakernel's envelope and input marshalling.

`why_not()` is the kernel's envelope (`ops/pallas_scan.run_fast_scan`'s
feature subset, table sizes and VMEM); whether the kernel is tried at all
is `engine/select.py`'s. `schedule()` marshals the encoded cluster into the
kernel's VMEM/SMEM layouts and runs it. Placements are identical to the XLA
scan — the tests in tests/test_fastpath.py assert equality — so callers can
switch freely.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..encoding import vocab as V
from ..obs import trace as obs
from ..obs.metrics import RECORDER
from ..obs.profile import launch_span, observed_jit_call
from ..ops import kernels
from ..ops.pallas_scan import CHUNK, VMEM_LIMIT_BYTES, FastInputs, run_fast_scan
from . import select
from .schedconfig import kernel_gap

HOSTNAME = "kubernetes.io/hostname"

# why_not's resident-row estimate must stay under this; the kernel itself is
# compiled under pallas_scan.VMEM_LIMIT_BYTES (64 MiB), which leaves the
# compiler several times the estimate. Checked against the compiler, not
# assumed: shapes at the budget's edge (plan/affinity/gpu/local-pv at
# 6.5k-7k nodes) compile for v5e on the installed JAX/libtpu.
_VMEM_BUDGET = 10 * 1024 * 1024
# scenarios a kernel step holds in a packed sweep: one a sublane of the
# vector registers' eight
SWEEP_SUBLANES = 8


def _pad8_static(n: int) -> int:
    return max(8, 8 * math.ceil(n / 8))


def applicable(prep, config=None) -> bool:
    return select.policy().off["megakernel"] is None and why_not(prep, config) is None


def why_not(prep, config=None) -> Optional[str]:
    """Envelope check for the megakernel: returns None when the prepared
    simulation can run on it, else a one-line reason (surfaced as engine
    attribution — VERDICT r4 #3). The kernel covers: static filters + fit +
    least/balanced/share + topology spread + inter-pod terms, hostname plus
    at most four other topology keys (stacked per-key count blocks), and a
    config's score weights and RequestedToCapacityRatio term."""
    gap = kernel_gap(config)
    if gap is not None:
        return f"a scheduler config the kernel cannot compute ({gap})"
    f = prep.features
    ec = prep.ec_np if prep.ec_np is not None else prep.ec
    if f.ports and int(ec.ports.max() if ec.ports.size else -1) >= 64:
        return "port-vocab ids >=64 exceed the 64 padded port rows"
    if f.gpu and int(ec.node_gpu_mem.shape[1]) > 8:
        return f"{int(ec.node_gpu_mem.shape[1])} GPUs/node > 8 supported"
    if f.local and (
        int(ec.node_vg_cap.shape[1]) > 8
        or int(ec.node_dev_cap.shape[1]) > 8
        or int(ec.dev_req_sizes.shape[2]) > 8
    ):
        return "open-local VG/device axes > 8 supported"
    # inter-pod terms are supported with bounded table sizes
    if f.interpod or f.prefg:
        if int(ec.anti_g_sel.shape[0]) > 16 or int(ec.prefg_sel.shape[0]) > 16:
            return "inter-pod global term tables > 16 rows"
        if (
            int(ec.at_sel.shape[1]) > 4
            or int(ec.an_sel.shape[1]) > 4
            or int(ec.pt_sel.shape[1]) > 4
        ):
            return "inter-pod per-template terms > 4 per pod"
    # N is padded to a 128-lane multiple at marshalling time
    # (build_inputs), so any encoder node_pad is acceptable
    N = 128 * math.ceil(int(ec.node_valid.shape[0]) / 128)
    U = int(ec.req.shape[0])
    A = int(ec.matches_sel.shape[1])
    R = int(ec.alloc.shape[1])
    # beyond 512 templates the kernel switches to big-U mode (template
    # tables in HBM, one DMA per step — see use_big_u/run_fast_scan);
    # 2048 bounds the SMEM scalar tables
    if R > 8 or U > 2048 or A > 64:
        over = [
            f"{label}={val} > {cap} supported"
            for label, val, cap in (("R", R, 8), ("U", U, 2048), ("A", A, 64))
            if val > cap
        ]
        return "table sizes outside envelope: " + ", ".join(over)
    vocab = prep.meta.vocab
    topo_keys = vocab.topo_keys.items()
    non_host = [k for k in topo_keys if k != HOSTNAME]
    if len(non_host) > 4:
        # hostname + up to four zone-like keys (compile-time unrolled
        # per-key loops; beyond that the XLA scan wins anyway)
        return f"{len(non_host)} non-hostname topology keys > 4 supported"
    # hostname domains must be node-identity (each valid node carries its
    # own hostname label) for the per-node count layout to be exact
    if HOSTNAME in topo_keys:
        tk = topo_keys.index(HOSTNAME)
        nd = np.asarray(ec.node_domain)[:, tk]
        nv = np.asarray(ec.node_valid)
        trash = np.asarray(ec.domain_topo).shape[0] - 1
        if (nd[nv] == trash).any():
            return "some valid nodes carry no hostname label"
        if len(np.unique(nd[nv])) != int(nv.sum()):
            return "hostname domains are not node-identity (duplicate hostname labels)"
    vmem = vmem_estimate(prep)
    if vmem > _VMEM_BUDGET:
        return f"VMEM estimate {vmem / 1e6:.1f} MB exceeds the {_VMEM_BUDGET / 1e6:.0f} MB budget"
    return None


def vmem_estimate(prep, sublanes: int = 1) -> int:
    """Bytes of the kernel's resident VMEM rows, as `why_not` reckons them
    and `mk.inputs` reports them, for `sublanes` scenarios a step: the
    per-scenario rows (state scratch, its outputs, validity) are held once a
    sublane, the shared tables once."""
    f = prep.features
    ec = prep.ec_np if prep.ec_np is not None else prep.ec
    N = 128 * math.ceil(int(ec.node_valid.shape[0]) / 128)
    U = int(ec.req.shape[0])
    A = int(ec.matches_sel.shape[1])
    R = int(ec.alloc.shape[1])
    topo_keys = prep.meta.vocab.topo_keys.items()
    non_host = [k for k in topo_keys if k != HOSTNAME]
    # VMEM budget. The pallas_call signature is generated per feature-flag
    # combination (_input_layout): a feature that is off contributes ZERO
    # rows — its buffers don't exist in the program. Resident rows ([x, N]),
    # shared / per scenario:
    #   always: alloc/used0 (2R) / used/used_out (2R), template tables (3U
    #   unless big-U), has_zone (K) / node_cnt (A), node_valid (1)
    #   +interpod: / anti_node + prefw_node (2G)
    #   +gpu: gpu0 (Gd) / gpu_free/gpu_out (2Gd)
    #   +local: vg cap/init (2Vg) / free/out (2Vg); dev cap/init + media
    #   one-hots (4Dv) / free/out (2Dv)
    #   +ports: / port_used (Hp)
    #   +na/tt/avoid: one [U, N] table each
    #   packed (sublanes > 1): zone_id (K) and the [SB, CHUNK] chosen window
    # plus the zone blocks: zone_NZ + zone_ZN (2·K·N·Z) and the per-scenario
    # [*, Z] scratch counts.
    if non_host:
        counts = []
        for key in non_host:
            nd = np.asarray(ec.node_domain)[:, topo_keys.index(key)]
            counts.append(len(np.unique(nd)))
        Z = max(128, 128 * math.ceil(max(counts) / 128))
    else:
        Z = 128
    K = max(len(non_host), 1)
    G = 16  # padded global-term row cap (≤16 enforced above)
    Gd_pad = _pad8_static(int(ec.node_gpu_mem.shape[1]))
    Vg_pad = _pad8_static(int(ec.node_vg_cap.shape[1]))
    Dv_pad = _pad8_static(int(ec.node_dev_cap.shape[1]))
    ports_np = np.asarray(ec.ports)
    Hp_pad = _pad8_static(
        int(ports_np.max()) + 1 if ports_np.size and ports_np.max() >= 0 else 1
    )
    U_resident = 0 if use_big_u(U, N) else U
    rows = 2 * R + 3 * U_resident + K
    scen_rows = 2 * R + A + 1
    zone_z_rows = K * A
    # [X, U] tables resident in non-big-U mode ([X, U_pad128] in big-U they
    # move to HBM): matches + ports + interpod term tables
    u_cols = 0 if use_big_u(U, N) else max(U, 128)
    u_rows = A  # matches_AU
    if f.interpod or f.prefg:
        scen_rows += 2 * G
        zone_z_rows += 2 * G
        u_rows += 4 * G  # antig/gmatch/prefg/pmatch
    if f.gpu:
        rows += Gd_pad
        scen_rows += 2 * Gd_pad
    if f.local:
        rows += 2 * Vg_pad + 4 * Dv_pad
        scen_rows += 2 * Vg_pad + 2 * Dv_pad
    if f.ports:
        scen_rows += Hp_pad
        u_rows += 2 * Hp_pad  # port_HU + port_conf_HU
    if f.pref_node_affinity:
        rows += U_resident
    if f.prefer_taints:
        rows += U_resident
    if f.prefer_avoid:
        rows += U_resident
    window = 0
    if sublanes > 1:
        rows += K
        window = 2 * sublanes * CHUNK  # double-buffered int32 chosen window
    rows += sublanes * scen_rows
    return (rows * N + (2 * K * N + sublanes * zone_z_rows) * Z + u_rows * u_cols + window) * 4


def sweep_sublanes(prep, S: int) -> int:
    """Scenarios a step of a sweep of S: eight, one a sublane, where there
    are two or more and the packed kernel's resident rows leave the compiler
    the room it needs under the limit it is compiled with (about twice the
    estimate: double-buffered blocks and the operand splits of the exact
    matmuls, see pallas_scan.VMEM_LIMIT_BYTES); else one."""
    if S >= 2 and 2 * vmem_estimate(prep, SWEEP_SUBLANES) <= VMEM_LIMIT_BYTES:
        return SWEEP_SUBLANES
    return 1


def _key_weights(weights, key_tks, K: int) -> np.ndarray:
    """[K+1] weight of each kernel topology key (0 = hostname, 1..K zone
    keys) from the [Tk] weights of the vocabulary's keys; `key_tks` lists the
    vocabulary key of each kernel key, -1 where there is none."""
    out = np.zeros((K + 1,), np.float32)
    for ki, tk in enumerate(key_tks):
        if tk >= 0:
            out[ki] = weights[tk]
    return out


def _key_tks(prep):
    """The vocabulary topology key of each kernel key (build_inputs' order)."""
    topo_keys = prep.meta.vocab.topo_keys.items()
    host_tk = topo_keys.index(HOSTNAME) if HOSTNAME in topo_keys else -1
    return [host_tk] + [i for i, k in enumerate(topo_keys) if k != HOSTNAME]


def _gc_row(prep) -> int:
    """Resource-axis row of alibabacloud.com/gpu-count when the dynamic
    allocatable path (Features.gc_dyn) is active, else -1."""
    if not prep.features.gc_dyn:
        return -1
    return kernels.gc_row_of(prep.ec_np if prep.ec_np is not None else prep.ec)


def use_big_u(U: int, N: int) -> bool:
    """Template tables move to HBM (per-step DMA) once the three resident
    [U, N] tables would crowd VMEM; below that the fully-resident kernel is
    faster. VMEM-aware: a 1000-template workload on a small cluster stays
    resident (536×256 is 1.6 MB), while 513 templates × 5120 nodes (31 MB)
    goes to HBM — matching the historical U>512 envelope at headline N."""
    return 3 * U * N * 4 > 4 * 1024 * 1024


_precompute_jit = jax.jit(kernels.precompute_static)


def _resolve_interpret(explicit: Optional[bool]) -> bool:
    """Interpret mode is asked for, never inferred from the backend: the
    explicit argument, else ``OPENSIM_FASTPATH=interpret``. A compiled run
    off a TPU backend is an error — silently interpreting there is how a
    CPU run gets read as a kernel result."""
    pol = select.policy()
    interpret = pol.interpret if explicit is None else explicit
    if not interpret and pol.platform != "tpu":
        raise RuntimeError(
            "the Pallas megakernel compiles only for a TPU backend "
            f"(jax.default_backend()={pol.platform!r}); pass "
            "interpret=True or set OPENSIM_FASTPATH=interpret to run the "
            "Pallas interpreter instead"
        )
    return interpret


def build_inputs(prep) -> Tuple[FastInputs, dict]:
    cached = getattr(prep, "_fast_inputs", None)
    if cached is not None:
        return cached
    # host-side numpy views: per-array np.asarray on device arrays is a
    # blocking device→host copy each, so use the retained numpy
    # EncodedCluster and fetch the static tables with one batched device_get
    ec = prep.ec_np if prep.ec_np is not None else jax.device_get(prep.ec)
    # static tables computed with ALL nodes valid: validity is applied as a
    # runtime row inside the kernel so scenario sweeps can mask nodes without
    # re-marshalling (static filters are per-node, so this is equivalent)
    ec_all_valid = prep.ec._replace(node_valid=jnp.ones_like(prep.ec.node_valid))
    stat = jax.device_get(_precompute_jit(ec_all_valid))
    # static_fail diagnostics must count over the REAL valid set (the
    # all-valid tables would count padding nodes); one extra cached
    # precompute fetches just that small array
    static_fail_real = np.asarray(jax.device_get(_precompute_jit(prep.ec).static_fail))
    # the kernel needs a 128-lane node axis; pad every [*, N] table here
    # (padding nodes are invalid, domain-less, zero-capacity) and trim the
    # outputs back in schedule()/sweep()
    N_orig = int(ec.node_valid.shape[0])
    N = 128 * math.ceil(N_orig / 128)
    pad_n = N - N_orig

    def _padN(a, axis=-1, fill=0):
        a = np.asarray(a)
        if pad_n == 0:
            return a
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, pad_n)
        return np.pad(a, widths, constant_values=fill)

    U = int(ec.req.shape[0])
    A = int(ec.matches_sel.shape[1])
    R = int(ec.alloc.shape[1])
    topo_keys = prep.meta.vocab.topo_keys.items()
    host_tk, *zone_tks = _key_tks(prep)

    trash = np.asarray(ec.domain_topo).shape[0] - 1
    node_domain = _padN(np.asarray(ec.node_domain), axis=0, fill=trash)

    # per-key zone one-hot blocks (dense, shared Z padded to 128 lanes);
    # topo-idx → key-index map: 0 = hostname, 1..K = zone keys in vocab order
    K = max(len(zone_tks), 1)
    if zone_tks:
        Z = max(
            128,
            128 * math.ceil(
                max(len(np.unique(node_domain[:, tk])) for tk in zone_tks) / 128
            ),
        )
    else:
        Z = 128
    # zone_NZ is [K, N, Z] (not [N, K*Z]): per-key blocks must start at lane
    # offset 0 — Mosaic cannot broadcast a vector sliced out of the flat
    # layout at lane offset k·Z
    zone_NZ = np.zeros((K, N, Z), np.float32)
    has_zone = np.zeros((K, N), np.float32)
    for ki, tk in enumerate(zone_tks):
        zd = node_domain[:, tk]
        _ids, zone_inv = np.unique(zd, return_inverse=True)
        present = zd != trash
        zone_NZ[ki, np.arange(N)[present], zone_inv[present]] = 1.0
        has_zone[ki] = present.astype(np.float32)
    zone_ZN = np.ascontiguousarray(
        zone_NZ.transpose(0, 2, 1).reshape(K * Z, N)
    )
    key_of_tk = {host_tk: 0}
    for ki, tk in enumerate(zone_tks):
        key_of_tk[tk] = ki + 1

    A_pad = max(8, 8 * math.ceil(A / 8))
    matches_AU = np.zeros((A_pad, U), np.float32)
    matches_AU[:A, :] = np.asarray(ec.matches_sel).T.astype(np.float32)

    spr_topo = np.asarray(ec.spr_topo)
    Cs = spr_topo.shape[1]
    spr_active = (spr_topo >= 0).astype(np.int32)
    _key_lut = np.zeros((max(len(topo_keys), 1) + 1,), np.int32)
    for tk, ki in key_of_tk.items():
        if tk >= 0:
            _key_lut[tk] = ki
    spr_key = _key_lut[np.maximum(spr_topo, 0)].astype(np.int32)
    spr_sel = np.maximum(np.asarray(ec.spr_sel), 0).astype(np.int32)
    spr_skew = np.asarray(ec.spr_skew).astype(np.float32)
    spr_hard = np.asarray(ec.spr_hard).astype(np.int32)
    matches_sel = np.asarray(ec.matches_sel)
    spr_self = np.zeros((U, Cs), np.float32)
    for u in range(U):
        for c in range(Cs):
            if spr_topo[u, c] >= 0:
                spr_self[u, c] = float(matches_sel[u, spr_sel[u, c]])

    # extension state, fetched in ONE batched device_get (one blocking
    # device→host round trip, not three), then transposed with sublane padding
    gpu_free0, vg_free0, dev_free0 = jax.device_get(
        (prep.st0.gpu_free, prep.st0.vg_free, prep.st0.dev_free)
    )

    def _padT(mat):  # [N_orig, K] -> [K_pad, N]
        mat = _padN(np.asarray(mat), axis=0)
        Kp = _pad8_static(mat.shape[1])
        out_m = np.zeros((Kp, mat.shape[0]), np.float32)
        out_m[: mat.shape[1]] = mat.T.astype(np.float32)
        return out_m

    gpu0_DN = _padT(gpu_free0)
    Gd_pad = gpu0_DN.shape[0]
    vg_cap_VN = _padT(prep.meta.node_vg_cap)
    vg0_VN = _padT(vg_free0)
    dev_cap_DN = _padT(prep.meta.node_dev_cap)
    dev0_DN = _padT(dev_free0)
    media = _padN(np.asarray(prep.meta.node_dev_media), axis=0, fill=-1)  # [N, Dv]
    Dv_pad = dev_cap_DN.shape[0]
    dev_media_DN = np.zeros((2 * Dv_pad, N), np.float32)
    for m in range(2):
        dev_media_DN[m * Dv_pad : m * Dv_pad + media.shape[1]] = (media.T == m).astype(np.float32)

    req_np = np.asarray(ec.req).astype(np.float32)
    cpu_nz = np.where(req_np[:, V.RES_CPU] > 0, req_np[:, V.RES_CPU], 100.0).astype(np.float32)
    mem_nz = np.where(req_np[:, V.RES_MEMORY] > 0, req_np[:, V.RES_MEMORY], 200.0 * 1024 * 1024).astype(
        np.float32
    )

    # inter-pod term tables: per-template incoming terms + padded global
    # existing-term rows (host flag, carried weights, selector matches)
    def terms(sel_arr, topo_arr):
        sel = np.asarray(sel_arr)
        topo = np.asarray(topo_arr)
        active = (sel >= 0).astype(np.int32)
        key = _key_lut[np.maximum(np.asarray(topo), 0)].astype(np.int32)
        return active, key, np.maximum(sel, 0).astype(np.int32)

    # host-port rows: [Hp_pad, U] template multi-hot
    ports_u = np.asarray(ec.ports)  # [U, Hp_tmpl] port vocab ids, -1 pad
    n_port_vocab = int(ports_u.max()) + 1 if ports_u.size and ports_u.max() >= 0 else 0
    Hp_pad = _pad8_static(max(n_port_vocab, 1))
    port_HU = np.zeros((Hp_pad, U), np.float32)
    for u_i in range(ports_u.shape[0]):
        for h in ports_u[u_i]:
            if h >= 0:
                port_HU[int(h), u_i] += 1.0
    # filter-side rows expand each template's ports to every CONFLICTING
    # vocab id (wildcard hostIP overlaps specific ones — nodeports.go);
    # the bind update keeps port_HU so only the pod's own triples are marked
    conf = np.asarray(ec.port_conflict).astype(np.float32)  # [Hv, Hv]
    port_conf_HU = np.zeros_like(port_HU)
    if n_port_vocab:
        port_conf_HU[:n_port_vocab] = (
            conf[:n_port_vocab, :n_port_vocab] @ port_HU[:n_port_vocab] > 0
        ).astype(np.float32)

    at_active, at_key, at_sel = terms(ec.at_sel, ec.at_topo)
    an_active, an_key, an_sel = terms(ec.an_sel, ec.an_topo)
    pt_active, pt_key, pt_sel = terms(ec.pt_sel, ec.pt_topo)
    at_self = np.where(at_active == 1, np.take_along_axis(matches_sel, at_sel, axis=1), 0.0).astype(
        np.float32
    )
    pt_w = np.asarray(ec.pt_w).astype(np.float32)

    g_sel = np.asarray(ec.anti_g_sel)
    g_topo = np.asarray(ec.anti_g_topo)
    G = g_sel.shape[0]
    G_pad = _pad8_static(G)
    anti_g_key = np.zeros((G_pad,), np.int32)
    antig_GU = np.zeros((G_pad, U), np.float32)
    gmatch_GU = np.zeros((G_pad, U), np.float32)
    anti_carry = np.asarray(ec.anti_g).astype(np.float32)  # [U, G]
    for g in range(G):
        anti_g_key[g] = int(_key_lut[max(int(g_topo[g]), 0)])
        antig_GU[g] = anti_carry[:, g]
        gmatch_GU[g] = matches_sel[:, g_sel[g]].astype(np.float32)
    p_sel = np.asarray(ec.prefg_sel)
    p_topo = np.asarray(ec.prefg_topo)
    Gp = p_sel.shape[0]
    Gp_pad = _pad8_static(Gp)
    prefg_key = np.zeros((Gp_pad,), np.int32)
    prefg_GU = np.zeros((Gp_pad, U), np.float32)
    pmatch_GU = np.zeros((Gp_pad, U), np.float32)
    pref_carry = np.asarray(ec.prefg_w).astype(np.float32)  # [U, Gp]
    for g in range(Gp):
        prefg_key[g] = int(_key_lut[max(int(p_topo[g]), 0)])
        prefg_GU[g] = pref_carry[:, g]
        pmatch_GU[g] = matches_sel[:, p_sel[g]].astype(np.float32)

    fi = FastInputs(
        alloc_T=np.ascontiguousarray(_padN(ec.alloc, axis=0).T.astype(np.float32)),
        used0_T=np.ascontiguousarray(_padN(jax.device_get(prep.st0.used), axis=0).T.astype(np.float32)),
        static_pass=_padN(stat.static_pass).astype(np.float32),
        aff_mask=_padN(stat.aff_mask).astype(np.float32),
        share_raw=_padN(stat.share_raw).astype(np.float32),
        zone_NZ=zone_NZ,
        zone_ZN=zone_ZN,
        has_zone=has_zone,
        matches_AU=matches_AU,
        node_valid=_padN(ec.node_valid, axis=0).astype(np.float32)[None, :],
        req=req_np,
        cpu_nz=cpu_nz,
        mem_nz=mem_nz,
        pin=np.asarray(ec.pin).astype(np.int32),
        spr_active=spr_active,
        spr_key=spr_key,
        spr_sel=spr_sel,
        spr_skew=spr_skew,
        spr_hard=spr_hard,
        spr_self=spr_self,
        key_weight=_key_weights(np.asarray(stat.spread_weight), [host_tk, *zone_tks], K),
        at_active=at_active,
        at_key=at_key,
        at_sel=at_sel,
        at_self=at_self,
        an_active=an_active,
        an_key=an_key,
        an_sel=an_sel,
        pt_active=pt_active,
        pt_key=pt_key,
        pt_sel=pt_sel,
        pt_w=pt_w,
        anti_g_key=anti_g_key,
        prefg_key=prefg_key,
        antig_GU=antig_GU,
        gmatch_GU=gmatch_GU,
        prefg_GU=prefg_GU,
        pmatch_GU=pmatch_GU,
        gpu_mem=np.asarray(ec.gpu_mem).astype(np.float32),
        gpu_cnt=np.asarray(ec.gpu_count).astype(np.float32),
        gpu0_DN=gpu0_DN,
        lvm_req=np.asarray(ec.lvm_req).astype(np.float32),
        dev_req=np.asarray(ec.dev_req).astype(np.float32),
        dev_need=np.asarray(ec.dev_req_count).astype(np.float32),
        dev_sizes=np.asarray(ec.dev_req_sizes).reshape(ec.dev_req_sizes.shape[0], -1).astype(np.float32),
        vg_cap_VN=vg_cap_VN,
        vg0_VN=vg0_VN,
        dev_cap_DN=dev_cap_DN,
        dev0_DN=dev0_DN,
        dev_media_DN=dev_media_DN,
        port_HU=port_HU,
        port_conf_HU=port_conf_HU,
        na_raw=_padN(stat.na_raw).astype(np.float32),
        tt_raw=_padN(stat.tt_raw).astype(np.float32),
        avoid_raw=_padN(ec.avoid_score).astype(np.float32),
    )
    meta = {"static_fail": static_fail_real, "n_orig": N_orig}
    # device-resident copies so repeated runs (capacity loops, sweeps) skip
    # the host→device transfer of ~25 arrays
    fi = FastInputs(*[jnp.asarray(a) for a in fi])
    try:
        prep._fast_inputs = (fi, meta)
    except AttributeError:
        pass
    return fi, meta


def _kernel_flags(prep) -> dict:
    """The feature flags that pick the generated kernel variant, and with
    open-local the VG and device rows a node really has (the widths of the
    tables build_inputs pads to eight rows, candidate nodes included): the
    variant's loops walk those alone."""
    f = prep.features
    flags = dict(
        has_interpod=bool(f.interpod or f.prefg),
        has_gpu=bool(f.gpu),
        has_local=bool(f.local),
        has_ports=bool(f.ports),
        has_na=bool(f.pref_node_affinity),
        has_tt=bool(f.prefer_taints),
        has_avoid=bool(f.prefer_avoid),
        gc_row=_gc_row(prep),
    )
    if f.local:
        flags.update(n_vg_real=int(prep.st0.vg_free.shape[1]), n_dev_real=int(prep.meta.node_dev_cap.shape[1]))
    return flags


def _gpu_rows(prep, fi: FastInputs) -> int:
    """Rows of the kernel's per-device block ([Gd, N], sublane-padded); 0
    where the variant without gpu-share runs and the block does not exist."""
    return int(fi.gpu0_DN.shape[0]) if prep.features.gpu else 0


def _local_attrs(prep, flags: dict, tmpl_ids) -> dict:
    """`mk.launch`'s open-local attributes, where the variant with the block
    runs: the VG and device rows its loops walk (`local_vgs`,
    `local_devices`) and `claim_steps`, the steps of the stream `tmpl_ids`
    whose template has an LVM or a device claim, the only steps that run the
    block. None where the variant without it runs."""
    if not flags["has_local"]:
        return {}
    ec = prep.ec_np if prep.ec_np is not None else prep.ec
    lvm, dev = (np.asarray(a) for a in jax.device_get((ec.lvm_req, ec.dev_req)))
    claims = (lvm > 0) | (dev > 0).any(axis=1)
    return dict(local_vgs=flags["n_vg_real"], local_devices=flags["n_dev_real"],
                claim_steps=int(claims[np.asarray(tmpl_ids)].sum()))


def _launch(
    prep, fi: FastInputs, tmpl_ids, pod_valid, forced, interpret: bool, big_u: bool,
    sublanes: int = 1, pad: int = 0, config=None,
):
    """`mk.launch`: everything the host does to get the kernel onto the
    device, for `fi` with its per-scenario rows set, `tmpl_ids` [P] and
    `pod_valid`/`forced` [S, P], `sublanes` scenarios a step, the last `pad`
    of the S padding. The span says `scenarios` (those asked for),
    `sublanes`, `blocks` (the grid's scenario blocks, S / sublanes) and
    `pad_scenarios`. `config` is the score profile, a static argument (None
    for the default one). run_fast_scan is one jitted function, entered
    through the compile watch as `megakernel`: a signature's first call in a
    process traces and lowers the kernel and looks the executable up in the
    persistent cache (the span says `entry="traced"`,
    simon_compile_total{fn="megakernel"} counts it), every later call is the
    jit's cache lookup, the transfer of the three pod streams and the enqueue
    (`entry="cached"`). The device's own time is mk.wait. A frame more or
    less between the caller and the kernel moves the kernel's MLIR locations,
    so the persistent cache's key and the seconds of that first lowering
    (0.3 s for one frame on the chip's host, PERF.md §6); the calls after it
    are not touched."""
    S = pod_valid.shape[0]
    flags = _kernel_flags(prep)
    with launch_span(
        "mk.launch", watch="megakernel", scenarios=S - pad, pods=len(tmpl_ids),
        nodes=fi.alloc_T.shape[1], templates=fi.static_pass.shape[0], big_u=big_u,
        gpu_devices=_gpu_rows(prep, fi), sublanes=sublanes, blocks=S // sublanes, pad_scenarios=pad,
        **_local_attrs(prep, flags, tmpl_ids),
    ):
        return observed_jit_call(
            "megakernel", run_fast_scan, (fi, tmpl_ids, pod_valid, forced),
            dict(interpret=interpret, big_u=big_u, sublanes=sublanes, config=config, **flags),
        )


class _SweepContext:
    """Host-side tables hoisted out of the per-scenario loop."""

    def __init__(self, prep) -> None:
        ec = prep.ec_np if prep.ec_np is not None else jax.device_get(prep.ec)
        self.node_domain = np.asarray(ec.node_domain)
        self.trash = np.asarray(ec.domain_topo).shape[0] - 1
        self.log_sizes = np.asarray(ec.log_sizes)
        self.key_tks = _key_tks(prep)

    def key_weights(self, node_valid: np.ndarray, K: int) -> np.ndarray:
        """[K+1] log(size+2) of each kernel topology key for a scenario's
        valid-node subset (domain counts are valid-set dependent). Weights
        come from the shared ec.log_sizes lookup so they are bitwise-identical
        to every other engine's."""
        Tk = self.node_domain.shape[1]
        sizes = np.zeros((Tk,), np.int64)
        for tk in range(Tk):
            doms = self.node_domain[node_valid, tk]
            sizes[tk] = len(np.unique(doms[doms != self.trash]))
        weights = self.log_sizes[np.clip(sizes, 0, self.log_sizes.shape[0] - 1)]
        return _key_weights(weights, self.key_tks, K)


def _scenario_rows(prep, fi: FastInputs, masks):
    """`fi` with the kernel's two per-scenario rows set for [S, N_orig] node
    masks: validity padded to the 128-lane node axis as [S, 1, N] and the
    topology keys' spread weights of each valid set as [S, K+1]. Also
    returns the padded masks as [S, N] bool. Everything else in `fi` was
    built with all nodes valid (build_inputs) and is shared by every
    scenario."""
    masks = np.asarray(masks, dtype=bool)
    nv = np.zeros((masks.shape[0], int(fi.node_valid.shape[1])), bool)
    nv[:, : masks.shape[1]] = masks
    ctx = _SweepContext(prep)
    K = int(fi.key_weight.shape[0]) - 1
    kw = np.stack([ctx.key_weights(m, K) for m in masks])
    fi = fi._replace(
        node_valid=jnp.asarray(nv.astype(np.float32)[:, None, :]),
        key_weight=jnp.asarray(kw),
    )
    return fi, nv


def sweep(
    prep, node_valid_masks, pod_valid_masks, forced_masks,
    interpret: Optional[bool] = None, big_u: Optional[bool] = None, config=None,
):
    """Scenario sweep on the megakernel: ALL scenarios in ONE batched
    dispatch. A step of the kernel holds `sweep_sublanes` scenarios, eight
    where they fit, one a sublane: the scenarios are padded to blocks of
    eight (a padding scenario has no valid node and no valid pod) and the
    grid walks the pod stream once a block, so a sweep of S scenarios makes
    ceil(S / 8) passes over the stream, not S; the per-scenario inputs (node
    validity, spread weights, pod masks) ride the block, the shared
    template/state tables are not duplicated. Returns (unscheduled [S], used
    [S, N, R], chosen [S, P], vg_used [S]) matching
    parallel.scenarios.SweepResult, the padding dropped. `big_u=None` defers
    to the use_big_u heuristic (tests override it to exercise the HBM-DMA
    path on small shapes). `config` is the scheduler config whose score
    profile the kernel computes (``why_not`` has admitted it)."""
    interpret = _resolve_interpret(interpret)
    S = node_valid_masks.shape[0]
    P = pod_valid_masks.shape[1]
    sublanes = sweep_sublanes(prep, S)
    S_pad = -(-S // sublanes) * sublanes
    with obs.span(
        "mk.inputs", scenarios=S, sublanes=sublanes, blocks=S_pad // sublanes, pad_scenarios=S_pad - S,
        vmem_estimate_bytes=vmem_estimate(prep, sublanes),
    ):
        fi, meta = build_inputs(prep)
        if big_u is None:
            big_u = use_big_u(*fi.static_pass.shape)
        pad = (-P) % CHUNK
        tmpl = np.asarray(prep.tmpl_ids)
        if pad:
            tmpl = np.concatenate([tmpl, np.zeros(pad, tmpl.dtype)])
        vg0 = np.asarray(fi.vg0_VN)
        N_orig = meta["n_orig"]
        pv_all = np.zeros((S_pad, P + pad), bool)
        pv_all[:S, :P] = np.asarray(pod_valid_masks, dtype=bool)
        fm_all = np.zeros((S_pad, P + pad), bool)
        fm_all[:S, :P] = np.asarray(forced_masks, dtype=bool)
        masks = np.zeros((S_pad, node_valid_masks.shape[1]), bool)
        masks[:S] = np.asarray(node_valid_masks, dtype=bool)
        fi, nv_all = _scenario_rows(prep, fi, masks)

    chosen_b, used_b, _gt, _gf, vg_b, _dev = outs = _launch(
        prep, fi, tmpl, pv_all, fm_all, interpret, big_u, sublanes, S_pad - S, config
    )
    RECORDER.count_megakernel_sweep_blocks(sublanes, S_pad // sublanes)
    with obs.span("mk.wait"):
        jax.block_until_ready(outs)
    with obs.span("mk.fetch"):
        chosen_all = np.asarray(chosen_b)[:S, :P]
        unscheduled = ((chosen_all < 0) & pv_all[:S, :P]).sum(axis=1).astype(np.int32)
        used = np.asarray(used_b)[:S].transpose(0, 2, 1)[:, :N_orig]
        # per the XLA sweep, VG usage counts only scenario-valid nodes
        vg_used = ((vg0[None] - np.asarray(vg_b)[:S]) * nv_all[:S, None, :]).sum(
            axis=(1, 2)
        ).astype(np.float32)
    return unscheduled, used, chosen_all, vg_used


def schedule(
    prep, tmpl_ids, pod_valid, forced, node_valid=None,
    interpret: Optional[bool] = None, big_u: Optional[bool] = None, config=None,
):
    """Run the megakernel on a pod stream (padded here to P % CHUNK == 0).
    Returns (chosen [P] i32, used_final [N, R], static_fail [U, 4],
    gpu_take [P, Gd], gpu_free [N, Gd], vg_free [N, Vg], dev_free [N, Dv]).
    `node_valid` ([N] bool over the prepared node axis; the planner's prep
    reuse) runs the stream over that subset of `prep`'s nodes, as one
    scenario of `sweep` does: the mask is the kernel's validity row, the
    spread weights are those of the masked set's domains, `static_fail`
    counts over the masked set, and the marshalled tables (`build_inputs`,
    all nodes valid) are reused as they are. `big_u=None` defers to the
    use_big_u heuristic; `config` is as in `sweep`."""
    from ..resilience import faults

    # stands in for a Mosaic compile failure (a construct passing interpret
    # mode but not the real compiler) — simulate()'s ladder demotes, or
    # fails hard under OPENSIM_REQUIRE_TPU=1 (chaos suite)
    faults.fault_point("engine.compile")
    interpret = _resolve_interpret(interpret)
    with obs.span("mk.inputs", sublanes=1, blocks=1, pad_scenarios=0, vmem_estimate_bytes=vmem_estimate(prep)):
        fi, meta = build_inputs(prep)
        if big_u is None:
            big_u = use_big_u(*fi.static_pass.shape)
        tmpl_ids = np.asarray(tmpl_ids)
        pod_valid = np.asarray(pod_valid)
        forced = np.asarray(forced)
        P = len(tmpl_ids)
        pad = (-P) % CHUNK
        if pad:
            tmpl_ids = np.concatenate([tmpl_ids, np.zeros(pad, tmpl_ids.dtype)])
            pod_valid = np.concatenate([pod_valid, np.zeros(pad, bool)])
            forced = np.concatenate([forced, np.zeros(pad, bool)])
        if node_valid is None:
            static_fail = meta["static_fail"]
            fi = fi._replace(node_valid=fi.node_valid[None], key_weight=fi.key_weight[None])
        else:
            mask = np.asarray(node_valid, dtype=bool)
            fi, _ = _scenario_rows(prep, fi, mask[None])
            # as build_inputs counts it over prep's own valid set: same
            # shapes, so the cached precompute serves and nothing recompiles
            static_fail = np.asarray(
                _precompute_jit(prep.ec._replace(node_valid=jnp.asarray(mask))).static_fail
            )
    outs = _launch(prep, fi, tmpl_ids, pod_valid[None], forced[None], interpret, big_u, config=config)
    with obs.span("mk.wait"):
        jax.block_until_ready(outs)
    with obs.span("mk.fetch"):
        chosen, used_T, gpu_take, gpu_T, vg_T, dev_T = (np.asarray(out)[0] for out in outs)
        Gd = int(prep.st0.gpu_free.shape[1])
        Vg = int(prep.st0.vg_free.shape[1])
        Dv = int(prep.st0.dev_free.shape[1])
        No = meta["n_orig"]  # lane padding added in build_inputs is trimmed here
        return (
            chosen[:P],
            used_T.T[:No],
            static_fail,
            gpu_take[:P, :Gd],
            gpu_T[:Gd].T[:No],
            vg_T[:Vg].T[:No],
            dev_T[:Dv].T[:No],
        )
