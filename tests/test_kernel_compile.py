"""The megakernel compiles for a v5e chip at the benchmark cells' own widths,
one scenario a step and eight (ISSUE 38): the TPU's compiler is installed
here and compiles for a chip that is described, not attached, so a kernel
that Mosaic refuses (a layout it cannot broadcast, a slice off the tiling,
too much VMEM) fails here and not on the chip. The interpreter runs none of
these checks. Nothing runs: results are the other tests'. Tier-1, a second
or two a case. And the XLA scan at plan-cl2's widths: what the chip's
compiler makes of its reads of the selector-count carry."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from opensim_tpu.ops.pallas_scan import CHUNK, FastInputs, run_fast_scan

_I32 = {"pin", "spr_active", "spr_key", "spr_sel", "spr_hard", "at_active", "at_key", "at_sel",
        "an_active", "an_key", "an_sel", "pt_active", "pt_key", "pt_sel", "anti_g_key", "prefg_key"}
_OFF = dict(has_interpod=False, has_gpu=False, has_local=False, has_ports=False, has_na=False,
            has_tt=False, has_avoid=False, gc_row=-1)
# (widths, flags, pod chunks): plan-short's k8s-5k-50k sweep (4,736 nodes, 4
# resources, 20 templates, 24 selectors, two spread constraints a template)
# plan-gpushare's openb-gpushare-1523 (1,664 nodes, 866 templates in big-U
# mode, eight devices a node) and plan-local's k8s-5k-50k-openlocal (plan-short's
# widths with the open-local rows: one VG and four devices a node, padded to
# eight, which the kernel's loops walk alone, and two claims of a media a
# template)
SHAPES = {
    "plan-short": (dict(N=4736, R=4, U=20, A=24, Cs=2), dict(_OFF), 50),
    "plan-gpushare": (dict(N=1664, R=6, U=866, A=8, Cs=1), dict(_OFF, has_gpu=True, big_u=True), 9),
    "plan-local": (dict(N=4736, R=4, U=20, A=24, Cs=2, Mv=2),
                   dict(_OFF, has_local=True, n_vg_real=1, n_dev_real=4), 50),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: the tests skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache and
    # cannot be read back without one: keep it out
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache)


def _inputs(dev, S, N, R, U, A, Cs, K=1, Z=128, X=8, Mv=1):
    rows = dict(
        alloc_T=(R, N), used0_T=(R, N), static_pass=(U, N), aff_mask=(U, N), share_raw=(U, N),
        zone_NZ=(K, N, Z), zone_ZN=(K * Z, N), has_zone=(K, N), matches_AU=(A, U), node_valid=(S, 1, N),
        req=(U, R), cpu_nz=(U,), mem_nz=(U,), pin=(U,), key_weight=(S, K + 1),
        **{n: (U, Cs) for n in ("spr_active", "spr_key", "spr_sel", "spr_skew", "spr_hard", "spr_self")},
        **{n: (U, 1) for n in ("at_active", "at_key", "at_sel", "at_self", "an_active", "an_key", "an_sel",
                               "pt_active", "pt_key", "pt_sel", "pt_w")},
        anti_g_key=(X,), prefg_key=(X,), antig_GU=(X, U), gmatch_GU=(X, U), prefg_GU=(X, U), pmatch_GU=(X, U),
        gpu_mem=(U,), gpu_cnt=(U,), gpu0_DN=(X, N), lvm_req=(U,), dev_req=(U, 2), dev_need=(U, 2), dev_sizes=(U, 2 * Mv),
        vg_cap_VN=(X, N), vg0_VN=(X, N), dev_cap_DN=(X, N), dev0_DN=(X, N), dev_media_DN=(2 * X, N),
        port_HU=(X, U), port_conf_HU=(X, U), na_raw=(U, N), tt_raw=(U, N), avoid_raw=(U, N),
    )
    return FastInputs(**{k: jax.ShapeDtypeStruct(v, jnp.int32 if k in _I32 else jnp.float32, sharding=dev)
                         for k, v in rows.items()})


@pytest.mark.parametrize("sublanes", [1, 8])
@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_the_kernel_compiles_for_the_chip_at_a_cells_widths(one_chip, cell, sublanes):
    widths, flags, chunks = SHAPES[cell]
    S, P = 2 * sublanes, chunks * CHUNK  # two scenario blocks
    stream = lambda *shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    lowered = run_fast_scan.lower(
        _inputs(one_chip, S, **widths), stream(P, dt=jnp.int32), stream(S, P, dt=jnp.bool_),
        stream(S, P, dt=jnp.bool_), sublanes=sublanes, **flags,
    )
    assert "tpu_custom_call" in lowered.compile().as_text()


#: plan-binpack's profile: RequestedToCapacityRatio on cpu and
#: memory with the folded shape 0 -> 0, 100 -> 100 and LeastAllocated off; and
#: a three-point shape over an extended resource column, whose segments and
#: mean divide
PROFILES = {
    "binpack": dict(w_least=0.0, w_rtcr=1.0, rtcr_shape=((0.0, 0.0), (100.0, 100.0)),
                    rtcr_resources=((0, 1.0), (1, 1.0))),
    "three_points": dict(w_rtcr=3.0, rtcr_shape=((0.0, 0.0), (40.0, 70.0), (100.0, 30.0)),
                         rtcr_resources=((0, 2.0), (1, 1.0), (3, 1.0))),
}


@pytest.mark.parametrize("S,sublanes", [(1, 1), (16, 8)], ids=["schedule", "packed-sweep"])
@pytest.mark.parametrize("profile", sorted(PROFILES))
def test_a_profiles_kernel_compiles_for_the_chip_at_plan_shorts_widths(one_chip, profile, S, sublanes):
    from opensim_tpu.engine.schedconfig import DEFAULT_CONFIG

    widths, flags, chunks = SHAPES["plan-short"]
    P = chunks * CHUNK
    stream = lambda *shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    lowered = run_fast_scan.lower(
        _inputs(one_chip, S, **widths), stream(P, dt=jnp.int32), stream(S, P, dt=jnp.bool_),
        stream(S, P, dt=jnp.bool_), sublanes=sublanes, config=DEFAULT_CONFIG._replace(**PROFILES[profile]), **flags,
    )
    assert "tpu_custom_call" in lowered.compile().as_text()


def gathers(hlo: str):
    """(operand type, slice_sizes) of every gather of an optimized HLO module."""
    out = []
    for comp in hlo.split("\n\n"):
        types = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", comp))
        for operand, sizes in re.findall(r" gather\(%([\w.\-]+), .*?slice_sizes=\{([\d,]+)\}", comp):
            out.append((types.get(operand), tuple(int(x) for x in sizes.split(","))))
    return out


def test_the_xla_scan_reads_plan_cl2s_count_carry_by_row_windows_on_the_chip(one_chip, tmp_path, monkeypatch):
    """`_schedule_pods_jit` at plan-cl2's widths, from the tiny size's
    encoding with its node, template, selector and domain axes widened:
    5,000 nodes (5,120 padded), 10,450 templates, 5,300 selectors, 5,000
    hostname domains, one zone and the trash row, 54,528 steps. After XLA's
    passes no gather reads the [D+1, A] count carry (the point gather of
    10,240 cells a step that `kernels.domain_counts` replaced, or a column
    gather, for which XLA lays the carry out by columns): it is read by
    windows of 128 whole rows and keeps its row-major layout everywhere; nor
    does any gather read a column of it node by node: hostname's counts are a
    slice, the zone's a compare-select."""
    import functools
    import importlib
    import json

    import numpy as np

    from benchmarks.drivers import Context
    from opensim_tpu.engine.scheduler import _schedule_pods_jit
    from opensim_tpu.engine.simulator import prepare
    from opensim_tpu.planner.apply import Applier, Options
    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
    with open(os.path.join(bench, "configs", "cl2-load-5k.json")) as f:
        config = json.load(f)
    with open(os.path.join(bench, "traffic", "fit-cl2.json")) as f:
        traffic = json.load(f)
    ctx = Context(config=config, traffic=traffic, seed=5, scratch=str(tmp_path), rehearse=True, sizes=config["tiny"])
    driver = importlib.import_module("benchmarks.drivers.plan_loop_kinds").Driver(ctx)
    driver.prepare()
    applier = Applier(Options(simon_config=driver.simon_config))
    prep = prepare(applier.load_cluster(), applier.load_apps())
    ec, st0 = prep.ec_np, prep.st0
    N, U, A, Dp1 = ec.node_valid.shape[0], ec.req.shape[0], ec.matches_sel.shape[1], ec.domain_topo.shape[0]
    wide = {N: 5120, N + 1: 5121, U: 10450, A: 5300, Dp1: 5002}
    # the four axes are told apart by their widths: every other axis of the tiny size is narrower
    assert len(wide) == 5 and all(d < 8 for a in (*ec, *st0) for d in np.shape(a) if d not in wide)
    spec = lambda a: jax.ShapeDtypeStruct(tuple(wide.get(d, d) for d in np.shape(a)), np.asarray(a).dtype,
                                          sharding=one_chip)
    stream = lambda dt: jax.ShapeDtypeStruct((54528,), dt, sharding=one_chip)
    hlo = jax.jit(functools.partial(_schedule_pods_jit, features=prep.features)).lower(
        jax.tree.map(spec, ec), jax.tree.map(spec, st0), stream(jnp.int32), stream(jnp.bool_), stream(jnp.bool_),
    ).compile().as_text()
    assert not [g for g in gathers(hlo) if g[0] == "f32[5002,5300]"]
    assert re.search(r"dynamic-slice\(.*dynamic_slice_sizes=\{5002,128\}", hlo)
    assert set(re.findall(r"f32\[5002,5300\]\{([\d,]+)", hlo)) == {"1,0"}
    assert prep.features.count_keys.paths() == {"slice": 1, "select": 1}
    assert not [g for g in gathers(hlo) if str(g[0]).startswith("f32[5002")], gathers(hlo)
