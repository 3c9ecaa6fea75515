"""Kernel-layer geometry and quantization invariants.

The contract of the PR that introduced `KernelConfig`:

  * `quantize_table` packs exact-small-integer tables to int8/int16 and
    NEVER silently changes results (auto falls back, explicit raises);
  * quantized matrix-form gathers are bit-identical to float32 gathers
    for every registered topology's distance table;
  * tile geometry (block_rows, lanes) is a performance knob, not a
    semantics knob: sweeping configs over tight/pow2/oversized buckets
    leaves objectives and accept/reject decisions bit-identical;
  * changing the kernel config never retraces a warm engine — a new
    config gets its own pooled engine, old executables stay warm;
  * the padding helpers shared in `kernels.pad` are inert (zero/self
    padding only);
  * `swap_gain_matrix` is a reference path: importable, not exported.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import Mapper, MappingSpec, ShapeBucket
from repro.core.graph import DeviceGraph, device_pairs, from_edges
from repro.core.spec import KernelSpec
from repro.kernels import KernelConfig, derive_kernel_config, quantize_table
from repro.kernels.pair_gain import (edge_objective, pair_gains,
                                     pair_gains_pallas)
from repro.kernels import pad as kpad
from repro.runtime.device import pallas_interpret
from repro.topology import list_topologies, make_topology
from repro.topology.matrix import MatrixTopology

INTERPRET = pallas_interpret()

# instantiation recipe per registered topology (integral distances, the
# Schulz–Träff structure the quantizer exploits)
_TOPO_RECIPES = {
    "tree": dict(factors=[4, 4, 4], distances=[1.0, 10.0, 100.0]),
    "torus": dict(dims=[8, 8]),
    "fattree": dict(arities=[4, 4, 4]),
    "dragonfly": dict(),                       # defaults: 4·8·9 = 288 PEs
    "matrix": None,                            # wrapped below
}


def _instance(name):
    if name == "matrix":
        base = make_topology("tree", **_TOPO_RECIPES["tree"])
        return MatrixTopology(base.matrix())
    return make_topology(name, **_TOPO_RECIPES[name])


def _int_graph(n, seed=0, deg=6):
    """Integer-weight workload: every f32 sum below is exact, so tiled /
    quantized paths must match the fused float path bit-for-bit."""
    rng = np.random.default_rng(seed)
    m = n * deg // 2
    u = rng.integers(0, n, m)
    v = (u + 1 + rng.integers(0, n - 1, m)) % n
    keep = u != v
    return from_edges(n, u[keep], v[keep],
                      rng.integers(1, 16, keep.sum()).astype(np.float64))


def _gain_inputs(g, seed=0, n_pairs=256):
    rng = np.random.default_rng(seed)
    dg = DeviceGraph.from_comm(g)
    perm = jnp.asarray(rng.permutation(g.n), jnp.int32)
    pairs = np.stack([rng.integers(0, g.n, n_pairs),
                      rng.integers(0, g.n, n_pairs)], axis=1)
    us, vs = device_pairs(pairs)
    return dg, perm, us, vs


# ------------------------------------------------------------ quantize_table
def test_quantize_table_auto_selects_narrowest_lossless_width():
    small = np.array([[0., 3.], [3., 0.]])
    packed, dt = quantize_table(small)
    assert dt == "int8" and packed.dtype == np.int8
    assert np.array_equal(packed.astype(np.float64), small)
    wide = np.array([[0., 300.], [300., 0.]])
    packed, dt = quantize_table(wide)
    assert dt == "int16" and packed.dtype == np.int16
    huge = np.array([[0., 40000.], [40000., 0.]])
    assert quantize_table(huge) is None          # auto: fall back, no error
    fractional = np.array([[0., 1.5], [1.5, 0.]])
    assert quantize_table(fractional) is None
    assert quantize_table(small, "off") is None


def test_quantize_table_forced_mode_refuses_lossy_packing():
    wide = np.array([[0., 300.], [300., 0.]])
    with pytest.raises(ValueError, match="exceeds"):
        quantize_table(wide, "int8")
    fractional = np.array([[0., 1.5], [1.5, 0.]])
    with pytest.raises(ValueError, match="not exact integers"):
        quantize_table(fractional, "int8")
    with pytest.raises(ValueError, match="unknown quantize mode"):
        quantize_table(wide, "int4")
    # forced int16 on an int8-range table is allowed (wider, still exact)
    small = np.array([[0., 3.], [3., 0.]])
    assert quantize_table(small, "int16")[1] == "int16"


def test_kernel_config_validation_and_identity():
    with pytest.raises(ValueError, match="lanes"):
        KernelConfig(lanes=100).validate()
    with pytest.raises(ValueError, match="block_rows"):
        KernelConfig(block_rows=0).validate()
    with pytest.raises(ValueError, match="acc_dtype"):
        KernelConfig(acc_dtype="bfloat16").validate()
    cfg = KernelConfig(block_rows=2, lanes=256, dist_dtype="int8")
    assert KernelConfig.from_dict(cfg.to_dict()) == cfg
    assert cfg.tag() == "b2:l256:float32:int8"
    assert cfg.replace(dist_dtype=None).key() != cfg.key()
    with pytest.raises(ValueError, match="unknown KernelConfig keys"):
        KernelConfig.from_dict({"block_rows": 2, "tile": 8})


def test_derive_kernel_config_is_backend_aware_and_honors_overrides():
    g = _int_graph(256)
    bucket = ShapeBucket.of(g)
    cpu = derive_kernel_config("tree", bucket=bucket, backend="cpu")
    tpu = derive_kernel_config("tree", bucket=bucket, backend="tpu")
    assert cpu.lanes % 128 == 0 and tpu.lanes <= 1024
    # CPU budget covers the bucket in one tile → tiled path == fused path
    assert cpu.block_rows * cpu.lanes >= bucket.num_edges
    pinned = derive_kernel_config("tree", bucket=bucket, backend="cpu",
                                  block_rows=2, lanes=256)
    assert (pinned.block_rows, pinned.lanes) == (2, 256)
    D = _instance("tree").matrix()
    q = derive_kernel_config("matrix", bucket=bucket, table=D)
    assert q.dist_dtype == "int8"
    off = derive_kernel_config("matrix", bucket=bucket, table=D,
                               quantize="off")
    assert off.dist_dtype is None


# ------------------------------------- quantized parity, every topology
@pytest.mark.parametrize("name", list_topologies())
def test_quantized_matrix_gather_bit_identical(name):
    topo = _instance(name)
    D = topo.matrix()
    packed = quantize_table(D)
    assert packed is not None, f"{name} table should quantize losslessly"
    n = topo.n_pe
    g = _int_graph(n, seed=1)
    dg, perm, us, vs = _gain_inputs(g, seed=1)
    D32 = jnp.asarray(D, jnp.float32)
    Dq = jnp.asarray(packed[0])
    obj_f = edge_objective("matrix", (), dg.eu, dg.ev, dg.ew, perm, D32)
    obj_q = edge_objective("matrix", (), dg.eu, dg.ev, dg.ew, perm, Dq)
    assert float(obj_f) == float(obj_q)          # bit-identical
    gains_f = pair_gains("matrix", (), dg.nbr, dg.wgt, perm, us, vs, D32)
    gains_q = pair_gains("matrix", (), dg.nbr, dg.wgt, perm, us, vs, Dq)
    assert np.array_equal(np.asarray(gains_f), np.asarray(gains_q))
    pg_f = pair_gains_pallas("matrix", (), dg.nbr, dg.wgt, perm, us, vs,
                             D32, interpret=INTERPRET)
    pg_q = pair_gains_pallas("matrix", (), dg.nbr, dg.wgt, perm, us, vs,
                             Dq, interpret=INTERPRET)
    assert np.array_equal(np.asarray(pg_f), np.asarray(pg_q))


def test_quantized_end_to_end_identical_mapping():
    """Same graph, same spec, quantize auto vs off: identical perms and
    objectives — the packing is invisible to results."""
    topo = MatrixTopology(_instance("tree").matrix())
    g = _int_graph(64, seed=2)
    spec = dict(construction="random", neighborhood="communication",
                neighborhood_dist=2, preconfiguration="fast",
                engine="device", seed=1)
    res_q = Mapper(topo, MappingSpec(**spec)).map(g)
    res_f = Mapper(topo, MappingSpec(
        **spec, kernel=KernelSpec(quantize="off"))).map(g)
    assert np.array_equal(res_q.perm, res_f.perm)
    assert res_q.final_objective == res_f.final_objective


# ------------------------------------------------- tile-geometry sweep
_SWEEP = [KernelConfig(block_rows=1, lanes=128),
          KernelConfig(block_rows=2, lanes=256),
          KernelConfig(block_rows=64, lanes=8192)]


@pytest.mark.parametrize("cfg", _SWEEP, ids=lambda c: c.tag())
def test_tile_geometry_sweep_kernels_bit_identical(cfg):
    g = _int_graph(128, seed=3)
    dg, perm, us, vs = _gain_inputs(g, seed=3)
    topo = _instance("tree")
    strides, dists = topo.kernel_params()[1:]
    params = (strides, dists)
    D0 = jnp.zeros((1, 1), jnp.float32)
    base_obj = edge_objective("tree", params, dg.eu, dg.ev, dg.ew,
                              perm, D0)
    base_gain = pair_gains("tree", params, dg.nbr, dg.wgt, perm,
                           us, vs, D0)
    obj = edge_objective("tree", params, dg.eu, dg.ev, dg.ew,
                         perm, D0, config=cfg)
    gain = pair_gains("tree", params, dg.nbr, dg.wgt, perm, us,
                      vs, D0, config=cfg)
    assert float(obj) == float(base_obj)
    assert np.array_equal(np.asarray(gain), np.asarray(base_gain))
    pg = pair_gains_pallas("tree", params, dg.nbr, dg.wgt, perm,
                           us, vs, D0, interpret=INTERPRET, config=cfg)
    assert np.array_equal(np.asarray(pg), np.asarray(base_gain))


@pytest.mark.parametrize("schedule,oversize",
                         [("tight", False), ("pow2", False),
                          ("tight", True)],
                         ids=["tight", "pow2", "oversized"])
def test_tile_geometry_sweep_plans_bit_identical(schedule, oversize):
    """Pinned tile geometries across bucket schedules: the mapping a
    plan produces is independent of both."""
    topo = _instance("tree")
    g = _int_graph(64, seed=4)
    bucket = ShapeBucket.of(g, schedule=schedule)
    if oversize:
        bucket = ShapeBucket(max_deg=bucket.max_deg * 2,
                             num_edges=bucket.num_edges * 4,
                             schedule=bucket.schedule)
    spec = dict(construction="random", neighborhood="communication",
                neighborhood_dist=2, preconfiguration="fast",
                engine="device", seed=1)
    ref = Mapper(topo, MappingSpec(**spec)).lower_for(g).execute(g)
    for ks in (KernelSpec(block_rows=1, lanes=128),
               KernelSpec(block_rows=2, lanes=256)):
        mapper = Mapper(topo, MappingSpec(**spec, kernel=ks))
        res = mapper.lower(bucket).execute(g)
        assert np.array_equal(res.perm, ref.perm)
        assert res.final_objective == ref.final_objective


# ------------------------------------------------- warm-path no-retrace
def test_kernel_config_changes_never_retrace_warm_engines():
    topo = _instance("tree")
    g = _int_graph(64, seed=5)
    spec = MappingSpec(construction="random",
                       neighborhood="communication", neighborhood_dist=2,
                       preconfiguration="fast", engine="device", seed=1)
    mapper = Mapper(topo, spec)
    plan = mapper.lower_for(g)
    plan.execute(g)
    eng = plan.engines[0]
    assert eng.trace_count() == 1
    for seed in (2, 3, 4):                       # warm serving stays warm
        plan.execute(g, seed=seed)
    assert eng.trace_count() == 1
    # a different kernel config = a different pooled engine; the first
    # engine's executable is untouched
    plan2 = mapper.lower_for(g, spec.replace(
        kernel=KernelSpec(block_rows=1, lanes=128)))
    assert plan2.engines[0] is not eng
    plan2.execute(g)
    assert eng.trace_count() == 1
    assert plan2.engines[0].trace_count() == 1
    plan.execute(g, seed=5)                      # and stays warm after
    assert eng.trace_count() == 1
    # same config → same pooled engine (no silent duplicate compiles)
    plan3 = mapper.lower_for(g, spec.replace(seed=9))
    assert plan3.engines[0] is eng


# ------------------------------------------------------- plan reporting
def test_describe_reports_kernel_configs():
    topo = MatrixTopology(_instance("tree").matrix())
    g = _int_graph(64, seed=6)
    spec = MappingSpec(construction="random",
                       neighborhood="communication", neighborhood_dist=2,
                       preconfiguration="fast", engine="device", seed=1)
    d = Mapper(topo, spec).lower_for(g).describe()
    assert "kernels" in d
    assert d["kernels"]["backend"] == jax.default_backend()
    cfgs = d["kernels"]["configs"]
    assert cfgs and all(KernelConfig.from_dict(c) for c in cfgs)
    assert d["kernels"]["quantized"]             # integral tree table
    assert all("kernel_config" in lvl for lvl in d["levels"])


def test_spec_kernel_block_round_trips_and_validates():
    ks = KernelSpec(block_rows=2, lanes=256, quantize="int8")
    spec = MappingSpec(construction="random", kernel=ks)
    again = MappingSpec.from_dict(spec.to_dict())
    assert again == spec and again.kernel == ks
    with pytest.raises(ValueError, match="lanes"):
        KernelSpec(lanes=100).validate()
    with pytest.raises(ValueError, match="quantize"):
        KernelSpec(quantize="int4").validate()


# ------------------------------------------------------- shared padding
def test_pad_helpers_are_inert():
    rng = np.random.default_rng(7)
    a = jnp.asarray(rng.standard_normal(300), jnp.float32)
    p = kpad.pad1(a, 512)
    assert p.shape == (512,)
    assert np.array_equal(np.asarray(p[:300]), np.asarray(a))
    assert not np.asarray(p[300:]).any()
    m = jnp.asarray(rng.standard_normal((5, 7)), jnp.float32)
    p2 = kpad.pad2(m, 8, 16)
    assert p2.shape == (8, 16)
    assert np.array_equal(np.asarray(p2[:5, :7]), np.asarray(m))
    assert float(jnp.sum(p2)) == pytest.approx(float(jnp.sum(m)))
    # pad_edge_arrays: zero-weight padding leaves the objective alone
    g = _int_graph(64, seed=7)
    u, v, w = g.edge_list()
    eu, ev, ew = kpad.pad_edge_arrays(u, v, w)
    assert eu.shape[0] % 128 == 0
    topo = _instance("tree")
    strides, dists = topo.kernel_params()[1:]
    D0 = jnp.zeros((1, 1), jnp.float32)
    padded = edge_objective("tree", (strides, dists), eu, ev, ew,
                            jnp.arange(64, dtype=jnp.int32), D0)
    raw = edge_objective("tree", (strides, dists), jnp.asarray(u),
                         jnp.asarray(v),
                         jnp.asarray(w, dtype=jnp.float32),
                         jnp.arange(64, dtype=jnp.int32), D0)
    assert float(padded) == float(raw)


def test_swap_gain_matrix_is_reference_only():
    import repro.kernels as kernels
    assert "swap_gain_matrix" not in kernels.__all__
    assert callable(kernels.swap_gain_matrix)    # still importable
