"""Compile-only TPU v5e checks of the main-path kernels at real shapes.

Nothing here runs on a chip: each test lowers a jitted entry against a
*described* v5e device and compiles it with the TPU compiler, which
refuses what interpret mode cannot see (unaligned blocks, VMEM
overflow, programs that do not fit the device).  The topology is
described inside a fixture — never at import — so every pytest worker
collects the same tests and only the one running this file loads the
TPU library.  The persistent compile cache is off around these
compiles: entries written for a described chip cannot be read back.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import Hierarchy
from repro.core.spec import ShapeBucket
from repro.engine import RefinementEngine
from repro.kernels import contract, derive_kernel_config
from repro.kernels import qap_objective as qk
from repro.topology import make_topology
from repro.topology.matrix import MatrixTopology

# the engine rehearsal shape: n PEs, ELL width K, E edges, P pairs,
# L portfolio lanes
N, K, E, P, L = 4096, 16, 32768, 65536, 8
E_OBJ = 65536                        # objective kernels' edge count
N_ML, E_ML = 16384, 47104            # grid3d(32, 32, 16) contraction


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _machine(kind: str):
    tree = Hierarchy((4, 16, 64), (1.0, 10.0, 100.0))
    if kind == "tree":
        return make_topology("tree", factors=list(tree.factors),
                             distances=list(tree.distances))
    if kind == "torus":
        return make_topology("torus", dims=[16, 16, 16])
    return MatrixTopology(matrix=tree.distance_matrix())


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


def _engine(kind: str) -> RefinementEngine:
    m = _machine(kind)
    cfg = derive_kernel_config(
        kind, bucket=ShapeBucket(max_deg=K, num_edges=E, num_pairs=P),
        backend="tpu", table=m.matrix() if kind == "matrix" else None)
    return RefinementEngine(m, max_sweeps=64, use_pallas=True,
                            interpret=False, kernel_config=cfg)


def _refine_args(eng, sh, lanes: int | None = None):
    lead = () if lanes is None else (lanes,)
    return (_sds((N, K), jnp.int32, sh), _sds((N, K), jnp.float32, sh),
            _sds((E,), jnp.int32, sh), _sds((E,), jnp.int32, sh),
            _sds((E,), jnp.float32, sh),
            _sds((P,), jnp.int32, sh), _sds((P,), jnp.int32, sh),
            _sds(lead + (N,), jnp.int32, sh),
            _sds(eng._D.shape, eng._D.dtype, sh),
            _sds(lead, jnp.float32, sh), _sds((), jnp.int32, sh),
            _sds((), jnp.bool_, sh), _sds((), jnp.bool_, sh))


@pytest.mark.parametrize("lane_vmap", [False, True],
                         ids=["single", "lanes"])
@pytest.mark.parametrize("kind", ["tree", "torus", "matrix"])
def test_refine_fn_compiles_for_v5e(one_chip, kind, lane_vmap):
    eng = _engine(kind)
    fn = eng._refine_fn
    if lane_vmap:
        fn = jax.vmap(fn, in_axes=(None,) * 7 + (0, None, 0)
                      + (None,) * 3)
    compiled, hlo = _compile(
        fn, *_refine_args(eng, one_chip, L if lane_vmap else None))
    assert "tpu_custom_call" in hlo         # the Pallas pair-gain kernel
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30


def _objective_entry(kind: str, derived: bool):
    m = _machine(kind)
    kp = m.kernel_params()
    geom = {}
    if derived:
        cfg = derive_kernel_config(
            kind, bucket=ShapeBucket(max_deg=K, num_edges=E_OBJ),
            backend="tpu")
        geom = {"lanes": cfg.lanes, "block_rows": cfg.block_rows}
    if kind == "tree":
        return (lambda pu, pv, w: qk.qap_objective_edges(
            pu, pv, w, strides=kp[1], dists=kp[2], **geom)), None
    if kind == "torus":
        return (lambda pu, pv, w: qk.qap_objective_edges_torus(
            pu, pv, w, dims=kp[1], weights=kp[2], **geom)), None
    return (lambda pu, pv, w, D: qk.qap_objective_edges_matrix(
        pu, pv, w, D, **geom)), m.n_pe


@pytest.mark.parametrize("derived", [False, True],
                         ids=["defaults", "derived"])
@pytest.mark.parametrize("kind", ["tree", "torus", "matrix"])
def test_objective_kernel_compiles_for_v5e(one_chip, kind, derived):
    fn, n_pe = _objective_entry(kind, derived)
    args = [_sds((E_OBJ,), jnp.int32, one_chip),
            _sds((E_OBJ,), jnp.int32, one_chip),
            _sds((E_OBJ,), jnp.float32, one_chip)]
    if n_pe is not None:
        args.append(_sds((n_pe, n_pe), jnp.float32, one_chip))
    _, hlo = _compile(fn, *args)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("step", ["heavy_edge_matching", "contract_edges"])
def test_contraction_step_compiles_for_v5e(one_chip, step):
    edges = [_sds((E_ML,), jnp.int32, one_chip),
             _sds((E_ML,), jnp.int32, one_chip),
             _sds((E_ML,), jnp.float32, one_chip)]
    if step == "heavy_edge_matching":
        fn = lambda eu, ev, ew: contract.heavy_edge_matching(  # noqa: E731
            eu, ev, ew, N_ML)
        args = edges
    else:
        fn = lambda eu, ev, ew, lab: contract.contract_edges(  # noqa: E731
            eu, ev, ew, lab, N_ML)
        args = edges + [_sds((N_ML,), jnp.int32, one_chip)]
    compiled, _ = _compile(fn, *args)
    out = jax.eval_shape(fn, *args)
    leaves = jax.tree.leaves(out)
    assert all(np.prod(x.shape) in (N_ML, E_ML) for x in leaves)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**30
