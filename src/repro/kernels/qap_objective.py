"""Pallas TPU kernels: sparse QAP objective over an edge list.

J(C, D, Π) = Σ_{e=(u,v)} w_e · D(Π(u), Π(v)) — the paper's O(m) evaluation
(guide §2.1) with the distance oracle in one of three device-side forms,
selected by the machine topology's ``kernel_params()``:

  tree    — online hierarchical oracle computed arithmetically in-register
            (guide's `hierarchyonline`): the k levels are small and static,
            so the oracle unrolls to k compare/select steps on the VPU,
  torus   — closed-form k-ary n-cube oracle: per-axis div/mod coordinates
            and ring distance, unrolled over the (static) axes — like the
            tree path, large n never materializes an n×n matrix anywhere,
  matrix  — explicit-D topologies: the (E,)-gather d_e = D[pu_e, pv_e]
            runs in the jit'd wrapper (XLA's gather is the right tool; D
            may exceed VMEM), and the Pallas kernel reduces Σ w_e · d_e.
            D may be a lossless int8/int16 packing — the gather then
            moves 1–2 bytes per edge instead of 4 and the post-gather
            f32 convert is exact, so the objective is bit-identical.

Inputs are pre-gathered PE ids pu = Π[u], pv = Π[v] (the gather is done in
the jit'd wrapper; XLA handles it well) shaped (rows, L) so each grid step
streams one (block_rows, L) lane-aligned block from VMEM and accumulates a
partial sum in SMEM scratch; the single grid dimension is sequential on
TPU which makes the scalar accumulation race-free.  ``lanes`` and
``block_rows`` come from the plan's :class:`~repro.kernels.config
.KernelConfig` ((8, 1024) without one: a TPU block's last two dims
must be multiples of 8 and 128); peak VMEM per step is the
(block_rows, lanes) tile, independent of E.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pad import pad_to_lanes as _pad_to_lanes


def _hier_distance(pu, pv, strides, dists):
    """Vector online distance oracle: d = dists[lca_level-1], 0 if equal."""
    out = jnp.zeros(pu.shape, jnp.float32)
    k = len(dists)
    # from the top level down: overwrite with smaller distances when the
    # pair is in the same subtree at that level
    out = jnp.where(pu != pv, jnp.float32(dists[k - 1]), out)
    for lvl in range(k - 1, 0, -1):
        same = (pu // strides[lvl]) == (pv // strides[lvl])
        out = jnp.where(same & (pu != pv), jnp.float32(dists[lvl - 1]), out)
    return out


def _torus_distance(pu, pv, dims, weights):
    """Closed-form k-ary n-cube oracle: Σ_a w_a · ring(|x_a − y_a|, k_a).
    Axis 0 is innermost in the PE index (mixed radix); the per-axis
    div/mod unrolls over the static axis list on the VPU."""
    out = jnp.zeros(pu.shape, jnp.float32)
    stride = 1
    for d, w in zip(dims, weights):
        xa = (pu // stride) % d
        ya = (pv // stride) % d
        delta = jnp.abs(xa - ya)
        out += jnp.float32(w) * jnp.minimum(delta, d - delta).astype(
            jnp.float32)
        stride *= d
    return out


def _qap_obj_kernel(pu_ref, pv_ref, w_ref, out_ref, acc_ref, *,
                    strides: tuple, dists: tuple, steps: int):
    r = pl.program_id(0)

    @pl.when(r == 0)
    def _init():
        acc_ref[0, 0] = 0.0

    pu = pu_ref[...]
    pv = pv_ref[...]
    w = w_ref[...]
    d = _hier_distance(pu, pv, strides, dists)
    acc_ref[0, 0] += jnp.sum(w * d)

    @pl.when(r == steps - 1)
    def _done():
        out_ref[0, 0] = acc_ref[0, 0]


def _qap_obj_torus_kernel(pu_ref, pv_ref, w_ref, out_ref, acc_ref, *,
                          dims: tuple, weights: tuple, steps: int):
    r = pl.program_id(0)

    @pl.when(r == 0)
    def _init():
        acc_ref[0, 0] = 0.0

    d = _torus_distance(pu_ref[...], pv_ref[...], dims, weights)
    acc_ref[0, 0] += jnp.sum(w_ref[...] * d)

    @pl.when(r == steps - 1)
    def _done():
        out_ref[0, 0] = acc_ref[0, 0]


def _weighted_sum_kernel(d_ref, w_ref, out_ref, acc_ref, *, steps: int):
    r = pl.program_id(0)

    @pl.when(r == 0)
    def _init():
        acc_ref[0, 0] = 0.0

    acc_ref[0, 0] += jnp.sum(w_ref[...] * d_ref[...])

    @pl.when(r == steps - 1)
    def _done():
        out_ref[0, 0] = acc_ref[0, 0]


def _reduce_call(kernel, blocks, block_rows: int, lanes: int,
                 interpret: bool):
    """Shared pallas_call shape for the three reductions: stream
    (block_rows, lanes) tiles down a sequential grid, accumulate one
    scalar in SMEM scratch."""
    rows = blocks[0].shape[0]
    steps = rows // block_rows
    out = pl.pallas_call(
        functools.partial(kernel, steps=steps),
        grid=(steps,),
        in_specs=[pl.BlockSpec((block_rows, lanes), lambda r: (r, 0))
                  for _ in blocks],
        out_specs=pl.BlockSpec((1, 1), lambda r: (0, 0),
                               memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        scratch_shapes=[pltpu.SMEM((1, 1), jnp.float32)],
        interpret=interpret,
    )(*blocks)
    return out[0, 0]


@functools.partial(jax.jit,
                   static_argnames=("strides", "dists", "lanes",
                                    "block_rows", "interpret"))
def qap_objective_edges(pu: jax.Array, pv: jax.Array, w: jax.Array,
                        strides: tuple, dists: tuple,
                        lanes: int = 1024, block_rows: int = 8,
                        interpret: bool = False) -> jax.Array:
    """Σ w_e · D(pu_e, pv_e) with the hierarchy (strides, dists).

    pu, pv: (E,) int32 PE ids; w: (E,) f32.  Padded with pu == pv (distance
    0) to a lane multiple and reshaped to (rows, lanes).
    """
    e = pu.shape[0]
    pu_p, pv_p, w_p = _pad_to_lanes(
        [pu.astype(jnp.int32), pv.astype(jnp.int32),
         w.astype(jnp.float32)], e, lanes, block_rows)
    kernel = functools.partial(_qap_obj_kernel, strides=tuple(strides),
                               dists=tuple(dists))
    return _reduce_call(kernel, [pu_p, pv_p, w_p], block_rows,
                        pu_p.shape[1], interpret)


@functools.partial(jax.jit,
                   static_argnames=("dims", "weights", "lanes",
                                    "block_rows", "interpret"))
def qap_objective_edges_torus(pu: jax.Array, pv: jax.Array, w: jax.Array,
                              dims: tuple, weights: tuple,
                              lanes: int = 1024, block_rows: int = 8,
                              interpret: bool = False) -> jax.Array:
    """Σ w_e · D_torus(pu_e, pv_e) for the k-ary n-cube (dims, weights)."""
    e = pu.shape[0]
    pu_p, pv_p, w_p = _pad_to_lanes(
        [pu.astype(jnp.int32), pv.astype(jnp.int32),
         w.astype(jnp.float32)], e, lanes, block_rows)
    kernel = functools.partial(_qap_obj_torus_kernel, dims=tuple(dims),
                               weights=tuple(weights))
    return _reduce_call(kernel, [pu_p, pv_p, w_p], block_rows,
                        pu_p.shape[1], interpret)


@functools.partial(jax.jit,
                   static_argnames=("lanes", "block_rows", "interpret"))
def qap_objective_edges_matrix(pu: jax.Array, pv: jax.Array, w: jax.Array,
                               D: jax.Array, lanes: int = 1024,
                               block_rows: int = 8,
                               interpret: bool = False) -> jax.Array:
    """Σ w_e · D[pu_e, pv_e] for an explicit distance matrix.

    The per-edge gather runs as an XLA gather in this wrapper (D may not
    fit VMEM, and XLA pipelines HBM gathers well); the Pallas kernel does
    the lane-aligned weighted reduction.  Gather-then-convert keeps the
    table in its storage dtype — an int8/int16 packing moves 1–2 bytes
    per edge and converts exactly, bit-identical to a float32 table.
    """
    e = pu.shape[0]
    d = D[pu, pv].astype(jnp.float32)
    d_p, w_p = _pad_to_lanes([d, w.astype(jnp.float32)], e, lanes,
                             block_rows)
    return _reduce_call(_weighted_sum_kernel, [d_p, w_p], block_rows,
                        d_p.shape[1], interpret)
