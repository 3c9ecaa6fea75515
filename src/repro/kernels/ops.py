"""Jit'd public wrappers for the QAP kernels with backend dispatch.

On TPU the Pallas kernels compile natively; on CPU they run in
``interpret=True`` mode, which executes the kernel body in Python —
bit-identical semantics, used by the allclose test sweeps.  Any other
backend raises (``repro.runtime.device.pallas_interpret``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from ..runtime.boundary import host_boundary
from ..runtime.device import pallas_interpret
from .qap_objective import qap_objective_edges
from .swap_gain import swap_gain_matrix


def gain_matrix(C, D, perm, tile: int = 128,
                interpret: bool | None = None) -> jax.Array:
    """Gain matrix for all pair exchanges under assignment ``perm``.

    C: (n,n) symmetric communication matrix; D: (n,n) PE distances;
    perm: (n,) process→PE.  Returns (n,n) f32, G[u,v] = improvement from
    swapping u and v.
    """
    interpret = pallas_interpret() if interpret is None else interpret
    C = jnp.asarray(C)
    D = jnp.asarray(D)
    perm = jnp.asarray(perm)
    B = D[perm][:, perm]
    return swap_gain_matrix(C, B, tile=tile, interpret=interpret)


def gain_matrix_ref(C, D, perm) -> jax.Array:
    C = jnp.asarray(C, jnp.float32)
    D = jnp.asarray(D, jnp.float32)
    perm = jnp.asarray(perm)
    return ref.swap_gain_matrix_ref(C, D[perm][:, perm])


def objective(graph, hierarchy, perm,
              interpret: bool | None = None) -> float:
    """Sparse QAP objective on device (kernel path).  Accepts the core
    CommGraph/Hierarchy types; each undirected edge counted once."""
    interpret = pallas_interpret() if interpret is None else interpret
    u, v, w = graph.edge_list()
    perm = np.asarray(perm)
    pu = jnp.asarray(perm[u], jnp.int32)
    pv = jnp.asarray(perm[v], jnp.int32)
    with host_boundary("objective.readback"):
        return float(qap_objective_edges(
            pu, pv, jnp.asarray(w, jnp.float32),
            strides=tuple(int(s) for s in hierarchy.strides),
            dists=tuple(float(d) for d in hierarchy.distances),
            interpret=interpret))


def objective_ref(graph, hierarchy, perm) -> float:
    u, v, w = graph.edge_list()
    perm = np.asarray(perm)
    with host_boundary("objective.readback"):
        return float(ref.qap_objective_edges_ref(
            jnp.asarray(perm[u], jnp.int32),
            jnp.asarray(perm[v], jnp.int32),
            jnp.asarray(w, jnp.float32),
            tuple(int(s) for s in hierarchy.strides),
            tuple(float(d) for d in hierarchy.distances)))
