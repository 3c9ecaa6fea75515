"""The plain reference the benchmark judges each placement by.

Float64 numpy and scipy only; it imports nothing of the program under
test.  A machine is the configuration's ``machine`` block:

* ``{"kind": "tree", "factors": [a_1, ..], "distances": [d_1, ..]}`` --
  the homogeneous hierarchy of Schulz & Traeff (factors innermost first):
  two PEs in the same level-l subtree but not the same level-(l-1) one
  are ``d_l`` apart;
* ``{"kind": "torus", "dims": [k_1, ..], "weights": [w_1, ..]}`` -- the
  k-ary n-cube, axis 0 innermost in the PE index, distance the weighted
  sum of ring distances.

J(perm) = sum over undirected edges (u, v) of w_uv * D(perm[u], perm[v]),
each edge once.
"""

from __future__ import annotations

import numpy as np


def distance(machine: dict, p, q) -> np.ndarray:
    """D(p, q) in float64, elementwise over broadcast PE index arrays."""
    p = np.asarray(p, dtype=np.int64)
    q = np.asarray(q, dtype=np.int64)
    if machine["kind"] == "tree":
        factors, dists = machine["factors"], machine["distances"]
        out = np.full(np.broadcast(p, q).shape, float(dists[-1]))
        stride = int(np.prod(factors[:-1]))
        for lvl in range(len(factors) - 1, 0, -1):
            # same level-lvl subtree: both lie in one block of `stride`
            out = np.where(p // stride == q // stride,
                           float(dists[lvl - 1]), out)
            stride //= int(factors[lvl - 1])
        return np.where(p == q, 0.0, out)
    if machine["kind"] == "torus":
        out = np.zeros(np.broadcast(p, q).shape)
        stride = 1
        for k, w in zip(machine["dims"], machine["weights"]):
            delta = np.abs(p // stride % k - q // stride % k)
            out += float(w) * np.minimum(delta, k - delta)
            stride *= int(k)
        return out
    raise ValueError(f"unknown machine kind {machine['kind']!r}")


def objective(machine: dict, u, v, w, perm) -> float:
    """J of ``perm`` (process -> PE) in float64."""
    perm = np.asarray(perm, dtype=np.int64)
    return float(np.sum(np.asarray(w, np.float64)
                        * distance(machine, perm[u], perm[v])))


def is_bijection(perm, n: int) -> bool:
    perm = np.asarray(perm)
    return perm.shape == (n,) and np.array_equal(np.sort(perm),
                                                 np.arange(n))
