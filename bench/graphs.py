"""Communication-graph families of the benchmark, generated from a seed.

Kept apart from the program under test: the benchmark draws its inputs
itself and hands the program only the finished graphs.  A graph is the
plain triplet ``(n, u, v, w)``: each undirected edge once with ``u < v``,
integer weights stored as float64.

Families (``config["graph"]["family"]``):

* ``stencil3d`` -- the 6-point stencil over an ``nx x ny x nz`` grid of
  ranks (no wrap-around), the halo-exchange pattern of a 3D domain
  decomposition;
* ``rgg`` -- a random geometric graph in the unit square at the DIMACS-10
  ``rgg`` radius ``r = radius_factor * sqrt(ln n / n)``, with its edge
  count fixed at the expected count for that radius: the nearest
  ``m = C(n, 2) * (pi r^2 - 8 r^3 / 3 + r^4 / 2)`` point pairs.  Every
  graph of a family then has the same size, so a seed changes which
  graphs arrive, not how much work they are.

Every request gets new integer weights ``lo..hi`` and a new vertex
shuffle; ``rgg`` also new points.

:func:`reshaped` turns a drawn graph into a warm-up graph of a given
maximum degree and edge count, for a request shape that the warm-up
draws did not happen to reach (an ``rgg`` with a rare hub).
"""

from __future__ import annotations

import numpy as np


def request_rng(seed: int, stream: int, index: int) -> np.random.Generator:
    """The generator of request ``index`` of ``stream`` (0: the measured
    stream, 1: warm-up draws, 2: rewired warm-up graphs) under the run's
    ``seed``.  ``SeedSequence``
    takes any non-negative integer, so seeds past 32 bits are fine."""
    return np.random.default_rng([int(seed), int(stream), int(index)])


def stencil3d_edges(dims) -> tuple[np.ndarray, np.ndarray]:
    nx, ny, nz = (int(d) for d in dims)
    vid = np.arange(nx * ny * nz).reshape(nx, ny, nz)
    us, vs = [], []
    for axis in range(3):
        lo = [slice(None)] * 3
        hi = [slice(None)] * 3
        lo[axis] = slice(0, -1)
        hi[axis] = slice(1, None)
        us.append(vid[tuple(lo)].ravel())
        vs.append(vid[tuple(hi)].ravel())
    return np.concatenate(us), np.concatenate(vs)


def rgg_edge_count(n: int, radius_factor: float) -> int:
    """Expected edge count of the unit-square ``rgg`` at its radius."""
    r = radius_factor * np.sqrt(np.log(n) / n)
    p = np.pi * r * r - 8.0 * r ** 3 / 3.0 + r ** 4 / 2.0
    return int(round(n * (n - 1) / 2 * p))


def rgg_edges(n: int, radius_factor: float, rng: np.random.Generator
              ) -> tuple[np.ndarray, np.ndarray]:
    from scipy.spatial import cKDTree
    m = rgg_edge_count(n, radius_factor)
    radius = 1.25 * radius_factor * np.sqrt(np.log(n) / n)
    pts = rng.random((n, 2))
    tree = cKDTree(pts)
    pairs = tree.query_pairs(radius, output_type="ndarray")
    while len(pairs) < m:
        radius *= 1.25
        pairs = tree.query_pairs(radius, output_type="ndarray")
    d2 = ((pts[pairs[:, 0]] - pts[pairs[:, 1]]) ** 2).sum(axis=1)
    keep = np.argsort(d2, kind="stable")[:m]
    pairs = np.sort(pairs[keep], axis=1)
    return pairs[:, 0].astype(np.int64), pairs[:, 1].astype(np.int64)


def draw(graph_cfg: dict, rng: np.random.Generator):
    """One request's graph: ``(n, u, v, w)`` with shuffled labels."""
    family = graph_cfg["family"]
    if family == "stencil3d":
        n = int(np.prod(graph_cfg["dims"]))
        u, v = stencil3d_edges(graph_cfg["dims"])
    elif family == "rgg":
        n = int(graph_cfg["n"])
        u, v = rgg_edges(n, float(graph_cfg["radius_factor"]), rng)
    else:
        raise ValueError(f"unknown graph family {family!r}")
    lo, hi = graph_cfg["weights"]
    w = rng.integers(int(lo), int(hi) + 1, len(u)).astype(np.float64)
    shuffle = rng.permutation(n)
    a, b = shuffle[u], shuffle[v]
    u, v = np.minimum(a, b), np.maximum(a, b)
    order = np.lexsort((v, u))
    return n, u[order], v[order], w[order]


def reshaped(graph, max_deg: int, num_edges: int, weights,
             rng: np.random.Generator):
    """``graph`` rewired to exactly ``num_edges`` edges and maximum
    degree exactly ``max_deg``: edges of vertices above ``max_deg`` are
    dropped, its highest-degree vertex gains edges up to ``max_deg``,
    then random edges away from that vertex are dropped or added until
    the count is reached.  New edges get weights in ``weights``."""
    n, u, v, w = graph
    lo, hi = (int(x) for x in weights)
    edges = {(int(a), int(b)): float(c) for a, b, c in zip(u, v, w)}
    adj: list = [set() for _ in range(n)]
    for a, b in edges:
        adj[a].add(b)
        adj[b].add(a)

    def drop(a, b):
        del edges[(min(a, b), max(a, b))]
        adj[a].discard(b)
        adj[b].discard(a)

    def add(a, b):
        edges[(min(a, b), max(a, b))] = float(rng.integers(lo, hi + 1))
        adj[a].add(b)
        adj[b].add(a)

    for a in range(n):
        while len(adj[a]) > max_deg:
            drop(a, int(rng.choice(sorted(adj[a]))))
    hub = max(range(n), key=lambda a: len(adj[a]))
    while len(adj[hub]) < max_deg:
        b = int(rng.integers(n))
        if b != hub and b not in adj[hub] and len(adj[b]) < max_deg:
            add(hub, b)
    while len(edges) > num_edges:
        a, b = list(edges)[int(rng.integers(len(edges)))]
        if hub not in (a, b):
            drop(a, b)
    while len(edges) < num_edges:
        a, b = (int(x) for x in rng.integers(n, size=2))
        if (a != b and hub not in (a, b) and b not in adj[a]
                and len(adj[a]) < max_deg and len(adj[b]) < max_deg):
            add(a, b)
    keys = sorted(edges)
    u = np.array([k[0] for k in keys], dtype=np.int64)
    v = np.array([k[1] for k in keys], dtype=np.int64)
    return n, u, v, np.array([edges[k] for k in keys])
