"""The float64 reference against hand-computed values and against the
program's own host objective."""

import numpy as np
import pytest

import graphs
import reference as R

TREE = {"kind": "tree", "factors": [2, 2, 2], "distances": [1.0, 10.0, 100.0]}
TORUS = {"kind": "torus", "dims": [4, 3], "weights": [1.0, 2.0]}


def test_tree_distances_by_hand():
    # PEs 0..7: pairs (0,1) share a core group, (0,2) a processor, (0,4)
    # nothing below the root
    assert R.distance(TREE, 0, 0) == 0.0
    assert R.distance(TREE, 0, 1) == 1.0
    assert R.distance(TREE, 2, 3) == 1.0
    assert R.distance(TREE, 0, 3) == 10.0
    assert R.distance(TREE, 5, 6) == 10.0
    assert R.distance(TREE, 3, 4) == 100.0
    assert R.distance(TREE, 7, 0) == 100.0


def test_torus_distances_by_hand():
    # PE index = x + 4 y on a 4 x 3 torus, y hops cost 2
    assert R.distance(TORUS, 0, 3) == 1.0        # x: 0 -> 3 wraps, 1 hop
    assert R.distance(TORUS, 0, 2) == 2.0
    assert R.distance(TORUS, 0, 8) == 2.0        # y: 0 -> 2 wraps, 1 hop
    assert R.distance(TORUS, 1, 10) == 1.0 + 2.0
    assert R.distance(TORUS, 5, 5) == 0.0


def test_objective_by_hand():
    u, v, w = np.array([0, 0, 2]), np.array([1, 4, 3]), np.array([3., 5., 7.])
    assert R.objective(TREE, u, v, w, np.arange(8)) == 3 * 1 + 5 * 100 + 7 * 1
    perm = np.array([0, 2, 1, 3, 4, 5, 6, 7])   # vertex 1 on PE 2, 2 on 1
    assert R.objective(TREE, u, v, w, perm) == 3 * 10 + 5 * 100 + 7 * 10


@pytest.mark.parametrize("kind", ["tree", "torus"])
def test_agrees_with_program(kind):
    from repro.core import from_edges, qap_objective
    from repro.topology import make_topology
    if kind == "tree":
        machine = {"kind": "tree", "factors": [4, 16, 2],
                   "distances": [1.0, 10.0, 100.0]}
        gcfg = {"family": "rgg", "n": 128, "radius_factor": 0.9,
                "weights": [1, 9]}
    else:
        machine = {"kind": "torus", "dims": [4, 4, 8],
                   "weights": [1.0, 1.0, 1.0]}
        gcfg = {"family": "stencil3d", "dims": [4, 4, 8], "weights": [1, 9]}
    m = dict(machine)
    prog = make_topology(m.pop("kind"), **m)
    rng = graphs.request_rng(2**40 + 11, 0, 3)
    n, u, v, w = graphs.draw(gcfg, rng)
    g = from_edges(n, u, v, w)
    perm = rng.permutation(n)
    idx = np.arange(n)
    assert np.array_equal(R.distance(machine, idx[:, None], idx[None, :]),
                          prog.distance(idx[:, None], idx[None, :]))
    assert R.objective(machine, u, v, w, perm) == qap_objective(g, prog, perm)


def test_rgg_has_fixed_size():
    gcfg = {"family": "rgg", "n": 4096, "radius_factor": 0.55,
            "weights": [1, 9]}
    sizes = {len(graphs.draw(gcfg, graphs.request_rng(s, 0, 0))[1])
             for s in (1, 2**33, 2**40 + 7)}
    assert sizes == {graphs.rgg_edge_count(4096, 0.55)}


def test_same_seed_same_graph():
    gcfg = {"family": "stencil3d", "dims": [4, 4, 4], "weights": [1, 9]}
    a = graphs.draw(gcfg, graphs.request_rng(2**35, 0, 5))
    b = graphs.draw(gcfg, graphs.request_rng(2**35, 0, 5))
    c = graphs.draw(gcfg, graphs.request_rng(2**35, 0, 6))
    assert all(np.array_equal(x, y) for x, y in zip(a[1:], b[1:]))
    assert not np.array_equal(a[1], c[1])


@pytest.mark.parametrize("max_deg", [16, 24, 32, 40])
def test_reshaped_graph_has_the_asked_shape(max_deg):
    gcfg = {"family": "rgg", "n": 1024, "radius_factor": 0.55,
            "weights": [1, 9]}
    rng = graphs.request_rng(2**40 + 3, 2, 0)
    n, u, v, w = graphs.reshaped(graphs.draw(gcfg, rng), max_deg, 3000,
                                 gcfg["weights"], rng)
    deg = np.bincount(np.concatenate([u, v]), minlength=n)
    assert n == 1024 and len(u) == 3000 and deg.max() == max_deg
    assert np.all(u < v)
    assert len({(a, b) for a, b in zip(u, v)}) == len(u)
    assert set(np.unique(w)) <= set(range(1, 10))
