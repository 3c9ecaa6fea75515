"""Device-resident refinement engine: DeviceGraph/ELL invariants, sparse
pair-gain kernel parity (jnp and Pallas-interpret), sweep-loop
monotonicity + local-optimum parity with `parallel_sweep_search` on every
distance form, vmapped map_many batching, spec/CLI plumbing, and the
frontier-BFS / seed satellites."""

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import (Hierarchy, Mapper, MappingSpec, grid3d,
                        qap_objective, random_geometric, swap_gain)
from repro.core.construction import construct
from repro.core.graph import DeviceGraph, device_pairs
from repro.core.local_search import (NEIGHBORHOODS, _bfs_pairs,
                                     communication_pairs,
                                     parallel_sweep_search)
from repro.core.objective import batched_swap_gains
from repro.engine import RefinementEngine, refine
from repro.topology import MatrixTopology, TorusTopology, TreeTopology

H64 = Hierarchy((4, 4, 4), (1.0, 10.0, 100.0))


def _machines():
    torus = TorusTopology((8, 8))
    return {
        "tree": TreeTopology(hierarchy=H64),
        "torus": torus,
        "matrix": MatrixTopology(matrix=torus.distance_matrix()),
    }


MACHINES = _machines()


def _gains_host(g, topo, perm, pairs):
    return batched_swap_gains(g, topo, perm, pairs)


# ------------------------------------------------------------- DeviceGraph
def test_device_graph_is_faithful_ell_view():
    g = random_geometric(48, 0.3, seed=1)
    dg = DeviceGraph.from_comm(g)
    nbr, wgt = np.asarray(dg.nbr), np.asarray(dg.wgt)
    assert dg.n == g.n and dg.num_edges == g.num_edges
    for u in range(g.n):
        live = wgt[u] != 0.0
        got = sorted(zip(nbr[u][live].tolist(), wgt[u][live].tolist()))
        want = sorted(zip(g.neighbors(u).tolist(), g.weights(u).tolist()))
        assert got == pytest.approx(want)
        # padding slots carry the row id and zero weight (inert for any D)
        assert np.all(nbr[u][~live] == u)
    u, v, w = g.edge_list()
    e = len(u)
    assert np.array_equal(np.asarray(dg.eu)[:e], u)
    assert np.array_equal(np.asarray(dg.ev)[:e], v)
    assert np.all(np.asarray(dg.ew)[e:] == 0.0)


def test_device_graph_pad_to_is_inert():
    g = grid3d(4, 4, 2)
    perm = np.arange(g.n, dtype=np.int64)
    pairs = communication_pairs(g, 2)
    from repro.kernels.pair_gain import edge_objective, pair_gains
    import jax.numpy as jnp
    kind, dims, weights = ("torus", (4, 8), (1.0, 1.0))
    D = jnp.zeros((1, 1), jnp.float32)
    p = jnp.asarray(perm, jnp.int32)
    us, vs = device_pairs(pairs)
    dg = DeviceGraph.from_comm(g)
    big = dg.pad_to(dg.max_deg + 16, dg.eu.shape[0] + 256)
    g1 = pair_gains(kind, (dims, weights), dg.nbr, dg.wgt, p, us, vs, D)
    g2 = pair_gains(kind, (dims, weights), big.nbr, big.wgt, p, us, vs, D)
    assert np.allclose(np.asarray(g1), np.asarray(g2))
    j1 = edge_objective(kind, (dims, weights), dg.eu, dg.ev, dg.ew, p, D)
    j2 = edge_objective(kind, (dims, weights), big.eu, big.ev, big.ew, p, D)
    assert float(j1) == pytest.approx(float(j2))


# ------------------------------------------------------------- gain kernels
@pytest.mark.parametrize("name", sorted(MACHINES))
def test_pair_gains_match_host_sparse_gains(name):
    import jax.numpy as jnp
    from repro.kernels.pair_gain import pair_gains
    topo = MACHINES[name]
    g = random_geometric(64, 0.25, seed=3)
    perm = construct("random", g, topo, seed=2)
    pairs = communication_pairs(g, 2)
    want = _gains_host(g, topo, perm, pairs)
    kp = topo.kernel_params()
    kind, params = kp[0], kp[1:] if kp[0] != "matrix" else ()
    D = jnp.asarray(topo.matrix(), jnp.float32) if kind == "matrix" else \
        jnp.zeros((1, 1), jnp.float32)
    dg = DeviceGraph.from_comm(g)
    us, vs = device_pairs(pairs)
    got = np.asarray(pair_gains(kind, params, dg.nbr, dg.wgt,
                                jnp.asarray(perm, jnp.int32), us, vs, D))
    assert got[:len(pairs)] == pytest.approx(want, rel=1e-5, abs=1e-4)
    assert np.all(got[len(pairs):] == 0.0)      # u == v padding is inert


@pytest.mark.parametrize("name", sorted(MACHINES))
def test_pallas_pair_gains_match_jnp(name):
    import jax.numpy as jnp
    from repro.kernels.pair_gain import pair_gains, pair_gains_pallas
    topo = MACHINES[name]
    g = grid3d(4, 4, 4)
    perm = construct("random", g, topo, seed=5)
    pairs = communication_pairs(g, 2)
    kp = topo.kernel_params()
    kind, params = kp[0], kp[1:] if kp[0] != "matrix" else ()
    D = jnp.asarray(topo.matrix(), jnp.float32) if kind == "matrix" else \
        jnp.zeros((1, 1), jnp.float32)
    dg = DeviceGraph.from_comm(g)
    us, vs = device_pairs(pairs)
    p = jnp.asarray(perm, jnp.int32)
    ref = np.asarray(pair_gains(kind, params, dg.nbr, dg.wgt, p, us, vs, D))
    got = np.asarray(pair_gains_pallas(kind, params, dg.nbr, dg.wgt, p,
                                       us, vs, D, interpret=True))
    assert got == pytest.approx(ref, rel=1e-5, abs=1e-4)


def test_edge_objective_matches_host():
    import jax.numpy as jnp
    from repro.kernels.pair_gain import edge_objective
    for name, topo in MACHINES.items():
        g = random_geometric(64, 0.25, seed=7)
        perm = construct("random", g, topo, seed=1)
        kp = topo.kernel_params()
        kind, params = kp[0], kp[1:] if kp[0] != "matrix" else ()
        D = jnp.asarray(topo.matrix(), jnp.float32) if kind == "matrix" \
            else jnp.zeros((1, 1), jnp.float32)
        dg = DeviceGraph.from_comm(g)
        got = float(edge_objective(kind, params, dg.eu, dg.ev, dg.ew,
                                   jnp.asarray(perm, jnp.int32), D))
        assert got == pytest.approx(qap_objective(g, topo, perm), rel=1e-5)


# -------------------------------------------------------------- sweep loop
def _tol(j0):
    return 1e-5 * max(1.0, abs(j0))


@pytest.mark.parametrize("name", sorted(MACHINES))
def test_engine_monotone_and_reaches_local_optimum(name):
    topo = MACHINES[name]
    g = random_geometric(64, 0.25, seed=11)
    perm = construct("random", g, topo, seed=4)
    j0 = qap_objective(g, topo, perm)
    pairs = communication_pairs(g, 2)
    res = refine(topo, g, perm, pairs, max_sweeps=64)
    st = res.stats
    tr = np.asarray(st.objective_trace)
    assert np.all(np.diff(tr) <= _tol(j0))          # device trace monotone
    assert st.final_objective <= j0 + _tol(j0)      # host f64 endpoints too
    assert st.final_objective == pytest.approx(
        qap_objective(g, topo, perm), rel=1e-9)     # reported = recomputed
    assert sorted(perm.tolist()) == list(range(g.n))
    # converged before the budget → no candidate pair has positive gain
    # beyond the engine's acceptance threshold (= a local optimum of the
    # exact same neighborhood the host drivers search)
    assert res.sweeps < 64
    eps = 2e-4 * max(1.0, abs(j0))
    gains = np.array([swap_gain(g, topo, perm, int(u), int(v))
                      for u, v in pairs])
    assert gains.max(initial=0.0) <= eps


@pytest.mark.parametrize("name", sorted(MACHINES))
def test_engine_parity_with_host_parallel_sweep(name):
    """The host batched sweep is the semantic reference: both drivers are
    monotone and both terminate in a local optimum of the same candidate
    set; the engine never ends above the host-greedy starting point."""
    topo = MACHINES[name]
    g = grid3d(4, 4, 4)
    pairs = communication_pairs(g, 2)
    p_dev = construct("random", g, topo, seed=9)
    p_host = p_dev.copy()
    j0 = qap_objective(g, topo, p_dev)
    dev = refine(topo, g, p_dev, pairs, max_sweeps=64).stats
    host = parallel_sweep_search(g, topo, p_host, pairs)
    assert dev.final_objective <= j0 + _tol(j0)
    assert host.final_objective <= j0 + 1e-9
    for stats, perm in ((dev, p_dev), (host, p_host)):
        tr = np.asarray(stats.objective_trace)
        assert np.all(np.diff(tr) <= _tol(j0))
        eps = 2e-4 * max(1.0, abs(j0))
        gains = np.array([swap_gain(g, topo, perm, int(u), int(v))
                          for u, v in pairs])
        assert gains.max(initial=0.0) <= eps


def test_engine_empty_pairs_is_noop():
    g = grid3d(4, 4, 4)
    perm = construct("identity", g, H64, seed=0)
    res = refine(H64, g, perm, np.zeros((0, 2), dtype=np.int64))
    assert res.stats.swaps == 0
    assert res.stats.final_objective == res.stats.initial_objective


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       machine=st.sampled_from(sorted(MACHINES)))
def test_engine_never_increases_objective_property(seed, machine):
    topo = MACHINES[machine]
    g = random_geometric(64, 0.22, seed=seed % 17)
    perm = construct("random", g, topo, seed=seed)
    j0 = qap_objective(g, topo, perm)
    res = refine(topo, g, perm, communication_pairs(g, 2), max_sweeps=16)
    tr = np.asarray(res.stats.objective_trace)
    assert np.all(np.diff(tr) <= _tol(j0))
    assert res.stats.final_objective <= j0 + _tol(j0)
    assert sorted(perm.tolist()) == list(range(g.n))


# --------------------------------------------------------------- sessions
def test_mapper_device_engine_improves_and_caches():
    spec = MappingSpec(construction="random", neighborhood="communication",
                       neighborhood_dist=2, preconfiguration="fast",
                       engine="device", seed=1)
    mapper = Mapper(H64, spec)
    g = grid3d(4, 4, 4)
    r1 = mapper.map(g)
    r2 = mapper.map(g)
    assert r1.final_objective <= r1.initial_objective
    assert np.array_equal(r1.perm, r2.perm)          # deterministic
    info = mapper.cache_info()
    assert info["engine_builds"] == 1                # one engine, reused
    assert info["pair_cache_hits"] == 1


def test_map_many_vmapped_batch_matches_single_maps():
    spec = MappingSpec(construction="random", neighborhood="communication",
                       neighborhood_dist=2, preconfiguration="fast",
                       engine="device", seed=3)
    graphs = []
    for i in range(4):
        g = grid3d(4, 4, 4)
        g.adjwgt = g.adjwgt * (1.0 + 0.5 * i)
        graphs.append(g)
    mapper = Mapper(H64, spec)
    batch = mapper.map_many(graphs)
    singles = [Mapper(H64, spec).map(g) for g in graphs]
    for got, want in zip(batch, singles):
        assert got.final_objective == pytest.approx(want.final_objective,
                                                    rel=1e-5)
        assert got.final_objective <= got.initial_objective
        assert sorted(got.perm.tolist()) == list(range(64))


def test_map_many_device_batches_structurally_different_graphs():
    spec = MappingSpec(construction="random", neighborhood="communication",
                       neighborhood_dist=2, preconfiguration="fast",
                       engine="device", seed=0)
    graphs = [grid3d(4, 4, 4), random_geometric(64, 0.25, seed=2)]
    mapper = Mapper(H64, spec)
    for got, g in zip(mapper.map_many(graphs), graphs):
        want = Mapper(H64, spec).map(g)
        assert got.final_objective == pytest.approx(want.final_objective,
                                                    rel=1e-5)


def test_engine_spec_round_trip_and_flags():
    import argparse
    spec = MappingSpec(engine="device")
    assert MappingSpec.from_dict(spec.to_dict()).engine == "device"
    ns = argparse.Namespace(engine="device")
    assert MappingSpec.from_flags(ns).engine == "device"
    with pytest.raises(ValueError, match="engine"):
        MappingSpec(engine="gpu").validate()


def test_pallas_engine_path_matches_jnp_engine():
    topo = MACHINES["torus"]
    g = grid3d(4, 4, 4)
    pairs = communication_pairs(g, 2)
    p_ref = construct("random", g, topo, seed=6)
    p_pl = p_ref.copy()
    ref = RefinementEngine(topo, max_sweeps=8).refine(g, p_ref, pairs)
    pl_ = RefinementEngine(topo, max_sweeps=8, use_pallas=True,
                           interpret=True).refine(g, p_pl, pairs)
    assert np.array_equal(p_ref, p_pl)
    assert pl_.final_objective == pytest.approx(ref.final_objective)


# ------------------------------------------- matching loop: the claim map
def _scatter_matching(g_m, pos, us, vs, perm):
    """The pair-to-vertex scatter formulation the claim map replaced:
    ``used`` scattered from each round's new pairs, the exit test
    re-gathering it, and the swaps and moved vertices scattered from the
    selected pair mask.  Returns ``(sel, used, rounds, perm_m,
    moved_v)``."""
    import jax
    import jax.numpy as jnp
    n, p = perm.shape[0], us.shape[0]
    idx = jnp.arange(p, dtype=jnp.int32)
    oob = jnp.int32(n)

    def match_round(mstate):
        sel, used, rounds = mstate
        elig = pos & ~used[us] & ~used[vs]
        ge = jnp.where(elig, g_m, -jnp.inf)
        vmax = jnp.full((n,), -jnp.inf, jnp.float32)
        vmax = vmax.at[us].max(ge).at[vs].max(ge)
        cand = elig & (ge >= vmax[us]) & (ge >= vmax[vs])
        vmin = jnp.full((n,), p, jnp.int32)
        masked_idx = jnp.where(cand, idx, p)
        vmin = vmin.at[us].min(masked_idx).at[vs].min(masked_idx)
        new = cand & (vmin[us] == idx) & (vmin[vs] == idx)
        used = used.at[jnp.where(new, us, oob)].set(True, mode="drop")
        used = used.at[jnp.where(new, vs, oob)].set(True, mode="drop")
        return sel | new, used, rounds + 1

    def match_cond(mstate):
        sel, used, _ = mstate
        return jnp.any(pos & ~used[us] & ~used[vs] & ~sel)

    sel, used, rounds = jax.lax.while_loop(
        match_cond, match_round,
        (jnp.zeros((p,), jnp.bool_), jnp.zeros((n,), jnp.bool_),
         jnp.int32(0)))
    pu, pv = perm[us], perm[vs]
    perm_m = perm.at[jnp.where(sel, us, oob)].set(pv, mode="drop")
    perm_m = perm_m.at[jnp.where(sel, vs, oob)].set(pu, mode="drop")
    moved_v = jnp.zeros((n,), jnp.bool_)
    moved_v = moved_v.at[jnp.where(sel, us, oob)].set(True, mode="drop")
    moved_v = moved_v.at[jnp.where(sel, vs, oob)].set(True, mode="drop")
    return sel, used, rounds, perm_m, moved_v


def _claim_matching(g_m, pos, us, vs, perm):
    from repro.engine.sweep import _apply_claims, _greedy_matching
    sel, used, claim, rounds = _greedy_matching(g_m, pos, us, vs,
                                                perm.shape[0])
    return sel, used, claim, rounds, _apply_claims(perm, used, claim, us, vs)


def _matching_case(case, seed, n=32, p=256):
    """Gains as the sweep masks them: small integers (many ties), a
    u == v pair at exactly 0, tabu-blocked pairs at -inf."""
    rng = np.random.default_rng(seed)
    us = rng.integers(0, n, p)
    vs = rng.integers(0, n, p)
    g = rng.integers(-1, 3, p).astype(np.float32)
    if case == "padding":                   # device_pairs' (0, 0) tail
        us[-p // 4:] = vs[-p // 4:] = 0
    elif case == "duplicates":              # each pair again, half reversed
        h = p // 2
        us[h:], vs[h:], g[h:] = us[:h], vs[:h], g[:h]
        us[h::2], vs[h::2] = vs[h::2].copy(), us[h::2].copy()
    elif case == "no_positive":
        g = -np.abs(g)
    elif case == "tabu":
        g[rng.random(p) < 0.3] = -np.inf
    g[us == vs] = 0.0
    perm = rng.permutation(n)
    return (np.asarray(g, np.float32), g > 0.5, us.astype(np.int32),
            vs.astype(np.int32), perm.astype(np.int32))


@pytest.mark.parametrize(
    "case", ["ties", "padding", "duplicates", "no_positive", "tabu"])
def test_claim_map_matching_equals_scatter_formulation(case):
    """The engine's claim-map matching selects the same pairs in the same
    rounds as the scatter formulation it replaced, and its n-long
    derivations of the swaps and the moved vertices equal the pair-side
    scatters."""
    import jax
    old_fn, new_fn = jax.jit(_scatter_matching), jax.jit(_claim_matching)
    rounds_seen = []
    for seed in range(6):
        args = _matching_case(case, seed)
        sel, used, rounds, perm_m, moved_v = map(np.asarray, old_fn(*args))
        sel2, used2, claim, rounds2, perm_m2 = map(np.asarray,
                                                   new_fn(*args))
        assert np.array_equal(sel2, sel)
        assert np.array_equal(used2, used)
        assert int(rounds2) == int(rounds)
        assert np.array_equal(perm_m2, perm_m)
        assert np.array_equal(used2, moved_v)  # moved_v when it is taken
        _, _, us, vs, _ = args
        v = np.flatnonzero(used2)
        assert np.all(sel2[claim[v]])
        assert np.all((us[claim[v]] == v) | (vs[claim[v]] == v))
        assert np.all(claim[~used2] == len(us))
        rounds_seen.append(int(rounds))
    if case == "no_positive":
        assert rounds_seen == [0] * 6
    else:
        assert max(rounds_seen) >= 3        # several rounds per matching


# Outputs of the sweep on two 64-vertex graphs, pinned bit for bit: the
# digests were recorded with the scatter formulation the claim map
# replaced.  Per case: graph 0 (refine, refine_batch lane 0 and
# refine_lanes lane 0 must all give it), refine_batch's graph 1, and
# refine_lanes' second starting permutation.
_SWEEP_DIGESTS = {
    ("tree", 0, False, False): ("434d0cf27ad64c18", "e75c32a235e9fcd6",
                                "64738b1cae69f6d3"),
    ("tree", 0, False, True): ("f138e66ba37c518a", "02b43c32a46bdd83",
                               "e545dc38a3146c25"),
    ("tree", 0, True, False): ("434d0cf27ad64c18", "e75c32a235e9fcd6",
                               "64738b1cae69f6d3"),
    ("tree", 0, True, True): ("f138e66ba37c518a", "02b43c32a46bdd83",
                              "e545dc38a3146c25"),
    ("tree", 3, False, False): ("82db11b9435d0c50", "043d5c66991c42b4",
                                "8330dc4c13171d49"),
    ("tree", 3, False, True): ("6581f147afdfd601", "e407b242adf6dbbe",
                               "c3284371a5ccc979"),
    ("tree", 3, True, False): ("efa53a43dafdc690", "043d5c66991c42b4",
                               "8330dc4c13171d49"),
    ("tree", 3, True, True): ("bf6ee0082f519610", "e407b242adf6dbbe",
                              "c3284371a5ccc979"),
    ("torus", 0, False, False): ("c676bedec90f549e", "ca919024dda3154a",
                                 "451b107f75c5f74e"),
    ("torus", 0, False, True): ("2b24cedac6a8bd63", "67a75d1ef8d19ccb",
                                "bda5759dbaf4a750"),
    ("torus", 0, True, False): ("c676bedec90f549e", "ca919024dda3154a",
                                "451b107f75c5f74e"),
    ("torus", 0, True, True): ("2b24cedac6a8bd63", "67a75d1ef8d19ccb",
                               "bda5759dbaf4a750"),
    ("torus", 3, False, False): ("fd0810e46cdc0f62", "13a394882fce8929",
                                 "a2e34d6a19fcb6fd"),
    ("torus", 3, False, True): ("11d583473bd168cd", "62245e8b270ff0fe",
                                "ef6829e2fa99f8ff"),
    ("torus", 3, True, False): ("d0384b3511d844a7", "7cf66109fe5bac2d",
                                "0720e69c4648281a"),
    ("torus", 3, True, True): ("59e0313be4e462d8", "3dc17b9c00d52e32",
                               "fc0d20f3aee2c278"),
}

@pytest.fixture(scope="module")
def digest_engines():
    """One engine per machine for the pinned-output cases: the toggles
    are runtime values, so every case reuses its executables."""
    return {name: RefinementEngine(MACHINES[name], max_sweeps=32)
            for name in ("tree", "torus")}


def _sweep_digest(perm, stats):
    import hashlib
    h = hashlib.sha256()
    h.update(np.asarray(perm, np.int64).tobytes())
    h.update(np.asarray(stats.objective_trace, np.float64).tobytes())
    h.update(np.int64(stats.swaps).tobytes())
    tel = stats.telemetry
    if tel is not None:
        for key in ("exchanges", "tabu_masked", "aspirations",
                    "match_rounds"):
            h.update(np.asarray(getattr(tel, key), np.int64).tobytes())
        h.update(np.int64([tel.passes, tel.sweeps,
                           tel.downhill_escapes]).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name,tenure,dlb,telemetry", sorted(_SWEEP_DIGESTS))
def test_sweep_outputs_pinned_across_toggles(digest_engines, name, tenure,
                                             dlb, telemetry):
    topo, eng = MACHINES[name], digest_engines[name]
    graphs = [grid3d(4, 4, 4), random_geometric(64, 0.25, seed=2)]
    pairs = [communication_pairs(g, 2) for g in graphs]
    perms = [construct("random", g, topo, seed=7 + i)
             for i, g in enumerate(graphs)]
    kw = {"tabu_tenure": tenure, "dlb": dlb, "telemetry": telemetry}
    first, batch1, lane1 = _SWEEP_DIGESTS[name, tenure, dlb, telemetry]
    p = perms[0].copy()
    assert _sweep_digest(p, eng.refine(graphs[0], p, pairs[0], **kw)) \
        == first
    ps = [q.copy() for q in perms]
    got = [_sweep_digest(q, s)
           for q, s in zip(ps, eng.refine_batch(graphs, ps, pairs, **kw))]
    assert got == [first, batch1]
    ps = [q.copy() for q in perms]
    got = [_sweep_digest(q, s) for q, s in
           zip(ps, eng.refine_lanes(graphs[0], ps, pairs[0], **kw))]
    assert got == [first, lane1]


def _while_parts(jaxpr):
    """``(body, cond)`` jaxprs of each ``while`` directly in ``jaxpr``
    (looking through calls, not into other loops)."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            out.append((eqn.params["body_jaxpr"].jaxpr,
                        eqn.params["cond_jaxpr"].jaxpr))
            continue
        for sub in _sub_jaxprs(eqn):
            out += _while_parts(sub)
    return out


def _sub_jaxprs(eqn):
    for val in eqn.params.values():
        for x in val if isinstance(val, (tuple, list)) else (val,):
            inner = getattr(x, "jaxpr", x)
            if hasattr(inner, "eqns"):
                yield inner


def _indirect_ops(jaxpr):
    """Counter of ``(primitive, index rows)`` over the gathers and
    scatters in ``jaxpr``, outside the loops it holds."""
    from collections import Counter
    out = Counter()
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "while":
            continue
        if name == "gather" or name.startswith("scatter"):
            out[name, eqn.invars[1].aval.shape[0]] += 1
        for sub in _sub_jaxprs(eqn):
            out += _indirect_ops(sub)
    return out


def test_matching_loop_has_no_pair_to_vertex_scatters():
    """The sweep's jaxpr: a matching round scatters only the vmax/vmin
    reductions over the P pairs, its exit test gathers nothing, and the
    sweep body scatters no pair mask onto the vertices (the swaps and the
    moved vertices come from the claim map)."""
    import jax
    import jax.numpy as jnp
    topo = MACHINES["torus"]
    g = grid3d(4, 4, 4)
    eng = RefinementEngine(topo, max_sweeps=4)
    dg = DeviceGraph.from_comm(g)
    us, vs = device_pairs(communication_pairs(g, 2), pad_to=512)
    n, p = g.n, us.shape[0]
    assert len({n, p, dg.eu.shape[0]}) == 3     # sizes tell the ops apart
    closed = jax.make_jaxpr(eng._refine_fn)(
        dg.nbr, dg.wgt, dg.eu, dg.ev, dg.ew, us, vs,
        jnp.arange(n, dtype=jnp.int32), eng._D, jnp.float32(1e-3),
        jnp.int32(0), jnp.bool_(False), jnp.bool_(False))
    (sweep_body, _), = _while_parts(closed.jaxpr)
    (round_body, round_cond), = _while_parts(sweep_body)
    per_round = _indirect_ops(round_body)
    assert per_round == {("scatter-max", p): 2, ("scatter-min", p): 2,
                         ("gather", p): 6, ("gather", n): 1}
    assert _indirect_ops(round_cond) == {}
    body_scatters = {k: c for k, c in _indirect_ops(sweep_body).items()
                     if k[0].startswith("scatter") and k[1] == p}
    # what is left is the don't-look bits' warm reduction
    assert body_scatters == {("scatter-max", p): 2}


# -------------------------------------------------- satellites: BFS + seed
def _bfs_reference(g, depth):
    """The original per-vertex Python BFS (pair-set oracle)."""
    out = set()
    for s in range(g.n):
        seen = {s}
        frontier = [s]
        for _ in range(depth):
            nxt = []
            for u in frontier:
                for v in g.neighbors(u):
                    v = int(v)
                    if v not in seen:
                        seen.add(v)
                        nxt.append(v)
            out.update((s, x) for x in nxt if x > s)
            frontier = nxt
            if not frontier:
                break
    return out


@pytest.mark.parametrize("seed,depth", [(0, 2), (1, 3), (2, 4), (3, 6)])
def test_frontier_bfs_pair_set_matches_reference(seed, depth):
    g = random_geometric(40, 0.25, seed=seed)
    got = _bfs_pairs(g, depth, max_pairs=2_000_000)
    want = _bfs_reference(g, depth)
    assert {tuple(p) for p in got} == want
    # deterministic lexicographic order
    assert np.array_equal(got, got[np.lexsort((got[:, 1], got[:, 0]))])


def test_frontier_bfs_chunked_expansion_is_equivalent(monkeypatch):
    import repro.core.local_search as ls
    g = random_geometric(40, 0.3, seed=5)
    want = ls._bfs_pairs(g, 3, 2_000_000)
    monkeypatch.setattr(ls, "_BFS_CHUNK", 7)    # force many tiny slices
    got = ls._bfs_pairs(g, 3, 2_000_000)
    assert np.array_equal(got, want)
    assert ls._bfs_pairs(g, 3, len(want) - 1) is None   # cap still fires


def test_frontier_bfs_respects_cap_like_reference():
    g = grid3d(4, 4, 4)
    full = communication_pairs(g, 4, max_pairs=2_000_000)
    capped = communication_pairs(g, 4, max_pairs=len(full) - 1)
    shallower = communication_pairs(g, 3, max_pairs=2_000_000)
    assert {tuple(p) for p in capped} == {tuple(p) for p in shallower}


def test_communication_generator_is_unseeded_and_cache_shared():
    nb = NEIGHBORHOODS["communication"]
    assert not nb.seeded
    g = grid3d(4, 4, 4)
    a = nb.generate(g, dist=3, seed=0, max_pairs=2_000_000)
    b = nb.generate(g, dist=3, seed=999, max_pairs=2_000_000)
    assert np.array_equal(a, b)
    # Mapper: same graph, different seeds → one cached pair set
    mapper = Mapper(H64, MappingSpec(neighborhood="communication",
                                     neighborhood_dist=2,
                                     preconfiguration="fast", seed=0))
    mapper.map(g)
    mapper.map(g, spec=mapper.spec.replace(seed=42))
    assert mapper.cache_info()["pair_cache_hits"] == 1


def test_seeded_generator_still_receives_seed():
    from repro.core.local_search import register_neighborhood
    calls = []

    # no explicit seeded=: the `seed` parameter in the signature is
    # auto-detected, so advertising a seed and not receiving it is
    # impossible by construction
    @register_neighborhood("_test_seeded")
    def _seeded(g, *, dist=1, seed=0, max_pairs=0):
        calls.append(seed)
        rng = np.random.default_rng(seed)
        u = rng.integers(0, g.n - 1, size=4)
        return np.stack([u, u + 1], axis=1).astype(np.int64)

    try:
        assert NEIGHBORHOODS["_test_seeded"].seeded
        g = grid3d(4, 4, 4)
        mapper = Mapper(H64, MappingSpec(neighborhood="_test_seeded",
                                         preconfiguration="fast", seed=7))
        mapper.map(g)
        mapper.map(g, spec=mapper.spec.replace(seed=8))
        assert calls == [7, 8]                      # seed forwarded, no
        assert mapper.cache_info()["pair_cache_hits"] == 0   # stale cache
    finally:
        del NEIGHBORHOODS["_test_seeded"]
