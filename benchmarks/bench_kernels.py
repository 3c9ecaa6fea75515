"""Kernel microbench: every hot-kernel form × path × precision × size.

Times the kernel layer the refinement engine actually runs — the sparse
pair-gain reduction and the edge-list objective — across the three
distance forms (tree / torus / matrix), both implementations (fused jnp
vs the Pallas kernel), and, for matrix-form tables, float32 vs the
lossless int8/int16 packing (``KernelConfig.dist_dtype``).  Emits
``BENCH_kernels.json`` (via :func:`benchmarks._common.write_bench`, so
the payload carries the backend/interpret/git provenance stamp):

  * ``timings``     — per (form, path, precision, n) microseconds/call;
    on a CPU host the Pallas rows run interpret=True (the meta block
    records ``pallas_interpret``), so these are CPU/interpreter times,
    never device times.  The device path is run on a TPU by
    ``chip_smoke.py`` at the repo root.
  * ``tiling``      — derived-config vs explicitly multi-tile wall time
    for the fori_loop paths (acceptance: tiled ≥ fused on CPU because
    the derived CPU config is single-tile → the identical fused graph).
  * ``bytes_moved`` — gather-path byte accounting for float vs quantized
    tables (table residency + per-edge / per-pair-slot gather traffic).
  * ``crossover``   — dense O(n²) ``swap_gain_matrix`` (reference path)
    vs the sparse candidate-pair kernel, the measurement behind keeping
    the dense form out of plan selection.
"""

from __future__ import annotations

import time

import numpy as np

from ._common import write_bench


def _timeit(fn, repeats=3):
    """Median wall time of ``fn()`` (which must block), after warmup."""
    import jax
    jax.block_until_ready(fn())
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6


def _tree_factors(n):
    """n = 4**k tree; distances 1,2,4,... stay <= 127 up to n = 4096 so
    the matrix form quantizes to int8 at every benchmarked size."""
    k = (n - 1).bit_length() // 2
    return [4] * k, [float(2 ** i) for i in range(k)] or [1.0]


def _workload(rng, n, deg=8):
    """Random integer-weight graph + perm + candidate pairs (integer
    weights keep every f32 reduction exact, so tiled-vs-fused rows are
    comparing identical results, not just close ones)."""
    from repro.core.graph import DeviceGraph, device_pairs, from_edges
    m = n * deg // 2
    u = rng.integers(0, n, m)
    v = (u + 1 + rng.integers(0, n - 1, m)) % n
    keep = u != v
    g = from_edges(n, u[keep], v[keep],
                   rng.integers(1, 16, keep.sum()).astype(np.float64))
    dg = DeviceGraph.from_comm(g)
    perm = np.asarray(rng.permutation(n))
    p = min(4 * n, 16384)
    pairs = np.stack([rng.integers(0, n, p), rng.integers(0, n, p)],
                     axis=1)
    us, vs = device_pairs(pairs)
    return g, dg, perm, us, vs


def _forms(n):
    """The three distance forms at PE count n (matrix = the tree's
    integer table, so quantization applies)."""
    from repro.topology.base import make_topology
    from repro.topology.matrix import MatrixTopology
    factors, dists = _tree_factors(n)
    tree = make_topology("tree", factors=factors, distances=dists)
    side = int(round(n ** 0.5))
    torus = make_topology("torus", dims=[side, side])
    return [("tree", tree), ("torus", torus),
            ("matrix", MatrixTopology(tree.matrix()))]


def run(report, smoke: bool = False, out: str = "BENCH_kernels.json"):
    import jax
    import jax.numpy as jnp

    from repro.kernels import (KernelConfig, derive_kernel_config,
                               qap_objective as qk, quantize_table)
    from repro.kernels.config import table_bytes
    from repro.kernels.pair_gain import (edge_objective, pair_gains,
                                         pair_gains_pallas)
    from repro.core.spec import ShapeBucket

    from repro.runtime.device import pallas_interpret
    interpret = pallas_interpret()
    rng = np.random.default_rng(0)
    sizes = [256] if smoke else [256, 1024, 4096]
    timings, tiling, bytes_moved = [], [], []

    def row(form, path, precision, n, us_, note=""):
        name = f"pair_gain/{form}/{path}/{precision}/n{n}"
        report(name, us_, note)
        timings.append({"form": form, "path": path,
                        "precision": precision, "n": n, "us": us_,
                        "note": note})

    for n in sizes:
        g, dg, perm_np, us, vs = _workload(rng, n)
        perm = jnp.asarray(perm_np, jnp.int32)
        bucket = ShapeBucket.of(g)
        for form, topo in _forms(n):
            kp = topo.kernel_params()
            kind, params = kp[0], kp[1:]
            if kind == "matrix":
                params = ()
                D32 = jnp.asarray(topo.matrix(), jnp.float32)
                packed = quantize_table(topo.matrix())
                Dq = None if packed is None else jnp.asarray(packed[0])
            else:
                D32, Dq = jnp.zeros((1, 1), jnp.float32), None
            cfg = derive_kernel_config(kind, bucket=bucket,
                                       table=topo.matrix()
                                       if kind == "matrix" else None)

            # ---- fused jnp vs Pallas pair gains (float tables)
            fused = jax.jit(lambda p: pair_gains(
                kind, params, dg.nbr, dg.wgt, p, us, vs, D32))
            t_fused = _timeit(lambda: fused(perm))
            row(form, "jnp_fused", "float32", n, t_fused)
            pall = jax.jit(lambda p: pair_gains_pallas(
                kind, params, dg.nbr, dg.wgt, p, us, vs, D32,
                interpret=interpret, config=cfg))
            row(form, "pallas", "float32", n, _timeit(lambda: pall(perm)),
                "interpret" if interpret else "device")

            # ---- quantized matrix tables (bit-identical, narrower moves)
            if Dq is not None:
                qf = jax.jit(lambda p: pair_gains(
                    kind, params, dg.nbr, dg.wgt, p, us, vs, Dq))
                row(form, "jnp_fused", packed[1], n, _timeit(
                    lambda: qf(perm)))
                qp = jax.jit(lambda p: pair_gains_pallas(
                    kind, params, dg.nbr, dg.wgt, p, us, vs, Dq,
                    interpret=interpret, config=cfg))
                row(form, "pallas", packed[1], n, _timeit(
                    lambda: qp(perm)),
                    "interpret" if interpret else "device")
                k_slots = int(us.shape[0]) * int(dg.nbr.shape[1]) * 4
                e_gather = int(dg.eu.shape[0])
                bytes_moved.append({
                    "n": n, "dist_dtype": packed[1],
                    "table_bytes_float32": table_bytes(n, None),
                    "table_bytes_packed": table_bytes(n, packed[1]),
                    "table_ratio": table_bytes(n, None)
                    / table_bytes(n, packed[1]),
                    # the host tables are float64, so end-to-end the
                    # packing shrinks resident distance state 8x (int8)
                    "table_ratio_vs_host_float64":
                        2 * table_bytes(n, None)
                        / table_bytes(n, packed[1]),
                    "gain_gather_bytes_float32": 2 * k_slots * 4,
                    "gain_gather_bytes_packed":
                        2 * k_slots * {"int8": 1, "int16": 2}[packed[1]],
                    "objective_gather_bytes_float32": e_gather * 4,
                    "objective_gather_bytes_packed":
                        e_gather * {"int8": 1, "int16": 2}[packed[1]],
                })

            # ---- edge objective: fused vs derived-tile vs forced tiles
            obj = jax.jit(lambda p: edge_objective(
                kind, params, dg.eu, dg.ev, dg.ew, p, D32))
            t_flat = _timeit(lambda: obj(perm))
            objc = jax.jit(lambda p: edge_objective(
                kind, params, dg.eu, dg.ev, dg.ew, p, D32, config=cfg))
            t_cfg = _timeit(lambda: objc(perm))
            small = KernelConfig(block_rows=1, lanes=128)
            objs = jax.jit(lambda p: edge_objective(
                kind, params, dg.eu, dg.ev, dg.ew, p, D32, config=small))
            t_small = _timeit(lambda: objs(perm))
            report(f"edge_objective/{form}/fused/n{n}", t_flat)
            report(f"edge_objective/{form}/derived_cfg/n{n}", t_cfg,
                   cfg.tag())
            e_pad = int(dg.eu.shape[0])
            tiling.append({"form": form, "n": n, "fused_us": t_flat,
                           "derived_cfg_us": t_cfg,
                           "derived_cfg": cfg.to_dict(),
                           # single-tile ⇒ the tiled path lowers to the
                           # identical fused graph (bit-identical, same
                           # work) — timing deltas are dispatch noise
                           "derived_single_tile":
                               cfg.block_rows * cfg.lanes >= e_pad,
                           "forced_128elem_tiles_us": t_small})

            # ---- Pallas edge-objective entry (the backend='pallas' path)
            pu = perm[dg.eu]
            pv = perm[dg.ev]
            geom = dict(lanes=cfg.lanes, block_rows=cfg.block_rows,
                        interpret=interpret)
            if kind == "tree":
                def pk():
                    return qk.qap_objective_edges(
                        pu, pv, dg.ew, strides=params[0],
                        dists=params[1], **geom)
            elif kind == "torus":
                def pk():
                    return qk.qap_objective_edges_torus(
                        pu, pv, dg.ew, dims=params[0],
                        weights=params[1], **geom)
            else:
                Dk = Dq if Dq is not None else D32

                def pk():
                    return qk.qap_objective_edges_matrix(
                        pu, pv, dg.ew, Dk, **geom)
            report(f"edge_objective/{form}/pallas/n{n}", _timeit(pk),
                   "interpret" if interpret else "device")

    # ---- dense/sparse crossover: the measurement behind keeping
    # swap_gain_matrix a reference path (never plan-selected)
    crossover = []
    from repro.kernels.swap_gain import swap_gain_matrix
    from repro.topology.base import make_topology
    for n in ([64, 128] if smoke else [64, 128, 256, 512]):
        g, dg, perm_np, us, vs = _workload(rng, n)
        perm = jnp.asarray(perm_np, jnp.int32)
        topo = make_topology("tree", factors=[2] * (n.bit_length() - 1),
                             distances=[float(i + 1) for i in
                                        range(n.bit_length() - 1)])
        D = topo.matrix()
        C = np.zeros((n, n))
        u, v, w = g.edge_list()
        C[u, v] = w
        C[v, u] = w
        Cd = jnp.asarray(C, jnp.float32)
        Bd = jnp.asarray(D[np.ix_(perm_np, perm_np)], jnp.float32)
        t_dense = _timeit(
            lambda: swap_gain_matrix(Cd, Bd, interpret=interpret))
        D32 = jnp.asarray(D, jnp.float32)
        sparse = jax.jit(lambda p: pair_gains(
            "matrix", (), dg.nbr, dg.wgt, p, us, vs, D32))
        t_sparse = _timeit(lambda: sparse(perm))
        report(f"crossover/dense_n{n}", t_dense,
               "interpret" if interpret else "device")
        report(f"crossover/sparse_n{n}", t_sparse,
               f"pairs={int(us.shape[0])}")
        crossover.append({"n": n, "dense_us": t_dense,
                          "sparse_us": t_sparse,
                          "pairs": int(us.shape[0])})

    payload = {
        "timings": timings,
        "tiling": tiling,
        "bytes_moved": bytes_moved,
        "crossover": crossover,
        "smoke": smoke,
        "notes": {
            "device_vs_interpret": "meta.backend/pallas_interpret name "
                                   "where these rows ran; the device "
                                   "path runs on a TPU through "
                                   "chip_smoke.py",
            "quantized_parity": "int8/int16 rows are bit-identical to "
                                "float32 rows by construction (exact "
                                "integer tables; tested in "
                                "tests/test_kernel_config.py)",
        },
    }
    write_bench(payload, out)
    report("bench_kernels/wrote", 0.0, out)


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default="BENCH_kernels.json")
    args = ap.parse_args(argv)
    run(lambda n, us, d="": print(f"{n},{us:.0f},{d}", flush=True),
        smoke=args.smoke, out=args.out)


if __name__ == "__main__":
    main()
