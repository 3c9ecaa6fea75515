"""Benchmark harness — one module per paper table.  Prints
``name,us_per_call,derived`` CSV rows (harness contract)."""

from __future__ import annotations

import sys


def main() -> None:
    from repro.runtime.device import enable_compile_cache
    enable_compile_cache()
    from . import (bench_construction, bench_engine, bench_kernels,
                   bench_local_search, bench_mesh_mapping,
                   bench_multilevel, bench_portfolio, bench_remap,
                   bench_serve, bench_topology)

    def report(name: str, us: float, derived: str = ""):
        print(f"{name},{us:.0f},{derived}", flush=True)

    smoke = "--smoke" in sys.argv[1:]
    # record tracer spans for the whole run: every BENCH_*.json gets a
    # span_breakdown block (per-stage timing split) via write_bench
    from repro.obs import get_tracer
    get_tracer().enable(capacity=65536)
    print("name,us_per_call,derived")
    bench_construction.run(report)
    bench_local_search.run(report)
    # kernel-layer axis: writes BENCH_kernels.json (forms x paths x dtypes)
    bench_kernels.run(report, smoke=smoke)
    bench_mesh_mapping.run(report)
    # machine-model axis: writes BENCH_topology.json next to the CSV stream
    bench_topology.run(report, smoke=smoke)
    # refinement-engine axis: writes BENCH_engine.json (host vs device)
    bench_engine.run(report, smoke=smoke)
    # multilevel axis: writes BENCH_multilevel.json (flat vs V-cycle)
    bench_multilevel.run(report, smoke=smoke)
    # portfolio axis: writes BENCH_portfolio.json (single vs multistart)
    bench_portfolio.run(report, smoke=smoke)
    # serving axis: writes BENCH_serve.json (MappingService vs per-request)
    bench_serve.run(report, smoke=smoke)
    # closed-loop axis: writes BENCH_remap.json (drift -> gate -> remap)
    bench_remap.run(report, smoke=smoke)


if __name__ == "__main__":
    main()
