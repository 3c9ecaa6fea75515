"""Bring-up smoke of the device placement path on one TPU chip.

    python3 chip_smoke.py

Drives the served placement path — ``repro.launch.serve
.placement_service`` → ``Mapper.lower`` → ``MappingPlan.execute`` — with
the device engine, the Pallas backend and a multilevel V-cycle on two
machines, each with a seeded 3D-stencil graph of its size:

* the paper's tree hierarchy ``4:16:256`` with distances ``1:10:100``
  (n = 16384): a cold request (lower + compile + execute) and an exact
  repeat (the service's result cache);
* a (16, 16, 16) torus (n = 4096): a cold request, the same graph under
  another runtime seed (warm: same plan and shapes, no retrace), an
  exact repeat, and one ``quality="strong"`` request (the vmapped
  portfolio lanes).

The spec is the fleet placement spec with ``engine="device"``,
``backend="pallas"``, ``MultilevelSpec()`` and an explicit sweep budget
of ``SWEEPS`` per engine call; the strong class is a 4-lane, 2-round
portfolio.  Both are sized so that the whole smoke, compiles included,
takes a few minutes on one v5e chip: a device sweep at n = 16384 costs
on the order of a second there.

Every request is checked: the permutation is a bijection, the returned
J matches the host float64 ``qap_objective`` to a relative 1e-5, J is no
greater than the constructed J (and strictly lower on at least one
request), every engine runs compiled Pallas (``use_pallas=True``,
``interpret=False``), and engine trace counts stay flat across the warm
requests.  Any failed check exits non-zero.  Printed times are one smoke
run each, not a benchmark; per request, the tracer's spans give the
host-clock split between construction and each V-cycle level's refine.

Without a TPU (or without the repo's ``src/`` beside this file) the
script exits non-zero before printing any result.  The last line of a
passing run is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import json
import sys
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parent
REL_TOL = 1e-5
TREE = ("4:16:256", "1:10:100")     # --hierarchy/--distance_parameter_string
TREE_GRID = (32, 32, 16)            # n = 16384
TORUS = (16, 16, 16)                # n = 4096, also its stencil's grid
WANT_COMPILED = (True, False)       # (use_pallas, interpret) on the chip
SWEEPS = 16                         # device-engine sweep budget per call
REQUEST_TIMEOUT_S = 900.0           # one request, compiles included
STRONG_LANES, STRONG_ROUNDS = 4, 2  # the "strong" portfolio class
# (label, stencil weight seed, quality class, runtime seed)
COLD = ("cold", 1, None, 0)
WARM = ("warm", 1, None, 1)
REPEAT = ("repeat", 1, None, 0)
STRONG = ("strong", 3, "strong", 0)
# the spans whose host-clock durations each request reports
SPANS = ("plan.lower", "vcycle.construct", "vcycle.refine", "plan.refine")


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class CompileEvents:
    """JAX compile-cache events and compile seconds, read from
    ``jax.monitoring`` (the service compiles on its worker thread)."""

    _SPANS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.events: Counter = Counter()
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_):
        if event.startswith("/jax/compilation_cache/"):
            self.events[event.rsplit("/", 1)[1]] += 1

    def _on_duration(self, event: str, duration: float, **_):
        if event in self._SPANS:
            self.compile_s += duration


def stencil(dims, seed: int):
    """Seeded 3D stencil: ``grid3d`` structure, integer weights 1..9."""
    import numpy as np

    from repro.core import from_edges, grid3d
    g = grid3d(*dims)
    u, v, _ = g.edge_list()
    w = np.random.default_rng(seed).integers(1, 10, len(u))
    return from_edges(g.n, u, v, w.astype(np.float64))


def _plan(svc, g, quality):
    """The session's lowered plan for ``g`` under a quality class (a
    plan-cache hit: nothing is lowered again)."""
    spec = svc.mapper.spec
    if quality is not None:
        spec = spec.replace(portfolio=svc.quality_classes[quality])
    return svc.mapper.lower(svc.mapper.bucket_of(g, schedule=svc.schedule),
                            spec)


def _span_split(spans) -> list:
    """``[name, level, seconds]`` per reported span, in recorded order."""
    return [[s.name, s.attrs.get("level"), s.dur] for s in spans
            if s.name in SPANS]


def run_machine(name: str, machine, grid, requests, want=WANT_COMPILED,
                events: CompileEvents | None = None) -> list[dict]:
    """One machine's requests through a fresh placement service; every
    check of the module docstring, per request.  Returns the per-request
    records that were printed."""
    import numpy as np

    from repro.core import MultilevelSpec, qap_objective
    from repro.core.spec import PortfolioSpec
    from repro.launch.serve import placement_service
    from repro.launch.specs import placement_spec
    from repro.obs import get_tracer

    tracer = get_tracer()
    tracer.enable()
    spec = placement_spec(seed=0).replace(
        engine="device", backend="pallas", multilevel=MultilevelSpec(),
        max_sweeps=SWEEPS)
    strong = PortfolioSpec(lanes=STRONG_LANES, rounds=STRONG_ROUNDS)
    graphs = {}
    records = []
    with placement_service(machine, spec, quality_classes={
            "fast": None, "strong": strong}) as svc:
        traces = None
        for label, wseed, quality, seed in requests:
            g = graphs.setdefault(wseed, stencil(grid, wseed))
            compile0 = events.compile_s if events is not None else 0.0
            tracer.clear()
            t0 = time.perf_counter()
            res = svc.map(g, spec=spec.replace(seed=seed), quality=quality,
                          timeout=REQUEST_TIMEOUT_S)
            wall = time.perf_counter() - t0
            compile_s = (events.compile_s - compile0
                         if events is not None else float("nan"))
            plan = _plan(svc, g, quality)
            perm = np.asarray(res.perm)
            n = machine.n_pe
            check(np.array_equal(np.sort(perm), np.arange(n)),
                  f"{name}/{label}: perm is not a bijection on {n} PEs")
            j_ref = qap_objective(g, machine, perm)
            rel = abs(res.final_objective - j_ref) / max(abs(j_ref), 1.0)
            check(rel <= REL_TOL,
                  f"{name}/{label}: J={res.final_objective!r} vs host "
                  f"float64 {j_ref!r} (rel {rel:.3g} > {REL_TOL})")
            check(res.final_objective <= res.initial_objective,
                  f"{name}/{label}: final J {res.final_objective!r} > "
                  f"constructed J {res.initial_objective!r}")
            for eng in plan.engines:
                check((eng.use_pallas, eng.interpret) == tuple(want),
                      f"{name}/{label}: engine runs use_pallas="
                      f"{eng.use_pallas} interpret={eng.interpret}, "
                      f"want {want}")
            now = [eng.trace_count() for eng in plan.engines]
            if label in ("warm", "repeat"):
                check(now == traces,
                      f"{name}/{label}: engine traces {traces} -> {now} "
                      f"(a warm request retraced)")
            if quality is None:
                traces = now
            rec = {"machine": name, "request": label, "n": n,
                   "seed": seed,
                   "J": res.final_objective, "J_host_f64": j_ref,
                   "rel_err": rel, "J_constructed": res.initial_objective,
                   "wall_s": wall, "compile_s": compile_s,
                   "execute_s": wall - compile_s,
                   "construction_s": res.construction_seconds,
                   "search_s": res.search_seconds,
                   "finest_sweeps":
                       len(res.search_stats.objective_trace) - 1,
                   "engine_traces": now,
                   "spans": _span_split(tracer.spans())}
            records.append(rec)
            print(f"  {json.dumps(rec)}", flush=True)
            if label in ("cold", "strong"):
                d = plan.describe()
                print(f"  {name}/{label} kernels: "
                      f"{json.dumps(d['kernels'])}", flush=True)
                print(f"  {name}/{label} levels: "
                      f"{[lv['n'] for lv in d['levels']]} lower_s="
                      f"{d['timings']['lower_seconds']:.3f}", flush=True)
        stats = svc.stats()
    tracer.disable()
    tracer.clear()
    check(stats["errors"] == 0, f"{name}: service reported errors")
    check(stats["result_cache_hits"] >= 1,
          f"{name}: the repeat request missed the result cache")
    return records


def main() -> int:
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (platform {dev.platform!r}); "
                         f"this smoke runs on the chip only")
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no src/repro beside {__file__}")
    sys.path.insert(0, str(ROOT / "src"))

    from repro.core import Hierarchy
    from repro.runtime.device import enable_compile_cache
    from repro.topology import make_topology

    cache_dir = enable_compile_cache()
    events = CompileEvents()
    print(f"compile cache: {cache_dir}", flush=True)
    print("timings: one smoke run each, not a benchmark", flush=True)
    t0 = time.perf_counter()
    phases = [
        ("tree", Hierarchy.from_strings(*TREE), TREE_GRID, [COLD, REPEAT]),
        ("torus", make_topology("torus", dims=list(TORUS)), TORUS,
         [COLD, WARM, REPEAT, STRONG]),
    ]
    records = []
    for name, machine, grid, requests in phases:
        print(f"phase {name}: n={machine.n_pe}", flush=True)
        records += run_machine(name, machine, grid, requests, events=events)
    check(any(r["J"] < r["J_constructed"] for r in records),
          "no request lowered J below its construction")
    print(f"compile cache events: {dict(events.events)}; compile_s total "
          f"{events.compile_s:.3f}; wall {time.perf_counter() - t0:.3f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
