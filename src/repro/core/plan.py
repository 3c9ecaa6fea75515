"""`MappingPlan` — the frozen AOT artifact between ``Mapper.lower()`` and
the zero-recompile ``execute()`` hot path.

The guide's workflow is "build the machine model once, map many
communication graphs against it"; the staged API makes that split
explicit:

    plan = mapper.lower(ShapeBucket.of(g))      # AOT: resolve + compile
    result = plan.execute(g)                    # hot path: pad + run
    results = plan.execute_batch(graphs)        # one vmapped device call

``lower`` resolves *everything* that does not depend on the individual
graph — the construction/neighborhood registry handles, the partition
config, the multilevel machine pyramid and its coarse machines, one
:class:`~repro.engine.RefinementEngine` per level (jitted executables),
and the Pallas objective kernel for the ``pallas`` backend — so
``execute`` does no registry resolution, no cache lookups, and no
host-side reconstruction: it pads the graph into the plan's
:class:`~repro.core.spec.ShapeBucket` (inert by the DeviceGraph padding
invariants, so results are bit-identical to exact shapes) and runs the
compiled pipeline.  The seed is a *runtime* input (``execute(g, seed=)``)
— nothing compiled depends on it — which is why a Mapper session keys
its plan cache on the seed-free spec.

A plan is portable: ``to_json()``/``save()`` serialize its
:class:`~repro.core.spec.PlanSpec` (spec + machine model + bucket), and
``from_json()``/``load()``/pickle rebuild the live plan — same machine,
same level geometry, same kernel forms — in a fresh process, reproducing
the original mappings bit-for-bit.  ``describe()`` reports what was
compiled without executing anything (the ``viem --explain`` surface).
"""

from __future__ import annotations

import json
from collections import OrderedDict
from dataclasses import dataclass

import numpy as np

from ..obs import EngineTelemetry, get_tracer
from ..runtime.device import pallas_interpret
from .construction import resolve_construction
from .graph import CommGraph
from .local_search import (SearchStats, _cyclic_search,
                           parallel_sweep_search, resolve_neighborhood)
from .objective import dense_gain_matrix, qap_objective
from .partition import PartitionConfig
from .spec import MappingSpec, PlanSpec, ShapeBucket, TopologySpec

_TR = get_tracer()


@dataclass
class MappingResult:
    perm: np.ndarray
    initial_objective: float
    final_objective: float
    construction_seconds: float
    search_seconds: float
    search_stats: SearchStats | None

    @property
    def improvement(self) -> float:
        if self.initial_objective == 0:
            return 0.0
        return 1.0 - self.final_objective / self.initial_objective


# device-engine sweep budget per preconfiguration when the spec leaves
# max_sweeps=None — the same flag that tunes the partitioner and the
# multilevel pyramid (eco keeps the engine's historical default of 64)
_PRECONF_SWEEPS = {"fast": 32, "eco": 64, "strong": 128}


def sweep_budget(spec: MappingSpec) -> int:
    """Device-engine sweep budget: the spec's explicit ``max_sweeps``,
    else the preconfiguration's (fast 32, eco 64, strong 128)."""
    if spec.max_sweeps is not None:
        return spec.max_sweeps
    return _PRECONF_SWEEPS.get(spec.preconfiguration, 64)


class _LRU:
    """Bounded LRU mapping with visible accounting: ``builds`` counts
    misses, ``hits`` counts reuses, ``evictions`` counts entries dropped
    at the cap — surfaced through ``cache_info()`` so long-lived serving
    sessions can assert their memory stays bounded as requests vary."""

    def __init__(self, cap: int, on_evict=None):
        self.cap = int(cap)
        self.builds = 0
        self.hits = 0
        self.evictions = 0
        self._on_evict = on_evict
        self._data: OrderedDict = OrderedDict()

    def __len__(self) -> int:
        return len(self._data)

    def values(self):
        return self._data.values()

    def items(self):
        return self._data.items()

    def clear(self):
        self._data.clear()

    def get_or_build(self, key, build):
        val = self._data.get(key)
        if val is not None:
            self._data.move_to_end(key)
            self.hits += 1
            return val
        val = build()
        self.builds += 1
        self._data[key] = val
        while len(self._data) > self.cap:
            _, dropped = self._data.popitem(last=False)
            self.evictions += 1
            if self._on_evict is not None:
                self._on_evict(dropped)
        return val


def _structure_key(g: CommGraph, with_weights: bool = False) -> tuple:
    """Adjacency-structure fingerprint; weights are included only for
    neighborhoods that declare ``weight_dependent`` (none of the built-ins
    read them, so same-structure requests share one candidate set)."""
    key = (g.n, int(g.xadj[-1]), hash(g.xadj.tobytes()),
           hash(g.adjncy.tobytes()))
    if with_weights:
        key += (hash(np.asarray(g.adjwgt).tobytes()),)
    return key


def build_objective_kernel(topology, interpret: bool | None = None,
                           config=None):
    """The edge-list QAP objective entry for the topology's device-side
    distance form: closed-form tree/torus oracles computed in-register,
    or the gather path against the materialized matrix.  ``config`` (a
    :class:`~repro.kernels.config.KernelConfig`) fixes the reduction-tile
    geometry and, for the matrix form, stores the table in its lossless
    int8/int16 packing — bit-identical objectives, narrower gathers."""
    import functools

    from ..kernels import qap_objective as qk
    if interpret is None:
        interpret = pallas_interpret()
    geom = {} if config is None else {"lanes": config.lanes,
                                      "block_rows": config.block_rows}
    kp = topology.kernel_params()
    kind = kp[0]
    if kind == "tree":
        _, strides, dists = kp
        return functools.partial(qk.qap_objective_edges, strides=strides,
                                 dists=dists, interpret=interpret, **geom)
    if kind == "torus":
        _, dims, weights = kp
        return functools.partial(qk.qap_objective_edges_torus, dims=dims,
                                 weights=weights, interpret=interpret,
                                 **geom)
    if kind == "matrix":
        import jax.numpy as jnp
        dist_dtype = getattr(config, "dist_dtype", None)
        if dist_dtype is not None:
            from ..kernels.config import quantize_table
            D = jnp.asarray(quantize_table(topology.matrix(),
                                           dist_dtype)[0])
        else:
            D = jnp.asarray(topology.matrix(), jnp.float32)
        return functools.partial(qk.qap_objective_edges_matrix, D=D,
                                 interpret=interpret, **geom)
    raise ValueError(f"unknown kernel_params kind {kind!r}")


_PLAN_CACHE_CAPS = {"pairs": 16, "pyramids": 8}


class MappingPlan:
    """One lowered (machine × spec × bucket) pipeline — see module
    docstring.  Build via ``Mapper.lower(...)`` (session-cached) or
    directly; rebuild a serialized plan with ``from_dict``/``load``."""

    def __init__(self, machine, spec: MappingSpec | None = None,
                 bucket: ShapeBucket | None = None,
                 cache_caps: dict | None = None, engine_factory=None,
                 machine_factory=None):
        with _TR.span("plan.lower") as sp:
            self._lower(machine, spec, bucket, cache_caps,
                        engine_factory, machine_factory)
            sp.attrs["machine"] = self.topology.kind
            sp.attrs["engine"] = self.spec.engine
            sp.attrs["bucket"] = (None if self.bucket is None
                                  else self.bucket.tag())
        # the lower wall-time, kept on the plan so describe() can report
        # the AOT cost even when the tracer is disabled
        self.lower_seconds = sp.dur

    def _lower(self, machine, spec, bucket, cache_caps, engine_factory,
               machine_factory):
        from ..topology.base import as_topology
        self.topology = as_topology(machine)
        self.spec = (spec or MappingSpec()).validate()
        self.bucket = None if bucket is None else bucket.validate()
        caps = dict(_PLAN_CACHE_CAPS)
        caps.update(cache_caps or {})
        # --- stage 1 (lower): resolve every handle the hot path needs
        self._construct = resolve_construction(self.spec.construction)
        self._cfg = PartitionConfig.preconfiguration(
            self.spec.preconfiguration)
        self._nb = (None if self.spec.neighborhood is None else
                    resolve_neighborhood(self.spec.neighborhood))
        self.max_sweeps = sweep_budget(self.spec)
        self._ml = self.spec.resolved_multilevel()
        # machine-side level pyramid: level l pairs the PEs (2b, 2b+1)
        # of level l-1 (graph-independent, fixed by n and the V-cycle
        # knobs — what makes the level geometry part of the AOT
        # artifact).  ``machine_factory(depth)`` lets a Mapper session
        # share the chain across plans (coarsening materializes O(n²)
        # coarse distance matrices); a standalone plan builds its own.
        machines = [self.topology]
        if self._ml is not None:
            from ..multilevel.coarsen import coarsen_machine, pyramid_depth
            depth = pyramid_depth(self.topology.n_pe, *self._ml)
            if machine_factory is not None:
                machines = list(machine_factory(depth))
            else:
                for _ in range(depth - 1):
                    machines.append(coarsen_machine(machines[-1]))
        self.machines = machines
        # kernel geometry: ONE KernelConfig per pyramid level, derived
        # from the plan bucket + backend (overridable via spec.kernel) at
        # lower time — part of the AOT artifact, reported by describe()
        # under "kernels".  Coarse matrix machines whose averaged
        # distances are no longer exact integers simply derive
        # dist_dtype=None (float tables) — quantization is per level.
        import jax

        from ..kernels.config import derive_kernel_config
        self.kernel_backend = jax.default_backend()
        kspec = self.spec.kernel
        kover = {} if kspec is None else {
            "block_rows": kspec.block_rows, "lanes": kspec.lanes,
            "acc_dtype": kspec.acc_dtype, "quantize": kspec.quantize}
        self.kernel_configs = []
        for m in machines:
            kind = m.kernel_params()[0]
            self.kernel_configs.append(derive_kernel_config(
                kind, bucket=self.bucket, backend=self.kernel_backend,
                table=m.matrix() if kind == "matrix" else None, **kover))
        # one jitted engine per level (device engine only); jax compiles
        # lazily on the first execute, then every same-bucket request
        # reuses the executable.  ``engine_factory(machine, max_sweeps,
        # kernel_config) -> (engine, built)`` lets a Mapper session pool
        # engines across plans (they are bucket-agnostic — the bucket is
        # a per-call argument), with ``built`` telling this plan whether
        # to count the construction; a standalone plan builds its own.
        self.engine_builds = 0
        self.engines = None
        if self.spec.engine == "device":
            if engine_factory is None:
                from ..engine import RefinementEngine

                def engine_factory(m, sweeps, config=None):
                    return RefinementEngine(m, max_sweeps=sweeps,
                                            kernel_config=config), True
            self.engines = []
            for m, cfg in zip(machines, self.kernel_configs):
                eng, built = engine_factory(m, self.max_sweeps, cfg)
                self.engine_builds += bool(built)
                self.engines.append(eng)
        # portfolio runner: the vmapped multistart/tabu search layer over
        # the finest-level engine (repro.portfolio) — per-lane
        # constructions resolved here, at lower time, like everything else
        self.portfolio = None
        if self.spec.portfolio is not None:
            from ..portfolio import PortfolioRunner
            names = dict.fromkeys(
                [self.spec.construction]
                + list(self.spec.portfolio.constructions or ()))
            self.portfolio = PortfolioRunner(
                self.engines[0], self.spec.portfolio,
                [(nm, resolve_construction(nm)) for nm in names])
        self.kernel_compiles = 0
        self._objective_fn = None
        if self.spec.backend == "pallas":
            self._objective_fn = build_objective_kernel(
                self.topology, config=self.kernel_configs[0])
            self.kernel_compiles += 1
        self._swap_gain_fn = None
        # --- per-request state (graph-content keyed, LRU-bounded)
        self._pairs_lru = _LRU(caps["pairs"])
        self._pyramids = _LRU(caps["pyramids"])
        self.executes = 0
        self.execute_seconds_total = 0.0

    # -------------------------------------------------------------- describe
    def describe(self) -> dict:
        """Structured report of what was lowered/compiled — per level:
        size, machine kind, device kernel form, sweep budget."""
        n = self.topology.n_pe
        levels = []
        for i, m in enumerate(self.machines):
            levels.append({
                "level": i,
                "n": n >> i,
                "machine_kind": m.kind,
                "kernel_form": m.kernel_params()[0],
                "kernel_config": self.kernel_configs[i].tag(),
                "engine_compiled": self.engines is not None,
                "max_sweeps": (self.max_sweeps if self.engines is not None
                               else self.spec.max_sweeps),
            })
        return {
            "machine": {"kind": self.topology.kind, "n_pe": n},
            "bucket": None if self.bucket is None else self.bucket.to_dict(),
            "construction": self.spec.construction,
            "neighborhood": self.spec.neighborhood,
            "neighborhood_dist": self.spec.neighborhood_dist,
            "preconfiguration": self.spec.preconfiguration,
            "engine": self.spec.engine,
            "backend": self.spec.backend,
            "multilevel": (None if self._ml is None else
                           {"levels": self._ml[0],
                            "coarsen_min": self._ml[1]}),
            "portfolio": (None if self.portfolio is None else
                          self.portfolio.describe()),
            "kernels": {
                "backend": self.kernel_backend,
                "configs": [cfg.to_dict() for cfg in self.kernel_configs],
                "quantized": any(cfg.dist_dtype is not None
                                 for cfg in self.kernel_configs),
            },
            "levels": levels,
            "compiled": {"engines": self.engine_builds,
                         "kernels": self.kernel_compiles},
            "timings": {
                "lower_seconds": self.lower_seconds,
                "executes": self.executes,
                "execute_seconds_total": self.execute_seconds_total,
                # per-level device trace counts: compiles paid so far —
                # growth across same-bucket executes means a retrace
                "engine_traces": [eng.trace_count()
                                  for eng in (self.engines or [])],
            },
        }

    def cache_info(self) -> dict:
        return {
            "engine_builds": self.engine_builds,
            "kernel_compiles": self.kernel_compiles,
            "pair_builds": self._pairs_lru.builds,
            "pair_hits": self._pairs_lru.hits,
            "pair_evictions": self._pairs_lru.evictions,
            "pyramid_builds": self._pyramids.builds,
            "pyramid_hits": self._pyramids.hits,
            "pyramid_evictions": self._pyramids.evictions,
            "executes": self.executes,
        }

    def clear_request_caches(self) -> None:
        """Drop all per-request state (candidate pairs, pyramids, device
        uploads) while keeping the compiled artifacts — benchmarks use
        this to time the full per-graph cost honestly."""
        self._pairs_lru.clear()
        self._pyramids.clear()
        for eng in (self.engines or []):
            eng._dg_cache.clear()
            eng._pair_cache.clear()

    # --------------------------------------------------------- serialization
    def plan_spec(self) -> PlanSpec:
        """The serializable identity (spec + machine + bucket)."""
        mspec = self.spec
        if mspec.topology is None:
            mspec = mspec.replace(topology=TopologySpec.of(self.topology))
        return PlanSpec(mapping=mspec, bucket=self.bucket).validate()

    def to_dict(self) -> dict:
        return self.plan_spec().to_dict()

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2)

    @classmethod
    def from_dict(cls, d: dict) -> "MappingPlan":
        ps = PlanSpec.from_dict(d).validate()
        return cls(ps.mapping.topology.build(), ps.mapping, ps.bucket)

    @classmethod
    def from_json(cls, text: str) -> "MappingPlan":
        return cls.from_dict(json.loads(text))

    def save(self, path) -> None:
        with open(path, "w") as fh:
            fh.write(self.to_json())

    @classmethod
    def load(cls, path) -> "MappingPlan":
        with open(path) as fh:
            return cls.from_json(fh.read())

    def __reduce__(self):
        return (_plan_from_dict, (self.to_dict(),))

    # ------------------------------------------------------------- hot path
    def _check(self, g: CommGraph) -> None:
        if g.n != self.topology.n_pe:
            raise ValueError(f"graph has {g.n} processes but the machine "
                             f"has {self.topology.n_pe} PEs — they must "
                             f"match (guide §4.1)")
        if self.bucket is not None and not self.bucket.admits(g):
            raise ValueError(
                f"graph (max_deg="
                f"{int(np.diff(g.xadj).max(initial=0))}, "
                f"E={g.num_edges}) exceeds the plan bucket "
                f"{self.bucket.tag()} — lower a larger plan")

    def _pairs(self, g: CommGraph, seed: int) -> np.ndarray:
        """Candidate pairs for ``g``, LRU-cached, under a ``plan.pairs``
        span carrying the pair count and whether the LRU hit."""
        nb = self._nb
        lru = self._pairs_lru
        with _TR.span("plan.pairs", n=g.n) as sp:
            hits = lru.hits
            # unseeded (deterministic) generators share one cache entry
            # across seeds — only genuinely randomized ones key on seed
            key = ((seed if nb.seeded else None,)
                   + _structure_key(g, nb.weight_dependent))
            pairs = lru.get_or_build(
                key, lambda: nb.generate(g,
                                         dist=self.spec.neighborhood_dist,
                                         seed=seed,
                                         max_pairs=self.spec.max_pairs))
            sp.attrs.update(pairs=len(pairs), hit=lru.hits > hits)
        return pairs

    def objective(self, g: CommGraph, perm: np.ndarray) -> float:
        """J(C, D, Π) via the plan's backend: host numpy float64, or the
        Pallas edge-list kernel compiled at lower time."""
        if self._objective_fn is not None:
            u, v, w = g.edge_list()
            perm = np.asarray(perm, dtype=np.int64)
            return float(self._objective_fn(perm[u].astype(np.int32),
                                            perm[v].astype(np.int32),
                                            w.astype(np.float32)))
        return qap_objective(g, self.topology, perm)

    def gain_matrix(self, g: CommGraph, perm: np.ndarray) -> np.ndarray:
        """Full pair-exchange gain matrix via the plan's backend (dense —
        small/medium n)."""
        perm = np.asarray(perm, dtype=np.int64)
        D = self.topology.matrix()
        if self.spec.backend == "pallas":
            if self._swap_gain_fn is None:
                import functools

                from ..kernels.swap_gain import swap_gain_matrix
                self._swap_gain_fn = functools.partial(
                    swap_gain_matrix, interpret=pallas_interpret())
                self.kernel_compiles += 1
            C = g.to_dense()
            B = D[np.ix_(perm, perm)]
            return np.asarray(self._swap_gain_fn(C, B))
        return dense_gain_matrix(g.to_dense(), D, perm)

    def _construct_one(self, g: CommGraph, seed: int
                       ) -> tuple[np.ndarray, float, float]:
        with _TR.span("plan.construct", n=g.n,
                      construction=self.spec.construction) as sp:
            perm = self._construct(g, self.topology, seed=seed,
                                   cfg=self._cfg)
        return perm, sp.dur, self.objective(g, perm)

    def _finish(self, g: CommGraph, perm: np.ndarray, j0: float,
                t_cons: float, t_search: float,
                stats: SearchStats | None) -> MappingResult:
        """Result assembly: the final objective is the search's
        incremental host float64 value on the ``numpy`` backend
        (legacy-identical) and recomputed through the plan backend
        otherwise, so j0 and jf stay comparable."""
        if stats is None:
            jf = j0
        elif self.spec.backend == "numpy":
            jf = stats.final_objective
        else:
            jf = self.objective(g, perm)
        return MappingResult(perm=perm, initial_objective=j0,
                             final_objective=jf,
                             construction_seconds=t_cons,
                             search_seconds=t_search, search_stats=stats)

    def execute(self, g: CommGraph, seed: int | None = None,
                telemetry: bool = False) -> MappingResult:
        """Map one graph through the lowered pipeline.  ``seed`` is the
        runtime seed (defaults to the plan spec's) — it steers the
        construction and any seeded neighborhood, never the compiled
        artifacts.  ``telemetry`` asks the device engine to collect its
        per-sweep counters (``result.search_stats.telemetry``) — a
        runtime toggle, masked on-device, never a retrace; it is on
        whenever the global tracer records."""
        seed = self.spec.seed if seed is None else int(seed)
        telemetry = telemetry or _TR.enabled
        self._check(g)
        self.executes += 1
        with _TR.span("plan.execute", n=g.n, engine=self.spec.engine,
                      seed=seed) as sp:
            if self.portfolio is not None:
                res = self._execute_portfolio(g, seed, telemetry)
            elif self._ml is not None:
                res = self._execute_multilevel(g, seed, telemetry)
            else:
                res = self._execute_flat(g, seed, telemetry)
            sp.attrs["final_objective"] = res.final_objective
        self.execute_seconds_total += sp.dur
        return res

    def _execute_flat(self, g: CommGraph, seed: int,
                      telemetry: bool) -> MappingResult:
        perm, t_cons, j0 = self._construct_one(g, seed)
        stats = None
        with _TR.span("plan.refine", n=g.n,
                      engine=self.spec.engine) as rsp:
            if self._nb is not None:
                pairs = self._pairs(g, seed)
                rsp.attrs["pairs"] = len(pairs)
                kw = {} if self.spec.max_sweeps is None else \
                    {"max_sweeps": self.spec.max_sweeps}
                if self.spec.engine == "device":
                    eng = self.engines[0]
                    before = eng.trace_count()
                    stats = eng.refine(g, perm, pairs, j0=j0,
                                       bucket=self.bucket,
                                       telemetry=telemetry)
                    rsp.attrs["retraces"] = eng.trace_count() - before
                    if stats.telemetry is not None:
                        rsp.attrs["telemetry"] = stats.telemetry
                elif self.spec.parallel_sweeps:
                    stats = parallel_sweep_search(g, self.topology, perm,
                                                  pairs, seed=seed, **kw)
                else:
                    stats = _cyclic_search(g, self.topology, perm, pairs,
                                           shuffle=self._nb.shuffle,
                                           seed=seed, **kw)
        return self._finish(g, perm, j0, t_cons, rsp.dur, stats)

    def candidate_pairs(self, g: CommGraph,
                        seed: int | None = None) -> np.ndarray:
        """The plan's candidate exchange pairs for this graph — the same
        (p, 2) array ``execute`` refines over, LRU-cached per structure.
        Exposed so incremental callers (:mod:`repro.monitor`) can build a
        runtime activity mask over a *fixed* pair set and keep the padded
        pair shape — and therefore the compiled executable — unchanged
        across warm re-executions."""
        if self._nb is None:
            return np.zeros((0, 2), np.int64)
        seed = self.spec.seed if seed is None else int(seed)
        return self._pairs(g, seed)

    def execute_warm(self, g: CommGraph, perm: np.ndarray,
                     pairs: np.ndarray | None = None,
                     active: np.ndarray | None = None,
                     seed: int | None = None,
                     telemetry: bool = False) -> MappingResult:
        """Warm-start: refine an incumbent ``perm`` on ``g`` with NO
        construction phase — the incremental-remap hot path.

        ``pairs`` fixes the candidate set (default: the plan's own
        ``candidate_pairs(g)``); ``active`` is an optional boolean mask
        over it.  Inactive pairs are replaced by inert ``(u, u)``
        self-pairs — exactly the engine's padding convention, zero gain
        and never selected — so the array length, the padded pair shape
        P, and the compiled executable are all unchanged: masking, never
        retracing (trace-count tested).  Dirty-region remaps pass the
        mask of pairs touching drifted vertices and leave the rest of
        the mapping frozen in place by construction of the sweep.

        The incumbent is *not* mutated; the result carries the refined
        copy.  ``initial_objective`` is the incumbent's objective on
        ``g``, so ``result.improvement`` reads as recovered drift.
        ``telemetry`` is as for :meth:`execute`."""
        seed = self.spec.seed if seed is None else int(seed)
        telemetry = telemetry or _TR.enabled
        self._check(g)
        self.executes += 1
        perm = np.array(perm, dtype=np.int64, copy=True)
        with _TR.span("plan.execute_warm", n=g.n, engine=self.spec.engine,
                      seed=seed) as sp:
            j0 = self.objective(g, perm)
            stats = None
            with _TR.span("plan.refine", n=g.n, engine=self.spec.engine,
                          warm=True) as rsp:
                if pairs is None:
                    pairs = self.candidate_pairs(g, seed)
                pairs = np.asarray(pairs, dtype=np.int64)
                if active is not None:
                    active = np.asarray(active, dtype=bool)
                    if active.shape != (len(pairs),):
                        raise ValueError(
                            f"active mask shape {active.shape} does not "
                            f"match {len(pairs)} candidate pairs")
                    masked = np.where(active[:, None], pairs,
                                      pairs[:, [0, 0]])
                else:
                    masked = pairs
                rsp.attrs["pairs"] = len(pairs)
                rsp.attrs["active"] = (len(pairs) if active is None
                                       else int(active.sum()))
                if len(pairs) and self.spec.engine == "device":
                    eng = self.engines[0]
                    before = eng.trace_count()
                    stats = eng.refine(g, perm, masked, j0=j0,
                                       bucket=self.bucket,
                                       telemetry=telemetry)
                    rsp.attrs["retraces"] = eng.trace_count() - before
                    if stats.telemetry is not None:
                        rsp.attrs["telemetry"] = stats.telemetry
                elif len(pairs):
                    live = masked if active is None else pairs[active]
                    kw = {} if self.spec.max_sweeps is None else \
                        {"max_sweeps": self.spec.max_sweeps}
                    stats = parallel_sweep_search(g, self.topology, perm,
                                                  live, seed=seed, **kw)
            res = self._finish(g, perm, j0, 0.0, rsp.dur, stats)
            sp.attrs["final_objective"] = res.final_objective
        self.execute_seconds_total += sp.dur
        return res

    def execute_batch(self, graphs, seed: int | None = None,
                      telemetry: bool = False) -> list[MappingResult]:
        """Map a batch through one vmapped device dispatch per level.

        Every graph must fit the plan bucket (they need not be
        structurally identical — padding into the common bucket is
        inert), so the whole batch shares the compiled executables.
        ``telemetry`` is as for :meth:`execute`."""
        graphs = list(graphs)
        if not graphs:
            return []
        seed = self.spec.seed if seed is None else int(seed)
        telemetry = telemetry or _TR.enabled
        if self.portfolio is not None:
            # the lane axis already fills the vmap batch dimension — each
            # graph runs its own portfolio (lanes × graphs would multiply
            # the device footprint, not amortize it)
            return [self.execute(g, seed=seed, telemetry=telemetry)
                    for g in graphs]
        if self._ml is not None:
            for g in graphs:
                self._check(g)
            self.executes += len(graphs)
            return self._execute_batch_multilevel(graphs, seed, telemetry)
        if self.spec.engine != "device" or self._nb is None:
            return [self.execute(g, seed=seed, telemetry=telemetry)
                    for g in graphs]
        for g in graphs:
            self._check(g)
        self.executes += len(graphs)
        with _TR.span("plan.execute_batch", batch=len(graphs),
                      n=graphs[0].n) as bsp:
            # duplicate lanes (the service pads batches by cycling its
            # tick's graphs) share one construction; every lane still
            # gets its own perm array because the engine refines in place
            memo: dict = {}
            prepped = []
            for g in graphs:
                hit = memo.get(id(g))
                if hit is None:
                    hit = memo[id(g)] = self._construct_one(g, seed)
                else:
                    hit = (hit[0].copy(), hit[1], hit[2])
                prepped.append(hit)
            perms = [perm for perm, _, _ in prepped]
            # timed window matches execute()'s: pair gen + refinement
            eng = self.engines[0]
            before = eng.trace_count()
            with _TR.span("plan.refine", batch=len(graphs)) as rsp:
                pairs_list = [self._pairs(g, seed) for g in graphs]
                stats_list = eng.refine_batch(
                    graphs, perms, pairs_list,
                    j0s=[j0 for _, _, j0 in prepped],
                    bucket=self.bucket, telemetry=telemetry)
            rsp.attrs["retraces"] = eng.trace_count() - before
            t_search = rsp.dur / len(graphs)
        self.execute_seconds_total += bsp.dur
        return [self._finish(g, perm, j0, t_cons, t_search, stats)
                for g, (perm, t_cons, j0), stats
                in zip(graphs, prepped, stats_list)]

    # ------------------------------------------------------------ multilevel
    def _pyramid(self, g: CommGraph, seed: int) -> list:
        """The graph-side level pyramid, LRU-cached per (graph structure
        *and weights* — the heavy-edge matching reads them, seed for
        seeded neighborhoods)."""
        from ..multilevel.coarsen import build_pyramid
        levels, cmin = self._ml
        if self._nb is None:
            pair_fn = lambda gg: np.zeros((0, 2), np.int64)  # noqa: E731
            skey = None
        else:
            nb = self._nb
            pair_fn = lambda gg: nb.generate(        # noqa: E731
                gg, dist=self.spec.neighborhood_dist, seed=seed,
                max_pairs=self.spec.max_pairs)
            skey = seed if nb.seeded else None
        key = (("pyramid", skey)
               + _structure_key(g, with_weights=True))
        return self._pyramids.get_or_build(
            key, lambda: build_pyramid(g, self.machines, levels, cmin,
                                       pair_fn))

    def _execute_multilevel(self, g: CommGraph, seed: int,
                            telemetry: bool = False) -> MappingResult:
        """The coarsen → map → uncoarsen V-cycle (:mod:`repro.multilevel`)
        over the plan's per-level engines; the reported initial objective
        is the projected (pre-refinement) finest-level objective."""
        from ..multilevel import vcycle_map
        pyramid = self._pyramid(g, seed)
        with _TR.span("plan.vcycle", n=g.n, levels=len(pyramid)) as sp:
            res = vcycle_map(pyramid, self.engines, self._construct,
                             self._cfg, seed=seed,
                             objective0=self.objective,
                             bucket=self.bucket, telemetry=telemetry)
        t_search = sp.dur - res.construction_seconds
        return self._finish(g, res.perm, res.initial_objective,
                            res.construction_seconds, t_search, res.stats)

    def _execute_batch_multilevel(self, graphs, seed: int,
                                  telemetry: bool = False
                                  ) -> list[MappingResult]:
        """Batched V-cycles: the forced perfect pairing gives every
        same-n graph the same level geometry, so each level's refinement
        runs as ONE vmapped engine call across the whole batch."""
        from ..multilevel import vcycle_map_batch
        pyramids = [self._pyramid(g, seed) for g in graphs]
        with _TR.span("plan.vcycle", batch=len(graphs),
                      levels=len(pyramids[0])) as sp:
            results = vcycle_map_batch(
                pyramids, self.engines, self._construct, self._cfg,
                seed=seed, objective0=self.objective, bucket=self.bucket,
                telemetry=telemetry)
        self.execute_seconds_total += sp.dur
        elapsed = sp.dur / len(graphs)
        return [self._finish(g, r.perm, r.initial_objective,
                             r.construction_seconds,
                             elapsed - r.construction_seconds, r.stats)
                for g, r in zip(graphs, results)]

    # ------------------------------------------------------------- portfolio
    def _execute_portfolio(self, g: CommGraph, seed: int,
                           telemetry: bool = False) -> MappingResult:
        """The portfolio pipeline (:mod:`repro.portfolio`): L lanes
        constructed with per-lane seeds, refined per level as ONE vmapped
        lane call (descending the V-cycle when the spec is multilevel),
        then the device round loop — kick → refine → tournament — at the
        finest level.  ``PortfolioSpec(lanes=1, rounds=1, tabu_tenure=0)``
        degenerates to the non-portfolio pipeline bit-for-bit (tested).

        With ``telemetry``, the finest-level lane refinement collects
        per-lane engine counters and the merged
        :class:`~repro.obs.EngineTelemetry` rides the result's stats
        (the round loop itself stays counter-free — one device dispatch,
        sweep/swap totals only)."""
        runner = self.portfolio
        empty = np.zeros((0, 2), np.int64)
        lane_stats = None
        pyramid = self._pyramid(g, seed) if self._ml is not None else None
        with _TR.span("plan.construct", lanes=runner.pspec.lanes) as csp:
            if pyramid is not None:
                coarsest = pyramid[-1]
                perms = runner.construct_lanes(
                    coarsest.graph, coarsest.machine, self._cfg, seed)
            else:
                perms = runner.construct_lanes(g, self.topology,
                                               self._cfg, seed)
        t_cons = csp.dur
        with _TR.span("plan.refine", n=g.n,
                      lanes=runner.pspec.lanes) as rsp:
            if pyramid is not None:
                from ..multilevel.coarsen import project_perm
                j0s = []
                pairs0 = pyramid[0].pairs
                for lvl in range(len(pyramid) - 1, -1, -1):
                    level = pyramid[lvl]
                    if lvl == 0:
                        j0s = [self.objective(level.graph, p)
                               for p in perms]
                    else:
                        j0s = [qap_objective(level.graph, level.machine,
                                             p) for p in perms]
                    lane_stats = runner.refine_lanes(
                        level.graph, perms, level.pairs, j0s=j0s,
                        bucket=self.bucket if lvl == 0 else None,
                        engine=self.engines[lvl],
                        telemetry=telemetry and lvl == 0)
                    if lvl > 0:
                        perms = [project_perm(p, level.fine_u,
                                              level.fine_v)
                                 for p in perms]
            else:
                j0s = [self.objective(g, p) for p in perms]
                pairs0 = self._pairs(g, seed) if self._nb is not None \
                    else empty
                lane_stats = runner.refine_lanes(g, perms, pairs0,
                                                 j0s=j0s,
                                                 bucket=self.bucket,
                                                 telemetry=telemetry)
            res = runner.run_rounds(g, perms, pairs0, j0s,
                                    bucket=self.bucket, seed=seed)
            rsp.attrs["rounds"] = res.rounds
        t_search = rsp.dur
        j0 = min(j0s) if j0s else self.objective(g, res.perm)
        stats = SearchStats()
        stats.initial_objective = j0
        stats.final_objective = qap_objective(g, self.topology, res.perm)
        stats.swaps = res.swaps
        stats.evaluated = res.sweeps * len(pairs0)
        if self._ml is None:
            stats.swaps += sum(s.swaps for s in lane_stats)
            stats.evaluated += sum(s.evaluated for s in lane_stats)
        stats.objective_trace = [j0] + res.round_objectives
        if telemetry and lane_stats:
            tels = [s.telemetry for s in lane_stats
                    if s.telemetry is not None]
            if tels:
                stats.telemetry = EngineTelemetry.merge(tels)
                rsp.attrs["telemetry"] = stats.telemetry
        return self._finish(g, res.perm, j0, t_cons, t_search, stats)


def _plan_from_dict(d: dict) -> MappingPlan:
    """Module-level pickle entry (``MappingPlan.__reduce__``)."""
    return MappingPlan.from_dict(d)
