"""Jaxpr audit: lower every registered construction x topology through
``Mapper.lower`` and walk the traced engine entry points.

What it asserts, per lowered plan:

- **no host callbacks** — ``pure_callback``/``io_callback``/
  ``debug_callback`` (and the legacy host_callback forms) would smuggle
  a host round-trip into the sweep ``while_loop``;
- **no device transfers** — a ``device_put`` inside the jaxpr means a
  host constant crossed into the trace per call instead of at lower
  time;
- **accumulator dtype discipline** — every floating-point intermediate
  matches the plan's ``KernelConfig.acc_dtype``; a stray float64 aval
  means a Python float or np.float64 leaked into the trace and doubled
  the accumulator width.

Entry points audited per plan level: the raw sweep fn (``execute``), the
batch-vmapped form (``execute_batch``), the lane-shared vmapped form
(portfolio), and the Pallas objective kernel when the backend compiles
one.  Combos a construction cannot lower (e.g. hierarchy constructions
on a non-tree machine) are reported as skipped, not failed.
"""

from __future__ import annotations

import numpy as np

FORBIDDEN_PRIMITIVES = {
    "pure_callback", "io_callback", "debug_callback",
    "outside_call", "host_callback_call", "infeed", "outfeed",
}
TRANSFER_PRIMITIVES = {"device_put", "copy_device_to_host",
                       "copy_host_to_device"}

# one small instance per registered topology kind (16 PEs each)
SMALL_TOPOLOGIES: dict[str, dict] = {
    "tree": {"factors": [4, 4], "distances": [1.0, 10.0]},
    "fattree": {"arities": [4, 4]},
    "torus": {"dims": [4, 4]},
    "dragonfly": {"pes_per_router": 2, "routers_per_group": 2,
                  "n_groups": 4},
    "matrix": {"matrix": [[float(abs(i - j)) for j in range(16)]
                          for i in range(16)]},
}


def _iter_eqns(jaxpr):
    """Depth-first over eqns including every sub-jaxpr (while/cond/scan/
    pjit/pallas_call bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                yield from _iter_eqns(sub)


def _sub_jaxprs(val):
    from jax.extend import core
    if isinstance(val, core.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, core.Jaxpr):
        yield val
    elif isinstance(val, (list, tuple)):
        for item in val:
            yield from _sub_jaxprs(item)


def check_jaxpr(closed_jaxpr, acc_dtype: str = "float32") -> list[str]:
    """Problems found walking one closed jaxpr (empty = clean)."""
    problems: list[str] = []
    seen_prims: set[str] = set()
    bad_dtypes: set[str] = set()
    for eqn in _iter_eqns(closed_jaxpr.jaxpr):
        name = eqn.primitive.name
        seen_prims.add(name)
        if name in FORBIDDEN_PRIMITIVES:
            problems.append(f"forbidden host-callback primitive: {name}")
        if name in TRANSFER_PRIMITIVES:
            problems.append(f"device transfer inside trace: {name}")
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None and np.issubdtype(dt, np.floating) \
                    and str(dt) != acc_dtype:
                bad_dtypes.add(str(dt))
    for dt in sorted(bad_dtypes):
        problems.append(
            f"floating intermediate dtype {dt} != KernelConfig "
            f"acc_dtype {acc_dtype}")
    return sorted(set(problems))


def _ring_graph(n: int):
    from ..core.graph import from_edges
    u = np.arange(n, dtype=np.int64)
    v = (u + 1) % n
    w = np.ones(n, dtype=np.float64)
    return from_edges(n, u, v, w)


def _dummy_engine_args(eng, n: int, k: int = 8, e: int = 128,
                       p: int = 128):
    import jax.numpy as jnp
    return (
        jnp.zeros((n, k), jnp.int32),       # nbr
        jnp.zeros((n, k), jnp.float32),     # wgt
        jnp.zeros((e,), jnp.int32),         # eu
        jnp.zeros((e,), jnp.int32),         # ev
        jnp.zeros((e,), jnp.float32),       # ew
        jnp.zeros((p,), jnp.int32),         # us
        jnp.zeros((p,), jnp.int32),         # vs
        jnp.arange(n, dtype=jnp.int32),     # perm0
        eng._D,                             # packed/topology distances
        jnp.float32(1e-4),                  # eps
        jnp.int32(0),                       # tenure
        jnp.bool_(False),                   # dlb
        jnp.bool_(False),                   # collect telemetry
    )


def audit_plan(plan) -> list[str]:
    """Audit every traced entry point of one lowered plan."""
    import jax
    import jax.numpy as jnp
    problems: list[str] = []
    for lvl, (eng, cfg) in enumerate(
            zip(plan.engines or [], plan.kernel_configs)):
        n = eng.topology.n_pe
        args = _dummy_engine_args(eng, n)
        acc = cfg.acc_dtype
        jaxpr = jax.make_jaxpr(eng._refine_fn)(*args)
        for p in check_jaxpr(jaxpr, acc):
            problems.append(f"level {lvl} refine: {p}")
        if lvl == 0:
            # the serving/batch and portfolio lane entry points share the
            # fn; audit their vmapped jaxprs once at the finest level
            b = 2
            batched = tuple(
                jnp.broadcast_to(a, (b,) + a.shape)
                if i not in (8, 10, 11, 12) else a
                for i, a in enumerate(args))
            vfn = jax.vmap(eng._refine_fn,
                           in_axes=(0, 0, 0, 0, 0, 0, 0, 0, None, 0,
                                    None, None, None))
            vargs = list(batched)
            vargs[9] = jnp.zeros((b,), jnp.float32)     # per-lane eps
            for p in check_jaxpr(jax.make_jaxpr(vfn)(*vargs), acc):
                problems.append(f"batch vmap: {p}")
            lfn = jax.vmap(eng._refine_fn,
                           in_axes=(None, None, None, None, None, None,
                                    None, 0, None, 0, None, None, None))
            largs = list(args)
            largs[7] = jnp.broadcast_to(args[7], (b, n))
            largs[9] = jnp.zeros((b,), jnp.float32)
            for p in check_jaxpr(jax.make_jaxpr(lfn)(*largs), acc):
                problems.append(f"lane vmap: {p}")
    if getattr(plan, "_objective_fn", None) is not None:
        e = 128
        pu = jnp.zeros((e,), jnp.int32)
        pv = jnp.zeros((e,), jnp.int32)
        w = jnp.zeros((e,), jnp.float32)
        acc = plan.kernel_configs[0].acc_dtype
        for p in check_jaxpr(jax.make_jaxpr(plan._objective_fn)(pu, pv, w),
                             acc):
            problems.append(f"objective kernel: {p}")
    return problems


def run_audit(constructions: list[str] | None = None,
              topologies: list[str] | None = None) -> dict:
    """Lower and audit every construction x topology combo; returns a
    JSON-friendly report dict."""
    from ..core import Mapper, MappingSpec, list_constructions
    from ..topology import list_topologies, make_topology

    constructions = constructions or list_constructions()
    topologies = topologies or list_topologies()
    entries: list[dict] = []
    for topo_kind in topologies:
        params = SMALL_TOPOLOGIES.get(topo_kind)
        if params is None:
            entries.append({"construction": "*", "topology": topo_kind,
                            "status": "skipped",
                            "problems": ["no small instance registered "
                                         "for this topology kind"]})
            continue
        topo = make_topology(topo_kind, **params)
        g = _ring_graph(topo.n_pe)
        for cons in constructions:
            spec = MappingSpec(construction=cons, engine="device",
                               backend="pallas").validate()
            entry = {"construction": cons, "topology": topo_kind,
                     "status": "ok", "problems": []}
            try:
                plan = Mapper(topo, spec).lower_for(g)
            except (ValueError, TypeError, NotImplementedError) as exc:
                entry["status"] = "skipped"
                entry["problems"] = [f"lower: {exc}"]
                entries.append(entry)
                continue
            problems = audit_plan(plan)
            if problems:
                entry["status"] = "failed"
                entry["problems"] = problems
            entries.append(entry)
    failed = [e for e in entries if e["status"] == "failed"]
    return {"entries": entries, "ok": not failed}
