"""Readings that set the limits of the check, and the control that must
fail it.  Not part of a benchmark run.

    python3 bench/control.py --workload <cell> --seeds <s1,s2,...> \
        --seconds <s> [--fault-seeds <k>] [--out <file.json>]

In one process (set-up once), for each seed: draw that seed's requests,
run a short closed-loop window at the cell's own load, and read the
check's numbers for

* ``program`` -- the answers as the program gives them (the lower
  readings of each limit);
* ``control`` -- the same answers with J recomputed by the reference in
  bfloat16, the precision below the float32 the program's objective
  states (must fail ``j_rel_err``);
* ``unchanged`` (for the first ``--fault-seeds`` seeds, on requests
  drawn from the seed plus ``FAULT_SEED_STEP``) -- the program with its
  refinement returning the mapping it was given (must fail
  ``j_ratio``);
* ``altered`` (seed plus twice the step) -- the program's answers with
  the permutation rotated by one after it is produced, J as reported
  (must fail ``j_rel_err``).

Prints one JSON object with every reading per seed and writes it to
``--out`` when given.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

import harness  # noqa: E402

FAULT_SEED_STEP = 1_000_003


def bf16_objective(machine: dict, u, v, w, perm) -> float:
    """J of ``perm`` computed in bfloat16 on the default device: weights
    and distances rounded to bfloat16, products and the sum in it."""
    import jax.numpy as jnp

    import reference
    d = reference.distance(machine, perm[u], perm[v])
    terms = jnp.asarray(w, jnp.bfloat16) * jnp.asarray(d, jnp.bfloat16)
    return float(jnp.sum(terms, dtype=jnp.bfloat16))


def control_check(run: harness.Run) -> dict:
    """The check of ``run``'s answers with each reported J replaced by
    the bfloat16 reference's J of the same permutation."""
    def bf16_j(rec):
        n, u, v, w = run.pool[rec["index"]]
        return bf16_objective(run.config["machine"], u, v, w,
                              np.asarray(rec["result"].perm))

    run.reported_j = bf16_j
    try:
        return run.check()
    finally:
        del run.reported_j


def unchanged_refine():
    """Fault: the engine's refinement returns the mapping unchanged.
    Returns a switch ``set(on)``."""
    from repro.engine import sweep
    orig = sweep.RefinementEngine.refine
    state = {"on": False}

    def refine(self, g, perm, pairs, *a, **kw):
        keep = perm.copy()
        stats = orig(self, g, perm, pairs, *a, **kw)
        if state["on"]:
            perm[:] = keep
        return stats

    sweep.RefinementEngine.refine = refine
    return lambda on: state.__setitem__("on", on)


def altered_answer():
    """Fault: the placed permutation is rotated by one vertex after it
    is produced.  Returns a switch ``set(on)``."""
    from repro.core import plan
    orig = plan.MappingPlan.execute
    state = {"on": False}

    def execute(self, *a, **kw):
        res = orig(self, *a, **kw)
        if state["on"]:
            res.perm = np.roll(res.perm, 1)
        return res

    plan.MappingPlan.execute = execute
    return lambda on: state.__setitem__("on", on)


def values(checks: dict) -> dict:
    return {k: c["value"] for k, c in checks.items()}


def read(run: harness.Run, seed: int, seconds: float) -> dict:
    """One seed's window on the set-up service: the check's numbers."""
    import graphs
    gcfg = run.config["graph"]
    run.seed = seed
    run.pool = [graphs.draw(gcfg, graphs.request_rng(seed, 0, i))
                for i in range(int(run.traffic["pool"]))]
    run.graphs = [harness.to_graph(*t) for t in run.pool]
    run.spec = run.spec.replace(seed=harness.service_seed(seed))
    run.seconds = seconds
    run.records = []
    run.window()
    out = {"placements": len(run.records),
           "program": values(run.check())}
    out["control"] = values(control_check(run))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    return control(harness.BENCH.parent, args.workload,
                   [int(s) for s in args.seeds.split(",")], args.seconds,
                   args.fault_seeds, args.out)


def control(root, workload: str, seeds: list, seconds: float,
            fault_seeds: int, out_path=None, require_chip: bool = True
            ) -> dict:
    switches = {}
    faults = (lambda: switches.__setitem__("unchanged", unchanged_refine()),
              lambda: switches.__setitem__("altered", altered_answer()))
    run = harness.Run(root, workload, seeds[0], seconds, False, T_START,
                      require_chip=require_chip, faults=faults)
    try:
        run.setup()
    except harness.NoChip as exc:
        print(f"control: {exc}", file=sys.stderr)
        raise SystemExit(3) from None
    result = {"workload": workload, "seconds": seconds,
              "device": run.devices[0].device_kind, "seeds": {}}
    for i, seed in enumerate(seeds):
        rec = read(run, seed, seconds)
        if i < fault_seeds:
            # fresh requests for each fault: the service would answer a
            # repeat of this seed's graphs from its result cache
            for j, name in enumerate(("unchanged", "altered")):
                switches[name](True)
                rec[name] = read(run, seed + FAULT_SEED_STEP * (j + 1),
                                seconds)["program"]
                switches[name](False)
        result["seeds"][str(seed)] = rec
        print(json.dumps({str(seed): rec}), file=sys.stderr, flush=True)
    run.svc.close()
    if out_path:
        Path(out_path).parent.mkdir(parents=True, exist_ok=True)
        Path(out_path).write_text(json.dumps(result, indent=1))
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
    sys.exit(0)
