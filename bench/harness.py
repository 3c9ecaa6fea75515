"""One benchmark run: set-up, the measured window, the check, the result.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``bench/configs/<config>.json`` (the entry's ``file``): the machine,
  the communication-graph family, the service spec and the limits of
  the check;
* ``bench/traffic/<traffic>.json``: how requests arrive;
* ``bench/metrics/<metric>.py``: a ``read(ctx)`` that returns the
  metric's value, or ``None`` where the run has nothing to read.

A run builds the program's placement service over the machine, draws a
pool of requests from the seed, warms every shape the pool reaches with
warm-up graphs of its own, then drives a closed loop for ``--seconds``:
issue a burst of new graphs, wait for their answers, issue the next.
The window closes when the last issued request is answered, so every
placement in it counts whole.  Afterwards each answer is checked
against the plain float64 reference (``reference.py``).
"""

from __future__ import annotations

import importlib.util
import json
import math
import queue
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
RESULT_WAIT_S = 60.0    # an answer may come this late after the close
WARMUP_WAIT_S = 900.0   # a warm-up placement, compiles included
SHORT_GAP_S = 1e-3      # idle gaps shorter than this are not attributed
SHORT_GAP = "between device ops (< 1 ms)"


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell asks."""


# ------------------------------------------------------------- registry
def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json")


def load_cell(root: Path, workload: str) -> tuple[dict, dict, dict, dict]:
    """``(benchmark, cell, config, traffic)`` for a workload name."""
    bench = load_json(root / "BENCHMARK.json")
    cell = find(bench["workloads"], workload, "workload")
    entry = find(bench["configs"], cell["config"], "config")
    config = load_json(root / entry["file"])
    traffic = load_json(root / "bench" / "traffic"
                        / f"{cell['traffic']}.json")
    return bench, cell, config, traffic


def load_metric(root: Path, name: str):
    """The ``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    for d in (root / "bench", path.parent):
        if str(d) not in sys.path:
            sys.path.insert(0, str(d))
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: dict, trace: bool) -> list:
    """The metric entries this cell reports in a run of this kind."""
    name = cell["name"]
    if not trace:
        return [m for m in bench["end_to_end"]
                if name in m.get("workloads", [name])]
    reported = {m["name"] for m in cell_metrics(bench, cell, False)}
    return [m for m in bench["per_layer"]
            if name in m.get("workloads", [name])
            and m["moves"] in reported]


# ------------------------------------------------------------ the device
def look_for_chip(chips: int):
    """The devices JAX found; raises :class:`NoChip` off the TPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform "
                     f"{devices[0].platform!r}; this benchmark measures "
                     f"the chip only")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices


def peak_memory(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


# -------------------------------------------------------------- the program
def program(root: Path):
    """Import the program under test from ``<root>/src``."""
    src = root / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"bench: no program at {src}/repro")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import repro
    return repro


def to_graph(n, u, v, w):
    from repro.core import from_edges
    return from_edges(n, u, v, w)


def build_service(config: dict, traffic: dict, seed: int):
    """The program's placement service over the configuration's machine,
    with the configuration's spec and the traffic's quality class."""
    from repro.launch.serve import placement_service
    from repro.launch.specs import placement_spec
    from repro.topology import make_topology
    m = dict(config["machine"])
    machine = make_topology(m.pop("kind"), **m)
    spec = placement_spec(seed=service_seed(seed)).replace(**config["spec"])
    svc = placement_service(machine, spec,
                            quality_classes={traffic["quality"]: None})
    return svc, spec


def service_seed(seed: int) -> int:
    """The service spec's seed, drawn from ``--seed`` (any size) into
    the 31-bit range the program's seeds take."""
    return int(np.random.default_rng([int(seed), 2]).integers(0, 2**31 - 1))


def shape_key(g, svc) -> tuple:
    """What a graph's compiled shapes depend on: its plan bucket and the
    neighbour-row width its device graph is built with before padding
    into the bucket (maximum degree rounded up to 8)."""
    deg = int(np.diff(g.xadj).max(initial=0))
    return (svc.mapper.bucket_of(g, schedule=svc.schedule).tag(),
            max(8, -(-deg // 8) * 8), g.num_edges)


# ------------------------------------------------------------------- a run
class Run:
    """One run of one cell (see module docstring).  ``faults`` are
    callables applied to the program after it is imported (for the
    tests and the control; never in a benchmark run)."""

    def __init__(self, root: Path, workload: str, seed: int,
                 seconds: float, trace: bool, t_start: float,
                 require_chip: bool = True, faults=()):
        self.root = Path(root)
        self.bench, self.cell, self.config, self.traffic = load_cell(
            self.root, workload)
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start
        self.require_chip = require_chip
        self.faults = faults
        self.records: list = []

    # ------------------------------------------------------------- set-up
    def setup(self):
        import jax
        self.devices = (look_for_chip(self.cell["chips"])
                        if self.require_chip else jax.devices())
        program(self.root)
        # compiles shorter than a second are cached too, so a later run
        # in this checkout loads every program it needs
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        from repro.runtime.device import enable_compile_cache
        enable_compile_cache()
        from compile_events import CompileEvents
        self.compiles = CompileEvents()
        for fault in self.faults:
            fault()
        import graphs
        gcfg = self.config["graph"]
        pool = int(self.traffic["pool"])
        self.pool = [graphs.draw(gcfg, graphs.request_rng(self.seed, 0, i))
                     for i in range(pool)]
        self.graphs = [to_graph(*t) for t in self.pool]
        self.svc, self.spec = build_service(self.config, self.traffic,
                                            self.seed)
        self.warmup(graphs)
        self.svc.reset_stats()

    def warmup(self, graphs):
        """Run one warm-up graph of each shape key the pool reaches.
        Warm-up graphs come from streams of their own, so nothing the
        window maps is computed or cached beforehand: draws first, then,
        for a shape no draw reached, a draw rewired to it."""
        want = {shape_key(g, self.svc) for g in self.graphs}
        done: set = set()
        gcfg = self.config["graph"]
        for i in range(int(self.traffic["warmup_draws"])):
            if want <= done:
                break
            g = to_graph(*graphs.draw(gcfg,
                                      graphs.request_rng(self.seed, 1, i)))
            key = shape_key(g, self.svc)
            if key in want and key not in done:
                self.place([g], time.perf_counter() + WARMUP_WAIT_S)
                done.add(key)
        for j, key in enumerate(sorted(want - done)):
            # a shape no draw reached: a warm-up draw rewired to it
            _, width, num_edges = key
            rng = graphs.request_rng(self.seed, 2, j)
            g = to_graph(*graphs.reshaped(graphs.draw(gcfg, rng), width,
                                          num_edges, gcfg["weights"], rng))
            if shape_key(g, self.svc) != key:
                raise SystemExit(f"bench: no warm-up graph reached the "
                                 f"shape {key} of the request pool")
            self.place([g], time.perf_counter() + WARMUP_WAIT_S)

    # ------------------------------------------------------------- window
    def place(self, graphs_, deadline: float) -> list:
        """Submit graphs at once and wait for every answer until the
        ``perf_counter`` time ``deadline``: ``(ticket, result, seconds)``
        in submission order; ``result`` is an exception where the
        request failed, and ``None`` where no answer came in time."""
        q = self.traffic["quality"]
        t0 = time.perf_counter()
        tickets = [self.svc.submit(g, spec=self.spec, quality=q)
                   for g in graphs_]
        got = {}
        while len(got) < len(tickets):
            try:
                t, res = self.svc.results.get(
                    timeout=max(deadline - time.perf_counter(), 0.0))
            except queue.Empty:
                break
            got[t] = (res, time.perf_counter() - t0)
        return [(t,) + got.get(t, (None, None)) for t in tickets]

    def window(self):
        import jax
        burst = int(self.traffic["burst"])
        tracer = None
        if self.trace:
            from repro.obs import get_tracer
            tracer = get_tracer()
            tracer.clear()
            tracer.enable()
            self.logdir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.logdir)
        c0 = self.compiles.compiles()
        self.t_window = time.perf_counter()
        self.setup_s = self.t_window - self.t_start
        deadline = self.t_window + self.seconds + RESULT_WAIT_S
        issued = 0
        while (time.perf_counter() - self.t_window < self.seconds
               and issued + burst <= len(self.graphs)):
            idx = list(range(issued, issued + burst))
            issued += burst
            with jax.profiler.TraceAnnotation("bench.request"):
                t_issue = time.perf_counter()
                answers = self.place([self.graphs[i] for i in idx],
                                     deadline)
            for i, (_, res, secs) in zip(idx, answers):
                self.records.append({"index": i, "result": res,
                                     "latency_s": secs,
                                     "t_issue": t_issue})
        self.window_s = time.perf_counter() - self.t_window
        self.window_compiles = self.compiles.compiles() - c0
        self.pool_exhausted = issued + burst > len(self.graphs)
        if self.trace:
            jax.profiler.stop_trace()
            tracer.disable()
            self.spans = [s.to_dict() for s in tracer.drain()
                          if s.t0 >= self.t_window]
            from xplane import DeviceTrace
            self.device_trace = DeviceTrace.from_dir(self.logdir)
            shutil.rmtree(self.logdir, ignore_errors=True)

    # -------------------------------------------------------------- check
    def check(self) -> dict:
        """Every answer of the window against the float64 reference:
        the compared numbers, each beside its limit."""
        import reference
        machine = self.config["machine"]
        limits = self.config["limits"]
        invalid = missing = failed = 0
        rel_err = 0.0
        ratios = []
        for rec in self.records:
            res = rec["result"]
            if res is None:
                missing += 1
                continue
            if isinstance(res, Exception):
                failed += 1
                continue
            n, u, v, w = self.pool[rec["index"]]
            perm = np.asarray(res.perm)
            if not reference.is_bijection(perm, n):
                invalid += 1
                continue
            j_ref = reference.objective(machine, u, v, w, perm)
            j_id = reference.objective(machine, u, v, w, np.arange(n))
            rel_err = max(rel_err, abs(float(self.reported_j(rec))
                                       - j_ref) / max(j_ref, 1.0))
            ratios.append(j_ref / j_id)
        self.failed = failed
        self.j_ratio = (math.exp(float(np.mean(np.log(ratios))))
                        if ratios else None)
        checks = {
            "missing": (missing, limits["missing"]),
            "perm_invalid": (invalid, limits["perm_invalid"]),
            "j_rel_err": (rel_err, limits["j_rel_err"]),
            "j_ratio": (self.j_ratio if ratios else math.inf,
                        limits["j_ratio"]),
        }
        return {k: {"value": v, "limit": lim}
                for k, (v, lim) in checks.items()}

    def reported_j(self, rec) -> float:
        """The J the program reported for a placement."""
        return rec["result"].final_objective

    # ------------------------------------------------------------- result
    def metrics_e2e(self) -> dict:
        done = sum(1 for r in self.records
                   if r["result"] is not None
                   and not isinstance(r["result"], Exception))
        values = {
            "placement_s": self.window_s / done if done else None,
            "j_ratio": self.j_ratio,
            "setup_s": self.setup_s,
        }
        return values

    def context(self) -> dict:
        """What a per-layer metric's ``read(ctx)`` may read."""
        peaks = load_json(BENCH / "peaks.json")["devices"]
        kind = self.devices[0].device_kind
        buckets = [self.svc.mapper.bucket_of(self.graphs[r["index"]],
                                             schedule=self.svc.schedule)
                   for r in self.records]
        return {
            "spans": getattr(self, "spans", []),
            "placements": [r for r in self.records
                           if r["result"] is not None
                           and not isinstance(r["result"], Exception)],
            "t_window": self.t_window,
            "window_s": self.window_s,
            "compiles": self.window_compiles,
            "trace": getattr(self, "device_trace", None),
            "peaks": peaks.get(kind),
            "device_kind": kind,
            "config": self.config,
            "spec": self.spec,
            "buckets": buckets,
            "kernel_config": self.kernel_config(buckets),
        }

    def kernel_config(self, buckets):
        """The finest level's ``KernelConfig`` of the plan the window's
        requests ran (a plan-cache hit), or ``None``."""
        if not buckets:
            return None
        plan = self.svc.mapper.lower(buckets[0],
                                     self.spec.replace(portfolio=None))
        return plan.kernel_configs[0]

    def execute(self) -> dict:
        self.setup()
        self.window()
        dev = self.devices[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(self.devices),
                  "memory_peak_bytes": peak_memory(dev)}
        ctx = self.context() if self.trace else None
        self.svc.close()
        checks = self.check()
        correct = all(c["value"] <= c["limit"] for c in checks.values())
        wanted = cell_metrics(self.bench, self.cell, self.trace)
        metrics = {}
        if self.trace:
            dt = self.device_trace
            device["busy_s"] = dt.busy_s()
            device["window_s"] = self.window_s
            for m in wanted:
                value = load_metric(self.root, m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            values = self.metrics_e2e()
            for m in wanted:
                if values.get(m["name"]) is not None:
                    metrics[m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
        out = {"correct": bool(correct and self.failed == 0),
               "attempted": len(self.records), "failed": self.failed,
               "metrics": metrics, "device": device}
        if self.trace:
            out["breakdown"] = breakdown(self.device_trace, ctx)
            out["traced_placement_s"] = self.metrics_e2e()["placement_s"]
        out["pool_exhausted"] = self.pool_exhausted
        out["checks"] = checks
        return out


# ------------------------------------------------------------- breakdown
def breakdown(dt, ctx) -> dict:
    """The device operations with the most time, and the idle seconds of
    the window by what the service's host thread was doing.  Gaps under
    ``SHORT_GAP_S`` (between the operations of one program) are summed
    under one name of their own."""
    idle: dict = {}
    offset = host_offset(dt, ctx)
    spans = sorted((s for s in ctx["spans"] if s["name"] != "service.tick"),
                   key=lambda s: s["t0"])
    devs = dt.devices()
    for dev in devs if offset is not None else ():
        t0 = ctx["t_window"] + offset
        for a, b in dt.idle_gaps(dev, t0, t0 + ctx["window_s"]):
            name = (SHORT_GAP if b - a < SHORT_GAP_S
                    else doing(spans, (a + b) / 2 - offset))
            idle[name] = idle.get(name, 0.0) + (b - a) / len(devs)
    return {"device_ops": dt.top("ops", 10),
            "idle_gaps": sorted(([k, v] for k, v in idle.items()),
                                key=lambda e: -e[1])[:10]}


def host_offset(dt, ctx):
    """Trace clock minus ``perf_counter``: from the first request's
    annotation, recorded on both clocks."""
    ann = sorted(e for e in dt.host if e[0] == "bench.request")
    recs = sorted(ctx["placements"], key=lambda r: r["t_issue"])
    if not ann or not recs:
        return None
    return ann[0][1] - recs[0]["t_issue"]


def doing(spans, t: float) -> str:
    """The innermost program span open at ``perf_counter`` time ``t``."""
    best = None
    for s in spans:
        if s["t0"] <= t <= s["t0"] + s["dur"]:
            if best is None or s["depth"] > best["depth"]:
                best = s
    return best["name"] if best is not None else "client"


# ------------------------------------------------------------------- main
def report(out: dict) -> None:
    """The compared numbers on standard error, last; the result line on
    standard output, last."""
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(args, t_start: float, root: Path | None = None,
         require_chip: bool = True, faults=()) -> int:
    root = BENCH.parent if root is None else Path(root)
    run = Run(root, args.workload, args.seed, args.seconds,
              bool(args.trace), t_start, require_chip=require_chip,
              faults=faults)
    try:
        out = run.execute()
    except NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    report(out)
    return 0
