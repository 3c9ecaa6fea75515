"""Spans inside the engine and the service: the refine stage's host work
(pair generation, upload, dispatch, wait, readback) nests under
``plan.refine``; tracing leaves answers and executables untouched while
it turns engine telemetry on; service spans carry their request tickets
and each request gets a ``service.queue`` span; live spans land on the
profiler trace's host plane."""

import glob
import time

import numpy as np
import pytest

from repro.core import Hierarchy, Mapper, MappingSpec, random_geometric
from repro.obs import Tracer, chrome_trace_events, get_tracer

H64 = Hierarchy((4, 4, 4), (1.0, 10.0, 100.0))
ENGINE_SPANS = ("engine.upload", "engine.dispatch", "engine.wait",
                "engine.readback")


def _spec(**kw):
    base = dict(construction="random", neighborhood="communication",
                neighborhood_dist=2, preconfiguration="fast",
                engine="device", seed=1)
    base.update(kw)
    return MappingSpec(**base)


def _graph(seed=3):
    return random_geometric(64, 0.3, seed=seed)


def _plan(mapper, g):
    return mapper.lower_for(g)


@pytest.fixture
def tracer():
    tr = get_tracer()
    tr.clear()
    tr.enable()
    try:
        yield tr
    finally:
        tr.disable()
        tr.clear()


def _by_id(spans):
    return {sp.id: sp for sp in spans}


def _only(spans, name):
    found = [sp for sp in spans if sp.name == name]
    assert len(found) == 1, [sp.name for sp in spans]
    return found[0]


# ------------------------------------------------------------ the tracer
def test_span_ids_parents_and_request_context():
    tr = Tracer(enabled=True)
    with tr.request([4, 5]):
        with tr.span("outer") as outer:
            with tr.span("inner") as inner:
                pass
            late = tr.record("after", 0.25, req=(9,))
    with tr.span("free") as free:
        pass
    assert outer.parent is None and inner.parent == outer.id
    assert late.parent == outer.id and late.depth == 1
    assert len({outer.id, inner.id, late.id, free.id}) == 4
    assert outer.req == inner.req == (4, 5)
    assert late.req == (9,) and free.req is None
    d = inner.to_dict()
    assert (d["id"], d["parent"], d["req"]) == (inner.id, outer.id, [4, 5])
    events = {e["name"]: e for e in chrome_trace_events(tr.spans())[
        "traceEvents"] if e["ph"] == "X"}
    assert events["inner"]["args"]["parent"] == outer.id
    assert events["outer"]["args"]["req"] == [4, 5]
    assert events["free"]["args"]["req"] is None


# ------------------------------------------------------------- (a) nesting
def test_refine_stage_spans_nest_under_plan_refine(tracer):
    mapper = Mapper(H64, _spec())
    g = _graph()
    plan = _plan(mapper, g)
    tracer.clear()
    plan.execute(g)
    spans = tracer.spans()
    by_id = _by_id(spans)
    execute = _only(spans, "plan.execute")
    refine = _only(spans, "plan.refine")
    for name in ("plan.pairs",) + ENGINE_SPANS:
        sp = _only(spans, name)
        assert sp.parent == refine.id, name
        assert sp.t0 >= refine.t0
        assert sp.t0 + sp.dur <= refine.t0 + refine.dur
    pairs = _only(spans, "plan.pairs")
    assert pairs.attrs["pairs"] > 0 and pairs.attrs["hit"] in (True, False)
    upload = _only(spans, "engine.upload")
    assert {"graph_hits", "pair_hits"} <= set(upload.attrs)
    children = sorted(sp.name for sp in spans if sp.parent == execute.id)
    assert children == ["plan.construct", "plan.refine"]
    assert all(sp.parent is None or sp.parent in by_id for sp in spans)


def test_upload_span_reports_cache_hits(tracer):
    mapper = Mapper(H64, _spec())
    g = _graph()
    plan = _plan(mapper, g)
    tracer.clear()
    plan.execute(g)                  # lowering warmed nothing: uploads
    plan.execute(g)                  # the same graph again: all hits
    first, second = [sp for sp in tracer.spans()
                     if sp.name == "engine.upload"]
    assert (first.attrs["graph_hits"], first.attrs["pair_hits"]) == (0, 0)
    assert (second.attrs["graph_hits"],
            second.attrs["pair_hits"]) == (1, 1)
    pairs = [sp.attrs["hit"] for sp in tracer.spans()
             if sp.name == "plan.pairs"]
    assert pairs == [False, True]


def test_batch_and_warm_paths_record_the_engine_spans(tracer):
    mapper = Mapper(H64, _spec())
    graphs = [_graph(3), _graph(5)]
    plan = _plan(mapper, graphs[0])
    tracer.clear()
    res = plan.execute_batch(graphs)
    plan.execute_warm(graphs[0], res[0].perm)
    spans = tracer.spans()
    by_id = _by_id(spans)
    refines = [sp for sp in spans if sp.name == "plan.refine"]
    assert len(refines) == 2
    for name in ENGINE_SPANS:
        found = [sp for sp in spans if sp.name == name]
        assert len(found) == 2, name
        assert all(by_id[sp.parent].name == "plan.refine" for sp in found)
    assert len([sp for sp in spans if sp.name == "plan.pairs"]) == 3
    assert all(r.search_stats.telemetry is not None for r in res)


# ---------------------------------------------------- (b) on against off
def test_tracing_is_bit_identical_and_turns_telemetry_on():
    mapper = Mapper(H64, _spec())
    g = _graph()
    plan = _plan(mapper, g)
    off = plan.execute(g)
    traces = plan.engines[0].trace_count()
    tr = get_tracer()
    tr.enable()
    try:
        on = plan.execute(g)
    finally:
        tr.disable()
        tr.clear()
    again = plan.execute(g)
    assert np.array_equal(off.perm, on.perm)
    assert off.final_objective == on.final_objective
    assert off.search_stats.objective_trace == \
        on.search_stats.objective_trace
    assert plan.engines[0].trace_count() == traces
    assert off.search_stats.telemetry is None
    assert again.search_stats.telemetry is None
    tel = on.search_stats.telemetry
    assert tel is not None and tel.match_rounds.sum() > 0
    assert tel.passes == len(tel.match_rounds)


def test_refine_span_carries_telemetry_when_traced(tracer):
    mapper = Mapper(H64, _spec())
    g = _graph()
    res = mapper.map(g)
    refine = [sp for sp in tracer.spans() if sp.name == "plan.refine"][-1]
    assert refine.attrs["telemetry"] is res.search_stats.telemetry


# ------------------------------------------------------------ (c) service
def test_service_queue_spans_and_request_tickets(tracer):
    from repro.launch.serve import MappingService
    mapper = Mapper(H64, _spec())
    with MappingService(mapper, max_wait_s=0.002) as svc:
        svc.map(_graph(), timeout=300)           # lower the plan first
        tracer.clear()
        latency = {}
        for seed in (5, 7):                       # one request a tick
            t0 = time.perf_counter()
            ticket = svc.submit(_graph(seed))
            t, res = svc.results.get(timeout=300)
            assert t == ticket and not isinstance(res, Exception)
            latency[ticket] = time.perf_counter() - t0
        t0 = time.perf_counter()
        burst = [svc.submit(_graph(seed)) for seed in (9, 11, 13)]
        for _ in burst:
            t, res = svc.results.get(timeout=300)
            assert not isinstance(res, Exception)
            latency[t] = time.perf_counter() - t0
    spans = tracer.spans()
    by_id = _by_id(spans)
    for ticket, lat in latency.items():
        queued = [sp for sp in spans if sp.name == "service.queue"
                  and sp.req == (ticket,)]
        assert len(queued) == 1, ticket
        assert 0.0 <= queued[0].dur <= lat
    ticks = [sp for sp in spans if sp.name == "service.tick"]
    served = [t for tick in ticks for t in tick.req]
    assert sorted(served) == sorted(latency)
    for sp in spans:
        top = sp
        while top.parent is not None:
            top = by_id[top.parent]
        if top.name != "service.tick" or top is sp:
            continue
        assert sp.req and set(sp.req) <= set(top.req), sp.name
    executes = [sp for sp in spans if sp.name.startswith("plan.execute")]
    assert executes and all(sp.req for sp in executes)


def test_service_records_no_queue_spans_untraced():
    from repro.launch.serve import MappingService
    tr = get_tracer()
    tr.clear()
    with MappingService(Mapper(H64, _spec()), max_wait_s=0.002) as svc:
        svc.map(_graph(), timeout=300)
    assert len(tr) == 0


# ----------------------------------------------------------- (d) profiler
def _host_events(logdir):
    from jax.profiler import ProfileData
    path = max(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    out.append((ev.name, (plane.name, i), ev.start_ns,
                                ev.duration_ns))
    return out


def test_live_spans_land_on_the_profiler_host_plane(tracer, tmp_path):
    import jax
    mapper = Mapper(H64, _spec())
    g = _graph()
    plan = _plan(mapper, g)
    plan.execute(g)                              # compile outside
    tracer.clear()
    with jax.profiler.trace(str(tmp_path)):
        plan.execute(g)
    spans = {sp.name: sp for sp in tracer.spans()}
    events = _host_events(tmp_path)
    names = ("plan.execute", "plan.refine", "engine.wait")
    found = {}
    for name in names:
        evs = [e for e in events if e[0] == name]
        assert len(evs) == 1, (name, len(evs))
        found[name] = evs[0]
    assert len({found[n][1] for n in names}) == 1      # one host thread
    for outer, inner in zip(names, names[1:]):
        _, _, o0, od = found[outer]
        _, _, i0, idur = found[inner]
        assert o0 <= i0 and i0 + idur <= o0 + od, (outer, inner)
    for name in names:
        dur = found[name][3] * 1e-9
        assert abs(dur - spans[name].dur) <= max(0.1 * spans[name].dur,
                                                 1e-3), name


def test_disabled_tracer_never_enters_a_trace_annotation(monkeypatch):
    import jax

    class Refused:
        def __init__(self, *a, **k):
            raise AssertionError("TraceAnnotation entered while disabled")

    mapper = Mapper(H64, _spec())
    g = _graph()
    plan = _plan(mapper, g)
    plan.execute(g)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refused)
    tr = get_tracer()
    assert not tr.enabled
    res = plan.execute(g)
    assert res.search_stats.telemetry is None
    assert len(tr) == 0


def test_queue_span_is_recorded_after_the_fact():
    tr = Tracer(enabled=True)
    sp = tr.record("service.queue", 0.5, t0=10.0, req=(3,))
    assert (sp.t0, sp.dur, sp.req, sp.parent) == (10.0, 0.5, (3,), None)
    assert tr.spans() == [sp]
