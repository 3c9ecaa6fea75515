"""Observability layer: tracer spans, engine telemetry invariants
(bit-identity, no-retrace toggle, padding inertness, lane parity,
exchange/objective consistency), metrics registry atomicity, Chrome
trace export, the MappingService stats compat view, viem --profile, and
the benchmark provenance stamp."""

import json
import threading

import numpy as np
import pytest

from repro.core import (Hierarchy, Mapper, MappingSpec, MultilevelSpec,
                        ShapeBucket, grid3d, random_geometric)
from repro.core.spec import PortfolioSpec
from repro.engine import RefinementEngine
from repro.obs import (EngineTelemetry, MetricsRegistry, Span, Tracer,
                       chrome_trace_events, get_tracer, span_breakdown,
                       write_chrome_trace)
from repro.topology import TreeTopology

H64 = Hierarchy((4, 4, 4), (1.0, 10.0, 100.0))
TOPO = TreeTopology(hierarchy=H64)


def _dev_spec(**kw):
    base = dict(construction="random", neighborhood="communication",
                neighborhood_dist=2, preconfiguration="fast",
                engine="device", seed=1)
    base.update(kw)
    return MappingSpec(**base)


def _workload(seed=3):
    return random_geometric(64, 0.3, seed=seed)


def _refine_inputs(seed=3, n_pairs=None):
    from repro.core.local_search import communication_pairs
    from repro.core.objective import qap_objective
    g = _workload(seed)
    rng = np.random.default_rng(seed)
    perm = rng.permutation(g.n).astype(np.int64)
    pairs = communication_pairs(g, dist=2)
    j0 = qap_objective(g, H64, perm)
    return g, perm, pairs, j0


# ------------------------------------------------------------------ tracer
def test_tracer_records_nested_spans_with_depth():
    tr = Tracer(enabled=True)
    with tr.span("outer", cat="t") as outer:
        with tr.span("inner") as inner:
            pass
    assert [sp.name for sp in tr.spans()] == ["inner", "outer"]
    assert outer.depth == 0 and inner.depth == 1
    assert outer.dur >= inner.dur >= 0.0
    assert outer.t0 <= inner.t0


def test_tracer_disabled_measures_but_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("quiet") as sp:
        pass
    assert sp.dur >= 0.0            # callers still read dur for timing
    assert len(tr) == 0


def test_tracer_ring_buffer_bounds_and_dropped():
    tr = Tracer(enabled=True, capacity=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert len(tr) == 4
    assert tr.dropped == 6
    assert [sp.name for sp in tr.spans()] == ["s6", "s7", "s8", "s9"]


def test_tracer_drain():
    tr = Tracer(enabled=True)
    with tr.span("work", cat="fn"):
        pass
    spans = tr.drain()
    assert [(sp.name, sp.cat) for sp in spans] == [("work", "fn")]
    assert len(tr) == 0
    assert tr.drain() == []
    with tr.span("again"):
        pass
    assert [sp.name for sp in tr.drain()] == ["again"]


def test_get_tracer_is_a_stable_singleton():
    assert get_tracer() is get_tracer()


# --------------------------------------------------------------- telemetry
def test_engine_telemetry_from_device_trims_to_passes():
    tel = EngineTelemetry.from_device(
        {"passes": np.int32(2), "sweeps": np.int32(2),
         "exchanges": np.array([3, 1, 0, 0], np.int32),
         "tabu_masked": np.zeros(4, np.int32),
         "aspirations": np.zeros(4, np.int32),
         "match_rounds": np.array([2, 1, 0, 0], np.int32),
         "downhill_escapes": np.int32(0)},
        objective_trace=np.array([9.0, 5.0, 5.0]))
    assert tel.passes == 2 and tel.sweeps == 2
    assert tel.exchanges.tolist() == [3, 1]
    assert tel.total_exchanges == 4
    assert len(tel.objective_trace) == 3
    s = tel.summary()
    assert s["exchanges"] == 4 and s["merged_from"] == 1


def test_engine_telemetry_merge_sums_and_envelopes():
    a = EngineTelemetry(passes=2, sweeps=2,
                        exchanges=np.array([3, 1]),
                        tabu_masked=np.array([0, 0]),
                        aspirations=np.array([1, 0]),
                        match_rounds=np.array([2, 1]),
                        downhill_escapes=1,
                        objective_trace=np.array([9.0, 5.0, 4.0]))
    b = EngineTelemetry(passes=1, sweeps=1,
                        exchanges=np.array([2]),
                        tabu_masked=np.array([4]),
                        aspirations=np.array([0]),
                        match_rounds=np.array([1]),
                        downhill_escapes=0,
                        objective_trace=np.array([8.0, 3.0]))
    m = EngineTelemetry.merge([a, b])
    assert m.merged_from == 2
    assert m.passes == 2 and m.sweeps == 2
    assert m.exchanges.tolist() == [5, 1]        # zero-padded sum
    assert m.tabu_masked.tolist() == [4, 0]
    assert m.total_exchanges == 6
    assert m.downhill_escapes == 1
    # objective envelope: elementwise min over the extended traces
    assert m.objective_trace.tolist() == [8.0, 3.0, 3.0]


# --------------------------------------------- engine telemetry invariants
def test_telemetry_off_and_on_are_bit_identical_and_no_retrace():
    g, perm, pairs, j0 = _refine_inputs()
    eng = RefinementEngine(TOPO, max_sweeps=32)
    p_off, p_on = perm.copy(), perm.copy()
    st_off = eng.refine(g, p_off, pairs, j0=j0)
    st_on = eng.refine(g, p_on, pairs, j0=j0, telemetry=True)
    assert np.array_equal(p_off, p_on)       # refined in place
    assert st_off.final_objective == st_on.final_objective
    assert st_off.telemetry is None
    assert st_on.telemetry is not None
    assert eng.trace_count() == 1      # the toggle never retraces
    # tabu toggles still share the executable too
    eng.refine(g, perm.copy(), pairs, j0=j0, tabu_tenure=4, dlb=True,
               telemetry=True)
    assert eng.trace_count() == 1


def test_telemetry_exchanges_sum_matches_swaps_and_trace():
    g, perm, pairs, j0 = _refine_inputs()
    eng = RefinementEngine(TOPO, max_sweeps=32)
    st = eng.refine(g, perm.copy(), pairs, j0=j0, telemetry=True)
    tel = st.telemetry
    assert st.swaps > 0
    assert int(tel.exchanges.sum()) == st.swaps
    assert tel.sweeps == len(st.objective_trace) - 1
    # without tabu the sweep is monotone: every pass with exchanges
    # must not increase the objective
    trace = np.asarray(st.objective_trace, dtype=float)
    assert np.all(np.diff(trace) <= 1e-6)
    assert tel.tabu_masked_total == 0 and tel.aspiration_fires == 0


def test_telemetry_tabu_counters_populate():
    g, perm, pairs, j0 = _refine_inputs()
    eng = RefinementEngine(TOPO, max_sweeps=48)
    st = eng.refine(g, perm.copy(), pairs, j0=j0, tabu_tenure=6,
                    dlb=True, telemetry=True)
    tel = st.telemetry
    assert tel.tabu_masked_total > 0
    assert tel.passes == len(tel.exchanges)
    assert 0.0 <= tel.aspiration_rate


def test_telemetry_is_padding_inert():
    g, perm, pairs, j0 = _refine_inputs()
    eng = RefinementEngine(TOPO, max_sweeps=32)
    tight = ShapeBucket.of(g)
    big = ShapeBucket(max_deg=tight.max_deg + 7,
                      num_edges=tight.num_edges + 33,
                      num_pairs=(tight.num_pairs or len(pairs)) + 11)
    p_t, p_b = perm.copy(), perm.copy()
    st_t = eng.refine(g, p_t, pairs, j0=j0, bucket=tight,
                      telemetry=True)
    st_b = eng.refine(g, p_b, pairs, j0=j0, bucket=big,
                      telemetry=True)
    assert np.array_equal(p_t, p_b)
    for f in ("exchanges", "tabu_masked", "aspirations", "match_rounds"):
        assert np.array_equal(getattr(st_t.telemetry, f),
                              getattr(st_b.telemetry, f)), f
    assert st_t.telemetry.downhill_escapes == \
        st_b.telemetry.downhill_escapes


def test_lane_telemetry_equals_single_refines():
    g, _, pairs, _ = _refine_inputs()
    from repro.core.objective import qap_objective
    rng = np.random.default_rng(0)
    perms = [rng.permutation(g.n).astype(np.int64) for _ in range(3)]
    j0s = [qap_objective(g, H64, p) for p in perms]
    eng = RefinementEngine(TOPO, max_sweeps=32)
    lane_perms = [p.copy() for p in perms]
    lane_stats = eng.refine_lanes(g, lane_perms, pairs, j0s=j0s,
                                  tabu_tenure=4, dlb=True,
                                  telemetry=True)
    for p, lp, j0, ls in zip(perms, lane_perms, j0s, lane_stats):
        sp = p.copy()
        single = eng.refine(g, sp, pairs, j0=j0, tabu_tenure=4,
                            dlb=True, telemetry=True)
        assert np.array_equal(lp, sp)
        for f in ("exchanges", "tabu_masked", "aspirations"):
            assert np.array_equal(getattr(ls.telemetry, f),
                                  getattr(single.telemetry, f)), f


@pytest.mark.parametrize("spec", [
    _dev_spec(),
    _dev_spec(multilevel=MultilevelSpec(levels=3, coarsen_min=8)),
    _dev_spec(portfolio=PortfolioSpec(lanes=2, rounds=2,
                                      tabu_tenure=4)),
], ids=["flat", "multilevel", "portfolio"])
def test_mapper_telemetry_toggle_is_bit_identical(spec):
    mapper = Mapper(H64, spec)
    g = _workload()
    r_off = mapper.map(g)
    r_on = mapper.map(g, telemetry=True)
    assert np.array_equal(r_off.perm, r_on.perm)
    assert r_off.final_objective == r_on.final_objective
    assert r_on.search_stats.telemetry is not None
    assert r_off.search_stats.telemetry is None
    # MappingResult timing fields survive the tracer refactor
    assert r_on.construction_seconds >= 0.0
    assert r_on.search_seconds >= 0.0


def test_map_many_telemetry_matches_singles():
    mapper = Mapper(H64, _dev_spec())
    gs = [_workload(3), _workload(5)]
    batch = mapper.map_many(gs, telemetry=True)
    for g, r in zip(gs, batch):
        tel = r.search_stats.telemetry
        assert tel is not None
        assert int(tel.exchanges.sum()) == r.search_stats.swaps


# ----------------------------------------------------------------- metrics
def test_metrics_registry_snapshot_is_deep_and_reset_keeps_names():
    m = MetricsRegistry()
    m.counter("a").inc(3)
    m.gauge("g").set_max(7)
    m.histogram("h").observe(0.5)
    snap = m.snapshot()
    assert snap["a"] == 3 and snap["g"] == 7
    assert snap["h"]["count"] == 1
    snap["h"]["count"] = 999               # mutating a snapshot is inert
    assert m.snapshot()["h"]["count"] == 1
    m.reset()
    snap2 = m.snapshot()
    assert set(snap2) == {"a", "g", "h"}   # registrations survive
    assert snap2["a"] == 0 and snap2["h"]["count"] == 0


def test_metrics_registry_rejects_kind_mismatch():
    m = MetricsRegistry()
    m.counter("x")
    with pytest.raises(TypeError, match="already registered"):
        m.gauge("x")


def test_metrics_histogram_percentiles_use_recent_window():
    m = MetricsRegistry()
    h = m.histogram("lat", window=4)
    for v in (10.0, 1.0, 2.0, 3.0, 4.0):   # 10.0 falls out of the window
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 5 and snap["max"] == 10.0
    assert snap["p99"] == 4.0


def test_prometheus_exposition_round_trips():
    from repro.obs import parse_prometheus
    m = MetricsRegistry()
    m.counter("monitor.remaps.committed").inc(3)
    m.gauge("monitor.drift.score").set(0.125)
    h = m.histogram("monitor.remap_seconds")
    for v in (0.1, 0.2, 0.3, 0.4):
        h.observe(v)
    text = m.to_prometheus()
    assert "# TYPE viem_monitor_remaps_committed counter" in text
    assert "# TYPE viem_monitor_drift_score gauge" in text
    assert "# TYPE viem_monitor_remap_seconds summary" in text
    back = parse_prometheus(text)
    assert back["viem_monitor_remaps_committed"]["type"] == "counter"
    assert back["viem_monitor_remaps_committed"]["samples"][""] == 3
    assert back["viem_monitor_drift_score"]["samples"][""] == 0.125
    summ = back["viem_monitor_remap_seconds"]
    assert summ["type"] == "summary"
    assert summ["samples"]["count"] == 4
    assert summ["samples"]["sum"] == pytest.approx(1.0)
    assert summ["samples"]['quantile="0.5"'] == pytest.approx(
        m.histogram("monitor.remap_seconds").percentile(0.5))


def test_prometheus_empty_registry_and_snapshot_parity():
    from repro.obs import parse_prometheus
    m = MetricsRegistry()
    assert m.to_prometheus() == ""
    m.counter("a.b-c").inc()
    back = parse_prometheus(m.to_prometheus())
    assert back == {"viem_a_b_c": {"type": "counter", "samples": {"": 1.0}}}


def test_service_prometheus_exposes_served_counters():
    from repro.launch.serve import MappingService
    from repro.obs import parse_prometheus
    kw = {"max_wait_s": 0.002}
    with MappingService(Mapper(H64, _dev_spec()), **kw) as svc:
        svc.map(_workload(), timeout=300)
        text = svc.prometheus()
    back = parse_prometheus(text)
    assert back["viem_served"]["samples"][""] >= 1.0
    assert back["viem_served"]["type"] == "counter"
    assert back["viem_latency_s"]["type"] == "summary"


# ------------------------------------------------------------------ export
def test_chrome_trace_events_structure_and_counters(tmp_path):
    tr = Tracer(enabled=True)
    tel = EngineTelemetry(passes=2, sweeps=2,
                          exchanges=np.array([3, 1]),
                          tabu_masked=np.array([2, 0]),
                          aspirations=np.array([1, 0]),
                          match_rounds=np.array([2, 1]),
                          downhill_escapes=0,
                          objective_trace=np.array([9.0, 5.0, 4.0]))
    with tr.span("plan.execute"):
        with tr.span("plan.refine", telemetry=tel, retraces=0):
            pass
    payload = chrome_trace_events(tr.spans())
    events = payload["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in complete} == {"plan.execute",
                                             "plan.refine"}
    for e in complete:
        assert e["ts"] >= 0.0 and e["dur"] >= 0.0
        json.dumps(e["args"])              # args must be JSON-safe
    counters = [e for e in events if e["ph"] == "C"]
    by_track = {}
    for e in counters:
        by_track.setdefault(e["name"], []).append(e["args"]["value"])
    assert by_track["engine/exchanges"] == [3, 1]
    assert by_track["engine/objective"] == [9.0, 5.0, 4.0]
    meta = [e for e in events if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in meta)
    # file round-trip
    path = tmp_path / "t.trace.json"
    n = write_chrome_trace(tr.spans(), path)
    assert n == len(json.loads(path.read_text())["traceEvents"])


def test_span_breakdown():
    spans = [Span("a", dur=d) for d in (0.5, 1.0, 1.5)] + [
        Span("b", dur=2.0, attrs={"k": np.int32(7)})]
    agg = span_breakdown(spans)
    assert set(agg) == {"a", "b"}
    assert agg["a"] == {"count": 3, "total_s": 3.0, "max_s": 1.5,
                        "mean_s": 1.0}
    assert agg["b"] == {"count": 1, "total_s": 2.0, "max_s": 2.0,
                        "mean_s": 2.0}
    assert span_breakdown([]) == {}


def test_plan_spans_and_describe_timings():
    tr = get_tracer()
    tr.enable()
    try:
        tr.clear()
        mapper = Mapper(H64, _dev_spec(
            multilevel=MultilevelSpec(levels=3, coarsen_min=8)))
        mapper.map(_workload())
        names = {sp.name for sp in tr.spans()}
        assert {"plan.lower", "plan.execute", "plan.vcycle",
                "vcycle.construct", "vcycle.refine"} <= names
        refines = [sp for sp in tr.spans()
                   if sp.name == "vcycle.refine"]
        assert {sp.attrs["level"] for sp in refines} == {0, 1, 2}
        assert all(sp.attrs["retraces"] >= 0 for sp in refines)
        plan = next(iter(mapper._plans.values()))
        t = plan.describe()["timings"]
        assert t["executes"] == 1
        assert t["lower_seconds"] > 0.0
        assert t["execute_seconds_total"] > 0.0
        assert all(c >= 1 for c in t["engine_traces"])
    finally:
        tr.disable()
        tr.clear()


# ----------------------------------------------------------------- service
def _service(mapper, **kw):
    from repro.launch.serve import MappingService
    kw.setdefault("max_wait_s", 0.002)
    return MappingService(mapper, **kw)


def test_service_stats_compat_keys_and_engine_aggregates():
    legacy = {"served", "batches", "batched_requests", "max_batch_seen",
              "result_cache_hits", "in_tick_deduped",
              "result_cache_size", "errors", "quality_served",
              "queue_depth", "peak_queue_depth", "latency_p50_s",
              "latency_p99_s"}
    with _service(Mapper(H64, _dev_spec()),
                  collect_telemetry=True) as svc:
        for s in (3, 5, 3):
            svc.map(_workload(s), timeout=300)
        stats = svc.stats()
    assert legacy <= set(stats)
    assert stats["served"] == 3
    assert stats["latency_count"] == 3
    assert stats["telemetry_requests"] >= 1
    assert stats["engine_sweeps_total"] > 0
    assert stats["engine_mean_sweeps_per_request"] > 0
    assert stats["quality_served"] == {"default": 3}


def test_service_reset_stats_zeroes_registry():
    with _service(Mapper(H64, _dev_spec())) as svc:
        svc.map(_workload(), timeout=300)
        assert svc.stats()["served"] == 1
        svc.reset_stats()
        stats = svc.stats()
    assert stats["served"] == 0
    assert stats["latency_count"] == 0
    assert stats["latency_p99_s"] == 0.0
    assert stats["quality_served"] == {"default": 0}


def test_service_stats_never_tear_under_burst():
    """A monitoring thread polling during a burst must always observe
    served == latency_count (they update under one registry lock)."""
    mapper = Mapper(H64, _dev_spec())
    torn = []
    stop = threading.Event()

    with _service(mapper) as svc:
        svc.map(_workload(), timeout=300)      # warm the plan first

        def monitor():
            while not stop.is_set():
                s = svc.stats()
                if s["served"] != s["latency_count"]:
                    torn.append((s["served"], s["latency_count"]))

        t = threading.Thread(target=monitor)
        t.start()
        try:
            tickets = [svc.submit(_workload(i % 4)) for i in range(24)]
            for _ in tickets:
                _, res = svc.results.get(timeout=300)
                assert not isinstance(res, Exception)
        finally:
            stop.set()
            t.join()
    assert torn == []


def test_service_without_telemetry_keeps_counters_quiet():
    with _service(Mapper(H64, _dev_spec())) as svc:
        svc.map(_workload(), timeout=300)
        stats = svc.stats()
    assert stats["telemetry_requests"] == 0
    assert stats["engine_exchanges_total"] == 0
    assert stats["engine_sweeps_total"] > 0   # from the objective trace


# --------------------------------------------------------------------- cli
def test_viem_profile_writes_loadable_trace(tmp_path, capsys):
    from repro.cli.viem import main as viem_main
    from repro.core import write_metis
    g = grid3d(4, 4, 4)
    gpath = tmp_path / "g.metis"
    write_metis(g, gpath)
    trace = tmp_path / "run.trace.json"
    tr = get_tracer()
    try:
        viem_main([str(gpath),
                   "--hierarchy_parameter_string=4:4:4",
                   "--distance_parameter_string=1:10:100",
                   "--engine=device",
                   f"--output_filename={tmp_path / 'perm'}",
                   f"--profile={trace}"])
    finally:
        tr.disable()
        tr.clear()
    out = capsys.readouterr().out
    assert "engine sweeps" in out
    payload = json.loads(trace.read_text())
    names = {e["name"] for e in payload["traceEvents"]
             if e.get("ph") == "X"}
    assert {"plan.lower", "plan.execute", "plan.refine"} <= names
    assert (tmp_path / "perm").exists()


# -------------------------------------------------------------- benchmarks
def test_bench_metadata_stamp(tmp_path):
    import sys
    sys.path.insert(0, "benchmarks")
    try:
        from _common import BENCH_SCHEMA_VERSION, write_bench
    finally:
        sys.path.pop(0)
    out = tmp_path / "BENCH_x.json"
    write_bench({"cells": [1, 2]}, str(out))
    payload = json.loads(out.read_text())
    assert payload["cells"] == [1, 2]
    meta = payload["meta"]
    assert meta["schema_version"] == BENCH_SCHEMA_VERSION
    assert meta["backend"] in ("cpu", "gpu", "tpu")
    assert meta["jax_version"]
    assert "git_sha" in meta and "timestamp" in meta
