"""The readers of the refine stage's spans, the queue spans and the
engine's matching rounds, on hand-made windows and on the small trace
recorded on a TPU v5e (``fixtures/torus-4x4x4.xplane.pb``)."""

from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import harness
from xplane import DeviceTrace

FIXTURE = Path(__file__).parent / "fixtures" / "torus-4x4x4.xplane.pb"
DEV = "/device:TPU:0"
SPAN_METRICS = {"service.queue_s": "service.queue",
                "plan.pairs_s": "plan.pairs",
                "engine.upload_s": "engine.upload",
                "engine.readback_s": "engine.readback"}


def reader(name):
    return harness.load_metric(harness.BENCH.parent, name)


def loop(name):
    return (f"%{name} = (s32[], pred[64]) while(%tuple.1), "
            f"condition=%cond, body=%body")


def placement(rounds=None):
    tel = (None if rounds is None else
           SimpleNamespace(match_rounds=np.asarray(rounds, np.int64)))
    return {"result": SimpleNamespace(
        search_stats=SimpleNamespace(telemetry=tel))}


def span(name, t0, dur):
    return {"name": name, "t0": t0, "dur": dur, "tid": 1, "depth": 2,
            "id": 0, "parent": None, "req": [0], "attrs": {}}


def nested_trace() -> DeviceTrace:
    """Two refine runs, each an outer loop holding eight passes of an
    inner loop and one pass of a cheaper one, plus a loop outside any
    refine run."""
    ops, modules = [], []
    for run in range(2):
        t = 10.0 * run
        modules.append([f"jit_refine_fn({run})", t, 5.0])
        ops.append([loop("while.9"), t, 4.0])
        for k in range(8):
            ops.append([loop("while.12"), t + 0.1 + 0.4 * k, 0.25])
        ops.append([loop("while.30"), t + 3.5, 0.4])
    ops.append([loop("while.3"), 7.0, 2.0])
    modules.append(["jit_other(1)", 7.0, 2.0])
    return DeviceTrace.from_json({"ops": {DEV: ops},
                                  "modules": {DEV: modules}, "host": []})


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_readers_per_placement(name):
    spans = [span(SPAN_METRICS[name], 0.0, 0.25),
             span(SPAN_METRICS[name], 1.0, 0.5),
             span("plan.refine", 0.0, 2.0)]
    ctx = {"spans": spans, "placements": [placement(), placement()]}
    assert reader(name)(ctx) == pytest.approx(0.375)


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_readers_without_their_spans(name):
    others = {"spans": [span("plan.refine", 0.0, 2.0)],
              "placements": [placement()]}
    assert reader(name)(others) is None
    assert reader(name)({"spans": [], "placements": []}) is None


def test_match_rounds_per_placement():
    read = reader("engine.match_rounds")
    ctx = {"placements": [placement([3, 2, 1]), placement([4, 0])]}
    assert read(ctx) == pytest.approx(5.0)
    assert read({"placements": [placement(), placement()]}) is None
    assert read({"placements": []}) is None


def test_matching_loop_rule_on_hand_made_trace():
    found = reader("engine.match_round_us").__globals__["matching_loop"](
        nested_trace())
    name, secs, events = found
    assert name == "%while.12"
    assert secs == pytest.approx(16 * 0.25)
    assert events == 16


def test_match_round_us_reads_loop_time_over_rounds():
    read = reader("engine.match_round_us")
    ctx = {"trace": nested_trace(),
           "placements": [placement([10, 10]), placement([20])]}
    assert read(ctx) == pytest.approx(1e6 * 4.0 / 40)
    assert read(dict(ctx, placements=[placement()])) is None
    assert read(dict(ctx, trace=None)) is None
    empty = DeviceTrace.from_json({"ops": {}, "modules": {}, "host": []})
    assert read(dict(ctx, trace=empty)) is None


def test_matching_loop_rule_on_recorded_trace():
    from jax.profiler import ProfileData
    dt = DeviceTrace.from_profile(ProfileData.from_file(str(FIXTURE)))
    matching_loop = reader("engine.match_round_us").__globals__[
        "matching_loop"]
    name, secs, events = matching_loop(dt)
    assert name == "%while.60"               # not the sweep loop %while.59
    assert secs == pytest.approx(dict(dt.top("ops", 50))["%while.60"])
    assert events == 14
    outer = [e for e in dt.ops[DEV] if e[0].startswith("%while.59 ")]
    assert len(outer) == 1 and outer[0][2] > secs
