"""The trace reduction, on hand-made events and on a small trace
recorded on a TPU v5e (one placement of a 64-rank stencil on a 4x4x4
torus through the benchmark's own run)."""

from pathlib import Path

import pytest

import harness
from xplane import DeviceTrace

FIXTURE = Path(__file__).parent / "fixtures" / "torus-4x4x4.xplane.pb"
DEV = "/device:TPU:0"
KERNEL_CALL = ('%body.1 = f32[1024,1]{1,0:T(8,128)} custom-call(s32[1024,1] '
               '%a, s32[1024,1] %b, s32[1024,128] %c, f32[1024,128] %d), '
               'custom_call_target="tpu_custom_call"')


def hand_made() -> DeviceTrace:
    return DeviceTrace.from_json({
        "ops": {DEV: [["%fusion.1 = f32[8]{0} fusion()", 0.0, 1.0],
                      [KERNEL_CALL, 0.5, 1.0],
                      ["%fusion.2 = f32[8]{0} fusion()", 3.0, 0.5]]},
        "modules": {DEV: [["jit_refine_fn(7)", 0.0, 1.5],
                          ["jit_other(9)", 3.0, 0.5]]},
        "host": [["bench.request", 0.0, 4.0]]})


def test_busy_is_the_union_of_op_intervals():
    dt = hand_made()
    assert dt.busy_intervals(DEV) == [[0.0, 1.5], [3.0, 3.5]]
    assert dt.busy_s() == pytest.approx(2.0)


def test_idle_gaps():
    gaps = hand_made().idle_gaps(DEV, 0.0, 4.0)
    assert gaps == [(1.5, 3.0), (3.5, 4.0)]


def test_totals_and_top():
    dt = hand_made()
    assert dt.total("ops", lambda n: "custom-call" in n) == (1.0, 1)
    assert dt.total("modules", lambda n: "jit_refine_fn" in n) == (1.5, 1)
    assert dt.top("ops", 2) == [["%fusion.1", 1.0],
                                ["%body.1 tpu_custom_call", 1.0]]


def test_metrics_on_hand_made_trace():
    from types import SimpleNamespace
    peaks = {"hbm_bytes_per_s": 1e9, "int8_ops_per_s": 1e15}
    ctx = {"trace": hand_made(), "window_s": 4.0, "peaks": peaks,
           "placements": [object()], "spans": [],
           "buckets": [SimpleNamespace(max_deg=8)],
           "config": {"machine": {"kind": "torus", "dims": [4, 4],
                                  "weights": [1.0, 1.0]}}}
    read = lambda name: harness.load_metric(  # noqa: E731
        harness.BENCH.parent, name)(ctx)
    assert read("device.idle") == pytest.approx(50.0)
    assert read("engine.refine_device_s") == pytest.approx(1.5)
    # one side call over 1024 pairs at K = 8: 1024 * (8 + 64 + 4) bytes
    # at 1 GB/s, against the call's 1.0 s
    assert read("pair_gain_roofline") == pytest.approx(
        100 * 1024 * 76 / 1e9)


def test_recorded_tpu_trace():
    dt = DeviceTrace.from_profile(__import__(
        "jax.profiler", fromlist=["ProfileData"]).ProfileData.from_file(
            str(FIXTURE)))
    assert dt.devices() == [DEV]
    busy = dt.busy_s()
    first = min(t for _, t, _ in dt.ops[DEV])
    last = max(t + d for _, t, d in dt.ops[DEV])
    assert 0 < busy <= last - first
    secs, count = dt.total("modules", lambda n: "jit_refine_fn" in n)
    assert count == 1 and 0 < secs <= busy
    pair_gain = harness.load_metric(harness.BENCH.parent,
                                    "pair_gain_roofline")
    calls = [n for n, _, _ in dt.ops[DEV]
             if pair_gain.__globals__["CALL"].search(n)]
    assert calls and len(calls) % 2 == 0
    assert any(name == "bench.request" for name, _, _ in dt.host)


@pytest.mark.parametrize("dist_dtype,itemsize", [(None, 4), ("int16", 2),
                                                 ("int8", 1)])
def test_matrix_side_bytes_follow_the_table_packing(dist_dtype, itemsize):
    import kernel_cost
    p, k = 1000, 16
    # two gathered distances at the stored width and a float32 weight
    # per slot, one float32 gain out per pair
    assert kernel_cost.side_bytes("matrix", p, k, dist_dtype) == \
        p * k * (2 * itemsize + 4) + p * 4


def test_roofline_reads_the_plans_table_packing():
    from types import SimpleNamespace
    call = KERNEL_CALL
    ctx = {"trace": DeviceTrace.from_json({
               "ops": {DEV: [[call, 0.0, 1.0]]}, "modules": {}, "host": []}),
           "peaks": {"hbm_bytes_per_s": 1e9, "int8_ops_per_s": 1e15},
           "buckets": [SimpleNamespace(max_deg=8)],
           "config": {"machine": {"kind": "matrix"}}}
    read = harness.load_metric(harness.BENCH.parent, "pair_gain_roofline")
    shares = {}
    for dtype in (None, "int16", "int8"):
        ctx["kernel_config"] = SimpleNamespace(dist_dtype=dtype)
        shares[dtype] = read(ctx)
    assert shares[None] == pytest.approx(100 * 1024 * (8 * 12 + 4) / 1e9)
    assert shares["int8"] == pytest.approx(100 * 1024 * (8 * 6 + 4) / 1e9)
    assert shares[None] > shares["int16"] > shares["int8"]
