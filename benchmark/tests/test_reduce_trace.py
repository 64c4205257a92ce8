"""The trace reduction on a hand-built event list."""

import pytest

from harness import readers
from harness.reduce_trace import (Event, calibrate, reduce_trace, self_times,
                                  union_seconds)

DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"


def _planes():
    mods0 = [Event("jit_step(11)", 0.0, 0.010), Event("jit_step(22)", 0.020, 0.030),
             Event("jit_step(11)", 0.060, 0.012), Event("jit_step(11)", 0.080, 0.020)]
    ops0 = [
        Event("while.1", 0.000, 0.010),            # control op around its body
        Event("copy.3", 0.001, 0.004), Event("fusion.7", 0.005, 0.004),
        Event("copy.3", 0.020, 0.020), Event("all-reduce.2", 0.040, 0.010),
        Event("fusion.7", 0.060, 0.012),
        Event("custom-call.9", 0.080, 0.020),
    ]
    ops1 = [Event("fusion.7", 0.000, 0.050)]
    return {DEV0: {"XLA Modules": mods0, "XLA Ops": ops0},
            DEV1: {"XLA Modules": [], "XLA Ops": ops1},
            "/host:CPU": {"python": [Event("x", 0.0, 1.0)]}}


def test_union_merges_overlaps():
    assert union_seconds([(0, 2), (1, 3), (5, 6)]) == pytest.approx(4.0)
    assert union_seconds([]) == 0.0


def test_self_time_of_a_control_op_excludes_its_body():
    own = {(e.name, e.start): t for e, t in self_times(_planes()[DEV0]["XLA Ops"])}
    assert own[("while.1", 0.0)] == pytest.approx(0.002)
    assert own[("copy.3", 0.001)] == pytest.approx(0.004)


def test_busy_idle_programs_and_ops():
    programs = {"jit_step(11)": ("paged", 1), "jit_step(22)": ("paged", 256)}
    red = reduce_trace(_planes(), programs)
    assert red["devices"] == [DEV0, DEV1]
    assert red["window_s"] == pytest.approx(0.100)
    busy0 = 0.010 + 0.030 + 0.012 + 0.020
    assert red["busy_s_per_device"][DEV0] == pytest.approx(busy0)
    assert red["busy_s"] == pytest.approx((busy0 + 0.050) / 2)
    assert red["programs"]["paged.w1"]["count"] == 3
    assert red["programs"]["paged.w1"]["median_ms"] == pytest.approx(12.0)
    assert red["programs"]["paged.w256"]["median_ms"] == pytest.approx(30.0)
    top = dict(red["device_ops"])
    assert top["copy.3"] == pytest.approx(0.024)
    assert top["while.1"] == pytest.approx(0.002)
    assert sum(top.values()) == pytest.approx(busy0)
    in_prefill = red["ops_by_program"]["paged.w256"]
    assert in_prefill["all-reduce.2"]["seconds"] == pytest.approx(0.010)
    gaps = dict(red["idle_gaps"])
    assert gaps["before paged.w256"] == pytest.approx(0.010)
    assert gaps["before paged.w1"] == pytest.approx(0.010 + 0.008)
    ctx = {"trace": red, "warm_widths": [1, 64, 256]}
    assert readers.trace_idle_share(ctx) == pytest.approx(
        100 * (1 - red["busy_s"] / 0.1))
    assert readers.trace_program_median(ctx, "paged", "widest") == \
        pytest.approx(30.0)
    assert readers.trace_program_median(ctx, "ragged", 1) is None


def test_calibration_maps_fingerprints_to_widths():
    helper = lambda t: Event("jit__threefry_split(9)", t, 0.001)
    planes = {DEV0: {"XLA Modules": [helper(-0.5), Event("jit_a(1)", 0.0, 1.0),
                                     helper(1.5), Event("jit_b(2)", 2.0, 1.0),
                                     helper(3.5), Event("jit_a(3)", 4.0, 1.0)],
                     "XLA Ops": [Event("f", 0.0, 1.0)]}}
    got = calibrate(planes, [("ragged", 1), ("paged", 1), ("ragged", 64)])
    assert got == {"jit_a(1)": ("ragged", 1), "jit_b(2)": ("paged", 1),
                   "jit_a(3)": ("ragged", 64)}
    with pytest.raises(ValueError, match="ran once"):
        calibrate(planes, [("ragged", 1)])


def test_no_device_plane_reads_as_nothing():
    red = reduce_trace({"/host:CPU": {"python": []}})
    assert red["window_s"] == 0.0
    assert readers.trace_idle_share({"trace": red}) is None


def test_counter_readers_take_window_deltas():
    hist = lambda counts: {"nxdi_queue_wait_seconds": {"series": [
        {"labels": {"outcome": "admitted"}, "buckets":
         [[0.01, counts[0]], [0.1, counts[1]], [1.0, counts[2]]]}]}}
    ctx = {"before": {"counters": {"host_stats.dispatches": 10,
                                   "client.tokens": 100},
                      "prom": hist([5, 5, 5])},
           "after": {"counters": {"host_stats.dispatches": 40,
                                  "client.tokens": 400},
                     "prom": hist([5, 15, 25])}}
    assert readers.counter_ratio(ctx, ["host_stats.dispatches"],
                                 ["client.tokens"]) == pytest.approx(0.1)
    # 20 new samples: 10 in (0.01, 0.1], 10 in (0.1, 1.0]; p90 = 18th sample
    assert readers.prom_quantile(
        ctx, "nxdi_queue_wait_seconds", 0.9, scale=1000.0,
        labels={"outcome": "admitted"}) == pytest.approx(
            1000 * (0.1 + 0.9 * 0.8))
    assert readers.counter_ratio(ctx, ["x"], ["y"]) is None
