"""The one-timeline readers on hand-built planes and snapshots, then through
``readers.read_metric`` and one toy run."""

import argparse
import json
import os
import shutil

import pytest

import run
from harness import build, host_spans, readers
from harness.reduce_trace import Event, reduce_trace

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
NEW_METRICS = (
    "host.sched_ms_per_dispatch", "host.run_prep_ms_per_dispatch",
    "host.dispatch_self_ms_per_dispatch", "host.loop_yield_ms_per_dispatch",
    "frontdoor.sse_lag_p90_ms", "device.idle_sched_share",
    "device.idle_dispatch_share", "device.idle_yield_share",
    "device.idle_nowork_share", "step.decode_attn_ms", "step.decode_moe_ms",
    "step.prefill_attn_ms", "step.prefill_moe_ms")


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def test_interval_arithmetic():
    busy = host_spans.merge([(3, 4), (0, 1), (0.5, 2), (6, 6)])
    assert busy == [(0, 2), (3, 4)]
    idle = host_spans.complement(busy, 0, 5)
    assert idle == [(2, 3), (4, 5)]
    assert host_spans.complement([], 1, 2) == [(1, 2)]
    assert host_spans.complement([(0, 9)], 1, 2) == []
    assert host_spans.intersect(idle, [(2.5, 4.5)]) == [(2.5, 3), (4, 4.5)]
    assert host_spans.subtract([(0, 10)], [(2, 3), (9, 12)]) == \
        [(0, 2), (3, 9)]
    assert host_spans.subtract([], [(0, 1)]) == []
    assert host_spans.seconds(idle) == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# device idle by host span, device self time by scope
# ---------------------------------------------------------------------------

def _op(name, start, dur, scope=""):
    return Event(name, start, dur, {"scope": scope})


def _planes():
    """Two passes on one chip (a second chip busy throughout). Pass 0
    admits a prompt (its prefill chunk is dispatched INSIDE pass.admit, as
    the default adapter does) and decodes; pass 1 decodes only.

    device 0 busy: [0.010, 0.040] prefill, [0.050, 0.060] and [0.075, 0.085]
    decode; the window is [0.010, 0.100] by device 1's one long operation.
    """
    a = "jit(paged_forward_step)/while/body/closed_call/attn/"
    m = "jit(paged_forward_step)/while/body/closed_call/moe/"
    mods = [Event("jit_paged_forward_step(22)", 0.010, 0.030),
            Event("jit_paged_forward_step(11)", 0.050, 0.010),
            Event("jit_paged_forward_step(11)", 0.075, 0.010),
            Event("jit__threefry_split(5)", 0.0745, 0.0001)]
    ops = [
        # prefill chunk: a layer loop around attention and experts
        _op("while.1", 0.010, 0.030, "jit(paged_forward_step)/while"),
        _op("slice_fusion.3", 0.010, 0.001,
            "jit(paged_forward_step)/while/body/squeeze:"),
        _op("fusion.5", 0.011, 0.012, a + "dot_general:"),
        _op("fusion.20", 0.023, 0.001, m + "top_k:"),
        # the compiler drops the path of the expert matmul's custom call:
        # it takes the scope both its neighbours have
        _op("ragged-dot-none", 0.024, 0.008, "ragged-dot-none:"),
        _op("fusion.21", 0.032, 0.001, m + "mul:"),
        _op("add.4", 0.033, 0.001, "jit(paged_forward_step)/while/body/add:"),
        _op("fusion.9", 0.034, 0.004, "jit(paged_forward_step)/lm_head/dot:"),
        # two decode steps: kernel, experts, sampling, one unscoped copy
        _op("paged_decode_attention", 0.050, 0.004, a + "pallas_call"),
        _op("fusion.2", 0.054, 0.003, m + "dot_general"),
        _op("fusion.3", 0.057, 0.002, "jit(paged_forward_step)/sample/argmax"),
        _op("copy.1", 0.059, 0.001),
        _op("paged_decode_attention", 0.075, 0.006, a + "pallas_call"),
        _op("fusion.2", 0.081, 0.004, m + "dot_general"),
    ]
    host = [
        Event("loop.idle", 0.000, 0.004),
        Event("pass.expire", 0.004, 0.001, {"pass_id": 0}),
        Event("pass.preempt", 0.005, 0.001, {"pass_id": 0}),
        Event("pass.admit", 0.006, 0.036, {"pass_id": 0}),
        Event("dispatch.prefill_chunk", 0.008, 0.033, {"pass_id": 0}),
        Event("run.paged", 0.008, 0.002, {"pass_id": 0}),
        Event("fetch.tokens", 0.0105, 0.030, {"pass_id": 0}),
        Event("pass.dispatch", 0.042, 0.020, {"pass_id": 0}),
        Event("run.paged", 0.046, 0.004, {"pass_id": 0}),
        Event("fetch.tokens", 0.0505, 0.0100, {"pass_id": 0}),
        Event("loop.yield", 0.062, 0.006, {"pass_id": 0}),
        Event("pass.expire", 0.068, 0.001, {"pass_id": 1}),
        Event("pass.dispatch", 0.070, 0.017, {"pass_id": 1}),
        Event("loop.yield", 0.088, 0.004, {"pass_id": 1}),
        Event("loop.idle", 0.092, 0.008, {"pass_id": 1}),
        Event("some.other.traceme", 0.0, 1.0),
    ]
    return {DEV0: {"XLA Modules": mods, "XLA Ops": ops},
            DEV1: {"XLA Modules": [], "XLA Ops": [_op("fusion.7", 0.010,
                                                       0.090)]},
            HOST: {"python": host[:9], "asyncio_1": host[9:]}}


def test_idle_by_span_splits_every_idle_interval():
    planes = _planes()
    idle = host_spans.idle_by_span(planes)
    assert idle["window_s"] == pytest.approx(0.090)
    # device 0 idles [0.040,0.050] [0.060,0.075] [0.085,0.100] = 0.040;
    # device 1 never; the mean over the two chips halves every number
    assert idle["idle_s"] == pytest.approx(0.040 / 2)
    # [0.040,0.041] under the prefill chunk, [0.042,0.050] + [0.060,0.062]
    # + [0.070,0.075] + [0.085,0.087] under pass.dispatch
    assert idle["dispatch_s"] == pytest.approx(0.018 / 2)
    # [0.041,0.042] of pass.admit past its chunk, [0.068,0.069] pass.expire
    assert idle["sched_s"] == pytest.approx(0.002 / 2)
    assert idle["yield_s"] == pytest.approx((0.006 + 0.004) / 2)
    assert idle["nowork_s"] == pytest.approx(0.008 / 2)
    # [0.069,0.070] and [0.087,0.088] lie between spans
    assert idle["remainder_s"] == pytest.approx(0.002 / 2)
    assert sum(idle[k] for k in ("dispatch_s", "sched_s", "yield_s",
                                 "nowork_s", "remainder_s")) \
        == pytest.approx(idle["idle_s"])
    # the same idle share the accepted reader reports
    red = reduce_trace({k: v for k, v in planes.items() if k != HOST})
    assert 1.0 - red["busy_s"] / red["window_s"] == \
        pytest.approx(idle["idle_s"] / idle["window_s"])


def test_idle_by_span_needs_a_device_plane_and_the_programs_spans():
    planes = _planes()
    assert host_spans.idle_by_span({HOST: planes[HOST]}) is None
    bare = dict(planes, **{HOST: {"python": [Event("x", 0.0, 1.0)]}})
    assert host_spans.idle_by_span(bare) is None          # the parent
    del bare[HOST]
    assert host_spans.idle_by_span(bare) is None


def test_scope_self_time_per_program():
    got = host_spans.scope_seconds(_planes())
    decode = got["jit_paged_forward_step(11)"]
    assert decode["count"] == 2
    assert decode["total_s"] == pytest.approx(0.020)
    assert decode["scopes"]["attn"] == pytest.approx(0.010)
    assert decode["scopes"]["moe"] == pytest.approx(0.007)
    assert decode["scopes"]["sample"] == pytest.approx(0.002)
    assert decode["scopes"][""] == pytest.approx(0.001)
    prefill = got["jit_paged_forward_step(22)"]["scopes"]
    assert prefill["attn"] == pytest.approx(0.012)
    assert prefill["moe"] == pytest.approx(0.010)
    assert prefill["lm_head"] == pytest.approx(0.004)
    # under no scope: the loop's own time (its duration less its body's),
    # the weight slice before attention and the add between experts and
    # lm_head (their scoped neighbours differ)
    assert prefill[""] == pytest.approx((0.030 - 0.028) + 0.001 + 0.001)
    assert host_spans.scope_of("jit(f)/while/body/mlp/jit(silu)/mul") == "mlp"
    assert host_spans.scope_of("jit(f)/attn_inputs/cos") is None
    assert host_spans.scope_of("") is None


# ---------------------------------------------------------------------------
# the wire reader, on a file encoded by hand
# ---------------------------------------------------------------------------

def _varint(v):
    v &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
        v >>= 7
        if not v:
            return bytes(out)


def _msg(*fields):
    """``(number, value)`` pairs: an int is a varint, bytes/str a
    length-delimited field."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def test_wire_reader_gives_events_their_metadatas_stats(tmp_path):
    from harness import xplane_wire
    path = "jit(paged_forward_step)/while/body/closed_call/attn/dot_general:"
    stat_md = [_msg((1, i), (2, _msg((1, i), (2, n))))
               for i, n in ((1, "tf_op"), (2, "pass_id"), (3, path),
                            (4, "flops"))]
    op_md = _msg((1, 7), (2, _msg(
        (1, 7), (2, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"),
        (5, _msg((1, 1), (7, 3))),               # tf_op = ref to name 3
        (5, _msg((1, 4), (3, 99))))))            # a stat nobody asked for
    span_md = _msg((1, 8), (2, _msg((1, 8), (2, "pass.dispatch"))))
    ops = _msg((2, "XLA Ops"), (3, 1000), (4, _msg(
        (1, 7), (2, 5_000_000), (3, 2_000_000))))
    other = _msg((2, "Steps"), (4, _msg((1, 7), (2, 1), (3, 1))))
    dev = _msg((1, 1), (2, DEV0), (3, ops), (3, other), (4, op_md),
               *[(5, m) for m in stat_md])
    span = _msg((1, 8), (2, 0), (3, 4_000_000), (4, _msg((1, 2), (4, -3))))
    host = _msg((2, HOST), (3, _msg((2, "python3"), (3, 2000), (4, span))),
                (4, span_md), *[(5, m) for m in stat_md])
    skipped = _msg((2, "/host:metadata"), (3, ops))
    f = tmp_path / "toy.xplane.pb"
    f.write_bytes(_msg((1, dev), (1, host), (1, skipped)))
    planes = xplane_wire.read_planes(
        str(f), want_plane=lambda p: p in (DEV0, HOST),
        want_line=lambda p, ln: ln != "Steps",
        keep_stats=("tf_op", "pass_id"))
    assert set(planes) == {DEV0, HOST} and set(planes[DEV0]) == {"XLA Ops"}
    (op,), (span,) = planes[DEV0]["XLA Ops"], planes[HOST]["python3"]
    assert op.name.startswith("%fusion.1 = ")
    assert op.start == pytest.approx(1000e-9 + 5e-6) and op.dur == 2e-6
    assert op.stats == {"tf_op": path}
    assert (span.name, span.stats) == ("pass.dispatch", {"pass_id": -3})
    assert span.start == pytest.approx(2e-6) and span.dur == 4e-6
    # and through the readers' loader: short names, the path as "scope"
    from harness import reduce_trace
    f2 = tmp_path / "plugins" / "profile" / "t" / "x.xplane.pb"
    f2.parent.mkdir(parents=True)
    f2.write_bytes(f.read_bytes())
    got = host_spans.load_planes(reduce_trace.find_xplane(str(tmp_path)))
    (op,) = got[DEV0]["XLA Ops"]
    assert (op.name, op.stats) == ("fusion.1", {"scope": path})
    assert host_spans.scope_of(op.stats["scope"]) == "attn"
    assert [e.name for e in host_spans.host_span_events(got)] == \
        ["pass.dispatch"]


# ---------------------------------------------------------------------------
# host seconds from the snapshots
# ---------------------------------------------------------------------------

def _snap(series, dispatches, prefill, lag=None):
    prom = {host_spans.HOST_SECONDS: {"type": "counter", "series": [
        {"labels": {"span": s, "under": u}, "value": v}
        for (s, u), v in series.items()]}}
    if lag is not None:
        prom["nxdi_sse_lag_seconds"] = {"type": "histogram", "series": [
            {"labels": {}, "count": lag[-1][1], "sum": 0.0, "buckets": lag}]}
    return {"counters": {"host_stats.dispatches": dispatches,
                         "host_stats.prefill_dispatches": prefill},
            "prom": prom}


def _counter_ctx():
    before = _snap({("pass.admit", ""): 1.0, ("run.paged", "pass.dispatch"):
                    0.5}, 10, 5, lag=[[0.001, 0], [0.0025, 0], [0.005, 0]])
    after = _snap({
        ("pass.expire", ""): 0.010, ("pass.preempt", ""): 0.020,
        ("pass.admit", ""): 1.0 + 3.000,
        ("dispatch.prefill_chunk", "pass.admit"): 2.900,
        ("run.paged", "dispatch.prefill_chunk"): 0.100,
        ("fetch.tokens", "dispatch.prefill_chunk"): 2.700,
        ("pass.dispatch", ""): 5.000,
        ("run.paged", "pass.dispatch"): 0.5 + 0.400,
        ("fetch.tokens", "pass.dispatch"): 4.300,
        ("loop.yield", ""): 0.600, ("loop.idle", ""): 1.070,
    }, 10 + 80, 5 + 20, lag=[[0.001, 50], [0.0025, 90], [0.005, 100]])
    return {"before": before, "after": after,
            "e2e": {"tokens_in_window": 1000, "tokens_per_s": 100.0}}


def test_host_breakdown_is_self_time_and_adds_up():
    b = host_spans.host_breakdown(_counter_ctx())
    assert b["dispatches"] == 100
    assert b["sched_s"] == pytest.approx(0.010 + 0.020 + 3.0 - 2.9)
    assert b["run_prep_s"] == pytest.approx(0.5)
    assert b["dispatch_self_s"] == pytest.approx((5.0 - 4.7) + (2.9 - 2.8))
    assert b["fetch_wait_s"] == pytest.approx(7.0)
    assert b["loop_yield_s"] == pytest.approx(0.6)
    assert b["loop_idle_s"] == pytest.approx(1.07)
    parts = ("sched_s", "run_prep_s", "dispatch_self_s", "fetch_wait_s",
             "loop_yield_s", "loop_idle_s")
    assert sum(b[k] for k in parts) == pytest.approx(b["named_s"])
    assert b["window_s"] == pytest.approx(10.0)
    assert b["coverage"] == pytest.approx(0.97)


def test_counter_readers_by_name(capsys):
    ctx = _counter_ctx()
    assert readers.read_metric("host.sched_ms_per_dispatch", ctx) == \
        pytest.approx(1.3)
    assert readers.read_metric("host.run_prep_ms_per_dispatch", ctx) == \
        pytest.approx(5.0)
    assert readers.read_metric("host.dispatch_self_ms_per_dispatch", ctx) \
        == pytest.approx(4.0)
    assert "coverage 0.97" in capsys.readouterr().out
    assert readers.read_metric("host.loop_yield_ms_per_dispatch", ctx) == \
        pytest.approx(6.0)
    # 90 of 100 tokens within 2.5 ms: the p90 is the bucket's upper edge
    assert readers.read_metric("frontdoor.sse_lag_p90_ms", ctx) == \
        pytest.approx(2.5)


def test_a_program_without_spans_or_counters_reads_nothing(monkeypatch,
                                                           tmp_path):
    """The parent commit: no counter, no span, no scope; no slice on disk."""
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    empty = {"counters": {"host_stats.dispatches": 3}, "prom": {}}
    ctx = {"before": empty, "after": dict(empty, counters={
        "host_stats.dispatches": 9}), "e2e": {}, "cell": {"none": 1},
        "trace": {"programs": {"paged.w1": {"count": 2, "total_s": 0.02}}},
        "warm_widths": [1, 16]}
    for name in NEW_METRICS:
        assert readers.read_metric(name, ctx) is None, name
    # a slice with device events but an old program: still nothing, no raise
    planes = _planes()
    planes[HOST] = {"python": [Event("x", 0.0, 1.0)]}
    for e in planes[DEV0]["XLA Ops"]:
        e.stats["scope"] = ""
    monkeypatch.setattr(host_spans, "load_slice", lambda ctx: {
        "planes": planes, "idle": host_spans.idle_by_span(planes),
        "scopes": host_spans.scope_seconds(planes)})
    for name in NEW_METRICS:
        assert readers.read_metric(name, ctx) is None, name


def test_trace_readers_by_name(monkeypatch, capsys):
    planes = _planes()
    monkeypatch.setattr(host_spans, "load_slice", lambda ctx, got={
        "planes": planes, "idle": host_spans.idle_by_span(planes),
        "scopes": host_spans.scope_seconds(planes)}: got)
    programs = {"jit_paged_forward_step(11)": ("paged", 1),
                "jit_paged_forward_step(22)": ("paged", 16)}
    red = reduce_trace({k: v for k, v in planes.items() if k != HOST},
                       programs)
    ctx = {"trace": red, "warm_widths": [1, 16]}
    shares = {c: readers.read_metric(f"device.idle_{c}_share", ctx)
              for c in ("sched", "dispatch", "yield", "nowork")}
    assert shares["dispatch"] == pytest.approx(100 * 0.009 / 0.090)
    assert shares["nowork"] == pytest.approx(100 * 0.004 / 0.090)
    said = capsys.readouterr().out
    assert said.count("device idle by host span") == 1       # printed once
    assert "remainder 1.111" in said
    remainder = 100 * 0.001 / 0.090
    assert sum(shares.values()) + remainder == \
        pytest.approx(readers.trace_idle_share(ctx))
    assert readers.read_metric("step.decode_attn_ms", ctx) == \
        pytest.approx(5.0)
    assert readers.read_metric("step.decode_moe_ms", ctx) == \
        pytest.approx(3.5)
    assert readers.read_metric("step.prefill_attn_ms", ctx) == \
        pytest.approx(12.0)
    assert readers.read_metric("step.prefill_moe_ms", ctx) == \
        pytest.approx(10.0)
    # a program that did not run in the slice has nothing to report
    assert host_spans.program_scope_ms(ctx, "paged", 64, "attn") is None


def test_a_scope_named_in_a_metrics_file_is_a_scope_of_the_trace(monkeypatch):
    """The toy benchmark's ``step.decode_mixer_ms.json`` gives its reader the
    scope ``mixer``: with that file in reach the trace is split by it, the
    outermost scope still wins, and the six built-in ones read as before."""
    assert host_spans.known_scopes() == host_spans.SCOPES
    assert host_spans.scope_of("jit(f)/while/body/mixer/attn/dot:") == "attn"
    monkeypatch.setattr(build, "DATA_ROOT", TOY)
    scopes = host_spans.known_scopes()
    assert scopes == host_spans.SCOPES + ("mixer",)
    assert host_spans.scope_of("jit(f)/while/body/mixer/attn/dot:",
                               scopes) == "mixer"
    planes = _planes()
    # the second decode step's expert matmul becomes a mixer's scan
    planes[DEV0]["XLA Ops"][-1] = _op(
        "fusion.2", 0.081, 0.004,
        "jit(paged_forward_step)/while/body/closed_call/mixer/scan")
    before = host_spans.scope_seconds(planes)["jit_paged_forward_step(11)"]
    assert "mixer" not in before["scopes"]
    assert before["scopes"][""] == pytest.approx(0.001 + 0.004)
    after = host_spans.scope_seconds(planes, scopes)
    decode = after["jit_paged_forward_step(11)"]["scopes"]
    assert decode["mixer"] == pytest.approx(0.004)
    assert decode["attn"] == pytest.approx(0.010)
    assert decode["moe"] == pytest.approx(0.003)
    assert after["jit_paged_forward_step(22)"] == \
        host_spans.scope_seconds(_planes())["jit_paged_forward_step(22)"]
    # through the reader kind, by the metric's name
    monkeypatch.setattr(host_spans, "load_slice", lambda ctx: {
        "planes": planes, "idle": None, "scopes": after})
    red = reduce_trace({k: v for k, v in planes.items() if k != HOST},
                       {"jit_paged_forward_step(11)": ("paged", 1),
                        "jit_paged_forward_step(22)": ("paged", 16)})
    ctx = {"trace": red, "warm_widths": [1, 16]}
    assert readers.read_metric("step.decode_mixer_ms", ctx) == \
        pytest.approx(2.0)
    assert readers.read_metric("step.decode_attn_ms", ctx) == \
        pytest.approx(5.0)


def test_every_new_metric_is_declared_as_its_file_says():
    with open(os.path.join(build.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-len(NEW_METRICS):] == \
        list(NEW_METRICS)                    # appended, in the issue's order
    for name in NEW_METRICS:
        spec = build.load_json("layer_metrics", name + ".json")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert declared[name][key] == spec[key], (name, key)
        has_code = os.path.exists(os.path.join(
            build.BENCH_DIR, "layer_metrics", name + ".py"))
        assert has_code == (spec["reader"]["kind"] == "python"), name


# ---------------------------------------------------------------------------
# one toy run: the metrics the CPU can read appear on the last line
# ---------------------------------------------------------------------------

FAKE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def test_toy_run_reports_the_host_metrics(monkeypatch, tmp_path):
    """The toy benchmark with the real ``per_layer`` entries of this PR
    appended: the counter-fed metrics are on the last line of a traced run;
    the eight that need a device plane find none on the CPU and are left
    out, and the slice's host plane carries the program's spans."""
    from neuronx_distributed_inference_tpu import telemetry
    from neuronx_distributed_inference_tpu.telemetry.trace import \
        disable_recorder
    root = tmp_path / "toy"
    shutil.copytree(TOY, root)
    with open(os.path.join(build.ROOT, "BENCHMARK.json")) as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    for name in NEW_METRICS:
        entry = {k: v for k, v in real[name].items() if k != "workloads"}
        bench["per_layer"].append(entry)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    monkeypatch.setattr(build, "DATA_ROOT", str(root))
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path / "out"))
    gate = build.logit_gate
    monkeypatch.setattr(build, "logit_gate",
                        lambda cfg, seed: gate(cfg, seed, "highest"))
    args = argparse.Namespace(workload="toy-closed", seed=2**31 + 25,
                              seconds=2.0, trace=1)
    try:
        out = run.run_cell(args, require_chips=lambda chips: dict(FAKE))
    finally:
        telemetry.disable()
        disable_recorder()
    assert out["correct"] is True and out["failed"] == 0
    m = out["metrics"]
    on_cpu = [n for n in NEW_METRICS if n.startswith(("host.", "frontdoor."))]
    assert [n for n in NEW_METRICS if n in m] == on_cpu
    for name in on_cpu:
        assert m[name]["unit"] == "ms" and m[name]["value"] >= 0.0, name
    assert m["host.run_prep_ms_per_dispatch"]["value"] > 0.0
    assert m["host.dispatch_self_ms_per_dispatch"]["value"] > 0.0
    # the slice found by the cell's file, its host plane full of spans
    ctx = {"cell": build.load_json("cells", "toy-closed.json")}
    assert host_spans.slice_trace_dir(ctx) == \
        str(tmp_path / "out" / "trace-toy-closed")
    planes = host_spans.load_slice(ctx)["planes"]
    spans = sorted(host_spans.host_span_events(planes), key=lambda e: e.start)
    assert {"pass.admit", "pass.dispatch", "loop.yield", "run.paged",
            "fetch.tokens"} <= {e.name for e in spans}
    # the wire reader and jax's own reader see the same events at the same
    # instants (jax's keeps no metadata stats, which is why there are two)
    from harness import reduce_trace
    from jax.profiler import ProfileData
    path = reduce_trace.find_xplane(host_spans.slice_trace_dir(ctx))
    theirs = sorted((e.start_ns * 1e-9, e.duration_ns * 1e-9, e.name)
                    for p in ProfileData.from_file(path).planes
                    if p.name == HOST for ln in p.lines for e in ln.events
                    if e.name in host_spans.SPANS or e.name.startswith("run."))
    assert len(theirs) == len(spans) > 20
    for (start, dur, name), e in zip(theirs, spans):
        assert name == e.name
        assert start == pytest.approx(e.start, abs=2e-9)
        assert dur == pytest.approx(e.dur, abs=2e-9)
