"""The per-layer metrics of a request's time to first token by phase (ISSUE
52): six data files over ``engine.ttft_*`` and one reader of the two marks
the program leaves on the slice's host plane."""

import argparse
import json
import os
import shutil

import pytest

import run
from harness import build, host_spans, readers, reduce_trace
from harness.reduce_trace import Event

PHASES = ("accept", "queue", "prefill_wait", "prefill", "write")
TTFT_METRICS = tuple(f"ttft.{p}_ms" for p in PHASES) + (
    "ttft.server_ms", "ttft.device_idle_share")
TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
DEV0, HOST = "/device:TPU:0", host_spans.HOST_PLANE


def _real_entries():
    with open(os.path.join(build.ROOT, "BENCHMARK.json")) as f:
        return {m["name"]: m for m in json.load(f)["per_layer"]}


@pytest.mark.parametrize("name", TTFT_METRICS)
def test_a_ttft_metric_is_declared_as_its_file_says(name):
    spec = build.load_json("layer_metrics", name + ".json")
    entry = _real_entries()[name]
    for key in ("unit", "better", "source", "layer"):
        assert entry[key] == spec[key], (name, key)
    assert entry["moves"] == spec["moves"] == "ttft_p50_ms"
    assert entry["workloads"] == ["olmoe-chat-steady"]
    kind = spec["reader"]["kind"]
    has_code = os.path.exists(os.path.join(
        build.BENCH_DIR, "layer_metrics", name + ".py"))
    assert has_code == (kind == "python"), name
    assert kind == "python" or kind in readers.KINDS, kind
    # the chat cell lists it, no other cell does
    cells = [w["name"] for w in json.load(open(os.path.join(
        build.ROOT, "BENCHMARK.json")))["workloads"]]
    listed = [c for c in cells if name in
              [m["name"] for m in run.load_cell(c)["per_layer"]]]
    assert listed == ["olmoe-chat-steady"]


def _ctx(before, after):
    return {"before": {"counters": {f"engine.{k}": v
                                    for k, v in before.items()}, "prom": {}},
            "after": {"counters": {f"engine.{k}": v
                                   for k, v in after.items()}, "prom": {}}}


def test_the_six_means_are_window_deltas_and_add_up():
    before = {"ttft_requests": 10, "ttft_server_s": 1.0,
              **{f"ttft_{p}_s": 0.2 for p in PHASES}}
    seconds = dict(zip(PHASES, (0.012, 0.004, 0.020, 0.140, 0.024)))
    after = {"ttft_requests": 30, "ttft_server_s": 1.0 + sum(
        seconds.values()), **{f"ttft_{p}_s": 0.2 + s
                              for p, s in seconds.items()}}
    ctx = _ctx(before, after)
    got = {p: readers.read_metric(f"ttft.{p}_ms", ctx) for p in PHASES}
    assert got == pytest.approx({p: 1e3 * s / 20
                                 for p, s in seconds.items()})
    assert readers.read_metric("ttft.server_ms", ctx) == pytest.approx(
        sum(got.values())) == pytest.approx(10.0)
    # a program without the keys (the parent): nothing to read, no raise
    parent = _ctx({"submitted": 3}, {"submitted": 30})
    for name in TTFT_METRICS[:-1]:
        assert readers.read_metric(name, parent) is None
    # a window in which no first token was written
    assert readers.read_metric("ttft.queue_ms", _ctx(before, before)) is None


def _mod():
    return readers.load_module(os.path.join(
        build.BENCH_DIR, "layer_metrics", "ttft.device_idle_share.py"))


def _mark(name, t, trace):
    return Event(name, t, 1e-7, {"trace": trace})


def test_marks_pair_by_their_trace_stat():
    mod = _mod()
    marks = [
        _mark("request.token", 0.5, "aa"),       # admitted before the slice
        _mark("request.admit", 1.0, "bb"),
        _mark("request.admit", 1.1, "cc"),       # rolled back ...
        _mark("request.token", 2.0, "bb"),
        _mark("request.admit", 2.5, "cc"),       # ... and admitted again
        _mark("request.token", 3.0, "cc"),
        _mark("request.admit", 3.5, 1234567),    # a trace id of digits alone
        _mark("request.token", 3.75, 1234567),   # comes back as an integer
        _mark("request.admit", 4.0, "dd"),       # answered after the slice
    ]
    assert mod.prefill_intervals(reversed(marks)) == [
        (1.0, 2.0), (2.5, 3.0), (3.5, 3.75)]
    assert mod.prefill_intervals([]) == []


def test_idle_inside_the_union_of_the_intervals():
    mod = _mod()
    planes = {DEV0: {reduce_trace.OPS_LINE: [
        Event("fusion.1", 0.0, 1.25), Event("fusion.2", 1.5, 0.25),
        Event("fusion.3", 2.75, 1.0)]}}
    # union 1.0 .. 3.0 (the two overlap); busy in it 1.0-1.25, 1.5-1.75,
    # 2.75-3.0
    idle_s, union_s = mod.idle_inside(planes, [(1.0, 2.0), (1.5, 3.0)])
    assert (idle_s, union_s) == pytest.approx((1.25, 2.0))
    # two chips: the mean of their idle time
    planes["/device:TPU:1"] = {reduce_trace.OPS_LINE: [
        Event("fusion.9", 0.0, 4.0)]}
    assert mod.idle_inside(planes, [(1.0, 3.0)])[0] == pytest.approx(0.625)
    assert mod.idle_inside(planes, []) is None
    assert mod.idle_inside({}, [(1.0, 2.0)]) is None


def test_the_reader_reads_a_number_whenever_one_pair_lies_in_the_slice(
        monkeypatch, tmp_path, capsys):
    planes = {DEV0: {reduce_trace.OPS_LINE: [Event("fusion.1", 0.0, 1.5),
                                             Event("fusion.2", 3.0, 1.0)]}}
    monkeypatch.setattr(host_spans, "load_slice", lambda ctx: {
        "planes": planes})
    monkeypatch.setattr(host_spans, "slice_trace_dir",
                        lambda ctx, out_dir=None: str(tmp_path))
    monkeypatch.setattr(reduce_trace, "find_xplane", lambda d: "x.pb")
    marks = [_mark("request.admit", 1.0, "bb"),
             _mark("request.token", 2.0, "bb")]
    from harness import xplane_wire
    monkeypatch.setattr(xplane_wire, "read_planes", lambda path, **kw: {
        HOST: {"python3": marks + [Event("pass.admit", 0.9, 1.2)]}})
    assert readers.read_metric("ttft.device_idle_share", {}) == \
        pytest.approx(50.0)
    assert "1 requests admitted and answered inside the slice" in \
        capsys.readouterr().out
    # no pair in the slice, no slice: nothing, and no raise
    marks.pop()
    assert readers.read_metric("ttft.device_idle_share", {}) is None
    monkeypatch.setattr(host_spans, "load_slice", lambda ctx: None)
    assert readers.read_metric("ttft.device_idle_share", {}) is None


FAKE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def test_toy_run_reports_the_six_means(monkeypatch, tmp_path):
    """The toy's open-loop cell with the real ``per_layer`` entries of this
    PR appended, traced: the six means are on the last line and the five
    add up to the sixth; the seventh needs a device plane, which the CPU's
    trace has not, and is left out; the marks are on the slice's host
    plane."""
    from neuronx_distributed_inference_tpu import telemetry
    from neuronx_distributed_inference_tpu.telemetry.trace import \
        disable_recorder
    root = tmp_path / "toy"
    shutil.copytree(TOY, root)
    real = _real_entries()
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    for name in TTFT_METRICS:
        bench["per_layer"].append(dict(real[name], workloads=["toy-open"]))
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    monkeypatch.setattr(build, "DATA_ROOT", str(root))
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path / "out"))
    gate = build.logit_gate
    monkeypatch.setattr(build, "logit_gate",
                        lambda cfg, seed: gate(cfg, seed, "highest"))
    args = argparse.Namespace(workload="toy-open", seed=2**31 + 52,
                              seconds=3.0, trace=1)
    try:
        out = run.run_cell(args, require_chips=lambda chips: dict(FAKE))
    finally:
        telemetry.disable()
        disable_recorder()
    assert out["correct"] is True and out["failed"] == 0
    m = out["metrics"]
    assert [n for n in TTFT_METRICS if n in m] == list(TTFT_METRICS[:-1])
    parts = [m[f"ttft.{p}_ms"]["value"] for p in PHASES]
    assert all(v >= 0.0 for v in parts) and m["ttft.prefill_ms"]["value"] > 0
    assert sum(parts) == pytest.approx(m["ttft.server_ms"]["value"],
                                       rel=1e-6)
    # below the client's own tail of the same run, which sees more
    assert m["ttft.server_ms"]["value"] < m["path.ttft_p90_ms"]["value"]
    mod = _mod()
    marks = mod.mark_events(reduce_trace.find_xplane(
        str(tmp_path / "out" / "trace-toy-open")))
    assert {e.name for e in marks} <= set(mod.MARKS)
    assert all(e.stats.get("trace") not in (None, "") for e in marks)
