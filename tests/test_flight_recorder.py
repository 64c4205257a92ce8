"""Flight recorder, trace timeline, post-mortem dumps, debug endpoints,
graph observatory, and the metric-name lint (ISSUE 7) — on the tiny
synthetic paged model shared with test_serving_engine (CPU, <20s).

Pins:
  * Chrome trace export from a closed-loop engine run is valid
    trace-event JSON with the GOLDEN stable event names;
  * a fault-injected run's post-mortem dump names the failing dispatch
    (phase + seq_ids) and states its own truncation;
  * the disabled-default path is bit-identical (tokens AND jit cache
    keys) to a recorder-enabled run — trace hooks change nothing;
  * tenant labels propagate onto the failure counters;
  * a span that ran long leaves a record (ISSUE 38): counted once, under
    the innermost such span, never under ``pass.*`` or ``loop.idle``; the
    record outlives a wrap of the ring; a stall injected through the fault
    hook lands in the adapter's gap counts and in the record;
  * metric names and the README table cannot drift (tier-1 lint).
"""

import asyncio
import json
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from neuronx_distributed_inference_tpu import telemetry
from neuronx_distributed_inference_tpu.config import TpuConfig
from neuronx_distributed_inference_tpu.models.application import \
    PagedCausalLMApplication
from neuronx_distributed_inference_tpu.models.llama import (
    LlamaFamily, LlamaInferenceConfig)
from neuronx_distributed_inference_tpu.resilience import (DeadlineExceeded,
                                                          FAULTS, StepFailure)
from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
from neuronx_distributed_inference_tpu.serving.engine import (ServingEngine,
                                                              ServingFrontend)
from neuronx_distributed_inference_tpu.telemetry import metrics as tmetrics
from neuronx_distributed_inference_tpu.telemetry import trace as trace_mod

REPO = Path(__file__).resolve().parent.parent

HF = dict(model_type="llama", hidden_size=64, intermediate_size=128,
          num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, vocab_size=512, rms_norm_eps=1e-5, rope_theta=10000.0,
          hidden_act="silu", tie_word_embeddings=False,
          torch_dtype="float32")


@pytest.fixture(scope="module")
def paged_app():
    """Same shapes as test_serving_engine so every graph is warm in the
    persistent compile cache."""
    tcfg = TpuConfig(batch_size=4, seq_len=64, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     is_block_kv_layout=True, pa_block_size=8,
                     is_prefix_caching=True)
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                                   LlamaFamily)
    app.init_random_weights(7).init_cache()
    return app


@pytest.fixture(autouse=True)
def _observability_disabled_after():
    yield
    telemetry.disable()
    telemetry.disable_recorder()


def _prompts(seed, n, length=9):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 500, size=length).tolist() for _ in range(n)]


def _drain(app, eng, prompts, n_new=5):
    streams = [eng.submit(p, n_new, tenant=f"t{i % 2}")
               for i, p in enumerate(prompts)]
    eng.run_until_drained()
    assert all(s.finish_reason == "length" for s in streams)
    assert not app.kv_mgr.tables
    return [s.tokens for s in streams]


# ---------------------------------------------------------------------------
# recorder unit semantics + exports (no device work)
# ---------------------------------------------------------------------------

def test_recorder_ring_bounded_and_drop_counter():
    reg = telemetry.enable()
    rec = trace_mod.FlightRecorder(capacity=4)
    for i in range(10):
        rec.instant("stream.deliver", tokens=i)
    assert len(rec) == 4 and rec.dropped == 6
    assert [e["args"]["tokens"] for e in rec.events()] == [6, 7, 8, 9]
    assert reg.get(tmetrics.TRACE_EVENTS_DROPPED_TOTAL).get(
        ring="trace") == 6
    # the tail (post-mortem payload) is newest-last and honest about size
    assert [e["args"]["tokens"] for e in rec.tail(2)] == [8, 9]
    assert rec.to_chrome()["otherData"]["dropped_events"] == 6


def test_span_ring_drop_counter():
    reg = telemetry.MetricsRegistry(max_spans=2)
    for i in range(5):
        reg.start_span("request", i=i).end()
    assert len(reg.spans) == 2 and reg.spans_dropped == 3
    assert reg.get(tmetrics.TRACE_EVENTS_DROPPED_TOTAL).get(
        ring="spans") == 3


def test_error_event_attaches_trace_id():
    rec = trace_mod.FlightRecorder()
    err = StepFailure("boom", phase="decode", seq_ids=(3, 4),
                      retry_safe=False)
    assert err.trace_id is None
    rec.error(err)
    ev = rec.events()[-1]
    assert err.trace_id == ev["id"]
    assert ev["name"] == "error.StepFailure"
    assert ev["args"]["seq_ids"] == [3, 4]
    assert ev["args"]["phase"] == "decode"
    assert ev["args"]["retry_safe"] is False


# ---------------------------------------------------------------------------
# a span that ran long leaves a record
# ---------------------------------------------------------------------------

class _Clock:
    """Stands in for ``time`` inside telemetry/trace.py: slices get the
    durations a test gives them."""

    def __init__(self):
        self.t = 100.0

    def perf_counter(self):
        return self.t


def test_a_slow_span_is_counted_once_under_the_innermost(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(trace_mod, "time", clock)
    monkeypatch.setattr(trace_mod, "STALL_SECONDS", 1.0)   # patched down
    reg = telemetry.enable()
    rec = trace_mod.FlightRecorder(capacity=8)
    with rec.span("pass.dispatch"):
        with rec.span("run.paged", cat="app", rows=2):
            clock.t += 0.4
            with rec.span("prep.rng", cat="app"):
                clock.t += 1.5                      # the stall
            clock.t += 0.4
        with rec.span("dispatch.retire", cat="adapter"):
            clock.t += 0.9                          # long, not a stall
    with rec.span("loop.idle"):
        clock.t += 30.0                             # the quiet: never
    stalls = rec.stalls()
    assert [(e["name"], e["dur"], e["around"]) for e in stalls] == \
        [("prep.rng", pytest.approx(1.5), ["pass.dispatch", "run.paged"])]
    assert stalls[0]["ts"] == pytest.approx(100.4)
    secs = reg.get(tmetrics.HOST_STALL_SECONDS_TOTAL)
    count = reg.get(tmetrics.HOST_STALLS_TOTAL)
    assert secs.get(span="prep.rng") == pytest.approx(1.5)
    assert count.get(span="prep.rng") == 1
    # run.paged was 2.3 s long and pass.dispatch 3.2 s: explained by the
    # stall inside (0.8 s and 1.7 s are their own), and pass.* never counts
    for name in ("run.paged", "pass.dispatch", "loop.idle",
                 "dispatch.retire"):
        assert count.get(span=name) == 0, name
    # a span whose OWN part is long as well is a stall of its own, for
    # that part; an unknown name folds into "other"; args are kept
    with rec.span("pass.admit"):
        with rec.span("dispatch.prefill_chunk", cat="adapter", rows=3):
            clock.t += 1.2
            with rec.span("not.a.stable.name"):
                clock.t += 2.0
    assert [e["name"] for e in rec.stalls()] == \
        ["prep.rng", "not.a.stable.name", "dispatch.prefill_chunk"]
    assert rec.stalls()[-1]["args"] == {"rows": 3}
    assert rec.stalls()[-1]["dur"] == pytest.approx(3.2)
    assert secs.get(span="other") == pytest.approx(2.0)
    assert secs.get(span="dispatch.prefill_chunk") == pytest.approx(1.2)
    assert count.get(span="pass.admit") == 0
    assert sum(s["value"] for s in secs._snapshot()) == pytest.approx(4.7)
    for s in secs._snapshot() + count._snapshot():
        assert s["labels"]["span"] in set(trace_mod.EVENT_NAMES) | {"other"}
    # registry off: the record is still kept, nothing is counted
    telemetry.disable()
    rec.complete("fetch.tokens", clock.t - 5.0, cat="adapter")
    assert rec.stalls()[-1]["name"] == "fetch.tokens"
    assert count.get(span="fetch.tokens") == 0


def test_stall_records_outlive_the_ring_and_are_bounded():
    rec = trace_mod.FlightRecorder(capacity=4)
    rec.complete("run.paged", 0.0, cat="app", t1=9.4, rows=32)
    for i in range(10):
        rec.instant("stream.deliver", tokens=i)
    assert rec.dropped == 7
    assert "run.paged" not in [e["name"] for e in rec.events()]
    assert [(e["name"], e["dur"], e["args"]) for e in rec.stalls()] == \
        [("run.paged", 9.4, {"rows": 32})]
    for i in range(trace_mod.STALL_RECORDS + 6):
        rec.complete("fetch.tokens", float(i), cat="adapter", t1=i + 2.5)
    kept = rec.stalls()
    assert len(kept) == trace_mod.STALL_RECORDS
    assert kept[0]["ts"] == 6.0 and kept[-1]["ts"] == 69.0   # the newest
    assert trace_mod.NULL_RECORDER.stalls() == []
    rec.clear()
    assert rec.stalls() == []


def test_host_seconds_series_follow_the_live_registry():
    """The recorder holds each series' adder per registry: a new registry
    gets its own, the old one is left as it was."""
    rec = trace_mod.FlightRecorder()
    first = telemetry.enable()
    rec.complete("pass.admit", 0.0, t1=0.5)
    rec.complete("pass.admit", 1.0, t1=1.25)
    telemetry.disable()
    second = telemetry.enable()
    assert second is not first
    rec.complete("pass.admit", 2.0, t1=3.0)
    assert first.get(tmetrics.HOST_SECONDS_TOTAL).get(
        span="pass.admit", under="") == pytest.approx(0.75)
    assert second.get(tmetrics.HOST_SECONDS_TOTAL).get(
        span="pass.admit", under="") == pytest.approx(1.0)


def _validate_chrome(chrome):
    """Minimal validating parser for Chrome trace-event JSON: the shape
    chrome://tracing / Perfetto load. Returns non-metadata event names."""
    chrome = json.loads(json.dumps(chrome))         # JSON-able
    assert isinstance(chrome["traceEvents"], list)
    names = []
    for ev in chrome["traceEvents"]:
        assert isinstance(ev["name"], str) and ev["ph"] in ("M", "X", "i")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] == "M":
            assert ev["args"]["name"].startswith("nxdi.")
            continue
        assert isinstance(ev["ts"], float) and ev["ts"] >= 0.0
        assert isinstance(ev["cat"], str) and ev["args"]["id"]
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], float) and ev["dur"] >= 0.0
        else:
            assert ev["s"] == "t"
        names.append(ev["name"])
    return names


def test_jsonl_export_parses():
    rec = trace_mod.FlightRecorder()
    rec.instant("compile", cat="app", kind="paged", bucket="16")
    with rec.span("pass.admit", cat="engine"):
        pass
    lines = rec.to_jsonl().splitlines()
    assert len(lines) == 2
    objs = [json.loads(l) for l in lines]
    assert objs[0]["name"] == "compile" and objs[0]["ph"] == "i"
    assert objs[1]["name"] == "pass.admit" and objs[1]["ph"] == "X"
    assert objs[1]["dur"] >= 0.0


GOLDEN_EVENT_NAMES = (
    "pass.expire", "pass.preempt", "pass.admit", "pass.dispatch",
    "loop.yield", "loop.idle",
    "stream.deliver", "admission.headroom", "deliver.tokens",
    "dispatch.decode", "dispatch.decode_loop", "dispatch.prefill_chunk",
    "dispatch.ragged", "fetch.tokens", "preempt", "dispatch.build",
    "dispatch.retire",
    "run.prefill", "run.decode", "run.decode_loop", "run.paged",
    "run.paged_loop", "run.ragged", "run.spec_draft", "run.spec_verify",
    "compile", "prep.inputs", "prep.rng", "prep.enqueue",
    "fleet.route", "fleet.drain", "kv.spill", "kv.restore", "handoff.send",
    "handoff.recv", "fleet.all_dead", "fleet.scale_up", "fleet.scale_down",
    "trace.begin", "trace.admit", "trace.requeue", "trace.emit",
    "request.accept", "request.queue", "request.prefill_wait",
    "request.prefill", "request.write",
    "degrade.enter", "degrade.exit", "compile.unexpected")


def test_event_names_golden():
    """The stable-name contract, spelled out: a rename or a removal is a
    breaking change and must edit this list (and the README table)."""
    assert trace_mod.EVENT_NAMES == GOLDEN_EVENT_NAMES
    readme = (REPO / "README.md").read_text()
    for name in GOLDEN_EVENT_NAMES:
        if not name.startswith("run."):          # one `run.<kind>` row
            assert f"`{name}`" in readme, name


# ---------------------------------------------------------------------------
# closed-loop engine run: golden event names + bit-identity pin
# ---------------------------------------------------------------------------

def test_engine_trace_golden_phases_and_disabled_bit_identity(paged_app):
    """The acceptance pin: a recorder-OFF run (library default) and a
    recorder-ON run produce bit-identical token streams and identical jit
    cache keys, and the ON run's Chrome export is valid trace-event JSON
    carrying the golden stable phase names."""
    prompts = _prompts(11, 4)
    assert not trace_mod.get_recorder().enabled     # library default

    def run():
        eng = ServingEngine(
            PagedEngineAdapter(paged_app, prefill_budget_tokens=16),
            starvation_bound_s=1e9)
        return _drain(paged_app, eng, prompts)

    base_tokens = run()                             # disabled baseline
    keys_before = sorted(paged_app._compiled.keys(), key=repr)

    rec = telemetry.enable_recorder()
    live_tokens = run()

    assert live_tokens == base_tokens               # bit-identical streams
    assert sorted(paged_app._compiled.keys(), key=repr) == keys_before

    names = set(_validate_chrome(rec.to_chrome()))
    # golden-pinned stable phase/event names (README "Flight recorder")
    for want in ("pass.expire", "pass.preempt", "pass.admit",
                 "pass.dispatch", "dispatch.prefill_chunk",
                 "dispatch.decode", "run.paged", "fetch.tokens",
                 "stream.deliver"):
        assert want in names, f"missing stable event {want!r}"
    # every recorded name is from the stable contract (errors prefixed)
    for n in names:
        assert n in trace_mod.EVENT_NAMES or n.startswith("error."), n
    # dispatch events carry seq labels
    ev = next(e for e in rec.events()
              if e["name"] == "dispatch.prefill_chunk")
    assert ev["args"]["seq_ids"] and ev["ph"] == "X"


# ---------------------------------------------------------------------------
# post-mortem dumps under the deterministic fault harness
# ---------------------------------------------------------------------------

def test_postmortem_dump_names_failing_decode_dispatch(paged_app, tmp_path):
    rec = telemetry.enable_recorder()
    eng = ServingEngine(PagedEngineAdapter(paged_app),
                        starvation_bound_s=1e9)
    streams = [eng.submit(p, 6, tenant="t") for p in _prompts(12, 2)]
    eng.run_pass()                                  # admitted + running
    running = sorted(eng._sid_of.values())
    with FAULTS.inject("decode_step") as fp:
        eng.run_pass()                              # retry-safe StepFailure
    assert fp.trips == 1
    assert eng.stats["step_retries"] == 1
    path = str(tmp_path / "postmortem.json")
    dump = eng.dump_debug_state(path)
    # the dump is a real artifact…
    on_disk = json.loads(Path(path).read_text())
    assert on_disk["schema"] == "nxdi-debug-state-v1"
    # …whose trace tail contains the failing dispatch with the right rows
    errs = [e for e in dump["trace"]["events"]
            if e["name"] == "error.StepFailure"]
    assert errs, "post-mortem lost the failure event"
    assert errs[-1]["args"]["phase"] == "decode"
    assert errs[-1]["args"]["seq_ids"] == running
    assert dump["trace"]["dropped"] == 0            # states its truncation
    # …and the engine/adapter snapshot carries the ISSUE's fields
    eng_state = dump["engine"]
    assert sorted(eng_state["active"]) == running
    ad = eng_state["adapter"]
    assert ad["running_ids"] == running
    assert ad["blocks"]["in_use"] > 0
    # the engine keeps one step in flight, and a fault at dispatch time
    # leaves the healthy in-flight step where it is
    assert ad["pipeline_inflight"] == len(running)
    eng.run_until_drained()                         # fault cleared: finishes
    assert all(s.finish_reason == "length" for s in streams)
    assert not paged_app.kv_mgr.tables


def test_postmortem_dump_names_failing_prefill_chunk(paged_app):
    rec = telemetry.enable_recorder()
    eng = ServingEngine(PagedEngineAdapter(paged_app),
                        starvation_bound_s=1e9)
    stream = eng.submit(_prompts(13, 1)[0], 4, tenant="t")
    with FAULTS.inject("prefill_chunk") as fp:
        eng.run_pass()                  # admission fails typed, requeued
    assert fp.trips == 1
    assert eng.stats["admission_retries"] == 1
    errs = [e for e in rec.events() if e["name"] == "error.StepFailure"]
    assert errs and errs[-1]["args"]["phase"] == "prefill"
    assert len(errs[-1]["args"]["seq_ids"]) == 1
    eng.run_until_drained()
    assert stream.finish_reason == "length"
    assert not paged_app.kv_mgr.tables


def test_queue_expiry_attaches_trace_id(paged_app):
    rec = telemetry.enable_recorder()
    eng = ServingEngine(PagedEngineAdapter(paged_app),
                        priority_preemption=False, starvation_bound_s=1e9)
    runners = [eng.submit(p, 30) for p in _prompts(14, 4)]
    eng.run_pass()
    doomed = eng.submit(_prompts(15, 1)[0], 4, deadline_s=0.01)
    time.sleep(0.02)
    eng.run_pass()
    assert doomed.finish_reason == "deadline"
    assert isinstance(doomed.error, DeadlineExceeded)
    assert doomed.error.trace_id is not None
    ev = next(e for e in rec.events()
              if e["id"] == doomed.error.trace_id)
    assert ev["name"] == "error.DeadlineExceeded"
    assert ev["args"]["where"] == "queue"
    for s in runners:
        s.cancel()
    assert not paged_app.kv_mgr.tables


# ---------------------------------------------------------------------------
# tenant label propagation onto the failure counters
# ---------------------------------------------------------------------------

def test_tenant_label_on_failure_counters(paged_app):
    reg = telemetry.enable()
    adapter = PagedEngineAdapter(paged_app)
    p1, p2 = _prompts(16, 2)
    adapter.add_requests([0], [p1], meta=[{"tenant": "acme"}])
    # preemption (scheduler-driven) carries the victim's tenant
    rec = adapter.preempt(0)
    assert rec.meta == {"tenant": "acme"}
    assert reg.get(tmetrics.PREEMPTIONS_TOTAL).get(
        engine="paged", reason="scheduler", tenant="acme") == 1
    adapter.take_preempted()
    # deadline expiry carries the tenant (the zero budget expires the
    # pending admission inside the synchronous chunked prefill)
    with pytest.raises(DeadlineExceeded):
        adapter.add_requests([1], [p2], deadline_s=0.0,
                             meta=[{"tenant": "acme"}])
    assert reg.get(tmetrics.DEADLINE_EXPIRED_TOTAL).get(
        engine="paged", tenant="acme") == 1
    # step failures carry the (unambiguous) tenant
    adapter.add_requests([2], [p2], meta=[{"tenant": "acme"}])
    with FAULTS.inject("decode_step"):
        with pytest.raises(StepFailure):
            adapter.step([2])
    assert reg.get(tmetrics.STEP_FAILURES_TOTAL).get(
        engine="paged", phase="decode", tenant="acme") == 1
    adapter.release([2])
    assert not paged_app.kv_mgr.tables


@pytest.mark.parametrize("point, traced", [("slow_step", False),
                                           ("pipeline_flush", True)])
def test_an_injected_stall_lands_in_the_gap_counts(paged_app, monkeypatch,
                                                   point, traced):
    """A decode step held up for over a second through the fault hook:
    an UNTRACED run's ``host_stats`` shows it (``decode_gaps_over_1s``,
    ``decode_gap_max_s``); recorder on, the stall record names the
    innermost span it sat in, and the post-mortem dump carries it."""
    monkeypatch.setattr(trace_mod, "STALL_SECONDS", 0.5)
    if traced:
        telemetry.enable()
        rec = telemetry.enable_recorder()
    adapter = PagedEngineAdapter(paged_app)
    eng = ServingEngine(adapter, starvation_bound_s=1e9)
    stream = eng.submit(_prompts(18, 1)[0], 8)
    for _ in range(4):
        eng.run_pass()                              # decoding, one in flight
    assert adapter.host_stats["decode_gaps"] >= 1
    assert adapter.host_stats["decode_gaps_over_1s"] == 0
    with FAULTS.inject(point, delay_s=1.05) as fp:
        eng.run_pass()
    assert fp.trips == 1
    eng.run_until_drained()
    assert stream.finish_reason == "length"
    st = adapter.host_stats
    assert st["decode_gaps_over_1s"] == 1
    assert st["decode_gaps_over_1s_behind_prefill"] == 0   # no prefill in it
    assert 1.05 <= st["decode_gap_max_s"] < st["decode_gap_s"]
    assert st["decode_gaps_behind_prefill"] == 0
    if not traced:
        return
    # the sleep sat in _retire before its fetch: dispatch.retire is the
    # innermost span around it (pass.dispatch, around that, never counts)
    assert [(e["name"], e["around"]) for e in rec.stalls()] == \
        [("dispatch.retire", ["pass.dispatch"])]
    reg = telemetry.get_registry()
    assert reg.get(tmetrics.HOST_STALLS_TOTAL).get(
        span="dispatch.retire") == 1
    assert reg.get(tmetrics.HOST_STALL_SECONDS_TOTAL).get(
        span="dispatch.retire") >= 1.05
    assert reg.get(tmetrics.DECODE_GAP_SECONDS).sum(
        engine="paged", behind="none") >= 1.05
    dump = eng.dump_debug_state()
    assert [e["name"] for e in dump["trace"]["stalls"]] == \
        ["dispatch.retire"]
    json.dumps(dump)                                # still an artifact


# ---------------------------------------------------------------------------
# debug endpoints through the asyncio front door
# ---------------------------------------------------------------------------

def test_debug_endpoints(paged_app):
    telemetry.enable_recorder()

    async def http(host, port, raw):
        r, w = await asyncio.open_connection(host, port)
        w.write(raw)
        await w.drain()
        data = await asyncio.wait_for(r.read(), timeout=90)
        w.close()
        return data

    async def main():
        eng = ServingEngine(PagedEngineAdapter(paged_app),
                            starvation_bound_s=1e9)
        fe = ServingFrontend(eng)
        host, port = await fe.start()
        body = json.dumps({"prompt": _prompts(17, 1)[0],
                           "max_new_tokens": 3}).encode()
        await http(host, port,
                   b"POST /v1/generate HTTP/1.1\r\nContent-Length: "
                   + str(len(body)).encode() + b"\r\n\r\n" + body)
        state = (await http(
            host, port, b"GET /v1/debug/state HTTP/1.1\r\n\r\n")).decode()
        dump = json.loads(state.split("\r\n\r\n", 1)[1])
        assert dump["schema"] == "nxdi-debug-state-v1"
        assert dump["engine"]["stats"]["completed"] == 1
        assert "blocks" in dump["engine"]["adapter"]
        assert dump["trace"]["enabled"] and dump["trace"]["events"]
        assert dump["trace"]["stalls"] == []        # nothing ran long
        trace_resp = (await http(
            host, port, b"GET /v1/debug/trace HTTP/1.1\r\n\r\n")).decode()
        chrome = json.loads(trace_resp.split("\r\n\r\n", 1)[1])
        assert "pass.dispatch" in _validate_chrome(chrome)
        await fe.stop()

    asyncio.run(main())
    assert not paged_app.kv_mgr.tables


# ---------------------------------------------------------------------------
# compiled-graph observatory (CPU static analysis)
# ---------------------------------------------------------------------------

def test_graph_observatory_cpu(paged_app):
    from neuronx_distributed_inference_tpu.telemetry import observatory
    reg = telemetry.enable()
    report = observatory.analyze_app(paged_app)
    assert report["schema"] == "nxdi-graph-report-v1"
    kinds = {(g["kind"], g["bucket"]) for g in report["graphs"]}
    assert ("paged", "w16xb4") in kinds and ("paged", "w1xb4") in kinds
    for g in report["graphs"]:
        assert g["flops"] > 0 and g["bytes_accessed"] > 0
        assert g["compile_seconds"] >= 0.0
        assert g["memory"]["peak_bytes"] > 0
        assert g["arithmetic_intensity"] > 0
        assert g["roofline"]["bound"] in ("memory", "compute")
        # single-device collective pin: the unsharded graphs census clean
        # (a shard_map/psum leak would have raised inside analyze_app)
        assert g["collectives"] == {} and g["collective_count"] == 0
        assert g["roofline"]["t_comm_ms"] == 0.0
    json.dumps(report)                              # artifact-ready
    # gauges landed (the bench heartbeat's cold-start signal)
    assert reg.get(tmetrics.COMPILE_SECONDS).get(
        kind="paged", bucket="w16xb4") > 0.0
    assert reg.get(tmetrics.GRAPH_FLOPS).get(
        kind="paged", bucket="w16xb4") > 0.0
    # AOT compiling through fresh wrappers left the app's jit cache alone
    assert ("graph_report", 0) not in paged_app._compiled


# ---------------------------------------------------------------------------
# tier-1 lint: metric names <-> README table
# ---------------------------------------------------------------------------

def test_metric_names_lint(tmp_path):
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "check_metric_names.py")],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "in sync" in r.stdout
    # drift in EITHER direction fails: a registered-but-undocumented name…
    readme = (REPO / "README.md").read_text()
    doctored = tmp_path / "README.md"
    doctored.write_text(readme.replace(
        "| `nxdi_queue_depth` |", "| `nxdi_queue_depht` |"))
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "check_metric_names.py"),
         "--readme", str(doctored)],
        capture_output=True, text=True)
    assert r.returncode == 1
    assert "nxdi_queue_depth" in r.stderr           # missing from table
    assert "nxdi_queue_depht" in r.stderr           # typo'd row flagged
