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
    "stream.deliver", "admission.headroom",
    "dispatch.decode", "dispatch.decode_loop", "dispatch.prefill_chunk",
    "dispatch.ragged", "fetch.tokens", "preempt",
    "run.prefill", "run.decode", "run.decode_loop", "run.paged",
    "run.paged_loop", "run.ragged", "run.spec_draft", "run.spec_verify",
    "compile",
    "fleet.route", "fleet.drain", "kv.spill", "kv.restore", "handoff.send",
    "handoff.recv", "fleet.all_dead", "fleet.scale_up", "fleet.scale_down",
    "trace.begin", "trace.admit", "trace.requeue", "trace.emit",
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
