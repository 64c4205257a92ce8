"""The ISSUE-14 observability plane: request-scoped tracing across the
fleet, served metrics exposition, and the per-tenant SLO plane — on the
tiny synthetic paged model shared with test_serving_engine (CPU, <20s
warm).

Pins:
  * flight-recorder drop accounting is EXACT: the counter equals
    ``rec.dropped`` after any export, under concurrent exports, and a
    flush while the registry is disabled defers (never loses) the count;
  * one trace id follows a request through submit → queue → admission →
    emission, through a preemption requeue, through a ROUTER FAILOVER
    (``trace.requeue`` recorded, same id on the survivor), and through a
    disaggregated prefill→decode handoff over the JSON wire (the
    acceptance stitch: identical trace id on both replicas, handoff
    events present);
  * ``Preempted.to_json``/``from_json`` round-trips the trace context
    (both the ``trace_id`` event pointer and ``meta["trace"]``);
  * ``GET /v1/metrics`` serves valid Prometheus text; with per-replica
    registries the fleet aggregation carries ``replica``-labeled
    ``nxdi_request_ttft_seconds`` series from BOTH replicas;
  * the SLO plane: rolling-window percentiles are bounded-memory and
    window-scoped, burn rate = violation/(1-objective), the hint obeys
    the both-windows rule, and the engine wires it read-only into
    ``debug_state()["slo"]``;
  * the extended metric-names lint: a helper registering an un-prefixed
    name or empty help is RED (rename-red verified), live tree green.
"""

import asyncio
import importlib.util
import json
import threading
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
from neuronx_distributed_inference_tpu.resilience import (FAULTS, Preempted)
from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
from neuronx_distributed_inference_tpu.serving.engine import (ServingEngine,
                                                              ServingFrontend)
from neuronx_distributed_inference_tpu.serving.fleet import (
    DEAD, EngineRouter, FleetMetricsAggregator, HostKVSpillTier,
    admit_handoff, capture_handoff, handoff_from_json, handoff_to_json)
from neuronx_distributed_inference_tpu.telemetry import metrics as tmetrics
from neuronx_distributed_inference_tpu.telemetry import request_trace
from neuronx_distributed_inference_tpu.telemetry import trace as trace_mod
from neuronx_distributed_inference_tpu.telemetry.slo import (RollingWindow,
                                                             SLOPolicy,
                                                             SLOTracker)

REPO = Path(__file__).resolve().parent.parent

HF = dict(model_type="llama", hidden_size=64, intermediate_size=128,
          num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, vocab_size=512, rms_norm_eps=1e-5, rope_theta=10000.0,
          hidden_act="silu", tie_word_embeddings=False,
          torch_dtype="float32")


def _make_paged_app():
    """Same shapes + seed as test_serving_engine/test_fleet so every
    graph is warm in the persistent compile cache and all replicas share
    one set of weights."""
    tcfg = TpuConfig(batch_size=4, seq_len=64, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     is_block_kv_layout=True, pa_block_size=8,
                     is_prefix_caching=True)
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                                   LlamaFamily)
    app.init_random_weights(7).init_cache()
    return app


@pytest.fixture(scope="module")
def apps():
    return _make_paged_app(), _make_paged_app()


@pytest.fixture(autouse=True)
def _observability_disabled_after():
    yield
    telemetry.disable()
    telemetry.disable_recorder()


def _prompts(seed, n, length=9):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 500, size=length).tolist() for _ in range(n)]


def _load_script(name):
    key = f"nxdi_script_{name}"
    import sys
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(
        key, REPO / "scripts" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# satellite: flight-recorder drop accounting (no device work)
# ---------------------------------------------------------------------------

def test_drop_accounting_deferred_while_registry_disabled():
    """A flush while the registry is disabled must DEFER the count, not
    zero it: once a live registry is back, the counter catches up to
    rec.dropped exactly (the old read-and-zero flush discarded drops
    flushed mid-tail() in that window)."""
    rec = trace_mod.FlightRecorder(capacity=4)
    for i in range(10):
        rec.instant("stream.deliver", tokens=i)
    assert rec.dropped == 6
    assert len(rec.tail(2)) == 2               # flush with registry OFF
    reg = telemetry.enable()
    rec.instant("stream.deliver", tokens=10)   # one more eviction
    rec.events()                               # flush with registry ON
    assert rec.dropped == 7
    assert reg.get(tmetrics.TRACE_EVENTS_DROPPED_TOTAL).get(
        ring="trace") == 7


def test_drop_accounting_exact_under_concurrent_exports():
    """Concurrent tail()/events() exports while pushes keep wrapping the
    ring: every drop is counted exactly once — the counter equals
    rec.dropped at quiescence (neither double-counted nor lost)."""
    reg = telemetry.enable()
    rec = trace_mod.FlightRecorder(capacity=8)
    stop = threading.Event()

    def pusher():
        while not stop.is_set():
            rec.instant("stream.deliver")

    def exporter():
        while not stop.is_set():
            rec.tail(4)
            rec.events()

    threads = ([threading.Thread(target=pusher) for _ in range(2)]
               + [threading.Thread(target=exporter) for _ in range(2)])
    for t in threads:
        t.start()
    time.sleep(0.25)
    stop.set()
    for t in threads:
        t.join()
    rec.tail(1)                                # final flush
    assert rec.dropped > 0
    assert reg.get(tmetrics.TRACE_EVENTS_DROPPED_TOTAL).get(
        ring="trace") == rec.dropped


# ---------------------------------------------------------------------------
# SLO plane units (no device work)
# ---------------------------------------------------------------------------

def test_rolling_window_percentiles_windows_and_bounds():
    win = RollingWindow(horizon_s=100.0, max_samples=8)
    for i in range(10):                        # 0..9 at t=i
        win.observe(float(i), now=float(i))
    assert len(win) == 8                       # max_samples bound: 2..9
    assert win.values(now=9.0) == [2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0, 9.0]
    assert win.percentile(0.5, now=9.0) == 6.0
    assert win.percentile(0.99, now=9.0) == 9.0
    # window scoping: only the last 3 seconds
    assert win.values(window_s=2.5, now=9.0) == [7.0, 8.0, 9.0]
    assert win.violation_fraction(7.5, window_s=2.5, now=9.0) == \
        pytest.approx(2 / 3)
    # horizon eviction on write
    win.observe(99.0, now=200.0)
    assert win.values(now=200.0) == [99.0]
    assert win.percentile(0.5, now=200.0) == 99.0
    assert RollingWindow().percentile(0.5) == 0.0      # empty
    with pytest.raises(ValueError):
        RollingWindow(horizon_s=0)


def test_slo_burn_math_and_both_windows_hint_rule():
    pol = SLOPolicy(targets={"ttft": 1.0, "queue_wait": 0.5},
                    objective=0.9, short_window_s=10.0,
                    long_window_s=100.0, burn_threshold=2.0)
    t = SLOTracker(pol)
    now = 1000.0
    # ttft: old violations only (outside the short window): 4 of 5 over
    # target in the long window -> long burns 8.0, short is clean -> NO
    # hint (the both-windows rule)
    for i, v in enumerate([2.0, 2.0, 2.0, 2.0, 0.5]):
        t.observe("acme", "ttft", v, now=now - 50.0 + i)
    rep = t.report(now=now)["tenants"]["acme"]["ttft"]
    assert rep["burn_rate"]["long"] == pytest.approx(0.8 / 0.1)
    assert rep["burn_rate"]["short"] == 0.0
    assert rep["attainment"]["long"] == pytest.approx(0.2)
    hint = t.degradation_hint(now=now)
    assert hint["degrade"] is False
    # queue_wait: burning in BOTH windows -> tighten_admission fires
    for i in range(4):
        t.observe("acme", "queue_wait", 2.0, now=now - 2.0 + 0.1 * i)
    hint = t.degradation_hint(now=now)
    assert hint["degrade"] is True
    entry = hint["tenants"]["acme"]
    assert entry["tighten_admission"] is True
    assert entry["shed_speculation"] is False
    assert entry["signals"]["queue_wait"] >= 2.0
    # untargeted signals track percentiles but never burn
    t.observe("acme", "tpot", 5.0, now=now)
    rep = t.report(now=now)["tenants"]["acme"]["tpot"]
    assert "burn_rate" not in rep and rep["p50_s"] == 5.0
    with pytest.raises(ValueError):
        SLOPolicy(targets={"nope": 1.0})
    with pytest.raises(ValueError):
        t.observe("acme", "nope", 1.0)


def test_slo_gauges_export():
    reg = telemetry.enable()
    t = SLOTracker(SLOPolicy(targets={"ttft": 1.0}, objective=0.9))
    now = 50.0
    for v in (2.0, 0.5):
        t.observe("a", "ttft", v, now=now)
    t.export(reg, now=now)
    assert reg.get(tmetrics.SLO_BURN_RATE).get(
        tenant="a", signal="ttft", window="short") == pytest.approx(5.0)
    assert reg.get(tmetrics.SLO_ATTAINMENT).get(
        tenant="a", signal="ttft", window="long") == pytest.approx(0.5)
    text = reg.render_prometheus()
    assert "nxdi_slo_burn_rate" in text and "nxdi_slo_attainment" in text


# ---------------------------------------------------------------------------
# satellite: trace context round-trips (no device work)
# ---------------------------------------------------------------------------

def test_preempted_round_trips_trace_context():
    now = time.perf_counter()
    rec = Preempted(seq_id=3, tokens=(1, 2, 3, 4), prompt_len=3,
                    n_generated=1, reason="scheduler", deadline=now + 5.0,
                    meta={"tenant": "t", "request_id": "r7",
                          "trace": "cafe0123deadbeef"},
                    trace_id="e42")
    back = Preempted.from_json(json.loads(json.dumps(rec.to_json(now=now))),
                               now=now)
    assert back.trace_id == "e42"                      # event pointer
    assert request_trace.trace_of(back.meta) == "cafe0123deadbeef"
    assert back.admission_kwargs()["meta"] == [rec.meta]
    # non-mapping metas never carry a trace
    assert request_trace.trace_of(None) is None
    assert request_trace.trace_of("opaque") is None


def test_trace_event_filtering_and_per_request_lanes():
    rec = trace_mod.FlightRecorder()
    rec.instant("trace.begin", cat="request", trace="aaa", request_id="r0")
    rec.instant("trace.begin", cat="request", trace="bbb", request_id="r1")
    rec.instant("dispatch.ragged", cat="adapter", seq_ids=[0, 1],
                traces=["aaa", "bbb"])
    rec.instant("trace.emit", cat="request", trace="aaa", reason="length")
    evs = request_trace.trace_events(rec.events(), "aaa")
    assert [e["name"] for e in evs] == ["trace.begin", "dispatch.ragged",
                                        "trace.emit"]
    assert request_trace.trace_ids_in(rec.events()) == ["aaa", "bbb"]
    chrome = request_trace.chrome_by_trace(rec)
    lanes = {e["args"]["name"]: e["tid"] for e in chrome["traceEvents"]
             if e["ph"] == "M"}
    assert lanes == {"trace:aaa": 1, "trace:bbb": 2}
    # the shared ragged dispatch is repeated on BOTH request lanes
    ragged = [e for e in chrome["traceEvents"]
              if e["name"] == "dispatch.ragged"]
    assert sorted(e["tid"] for e in ragged) == [1, 2]
    assert chrome["otherData"]["traces"] == ["aaa", "bbb"]


# ---------------------------------------------------------------------------
# engine + fleet trace lifecycle (device; tiny warm graphs)
# ---------------------------------------------------------------------------

def test_engine_trace_lifecycle_and_debug_endpoint(apps):
    """submit → admit → emit under ONE trace id; /v1/debug/trace/<id>
    serves exactly that request's events; the SLO section rides
    debug_state read-only."""
    app, _ = apps
    rec = telemetry.enable_recorder()
    tracker = SLOTracker(SLOPolicy(targets={"ttft": 30.0, "tpot": 30.0,
                                            "queue_wait": 30.0}))
    eng = ServingEngine(PagedEngineAdapter(app), starvation_bound_s=1e9,
                        slo=tracker)
    s0, s1 = [eng.submit(p, 4, tenant="t") for p in _prompts(31, 2)]
    eng.run_until_drained()
    assert s0.finish_reason == "length" and s1.finish_reason == "length"
    tid0, tid1 = eng.trace_id_of(s0.request_id), eng.trace_id_of(
        s1.request_id)
    assert tid0 and tid1 and tid0 != tid1
    evs = request_trace.trace_events(rec.events(), tid0)
    names = [e["name"] for e in evs]
    assert names[0] == "trace.begin" and names[-1] == "trace.emit"
    assert "trace.admit" in names
    begin = evs[0]["args"]
    assert begin["request_id"] == s0.request_id
    assert begin["prompt_len"] == 9 and begin["continued"] is False
    emit = evs[-1]["args"]
    assert emit["reason"] == "length" and emit["n_tokens"] == 4
    # nothing from the other request leaked into this trace
    assert all(e["args"].get("request_id", s0.request_id) == s0.request_id
               for e in evs)
    # SLO plane rode along read-only
    slo_state = eng.debug_state()["slo"]
    assert slo_state["tenants"]["t"]["ttft"]["n"] == 2
    assert slo_state["tenants"]["t"]["tpot"]["n"] == 2
    assert slo_state["hint"]["degrade"] is False

    async def main():
        fe = ServingFrontend(eng)
        host, port = await fe.start()

        async def get(path):
            r, w = await asyncio.open_connection(host, port)
            w.write(f"GET {path} HTTP/1.1\r\n\r\n".encode())
            await w.drain()
            data = await asyncio.wait_for(r.read(), timeout=90)
            w.close()
            return data.decode()

        resp = await get(f"/v1/debug/trace/{s0.request_id}")
        chrome = json.loads(resp.split("\r\n\r\n", 1)[1])
        assert chrome["otherData"]["trace_id"] == tid0
        served = [e["name"] for e in chrome["traceEvents"]
                  if e["ph"] != "M"]
        assert served == names                   # same events, chrome form
        # raw trace id works too; unknown ids 404
        resp = await get(f"/v1/debug/trace/{tid0}")
        assert resp.startswith("HTTP/1.1 200")
        resp = await get("/v1/debug/trace/nope")
        assert resp.startswith("HTTP/1.1 404")
        await fe.stop()

    asyncio.run(main())
    assert not app.kv_mgr.tables


def test_router_failover_requeue_continues_trace(apps):
    """The satellite pin: a replica dying mid-decode requeues its
    request onto the survivor with the SAME trace id — trace.requeue
    recorded with the replica pair, the survivor's trace.begin marked
    continued — and the stitched stream still finishes."""
    app_a, app_b = apps
    rec = telemetry.enable_recorder()
    eng_a = ServingEngine(PagedEngineAdapter(app_a),
                          starvation_bound_s=1e9)
    eng_b = ServingEngine(PagedEngineAdapter(app_b), starvation_bound_s=1e9)
    router = EngineRouter({"A": eng_a, "B": eng_b})
    s = router.submit(_prompts(32, 1)[0], 6)
    tid = router.trace_id_of(s.request_id)
    assert tid is not None
    assert eng_a.trace_id_of(s.request_id) == tid  # replica CONTINUED it
    passes = 0
    while s.n_tokens < 2:
        router.run_pass()
        passes += 1
        assert passes < 100
    with FAULTS.inject("pipeline_flush") as fp:
        while fp.trips == 0:
            router.run_pass()
    assert router.replicas["A"].state == DEAD
    router.run_until_drained()
    assert s.finish_reason == "length" and len(s.tokens) == 6
    assert eng_b.trace_id_of(s.request_id) == tid  # same trace on B
    evs = request_trace.trace_events(rec.events(), tid)
    names = [e["name"] for e in evs]
    req = next(e for e in evs if e["name"] == "trace.requeue")
    assert req["args"]["reason"] == "replica_failure"
    assert req["args"]["from_replica"] == "A"
    assert req["args"]["to_replica"] == "B"
    begins = [e for e in evs if e["name"] == "trace.begin"]
    assert [b["args"]["continued"] for b in begins] == [True, True]
    assert names[-1] == "trace.emit"
    # fictional-failure leftovers on the dead replica's app: reclaim
    for sid in list(app_a.kv_mgr.tables):
        app_a.kv_mgr.end_sequence(sid)
    assert not app_b.kv_mgr.tables


def test_handoff_stitches_one_trace_across_replicas(apps):
    """The acceptance pin: one request served through a 2-replica
    disaggregated prefill→decode handoff (over the JSON wire) yields a
    SINGLE stitched trace — identical trace id on both replicas,
    handoff.send and handoff.recv both present and both carrying it."""
    app_a, app_b = apps

    def adapter_golden(app, sid, prompt, n):
        ad = PagedEngineAdapter(app)
        first = ad.add_requests([sid], [prompt])
        toks = [first[sid]]
        for _ in range(n - 1):
            toks.append(ad.step([sid])[sid])
        ad.release([sid])
        return toks

    prompt = _prompts(33, 1, length=17)[0]      # 2 full blocks + tail
    golden = adapter_golden(app_a, 90, prompt, 5)   # uninterrupted run
    rec = telemetry.enable_recorder()
    prefill = PagedEngineAdapter(app_a)
    decode = PagedEngineAdapter(app_b, kv_spill_tier=HostKVSpillTier(32))
    tid = request_trace.new_trace_id()
    first = prefill.add_requests(
        [5], [prompt], meta=[{"request_id": "h0", "tenant": "t",
                              "trace": tid}])
    assert first[5] == golden[0]
    record = capture_handoff(prefill, 5)
    assert request_trace.trace_of(record["preempted"]["meta"]) == tid
    wire = json.dumps(handoff_to_json(record))      # cross-process wire
    received = handoff_from_json(json.loads(wire))
    first_b = admit_handoff(decode, received, 0)
    toks = [first_b[0]]
    for _ in range(3):
        toks.append(decode.step([0])[0])
    decode.release([0])
    assert toks == golden[1:5]              # decode continued bit-identical
    evs = request_trace.trace_events(rec.events(), tid)
    names = [e["name"] for e in evs]
    assert "handoff.send" in names and "handoff.recv" in names
    send = next(e for e in evs if e["name"] == "handoff.send")
    recv = next(e for e in evs if e["name"] == "handoff.recv")
    assert send["args"]["trace"] == recv["args"]["trace"] == tid
    assert send["args"]["engine"] == recv["args"]["engine"] == "paged"
    # detach the spill hook admit_handoff installed on app_b
    if hasattr(app_b.kv_mgr.allocator, "on_evict"):
        app_b.kv_mgr.allocator.on_evict = None
    assert not app_a.kv_mgr.tables and not app_b.kv_mgr.tables


def test_ragged_dispatch_rows_carry_traces(apps):
    """Every ragged-step row a request occupies lands on its trace: the
    dispatch.ragged events' per-row traces list lines up with seq_ids,
    and filtering one request's trace includes its ragged dispatches."""
    app, _ = apps
    rec = telemetry.enable_recorder()
    eng = ServingEngine(PagedEngineAdapter(app, ragged=True),
                        starvation_bound_s=1e9)
    s0, s1 = [eng.submit(p, 3, tenant="t") for p in _prompts(34, 2)]
    eng.run_until_drained()
    assert s0.finish_reason == "length" and s1.finish_reason == "length"
    tid = eng.trace_id_of(s0.request_id)
    dispatches = [e for e in rec.events() if e["name"] == "dispatch.ragged"]
    assert dispatches
    for ev in dispatches:
        assert len(ev["args"]["traces"]) == len(ev["args"]["seq_ids"])
    mine = [e for e in request_trace.trace_events(rec.events(), tid)
            if e["name"] == "dispatch.ragged"]
    assert mine, "the request's trace lost its ragged dispatches"
    assert not app.kv_mgr.tables


def test_slo_single_pass_delivery_and_requeue_wait_semantics(apps):
    """Review-fix pins: (a) a request whose tokens ALL land in one
    delivery pass contributes NO TPOT sample (never a fake-perfect
    0.0); (b) a re-admission's SLO queue wait measures from the requeue
    time, not the original submit."""
    app, _ = apps
    tracker = SLOTracker(SLOPolicy(targets={"tpot": 1e-9},
                                   objective=0.9))
    eng = ServingEngine(PagedEngineAdapter(app), starvation_bound_s=1e9,
                        decode_steps_per_pass=8, slo=tracker)
    s = eng.submit(_prompts(36, 1)[0], 4, tenant="t")
    eng.run_until_drained()
    assert s.finish_reason == "length"
    rep = tracker.report()["tenants"]["t"]
    # non-deferred admission delivers token 1, the fused horizon the
    # other 3 — two delivery passes would give an interval, but with
    # the whole budget in step_many the interval may be one pass; the
    # invariant pinned here: ttft/queue_wait always observed, and tpot
    # is either absent or from a REAL (> 0) interval
    assert rep["ttft"]["n"] == 1 and rep["queue_wait"]["n"] == 1
    if rep.get("tpot", {}).get("n"):
        assert rep["tpot"]["p99_s"] > 0.0
    # (b) requeue wait: white-box — a victim that ran for "ages" then
    # requeued a moment ago must observe a SMALL queue wait
    s2 = eng.submit(_prompts(37, 1)[0], 2, tenant="t")
    req = next(r for r in eng._queued() if r.request_id == s2.request_id)
    req.timeline.submit = time.perf_counter() - 100.0   # "ages" ago
    req.last_enqueue_t = time.perf_counter() - 0.01   # requeued just now
    eng.run_until_drained()
    waits = tracker._windows[("t", "queue_wait")].values()
    assert max(waits) < 50.0, waits    # the 100s run time never counted
    assert not app.kv_mgr.tables


# ---------------------------------------------------------------------------
# served exposition + fleet aggregation
# ---------------------------------------------------------------------------

def test_fleet_aggregator_merges_replica_registries():
    ra, rb = telemetry.MetricsRegistry(), telemetry.MetricsRegistry()
    tmetrics.ttft_histogram(ra).observe(0.01, engine="paged", tenant="t")
    tmetrics.ttft_histogram(rb).observe(0.02, engine="paged", tenant="t")
    tmetrics.queue_depth_gauge(ra).set(3, tenant="t")
    agg = FleetMetricsAggregator({"r0": ra, "r1": rb.snapshot()})
    text = agg.render_prometheus()
    cme = _load_script("check_metrics_exposition")
    assert cme.validate_prometheus_text(text) == []
    assert 'nxdi_request_ttft_seconds_bucket{replica="r0"' in text
    assert 'nxdi_request_ttft_seconds_bucket{replica="r1"' in text
    assert 'nxdi_queue_depth{replica="r0",tenant="t"} 3' in text
    # one TYPE header per family, not per replica
    assert text.count("# TYPE nxdi_request_ttft_seconds ") == 1
    snap = agg.snapshot()
    assert snap["schema"] == "nxdi-fleet-metrics-v1"
    assert set(snap["replicas"]) == {"r0", "r1"}
    # drift pin: a one-source aggregation IS the registry's own
    # exposition with the replica label injected — both surfaces ride
    # registry.render_series, so they can never format-diverge
    solo = FleetMetricsAggregator({"x": ra}).render_prometheus()
    stripped = solo.replace('replica="x",', "").replace(
        '{replica="x"}', "")
    assert stripped == ra.render_prometheus()
    with pytest.raises(Exception):
        FleetMetricsAggregator({})
    with pytest.raises(Exception):
        FleetMetricsAggregator({"r0": 42}).render_prometheus()


def test_v1_metrics_serves_fleet_aggregation(apps):
    """The acceptance pin: GET /v1/metrics on a fleet frontend returns
    valid Prometheus text with fleet-aggregated nxdi_request_ttft_seconds
    under replica labels — each replica accumulated its OWN series via
    the router's registry scoping."""
    app_a, app_b = apps
    telemetry.enable()                 # router-level series need a live
    ra, rb = telemetry.MetricsRegistry(), telemetry.MetricsRegistry()
    eng_a = ServingEngine(PagedEngineAdapter(app_a), starvation_bound_s=1e9,
                          slo=SLOTracker(SLOPolicy(targets={"ttft": 30.0})))
    eng_b = ServingEngine(PagedEngineAdapter(app_b), starvation_bound_s=1e9)
    # partial registry coverage is rejected typed (uncovered replicas
    # would silently vanish from the aggregated scrape)
    from neuronx_distributed_inference_tpu.resilience import \
        ConfigurationError
    with pytest.raises(ConfigurationError):
        EngineRouter({"r0": eng_a, "r1": eng_b},
                     metrics_registries={"r0": ra})
    router = EngineRouter({"r0": eng_a, "r1": eng_b},
                          metrics_registries={"r0": ra, "r1": rb})
    # distinct prompts: the second submit routes to the idle replica
    p0, p1 = _prompts(35, 2)
    s0 = router.submit(p0, 3)
    s1 = router.submit(p1, 3)
    assert {router._requests[s.request_id].replica
            for s in (s0, s1)} == {"r0", "r1"}
    router.run_until_drained()
    assert s0.finish_reason == "length" and s1.finish_reason == "length"
    # each replica's TTFT landed in its OWN registry
    assert tmetrics.ttft_histogram(ra).count(engine="paged", tenant="default") == 1
    assert tmetrics.ttft_histogram(rb).count(engine="paged", tenant="default") == 1

    cme = _load_script("check_metrics_exposition")
    text = cme.scrape_frontend_fleet(eng_a, router)
    assert cme.validate_prometheus_text(text) == []
    assert 'nxdi_request_ttft_seconds_bucket{replica="r0"' in text
    assert 'nxdi_request_ttft_seconds_bucket{replica="r1"' in text
    # a replica engine's SLO tracker surfaces in the FLEET scrape too
    # (export_slo targets the replica's own registry, not the global)
    assert 'nxdi_slo_attainment{replica="r0"' in text
    # ...and the ROUTER's own global-registry series are merged in, the
    # fleet counters keeping their own replica label
    assert 'nxdi_fleet_routed_total{replica="r0"' in text
    assert 'nxdi_fleet_routed_total{replica="r1"' in text
    assert not app_a.kv_mgr.tables and not app_b.kv_mgr.tables


def test_metrics_exposition_lint_in_process(apps):
    """The tier-1 exposition lint, in-process (no subprocess jax
    import): a real /v1/metrics scrape over the tiny engine validates,
    and the validator is RED on doctored text."""
    app, _ = apps
    cme = _load_script("check_metrics_exposition")
    reg = telemetry.enable()
    tracker = SLOTracker(SLOPolicy(targets={"ttft": 30.0}))
    eng = ServingEngine(PagedEngineAdapter(app), starvation_bound_s=1e9,
                        slo=tracker)
    text = cme.scrape_frontend(eng)
    assert cme.validate_prometheus_text(text) == []
    assert "nxdi_request_ttft_seconds_bucket" in text
    assert "nxdi_slo_attainment" in text       # scrape-time SLO export
    # the alias keeps serving the same body shape
    assert not app.kv_mgr.tables
    # validator redness, rule by rule
    red = cme.validate_prometheus_text
    assert red("")                                      # nothing measured
    assert any("no preceding # TYPE" in p
               for p in red("nxdi_x_total 1\n"))
    assert any("negative" in p for p in red(
        "# TYPE nxdi_x_total counter\nnxdi_x_total -1\n"))
    assert any("cumulative" in p for p in red(
        "# TYPE nxdi_h histogram\n"
        'nxdi_h_bucket{le="1"} 5\nnxdi_h_bucket{le="2"} 3\n'
        'nxdi_h_bucket{le="+Inf"} 5\nnxdi_h_sum 1\nnxdi_h_count 5\n'))
    assert any("+Inf bucket" in p and "_count" in p for p in red(
        "# TYPE nxdi_h histogram\n"
        'nxdi_h_bucket{le="1"} 3\nnxdi_h_bucket{le="+Inf"} 3\n'
        "nxdi_h_sum 1\nnxdi_h_count 4\n"))
    assert any("unparseable sample" in p for p in red(
        "# TYPE nxdi_x gauge\nnxdi_x{borked 1\n"))
    assert any("duplicate TYPE" in p for p in red(
        "# TYPE nxdi_x gauge\n# TYPE nxdi_x gauge\nnxdi_x 1\n"))


# ---------------------------------------------------------------------------
# satellite: extended metric-names lint (helper contract, rename-red)
# ---------------------------------------------------------------------------

def test_metric_names_helper_contract_red_and_green(tmp_path):
    from conftest import load_nxdi_lint
    mod = load_nxdi_lint()
    # live tree: green (the driver runs the pass against the real files)
    report = mod.run(names=["metric-names"])
    assert not report.findings
    metrics_path = (REPO / "neuronx_distributed_inference_tpu" /
                    "telemetry" / "metrics.py")
    readme_path = REPO / "README.md"
    src = metrics_path.read_text()

    def run_doctored(new_src):
        doctored = tmp_path / "metrics.py"
        doctored.write_text(new_src)
        from neuronx_distributed_inference_tpu.analysis.registry import (
            LintContext, get_pass)
        ctx = LintContext(REPO)
        return get_pass("metric-names").run(
            ctx, paths=[str(doctored), str(readme_path)])

    # a helper registering an UN-PREFIXED literal name: red
    bad = src + ('\n\ndef rogue_counter(reg):\n'
                 '    return reg.counter("rogue_total", "help text")\n')
    msgs = [f.message for f in run_doctored(bad)]
    assert any("nxdi_ prefix" in m for m in msgs)
    # a helper with EMPTY help: red
    bad = src + ('\n\ndef blank_counter(reg):\n'
                 '    return reg.counter(SLO_BURN_RATE, "")\n')
    msgs = [f.message for f in run_doctored(bad)]
    assert any("non-empty" in m and "help" in m for m in msgs)
    # a helper whose name arg resolves to nothing: red
    bad = src + ('\n\ndef ghost_counter(reg):\n'
                 '    return reg.counter(NO_SUCH_CONST, "help")\n')
    msgs = [f.message for f in run_doctored(bad)]
    assert any("not a module-level nxdi_* constant" in m for m in msgs)
    # a `reg` helper that never builds an instrument: red
    bad = src + '\n\ndef lazy_helper(reg):\n    return None\n'
    msgs = [f.message for f in run_doctored(bad)]
    assert any("never builds an instrument" in m for m in msgs)
    # rename-red: renaming a constant's VALUE desyncs the README table
    bad = src.replace('"nxdi_slo_burn_rate"', '"nxdi_slo_burn_rte"')
    msgs = [f.message for f in run_doctored(bad)]
    assert any("nxdi_slo_burn_rate" in m for m in msgs)   # missing
    assert any("nxdi_slo_burn_rte" in m for m in msgs)    # typo'd
    # the no-constants early return must KEEP helper findings (a
    # constants-free file is exactly where helpers go rogue)
    bad = 'def rogue(reg):\n    return reg.counter("oops_total", "")\n'
    msgs = [f.message for f in run_doctored(bad)]
    assert any("no nxdi_* constants" in m for m in msgs)
    assert any("nxdi_ prefix" in m for m in msgs)
    assert any("non-empty" in m for m in msgs)
