"""Runtime telemetry tests: metrics registry semantics, Prometheus/JSON
export, request spans, serving-adapter + application instrumentation
(TTFT / TPOT / recompile / bucket / KV-occupancy), and the
zero-cost-when-disabled contract (outputs and jit cache keys bit-identical
with telemetry off)."""

import json
import re

import numpy as np
import pytest

from neuronx_distributed_inference_tpu import telemetry
from neuronx_distributed_inference_tpu.config import TpuConfig
from neuronx_distributed_inference_tpu.models.application import (
    CausalLMApplication, PagedCausalLMApplication)
from neuronx_distributed_inference_tpu.models.llama import (
    LlamaFamily, LlamaInferenceConfig)
from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
from neuronx_distributed_inference_tpu.telemetry import metrics as tmetrics

HF = dict(model_type="llama", hidden_size=64, intermediate_size=128,
          num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, vocab_size=512, rms_norm_eps=1e-5, rope_theta=10000.0,
          hidden_act="silu", tie_word_embeddings=False,
          torch_dtype="float32")


@pytest.fixture
def live_registry():
    """A live global registry for the test, restored to disabled after."""
    reg = telemetry.MetricsRegistry()
    telemetry.set_registry(reg)
    yield reg
    telemetry.disable()


@pytest.fixture(autouse=True)
def _always_disabled_after():
    yield
    telemetry.disable()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_gauge_label_series():
    reg = telemetry.MetricsRegistry()
    c = reg.counter("t_requests_total", "help text", labels=("engine",))
    c.inc(engine="cb")
    c.inc(2, engine="paged")
    assert c.get(engine="cb") == 1.0
    assert c.get(engine="paged") == 2.0
    assert c.get(engine="other") == 0.0
    with pytest.raises(ValueError):
        c.inc(-1, engine="cb")                 # counters only go up
    with pytest.raises(ValueError):
        c.inc(1)                               # missing label
    g = reg.gauge("t_live", labels=("engine",))
    g.set(3, engine="cb")
    g.inc(2, engine="cb")
    g.dec(1, engine="cb")
    assert g.get(engine="cb") == 4.0


def test_registry_rejects_schema_conflicts():
    reg = telemetry.MetricsRegistry()
    reg.counter("t_x_total", labels=("a",))
    with pytest.raises(ValueError):
        reg.gauge("t_x_total")                 # type conflict
    with pytest.raises(ValueError):
        reg.counter("t_x_total", labels=("b",))  # label-set conflict
    with pytest.raises(ValueError):
        reg.counter("9starts_with_digit")
    with pytest.raises(ValueError):
        reg.counter("has space")


def test_histogram_buckets_and_percentile():
    reg = telemetry.MetricsRegistry()
    h = reg.histogram("t_lat_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count() == 5
    assert h.sum() == pytest.approx(5.605)
    snap = h._snapshot()[0]
    # cumulative per-bucket counts: <=0.01 -> 1, <=0.1 -> 3, <=1.0 -> 4
    assert snap["buckets"] == [[0.01, 1], [0.1, 3], [1.0, 4]]
    assert h.percentile(0.5) == 0.1
    assert h.percentile(0.0) == 0.01
    with pytest.raises(ValueError):
        reg.histogram("t_bad", buckets=(1.0, 0.5))   # not increasing


def test_default_latency_buckets_are_log_spaced_and_fixed():
    bs = telemetry.DEFAULT_LATENCY_BUCKETS
    assert list(bs) == sorted(bs)
    assert bs[0] <= 1e-4 and bs[-1] >= 60.0


# ---------------------------------------------------------------------------
# export surfaces
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{((?:[a-zA-Z_][a-zA-Z0-9_]*='
    r'"(?:[^"\\]|\\.)*",?)*)\})? (\S+)$')


def _parse_prometheus(text):
    """Minimal validating parser for Prometheus text exposition 0.0.4.
    Raises AssertionError on any malformed line; returns {name: type} and
    [(sample_name, labels, float_value)]."""
    types, samples = {}, []
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            assert re.match(r"^# HELP [a-zA-Z_:][a-zA-Z0-9_:]* ", line), line
            continue
        if line.startswith("# TYPE "):
            m = re.match(r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) "
                         r"(counter|gauge|histogram|summary|untyped)$", line)
            assert m, line
            types[m.group(1)] = m.group(2)
            continue
        assert not line.startswith("#"), f"unknown comment: {line!r}"
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        name, labelstr, value = m.groups()
        labels = dict(re.findall(
            r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"', labelstr or ""))
        v = float("inf") if value == "+Inf" else float(value)
        samples.append((name, labels, v))
    return types, samples


def test_render_prometheus_golden():
    reg = telemetry.MetricsRegistry()
    reg.counter("t_req_total", "requests served", labels=("engine",)).inc(
        3, engine="cb")
    reg.gauge("t_occupancy").set(0.5)
    h = reg.histogram("t_lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.25)
    h.observe(0.5)
    text = reg.render_prometheus()
    assert text == (
        '# HELP t_lat_seconds latency\n'
        '# TYPE t_lat_seconds histogram\n'
        't_lat_seconds_bucket{le="0.1"} 0\n'
        't_lat_seconds_bucket{le="1"} 2\n'
        't_lat_seconds_bucket{le="+Inf"} 2\n'
        't_lat_seconds_sum 0.75\n'
        't_lat_seconds_count 2\n'
        '# TYPE t_occupancy gauge\n'
        't_occupancy 0.5\n'
        '# HELP t_req_total requests served\n'
        '# TYPE t_req_total counter\n'
        't_req_total{engine="cb"} 3\n'
    )
    types, samples = _parse_prometheus(text)
    assert types == {"t_lat_seconds": "histogram", "t_req_total": "counter",
                     "t_occupancy": "gauge"}
    assert ("t_req_total", {"engine": "cb"}, 3.0) in samples


def test_label_escaping_in_prometheus_output():
    reg = telemetry.MetricsRegistry()
    reg.counter("t_esc_total", labels=("p",)).inc(p='a"b\\c\nd')
    types, samples = _parse_prometheus(reg.render_prometheus())
    assert samples[0][1]["p"] == 'a\\"b\\\\c\\nd'   # escaped on the wire


def test_snapshot_is_json_able():
    reg = telemetry.MetricsRegistry()
    reg.counter("t_a_total", labels=("k",)).inc(k="x")
    reg.histogram("t_h_seconds", buckets=(1.0,)).observe(0.5)
    with reg.start_span("request", seq_id=3) as sp:
        sp.event("first_token", ttft_s=0.1)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["metrics"]["t_a_total"]["type"] == "counter"
    assert snap["metrics"]["t_a_total"]["series"] == [
        {"labels": {"k": "x"}, "value": 1.0}]
    assert snap["metrics"]["t_h_seconds"]["series"][0]["count"] == 1
    assert snap["spans"][0]["labels"] == {"seq_id": "3"}
    assert snap["spans"][0]["events"][0]["name"] == "first_token"
    assert snap["spans"][0]["duration_s"] >= 0.0


def test_span_ring_is_bounded():
    reg = telemetry.MetricsRegistry(max_spans=4)
    for i in range(10):
        reg.start_span("request", i=i).end()
    assert len(reg.spans) == 4
    assert [s["labels"]["i"] for s in reg.spans] == ["6", "7", "8", "9"]


def test_span_elapsed_since():
    sp = telemetry.Span("request")
    assert sp.elapsed_since("first_token") is None
    sp.event("first_token")
    assert sp.elapsed_since("first_token") >= 0.0
    sp.end()
    d1 = sp.end()                                   # idempotent
    assert d1 == sp.to_dict()["duration_s"]


# ---------------------------------------------------------------------------
# disabled (default) path
# ---------------------------------------------------------------------------

def test_disabled_registry_is_inert():
    reg = telemetry.get_registry()
    assert isinstance(reg, telemetry.NullRegistry)
    assert not reg.enabled
    c = reg.counter("t_whatever_total", labels=("a",))
    c.inc(5, a="x")                                 # no-op, no validation cost
    assert c.get(a="x") == 0.0
    assert reg.render_prometheus() == ""
    assert reg.snapshot() == {"metrics": {}, "spans": []}
    assert reg.stats_line() == ""
    sp = reg.start_span("request")
    assert sp is telemetry.NULL_SPAN
    sp.event("x").end()


def test_enable_disable_roundtrip():
    reg = telemetry.enable()
    assert telemetry.get_registry() is reg
    assert telemetry.enable() is reg                # idempotent
    telemetry.disable()
    assert telemetry.get_registry() is telemetry.NULL_REGISTRY


# ---------------------------------------------------------------------------
# serving-adapter + application instrumentation (CPU, tiny llama)
# ---------------------------------------------------------------------------

def _paged_app():
    tcfg = TpuConfig(batch_size=4, seq_len=64, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     is_block_kv_layout=True, pa_block_size=8,
                     is_prefix_caching=True)
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                                   LlamaFamily)
    app.init_random_weights(7).init_cache()
    return app


def _drive(eng):
    rng = np.random.default_rng(0)
    p1 = rng.integers(1, 500, size=9).tolist()
    p2 = rng.integers(1, 500, size=12).tolist()
    eng.add_requests([0], [p1])
    for _ in range(3):
        eng.step()
    eng.add_requests([1], [p2])
    for _ in range(3):
        eng.step()
    eng.release([0, 1])


def test_adapter_records_serving_metrics(live_registry):
    reg = live_registry
    _drive(PagedEngineAdapter(_paged_app()))

    ttft = reg.get(tmetrics.REQUEST_TTFT_SECONDS)
    assert ttft.count(engine="paged", tenant="") == 2
    assert ttft.sum(engine="paged", tenant="") > 0.0
    step = reg.get(tmetrics.DECODE_STEP_SECONDS)
    assert step.count(engine="paged") == 6
    assert step.sum(engine="paged") > 0.0
    tpot = reg.get(tmetrics.REQUEST_TPOT_SECONDS)
    assert tpot.count(engine="paged", tenant="") == 2
    req = reg.get(tmetrics.REQUESTS_TOTAL)
    assert req.get(engine="paged", event="added") == 2
    assert req.get(engine="paged", event="released") == 2
    # live rows by phase: 3 steps of one row + 3 of two, and one chunk row
    # a prompt; a lone row runs the one-row rung of both ladders (no pad)
    live = reg.get(tmetrics.LIVE_ROWS_TOTAL)
    assert live.get(engine="paged", phase="decode") == 9
    assert live.get(engine="paged", phase="prefill") == 2
    assert reg.get(tmetrics.PREFILL_CHUNKS_TOTAL).get(engine="paged") == 2
    assert reg.get(tmetrics.LIVE_BATCH_SIZE).get(engine="paged") == 2
    # bucket selections were tagged
    bucket = reg.get(tmetrics.BUCKET_SELECTED_TOTAL)
    assert bucket.get(kind="ctx", bucket="16") == 2
    assert bucket.get(kind="prefill_rows", bucket="1") == 2
    assert sum(s["value"] for s in bucket._snapshot()
               if s["labels"]["kind"] == "batch") > 0
    # recompiles vs cache hits: the chunk and the step compile once a
    # shape, repeats hit
    compiles = reg.get(tmetrics.JIT_COMPILES_TOTAL)
    hits = reg.get(tmetrics.JIT_CACHE_HITS_TOTAL)
    assert sum(s["value"] for s in compiles._snapshot()
               if s["labels"]["kind"] == "paged") >= 2
    assert hits.get(kind="paged") >= 4
    # request spans landed in the ring with first_token + released events
    spans = [s for s in reg.spans if s["name"] == "request"]
    assert len(spans) == 2
    ev_names = [e["name"] for e in spans[0]["events"]]
    assert ev_names[0] == "first_token" and "released" in ev_names
    # run_seconds: the HOST side of every _run_* call at the app boundary;
    # there is no device part — telemetry never syncs the device (C7)
    run = reg.get(tmetrics.RUN_SECONDS)
    assert run.count(kind="paged", part="host") == 8
    assert {s["labels"]["part"] for s in run._snapshot()} == {"host"}
    assert reg.get(tmetrics.GENERATED_TOKENS_TOTAL).get(engine="paged") > 0
    # the whole thing renders as valid Prometheus text
    types, samples = _parse_prometheus(reg.render_prometheus())
    assert types[tmetrics.REQUEST_TTFT_SECONDS] == "histogram"
    assert types[tmetrics.JIT_COMPILES_TOTAL] == "counter"


def test_paged_adapter_records_kv_occupancy(live_registry, monkeypatch):
    reg = live_registry
    app = _paged_app()
    eng = PagedEngineAdapter(app)
    # enabling the registry adds NO device sync: any block_until_ready on
    # the serving path from here on is the instrument's, and fails the test
    import jax
    syncs = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: syncs.append(x) or x)
    rng = np.random.default_rng(0)
    p1 = rng.integers(1, 500, size=9).tolist()
    eng.add_requests([0], [p1])
    in_use_mid = reg.get(tmetrics.KV_BLOCKS_IN_USE).get()
    total = reg.get(tmetrics.KV_BLOCKS_TOTAL).get()
    assert total == app.tpu_config.pa_num_blocks
    assert 0 < in_use_mid <= total
    for _ in range(3):
        eng.step()
    eng.release([0])
    # prefix caching keeps full hashed blocks resident (ref_count 0) but
    # in-use must drop back to untracked-by-sequences
    assert reg.get(tmetrics.KV_BLOCKS_IN_USE).get() == 0
    # serving + app histograms flowed through the paged engine too
    assert reg.get(tmetrics.REQUEST_TTFT_SECONDS).count(engine="paged", tenant="") == 1
    assert reg.get(tmetrics.DECODE_STEP_SECONDS).count(engine="paged") == 3
    run = reg.get(tmetrics.RUN_SECONDS)
    assert run.count(kind="paged", part="host") >= 4
    assert run.sum(kind="paged", part="host") > 0.0
    assert run.count(kind="paged", part="device") == 0
    assert syncs == []
    # paged graph: one compile for the prefill width, repeat shapes hit
    compiles = reg.get(tmetrics.JIT_COMPILES_TOTAL)
    assert sum(s["value"] for s in compiles._snapshot()
               if s["labels"]["kind"] == "paged") >= 2  # width 16 + width 1
    assert reg.get(tmetrics.JIT_CACHE_HITS_TOTAL).get(kind="paged") >= 2
    # block-table width buckets tagged
    bucket = reg.get(tmetrics.BUCKET_SELECTED_TOTAL)
    assert sum(s["value"] for s in bucket._snapshot()
               if s["labels"]["kind"] == "block_table") > 0
    _parse_prometheus(reg.render_prometheus())


def test_prefix_cache_hit_tokens_counter(live_registry):
    reg = live_registry
    app = _paged_app()
    eng = PagedEngineAdapter(app)
    prompt = list(range(1, 17))                     # two full 8-token blocks
    eng.add_requests([0], [prompt])
    eng.release([0])
    assert reg.get(tmetrics.PREFIX_CACHE_HIT_TOKENS_TOTAL) is None \
        or reg.get(tmetrics.PREFIX_CACHE_HIT_TOKENS_TOTAL).get() == 0
    eng.add_requests([1], [prompt])                 # same prompt: blocks hit
    assert reg.get(tmetrics.PREFIX_CACHE_HIT_TOKENS_TOTAL).get() >= 8
    eng.release([1])


def test_enabling_telemetry_after_warmup_counts_hits_not_compiles():
    """A graph compiled while telemetry was disabled must register as a
    cache HIT (not a fresh compile) once telemetry is enabled — otherwise
    the recompile signal false-alarms right after every warmup."""
    assert not telemetry.get_registry().enabled
    app = _fresh_app()
    ids = np.arange(1, 17, dtype=np.int32).reshape(2, 8)
    app._run_prefill(ids, np.full((2,), 8, np.int32))    # warm, uncounted
    app.reset()
    app.telemetry = telemetry.MetricsRegistry()
    app._run_prefill(ids, np.full((2,), 8, np.int32))
    assert app.telemetry.get(tmetrics.JIT_CACHE_HITS_TOTAL).get(
        kind="prefill") == 1
    assert app.telemetry.get(tmetrics.JIT_COMPILES_TOTAL) is None


def test_recompile_counter_across_bucket_changes(live_registry):
    reg = live_registry
    tcfg = TpuConfig(batch_size=2, seq_len=64, dtype="float32",
                     enable_bucketing=True,
                     context_encoding_buckets=[8, 16])
    app = CausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                              LlamaFamily)
    app.init_random_weights(7).init_cache()
    ids8 = np.ones((2, 8), np.int32)
    app._run_prefill(ids8, np.full((2,), 8, np.int32))
    app.reset()
    app._run_prefill(ids8, np.full((2,), 8, np.int32))
    app.reset()
    app._run_prefill(np.ones((2, 16), np.int32), np.full((2,), 16, np.int32))
    compiles = reg.get(tmetrics.JIT_COMPILES_TOTAL)
    hits = reg.get(tmetrics.JIT_CACHE_HITS_TOTAL)
    assert compiles.get(kind="prefill", bucket="8") == 1
    assert compiles.get(kind="prefill", bucket="16") == 1
    assert hits.get(kind="prefill") == 1


# ---------------------------------------------------------------------------
# zero-cost-when-disabled: outputs + jit cache keys pinned
# ---------------------------------------------------------------------------

def _pinned_run(app):
    ids = np.arange(1, 17, dtype=np.int32).reshape(2, 8)
    pre = app._run_prefill(ids, np.full((2,), 8, np.int32))
    dec = app._run_decode(np.asarray(pre["tokens"]).astype(np.int32)[:, None],
                          np.full((2, 1), 8, np.int32))
    return (np.asarray(pre["logits"]), np.asarray(pre["tokens"]),
            np.asarray(dec["logits"]), np.asarray(dec["tokens"]),
            sorted(app._compiled.keys(), key=repr))


def _fresh_app():
    tcfg = TpuConfig(batch_size=2, seq_len=64, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[8],
                     output_logits=True)
    app = CausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                              LlamaFamily)
    app.init_random_weights(7).init_cache()
    return app


def test_disabled_telemetry_is_bit_identical_and_keeps_cache_keys():
    assert not telemetry.get_registry().enabled     # library default
    base = _pinned_run(_fresh_app())

    app = _fresh_app()
    app.telemetry = telemetry.MetricsRegistry()     # per-app live registry
    live = _pinned_run(app)

    for b, l in zip(base[:4], live[:4]):
        np.testing.assert_array_equal(b, l)         # bit-identical outputs
    assert base[4] == live[4]                       # identical jit cache keys
    # and the instrumented run actually recorded something
    assert app.telemetry.get(tmetrics.RUN_SECONDS).count(
        kind="prefill", part="host") == 1


def test_disabled_adapters_add_no_metric_keys():
    assert not telemetry.get_registry().enabled
    _drive(PagedEngineAdapter(_paged_app()))
    reg = telemetry.get_registry()
    assert reg.snapshot() == {"metrics": {}, "spans": []}
    assert reg.render_prometheus() == ""
