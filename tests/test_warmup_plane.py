"""Cold-start truth (ISSUE 16, serving/warmup.py): the fleet precompile
plane walks the serving graph ladder through the application's OWN jit
entry points and classifies every graph (XLA build vs persistent-cache
load vs warm hit) — so a second replica sharing the compilation cache
reports ZERO compiles (ROADMAP item 5). Afterwards the app is in
declared steady state: any later first-seen signature is a tracked
incident (counter + ``compile.unexpected`` event + request-trace
attribution + ``/v1/debug/state["warmup"]``). The HBM ledger reconciles
bit-for-bit with the adapter's block accounting and is served as
``GET /v1/debug/memory``; the scheduler logs admission headroom on
capacity rejects; the typed 404 body and the hardened ``/v1/metrics``
exposition (label escaping + versioned Content-Type) are pinned over
the real asyncio front door. Tiny synthetic model, CPU, <20s warm."""

import asyncio
import json

import numpy as np
import pytest

from neuronx_distributed_inference_tpu import telemetry
from neuronx_distributed_inference_tpu.config import TpuConfig
from neuronx_distributed_inference_tpu.models.application import \
    PagedCausalLMApplication
from neuronx_distributed_inference_tpu.models.llama import (
    LlamaFamily, LlamaInferenceConfig)
from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
from neuronx_distributed_inference_tpu.serving.engine import (
    ServingEngine, ServingFrontend)
from neuronx_distributed_inference_tpu.serving.warmup import (
    LEDGER_SCHEMA, WARMUP_SCHEMA, admission_headroom, memory_ledger,
    precompile)
from neuronx_distributed_inference_tpu.telemetry import metrics as tmetrics
from neuronx_distributed_inference_tpu.telemetry import trace as trace_mod
from neuronx_distributed_inference_tpu.telemetry.registry import \
    MetricsRegistry

HF = dict(model_type="llama", hidden_size=64, intermediate_size=128,
          num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, vocab_size=512, rms_norm_eps=1e-5, rope_theta=10000.0,
          hidden_act="silu", tie_word_embeddings=False,
          torch_dtype="float32")

RNG = np.random.default_rng(29)
P_A = RNG.integers(1, 500, size=9).tolist()
P_B = RNG.integers(1, 500, size=11).tolist()

# the reduced warm ladder the module's shared app precompiles — anything
# OUTSIDE it dispatched in steady state is a provoked incident
WARM_WIDTHS = [1, 4]


def _fresh_app():
    """Same shapes as test_serving_engine's paged_app, so every graph is
    already in the suite's shared persistent compilation cache."""
    tcfg = TpuConfig(batch_size=4, seq_len=64, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     is_block_kv_layout=True, pa_block_size=8,
                     is_prefix_caching=True)
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                                   LlamaFamily)
    app.init_random_weights(7).init_cache()
    return app


@pytest.fixture(scope="module")
def warm_app():
    """One shared precompiled app in declared steady state (reduced
    ladder: ragged widths 1 and 4 only)."""
    app = _fresh_app()
    precompile(app, registry=MetricsRegistry(), widths=WARM_WIDTHS)
    return app


def _dummy_ragged(app, w):
    """A no-write ragged dispatch of row width ``w`` (every slot
    negative, nothing emitted) — the warmup plane's own dummy-call
    discipline, reused here to provoke shapes on demand."""
    b = app.tpu_config.batch_size
    tw = sorted(app._bt_buckets)[0]
    app._run_ragged(np.zeros((b, w), np.int32), np.zeros((b, w), np.int32),
                    np.full((b, w), -1, np.int32),
                    np.zeros((b, tw), np.int32),
                    np.ones((b,), np.int32), np.zeros((b,), np.int32))


# ---------------------------------------------------------------------------
# the precompile plane
# ---------------------------------------------------------------------------

def test_precompile_report_and_debug_surface(warm_app):
    """The warmup report is schema-stable, accounts for every planned
    graph exactly once, and surfaces through ``warmup_state()`` (the
    ``/v1/debug/state["warmup"]`` payload) with steady state declared."""
    rep = warm_app._warmup_report
    ws = warm_app.warmup_state()
    assert rep["schema"] == WARMUP_SCHEMA
    assert rep["n_graphs"] == len(rep["graphs"]) >= len(WARM_WIDTHS)
    assert (rep["n_compiles"] + rep["n_cache_loads"] + rep["n_warm_hits"]
            == rep["n_graphs"])
    assert rep["total_seconds"] > 0
    for g in rep["graphs"]:
        assert g["outcome"] in ("compile", "cache_load", "warm")
        assert g["seconds"] >= 0
        assert g["kind"] in ("ragged", "paged", "carry_ids")
    assert sorted(g["bucket"] for g in rep["graphs"]
                  if g["kind"] == "ragged") == sorted(WARM_WIDTHS)
    # the two-phase graph the DEFAULT adapter dispatches is warmed too:
    # T=1 plus whichever warm widths are ctx buckets (none of these are)
    assert [g["bucket"] for g in rep["graphs"]
            if g["kind"] == "paged"] == [1]
    # ... and beside the decode step, the program that makes its ids on the
    # device when the live set changed under the step in flight
    assert [g["bucket"] for g in rep["graphs"]
            if g["kind"] == "carry_ids"] == [warm_app.tpu_config.batch_size]
    # every traced graph noted the pool's page as allocated (2 heads of 16
    # do not fold: a slot a head), and every T=1 graph which attention path
    # it took — this toy's head_dim 16 is outside the kernels' geometry,
    # and both say so (the chunk's: ops/paged_prefill.py)
    assert rep["kernels"] == [
        {"site": "kv_pool", "path": "xla", "reason": "page=2x16 heads=2x16"},
        {"site": "paged_decode", "path": "xla",
         "reason": "unsupported geometry (head_dim / attn_chunk)"},
        {"site": "paged_prefill", "path": "xla",
         "reason": "rows=4 width=4: a kv row of 16 lanes of heads of 16 is "
                   "not whole vregs"}]
    assert ws["kernels"] == rep["kernels"]
    assert ws["steady_state"] is True
    assert ws["graphs_seen"] >= rep["n_graphs"]
    assert ws["precompile"]["n_graphs"] == rep["n_graphs"]


def test_second_replica_compiles_nothing():
    """ROADMAP item 5, out of the counters: replica 1 walks the ladder
    and populates the shared persistent compilation cache; replica 2
    (fresh app, fresh registry, same shapes) walks the same ladder and
    reports ZERO compiles — every graph is a persistent-cache load,
    counted as ``nxdi_jit_cache_hits_total`` instead of
    ``nxdi_jit_compiles_total``."""
    app1, app2 = _fresh_app(), _fresh_app()
    reg1, reg2 = MetricsRegistry(), MetricsRegistry()
    rep1 = precompile(app1, registry=reg1, widths=WARM_WIDTHS)
    if not rep1["cache_monitored"]:
        pytest.skip("jax compilation-cache monitoring unavailable — "
                    "compile-vs-load classification cannot be trusted")
    rep2 = precompile(app2, registry=reg2, widths=WARM_WIDTHS)
    assert rep2["n_graphs"] == rep1["n_graphs"]
    assert rep2["n_compiles"] == 0
    assert rep2["n_cache_loads"] == rep2["n_graphs"]
    # counters tell the same story: no compile series on replica 2 ...
    c2 = reg2.get(tmetrics.JIT_COMPILES_TOTAL)
    assert c2 is None or c2.get(kind="ragged", bucket="1") == 0
    hits2 = reg2.get(tmetrics.JIT_CACHE_HITS_TOTAL)
    assert (hits2.get(kind="ragged") + hits2.get(kind="paged")
            + hits2.get(kind="carry_ids") == rep2["n_graphs"])
    # ... but cold-start truth per graph regardless: compile_seconds is
    # set for every first-seen signature, build or load
    for w in WARM_WIDTHS:
        assert reg2.get(tmetrics.COMPILE_SECONDS).get(
            kind="ragged", bucket=str(w)) > 0
    # a re-walk of an already-warm replica touches no caches at all
    rep2b = precompile(app2, registry=reg2, widths=WARM_WIDTHS)
    assert rep2b["n_warm_hits"] == rep2b["n_graphs"]
    assert rep2b["n_compiles"] == rep2b["n_cache_loads"] == 0


# ---------------------------------------------------------------------------
# the recompile sentinel
# ---------------------------------------------------------------------------

def test_steady_state_recompile_is_a_tracked_incident(warm_app):
    """A first-seen signature AFTER declared steady state: the
    ``nxdi_steady_state_recompiles_total`` counter moves, a
    ``compile.unexpected`` event lands on the flight recorder carrying
    the request traces packed into the dispatch, and the incident shows
    in ``warmup_state()``."""
    reg = telemetry.enable()
    rec = trace_mod.enable_recorder()
    try:
        before = reg.get(tmetrics.STEADY_STATE_RECOMPILES_TOTAL)
        before = before.get(kind="ragged", bucket="2") if before else 0.0
        with warm_app.request_context(["t-direct", None]):
            _dummy_ragged(warm_app, 2)     # width 2 is NOT in WARM_WIDTHS
        after = reg.get(tmetrics.STEADY_STATE_RECOMPILES_TOTAL).get(
            kind="ragged", bucket="2")
        assert after == before + 1
        hits = [i for i in warm_app.warmup_state()["incidents"]
                if "t-direct" in i["traces"]]
        assert len(hits) == 1
        assert hits[0]["kind"] == "ragged" and hits[0]["bucket"] == "2"
        assert hits[0]["traces"] == ["t-direct"]     # None filtered out
        evs = [e for e in rec.events()
               if e["name"] == "compile.unexpected"
               and e["args"].get("traces") == ["t-direct"]]
        assert len(evs) == 1 and evs[0]["args"]["kind"] == "ragged"
        # the warm ladder itself stays incident-free
        with warm_app.request_context(["t-warm"]):
            _dummy_ragged(warm_app, WARM_WIDTHS[0])
        assert not [i for i in warm_app._steady_incidents
                    if "t-warm" in i["traces"]]
    finally:
        trace_mod.disable_recorder()
        telemetry.disable()


def test_adapter_dispatch_attributes_incident_to_request_trace():
    """Through the serving path: an adapter whose app only precompiled
    width 1 drives a real chunked prefill in steady state — the provoked
    compile is attributed to the triggering request's trace id (the
    ``meta["trace"]`` passthrough), not lost."""
    app = _fresh_app()
    precompile(app, widths=[1])
    rec = trace_mod.enable_recorder()
    try:
        ad = PagedEngineAdapter(app, ragged=True)
        assert ad.add_requests([0], [P_A],
                               meta=[{"trace": "t-adapter"}]) == {}
        for _ in range(3):
            ad.step()
        ad.release([0])
        hits = [i for i in app._steady_incidents
                if "t-adapter" in i["traces"]]
        assert hits, "steady-state compile lost its request attribution"
        assert all(i["kind"] in ("ragged", "paged") for i in hits)
    finally:
        trace_mod.disable_recorder()


# ---------------------------------------------------------------------------
# the HBM ledger
# ---------------------------------------------------------------------------

def test_memory_ledger_reconciles_with_block_accounting(warm_app):
    """The ledger's block split equals ``adapter.debug_state()`` exactly
    (same allocator, no estimation), byte splits tile the usable pool,
    fragmentation is a ratio, and the ``nxdi_hbm_*`` gauges carry the
    same numbers."""
    reg = MetricsRegistry()
    ad = PagedEngineAdapter(warm_app)
    ad.add_requests([0, 1], [P_A, P_B])
    try:
        led = memory_ledger(ad, registry=reg)
        assert led["schema"] == LEDGER_SCHEMA
        assert led["model_bytes"] > 0
        kv = led["kv"]
        assert kv["blocks"] == ad.debug_state()["blocks"]
        assert kv["blocks"]["in_use"] > 0
        assert (kv["bytes"]["used"] + kv["bytes"]["free"]
                == kv["blocks"]["usable"] * kv["block_bytes"])
        assert kv["live_tokens"] >= len(P_A) + len(P_B)
        assert 0.0 <= kv["fragmentation_ratio"] <= 1.0
        head = led["headroom"]
        assert head == admission_headroom(ad)
        assert head["headroom_tokens"] == (head["free_blocks"]
                                           * kv["block_size"])
        assert reg.get(tmetrics.HBM_MODEL_BYTES).get() == led["model_bytes"]
        for state, nbytes in kv["bytes"].items():
            assert reg.get(tmetrics.HBM_KV_BYTES).get(state=state) == nbytes
        assert (reg.get(tmetrics.KV_FRAGMENTATION_RATIO).get()
                == kv["fragmentation_ratio"])
    finally:
        ad.release([0, 1])
    after = memory_ledger(ad)
    assert after["kv"]["blocks"]["free"] > led["kv"]["blocks"]["free"]


def test_scheduler_logs_admission_headroom_on_reject(warm_app):
    """The scheduler's capacity-reject event carries the live headroom
    estimate (free slots / free blocks / token headroom) so a rejected
    admission explains itself; the engine's debug state exposes the
    warmup account."""
    rec = trace_mod.enable_recorder()
    try:
        eng = ServingEngine(PagedEngineAdapter(warm_app),
                            starvation_bound_s=1e9)
        eng._note_headroom("admit")
        evs = [e for e in rec.events() if e["name"] == "admission.headroom"]
        assert evs and evs[-1]["args"]["where"] == "admit"
        want = admission_headroom(eng.adapter)
        got = {k: evs[-1]["args"][k] for k in want}
        assert got == want
        assert eng.debug_state()["warmup"]["steady_state"] is True
    finally:
        trace_mod.disable_recorder()


# ---------------------------------------------------------------------------
# the front door: /v1/debug/memory, typed 404, hardened exposition
# ---------------------------------------------------------------------------

def test_frontend_memory_trace404_and_hardened_metrics(warm_app):
    """Over a real asyncio socket: ``GET /v1/debug/memory`` serves the
    reconciling ledger; an unknown trace id is a TYPED 404 JSON body
    (``"type": "trace_not_found"``), not a bare status line; and
    ``/v1/metrics`` survives a hostile tenant label (quotes, backslash,
    newline) with correct escaping under the versioned Content-Type."""
    tenant = 'bad"t\\t\nt'
    escaped = 'tenant="bad\\"t\\\\t\\nt"'

    async def http(host, port, raw):
        r, w = await asyncio.open_connection(host, port)
        w.write(raw)
        await w.drain()
        data = await asyncio.wait_for(r.read(), timeout=30)
        w.close()
        return data

    async def main():
        eng = ServingEngine(PagedEngineAdapter(warm_app),
                            starvation_bound_s=1e9)
        fe = ServingFrontend(eng)
        host, port = await fe.start()
        mem = (await http(host, port,
                          b"GET /v1/debug/memory HTTP/1.1\r\n\r\n")).decode()
        assert mem.startswith("HTTP/1.1 200")
        led = json.loads(mem.split("\r\n\r\n", 1)[1])
        assert led["schema"] == LEDGER_SCHEMA
        assert led["kv"]["blocks"] == eng.adapter.debug_state()["blocks"]
        assert "headroom" in led and led["model_bytes"] > 0
        # typed 404: machine-readable error body, not just a status line
        missing = (await http(
            host, port,
            b"GET /v1/debug/trace/nope HTTP/1.1\r\n\r\n")).decode()
        assert missing.startswith("HTTP/1.1 404")
        err = json.loads(missing.split("\r\n\r\n", 1)[1])
        assert err["type"] == "trace_not_found" and err["status"] == 404
        assert "nope" in err["error"]
        # hostile tenant: one well-formed series line, versioned exposition
        tmetrics.queue_depth_gauge(telemetry.get_registry()).set(
            3, tenant=tenant)
        resp = (await http(host, port,
                           b"GET /v1/metrics HTTP/1.1\r\n\r\n")).decode()
        head, body = resp.split("\r\n\r\n", 1)
        assert "text/plain; version=0.0.4" in head
        lines = [l for l in body.splitlines() if escaped in l]
        assert len(lines) == 1 and lines[0].startswith("nxdi_queue_depth{")
        assert tenant not in body          # raw newline never leaks a line
        await fe.stop()

    telemetry.enable()
    try:
        asyncio.run(main())
    finally:
        telemetry.disable()
