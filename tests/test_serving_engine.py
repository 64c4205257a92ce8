"""Multi-tenant serving engine (serving/engine/): weighted fairness under
oversubscription, priority preemption with requeue bit-identity from the
ENGINE path, warm-prefix admission ordering, deadline expiry in queue
(zero device work), stream cancellation reclaiming blocks, typed queue
overflow, and an SSE round trip through the asyncio front door — all on
the tiny synthetic model shared with test_serving_adapter (CPU, <20s)."""

import asyncio
import json
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from neuronx_distributed_inference_tpu.config import TpuConfig
from neuronx_distributed_inference_tpu.models.application import (
    CausalLMApplication, PagedCausalLMApplication)
from neuronx_distributed_inference_tpu.models.llama import (
    LlamaFamily, LlamaInferenceConfig)
from neuronx_distributed_inference_tpu.resilience import (
    Cancelled, CapacityError, DeadlineExceeded, Preempted, QueueOverflow,
    ServingError)
from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
from neuronx_distributed_inference_tpu.serving.engine import (
    MultiTenantQueue, QueuedRequest, ServingEngine, ServingFrontend,
    TokenStream)

REPO = Path(__file__).resolve().parent.parent

HF = dict(model_type="llama", hidden_size=64, intermediate_size=128,
          num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, vocab_size=512, rms_norm_eps=1e-5, rope_theta=10000.0,
          hidden_act="silu", tie_word_embeddings=False,
          torch_dtype="float32")


@pytest.fixture(scope="module")
def paged_app():
    """One shared batch-4 paged app (same shapes as test_serving_adapter,
    so every graph is warm); tests build fresh adapters/engines over it
    and must release everything they admit."""
    tcfg = TpuConfig(batch_size=4, seq_len=64, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     is_block_kv_layout=True, pa_block_size=8,
                     is_prefix_caching=True)
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                                   LlamaFamily)
    app.init_random_weights(7).init_cache()
    return app


@pytest.fixture(scope="module")
def ref_app():
    """Single-request golden generator (same weights seed)."""
    tcfg = TpuConfig(batch_size=1, seq_len=64, dtype="float32",
                     enable_bucketing=False)
    app = CausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                              LlamaFamily)
    app.init_random_weights(7).init_cache()
    return app


def _golden(ref_app, prompt, n):
    out = ref_app.generate(np.asarray([prompt]), max_new_tokens=n)
    return list(np.asarray(out["generated"])[0])


def _prompts(seed, n, lo=1, hi=500, length=9):
    rng = np.random.default_rng(seed)
    return [rng.integers(lo, hi, size=length).tolist() for _ in range(n)]


# ---------------------------------------------------------------------------
# queue unit semantics (no device work)
# ---------------------------------------------------------------------------

def _qreq(rid, tenant, prio=0, order=0, enqueue_t=None, deadline=None):
    stream = TokenStream(rid, tenant)
    # when it was submitted is on the timeline its stream carries
    stream.timeline.accept = stream.timeline.submit = (
        time.perf_counter() if enqueue_t is None else enqueue_t)
    return QueuedRequest(
        request_id=rid, tokens=[1, 2, 3], max_new_tokens=4, tenant=tenant,
        priority=prio, deadline=deadline,
        order=order, stream=stream, orig_prompt_len=3)


def test_queue_weighted_fair_and_priority():
    q = MultiTenantQueue({"a": 1.0, "b": 3.0}, starvation_bound_s=1e9)
    order = 0
    for i in range(4):
        q.push(_qreq(f"a{i}", "a", order=order)); order += 1
    for i in range(12):
        q.push(_qreq(f"b{i}", "b", order=order)); order += 1
    picked = q.pop_batch(8, {})
    by_tenant = [r.tenant for r in picked]
    assert by_tenant.count("a") == 2 and by_tenant.count("b") == 6
    # within a tenant: strict priority beats FIFO
    q2 = MultiTenantQueue()
    q2.push(_qreq("lo", "t", prio=0, order=0))
    q2.push(_qreq("hi", "t", prio=9, order=1))
    assert [r.request_id for r in q2.pop_batch(2, {})] == ["hi", "lo"]


def test_queue_starvation_bound_jumps_wfq():
    now = time.perf_counter()
    q = MultiTenantQueue({"big": 100.0, "tiny": 0.001},
                         starvation_bound_s=2.0)
    q.push(_qreq("old", "tiny", order=0, enqueue_t=now - 10.0))
    for i in range(4):
        q.push(_qreq(f"big{i}", "big", order=i + 1))
    # tiny's weight share is ~0, but its head blew the starvation bound
    assert q.pop_batch(1, {})[0].request_id == "old"


def test_queue_rejects_nonpositive_weights():
    from neuronx_distributed_inference_tpu.resilience import \
        ConfigurationError
    with pytest.raises(ConfigurationError):
        MultiTenantQueue({"free": 0.0})      # would divide by zero in WFQ
    with pytest.raises(ConfigurationError):
        MultiTenantQueue(default_weight=-1.0)


def test_queue_overflow_and_requeue_bypass():
    q = MultiTenantQueue(max_depth=2)
    q.push(_qreq("r0", "t", order=0))
    q.push(_qreq("r1", "t", order=1))
    with pytest.raises(QueueOverflow) as ei:
        q.push(_qreq("r2", "t", order=2))
    assert isinstance(ei.value, CapacityError)       # typed, catchable
    assert isinstance(ei.value, ServingError)
    q.push(_qreq("victim", "t", order=3), front=True)  # requeue bypasses
    assert q.depth == 3


def test_preempted_requeue_payload():
    now = time.perf_counter()
    rec = Preempted(seq_id=7, tokens=(1, 2, 3, 9), prompt_len=3,
                    n_generated=1, reason="scheduler", deadline=now + 5.0,
                    meta={"tenant": "t", "request_id": "r7"})
    kw = rec.admission_kwargs(seq_id=42, now=now)
    assert kw["seq_ids"] == [42] and kw["prompts"] == [[1, 2, 3, 9]]
    assert kw["meta"] == [{"tenant": "t", "request_id": "r7"}]
    assert kw["deadline_s"][0] == pytest.approx(5.0)
    assert Preempted(seq_id=1, tokens=(1,), prompt_len=1, n_generated=0,
                     reason="grow").admission_kwargs()["deadline_s"] == [None]


# ---------------------------------------------------------------------------
# closed-loop engine semantics (shared tiny app)
# ---------------------------------------------------------------------------

def test_weighted_fairness_under_oversubscription(paged_app, ref_app):
    """9 requests over 4 slots (2.25x), weights a:b:c = 1:1:2: the running
    batch converges to 1/1/2 slots, nothing starves, and every stream is
    bit-identical (and token-ordered) vs the bare single-request golden."""
    eng = ServingEngine(
        PagedEngineAdapter(paged_app, prefill_budget_tokens=16),
        tenant_weights={"a": 1.0, "b": 1.0, "c": 2.0},
        starvation_bound_s=1e9)
    prompts = _prompts(0, 9)
    streams = []
    for i, p in enumerate(prompts):
        streams.append(eng.submit(p, 6, tenant="abc"[i // 3]))
    for _ in range(4):
        eng.run_pass()      # deferred chunked prefill needs a few passes
    share = {}
    for req in eng._active.values():
        share[req.tenant] = share.get(req.tenant, 0) + 1
    assert share == {"a": 1, "b": 1, "c": 2}
    eng.run_until_drained()
    assert eng.stats["completed"] == 9       # zero starvation
    assert all(s.finish_reason == "length" for s in streams)
    for p, s in zip(prompts, streams):
        assert s.tokens == _golden(ref_app, p, 6)
    assert not paged_app.kv_mgr.tables       # everything released


def test_priority_preemption_requeue_bit_identity(paged_app, ref_app):
    """Batch full of low-priority work; a priority-9 submit evicts the
    most recent victim through the adapter hook, runs first, and the
    victim's requeued stream is still bit-identical — the engine-path
    greedy-requeue pin the ISSUE asks for."""
    eng = ServingEngine(PagedEngineAdapter(paged_app),
                        starvation_bound_s=1e9)
    prompts = _prompts(1, 5)
    low = [eng.submit(p, 8, tenant="low") for p in prompts[:4]]
    eng.run_pass()                            # batch now full (4/4)
    assert eng.adapter.free_capacity == 0
    hi = eng.submit(prompts[4], 4, tenant="vip", priority=9)
    unfinished_low_at_hi_done = None
    while eng.has_work:
        eng.run_pass()
        if hi.finished and unfinished_low_at_hi_done is None:
            unfinished_low_at_hi_done = sum(not s.finished for s in low)
    assert eng.stats["priority_preemptions"] >= 1
    assert eng.stats["preempt_requeues"] >= 1
    assert hi.finish_reason == "length"
    # the evicted victim was still out when the priority request finished
    assert unfinished_low_at_hi_done >= 1
    assert hi.tokens == _golden(ref_app, prompts[4], 4)
    for p, s in zip(prompts[:4], low):
        assert s.finish_reason == "length"
        assert s.tokens == _golden(ref_app, p, 8)
    assert not paged_app.kv_mgr.tables


def test_priority_eviction_slot_is_reserved(paged_app):
    """The slot freed by a priority eviction must go to the request that
    justified it — NOT back through weighted fairness, which (with the
    victim's tenant far under its share) would re-admit the victim and
    livelock in an evict/re-prefill cycle while the VIP request starves."""
    eng = ServingEngine(
        PagedEngineAdapter(paged_app),
        tenant_weights={"vip": 1.0, "bulk": 100.0},
        starvation_bound_s=1e9)
    prompts = _prompts(7, 5)
    vip_low = [eng.submit(p, 10, tenant="vip") for p in prompts[:2]]
    bulk = [eng.submit(p, 10, tenant="bulk") for p in prompts[2:4]]
    eng.run_pass()
    assert eng.adapter.free_capacity == 0
    hi = eng.submit(prompts[4], 4, tenant="vip", priority=9)
    eng.run_pass()
    # the freed slot went to the priority request, not back to the
    # bulk victim (whose tenant is far below its weighted share)
    assert hi.request_id in eng._sid_of
    assert eng.stats["priority_preemptions"] == 1
    eng.run_pass()
    assert eng.stats["priority_preemptions"] == 1      # no thrash
    eng.run_until_drained()
    assert eng.stats["priority_preemptions"] == 1
    assert all(s.finish_reason == "length"
               for s in vip_low + bulk + [hi])
    assert not paged_app.kv_mgr.tables


def test_overlong_prompt_rejected_at_submit(paged_app):
    """A prompt beyond the compiled seq_len fails typed at submit() —
    by admission time it would be batched with innocent neighbours
    inside one transactional add_requests call."""
    from neuronx_distributed_inference_tpu.resilience import AdmissionError
    eng = ServingEngine(PagedEngineAdapter(paged_app),
                        starvation_bound_s=1e9)
    with pytest.raises(AdmissionError):
        eng.submit(list(range(1, 100)), 4)             # seq_len is 64
    assert eng.queue.depth == 0 and not eng.has_work


def test_warm_prefix_admission_ordering(paged_app):
    """Two queued requests, same tenant+priority, cold submitted FIRST:
    the admission batch is reordered warm-prefix-first (read-only probe of
    the block-hash state), so the warm request gets the earlier admission
    index and its cached blocks are actually hit."""
    adapter = PagedEngineAdapter(paged_app)
    warm_prefix = list(range(100, 116))       # 2 full 8-token blocks
    # park the prefix in the cache: run + release a request that used it
    seed_eng = ServingEngine(adapter, starvation_bound_s=1e9)
    seed_eng.submit(warm_prefix + [7], 2, tenant="seed")
    seed_eng.run_until_drained()
    assert adapter.prefix_warmth(warm_prefix + [9, 9]) == 16
    cold_prompt = list(range(300, 317))
    assert adapter.prefix_warmth(cold_prompt) == 0
    eng = ServingEngine(adapter, starvation_bound_s=1e9)
    cold = eng.submit(cold_prompt, 4, tenant="t")
    warm = eng.submit(warm_prefix + [9, 9], 4, tenant="t")
    eng.run_pass()      # admits both; they stay active (budget not hit)
    sid_cold = eng._sid_of.get(cold.request_id)
    sid_warm = eng._sid_of.get(warm.request_id)
    assert sid_cold is not None and sid_warm is not None
    seqs = adapter.seqs
    assert seqs[sid_warm].admit_idx < seqs[sid_cold].admit_idx
    assert paged_app.kv_mgr._hit_blocks.get(sid_warm, 0) == 2  # real hits
    eng.run_until_drained()
    assert not paged_app.kv_mgr.tables


def test_deadline_expiry_in_queue_no_device_work(paged_app):
    """A queued request whose deadline passes while the batch is full is
    typed-expired WITHOUT any device work — the adapter's prefill
    dispatch counters never move for it."""
    eng = ServingEngine(PagedEngineAdapter(paged_app),
                        priority_preemption=False, starvation_bound_s=1e9)
    runners = [eng.submit(p, 30, tenant="t") for p in _prompts(2, 4)]
    eng.run_pass()
    assert eng.adapter.free_capacity == 0
    before = dict(eng.adapter.host_stats)
    doomed = eng.submit(_prompts(3, 1)[0], 8, tenant="t",
                        deadline_s=0.02)
    time.sleep(0.03)
    eng.run_pass()
    assert doomed.finish_reason == "deadline"
    assert isinstance(doomed.error, DeadlineExceeded)
    assert doomed.tokens == []
    assert doomed.request_id not in eng._sid_of
    after = eng.adapter.host_stats
    assert after["prefill_dispatches"] == before["prefill_dispatches"]
    assert eng.stats["expired_queue"] == 1
    for s in runners:                          # cleanup via cancellation
        s.cancel()
    assert not eng.has_work
    assert not paged_app.kv_mgr.tables


def test_cancel_reclaims_blocks(paged_app):
    """Cancelling a running stream releases the sequence and reclaims its
    KV blocks; cancelling a queued one costs nothing; double-cancel and
    unknown ids are clean no-ops."""
    free0 = paged_app.kv_mgr.allocator.num_free
    eng = ServingEngine(PagedEngineAdapter(paged_app),
                        starvation_bound_s=1e9)
    running = [eng.submit(p, 20, tenant="t") for p in _prompts(4, 4)]
    queued = eng.submit(_prompts(5, 1)[0], 20, tenant="t")
    for _ in range(3):
        eng.run_pass()
    assert all(len(s.tokens) > 0 for s in running)
    # cancel the QUEUED request first, while the batch is still full:
    # zero device work was ever spent on it
    assert queued.request_id not in eng._sid_of        # never admitted
    assert eng.cancel(queued.request_id)
    assert queued.finish_reason == "cancelled" and queued.tokens == []
    victim = running[1]
    assert eng.cancel(victim.request_id)
    assert victim.finish_reason == "cancelled"
    assert isinstance(victim.error, Cancelled)
    assert isinstance(victim.error, ServingError)
    n_before = len(victim.tokens)
    eng.run_pass()
    assert len(victim.tokens) == n_before              # no late tokens
    assert victim.request_id not in eng._sid_of
    assert not eng.cancel(victim.request_id)           # already finished
    assert not eng.cancel("nonexistent")
    for s in running:
        s.cancel()
    assert not eng.has_work
    assert not paged_app.kv_mgr.tables
    assert paged_app.kv_mgr.allocator.num_free == free0


def test_submit_validation_and_overflow(paged_app):
    eng = ServingEngine(PagedEngineAdapter(paged_app), max_queue_depth=2,
                        starvation_bound_s=1e9)
    with pytest.raises(ValueError):
        eng.submit([], 4)
    with pytest.raises(ValueError):
        eng.submit([1, 2], 0)
    eng.submit([1, 2, 3], 4)
    eng.submit([1, 2, 3], 4)
    with pytest.raises(QueueOverflow):         # typed admission control
        eng.submit([1, 2, 3], 4)
    eng.close()                                # drops queued work
    assert eng.stats["submitted"] == 2 and not eng.has_work


def test_sse_round_trip_and_endpoints(paged_app, ref_app):
    """Real asyncio client in-process: POST /v1/generate streams SSE
    events that reproduce the golden tokens in order; /healthz and
    /metrics (with telemetry enabled, carrying the new queue metrics)
    round-trip; /v1/cancel kills a slow request."""
    from neuronx_distributed_inference_tpu import telemetry

    prompt = _prompts(6, 1)[0]
    want = _golden(ref_app, prompt, 5)

    async def http(host, port, raw):
        r, w = await asyncio.open_connection(host, port)
        w.write(raw)
        await w.drain()
        data = await asyncio.wait_for(r.read(), timeout=90)
        w.close()
        return data

    async def main():
        # max_unread_tokens armed: the non-streaming path must CONSUME
        # while it waits, or its own backpressure would deadlock it
        eng = ServingEngine(PagedEngineAdapter(paged_app),
                            starvation_bound_s=1e9, max_unread_tokens=2)
        fe = ServingFrontend(eng)
        host, port = await fe.start()
        body = json.dumps({"prompt": prompt, "max_new_tokens": 5}).encode()
        raw = (b"POST /v1/generate HTTP/1.1\r\nContent-Length: "
               + str(len(body)).encode() + b"\r\n\r\n" + body)
        resp = (await http(host, port, raw)).decode()
        assert "text/event-stream" in resp
        events = [json.loads(line[6:]) for line in resp.splitlines()
                  if line.startswith("data: ")]
        assert [e["token"] for e in events[:-1]] == want
        assert [e["index"] for e in events[:-1]] == list(range(5))
        assert events[-1] == {"done": True, "reason": "length",
                              "request_id": events[-1]["request_id"]}
        # submit + cancel round trip
        body2 = json.dumps({"prompt": prompt, "max_new_tokens": 40}).encode()
        raw2 = (b"POST /v1/submit HTTP/1.1\r\nContent-Length: "
                + str(len(body2)).encode() + b"\r\n\r\n" + body2)
        resp2 = (await http(host, port, raw2)).decode()
        rid = json.loads(resp2.split("\r\n\r\n", 1)[1])["request_id"]
        resp3 = (await http(
            host, port,
            f"POST /v1/cancel/{rid} HTTP/1.1\r\n\r\n".encode())).decode()
        assert json.loads(resp3.split("\r\n\r\n", 1)[1])["cancelled"]
        # health + metrics
        health = (await http(host, port,
                             b"GET /healthz HTTP/1.1\r\n\r\n")).decode()
        assert json.loads(health.split("\r\n\r\n", 1)[1])["ok"]
        metrics = (await http(host, port,
                              b"GET /metrics HTTP/1.1\r\n\r\n")).decode()
        assert "nxdi_queue_depth" in metrics
        assert "nxdi_queue_wait_seconds" in metrics
        assert 'tenant="default"' in metrics
        missing = (await http(
            host, port, b"GET /v1/stream/nope HTTP/1.1\r\n\r\n")).decode()
        assert missing.startswith("HTTP/1.1 404")
        # non-streaming generate completes under backpressure (tokens are
        # consumed while waiting) and returns one JSON body
        body3 = json.dumps({"prompt": prompt, "max_new_tokens": 5,
                            "stream": False}).encode()
        resp4 = (await http(
            host, port,
            b"POST /v1/generate HTTP/1.1\r\nContent-Length: "
            + str(len(body3)).encode() + b"\r\n\r\n" + body3)).decode()
        got = json.loads(resp4.split("\r\n\r\n", 1)[1])
        assert got["tokens"] == want and got["reason"] == "length"
        # malformed Content-Length gets a clean 400, not a dead socket
        bad = (await http(
            host, port,
            b"POST /v1/generate HTTP/1.1\r\nContent-Length: -1\r\n\r\n"
        )).decode()
        assert bad.startswith("HTTP/1.1 400")
        await fe.stop()

    telemetry.enable()
    try:
        asyncio.run(main())
    finally:
        telemetry.disable()
    assert not paged_app.kv_mgr.tables


# ---------------------------------------------------------------------------
# tier-1 lint coverage of the engine package
# ---------------------------------------------------------------------------

def test_lints_cover_engine_package(tmp_path):
    """The error-paths pass lints serving/engine/ (typed raises only)
    and the host-sync derived-coverage guard sees the engine's
    dispatch-driving loop — asserted against the unified driver's
    --json artifact instead of brittle "N file(s)" stdout pins, so
    adding a file to lint coverage cannot break this test."""
    from conftest import load_nxdi_lint
    nxdi_lint = load_nxdi_lint()
    out = tmp_path / "lint.json"
    assert nxdi_lint.main(
        ["--passes", "error-paths,host-sync", "--json", str(out)]) == 0
    data = json.loads(out.read_text())
    assert data["findings"] == []
    covered = set(data["files"])
    for rel in ("neuronx_distributed_inference_tpu/serving/engine/queue.py",
                "neuronx_distributed_inference_tpu/serving/engine/"
                "scheduler.py",
                "neuronx_distributed_inference_tpu/serving/engine/"
                "streams.py",
                "neuronx_distributed_inference_tpu/serving/engine/"
                "frontend.py",
                "neuronx_distributed_inference_tpu/serving/adapter.py"):
        assert rel in covered, f"{rel} dropped from lint coverage"
    # the dispatch-driving loop is a DISCOVERED host-sync region (the
    # hand-maintained expected-regions list is gone)
    analysis = nxdi_lint.load_analysis()
    hs = analysis.get_pass("host-sync")
    import importlib as _il
    hs_mod = _il.import_module(type(hs).__module__)
    ctx = analysis.LintContext(REPO)
    regions = set()
    for rel in hs.default_paths:
        regions.update(hs_mod.region_functions(ctx.source(rel)))
    assert "_dispatch_engine_pass" in regions
