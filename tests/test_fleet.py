"""Fleet layer (serving/fleet/): replicated-engine router with
prefix-affinity + drain + replica-failure requeue bit-identity, host-RAM
KV spill tier with spill/restore bit-identity vs recompute, disaggregated
prefill→decode handoff bit-identity, and the kv_spill/kv_restore/handoff
fault-point contracts — on the tiny synthetic model shared with
test_serving_engine (same shapes, so every graph is warm; CPU, <20s)."""

import json
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
    ConfigurationError, FAULTS, HandoffError, Preempted, ReplicaUnavailable,
    StepFailure)
from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
from neuronx_distributed_inference_tpu.serving.engine import (
    ServingEngine, ServingFrontend, TokenStream)
from neuronx_distributed_inference_tpu.serving.fleet import (
    DEAD, DRAINING, HEALTHY, EngineRouter, HostKVSpillTier, admit_handoff,
    capture_handoff, handoff_from_json, handoff_to_json)

REPO = Path(__file__).resolve().parent.parent

HF = dict(model_type="llama", hidden_size=64, intermediate_size=128,
          num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, vocab_size=512, rms_norm_eps=1e-5, rope_theta=10000.0,
          hidden_act="silu", tie_word_embeddings=False,
          torch_dtype="float32")


def _make_paged_app():
    """Same shapes as test_serving_engine's paged_app (warm graphs);
    seed 7 so every replica — and the single-engine golden — shares one
    set of weights (replicas of one model, the fleet premise)."""
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
    """Two same-weights paged apps: replica A and replica B (also the
    prefill-role and decode-role engines of the handoff tests). Tests
    build fresh adapters/engines over them and must release everything
    they admit (detaching any spill hook they installed)."""
    return _make_paged_app(), _make_paged_app()


@pytest.fixture(scope="module")
def ref_app():
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


def _evict_lru(app, seed=991):
    """Drive every LRU-resident prefix block through the eviction hook
    with ONE genuine pool-sized cold admission (the allocator consumes
    the whole free list, then evicts every resident; the admission is
    aborted so its never-written hashes are purged). Token values sit
    far above every test prompt's range, so the cold chains can never
    prefix-hit real content."""
    mgr = app.kv_mgr
    usable = mgr.spec.num_blocks - 1
    rng = np.random.default_rng(seed)
    cold = rng.integers(600, 5000, size=usable * mgr.spec.block_size)
    mgr.begin_sequence(999, cold.tolist())
    mgr.abort_sequence(999)
    assert not getattr(mgr.allocator, "_lru", []), "LRU not drained"


def _run_adapter(adapter, sid, prompt, n):
    """Admit + decode n tokens eagerly; returns the stream and releases."""
    first = adapter.add_requests([sid], [prompt])
    toks = [first[sid]]
    for _ in range(n - 1):
        toks.append(adapter.step([sid])[sid])
    adapter.release([sid])
    return toks


def _detach_spill_hook(app):
    if hasattr(app.kv_mgr.allocator, "on_evict"):
        app.kv_mgr.allocator.on_evict = None


# ---------------------------------------------------------------------------
# satellite contracts (no device work)
# ---------------------------------------------------------------------------

def test_preempted_json_round_trip():
    """Preempted.to_json/from_json cross a process boundary: pure JSON,
    and the absolute perf_counter deadline travels as a REMAINING
    relative budget re-anchored to the receiver's clock."""
    now = time.perf_counter()
    rec = Preempted(seq_id=7, tokens=(1, 2, 3, 9), prompt_len=3,
                    n_generated=1, reason="handoff", deadline=now + 5.0,
                    meta={"tenant": "t", "request_id": "r7", "priority": 2})
    wire = json.dumps(rec.to_json(now=now))       # must be JSON-safe
    later = now + 1.5
    back = Preempted.from_json(json.loads(wire), now=later)
    assert back.tokens == rec.tokens and back.prompt_len == 3
    assert back.n_generated == 1 and back.reason == "handoff"
    assert back.meta == rec.meta
    assert back.deadline == pytest.approx(later + 5.0)
    # the requeue payload built from the round-tripped record matches
    kw = back.admission_kwargs(seq_id=42, now=later)
    assert kw["prompts"] == [[1, 2, 3, 9]]
    assert kw["deadline_s"][0] == pytest.approx(5.0)
    # None deadline stays None
    rec2 = Preempted(seq_id=1, tokens=(4,), prompt_len=1, n_generated=0,
                     reason="grow")
    assert Preempted.from_json(rec2.to_json()).deadline is None
    with pytest.raises(KeyError):
        Preempted.from_json({"schema": "bogus"})


def test_spill_tier_bounds_and_eviction_order():
    """The host pool is bounded with oldest-TOUCHED-first eviction;
    hits refresh recency; seed() rides the same bound."""
    tier = HostKVSpillTier(max_blocks=2)
    p = lambda x: np.full((2, 8, 2, 16), x, np.float32)  # noqa: E731
    tier.spill(b"h1", p(1), p(1))
    tier.spill(b"h2", p(2), p(2))
    assert len(tier) == 2 and tier.nbytes > 0
    assert tier.get(b"h1") is not None            # touch h1 → h2 is oldest
    tier.spill(b"h3", p(3), p(3))
    assert tier.contains(b"h1") and tier.contains(b"h3")
    assert not tier.contains(b"h2")
    assert tier.stats["spilled"] == 3 and tier.stats["evicted"] == 1
    assert tier.get(b"h2") is None and tier.stats["misses"] == 1
    # re-spill of a resident hash is a recency touch, not a copy
    tier.spill(b"h1", p(1), p(1))
    assert tier.stats["spilled"] == 3
    tier.seed({b"h4": {"k": p(4), "v": p(4)}})
    assert tier.stats["seeded"] == 1 and len(tier) == 2
    with pytest.raises(ConfigurationError):
        HostKVSpillTier(max_blocks=0)


def test_frontend_registry_knob_and_fleet_debug(apps):
    """The /v1/submit stream-registry bound is a constructor knob with
    the pre-knob default (256) pinned, and a frontend built with fleet=
    serves the router snapshot in its debug payload."""
    app_a, _ = apps
    eng = ServingEngine(PagedEngineAdapter(app_a), starvation_bound_s=1e9)
    assert ServingFrontend(eng).max_retained_streams == 256   # default pin
    fe = ServingFrontend(eng, max_retained_streams=2)
    for i in range(5):
        fe._prune_streams()
        s = TokenStream(f"s{i}", "t")
        s.finish("length")
        fe._streams[s.request_id] = s
        assert len(fe._streams) <= 2
    with pytest.raises(ConfigurationError):
        ServingFrontend(eng, max_retained_streams=0)
    router = EngineRouter({"a": eng})
    payload = ServingFrontend(eng, fleet=router)._debug_payload()
    assert payload["fleet"]["replicas"]["a"]["state"] == HEALTHY
    assert "stats" in payload["fleet"]
    assert "fleet" not in ServingFrontend(eng)._debug_payload()
    eng.close()


# ---------------------------------------------------------------------------
# router: affinity, drain, replica-failure requeue
# ---------------------------------------------------------------------------

def test_router_affinity_drain_and_bit_identity(apps, ref_app):
    """Warm-prefix requests route to the replica whose block cache is
    warmest, cold ones to the least-loaded; drain() stops new admissions
    while running streams finish; every stream is bit-identical to the
    single-engine golden regardless of where it ran."""
    app_a, app_b = apps
    eng_a = ServingEngine(PagedEngineAdapter(app_a), starvation_bound_s=1e9)
    eng_b = ServingEngine(PagedEngineAdapter(app_b), starvation_bound_s=1e9)
    router = EngineRouter({"A": eng_a, "B": eng_b})
    warm_prefix = list(range(100, 116))           # 2 full 8-token blocks
    # park the prefix on B only
    eng_b.submit(warm_prefix + [7], 2, tenant="seed")
    eng_b.run_until_drained()
    assert eng_b.adapter.prefix_warmth(warm_prefix + [9, 9]) == 16
    assert eng_a.adapter.prefix_warmth(warm_prefix + [9, 9]) == 0

    warm_prompt = warm_prefix + [9, 9]
    cold_prompt = _prompts(11, 1)[0]
    s_warm = router.submit(warm_prompt, 4)
    assert router._requests[s_warm.request_id].replica == "B"
    s_cold = router.submit(cold_prompt, 4)        # B busier → A
    assert router._requests[s_cold.request_id].replica == "A"
    assert router.stats["affinity_warm"] == 1
    assert router.stats["affinity_cold"] == 1

    router.drain("B")
    assert router.replicas["B"].state == DRAINING
    s_warm2 = router.submit(warm_prefix + [8, 8], 4)
    assert router._requests[s_warm2.request_id].replica == "A"  # not B
    router.run_until_drained()                    # draining B still finishes
    assert s_warm.finish_reason == "length"
    assert s_warm.tokens == _golden(ref_app, warm_prompt, 4)
    assert s_cold.tokens == _golden(ref_app, cold_prompt, 4)
    assert s_warm2.tokens == _golden(ref_app, warm_prefix + [8, 8], 4)

    router.undrain("B")
    assert router.replicas["B"].state == HEALTHY
    # serving s_warm2 warmed A's cache too: both replicas now tie at
    # warmth 16, and the tie-break is stable name order — deterministic
    s_back = router.submit(warm_prompt, 4)
    assert router._requests[s_back.request_id].replica == "A"
    assert router.stats["affinity_warm"] == 2     # s_warm + s_back
    router.run_until_drained()
    assert s_back.tokens == s_warm.tokens
    assert router.stats["completed"] == 4 and router.stats["drains"] == 1
    assert not app_a.kv_mgr.tables and not app_b.kv_mgr.tables
    eng_a.close(), eng_b.close()


def test_replica_failure_requeue_bit_identity(apps, ref_app):
    """A replica dying mid-decode (unrecoverable StepFailure via the
    pipeline_flush fault) is marked dead; its in-flight request requeues
    onto the survivor riding Preempted.admission_kwargs(), and the
    stitched fleet stream is STILL bit-identical to the golden."""
    app_a, app_b = apps
    # the engine keeps a step in flight on A (step_ahead), so the
    # deferred-fetch fault point exists
    eng_a = ServingEngine(PagedEngineAdapter(app_a),
                          starvation_bound_s=1e9)
    eng_b = ServingEngine(PagedEngineAdapter(app_b), starvation_bound_s=1e9)
    router = EngineRouter({"A": eng_a, "B": eng_b})
    p_a, p_b = _prompts(21, 2)
    s_a = router.submit(p_a, 6)                   # empty fleet → A
    assert router._requests[s_a.request_id].replica == "A"
    s_b = router.submit(p_b, 6)                   # A has work → B
    assert router._requests[s_b.request_id].replica == "B"
    passes = 0
    while s_a.n_tokens < 2:
        router.run_pass()
        passes += 1
        assert passes < 100
    with FAULTS.inject("pipeline_flush") as fp:
        while fp.trips == 0:
            router.run_pass()
    assert router.replicas["A"].state == DEAD
    assert router.stats["replica_failures"] == 1
    router.run_until_drained()
    assert router.stats["requeues"] == 1
    assert router._done and s_a.finish_reason == "length"
    assert s_a.tokens == _golden(ref_app, p_a, 6)   # stitched, bit-identical
    assert s_b.tokens == _golden(ref_app, p_b, 6)   # survivor undisturbed
    # requeued request ended on the survivor
    assert not app_b.kv_mgr.tables
    # new submissions keep working on the surviving replica...
    s_c = router.submit(_prompts(22, 1)[0], 3)
    assert router._requests[s_c.request_id].replica == "B"
    router.run_until_drained()
    assert s_c.finish_reason == "length"
    # ...and with B drained too, there is nowhere to route: typed shed
    router.drain("B")
    with pytest.raises(ReplicaUnavailable):
        router.submit([1, 2, 3], 2)
    eng_b.close()
    # the dead replica's app holds fictional-failure leftovers: reclaim
    for sid in list(app_a.kv_mgr.tables):
        app_a.kv_mgr.end_sequence(sid)


def test_closed_replica_fails_over(apps, ref_app):
    """A replica CLOSED out from under the router (graceful shutdown, not
    a device failure) is marked dead, its in-flight request requeues onto
    the survivor bit-identically, and submit() never routes to a closed
    engine the router has not noticed yet."""
    app_a, app_b = apps
    eng_a = ServingEngine(PagedEngineAdapter(app_a), starvation_bound_s=1e9)
    eng_b = ServingEngine(PagedEngineAdapter(app_b), starvation_bound_s=1e9)
    router = EngineRouter({"A": eng_a, "B": eng_b})
    p = _prompts(61, 1)[0]
    s = router.submit(p, 6)
    assert router._requests[s.request_id].replica == "A"
    while s.n_tokens < 2:
        router.run_pass()
    eng_a.close()                     # external shutdown, streams cancelled
    # submit() must not route to the closed-but-not-yet-marked replica
    s2 = router.submit(_prompts(62, 1)[0], 3)
    assert router.replicas["A"].state == DEAD
    assert router._requests[s2.request_id].replica == "B"
    router.run_until_drained()
    assert router.stats["requeues"] == 1
    assert s.finish_reason == "length"
    assert s.tokens == _golden(ref_app, p, 6)    # stitched, bit-identical
    assert s2.finish_reason == "length"
    assert not app_b.kv_mgr.tables
    eng_b.close()
    for sid in list(app_a.kv_mgr.tables):        # closed engine leftovers
        app_a.kv_mgr.end_sequence(sid)


# ---------------------------------------------------------------------------
# host-RAM KV spill tier
# ---------------------------------------------------------------------------

def test_spill_restore_bit_identity_vs_recompute(apps, ref_app):
    """Prefix blocks LRU-evicted from the device pool spill to the host
    tier; a later admission of the same prompt restores them by H2D copy
    instead of recompute-prefill — and the restored stream is
    bit-identical to the recomputed one."""
    app_a, _ = apps
    tier = HostKVSpillTier(max_blocks=16)
    adapter = PagedEngineAdapter(app_a, kv_spill_tier=tier)
    try:
        prompt = _prompts(31, 1, length=17)[0]    # 2 full blocks + 1
        golden = _golden(ref_app, prompt, 6)
        assert _run_adapter(adapter, 0, prompt, 6) == golden  # recompute run
        free_before = app_a.kv_mgr.allocator.num_free
        _evict_lru(app_a)                         # hook spills on eviction
        assert tier.stats["spilled"] == 2
        assert adapter.host_stats["kv_spilled_blocks"] == 2
        # device cache is cold now, but the tier counts as warmth
        assert app_a.kv_mgr.probe_cached_tokens(prompt)[0] == 0
        assert adapter.prefix_warmth(prompt) == 16
        real_before = adapter.host_stats["prefill_real_tokens"]
        assert _run_adapter(adapter, 1, prompt, 6) == golden  # restored run
        assert tier.stats["restored"] == 2
        assert adapter.host_stats["kv_restored_blocks"] == 2
        # only the uncovered suffix recomputed (17 tokens - 16 restored)
        assert adapter.host_stats["prefill_real_tokens"] - real_before == 1
        assert app_a.kv_mgr.allocator.num_free == free_before
        assert not app_a.kv_mgr.tables
    finally:
        _detach_spill_hook(app_a)


def test_kv_restore_fault_rolls_back_admission(apps, ref_app):
    """The kv_restore fault point fires before the H2D write: the
    transactional add_requests rolls back exactly (typed StepFailure,
    free pool restored, nothing admitted) and a plain retry heals."""
    app_a, _ = apps
    tier = HostKVSpillTier(max_blocks=16)
    adapter = PagedEngineAdapter(app_a, kv_spill_tier=tier)
    try:
        prompt = _prompts(33, 1, length=17)[0]
        golden = _golden(ref_app, prompt, 4)
        assert _run_adapter(adapter, 0, prompt, 4) == golden
        _evict_lru(app_a, seed=992)
        assert tier.stats["spilled"] >= 2
        free_before = app_a.kv_mgr.allocator.num_free
        with FAULTS.inject("kv_restore") as fp:
            with pytest.raises(StepFailure) as ei:
                adapter.add_requests([1], [prompt])
        assert fp.trips == 1
        assert ei.value.phase == "prefill" and ei.value.retry_safe
        assert app_a.kv_mgr.allocator.num_free == free_before
        assert not app_a.kv_mgr.tables and not adapter.seqs
        assert adapter.pending_prefill_ids == ()
        # retry heals: same admission restores and matches the golden
        assert _run_adapter(adapter, 1, prompt, 4) == golden
        assert tier.stats["restored"] == 2
    finally:
        _detach_spill_hook(app_a)


def test_kv_spill_fault_degrades_to_recompute(apps, ref_app):
    """A failing spill (kv_spill fault) is best-effort: the eviction that
    triggered it succeeds, the payload is simply dropped (counted), and
    the later admission recomputes — still bit-identical."""
    app_a, _ = apps
    tier = HostKVSpillTier(max_blocks=16)
    adapter = PagedEngineAdapter(app_a, kv_spill_tier=tier)
    try:
        prompt = _prompts(35, 1, length=17)[0]
        golden = _golden(ref_app, prompt, 4)
        assert _run_adapter(adapter, 0, prompt, 4) == golden
        with FAULTS.inject("kv_spill", times=99):
            _evict_lru(app_a, seed=993)           # evictions still succeed
        assert tier.stats["spill_errors"] >= 2
        assert tier.stats["spilled"] == 0 and len(tier) == 0
        assert adapter.prefix_warmth(prompt) == 0  # nothing restorable
        assert _run_adapter(adapter, 1, prompt, 4) == golden  # recompute
        assert tier.stats["restored"] == 0
        assert not app_a.kv_mgr.tables
    finally:
        _detach_spill_hook(app_a)


# ---------------------------------------------------------------------------
# disaggregated prefill → decode handoff
# ---------------------------------------------------------------------------

def test_handoff_bit_identity_and_faults(apps, ref_app):
    """A prefill-role engine admits + prefills, hands the sequence off
    through the JSON wire form, and the decode-role engine's stream is
    bit-identical to the single-engine golden; both sides fail typed
    (handoff fault point) with their engine state unchanged."""
    app_a, app_b = apps
    prefill = PagedEngineAdapter(app_a)
    tier_b = HostKVSpillTier(max_blocks=16)
    decode = PagedEngineAdapter(app_b, kv_spill_tier=tier_b)
    try:
        prompt = _prompts(41, 1, length=17)[0]
        golden = _golden(ref_app, prompt, 6)
        first = prefill.add_requests([5], [prompt])
        assert first[5] == golden[0]
        # capture-side failures leave the sequence running
        with pytest.raises(HandoffError):
            capture_handoff(prefill, 99)          # unknown seq
        with FAULTS.inject("handoff"):
            with pytest.raises(HandoffError):
                capture_handoff(prefill, 5)
        assert 5 in prefill.seqs                  # still on the prefill side
        record = capture_handoff(prefill, 5)
        assert 5 not in prefill.seqs and not app_a.kv_mgr.tables
        assert record["preempted"]["reason"] == "handoff"
        # the wire form is pure JSON (process boundary)
        wire = json.dumps(handoff_to_json(record))
        received = handoff_from_json(json.loads(wire))
        assert received["kv_blocks"][0]["k"].dtype == np.float32
        # admit-side failures leave the decode engine unchanged
        free_b = app_b.kv_mgr.allocator.num_free
        with pytest.raises(HandoffError):
            admit_handoff(PagedEngineAdapter(app_b), received, 0)  # no tier
        with FAULTS.inject("handoff"):
            with pytest.raises(HandoffError):
                admit_handoff(decode, received, 0)
        with pytest.raises(HandoffError):
            admit_handoff(decode, {"schema": "bogus"}, 0)
        assert app_b.kv_mgr.allocator.num_free == free_b
        # the real admission: KV restored, only the suffix recomputes
        real_before = decode.host_stats["prefill_real_tokens"]
        first_b = admit_handoff(decode, received, 0)
        toks = [golden[0], first_b[0]]
        for _ in range(4):
            toks.append(decode.step([0])[0])
        decode.release([0])
        assert toks == golden                     # bit-identical to 1 engine
        assert tier_b.stats["restored"] == 2
        # prompt+t0 is 18 tokens; 16 restored → 2 recomputed
        assert decode.host_stats["prefill_real_tokens"] - real_before == 2
        assert not app_b.kv_mgr.tables
    finally:
        _detach_spill_hook(app_a)
        _detach_spill_hook(app_b)


# ---------------------------------------------------------------------------
# observability + lint coverage
# ---------------------------------------------------------------------------

def test_fleet_metrics_and_events(apps):
    """The fleet events are in the stable EVENT_NAMES contract, routing
    and spill/restore land on the recorder and the nxdi_fleet_*/
    nxdi_kv_* metrics, and /metrics renders them."""
    from neuronx_distributed_inference_tpu import telemetry
    from neuronx_distributed_inference_tpu.telemetry import trace as trace_mod

    for name in ("fleet.route", "fleet.drain", "kv.spill", "kv.restore",
                 "handoff.send", "handoff.recv"):
        assert name in trace_mod.EVENT_NAMES
    app_a, app_b = apps
    reg = telemetry.enable()
    rec = telemetry.enable_recorder()
    try:
        rec.clear()
        tier = HostKVSpillTier(max_blocks=16)
        adapter_a = PagedEngineAdapter(app_a, kv_spill_tier=tier)
        eng_a = ServingEngine(adapter_a, starvation_bound_s=1e9)
        eng_b = ServingEngine(PagedEngineAdapter(app_b),
                              starvation_bound_s=1e9)
        router = EngineRouter({"A": eng_a, "B": eng_b})
        prompt = _prompts(51, 1, length=17)[0]
        router.submit(prompt, 3)
        router.drain("B")
        router.run_until_drained()
        _evict_lru(app_a, seed=994)               # spill events/metrics
        router.submit(prompt, 3)                  # restore on re-admission
        router.run_until_drained()
        names = {e["name"] for e in rec.events()}
        assert {"fleet.route", "fleet.drain", "kv.spill",
                "kv.restore"} <= names
        route = next(e for e in rec.events() if e["name"] == "fleet.route")
        assert route["cat"] == "fleet" and route["args"]["replica"] == "A"
        text = reg.render_prometheus()
        assert 'nxdi_fleet_routed_total{replica="A",affinity="cold"}' in text
        assert 'nxdi_fleet_routed_total{replica="A",affinity="warm"}' in text
        assert "nxdi_kv_spill_blocks_total" in text
        assert "nxdi_kv_spill_bytes" in text
        from neuronx_distributed_inference_tpu.telemetry import \
            metrics as tmetrics
        assert tmetrics.kv_restore_blocks_counter(reg).get() == 2
        assert tmetrics.kv_restore_tokens_counter(reg).get() == 16
        eng_a.close(), eng_b.close()
        assert not app_a.kv_mgr.tables and not app_b.kv_mgr.tables
    finally:
        _detach_spill_hook(app_a)
        telemetry.disable_recorder()
        telemetry.disable()


def test_lints_cover_fleet_package(tmp_path):
    """error-paths + host-sync lint the three serving/fleet/ files (and
    the package __init__) with zero findings and zero suppressions —
    asserted against the unified driver's --json artifact."""
    from conftest import load_nxdi_lint
    nxdi_lint = load_nxdi_lint()
    out = tmp_path / "lint.json"
    assert nxdi_lint.main(
        ["--passes", "error-paths,host-sync", "--json", str(out)]) == 0
    data = json.loads(out.read_text())
    assert data["findings"] == [] and data["suppressed"] == []
    covered = set(data["files"])
    for rel in ("neuronx_distributed_inference_tpu/serving/fleet/router.py",
                "neuronx_distributed_inference_tpu/serving/fleet/"
                "kv_tier.py",
                "neuronx_distributed_inference_tpu/serving/fleet/"
                "handoff.py",
                "neuronx_distributed_inference_tpu/serving/fleet/"
                "__init__.py"):
        assert rel in covered, f"{rel} dropped from lint coverage"
