"""Resilience control plane (ISSUE 15): the degradation controller acts
on SLO burn with hysteresis (speculation shed / admission tightening /
ragged fallback, all bit-identical), the fleet router's replica health
state machine (quarantine -> probation -> re-admit without undrain,
escalation to dead with bit-identical failover), run_forever's typed
teardown of unexpected exceptions, the fault-points lint pass (green
live, red on doctored copies both directions), and the seeded chaos
campaign (smoke subset tier-1; red-verified on a doctored invariant) —
all on the tiny synthetic model shared with test_fleet (same shapes, so
every graph is warm; CPU)."""

import asyncio
import json
import textwrap
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from neuronx_distributed_inference_tpu.config import (LoraServingConfig,
                                                      TpuConfig)
from neuronx_distributed_inference_tpu.models.application import (
    CausalLMApplication, PagedCausalLMApplication)
from neuronx_distributed_inference_tpu.models.llama import (
    LlamaFamily, LlamaInferenceConfig)
from neuronx_distributed_inference_tpu.resilience import (
    ConfigurationError, DegradationController, FAULTS, ReplicaUnavailable,
    StepFailure)
from neuronx_distributed_inference_tpu.resilience.chaos import (
    ChaosCampaign, default_cells)
from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
from neuronx_distributed_inference_tpu.serving.engine import (
    MultiTenantQueue, ServingEngine)
from neuronx_distributed_inference_tpu.serving.fleet import (
    BACKING_OFF, DEAD, HEALTHY, EngineRouter)
from neuronx_distributed_inference_tpu.telemetry.slo import (SLOPolicy,
                                                             SLOTracker)

REPO = Path(__file__).resolve().parent.parent

HF = dict(model_type="llama", hidden_size=64, intermediate_size=128,
          num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, vocab_size=512, rms_norm_eps=1e-5, rope_theta=10000.0,
          hidden_act="silu", tie_word_embeddings=False,
          torch_dtype="float32")


def _make_paged_app():
    """Same shapes as test_fleet / test_serving_engine (warm graphs);
    seed 7 so every replica and the golden share one set of weights.
    LoRA-built (slots start zero, so base streams stay bit-identical
    with the no-LoRA golden): the chaos workload's adapter-churn phase
    needs the stacked arrays to traverse adapter_swap/adapter_spill."""
    tcfg = TpuConfig(batch_size=4, seq_len=64, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     is_block_kv_layout=True, pa_block_size=8,
                     is_prefix_caching=True,
                     lora_config=LoraServingConfig(
                         max_loras=3, max_lora_rank=4,
                         target_modules=["q_proj", "v_proj"]))
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                                   LlamaFamily)
    app.init_random_weights(7).init_cache()
    return app


@pytest.fixture(scope="module")
def apps():
    """Three same-weights paged apps: the chaos campaign's replica
    roles; router/engine tests borrow subsets. Tests must leave every
    app clean (no tables, hooks detached)."""
    return _make_paged_app(), _make_paged_app(), _make_paged_app()


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


def _burning_tracker(signal="ttft", short_s=0.15):
    """A tracker whose target is unmeetable on any host — every sample
    violates, so both windows burn as soon as samples exist."""
    return SLOTracker(SLOPolicy(targets={signal: 1e-9}, objective=0.9,
                                short_window_s=short_s, long_window_s=30.0))


# ---------------------------------------------------------------------------
# controller unit semantics (no device work)
# ---------------------------------------------------------------------------

class _FakeAdapter:
    def __init__(self):
        self.spec_shed = False
        self.ragged_shed = False

    def set_speculation_shed(self, shed):
        self.spec_shed = bool(shed)

    def set_ragged_shed(self, shed):
        self.ragged_shed = bool(shed)


def _fake_engine(tracker):
    return SimpleNamespace(slo=tracker, adapter=_FakeAdapter(),
                           queue=MultiTenantQueue())


def test_controller_hysteresis_enter_hold_exit():
    """Enter on both-windows burn >= enter_burn; exit only once the burn
    falls below exit_burn AND min_hold_s elapsed — oscillation around
    one threshold cannot flap the actuator."""
    tracker = SLOTracker(SLOPolicy(targets={"tpot": 0.01}, objective=0.9,
                                   short_window_s=1.0, long_window_s=10.0))
    eng = _fake_engine(tracker)
    ctl = DegradationController(enter_burn=2.0, exit_burn=1.0,
                                min_hold_s=5.0)
    t = 100.0
    for i in range(4):                     # every sample violates: burn 10
        tracker.observe("tA", "tpot", 1.0, now=t + i * 0.01)
    ctl.update(eng, now=t + 0.5)
    assert ctl.is_active("shed_speculation", "tA")
    assert eng.adapter.spec_shed
    assert ctl.stats["enters"] == 1
    # burn gone (short window empties) but the hold is not over: held
    ctl.update(eng, now=t + 2.0)
    assert ctl.is_active("shed_speculation", "tA")
    assert eng.adapter.spec_shed
    # hold elapsed and burn still below exit: released
    ctl.update(eng, now=t + 6.0)
    assert not ctl.degraded and not eng.adapter.spec_shed
    assert ctl.stats["exits"] == 1
    # state() is JSON-able and reflects emptiness
    assert json.dumps(ctl.state())
    assert ctl.state()["active"] == []


def test_controller_tighten_admission_scales_and_restores():
    tracker = SLOTracker(SLOPolicy(targets={"queue_wait": 0.01},
                                   objective=0.9, short_window_s=1.0,
                                   long_window_s=10.0))
    eng = _fake_engine(tracker)
    eng.queue = MultiTenantQueue({"bulk": 2.0})
    ctl = DegradationController(enter_burn=2.0, exit_burn=1.0,
                                min_hold_s=0.0, admission_scale=0.25)
    t = 50.0
    for i in range(3):
        tracker.observe("bulk", "queue_wait", 1.0, now=t + i * 0.01)
    ctl.update(eng, now=t + 0.1)
    assert ctl.is_active("tighten_admission", "bulk")
    assert eng.queue.weight_of("bulk") == pytest.approx(0.5)  # 2.0 * 0.25
    # an OPERATOR-set scale on another tenant survives the reconcile
    eng.queue.set_weight_scale("ops", 0.5)
    ctl.update(eng, now=t + 0.2)
    assert eng.queue.weight_of("ops") == pytest.approx(0.5)
    ctl.update(eng, now=t + 3.0)           # short window drained
    assert not ctl.degraded
    assert eng.queue.weight_of("bulk") == pytest.approx(2.0)  # exact restore
    assert eng.queue.weight_of("ops") == pytest.approx(0.5)   # untouched
    eng.queue.set_weight_scale("ops", 1.0)
    # speculation untouched by an admission-side action
    assert not eng.adapter.spec_shed


def test_controller_and_queue_validation():
    with pytest.raises(ConfigurationError):
        DegradationController(enter_burn=2.0, exit_burn=2.0)  # would flap
    with pytest.raises(ConfigurationError):
        DegradationController(admission_scale=0.0)
    with pytest.raises(ConfigurationError):
        DegradationController(min_hold_s=-1.0)
    q = MultiTenantQueue()
    with pytest.raises(ConfigurationError):
        q.set_weight_scale("t", 0.0)
    q.set_weight_scale("t", 0.5)
    assert q.weight_of("t") == pytest.approx(0.5)
    q.set_weight_scale("t", 1.0)
    assert not q._weight_scale                 # overlay fully removed


def test_engine_requires_slo_for_degradation(apps):
    app, _, _ = apps
    with pytest.raises(ConfigurationError):
        ServingEngine(PagedEngineAdapter(app),
                      degradation=DegradationController())
    # a DEFAULTED enter threshold that lands at or below exit_burn is
    # rejected at construction, not discovered as per-pass flapping
    low = SLOTracker(SLOPolicy(targets={"ttft": 1.0}, burn_threshold=1.0))
    with pytest.raises(ConfigurationError):
        ServingEngine(PagedEngineAdapter(app), slo=low,
                      degradation=DegradationController())   # exit_burn 1.0


def test_draining_replica_keeps_quarantine_threshold(apps):
    """A draining replica gets the same quarantine_after grace as a
    healthy one — one transient retry-safe failure while its queued
    work finishes must not park it in backing_off."""
    app_a, _, _ = apps
    eng_a = ServingEngine(PagedEngineAdapter(app_a), starvation_bound_s=1e9)
    router = EngineRouter({"A": eng_a}, quarantine_after=3,
                          backoff_base_s=0.01)
    router.drain("A")
    rep = router.replicas["A"]
    now = time.perf_counter()
    router._quarantine(rep, now)
    router._quarantine(rep, now)
    assert rep.state == "draining" and rep.failures == 2
    router._quarantine(rep, now)           # threshold reached
    assert rep.state == BACKING_OFF and rep.was_draining
    eng_a.close()


# ---------------------------------------------------------------------------
# closed-loop degradation on the live engine (bit-identity pinned)
# ---------------------------------------------------------------------------

def test_degradation_sheds_speculation_bit_identical(apps, ref_app):
    """Under a deliberately burning TTFT target the controller sheds
    speculation mid-serve (draft dispatches stop), every stream stays
    bit-identical to the never-degraded greedy run, and the hysteresis
    exit restores drafting — enter/exit events + gauge observed."""
    from neuronx_distributed_inference_tpu import telemetry
    from neuronx_distributed_inference_tpu.telemetry import trace as trace_mod

    for name in ("degrade.enter", "degrade.exit", "fleet.all_dead"):
        assert name in trace_mod.EVENT_NAMES
    app, _, _ = apps
    adapter = PagedEngineAdapter(app, speculation=2)
    # warm the spec width-ladder graphs first: a cold compile (~1s/pass)
    # would outlive the short burn window and make pass timing, not the
    # controller, decide the test
    warm = ServingEngine(adapter, starvation_bound_s=1e9)
    for p in _prompts(80, 3):
        warm.submit(p, 6, tenant="w")
    warm.submit(_prompts(79, 1)[0], 1, tenant="w")   # width-1 verify graph
    warm.run_until_drained()
    # a LONG hold while serving: a stray slow pass (host hiccup) must
    # not flap the action mid-test; the exit phase relaxes it
    ctl = DegradationController(min_hold_s=60.0)
    eng = ServingEngine(adapter, starvation_bound_s=1e9,
                        slo=_burning_tracker("ttft"), degradation=ctl)
    reg = telemetry.enable()
    rec = telemetry.enable_recorder()
    try:
        rec.clear()
        prompts = _prompts(81, 3)
        streams = [eng.submit(p, 6, tenant="t") for p in prompts]
        eng.run_until_drained()
        assert ctl.is_active("shed_speculation", "t")
        assert adapter.speculation_shed
        assert eng.debug_state()["degradation"]["degraded"]
        for p, s in zip(prompts, streams):
            assert s.finish_reason == "length"
            assert s.tokens == _golden(ref_app, p, 6)
        # while shed: zero draft dispatches for a whole new request
        d0 = adapter.host_stats["spec_draft_dispatches"]
        p2 = _prompts(82, 1)[0]
        s2 = eng.submit(p2, 5, tenant="t")
        eng.run_until_drained()
        assert s2.tokens == _golden(ref_app, p2, 5)
        assert adapter.host_stats["spec_draft_dispatches"] == d0
        # hysteresis exit: the short window drains, the controller
        # releases the action and drafting resumes (hold relaxed so the
        # exit is driven by the burn falling, not by wall-clock waiting)
        ctl.min_hold_s = 0.0
        time.sleep(0.2)
        eng.run_pass()
        assert not ctl.degraded and not adapter.speculation_shed
        p3 = _prompts(83, 1)[0]
        s3 = eng.submit(p3, 5, tenant="t")
        eng.run_until_drained()
        assert s3.tokens == _golden(ref_app, p3, 5)
        assert adapter.host_stats["spec_draft_dispatches"] > d0
        names = [e["name"] for e in rec.events()]
        assert "degrade.enter" in names and "degrade.exit" in names
        enter = next(e for e in rec.events()
                     if e["name"] == "degrade.enter")
        assert enter["args"]["action"] == "shed_speculation"
        assert enter["args"]["tenant"] == "t"
        assert enter["args"]["burn"] >= 2.0
        text = reg.render_prometheus()
        assert 'nxdi_degraded{tenant="t",action="shed_speculation"}' in text
    finally:
        telemetry.disable_recorder()
        telemetry.disable()
    assert not app.kv_mgr.tables


def test_degradation_drops_ragged_to_two_phase(apps, ref_app):
    """With drop_ragged opted in, decode-side burn drops the unified
    dispatch back to the two-phase path — ragged dispatches stop, the
    streams stay bit-identical, and chunked prefill still works."""
    app, _, _ = apps
    adapter = PagedEngineAdapter(app, ragged=True)
    warm = ServingEngine(adapter, starvation_bound_s=1e9)   # compile warmup
    for p in _prompts(84, 2, length=17):
        warm.submit(p, 5, tenant="w")
    warm.run_until_drained()
    ctl = DegradationController(min_hold_s=60.0, drop_ragged=True)
    eng = ServingEngine(adapter, starvation_bound_s=1e9,
                        slo=_burning_tracker("ttft"), degradation=ctl)
    prompts = _prompts(85, 2, length=17)       # 2 chunks: 16 + 1
    streams = [eng.submit(p, 5, tenant="t") for p in prompts]
    eng.run_until_drained()
    assert ctl.is_active("drop_ragged", "t")
    assert adapter.ragged_shed and adapter.speculation_shed
    for p, s in zip(prompts, streams):
        assert s.tokens == _golden(ref_app, p, 5)
    rd0 = adapter.host_stats["ragged_dispatches"]
    assert rd0 >= 1                            # ragged ran before the shed
    p2 = _prompts(86, 1, length=17)[0]
    s2 = eng.submit(p2, 5, tenant="t")
    eng.run_until_drained()
    assert s2.tokens == _golden(ref_app, p2, 5)
    assert adapter.host_stats["ragged_dispatches"] == rd0  # two-phase now
    assert not app.kv_mgr.tables


# ---------------------------------------------------------------------------
# replica health state machine
# ---------------------------------------------------------------------------

def test_replica_quarantine_probe_readmit(apps, ref_app):
    """A replica absorbing retry-safe step failures is quarantined
    (backing_off), probed after its jittered backoff, and re-admitted by
    a clean probing pass — no operator undrain(); its stream finishes
    bit-identical to the golden."""
    app_a, app_b, _ = apps
    eng_a = ServingEngine(PagedEngineAdapter(app_a), starvation_bound_s=1e9)
    eng_b = ServingEngine(PagedEngineAdapter(app_b), starvation_bound_s=1e9)
    router = EngineRouter({"A": eng_a, "B": eng_b},
                          quarantine_after=1, backoff_base_s=0.01,
                          backoff_max_s=0.05, max_replica_failures=6,
                          seed=3)
    p = _prompts(91, 1)[0]
    s = router.submit(p, 6)                    # idle fleet: name order -> A
    assert router._requests[s.request_id].replica == "A"
    while s.n_tokens < 2:
        router.run_pass()
    # the next TWO decode dispatches fail retry-safe (injected): pass 1
    # quarantines A, the probe pass hits the second trip and escalates
    # the backoff, the following probe is clean and re-admits
    with FAULTS.inject("decode_step", nth=1, times=2) as fp:
        router.run_pass()
        assert fp.trips == 1
        assert router.replicas["A"].state == BACKING_OFF
        assert router.stats["quarantines"] == 1
        deadline = time.perf_counter() + 5.0
        while router.replicas["A"].state != HEALTHY:
            router.run_pass()
            if time.perf_counter() > deadline:
                pytest.fail(f"probation never re-admitted A "
                            f"(state={router.replicas['A'].state})")
            time.sleep(0.002)
        assert fp.trips == 2                   # the failed probe consumed it
    assert router.stats["probes"] >= 1
    assert router.stats["probe_readmits"] == 1
    assert router.stats["quarantines"] == 2    # initial + failed probe
    assert router.replicas["A"].failures == 0  # streak reset on re-admit
    router.run_until_drained()
    assert s.finish_reason == "length"
    assert s.tokens == _golden(ref_app, p, 6)  # bit-identical throughout
    assert router.stats["replica_failures"] == 0   # never died
    assert not app_a.kv_mgr.tables and not app_b.kv_mgr.tables
    eng_a.close(), eng_b.close()


def test_replica_retry_exhaustion_escalates_dead_failover(apps, ref_app):
    """Retry-safe failures that never stop escalate the replica to dead
    after max_replica_failures; its in-flight request is cancelled on
    the (still live) engine and requeued onto the survivor — the
    stitched stream stays bit-identical."""
    app_a, app_b, _ = apps
    eng_a = ServingEngine(PagedEngineAdapter(app_a), starvation_bound_s=1e9)
    eng_b = ServingEngine(PagedEngineAdapter(app_b), starvation_bound_s=1e9)
    router = EngineRouter({"A": eng_a, "B": eng_b},
                          quarantine_after=1, backoff_base_s=0.005,
                          backoff_max_s=0.02, max_replica_failures=2,
                          seed=4)
    p = _prompts(93, 1)[0]
    s = router.submit(p, 6)
    assert router._requests[s.request_id].replica == "A"
    while s.n_tokens < 2:
        router.run_pass()
    with FAULTS.inject("decode_step", nth=1, times=99):
        deadline = time.perf_counter() + 5.0
        while router.replicas["A"].state != DEAD:
            router.run_pass()
            if time.perf_counter() > deadline:
                pytest.fail("retry exhaustion never escalated A to dead")
            time.sleep(0.002)
        # A's engine is alive (every failure was retry-safe): the
        # router reclaimed the in-flight request via cancel, so A holds
        # no device state for it
        assert not eng_a.closed
        assert not app_a.kv_mgr.tables
    # disarm BEFORE draining: the armed point would hit the survivor too
    router.run_until_drained()
    assert router.stats["requeues"] == 1
    assert router._requests == {}
    assert s.finish_reason == "length"
    assert s.tokens == _golden(ref_app, p, 6)  # stitched, bit-identical
    assert not app_b.kv_mgr.tables
    eng_a.close(), eng_b.close()


def test_all_dead_event_and_unavailable_depth(apps):
    """Losing the LAST healthy replica records fleet.all_dead with the
    stranded in-flight count, and ReplicaUnavailable surfaces the
    per-state census + pending depth instead of a bare shed."""
    from neuronx_distributed_inference_tpu import telemetry
    app_a, _, _ = apps
    eng_a = ServingEngine(PagedEngineAdapter(app_a), starvation_bound_s=1e9)
    router = EngineRouter({"A": eng_a})
    rec = telemetry.enable_recorder()
    try:
        rec.clear()
        s = router.submit(_prompts(95, 1)[0], 8)
        router.run_pass()
        assert s.n_tokens >= 1
        eng_a.close()                          # external shutdown
        router.run_pass()                      # notices + fails over (none)
        assert router.replicas["A"].state == DEAD
        ev = next(e for e in rec.events() if e["name"] == "fleet.all_dead")
        assert ev["args"]["in_flight"] == 1
        with pytest.raises(ReplicaUnavailable) as ei:
            router.submit([1, 2, 3], 2)
        msg = str(ei.value)
        assert "dead=1" in msg and "in-flight" in msg
    finally:
        telemetry.disable_recorder()
    for sid in list(app_a.kv_mgr.tables):      # closed engine leftovers
        app_a.kv_mgr.end_sequence(sid)


# ---------------------------------------------------------------------------
# run_forever: unexpected exceptions die typed, with a post-mortem
# ---------------------------------------------------------------------------

def test_run_forever_unexpected_exception_postmortem(apps, tmp_path):
    """A non-ServingError escaping a pass (an engine bug) must not kill
    run_forever bare: the post-mortem is dumped, every stream finishes
    typed ("error"), and the raised wrapper is an unrecoverable
    StepFailure chaining the original."""
    app, _, _ = apps
    adapter = PagedEngineAdapter(app)
    eng = ServingEngine(adapter, starvation_bound_s=1e9,
                        debug_dump_dir=str(tmp_path))
    s = eng.submit(_prompts(97, 1)[0], 4)

    def boom(*a, **k):
        raise KeyError("engine bug")

    adapter.step = adapter.step_ahead = boom    # the engine's decode call

    async def main():
        with pytest.raises(StepFailure) as ei:
            await eng.run_forever()
        return ei.value

    err = asyncio.run(main())
    assert err.retry_safe is False and err.phase == "engine"
    assert isinstance(err.__cause__, KeyError)
    assert eng.closed
    assert s.finished and s.finish_reason == "error"
    assert isinstance(s.error, StepFailure)
    dumps = list(tmp_path.glob("nxdi_postmortem_*.json"))
    assert len(dumps) == 1
    dump = json.loads(dumps[0].read_text())
    assert dump["schema"] == "nxdi-debug-state-v1"
    assert dump["error"]["type"] == "StepFailure"
    assert dump["error"]["retry_safe"] is False
    for sid in list(app.kv_mgr.tables):        # fatal teardown leftovers
        app.kv_mgr.end_sequence(sid)
    # an unexpected TYPED error (an engine bug surfacing as e.g.
    # SequenceStateError — never a legitimate run_pass escape) gets the
    # SAME teardown, not a bare re-raise with streams left hanging
    from neuronx_distributed_inference_tpu.resilience import \
        SequenceStateError
    adapter2 = PagedEngineAdapter(app)
    eng2 = ServingEngine(adapter2, starvation_bound_s=1e9)
    s2 = eng2.submit(_prompts(98, 1)[0], 4)

    def typed_boom(*a, **k):
        raise SequenceStateError("engine bug")

    adapter2.step = adapter2.step_ahead = typed_boom

    async def main2():
        with pytest.raises(StepFailure) as ei:
            await eng2.run_forever()
        return ei.value

    err2 = asyncio.run(main2())
    assert isinstance(err2.__cause__, SequenceStateError)
    assert eng2.closed
    assert s2.finished and s2.finish_reason == "error"
    for sid in list(app.kv_mgr.tables):
        app.kv_mgr.end_sequence(sid)


def test_flush_path_step_failure_is_fatal_typed(apps):
    """A deferred-fetch failure surfacing on the NO-ELIGIBLE-ROWS branch
    (every row backpressured, adapter.flush() raises) runs the same
    fatal teardown as the dispatch branch: engine closed, streams
    finish typed — so run_forever's 'a StepFailure raise site ran
    _fatal first' invariant holds on every path."""
    app, _, _ = apps
    adapter = PagedEngineAdapter(app)
    eng = ServingEngine(adapter, starvation_bound_s=1e9,
                        max_unread_tokens=2)
    s = eng.submit(_prompts(99, 1)[0], 8)
    eng.run_pass()                 # admit (token 1) + dispatch in flight
    # token 2 is in flight and counts as unread: the consumer is behind,
    # the row is ineligible, and the pass takes the flush() branch
    assert s.unread == 1 and adapter.lookahead_ids == {0}
    assert adapter._inflight is not None
    with FAULTS.inject("pipeline_flush") as fp:
        with pytest.raises(StepFailure) as ei:
            eng.run_pass()         # flush() path, deferred fetch fails
    assert fp.trips == 1
    assert ei.value.retry_safe is False
    assert eng.closed
    assert s.finished and s.finish_reason == "error"
    for sid in list(app.kv_mgr.tables):
        app.kv_mgr.end_sequence(sid)


# ---------------------------------------------------------------------------
# fault-points lint: green live, red on doctored copies both directions
# ---------------------------------------------------------------------------

def test_fault_points_lint_green_and_rename_red(tmp_path):
    from conftest import load_nxdi_lint
    nxdi_lint = load_nxdi_lint()
    out = tmp_path / "lint.json"
    assert nxdi_lint.main(["--passes", "fault-points", "--json",
                           str(out)]) == 0
    data = json.loads(out.read_text())
    assert data["findings"] == []
    covered = set(data["files"])
    assert ("neuronx_distributed_inference_tpu/resilience/faults.py"
            in covered)
    assert ("neuronx_distributed_inference_tpu/serving/adapter.py"
            in covered)

    analysis = nxdi_lint.load_analysis()
    fp_pass = analysis.get_pass("fault-points")
    faults_src = (REPO / "neuronx_distributed_inference_tpu/resilience/"
                  "faults.py").read_text()
    # doctored registry: one real point renamed -> the unchanged call
    # sites are unknown-name findings AND the renamed point is orphaned
    doctored = tmp_path / "faults.py"
    doctored.write_text(faults_src.replace('"decode_step"',
                                           '"decode_step_renamed"'))
    fire_all = tmp_path / "firing.py"
    fire_all.write_text(textwrap.dedent("""\
        from resilience.faults import FAULTS as _FAULTS
        def run():
            _FAULTS.fire("decode_step")
            _FAULTS.fire("paged_alloc")
            _FAULTS.fire("prefill_step")
            _FAULTS.fire("prefill_chunk")
            _FAULTS.fire("slow_step")
            _FAULTS.fire("pipeline_flush")
            _FAULTS.fire("spec_draft")
            _FAULTS.fire("spec_verify")
            _FAULTS.fire("ragged_step")
            _FAULTS.fire("kv_spill")
            _FAULTS.fire("kv_restore")
            _FAULTS.fire("handoff")
            _FAULTS.fire("migrate_capture")
            _FAULTS.fire("migrate_admit")
            _FAULTS.fire("autoscale")
            _FAULTS.fire("adapter_swap")
            _FAULTS.fire("adapter_spill")
        """))
    ctx = analysis.LintContext(tmp_path)
    findings = fp_pass.run(ctx, paths=[str(doctored), str(fire_all)])
    msgs = [f.message for f in findings]
    assert any("'decode_step'" in m and "not a registered" in m
               for m in msgs), msgs
    assert any("'decode_step_renamed'" in m and "no" in m
               for m in msgs), msgs
    # a green doctored pair: registry + full call-site coverage
    clean = tmp_path / "faults_clean.py"
    clean.write_text(faults_src)
    ctx2 = analysis.LintContext(tmp_path)
    assert fp_pass.run(ctx2, paths=[str(clean), str(fire_all)]) == []
    # a non-literal fire is a finding (it dodges both checks)
    dyn = tmp_path / "dynamic.py"
    dyn.write_text("def f(FAULTS, p):\n    FAULTS.fire(p)\n")
    ctx3 = analysis.LintContext(tmp_path)
    dyn_findings = fp_pass.run(ctx3, paths=[str(clean), str(fire_all),
                                            str(dyn)])
    assert any("non-literal" in f.message for f in dyn_findings)


def test_lints_cover_resilience_files(tmp_path):
    """controller.py + chaos.py ride error-paths and host-sync with
    zero findings and zero suppressions."""
    from conftest import load_nxdi_lint
    nxdi_lint = load_nxdi_lint()
    out = tmp_path / "lint.json"
    assert nxdi_lint.main(
        ["--passes", "error-paths,host-sync,metric-names,fault-points",
         "--json", str(out)]) == 0
    data = json.loads(out.read_text())
    assert data["findings"] == [] and data["suppressed"] == []
    covered = set(data["files"])
    for rel in ("neuronx_distributed_inference_tpu/resilience/"
                "controller.py",
                "neuronx_distributed_inference_tpu/resilience/chaos.py"):
        assert rel in covered, f"{rel} dropped from lint coverage"


# ---------------------------------------------------------------------------
# chaos campaign: seeded smoke (tier-1) + red-verified harness
# ---------------------------------------------------------------------------

def test_chaos_smoke_seeded_subset(apps):
    """One seed, a seeded random subset of the fault x schedule matrix
    against the full mixed workload — every invariant green."""
    campaign = ChaosCampaign(list(apps), seed=0)
    cells = campaign.sample_cells(3)
    report = campaign.run(cells)
    assert report["schema"] == "nxdi-chaos-v1"
    assert report["golden"]["streams"] == 8     # handoff + 6 engine + lora
    assert report["golden"]["bad"] == []
    for row in report["cells"]:
        assert row["ok"], row
        assert row["trips"] >= 1
    assert report["ok"]
    for app in apps:                            # campaign left no state
        assert not app.kv_mgr.tables


def test_chaos_migration_and_autoscale_cells(apps):
    """The ISSUE-17 cells, explicitly: killing a replica mid-migration at
    BOTH migration fault points x BOTH schedules (and aborting the
    autoscaler evaluation) heals with zero lost streams — every stream
    bit-identical to its golden, free pools exact, the armed point
    actually fired."""
    campaign = ChaosCampaign(list(apps), seed=0)
    cells = default_cells(points=["migrate_capture", "migrate_admit",
                                  "autoscale"])
    assert len(cells) == 6                      # 3 points x 2 schedules
    report = campaign.run(cells)
    for row in report["cells"]:
        assert row["ok"], row
        assert row["trips"] >= 1                # the armed point fired
        assert row["checks"]["free_pool_exact"], row
        assert row["checks"]["streams_bit_identical"], row
    assert report["ok"]
    # the migration legs genuinely ran in every cell (not vacuous)
    assert all(row["migrations"] >= 1 for row in report["cells"])
    for app in apps:                            # campaign left no state
        assert not app.kv_mgr.tables


def test_chaos_red_on_doctored_invariant(apps):
    """The harness itself is verified red: a cell hook that deliberately
    leaks a block (an un-ended sequence) must fail the free-pool
    invariant and turn the campaign red."""
    app0 = apps[0]

    def leak(campaign, point):
        app0.kv_mgr.begin_sequence(31337, list(range(1, 18)))

    campaign = ChaosCampaign(list(apps), seed=0, cell_hook=leak)
    try:
        report = campaign.run([default_cells()[0]])   # one cell suffices
        assert not report["ok"]
        row = report["cells"][0]
        assert not row["ok"]
        assert row["checks"]["free_pool_exact"] is False
    finally:
        if 31337 in app0.kv_mgr.tables:
            app0.kv_mgr.end_sequence(31337)
    assert not app0.kv_mgr.tables
