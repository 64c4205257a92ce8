"""Elastic fleet (ISSUE 17): live decode→decode migration (bit-identical
vs an undisturbed single-engine golden, including spill-tier-resident
prefixes and speculative-proposer sequences; migrate_capture /
migrate_admit failures leave BOTH engines unchanged), migrate-mode
drain + rebalance, drain-while-quarantined, the closed-loop
FleetAutoscaler (hysteresis, precompile-before-healthy, two-phase
retirement — virtual clock + fake engines, no device work), the seeded
load generators, and the dead-replica report stubs — on the tiny
synthetic model shared with test_fleet (same shapes, warm graphs;
CPU, <20s)."""

import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from neuronx_distributed_inference_tpu import telemetry
from neuronx_distributed_inference_tpu.config import TpuConfig
from neuronx_distributed_inference_tpu.models.application import (
    CausalLMApplication, PagedCausalLMApplication)
from neuronx_distributed_inference_tpu.models.llama import (
    LlamaFamily, LlamaInferenceConfig)
from neuronx_distributed_inference_tpu.resilience import (
    ConfigurationError, FAULTS, HandoffError)
from neuronx_distributed_inference_tpu.resilience.faults import FAULT_POINTS
from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
from neuronx_distributed_inference_tpu.serving.engine import ServingEngine
from neuronx_distributed_inference_tpu.serving.fleet import (
    BACKING_OFF, DEAD, DRAINING, HEALTHY, PROBATION, EngineRouter,
    FleetAutoscaler, HostKVSpillTier, migrate)
from neuronx_distributed_inference_tpu.telemetry import (
    metrics as tmetrics)
from neuronx_distributed_inference_tpu.telemetry import trace as trace_mod

REPO = Path(__file__).resolve().parent.parent

HF = dict(model_type="llama", hidden_size=64, intermediate_size=128,
          num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, vocab_size=512, rms_norm_eps=1e-5, rope_theta=10000.0,
          hidden_act="silu", tie_word_embeddings=False,
          torch_dtype="float32")


def _make_paged_app():
    """Same shapes as test_fleet (warm graphs); seed 7 so every replica
    and the single-engine golden share one set of weights."""
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
    """Two same-weights paged apps: migration source and destination.
    Tests build fresh adapters/engines over them and must leave every
    app clean (no tables, spill hooks detached)."""
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
    """Drain the prefix cache's LRU through the spill hook with one
    pool-sized cold admission (same idiom as test_fleet)."""
    mgr = app.kv_mgr
    usable = mgr.spec.num_blocks - 1
    rng = np.random.default_rng(seed)
    cold = rng.integers(600, 5000, size=usable * mgr.spec.block_size)
    mgr.begin_sequence(999, cold.tolist())
    mgr.abort_sequence(999)
    assert not getattr(mgr.allocator, "_lru", []), "LRU not drained"


def _detach_spill_hook(app):
    if hasattr(app.kv_mgr.allocator, "on_evict"):
        app.kv_mgr.allocator.on_evict = None


def _fleet(apps, *, tiers=(True, True), speculation=(None, None), **kw):
    """Two-replica router over the module apps; returns
    (router, engines, adapters)."""
    engines, adapters = [], []
    for app, tier, spec in zip(apps, tiers, speculation):
        ad = PagedEngineAdapter(
            app, speculation=spec,
            kv_spill_tier=HostKVSpillTier(max_blocks=64) if tier else None)
        adapters.append(ad)
        engines.append(ServingEngine(ad, starvation_bound_s=1e9))
    router = EngineRouter({"A": engines[0], "B": engines[1]}, **kw)
    return router, engines, adapters


def _decode_until(router, stream, n):
    while stream.n_tokens < n and not stream.finished:
        router.run_pass()


# ---------------------------------------------------------------------------
# registration contracts (no device work)
# ---------------------------------------------------------------------------

def test_fault_points_and_events_registered():
    """The three new fault points are registered (so the fault-points
    lint covers their fire() sites) and the autoscaler's actions are
    stable flight-recorder event names."""
    for point in ("migrate_capture", "migrate_admit", "autoscale"):
        assert point in FAULT_POINTS
    for name in ("fleet.scale_up", "fleet.scale_down",
                 "handoff.send", "handoff.recv", "trace.requeue"):
        assert name in trace_mod.EVENT_NAMES


def test_lints_cover_elastic_files(tmp_path):
    """error-paths + host-sync cover the autoscaler with zero findings
    and zero suppressions."""
    import json
    from conftest import load_nxdi_lint
    nxdi_lint = load_nxdi_lint()
    out = tmp_path / "lint.json"
    assert nxdi_lint.main(
        ["--passes", "error-paths,host-sync", "--json", str(out)]) == 0
    data = json.loads(out.read_text())
    assert data["findings"] == [] and data["suppressed"] == []
    assert ("neuronx_distributed_inference_tpu/serving/fleet/autoscaler.py"
            in data["files"])


def test_autoscaler_construction_validation():
    """Mis-shaped hysteresis knobs fail at construction (same discipline
    as the degradation controller's check_policy), not at 3am."""
    ok = lambda **kw: FleetAutoscaler(lambda: None, **kw)  # noqa: E731
    ok()                                                   # defaults valid
    with pytest.raises(ConfigurationError):
        FleetAutoscaler("not-callable")
    with pytest.raises(ConfigurationError):
        ok(min_replicas=0)
    with pytest.raises(ConfigurationError):
        ok(min_replicas=3, max_replicas=2)
    with pytest.raises(ConfigurationError):
        ok(queue_enter=4.0, queue_exit=4.0)     # no dead band
    with pytest.raises(ConfigurationError):
        ok(burn_enter=1.0, burn_exit=1.5)
    with pytest.raises(ConfigurationError):
        ok(headroom_enter_slots=2, headroom_exit_slots=2)
    with pytest.raises(ConfigurationError):
        ok(min_hold_s=-1.0)
    with pytest.raises(ConfigurationError):
        ok(cooldown_s=-0.1)
    # the router validates the autoscaler surface too
    with pytest.raises(ConfigurationError):
        EngineRouter({"A": SimpleNamespace(run_pass=lambda: 0,
                                           adapter=None)},
                     autoscaler=object())


# ---------------------------------------------------------------------------
# autoscaler closed loop (virtual clock + fake engines, no device work)
# ---------------------------------------------------------------------------

class _FakeEngine:
    """The minimal engine surface the router + autoscaler read."""

    def __init__(self, queue=0.0, free_slots=4):
        self.closed = False
        self.has_work = False
        self.load = (queue, 0)
        self.adapter = SimpleNamespace(app=None, free_capacity=free_slots)
        self.slo = None

    def run_pass(self):
        return 0

    def close(self):
        self.closed = True

    def set_pressure(self, queue, free_slots):
        self.load = (queue, 0)
        self.adapter.free_capacity = free_slots


def test_autoscaler_full_cycle_hysteresis(monkeypatch):
    """The whole closed loop on a virtual clock: hot must HOLD
    min_hold_s before scale-up, the spawned replica joins only with
    n_compiles == 0, cooldown blocks the next action, calm must hold
    before the two-phase scale-down (migrate-drain then reap), and the
    replica-state gauge tracks it all."""
    from neuronx_distributed_inference_tpu.serving import warmup
    monkeypatch.setattr(warmup, "precompile",
                        lambda app, registry=None: {"n_compiles": 0})
    clock = [0.0]
    seed = _FakeEngine()
    spawned = []

    def factory():
        eng = _FakeEngine()
        spawned.append(eng)
        return eng

    auto = FleetAutoscaler(factory, min_replicas=1, max_replicas=2,
                           queue_enter=4.0, queue_exit=1.0,
                           burn_enter=1.0, burn_exit=0.25,
                           headroom_enter_slots=0, headroom_exit_slots=2,
                           min_hold_s=1.0, cooldown_s=5.0,
                           now_fn=lambda: clock[0])
    router = EngineRouter({"r0": seed}, autoscaler=auto)
    reg = telemetry.enable()
    rec = telemetry.enable_recorder()
    try:
        gauge = tmetrics.fleet_replicas_gauge(reg)
        seed.set_pressure(queue=10.0, free_slots=0)    # hot
        assert auto.update(router) is None             # hold not yet met
        assert auto.stats["evaluations"] == 1
        clock[0] = 1.0
        assert auto.update(router) == "scale_up"       # held 1.0s
        assert "auto0" in router.replicas
        assert router.replicas["auto0"].state == HEALTHY
        assert auto.stats["scale_ups"] == 1
        assert gauge.get(state=HEALTHY) == 2
        up = next(e for e in rec.events()
                  if e["name"] == "fleet.scale_up")
        assert up["args"]["replica"] == "auto0"
        assert up["args"]["n_compiles"] == 0
        clock[0] = 1.5
        seed.set_pressure(queue=10.0, free_slots=0)    # still hot
        assert auto.update(router) is None             # cooldown holds
        # pressure gone: both replicas calm
        seed.set_pressure(queue=0.0, free_slots=4)
        clock[0] = 6.5                                 # cooldown over
        assert auto.update(router) is None             # calm hold starts
        clock[0] = 7.5
        assert auto.update(router) == "scale_down"     # calm held 1.0s
        assert router.replicas["auto0"].state == DRAINING
        assert auto.stats["scale_downs"] == 1
        down = next(e for e in rec.events()
                    if e["name"] == "fleet.scale_down")
        assert down["args"]["replica"] == "auto0"      # self-spawned first
        # opposite actions are >= cooldown_s apart (no flapping)
        acts = [h for h in auto.history
                if h["action"] in ("scale_up", "scale_down")]
        assert acts[1]["t"] - acts[0]["t"] >= auto.cooldown_s
        clock[0] = 13.0                                # quiesced: reap
        auto.update(router)
        assert "auto0" not in router.replicas
        assert auto.stats["reaped"] == 1
        assert spawned[0].closed                       # self-spawned: closed
        assert gauge.get(state=HEALTHY) == 1
        # never below min_replicas: calm forever, nothing to retire
        clock[0] = 30.0
        assert auto.update(router) is None
        assert auto.stats["scale_downs"] == 1
    finally:
        telemetry.disable_recorder()
        telemetry.disable()


def test_autoscaler_rejects_cold_replica_and_fault_aborts(monkeypatch):
    """Precompile-before-healthy: a spawn that would compile under
    traffic is closed and rejected, never added; an injected autoscale
    fault aborts the evaluation with the fleet unchanged."""
    from neuronx_distributed_inference_tpu.serving import warmup
    monkeypatch.setattr(warmup, "precompile",
                        lambda app, registry=None: {"n_compiles": 3})
    clock = [0.0]
    seed = _FakeEngine(queue=10.0, free_slots=0)       # permanently hot
    cold = []
    auto = FleetAutoscaler(lambda: cold.append(_FakeEngine()) or cold[-1],
                           min_replicas=1, max_replicas=2,
                           queue_enter=4.0, queue_exit=1.0,
                           min_hold_s=0.0, cooldown_s=1.0,
                           now_fn=lambda: clock[0])
    router = EngineRouter({"r0": seed}, autoscaler=auto)
    assert auto.update(router) is None                 # rejected: cold
    assert auto.stats["rejected_cold"] == 1
    assert list(router.replicas) == ["r0"]
    assert cold[0].closed                              # rejected AND closed
    assert auto.history[-1]["action"] == "reject_cold"
    with FAULTS.inject("autoscale", nth=1, times=1) as fp:
        clock[0] = 10.0
        assert auto.update(router) is None
        assert fp.trips == 1
    assert auto.stats["aborted"] == 1
    assert list(router.replicas) == ["r0"]             # fleet unchanged


# ---------------------------------------------------------------------------
# live decode→decode migration (device work)
# ---------------------------------------------------------------------------

def test_migrate_bit_identical_and_validation(apps, ref_app):
    """A mid-decode stream migrated A→B continues bit-identically to an
    undisturbed single-engine golden, the KV moves (counted), both pools
    come back exact, and the bad-argument paths fail typed with nothing
    changed."""
    app_a, app_b = apps
    router, engines, _ = _fleet(apps)
    reg = telemetry.enable()
    try:
        p = _prompts(171, 1)[0]
        s = router.submit(p, 8)
        rid = s.request_id
        assert router._requests[rid].replica == "A"
        with pytest.raises(HandoffError):
            migrate(router, "nope")                    # unknown request
        with pytest.raises(HandoffError):
            migrate(router, rid, src="B")              # wrong source
        with pytest.raises(HandoffError):
            migrate(router, rid, dst="A")              # dst == src
        _decode_until(router, s, 3)
        dst = migrate(router, rid)                     # auto-pick: B
        assert dst == "B"
        assert router._requests[rid].replica == "B"
        assert router.stats["migrations"] == 1
        assert router.stats["migrated_kv_tokens"] > 0
        assert tmetrics.handoffs_counter(reg).get(role="migrate_send") == 1
        assert tmetrics.handoffs_counter(reg).get(role="migrate_recv") == 1
        router.run_until_drained()
        assert s.finish_reason == "length"
        assert s.tokens == _golden(ref_app, p, 8)      # bit-identical
        for eng in engines:
            eng.close()
        assert not app_a.kv_mgr.tables and not app_b.kv_mgr.tables
    finally:
        _detach_spill_hook(app_a), _detach_spill_hook(app_b)
        telemetry.disable()


def test_migrate_fault_points_leave_both_engines_unchanged(apps, ref_app):
    """An injected failure at either migration fault point is a typed
    HandoffError that leaves BOTH engines exactly as found (free pools
    to the block) — the stream keeps serving on the source and still
    finishes bit-identical."""
    app_a, app_b = apps
    router, engines, _ = _fleet(apps)
    try:
        p = _prompts(173, 1)[0]
        s = router.submit(p, 8)
        rid = s.request_id
        _decode_until(router, s, 2)
        for point in ("migrate_capture", "migrate_admit"):
            free_a = app_a.kv_mgr.allocator.num_free
            free_b = app_b.kv_mgr.allocator.num_free
            tokens_before = list(s.tokens)
            with FAULTS.inject(point, nth=1, times=1) as fp:
                with pytest.raises(HandoffError):
                    migrate(router, rid, dst="B")
                assert fp.trips == 1
            assert app_a.kv_mgr.allocator.num_free == free_a
            assert app_b.kv_mgr.allocator.num_free == free_b
            assert router._requests[rid].replica == "A"
            assert list(s.tokens) == tokens_before
            router.run_pass()                          # still decoding on A
            assert s.n_tokens > len(tokens_before)
            assert router.stats["migrations"] == 0
        router.run_until_drained()
        assert s.tokens == _golden(ref_app, p, 8)
        for eng in engines:
            eng.close()
        assert not app_a.kv_mgr.tables and not app_b.kv_mgr.tables
    finally:
        _detach_spill_hook(app_a), _detach_spill_hook(app_b)


def test_migrate_spill_resident_prefix_bit_identical(apps, ref_app):
    """Migrating a sequence whose prefix blocks were RESTORED from the
    source's spill tier at admission stays bit-identical — capture reads
    the device blocks the restore landed, and the destination re-seeds
    its own tier from the wire payload."""
    app_a, app_b = apps
    router, engines, _ = _fleet(apps)
    try:
        p = _prompts(177, 1, length=17)[0]             # 2 full blocks of 8
        router.drain("B")                              # pin warmup on A
        s0 = router.submit(p, 3)
        router.run_until_drained()
        assert s0.finished
        router.undrain("B")
        _evict_lru(app_a, seed=995)                    # prefix -> spill tier
        s = router.submit(p, 8)                        # warm affinity: A
        rid = s.request_id
        assert router._requests[rid].replica == "A"
        _decode_until(router, s, 2)
        assert migrate(router, rid) == "B"
        router.run_until_drained()
        assert s.tokens == _golden(ref_app, p, 8)      # bit-identical
        assert router.stats["migrated_kv_tokens"] >= 16
        for eng in engines:
            eng.close()
        assert not app_a.kv_mgr.tables and not app_b.kv_mgr.tables
    finally:
        _detach_spill_hook(app_a), _detach_spill_hook(app_b)


def test_migrate_speculative_sequence_bit_identical(apps, ref_app):
    """Migrating a stream served by a speculative (self-drafting) source
    replica stays bit-identical: the proposer's draft state drops with
    the source release, and the plain-decode destination continues the
    exact greedy stream."""
    app_a, app_b = apps
    router, engines, _ = _fleet(apps, speculation=(2, None))
    try:
        p = _prompts(179, 1)[0]
        s = router.submit(p, 8)
        rid = s.request_id
        assert router._requests[rid].replica == "A"
        _decode_until(router, s, 3)
        assert migrate(router, rid) == "B"
        router.run_until_drained()
        assert s.finish_reason == "length"
        assert s.tokens == _golden(ref_app, p, 8)      # bit-identical
        for eng in engines:
            eng.close()
        assert not app_a.kv_mgr.tables and not app_b.kv_mgr.tables
    finally:
        _detach_spill_hook(app_a), _detach_spill_hook(app_b)


# ---------------------------------------------------------------------------
# drain modes, rebalance, quarantine interplay, dead-replica stubs
# ---------------------------------------------------------------------------

def test_drain_migrate_mode_moves_streams(apps, ref_app):
    """drain(mode="migrate") live-migrates every bound stream off the
    replica (returning the count) instead of waiting them out; a bogus
    mode fails typed; draining a DEAD replica is a no-op returning 0."""
    app_a, app_b = apps
    router, engines, _ = _fleet(apps)
    try:
        router.drain("B")                              # pin both on A
        ps = _prompts(181, 2)
        streams = [router.submit(p, 8) for p in ps]
        for s in streams:
            _decode_until(router, s, 2)
        router.undrain("B")
        with pytest.raises(ConfigurationError):
            router.drain("A", mode="bogus")
        moved = router.drain("A", mode="migrate")
        assert moved == 2
        assert router.stats["migrate_drains"] == 1
        assert router.stats["migrations"] == 2
        assert all(router._requests[s.request_id].replica == "B"
                   for s in streams)
        router.run_until_drained()
        for p, s in zip(ps, streams):
            assert s.tokens == _golden(ref_app, p, 8)  # bit-identical
        router.undrain("A")
        engines[1].close()                             # dead drain: no-op
        router.run_pass()
        assert router.replicas["B"].state == DEAD
        assert router.drain("B", mode="migrate") == 0
        for eng in engines:
            if not eng.closed:
                eng.close()
        assert not app_a.kv_mgr.tables and not app_b.kv_mgr.tables
    finally:
        _detach_spill_hook(app_a), _detach_spill_hook(app_b)


def test_rebalance_levels_running_streams(apps, ref_app):
    """rebalance() migrates hottest→coldest until stream counts are
    within one, and is a no-op on a balanced fleet."""
    app_a, app_b = apps
    router, engines, _ = _fleet(apps)
    try:
        router.drain("B")
        ps = _prompts(183, 2)
        streams = [router.submit(p, 8) for p in ps]
        for s in streams:
            _decode_until(router, s, 2)
        router.undrain("B")                            # A:2 B:0
        with pytest.raises(ConfigurationError):
            router.rebalance(max_moves=0)
        assert router.rebalance() == 1                 # A:1 B:1 — done
        assert router.stats["rebalances"] == 1
        assert router.rebalance() == 0                 # balanced: no-op
        assert router.stats["rebalances"] == 1
        counts = {}
        for s in streams:
            counts.setdefault(router._requests[s.request_id].replica, 0)
            counts[router._requests[s.request_id].replica] += 1
        assert counts == {"A": 1, "B": 1}
        router.run_until_drained()
        for p, s in zip(ps, streams):
            assert s.tokens == _golden(ref_app, p, 8)
        for eng in engines:
            eng.close()
        assert not app_a.kv_mgr.tables and not app_b.kv_mgr.tables
    finally:
        _detach_spill_hook(app_a), _detach_spill_hook(app_b)


def test_drain_while_quarantined_lands_draining(apps, ref_app):
    """drain() on a mid-backoff replica no longer silently does nothing:
    the intent is remembered and the probe re-admission lands the
    replica in DRAINING (not HEALTHY), its stream finishing
    bit-identical throughout."""
    app_a, app_b = apps
    router, engines, _ = _fleet(apps, quarantine_after=1,
                                backoff_base_s=0.01, backoff_max_s=0.05,
                                max_replica_failures=6, seed=5)
    try:
        p = _prompts(187, 1)[0]
        s = router.submit(p, 6)
        assert router._requests[s.request_id].replica == "A"
        _decode_until(router, s, 2)
        with FAULTS.inject("decode_step", nth=1, times=1):
            router.run_pass()
        assert router.replicas["A"].state == BACKING_OFF
        drains_before = router.stats["drains"]
        assert router.drain("A") == 0                  # quarantined: no move
        assert router.replicas["A"].was_draining       # ...but remembered
        assert router.stats["drains"] == drains_before + 1
        assert router.replicas["A"].state == BACKING_OFF
        deadline = time.perf_counter() + 5.0
        while router.replicas["A"].state in (BACKING_OFF, PROBATION):
            router.run_pass()
            if time.perf_counter() > deadline:
                pytest.fail("probe never re-admitted A")
            time.sleep(0.002)
        assert router.replicas["A"].state == DRAINING  # NOT healthy
        router.run_until_drained()
        assert s.tokens == _golden(ref_app, p, 6)      # bit-identical
        router.undrain("A")
        assert router.replicas["A"].state == HEALTHY
        assert not router.replicas["A"].was_draining
        for eng in engines:
            eng.close()
        assert not app_a.kv_mgr.tables and not app_b.kv_mgr.tables
    finally:
        _detach_spill_hook(app_a), _detach_spill_hook(app_b)


def test_reports_tolerate_replica_dying_mid_enumeration(apps):
    """memory_report() and debug_state() serve a {"state": "dead"} stub
    for a replica that dies between enumeration and its report, instead
    of sinking the whole fleet endpoint."""
    app_a, app_b = apps
    router, engines, _ = _fleet(apps)
    try:
        eng_b = engines[1]
        eng_b.close()            # died under the router's feet: the
        # router still believes B is healthy until its next run_pass
        assert router.replicas["B"].state == HEALTHY
        report = router.memory_report()
        assert report["B"] == {"state": "dead"}
        assert report["A"]["model_bytes"] > 0          # A unaffected
        eng_b.debug_state = lambda: (_ for _ in ()).throw(
            RuntimeError("torn down mid-report"))
        ds = router.debug_state()
        assert ds["replicas"]["B"]["state"] == DEAD    # stubbed
        assert ds["replicas"]["A"]["state"] == HEALTHY
        assert "queue_depth" in ds["replicas"]["A"]
        engines[0].close()
        assert not app_a.kv_mgr.tables and not app_b.kv_mgr.tables
    finally:
        _detach_spill_hook(app_a), _detach_spill_hook(app_b)
