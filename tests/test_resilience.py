"""Serving resilience layer: typed failure hierarchy, transactional
admission, recompute preemption under KV pressure, per-request budgets,
and the deterministic fault-injection harness.

Acceptance pins (ISSUE 2):
  (a) a failed paged admission leaves the free-block count and
      ``adapter.seqs`` bit-identical to before the call;
  (b) an allocation failure during ``grow`` triggers preemption, the
      victim's blocks are reclaimed, and re-queueing its ``Preempted``
      record reproduces the uninterrupted greedy tokens;
  (c) disabled fault points cost a single attribute check on the step hot
      path — ``fire()`` is never entered while disarmed.
"""

import functools
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from neuronx_distributed_inference_tpu import telemetry
from neuronx_distributed_inference_tpu.config import TpuConfig
from neuronx_distributed_inference_tpu.models.application import (
    CausalLMApplication, PagedCausalLMApplication)
from neuronx_distributed_inference_tpu.models.llama import (
    LlamaFamily, LlamaInferenceConfig)
from neuronx_distributed_inference_tpu.modules.block_kv_cache import (
    BlockKVCacheManager, BlockKVSpec)
from neuronx_distributed_inference_tpu.resilience import (
    AdmissionError, CapacityError, ConfigurationError, DeadlineExceeded,
    FAULTS, InjectedFault, KVCacheStateError, SequenceStateError,
    ServingError, StepFailure)
from neuronx_distributed_inference_tpu.resilience import faults as faults_mod
from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
from neuronx_distributed_inference_tpu.telemetry import metrics as tmetrics
from serving_stacks import stack_app  # noqa: F401  (a fixture)

REPO = Path(__file__).resolve().parent.parent

HF = dict(model_type="llama", hidden_size=64, intermediate_size=128,
          num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, vocab_size=512, rms_norm_eps=1e-5, rope_theta=10000.0,
          hidden_act="silu", tie_word_embeddings=False,
          torch_dtype="float32")

RNG = np.random.default_rng(0)
P1 = RNG.integers(1, 500, size=9).tolist()
P2 = RNG.integers(1, 500, size=12).tolist()
P8 = RNG.integers(1, 500, size=8).tolist()
P3 = RNG.integers(1, 500, size=9).tolist()   # only used by the poison test
Q1 = RNG.integers(1, 128, size=9).tolist()   # inside every toy's vocabulary


_GOLDEN_APP = None


@functools.lru_cache(maxsize=None)
def _golden8(prompt):
    """Uninterrupted single-request greedy generation (the reference);
    one shared batch-1 app, 8 tokens per prompt, sliced by callers."""
    global _GOLDEN_APP
    if _GOLDEN_APP is None:
        tcfg = TpuConfig(batch_size=1, seq_len=64, dtype="float32",
                         enable_bucketing=False)
        _GOLDEN_APP = CausalLMApplication(
            None, LlamaInferenceConfig(tcfg, **HF), LlamaFamily)
        _GOLDEN_APP.init_random_weights(7).init_cache()
    out = _GOLDEN_APP.generate(np.asarray([list(prompt)]), max_new_tokens=8)
    return np.asarray(out["generated"])[0]


def _golden(prompt, n):
    return _golden8(prompt)[:n]


@pytest.fixture(autouse=True)
def _no_armed_faults():
    """Every test starts and ends with the harness disarmed."""
    assert FAULTS.active is False and not FAULTS._armed
    yield
    assert FAULTS.active is False and not FAULTS._armed


@pytest.fixture(scope="module")
def paged_app():
    tcfg = TpuConfig(batch_size=4, seq_len=64, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     is_block_kv_layout=True, pa_block_size=8,
                     is_prefix_caching=True)
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                                   LlamaFamily)
    app.init_random_weights(7).init_cache()
    return app


@pytest.fixture
def paged_eng(paged_app):
    eng = PagedEngineAdapter(paged_app)
    yield eng
    eng.release(list(eng.seqs))
    paged_app.release()                 # free any stray tables


def _kv_state(app):
    """Everything transactional admission promises to leave untouched."""
    return (app.kv_mgr.allocator.num_free,
            {k: list(v) for k, v in app.kv_mgr.tables.items()},
            dict(app.kv_mgr.lens))


# ---------------------------------------------------------------------------
# hierarchy + harness mechanics (no device work)
# ---------------------------------------------------------------------------

def test_error_types_subclass_builtins():
    # the whole family is catchable as ServingError...
    for exc in (AdmissionError, SequenceStateError, ConfigurationError,
                CapacityError, KVCacheStateError, DeadlineExceeded,
                StepFailure):
        assert issubclass(exc, ServingError)
    # ...and each also subclasses the builtin it replaced (compat)
    assert issubclass(AdmissionError, ValueError)
    assert issubclass(SequenceStateError, ValueError)
    assert issubclass(ConfigurationError, ValueError)
    assert issubclass(CapacityError, RuntimeError)
    assert issubclass(KVCacheStateError, RuntimeError)
    assert issubclass(DeadlineExceeded, TimeoutError)
    assert issubclass(StepFailure, RuntimeError)
    assert not issubclass(InjectedFault, ServingError)


def test_fault_harness_trigger_on_nth_call():
    with FAULTS.inject("decode_step", nth=2) as fp:
        FAULTS.fire("decode_step")                  # call 1: below nth
        with pytest.raises(InjectedFault):
            FAULTS.fire("decode_step")              # call 2: trips
        FAULTS.fire("decode_step")                  # call 3: past window
        FAULTS.fire("prefill_step")                 # unarmed point: no-op
    assert fp.calls == 3 and fp.trips == 1
    assert FAULTS.active is False
    FAULTS.fire("decode_step")                      # disarmed: no-op


def test_fault_harness_arming_errors():
    with pytest.raises(ValueError):
        FAULTS.inject("not_a_point")
    with pytest.raises(ValueError):
        FAULTS.inject("decode_step", nth=0)
    with FAULTS.inject("decode_step"):
        with pytest.raises(RuntimeError):
            with FAULTS.inject("decode_step"):
                pass
        assert FAULTS.active is True                # inner failure kept arming
    assert FAULTS.active is False


def test_kv_manager_shrink_inverts_grow():
    spec = BlockKVSpec(num_layers=1, num_blocks=6, block_size=4,
                       num_kv_heads=1, head_dim=4)
    mgr = BlockKVCacheManager(spec, enable_prefix_caching=False)
    mgr.begin_sequence(0, list(range(6)))           # 2 blocks
    free0 = mgr.allocator.num_free
    mgr.grow(0, 3)                                  # 6 -> 9 tokens: 3 blocks
    assert len(mgr.tables[0]) == 3
    mgr.shrink(0, 3)
    assert mgr.lens[0] == 6 and len(mgr.tables[0]) == 2
    assert mgr.allocator.num_free == free0
    with pytest.raises(KVCacheStateError):
        mgr.shrink(0, 7)                            # below zero
    with pytest.raises(KVCacheStateError):
        mgr.shrink(99)                              # unknown seq


# ---------------------------------------------------------------------------
# admission validation (typed, pre-state-change)
# ---------------------------------------------------------------------------

def test_admission_validation_paged(paged_eng, paged_app):
    before = _kv_state(paged_app)
    with pytest.raises(AdmissionError, match="empty seq_ids"):
        paged_eng.add_requests([], [])
    with pytest.raises(AdmissionError, match="length mismatch"):
        paged_eng.add_requests([0, 1], [P1])
    with pytest.raises(AdmissionError, match="duplicate"):
        paged_eng.add_requests([0, 0], [P1, P2])
    with pytest.raises(AdmissionError, match="zero-length"):
        paged_eng.add_requests([0], [[]])
    with pytest.raises(AdmissionError, match="seq_len"):
        paged_eng.add_requests([0], [list(range(1, 64 + 2))])
    assert paged_eng.seqs == {}
    assert _kv_state(paged_app) == before


def test_configuration_errors():
    tcfg = TpuConfig(batch_size=2, seq_len=64, dtype="float32",
                     enable_bucketing=False)
    app = CausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                              LlamaFamily)
    # typed hierarchy at the boundary, still catchable as plain ValueError
    # (pre-hierarchy compat — see README "Serving resilience")
    with pytest.raises(ValueError) as ei:
        PagedEngineAdapter(app)             # needs block layout
    assert isinstance(ei.value, ConfigurationError)
    assert isinstance(ei.value, ServingError)


def test_paged_preemption_policy_validated(paged_app):
    with pytest.raises(ConfigurationError, match="preemption_policy"):
        PagedEngineAdapter(paged_app, preemption_policy="fifo")


# ---------------------------------------------------------------------------
# transactional admission — acceptance (a)
# ---------------------------------------------------------------------------

def test_paged_admission_rollback_on_injected_alloc_failure(paged_app):
    """Alloc failure on the SECOND sequence of one call must end the first
    sequence's allocation too: free-block count, tables, lens and
    adapter.seqs all bit-identical to before the call."""
    reg = telemetry.MetricsRegistry()
    eng = PagedEngineAdapter(paged_app, telemetry=reg,
                             preemption_policy=None)
    before = _kv_state(paged_app)
    with FAULTS.inject("paged_alloc", nth=2) as fp:
        with pytest.raises(CapacityError):
            eng.add_requests([0, 1], [P1, P2])
    assert fp.trips == 1
    assert _kv_state(paged_app) == before
    assert eng.seqs == {}
    assert reg.get(tmetrics.ADMISSION_ROLLBACKS_TOTAL).get(
        engine="paged") == 1
    # the same admission goes through once the pressure clears
    res = eng.add_requests([0, 1], [P1, P2])
    assert res[0] == _golden(tuple(P1), 1)[0]
    assert res[1] == _golden(tuple(P2), 1)[0]
    eng.release([0, 1])


def test_paged_admission_rollback_natural_oom():
    """Satellite: the pre-existing leak, reproduced WITHOUT the harness —
    a pool genuinely too small for the second prompt must not leak the
    first prompt's blocks (no device step runs, so this is cheap)."""
    tcfg = TpuConfig(batch_size=2, seq_len=64, dtype="float32",
                     enable_bucketing=False, is_block_kv_layout=True,
                     pa_block_size=8, pa_num_blocks=4)
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                                   LlamaFamily)
    app.init_random_weights(7).init_cache()
    eng = PagedEngineAdapter(app)           # no running seqs -> no victims
    free0 = app.kv_mgr.allocator.num_free
    assert free0 == 4
    with pytest.raises(CapacityError):
        # 9 tokens = 2 blocks, then 25 tokens = 4 blocks > the 2 left
        eng.add_requests([0, 1], [P1, list(range(1, 26))])
    assert app.kv_mgr.allocator.num_free == free0
    assert app.kv_mgr.tables == {} and app.kv_mgr.lens == {}
    assert eng.seqs == {}


def test_paged_admission_rollback_on_prefill_fault(paged_app):
    eng = PagedEngineAdapter(paged_app)
    before = _kv_state(paged_app)
    with FAULTS.inject("prefill_step"):
        with pytest.raises(StepFailure) as ei:
            eng.add_requests([0, 1], [P1, P2])
    assert ei.value.phase == "prefill" and ei.value.seq_ids == (0, 1)
    assert isinstance(ei.value.__cause__, InjectedFault)
    assert _kv_state(paged_app) == before and eng.seqs == {}
    res = eng.add_requests([0, 1], [P1, P2])        # retry succeeds
    assert res[0] == _golden(tuple(P1), 1)[0]
    eng.release([0, 1])


def test_rollback_shared_prefix_does_not_poison_prefix_cache(paged_app):
    """Two identical prompts in ONE failed call: the second sequence
    prefix-hits blocks the first allocated (and hashed) moments earlier,
    whose KV is never written. Rollback must retire those hashes — unwound
    in reverse admission order — or a later admission of the same prompt
    would greedy-decode from garbage KV served as a prefix hit."""
    eng = PagedEngineAdapter(paged_app)
    free0 = paged_app.kv_mgr.allocator.num_free
    with FAULTS.inject("prefill_step"):
        with pytest.raises(StepFailure):
            eng.add_requests([0, 1], [P3, P3])
    assert paged_app.kv_mgr.allocator.num_free == free0
    # re-admitting the same prompt must recompute from scratch and match
    # the uninterrupted golden, not "hit" the rolled-back blocks
    assert eng.add_requests([2], [P3])[2] == _golden(tuple(P3), 1)[0]
    eng.release([2])


# ---------------------------------------------------------------------------
# step failure: rollback + retry
# ---------------------------------------------------------------------------

def test_paged_decode_fault_rolls_back_growth_and_retries(paged_app):
    want = _golden(tuple(P8), 2)
    eng = PagedEngineAdapter(paged_app)
    assert eng.add_requests([0], [P8])[0] == want[0]
    before = _kv_state(paged_app)
    pos0 = eng.seqs[0].position
    with FAULTS.inject("decode_step"):
        with pytest.raises(StepFailure) as ei:
            eng.step()
    assert ei.value.phase == "decode"
    assert ei.value.seq_ids == (0,)
    assert ei.value.retry_safe is True              # pre-dispatch failure
    # grow() had appended a block (8 tokens -> 9); rollback freed it
    assert _kv_state(paged_app) == before
    assert eng.seqs[0].position == pos0
    assert eng.step()[0] == want[1]                 # retry is clean
    eng.release([0])


def test_genuine_async_device_failure_wrapped_not_retry_safe(
        paged_app, monkeypatch):
    """Dispatch is asynchronous: a real device failure surfaces only when
    the tokens are fetched, AFTER the donated cache was consumed. It must
    still come out typed, with host bookkeeping rolled back — but marked
    retry_safe=False because device state is lost."""
    eng = PagedEngineAdapter(paged_app)
    eng.add_requests([0], [P8])
    state = _kv_state(paged_app)
    real_cache = paged_app.cache

    class _Poisoned:
        def __array__(self, *a, **k):
            raise RuntimeError("simulated async XLA failure")

    def fake_run(*a, **k):
        paged_app.cache = {"k": None, "v": None}    # donated + swapped
        return {"tokens": _Poisoned(), "cache": paged_app.cache}

    monkeypatch.setattr(paged_app, "_run_paged", fake_run)
    try:
        with pytest.raises(StepFailure) as ei:
            eng.step()
        assert ei.value.retry_safe is False
        assert ei.value.phase == "decode"
        assert _kv_state(paged_app) == state        # host rollback still ran
    finally:
        paged_app.cache = real_cache
    eng.release([0])


# ---------------------------------------------------------------------------
# recompute preemption — acceptance (b)
# ---------------------------------------------------------------------------

def test_preemption_on_grow_reclaims_and_recomputes(paged_app):
    """Grow failure evicts the LIFO victim; its blocks are reclaimed and
    re-queueing its Preempted.tokens reproduces the uninterrupted greedy
    stream."""
    want1 = _golden(tuple(P1), 8)
    want2 = _golden(tuple(P2), 8)
    reg = telemetry.MetricsRegistry()
    eng = PagedEngineAdapter(paged_app, telemetry=reg,
                             preemption_policy="lifo")

    got1 = [eng.add_requests([0], [P1])[0]]
    for _ in range(3):
        got1.append(eng.step()[0])
    got2 = [eng.add_requests([1], [P2])[1]]

    free_with_both = paged_app.kv_mgr.allocator.num_free
    with FAULTS.inject("paged_alloc") as fp:        # next grow "runs dry"
        res = eng.step()
    assert fp.trips == 1
    # seq 1 (most recently admitted) was evicted; seq 0 stepped normally
    assert set(res) == {0}
    got1.append(res[0])
    recs = eng.take_preempted()
    assert [r.seq_id for r in recs] == [1]
    rec = recs[0]
    assert rec.reason == "grow"
    assert list(rec.tokens) == P2 + got2            # prompt + generated
    assert rec.prompt_len == len(P2) and rec.n_generated == 1
    assert 1 not in eng.seqs and 1 not in paged_app.kv_mgr.tables
    assert paged_app.kv_mgr.allocator.num_free > free_with_both
    assert eng.take_preempted() == []               # drained
    assert reg.get(tmetrics.PREEMPTIONS_TOTAL).get(
        engine="paged", reason="grow", tenant="") == 1

    for _ in range(3):
        got1.append(eng.step()[0])
    np.testing.assert_array_equal(got1, want1)

    # re-queue the preempted record as a fresh prompt: greedy continuation
    # is bit-identical to the uninterrupted run
    got2.append(eng.add_requests([1], [list(rec.tokens)])[1])
    while len(got2) < 8:
        got2.append(eng.step([1])[1])
    np.testing.assert_array_equal(got2, want2)
    eng.release([0, 1])


def test_preemption_policy_fewest_generated(paged_app):
    """fewest_generated evicts the seq with the least decode progress even
    when LIFO would pick the other one."""
    eng = PagedEngineAdapter(paged_app,
                             preemption_policy="fewest_generated")
    eng.add_requests([2], [P2])                     # older, 1 generated
    eng.add_requests([3], [P1])                     # newer (LIFO victim)
    for _ in range(3):
        eng.step([3])                               # newer has 4 generated
    with FAULTS.inject("paged_alloc"):
        res = eng.step([3])
    assert set(res) == {3}
    recs = eng.take_preempted()
    assert [r.seq_id for r in recs] == [2]          # fewest generated
    assert recs[0].n_generated == 1
    eng.release([3])


def test_grow_capacity_error_when_preemption_disabled(paged_app):
    eng = PagedEngineAdapter(paged_app, preemption_policy=None)
    eng.add_requests([0], [P8])
    state = _kv_state(paged_app)
    pos0 = eng.seqs[0].position
    with FAULTS.inject("paged_alloc"):
        with pytest.raises(CapacityError):
            eng.step()
    assert _kv_state(paged_app) == state            # growth rolled back
    assert eng.seqs[0].position == pos0
    assert eng.take_preempted() == []
    eng.release([0])


# ---------------------------------------------------------------------------
# per-request budgets: deadlines + decode-past-seq_len guard
# ---------------------------------------------------------------------------

def test_deadline_exceeded_is_typed_and_counted_once(stack_app):
    reg = telemetry.MetricsRegistry()
    eng = PagedEngineAdapter(stack_app, telemetry=reg)
    eng.add_requests([0], [Q1], deadline_s=60.0)
    assert eng.seqs[0].deadline is not None
    eng.seqs[0].deadline = 0.0                      # the budget ran out
    kv = _kv_state(stack_app)
    with pytest.raises(DeadlineExceeded) as ei:
        eng.step()
    assert ei.value.seq_ids == (0,)
    with pytest.raises(DeadlineExceeded):           # still not released
        eng.step()
    assert reg.get(tmetrics.DEADLINE_EXPIRED_TOTAL).get(engine="paged",
                                                        tenant="") == 1
    assert _kv_state(stack_app) == kv               # before any growth
    eng.release([0])
    assert eng.step() == {}                         # nothing live: clean


def test_deadline_driven_by_slow_step_fault(paged_eng):
    paged_eng.add_requests([0], [P1], deadline_s=0.05)
    with FAULTS.inject("slow_step", delay_s=0.1):   # device "stalls"
        with pytest.raises(DeadlineExceeded) as ei:
            paged_eng.step()
    assert ei.value.seq_ids == (0,)
    # the failed step changed nothing: release and continue serving
    paged_eng.release([0])
    assert paged_eng.add_requests([0], [P1])[0] == _golden(tuple(P1), 1)[0]


def test_decode_past_seq_len_guard(stack_app):
    seq_len = stack_app.tpu_config.seq_len
    eng = PagedEngineAdapter(stack_app)
    prompt = RNG.integers(1, 128, size=seq_len - 2).tolist()
    eng.add_requests([0], [prompt])                 # position seq_len - 2
    eng.step()                                      # writes slot seq_len - 2
    eng.step()                                      # writes the last slot
    kv = _kv_state(stack_app)
    with pytest.raises(CapacityError, match="seq_len") as ei:
        eng.step()                                  # one past would be OOB
    assert ei.value.seq_ids == (0,)                 # structured, not regex
    assert eng.seqs[0].position == seq_len          # state untouched
    assert _kv_state(stack_app) == kv               # before any growth
    eng.release([0])
    tcfg = TpuConfig(batch_size=2, seq_len=16, dtype="float32",
                     enable_bucketing=False, is_continuous_batching=True)
    app = CausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                              LlamaFamily)
    app.init_random_weights(7).init_cache()
    # the same guard sits one layer down, on the raw application call
    with pytest.raises(CapacityError, match="seq_len"):
        app._run_decode(np.zeros((2, 1), np.int32),
                        np.full((2, 1), 16, np.int32))


# ---------------------------------------------------------------------------
# satellite: error-path coverage for pre-existing adapter behaviors
# ---------------------------------------------------------------------------

def test_lifecycle_error_paths_paged(paged_eng, paged_app):
    paged_eng.add_requests([0], [P1])
    with pytest.raises(AdmissionError, match="already running"):
        paged_eng.add_requests([0], [P2])                 # dup across calls
    with pytest.raises(SequenceStateError, match="not running"):
        paged_eng.step([3])                               # never added
    paged_eng.release([0])
    with pytest.raises(SequenceStateError, match="not running"):
        paged_eng.step([0])                               # released id
    paged_eng.release([3])                                # never added: no-op
    assert paged_eng.seqs == {}
    assert 0 not in paged_app.kv_mgr.tables         # release freed blocks


# ---------------------------------------------------------------------------
# zero overhead while disarmed — acceptance (c)
# ---------------------------------------------------------------------------

def test_disarmed_paged_step_never_enters_fire(paged_eng, monkeypatch):
    """While nothing is armed the hot path reads FAULTS.active and stops:
    fire() must never be entered (so there is no per-step dict lookup or
    allocation). Pinned by making any fire() call explode."""
    assert FAULTS.active is False

    def _boom(self, point):
        raise AssertionError(f"fire({point!r}) entered while disarmed")
    monkeypatch.setattr(faults_mod.FaultInjector, "fire", _boom)
    want = _golden(tuple(P8), 3)
    got = [paged_eng.add_requests([0], [P8])[0]]
    got.append(paged_eng.step()[0])
    got.append(paged_eng.step()[0])
    np.testing.assert_array_equal(got, want)        # bit-identical tokens


# ---------------------------------------------------------------------------
# tier-1 lint: typed raises only
# ---------------------------------------------------------------------------

def test_error_path_lint(tmp_path):
    script = REPO / "scripts" / "check_error_paths.py"
    r = subprocess.run([sys.executable, str(script)],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr

    bad = tmp_path / "bad.py"
    bad.write_text("def f():\n    raise ValueError('x')\n"
                   "def g():\n    raise RuntimeError('y')\n")
    r = subprocess.run([sys.executable, str(script), str(bad)],
                       capture_output=True, text=True)
    assert r.returncode == 1
    assert "ValueError" in r.stderr and "RuntimeError" in r.stderr

    good = tmp_path / "good.py"
    good.write_text(
        "from neuronx_distributed_inference_tpu.resilience.errors import "
        "CapacityError\n"
        "def f():\n"
        "    try:\n"
        "        raise CapacityError('x')\n"
        "    except CapacityError:\n"
        "        raise\n")
    r = subprocess.run([sys.executable, str(script), str(good)],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
