"""Chunked, packed, schedulable prefill on the paged adapter (ISSUE 5).

Acceptance pins:
  (a) chunked+packed token streams are bit-identical to monolithic
      admission (greedy), with and without prefix-cache hits;
  (b) a prompt longer than the largest ctx bucket (but <= seq_len) is
      admitted successfully and matches the contiguous-app golden;
  (c) packed mixed-length admission matches per-sequence admissions;
  (d) a ``prefill_chunk`` fault rolls partially-prefilled sequences back
      transactionally (no block leak, no prefix-cache poisoning), and a
      deadline can expire mid-prefill;
  (e) a half-prefilled sequence can be preempted (``n_generated == 0``,
      ``tokens`` = the bare prompt) and replays bit-identically;
  (f) the packed chunk-dispatch region is covered by the host-sync lint.

Everything compares chunked runs against monolithic runs of the SAME app
(greedy — no separate golden model), so the module costs a handful of
tiny-graph compiles only (870s tier-1 budget; target ~20s like
test_decode_pipeline.py). The main app runs with prefix caching OFF so
reference runs don't seed hits that change later tests' chunk counts; the
hit path gets its own app.
"""

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
    AdmissionError, DeadlineExceeded, FAULTS, StepFailure)
from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter

REPO = Path(__file__).resolve().parent.parent

HF = dict(model_type="llama", hidden_size=64, intermediate_size=128,
          num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, vocab_size=512, rms_norm_eps=1e-5, rope_theta=10000.0,
          hidden_act="silu", tie_word_embeddings=False,
          torch_dtype="float32")

RNG = np.random.default_rng(11)
P_SHORT = RNG.integers(1, 500, size=5).tolist()
P_MED = RNG.integers(1, 500, size=12).tolist()
P_LONG = RNG.integers(1, 500, size=40).tolist()     # > ctx bucket 16


def _make_app(**over):
    tcfg = TpuConfig(batch_size=2, seq_len=64, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     is_block_kv_layout=True, pa_block_size=8, **over)
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                                   LlamaFamily)
    app.init_random_weights(7).init_cache()
    return app


@pytest.fixture(scope="module")
def paged_app():
    return _make_app(is_prefix_caching=False)


@pytest.fixture(scope="module")
def prefix_app():
    """Prefix caching ON — the hit-path bit-identity test only."""
    return _make_app(is_prefix_caching=True)


@pytest.fixture(scope="module")
def small_pool_app():
    """Tight block pool (10 usable blocks of 8) for the preemption path."""
    return _make_app(is_prefix_caching=False, pa_num_blocks=10)


def _stream(app, prompt, n_decode, sid=0, **adapter_kw):
    """prompt's first token + n_decode decode tokens from a fresh
    adapter."""
    eng = PagedEngineAdapter(app, **adapter_kw)
    out = [eng.add_requests([sid], [prompt])[sid]]
    for _ in range(n_decode):
        out.append(eng.step()[sid])
    eng.release([sid])
    return out


# ---------------------------------------------------------------------------
# bit-identity: chunked+packed == monolithic — acceptance (a)
# ---------------------------------------------------------------------------

def test_chunked_matches_monolithic(paged_app):
    """chunk=4 walks each suffix in 4-token dispatches; the delivered
    stream must be bit-identical to the single-dispatch monolithic
    admission (default chunk = the 16-wide ctx bucket)."""
    ref = {s: _stream(paged_app, p, 4, sid=s)
           for s, p in ((0, P_SHORT), (1, P_MED))}
    eng = PagedEngineAdapter(paged_app, prefill_chunk_tokens=4)
    res = eng.add_requests([0, 1], [P_SHORT, P_MED])
    got = {0: [res[0]], 1: [res[1]]}
    # packed: [4,4] + [1,4] + [-,4] = 3 dispatches, zero padded-token
    # growth from the short row after it finishes
    assert eng.host_stats["prefill_dispatches"] == 3
    assert eng.host_stats["prefill_real_tokens"] == len(P_SHORT) + len(P_MED)
    for _ in range(4):
        for s, t in eng.step().items():
            got[s].append(t)
    eng.release([0, 1])
    assert got == ref
    assert paged_app.kv_mgr.tables == {}
    assert eng._unwritten == set()


def test_chunked_matches_monolithic_with_prefix_hits(prefix_app):
    """Re-admitting a prompt whose blocks are prefix-cached must stay
    bit-identical under chunking (the cached prefix is skipped, the
    remainder chunks)."""
    prompt = RNG.integers(1, 500, size=21).tolist()   # 2 full blocks + tail
    ref = _stream(prefix_app, prompt, 3)              # also warms the cache
    hit = _stream(prefix_app, prompt, 3)              # monolithic, hits
    chunked = _stream(prefix_app, prompt, 3, prefill_chunk_tokens=4)
    assert ref == hit == chunked


# ---------------------------------------------------------------------------
# long-prompt admission beyond the largest ctx bucket — acceptance (b)
# ---------------------------------------------------------------------------

def test_long_prompt_admitted_beyond_ctx_bucket(paged_app):
    """40-token prompt on a 16-wide ctx bucket: monolithic admission was
    impossible (AdmissionError); the default adapter now walks it in
    bucket-sized chunks and matches the contiguous-app golden stream."""
    tcfg = TpuConfig(batch_size=1, seq_len=64, dtype="float32",
                     enable_bucketing=False)
    gold_app = CausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                                   LlamaFamily)
    gold_app.init_random_weights(7).init_cache()
    want = np.asarray(gold_app.generate(np.asarray([P_LONG]),
                                        max_new_tokens=5)["generated"])[0]
    got = _stream(paged_app, P_LONG, 4)
    np.testing.assert_array_equal(got, want)
    # beyond seq_len still rejects typed
    eng = PagedEngineAdapter(paged_app)
    with pytest.raises(AdmissionError, match="seq_len"):
        eng.add_requests([0], [list(range(1, 66))])


# ---------------------------------------------------------------------------
# packed mixed-length admission — acceptance (c)
# ---------------------------------------------------------------------------

def test_packed_mixed_lengths_match_individual(paged_app):
    """Skewed prompts admitted together pack chunk rows into shared
    dispatches; each stream must match its individually-admitted run, and
    the packed call must do strictly less padded-token work than
    monolithic padding of both rows to the longest suffix."""
    ref0 = _stream(paged_app, P_SHORT, 3, prefill_chunk_tokens=8)
    ref1 = _stream(paged_app, P_LONG, 3, prefill_chunk_tokens=8)
    eng = PagedEngineAdapter(paged_app, prefill_chunk_tokens=8)
    res = eng.add_requests([0, 1], [P_SHORT, P_LONG])
    got = {0: [res[0]], 1: [res[1]]}
    # row 0 rides only the first dispatch; the rest carry row 1 alone
    assert eng.host_stats["prefill_dispatches"] == 5
    padded = eng.host_stats["prefill_padded_tokens"]
    real = eng.host_stats["prefill_real_tokens"]
    assert real == len(P_SHORT) + len(P_LONG)
    # every dispatch runs at the 16-wide ctx bucket (this app's only one);
    # the first carries both rows and runs the full batch of 2, the other
    # four carry row 1 alone and run one row (app.prefill_row_buckets).
    assert padded == 2 * 16 + 4 * 1 * 16
    for _ in range(3):
        for s, t in eng.step().items():
            got[s].append(t)
    eng.release([0, 1])
    assert got[0] == ref0 and got[1] == ref1


# ---------------------------------------------------------------------------
# interleaved (deferred) prefill under prefill_budget_tokens
# ---------------------------------------------------------------------------

def test_budgeted_prefill_interleaves_with_decode(paged_app):
    """prefill_budget_tokens defers the device work to step(): admission
    returns {}, each step runs at most ONE chunk dispatch (<= budget
    tokens) before decoding the running rows, and the first token arrives
    from the step whose dispatch completes the prompt — all streams
    bit-identical to the undeferred runs."""
    ref_run = _stream(paged_app, P_MED, 6)            # the running sequence
    ref_new = _stream(paged_app, P_LONG, 2)
    eng = PagedEngineAdapter(paged_app, prefill_chunk_tokens=16,
                             prefill_budget_tokens=16)
    assert eng.add_requests([0], [P_MED]) == {}       # deferred
    run = [eng.step()[0]]                             # 12 <= budget: 1 chunk
    run.append(eng.step()[0])                         # plain decode step
    assert run == ref_run[:2]
    assert eng.add_requests([1], [P_LONG]) == {}      # deferred
    new = []
    steps = 0
    while not new:
        before = eng.host_stats["prefill_dispatches"]
        res = eng.step()
        steps += 1
        assert eng.host_stats["prefill_dispatches"] - before == 1
        run.append(res[0])                            # decode never stalls
        if 1 in res:
            new.append(res[1])
    assert steps == 3                                 # 40 tokens / 16 budget
    for _ in range(2):
        res = eng.step()
        run.append(res[0])
        new.append(res[1])
    eng.release([0, 1])
    assert run == ref_run[:len(run)]
    assert new == ref_new[:len(new)]


def test_budgeted_admission_returns_empty_and_steps_alone(paged_app):
    """With no running rows, step() still drives pending prefill and
    returns {} until the final chunk's token is ready."""
    ref = _stream(paged_app, P_LONG, 1)
    eng = PagedEngineAdapter(paged_app, prefill_chunk_tokens=8,
                             prefill_budget_tokens=8)
    assert eng.add_requests([3], [P_LONG]) == {}
    outs = [eng.step() for _ in range(5)]             # 40 tokens / 8
    assert outs[:4] == [{}] * 4 and list(outs[4]) == [3]
    got = [outs[4][3], eng.step([3])[3]]
    eng.release([3])
    assert got == ref[:2]
    assert paged_app.kv_mgr.tables == {}


# ---------------------------------------------------------------------------
# resilience: chunk faults, deadlines, preemption — acceptance (d), (e)
# ---------------------------------------------------------------------------

def test_chunk_fault_rolls_back_admission_transactionally(paged_app):
    """A chunk-dispatch fault mid-admission (2nd of 3 dispatches — the
    first sequence already finished its prefill) must admit NOTHING, leak
    no blocks, and leave nothing stale behind."""
    free0 = paged_app.kv_mgr.allocator.num_free
    eng = PagedEngineAdapter(paged_app, prefill_chunk_tokens=16)
    with FAULTS.inject("prefill_chunk", nth=2) as fp:
        with pytest.raises(StepFailure) as ei:
            eng.add_requests([0, 1], [P_SHORT, P_LONG])
    assert fp.trips == 1
    assert ei.value.phase == "prefill"
    assert eng.seqs == {} and eng._chunks == {} and eng._ready == {}
    assert paged_app.kv_mgr.tables == {}
    assert paged_app.kv_mgr.allocator.num_free == free0
    assert eng._unwritten == set()
    # retry reproduces the clean streams (nothing stale served)
    res = eng.add_requests([0, 1], [P_SHORT, P_LONG])
    assert res[0] == _stream(paged_app, P_SHORT, 0)[0]
    assert res[1] == _stream(paged_app, P_LONG, 0)[0]
    eng.release([0, 1])


def test_chunk_fault_deferred_aborts_only_packed_rows(paged_app):
    """In deferred mode a chunk-dispatch failure rolls back the sequences
    packed in THAT dispatch; running decode rows are untouched and keep
    stepping."""
    ref_run = _stream(paged_app, P_MED, 4)
    eng = PagedEngineAdapter(paged_app, prefill_chunk_tokens=8,
                             prefill_budget_tokens=8)
    assert eng.add_requests([0], [P_MED]) == {}
    assert eng.step() == {}                           # chunk 1 of 2 (8 tok)
    run = [eng.step()[0]]                             # final chunk: token
    eng.add_requests([1], [P_LONG])
    run.append(eng.step()[0])                         # chunk 1 + decode
    with FAULTS.inject("prefill_chunk") as fp:
        with pytest.raises(StepFailure) as ei:
            eng.step()                                # chunk 2 faults
    assert fp.trips == 1 and ei.value.seq_ids == (1,)
    assert 1 not in eng._chunks and 1 not in paged_app.kv_mgr.tables
    assert 0 in eng.seqs                              # running row unharmed
    for _ in range(2):
        run.append(eng.step()[0])
    eng.release([0])
    assert run == ref_run[:len(run)]


def test_deadline_expires_mid_prefill(paged_app):
    """A pending admission's deadline is enforced BEFORE chunk device
    work — but only for steps that target it: an explicit seq_ids step on
    a healthy row must not be stalled by an unrelated expired admission.
    Releasing the expired sequence aborts its half-written blocks."""
    free0 = paged_app.kv_mgr.allocator.num_free
    eng = PagedEngineAdapter(paged_app, prefill_chunk_tokens=8,
                             prefill_budget_tokens=8)
    assert eng.add_requests([6], [P_SHORT]) == {}  # healthy running row
    assert list(eng.step()) == [6]                 # 5 tokens: one chunk
    assert eng.add_requests([5], [P_LONG], deadline_s=0.05) == {}
    eng.step()                                    # first chunk runs
    time.sleep(0.07)
    assert list(eng.step([6])) == [6]             # healthy row: no stall
    with pytest.raises(DeadlineExceeded) as ei:
        eng.step()                                # targets all: raises
    assert ei.value.seq_ids == (5,)
    assert 5 in eng._chunks                       # still pending: engine
    eng.release([5, 6])                           # decides, then releases
    assert eng._chunks == {} and 5 not in paged_app.kv_mgr.tables
    assert paged_app.kv_mgr.allocator.num_free == free0


def test_preempt_half_prefilled_sequence(small_pool_app):
    """KV pressure from a new admission may evict a PENDING sequence: the
    record carries the bare prompt (n_generated 0), its blocks come back,
    and the re-queued prompt replays bit-identically."""
    app = small_pool_app
    p_big = RNG.integers(1, 500, size=30).tolist()     # 4 blocks
    ref_victim = _stream(app, p_big, 2, prefill_chunk_tokens=8)
    eng = PagedEngineAdapter(app, prefill_chunk_tokens=8,
                             prefill_budget_tokens=8,
                             preemption_policy="lifo")
    assert eng.add_requests([0], [p_big]) == {}
    eng.step()                                         # half-prefilled
    assert 0 in eng._chunks and eng._chunks[0].done > 0
    # 60 tokens want 8 blocks, only 6 free -> evicts pending seq 0
    assert eng.add_requests(
        [1], [RNG.integers(1, 500, size=60).tolist()]) == {}
    recs = eng.take_preempted()
    assert [r.seq_id for r in recs] == [0]
    assert recs[0].n_generated == 0 and recs[0].reason == "admission"
    assert list(recs[0].tokens) == p_big
    assert 0 not in eng._chunks and 0 not in app.kv_mgr.tables
    eng.release([1])
    # re-queue the preempted prompt: replay is bit-identical
    assert eng.add_requests([0], [list(recs[0].tokens)]) == {}
    got = []
    while not got:
        got.extend(eng.step().values())
    for _ in range(2):
        got.append(eng.step()[0])
    eng.release([0])
    assert got == ref_victim
    assert eng._unwritten == set()


def test_prefill_metrics_flow(paged_app):
    """nxdi_prefill_chunks_total counts per-sequence chunks and
    nxdi_prefill_pad_waste records per-dispatch waste fractions."""
    from neuronx_distributed_inference_tpu import telemetry
    from neuronx_distributed_inference_tpu.telemetry import metrics as tm
    reg = telemetry.MetricsRegistry()
    telemetry.set_registry(reg)
    try:
        eng = PagedEngineAdapter(paged_app, prefill_chunk_tokens=8)
        eng.add_requests([0, 1], [P_SHORT, P_LONG])
        eng.release([0, 1])
    finally:
        telemetry.disable()
    # 5 tokens -> 1 chunk; 40 tokens -> 5 chunks of 8
    assert reg.get(tm.PREFILL_CHUNKS_TOTAL).get(engine="paged") == 6
    waste = reg.get(tm.PREFILL_PAD_WASTE)
    assert waste.count(engine="paged") == 5           # one per dispatch
    assert 0.0 <= waste.sum(engine="paged") <= 5.0


def test_chunk_fault_shared_prefix_pending_does_not_poison_cache(prefix_app):
    """Review regression pin: two deferred admissions sharing a prefix
    (the second prefix-HITS the first's hashed-but-unwritten blocks); the
    packed chunk dispatch faults and both roll back. The shared hash must
    be retired — the next admission of that prefix must recompute, not
    'hit' garbage KV."""
    base = RNG.integers(1, 500, size=16).tolist()      # 2 full blocks
    pa = base + RNG.integers(1, 500, size=5).tolist()
    pb = base + RNG.integers(1, 500, size=9).tolist()
    eng = PagedEngineAdapter(prefix_app, prefill_chunk_tokens=8,
                             prefill_budget_tokens=32)
    assert eng.add_requests([0], [pa]) == {}           # nothing written yet
    assert eng.add_requests([1], [pb]) == {}           # hits 0's blocks
    with FAULTS.inject("prefill_chunk") as fp:
        with pytest.raises(StepFailure) as ei:
            eng.step()                  # packs BOTH rows (16 <= budget)
    assert fp.trips == 1 and set(ei.value.seq_ids) == {0, 1}
    assert prefix_app.kv_mgr.tables == {}
    _, cached = prefix_app.kv_mgr.begin_sequence(9, base)
    assert cached == 0                                 # nothing servable
    prefix_app.kv_mgr.end_sequence(9)


def test_release_pending_shared_prefix_does_not_poison_cache(prefix_app):
    """Review regression pin: releasing the ORIGINATING pending sequence
    first, then the sibling that prefix-hit its unwritten blocks, must
    invalidate the shared hash on the final dereference — a hit block
    whose writer never landed is itself unwritten."""
    base = RNG.integers(1, 500, size=16).tolist()      # 2 fresh full blocks
    pa = base + RNG.integers(1, 500, size=5).tolist()
    pb = base + RNG.integers(1, 500, size=9).tolist()
    eng = PagedEngineAdapter(prefix_app, prefill_chunk_tokens=8,
                             prefill_budget_tokens=8)
    assert eng.add_requests([0], [pa]) == {}           # nothing written yet
    assert eng.add_requests([1], [pb]) == {}           # hits 0's blocks
    eng.release([0])                                   # originator first
    eng.release([1])                                   # last dereference
    assert prefix_app.kv_mgr.tables == {}
    assert eng._unwritten == set()
    _, cached = prefix_app.kv_mgr.begin_sequence(9, base)
    assert cached == 0                                 # nothing servable
    prefix_app.kv_mgr.end_sequence(9)


def test_over_batch_admission_rejected_typed(paged_app):
    """Review regression pin: the monolithic path rejected a call with
    more sequences than the compiled batch (typed, inside its try); the
    chunked packer must reject it too — BEFORE any state change — instead
    of admitting and wedging the next decode step on an untyped bucket
    error. Cumulative (running + pending) overflow counts as well."""
    eng = PagedEngineAdapter(paged_app, prefill_chunk_tokens=8)
    with pytest.raises(AdmissionError, match="compiled batch"):
        eng.add_requests([0, 1, 2], [P_SHORT, P_MED, P_LONG])
    assert eng.seqs == {} and eng._chunks == {}
    assert paged_app.kv_mgr.tables == {}
    eng.add_requests([0, 1], [P_SHORT, P_MED])
    with pytest.raises(AdmissionError, match="compiled batch"):
        eng.add_requests([2], [P_LONG])
    eng.release([0, 1])


def test_rolled_back_admission_leaves_no_telemetry(paged_app):
    """Review regression pin: a sibling chunk failure rolls the whole call
    back AFTER the first sequence finished its prefill — no request may be
    counted as admitted and no span entry may leak."""
    from neuronx_distributed_inference_tpu import telemetry
    from neuronx_distributed_inference_tpu.telemetry import metrics as tm
    reg = telemetry.MetricsRegistry()
    telemetry.set_registry(reg)
    try:
        eng = PagedEngineAdapter(paged_app, prefill_chunk_tokens=16)
        with FAULTS.inject("prefill_chunk", nth=2):
            with pytest.raises(StepFailure):
                eng.add_requests([0, 1], [P_SHORT, P_LONG])
    finally:
        telemetry.disable()
    req = reg.get(tm.REQUESTS_TOTAL)
    assert req is None or req.get(engine="paged", event="added") == 0
    assert eng.telemetry._requests == {}


def test_chunk_dispatch_region_linted():
    """The packed chunk-dispatch region is covered by the host-sync lint,
    and the lint's expected-region guard knows about it (acceptance f)."""
    script = REPO / "scripts" / "check_host_sync.py"
    r = subprocess.run([sys.executable, str(script), "--list-regions"],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "_dispatch_prefill_chunk" in r.stdout
    r = subprocess.run([sys.executable, str(script)],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
