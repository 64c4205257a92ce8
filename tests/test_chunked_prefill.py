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


#: how a prompt's chunks are deferred to the step calls: under
#: ``prefill_budget_tokens`` (one capped dispatch a call), or as the serving
#: engine defers them over a default adapter (``defer=True``: paced)
MODES = ["budget", "paced"]


def _deferring(app, mode, load=0, **adapter_kw):
    """An adapter of 8-token chunks whose prompts are deferred to its step
    calls, and what its admissions pass: ``budget`` by the budget (ONE chunk
    before each decode step); ``paced`` with no budget, as the serving engine
    admits. Its window of decode gaps is filled with gaps that saw ``load``
    prefill dispatches each: f = ``load``, so a paced adapter runs
    ``max(1, 3 * load)`` chunks before a step while a row decodes (with none
    decoding it runs the whole chain, which the scripts below avoid by
    keeping one live)."""
    kw = {"prefill_budget_tokens": 8} if mode == "budget" else {}
    eng = PagedEngineAdapter(app, prefill_chunk_tokens=8, **kw, **adapter_kw)
    eng._pace_gaps.extend([load] * eng._pace_gaps.maxlen)
    eng._pace_sum = load * eng._pace_gaps.maxlen
    return eng, ({} if mode == "budget" else {"defer": True})


def _start_row(eng, sid, prompt, **kw):
    """Admit ``prompt`` deferred and step until its first token is out (two
    calls under the budget, one where the whole chain runs at once)."""
    assert eng.add_requests([sid], [prompt], **kw) == {}
    out = {}
    while sid not in out:
        out = eng.step()
    return out[sid]


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


@pytest.mark.parametrize("mode", MODES)
def test_chunk_fault_deferred_aborts_only_packed_rows(paged_app, mode):
    """In deferred mode a chunk-dispatch failure rolls back the sequences
    packed in THAT dispatch; running decode rows are untouched and keep
    stepping."""
    ref_run = _stream(paged_app, P_MED, 4)
    eng, kw = _deferring(paged_app, mode)
    run = [_start_row(eng, 0, P_MED, **kw)]           # two chunks: token
    assert eng.add_requests([1], [P_LONG], **kw) == {}
    run.append(eng.step()[0])                         # chunk 1 + decode
    assert eng._chunks[1].done == 8
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


@pytest.mark.parametrize("mode", MODES)
def test_deadline_expires_mid_prefill(paged_app, mode):
    """A pending admission's deadline is enforced BEFORE chunk device
    work — but only for steps that target it: an explicit seq_ids step on
    a healthy row must not be stalled by an unrelated expired admission.
    Releasing the expired sequence aborts its half-written blocks."""
    free0 = paged_app.kv_mgr.allocator.num_free
    eng, kw = _deferring(paged_app, mode)
    assert eng.add_requests([6], [P_SHORT], **kw) == {}  # healthy running row
    assert list(eng.step()) == [6]                 # 5 tokens: one chunk
    assert eng.add_requests([5], [P_LONG], deadline_s=0.05, **kw) == {}
    eng.step()                                    # first chunk runs
    assert eng._chunks[5].done == 8               # ... and no other
    time.sleep(0.07)
    assert list(eng.step([6])) == [6]             # healthy row: no stall
    with pytest.raises(DeadlineExceeded) as ei:
        eng.step()                                # targets all: raises
    assert ei.value.seq_ids == (5,)
    assert 5 in eng._chunks                       # still pending: engine
    eng.release([5, 6])                           # decides, then releases
    assert eng._chunks == {} and 5 not in paged_app.kv_mgr.tables
    assert paged_app.kv_mgr.allocator.num_free == free0


@pytest.mark.parametrize("mode", MODES)
def test_preempt_half_prefilled_sequence(small_pool_app, mode):
    """KV pressure from a new admission may evict a PENDING sequence: the
    record carries the bare prompt (n_generated 0), its blocks come back,
    and the re-queued prompt replays bit-identically."""
    app = small_pool_app
    p_big = np.random.default_rng(5).integers(1, 500, size=30).tolist()
    ref_victim = _stream(app, p_big, 2, prefill_chunk_tokens=8)
    eng, kw = _deferring(app, mode, preemption_policy="lifo")
    _start_row(eng, 7, P_SHORT, **kw)                  # a row decodes
    assert eng.add_requests([0], [p_big], **kw) == {}  # 4 blocks
    eng.step()                                         # half-prefilled
    assert 0 in eng._chunks and eng._chunks[0].done == 8
    eng.release([7])
    # 60 tokens want 8 blocks, only 6 free -> evicts pending seq 0
    assert eng.add_requests(
        [1], [RNG.integers(1, 500, size=60).tolist()], **kw) == {}
    recs = eng.take_preempted()
    assert [r.seq_id for r in recs] == [0]
    assert recs[0].n_generated == 0 and recs[0].reason == "admission"
    assert list(recs[0].tokens) == p_big
    assert 0 not in eng._chunks and 0 not in app.kv_mgr.tables
    eng.release([1])
    # re-queue the preempted prompt: replay is bit-identical
    assert eng.add_requests([0], [list(recs[0].tokens)], **kw) == {}
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


# ---------------------------------------------------------------------------
# how many chunks go before a decode step (ISSUE 63)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gaps, dispatches, decoding, budgeted, want", [
    (96, 2, True, False, 1),        # a light mix: f = 0.02
    (96, 0, True, False, 1),        # no prefill in the window at all
    (96, 32, True, False, 1),       # f = 1/3 exactly: one gap in three
    (96, 35, True, False, 2),       # f = 0.36
    (96, 130, True, False, 5),      # f = 1.35
    (95, 130, True, False, None),   # the window has not filled: whole chain
    (0, 0, True, False, None),
    (96, 130, False, False, None),  # nobody decoding: whole chain
    (96, 130, True, True, 1),       # a budget: one dispatch whatever f
    (96, 130, False, True, 1),
    (0, 0, False, True, 1),
], ids=lambda v: str(v))
def test_chunks_before_a_step_by_the_two_counts(gaps, dispatches, decoding,
                                                budgeted, want):
    from neuronx_distributed_inference_tpu.serving.adapter import \
        chunks_before_step
    assert chunks_before_step(gaps, dispatches, decoding, budgeted) == want


@pytest.mark.parametrize("load", [0, 5], ids=["light", "heavy"])
def test_a_budget_is_one_capped_dispatch_whatever_the_load(paged_app, load):
    """With ``prefill_budget_tokens`` the observed prefill load decides
    nothing: every step runs ONE dispatch of at most that many tokens."""
    eng, kw = _deferring(paged_app, "budget", load)
    _start_row(eng, 0, P_SHORT, **kw)
    assert eng.add_requests([1], [P_LONG], **kw) == {}    # five chunks of 8
    for n in range(1, 5):
        before = eng.host_stats["prefill_dispatches"]
        assert list(eng.step()) == [0]
        assert eng.host_stats["prefill_dispatches"] == before + 1
        assert eng._chunks[1].done == 8 * n
    assert set(eng.step()) == {0, 1}
    h = eng.host_stats
    assert h["prefill_paced_passes"] == h["prefill_chains_whole"] == 0
    assert h["prefill_pace_k"] == 1
    eng.release([0, 1])


@pytest.mark.parametrize("load, k", [(0, 1), (1, 3)], ids=["light", "heavy"])
def test_a_deferred_default_admission_runs_k_chunks_a_step(paged_app, load,
                                                           k):
    """No budget, a row decoding, the window full: ``k`` from the window's
    load, each dispatch one whole 8-token chunk; nobody decoding: the whole
    chain at once."""
    eng, kw = _deferring(paged_app, "paced", load)
    ref = _stream(paged_app, P_LONG, 2, prefill_chunk_tokens=8)
    first = _start_row(eng, 0, P_SHORT, **kw)
    assert eng.host_stats["prefill_chains_whole"] == 1
    assert eng.add_requests([1], [P_LONG], **kw) == {}    # five chunks of 8
    before = eng.host_stats["prefill_dispatches"]
    assert list(eng.step()) == [0]
    assert eng.host_stats["prefill_dispatches"] == before + k
    assert eng._chunks[1].done == 8 * k
    assert eng.host_stats["prefill_pace_k"] == k
    got = {}
    while 1 not in got:
        got = eng.step()
    assert [got[1], eng.step()[1], eng.step()[1]] == ref
    assert eng.host_stats["prefill_paced_chunks"] == 5
    assert eng.host_stats["prefill_chains_whole"] == 1
    eng.release([0, 1])
    assert first == _stream(paged_app, P_SHORT, 0)[0]
    assert paged_app.kv_mgr.tables == {} and eng._unwritten == set()


def test_a_paced_chain_leads_with_one_chunk_and_holds_the_step(paged_app):
    """The lookahead's order of a paced chain of k = 3: ONE chunk in front of
    the step in flight, that step fetched and no step enqueued; the next
    call issues the other two with nothing to fetch, then the step. The
    tokens in flight are not kept waiting behind the chain's host work, and
    the gap counters give the whole chain to the ONE gap it stands in on the
    device."""
    eng, kw = _deferring(paged_app, "paced", load=1)
    ref0 = _stream(paged_app, P_SHORT, 6)
    ref1 = _stream(paged_app, P_LONG, 1, prefill_chunk_tokens=8)
    assert eng.add_requests([0], [P_SHORT], **kw) == {}
    got0 = [eng.step_ahead()[0]]        # nobody decoding: chain, token, step
    assert eng._inflight is not None
    got0.append(eng.step_ahead()[0])
    h = eng.host_stats
    gaps, behind = h["decode_gaps"], h["decode_gaps_behind_prefill"]
    assert eng.add_requests([1], [P_LONG], **kw) == {}    # five chunks of 8
    steps = h["dispatches"]
    got0.append(eng.step_ahead()[0])    # leads: chunk 1, the fetch, no step
    assert (eng._chunks[1].done, h["dispatches"]) == (8, steps)
    assert eng._inflight is None
    assert eng.step_ahead() == {}       # chunks 2 and 3, then the step
    assert (eng._chunks[1].done, h["dispatches"]) == (24, steps + 1)
    got0.append(eng.step_ahead()[0])    # leads again: chunk 4
    assert (eng._chunks[1].done, h["dispatches"]) == (32, steps + 1)
    assert eng.step_ahead() == {}       # the last chunk, parked; the step
    assert eng._chunks[1].done == 40 and len(eng._parked) == 1
    out = eng.step_ahead()              # behind that step's fetch: graduates
    got0.append(out[0])
    assert set(out) == {0, 1} and not eng._parked
    got1 = [out[1]]
    out = eng.step_ahead()              # (row 1 joined the step in flight)
    got0.append(out[0])
    got1.append(eng.step_ahead()[1])
    assert got0 == ref0[:6] and got1 == ref1
    # two gaps stood behind prefill on the device: three chunks, then two
    assert h["decode_gaps_behind_prefill"] == behind + 2
    assert h["prefill_dispatches_in_gaps"] == 5
    assert h["prefill_paced_chunks"] == 5 and h["prefill_paced_passes"] == 4
    assert h["prefill_blocking_fetches"] == 1          # the ramp's alone
    eng.flush()
    eng.release([0, 1])
    assert paged_app.kv_mgr.tables == {} and eng._unwritten == set()
