"""Decode pipeline (ISSUE 3): device-resident token feedback
(``step_ahead()``), fused multi-step ``step_many(k)``, incremental host
bookkeeping, and the lookahead-aware failure contract.

Acceptance pins:
  (a) ``step_many(k)`` token streams are bit-identical to k eager
      ``step()`` calls;
  (b) ``step_ahead()`` streams are bit-identical to ``step()`` streams
      (tokens arrive one call later; ``flush()`` drains the last);
  (c) a lookahead ``StepFailure`` (``pipeline_flush`` fault) rolls
      positions and paged KV growth back to the last DELIVERED token with
      ``retry_safe=False``; a dispatch-time fault preserves the healthy
      in-flight step with ``retry_safe=True``, whichever scratch kind lays
      the step's rows out (``serving_stacks``);
  (d) deadline and preemption paths still work with a step in flight;
  (e) a changed live set does not empty the pipeline (ISSUE 62): under
      ``prefill_budget_tokens`` a prompt's last chunk is not waited for
      while a decode row is live, and a row that joins or leaves is CARRIED
      (the next step's ids are merged on the device) — streams, counters,
      the failure contract and a row that leaves before it graduates, for
      both scratch kinds.

Everything compares pipelined/fused runs against eager runs of the SAME
app (greedy sampling — no separate golden model), so the module costs a
handful of tiny-graph compiles only (870s tier-1 budget).
"""

import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from neuronx_distributed_inference_tpu.config import TpuConfig
from neuronx_distributed_inference_tpu.models.application import \
    PagedCausalLMApplication
from neuronx_distributed_inference_tpu.models.llama import (
    LlamaFamily, LlamaInferenceConfig)
from neuronx_distributed_inference_tpu.resilience import (
    CapacityError, ConfigurationError, DeadlineExceeded, FAULTS, StepFailure)
from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
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
#: inside every toy stack's vocabulary (128)
Q1 = RNG.integers(1, 128, size=9).tolist()
Q2 = RNG.integers(1, 128, size=12).tolist()


@pytest.fixture(scope="module")
def paged_app():
    tcfg = TpuConfig(batch_size=2, seq_len=64, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     is_block_kv_layout=True, pa_block_size=8)
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                                   LlamaFamily)
    app.init_random_weights(7).init_cache()
    return app


def _eager_streams(app, n_steps, prompts=(P1, P2)):
    """{seq_id: [prefill + n_steps tokens]} from a fresh adapter's
    ``step()``."""
    eng = PagedEngineAdapter(app)
    res = eng.add_requests([0, 1], list(prompts))
    out = {0: [res[0]], 1: [res[1]]}
    for _ in range(n_steps):
        for s, t in eng.step().items():
            out[s].append(t)
    eng.release([0, 1])
    return out


# ---------------------------------------------------------------------------
# bit-identity: step_many(k) == k eager steps — acceptance (a)
# ---------------------------------------------------------------------------

def test_paged_step_many_matches_eager(paged_app):
    ref = _eager_streams(paged_app, 6)
    eng = PagedEngineAdapter(paged_app)
    res = eng.add_requests([0, 1], [P1, P2])
    got = {0: [res[0]], 1: [res[1]]}
    for _ in range(2):
        for s, ts in eng.step_many(3).items():
            got[s].extend(ts)
    eng.release([0, 1])
    assert got == ref
    # one fused dispatch + one blocking fetch per 3-token horizon
    assert eng.host_stats["dispatches"] == 2
    assert eng.host_stats["blocking_fetches"] == 2
    assert eng.host_stats["device_steps"] == 6
    assert eng.flush() == {}                # nothing was ever in flight


# ---------------------------------------------------------------------------
# bit-identity: step_ahead() == step() — acceptance (b)
# ---------------------------------------------------------------------------

def test_paged_pipelined_matches_eager(paged_app):
    ref = _eager_streams(paged_app, 6)
    eng = PagedEngineAdapter(paged_app)
    res = eng.add_requests([0, 1], [P1, P2])
    got = {0: [res[0]], 1: [res[1]]}
    assert eng.step_ahead() == {}           # pipeline filling: one behind
    for _ in range(4):
        for s, t in eng.step_ahead().items():
            got[s].append(t)
    # the caller steps another set of the running rows: that (and only
    # that, a preemption and a re-admitted seq_id) still drains the
    # in-flight both-row dispatch synchronously
    for s, t in eng.step_ahead([0]).items():
        got[s].append(t)
    assert eng.host_stats["pipeline_drains_liveset"] == 1
    for s, t in eng.flush().items():
        got[s].append(t)
    eng.release([0, 1])
    assert got[0] == ref[0] and got[1] == ref[1][:6], (got, ref)
    assert eng._inflight is None


def test_pipeline_depth_validated(paged_app):
    with pytest.raises(ConfigurationError, match="pipeline_depth"):
        PagedEngineAdapter(paged_app, pipeline_depth=2)
    # step() means one thing: the value that made it return the PREVIOUS
    # step's tokens is refused, and the refusal names the call that does
    with pytest.raises(ConfigurationError, match=r"step_ahead\(\)"):
        PagedEngineAdapter(paged_app, pipeline_depth=1)
    with pytest.raises(ConfigurationError, match="num_steps"):
        PagedEngineAdapter(paged_app).step_many(0)
    # 0 is the eager reference under the engine: step_ahead() is step()
    eng = PagedEngineAdapter(paged_app, pipeline_depth=0)
    eng.add_requests([0], [P1])
    assert set(eng.step_ahead()) == {0} and eng._inflight is None
    eng.release([0])


# ---------------------------------------------------------------------------
# lookahead-aware failure contract — acceptance (c)
# ---------------------------------------------------------------------------

def test_lookahead_fetch_failure_rolls_back_to_delivered(paged_app):
    """A failure surfacing at the deferred fetch (step N's device error
    seen at step N+1) unwinds BOTH in-flight dispatches — positions and KV
    growth return to the last token the engine actually received — and is
    not retry-safe (the donated cache chain was consumed)."""
    eng = PagedEngineAdapter(paged_app)
    eng.add_requests([0], [P1])
    free_admitted = paged_app.kv_mgr.allocator.num_free
    assert eng.step_ahead() == {}            # dispatch 1 in flight
    with FAULTS.inject("pipeline_flush"):
        with pytest.raises(StepFailure) as ei:
            eng.step_ahead()                 # dispatch 2, then fetch 1 fails
    assert ei.value.retry_safe is False
    assert ei.value.phase == "decode"
    assert eng.seqs[0].position == len(P1)   # last delivered = prefill token
    assert paged_app.kv_mgr.lens[0] == len(P1)
    assert paged_app.kv_mgr.allocator.num_free == free_admitted
    assert eng._inflight is None
    eng.release([0])
    assert paged_app.kv_mgr.tables == {}


def test_dispatch_fault_preserves_lookahead_and_stream(stack_app):
    """A fault at dispatch time (decode_step point) must NOT poison the
    healthy in-flight step: StepFailure is retry-safe, and retrying
    delivers the exact eager stream."""
    ref = _eager_streams(stack_app, 3, (Q1, Q2))
    eng = PagedEngineAdapter(stack_app)
    res = eng.add_requests([0, 1], [Q1, Q2])
    got = {0: [res[0]], 1: [res[1]]}
    assert eng.step_ahead() == {}
    kv_before = dict(stack_app.kv_mgr.lens)
    with FAULTS.inject("decode_step"):
        with pytest.raises(StepFailure) as ei:
            eng.step_ahead()
    assert ei.value.retry_safe is True
    assert eng._inflight is not None         # lookahead step preserved
    assert dict(stack_app.kv_mgr.lens) == kv_before   # growth rolled back
    for _ in range(2):                       # retry: stream is unharmed
        for s, t in eng.step_ahead().items():
            got[s].append(t)
    for s, t in eng.flush().items():
        got[s].append(t)
    eng.release([0, 1])
    # the failed call dispatched nothing: prefill + 3 delivered decode
    # tokens, bit-identical to the uninterrupted eager stream
    assert got == ref


def test_pipelined_deadline_leaves_pipeline_intact(paged_app):
    """DeadlineExceeded fires BEFORE the pipeline is touched; releasing
    the expired row drains the in-flight step and drops its token."""
    eng = PagedEngineAdapter(paged_app)
    eng.add_requests([0], [P1], deadline_s=0.25)
    assert eng.step_ahead() == {}            # in flight
    with FAULTS.inject("slow_step", delay_s=0.3):
        with pytest.raises(DeadlineExceeded):
            eng.step_ahead()
    assert eng._inflight is not None         # untouched by the deadline
    eng.release([0])                         # drains + drops the token
    assert eng._inflight is None and eng._ready == {}
    assert paged_app.kv_mgr.tables == {}


def test_pipelined_preemption_replays_bit_identical(paged_app):
    """Preemption under KV pressure mid-pipeline: the victim's Preempted
    record (which misses its still-in-flight token) replays to the exact
    uninterrupted greedy stream — acceptance (d)."""
    def eager(prompt, sid, n):
        eng = PagedEngineAdapter(paged_app)
        out = [eng.add_requests([sid], [prompt])[sid]]
        for _ in range(n - 1):
            out.append(eng.step()[sid])
        eng.release([sid])
        return out

    ref0 = eager(P1, 0, 6)
    ref1 = eager(P2, 1, 6)

    eng = PagedEngineAdapter(paged_app, preemption_policy="lifo")
    got0 = [eng.add_requests([0], [P1])[0]]
    assert eng.step_ahead() == {}                    # d1: row 0 only
    got1 = [eng.add_requests([1], [P2])[1]]
    # live set changed by a row that joined: this call carries d1's token
    # into the both-row dispatch on the device, and only then fetches d1
    got0.append(eng.step_ahead()[0])
    assert eng.host_stats["pipeline_carries_admit"] == 1
    assert eng.host_stats["pipeline_drains_admit"] == 0
    with FAULTS.inject("paged_alloc") as fp:         # next grow runs dry
        res = eng.step_ahead()                       # preempts row 1 (LIFO)
    assert fp.trips == 1
    got0.extend(t for s, t in res.items() if s == 0)
    got1.extend(t for s, t in res.items() if s == 1)
    recs = eng.take_preempted()
    assert [r.seq_id for r in recs] == [1]
    assert recs[0].reason == "grow"
    # the in-flight token was never delivered; the record carries only
    # prompt + delivered tokens, and the replay regenerates the rest
    assert list(recs[0].tokens) == P2 + got1
    while len(got0) < 6:
        r = eng.step_ahead()
        if 0 in r:
            got0.append(r[0])
    got0.extend(eng.flush().values())
    assert got0[:6] == ref0[:len(got0[:6])]

    got1b = [eng.add_requests([1], [list(recs[0].tokens)])[1]]
    replay = list(recs[0].tokens[len(P2):]) + got1b
    while len(replay) < 6:
        r = eng.step_ahead([1])
        if 1 in r:
            replay.append(r[1])
    replay.extend(eng.flush().values())
    assert replay[:6] == ref1[:6]
    eng.release([0, 1])


# ---------------------------------------------------------------------------
# a changed live set does not empty the pipeline — acceptance (e)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def bucketed_app():
    """The attention stack with a LADDER of batch buckets (1, 2, 4): a row
    that joins or leaves changes the pad rows too."""
    tcfg = TpuConfig(batch_size=4, seq_len=64, dtype="float32",
                     enable_bucketing=True, enable_2d_bucketing=True,
                     context_encoding_buckets=[16],
                     is_block_kv_layout=True, pa_block_size=8)
    hf = dict(HF, vocab_size=128)
    app = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **hf),
                                   LlamaFamily)
    app.init_random_weights(7).init_cache()
    assert app.batch_buckets == [1, 2, 4]
    return app


@pytest.fixture()
def carry_app(stack_app, paged_app, bucketed_app):
    """``stack_app`` with the bucketed attention app in place of the
    module's two-row one: both scratch kinds, four rows each."""
    return bucketed_app if stack_app is paged_app else stack_app


#: one chunk (final at once), two chunks of 16 (an intermediate one first)
A9, B21, C12, D7 = (RNG.integers(1, 128, size=n).tolist()
                    for n in (9, 21, 12, 7))
BUDGET = 16


def _alone(app, prompt, n):
    """``n`` tokens of ``prompt`` served alone and eagerly: a row's stream
    does not depend on who shares its steps."""
    eng = PagedEngineAdapter(app)
    out = [eng.add_requests([0], [prompt])[0]]
    while len(out) < n:
        out.append(eng.step()[0])
    eng.release([0])
    return out


class _Run:
    """A budgeted adapter driven by hand through ``step_ahead()``."""

    def __init__(self, app, **kw):
        self.app = app
        self.eng = PagedEngineAdapter(app, prefill_budget_tokens=BUDGET, **kw)
        self.got, self.prompts = {}, {}

    def add(self, sid, prompt, **kw):
        assert self.eng.add_requests([sid], [prompt], **kw) == {}
        self.prompts[sid] = prompt
        self.got[sid] = []

    def call(self, n=1, seq_ids=None):
        for _ in range(n):
            res = self.eng.step_ahead(seq_ids)
            for s, t in res.items():
                self.got[s].append(t)
        return res

    def end(self):
        for s, t in self.eng.flush().items():
            self.got[s].append(t)
        self.eng.release(list(self.eng.seqs) + list(self.eng._chunks))
        assert self.app.kv_mgr.tables == {} and not self.eng._unwritten
        assert not self.eng._parked and not self.eng._state_slot
        for sid, toks in self.got.items():
            assert toks == _alone(self.app, self.prompts[sid], len(toks)), sid

    def stat(self, *keys):
        return [self.eng.host_stats[k] for k in keys]


def test_joins_and_leaves_are_carried_not_drained(carry_app):
    """A leave, a budgeted join, a join and a leave in one pass, and (on
    the bucketed attention app) the pad rows changing with each: every
    stream is the row's own eager stream, the step in flight is never
    fetched before the next dispatch, and the only fetch that blocks on a
    prompt's last chunk is the one with no decode row to hide behind."""
    run = _Run(carry_app)
    eng = run.eng
    carries = ("pipeline_carries_admit", "pipeline_carries_release")
    drains = [f"pipeline_drains_{c}" for c in
              ("admit", "release", "preempt", "liveset")]
    run.add(0, A9)
    assert run.call() == {0: run.got[0][0]}      # nobody decodes: fetched
    assert run.stat("prefill_blocking_fetches") == [1]
    assert run.call() == {}                      # d1 fills the pipeline
    run.add(1, B21)
    run.call()                                   # B's first chunk of two
    assert set(run.call()) == {0}                # B's last chunk: parked
    assert eng.pending_prefill_ids == (1,) and 1 not in eng.seqs
    assert len(eng._parked) == 1 and run.got[1] == []
    assert set(run.call()) == {0, 1}             # ... graduates a call later
    assert eng.pending_prefill_ids == () and 1 in eng.seqs
    assert len(run.got[1]) == 1 and not eng._parked
    assert set(run.call()) == {0}                # row 1 joins: carried
    assert run.stat(*carries) == [1, 0]
    assert set(run.call()) == {0, 1}
    run.add(2, C12)
    run.call()                                   # C's only chunk: parked
    assert set(run.call()) == {0, 1, 2}          # graduates
    eng.release([0])                             # ... and row 0 leaves
    assert set(run.call()) == {1}                # both in ONE carry
    assert run.stat(*carries) == [2, 0] and eng._inflight.live == (1, 2)
    assert set(run.call()) == {1, 2}
    eng.release([1])                             # a leave alone
    assert set(run.call()) == {2}
    assert run.stat(*carries) == [2, 1] and eng._inflight.live == (2,)
    run.call(2)
    assert run.stat(*drains) == [0, 0, 0, 0]
    # every decode dispatch but the one that filled the pipeline went out
    # with the step before it unfetched
    n, over = run.stat("dispatches", "overlapped_dispatches")
    assert over == n - 1
    assert run.stat("prefill_blocking_fetches") == [1]
    run.end()
    assert len(run.got[2]) >= 5 and len(run.got[0]) >= 8


def test_a_fault_with_a_row_graduating_rolls_it_back(carry_app):
    """(3): the ``prefill_chunk`` fault at the dispatch that holds a
    prompt's last chunk, at a later dispatch while a row is parked, and the
    ``pipeline_flush`` fault at the fetch it would graduate behind: typed
    failures, every packed sequence rolled back (blocks, state slots,
    nothing left unwritten), tokens fetched before still delivered."""
    run = _Run(carry_app)
    eng, mgr = run.eng, carry_app.kv_mgr
    run.add(0, A9)
    run.call(2)                                  # row 0 decodes, d1 in flight
    free, slots = mgr.allocator.num_free, len(eng._state_free)
    # -- at the final chunk's own dispatch: as an intermediate chunk's ----
    run.add(1, C12)
    with FAULTS.inject("prefill_chunk"):
        with pytest.raises(StepFailure) as ei:
            run.call()
    assert (ei.value.phase, ei.value.retry_safe) == ("prefill", True)
    assert ei.value.seq_ids == (1,) and not eng._chunks and not eng._parked
    assert mgr.allocator.num_free == free and not eng._unwritten
    assert len(eng._state_free) == slots and eng._inflight is not None
    del run.got[1]
    # -- at a later dispatch, a row parked: the parked row is not touched --
    run.add(1, C12)
    run.call()                                   # parked
    run.add(2, D7)
    with FAULTS.inject("prefill_chunk"):
        with pytest.raises(StepFailure) as ei:
            run.call()
    assert ei.value.seq_ids == (2,) and list(eng._chunks) == [1]
    del run.got[2]
    # the pipeline goes on: the step enqueued behind row 1's chunk is
    # fetched by the next call, and row 1 graduates there
    assert set(run.call()) == {0, 1} and len(run.got[1]) == 1
    run.call(2)
    # -- at the fetch a parked row would graduate behind ------------------
    run.add(2, D7)
    run.call()                                   # parked, d(n) in flight
    before = {s: list(t) for s, t in run.got.items()}
    free_running = {s: len(mgr.tables[s]) for s in (0, 1)}
    with FAULTS.inject("pipeline_flush"):
        with pytest.raises(StepFailure) as ei:
            run.call()
    assert (ei.value.phase, ei.value.retry_safe) == ("decode", False)
    assert not eng._chunks and not eng._parked and eng._inflight is None
    assert 2 not in mgr.tables and not eng._unwritten
    assert len(eng._state_free) == max(carry_app.state_slots - 2, 0)
    # rolled back to the last DELIVERED token, which is still the stream's
    for s in (0, 1):
        assert eng.seqs[s].position == len(eng.seqs[s].tokens) - 1
        assert mgr.lens[s] == eng.seqs[s].position
        assert len(mgr.tables[s]) <= free_running[s]
    assert run.got == before
    del run.got[2]
    run.end()


def test_a_deferred_final_chunk_failure_is_a_prefill_failure(bucketed_app,
                                                             monkeypatch):
    """A final chunk nobody waited for that fails at ITS fetch (the decode
    step behind it fetched fine): ``StepFailure`` of phase ``prefill``, not
    retry-safe, the packed sequences rolled back, and the decode step's
    tokens of that call kept for the next one."""
    run = _Run(bucketed_app)
    eng = run.eng
    run.add(0, A9)
    run.call(2)
    run.add(1, C12)
    run.call()                                   # parked
    inner = eng._fetch_prefill_tokens

    def boom(out, waited=True):
        raise RuntimeError("device lost")
    monkeypatch.setattr(eng, "_fetch_prefill_tokens", boom)
    with pytest.raises(StepFailure) as ei:
        run.call()
    monkeypatch.setattr(eng, "_fetch_prefill_tokens", inner)
    assert (ei.value.phase, ei.value.retry_safe) == ("prefill", False)
    assert ei.value.seq_ids == (1,) and not eng._chunks and not eng._parked
    assert 1 not in bucketed_app.kv_mgr.tables and not eng._unwritten
    # what that call fetched is kept, the step on top of the chunk unwound
    assert set(eng._ready) == {0} and eng._inflight is None
    assert eng.seqs[0].position == len(eng.seqs[0].tokens) - 1
    del run.got[1]
    assert set(run.call()) == {0}
    run.call(2)
    run.end()


@pytest.mark.parametrize("how", ["release", "preempt", "deadline"])
def test_a_parked_row_that_leaves_leaves_nothing_behind(carry_app, how):
    """(4): a row released, preempted or expired between its last chunk's
    dispatch and its graduation is passed over where its token arrives."""
    run = _Run(carry_app)
    eng, mgr = run.eng, carry_app.kv_mgr
    run.add(0, A9)
    run.call(2)
    slots = len(eng._state_free)
    run.add(1, C12, deadline_s=None if how != "deadline" else 30.0)
    run.call()                                   # parked
    assert len(eng._parked) == 1 and eng.pending_prefill_ids == (1,)
    if how == "release":
        eng.release([1])
    elif how == "preempt":
        rec = eng.preempt(1)
        assert list(rec.tokens) == C12 and rec.n_generated == 0
        assert [r.seq_id for r in eng.take_preempted()] == [1]
    else:
        eng._chunks[1].deadline = 0.0
        with pytest.raises(DeadlineExceeded) as ei:
            run.call()
        assert tuple(ei.value.seq_ids) == (1,)
        eng.release([1])
    assert not eng._chunks and 1 not in mgr.tables
    assert len(eng._state_free) == slots and not eng._unwritten
    assert set(run.call()) == {0}                # its token arrives: dropped
    assert not eng._parked and 1 not in eng.seqs and 1 not in eng._ready
    del run.got[1]
    # the seq_id re-admitted while its old chunk is still parked: the old
    # chunk's token arrives in the call that parks the new one
    run.add(1, C12)
    run.call()                                   # parked
    old = eng._parked[0]
    eng.release([1])
    run.add(1, D7)
    assert set(run.call()) == {0}                # C's token: not D's row's
    assert len(eng._parked) == 1 and eng._parked[0] is not old
    assert eng.pending_prefill_ids == (1,)
    run.call(3)
    assert len(run.got[1]) == 2                  # D's tokens (``end``)
    run.end()


def test_the_merge_program_is_warmed_with_the_step(paged_app, bucketed_app):
    """(5): ``precompile`` lists the program that makes a carried step's
    ids, one a batch bucket and one a pair of them, and a carry in declared
    steady state is no incident."""
    from neuronx_distributed_inference_tpu.serving.warmup import precompile
    report = precompile(bucketed_app, declare_steady=False)
    carry = [(g["kind"], g["bucket"]) for g in report["graphs"]
             if g["kind"].startswith("carry_ids")]
    assert sorted(carry) == sorted(
        ("carry_ids" if a == b else f"carry_ids_from{a}", b)
        for a in (1, 2, 4) for b in (1, 2, 4))
    report = precompile(paged_app)
    try:
        assert [g["bucket"] for g in report["graphs"]
                if g["kind"].startswith("carry_ids")] == [2]
        eng = PagedEngineAdapter(paged_app)
        eng.add_requests([0, 1], [P1, P2])
        eng.step_ahead()
        eng.release([1])
        eng.step_ahead()
        assert eng.host_stats["pipeline_carries_release"] == 1
        assert paged_app.warmup_state()["incidents"] == []
        eng.release([0])
    finally:
        paged_app.declare_steady_state(False)


# ---------------------------------------------------------------------------
# horizon-aware budgets + satellites
# ---------------------------------------------------------------------------

def test_paged_scratch_invalidated_on_readmission(paged_app):
    """Release + re-admit under the SAME live composition and block count:
    the freed blocks come back in a different ORDER, so a cached block
    table would silently write KV through the old block ids
    (fill_block_table skips rows whose count is unchanged). The scratch
    must be dropped on release/admission and the next dispatch must use
    the fresh table (review regression pin)."""
    p3 = RNG.integers(1, 500, size=len(P2)).tolist()   # same block count
    eng = PagedEngineAdapter(paged_app)
    eng.add_requests([0, 1], [P1, P2])
    eng.step()                               # caches the (0, 1) scratch
    assert eng._scratch is not None
    old_table = list(paged_app.kv_mgr.tables[1])
    eng.release([1])
    assert eng._scratch is None              # invalidated by release
    got3 = [eng.add_requests([1], [p3])[1]]
    assert eng._scratch is None              # invalidated by admission
    # freed blocks come back reordered — the stale-table hazard is real
    assert paged_app.kv_mgr.tables[1] != old_table
    for _ in range(2):
        got3.append(eng.step()[1])
    # the dispatch scratch mirrors the CURRENT block table, not the stale
    # pre-release one
    np.testing.assert_array_equal(
        eng._scratch.bt[1, :len(paged_app.kv_mgr.tables[1])],
        paged_app.kv_mgr.tables[1])
    eng.release([0, 1])
    # token values are block-id independent: the re-admitted stream must
    # match a clean single-request run
    ge = PagedEngineAdapter(paged_app)
    ref3 = [ge.add_requests([1], [p3])[1]]
    for _ in range(2):
        ref3.append(ge.step()[1])
    ge.release([1])
    assert got3 == ref3


def test_pipelined_deadline_keeps_drained_token(paged_app):
    """A recoverable DeadlineExceeded between drain and dispatch must not
    drop an already-generated token from the stream (review regression
    pin): the token stays pending and the next call delivers it."""
    eng = PagedEngineAdapter(paged_app)
    ref = _eager_streams(paged_app, 2)
    eng.add_requests([0, 1], [P1, P2])
    assert eng.step_ahead() == {}            # both-row dispatch in flight
    eng.release([1])                         # nothing blocks: still in flight
    eng.seqs[0].deadline = 0.0               # expire row 0
    with pytest.raises(DeadlineExceeded):
        eng.step_ahead([0])
    eng.seqs[0].deadline = None              # budget raised: call again
    eng.seqs[0].expired_reported = False
    got = eng.step_ahead([0])
    assert got[0] == ref[0][1]               # the drained token, delivered
    eng.release([0])


def test_step_many_horizon_guard(paged_app):
    eng = PagedEngineAdapter(paged_app)
    eng.add_requests([0], [P1])              # position 9 on a seq_len-64 app
    free = paged_app.kv_mgr.allocator.num_free
    with pytest.raises(CapacityError, match="horizon") as ei:
        eng.step_many(60)                    # 9 + 60 > 64: pre-dispatch
    assert ei.value.seq_ids == (0,)
    assert eng.seqs[0].position == len(P1)   # nothing ran
    assert paged_app.kv_mgr.allocator.num_free == free   # nothing grew
    eng.release([0])


def test_host_sync_lint(tmp_path):
    script = REPO / "scripts" / "check_host_sync.py"
    r = subprocess.run([sys.executable, str(script)],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr

    bad = tmp_path / "bad.py"
    bad.write_text(
        "import numpy as np\n"
        "def _dispatch_decode(self, out):\n"
        "    toks = np.asarray(out['tokens'])\n"
        "    return toks.tolist()\n"
        "def retire(out):\n"
        "    return np.asarray(out['tokens'])   # outside the region: ok\n")
    r = subprocess.run([sys.executable, str(script), str(bad)],
                       capture_output=True, text=True)
    assert r.returncode == 1
    assert "asarray" in r.stderr and "_dispatch_decode" in r.stderr
    assert "bad.py:6" not in r.stderr        # outside the region: not flagged

    good = tmp_path / "good.py"
    good.write_text(
        "def _dispatch_decode(self, scr):\n"
        "    out = self.app._run_decode(scr.toks_p, scr.pos_p)\n"
        "    out['tokens'].copy_to_host_async()\n"
        "    return out\n")
    r = subprocess.run([sys.executable, str(script), str(good)],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
