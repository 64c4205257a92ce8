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
  (d) deadline and preemption paths still work with a step in flight.

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
    # live-set change drains the in-flight both-row dispatch synchronously
    for s, t in eng.step_ahead([0]).items():
        got[s].append(t)
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
    # live set changed: this call drains d1 and dispatches both rows
    got0.append(eng.step_ahead()[0])
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
    eng.release([1])                         # drains; row 0's token pends
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
