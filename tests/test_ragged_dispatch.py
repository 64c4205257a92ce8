"""Ragged unified dispatch: ONE mixed prefill+decode+verify dispatch per
engine step (ISSUE 13, ROADMAP item 1).

Acceptance pins:
  (a) under mixed load (pending prefill chunks + live decode rows + k>0
      verify windows in the SAME step) a ragged engine step runs EXACTLY
      ONE materialized dispatch — dispatch-count pinned per step;
  (b) token streams are bit-identical to the current interleaved
      two-phase path WITHOUT speculation (plain decode rows) and WITH
      speculation (self-draft accept pinned at exactly 1.0; the
      perturbed proposer's fixed partial accept rate unchanged);
  (c) the ``ragged_step`` fault point rolls EVERY packed row back to its
      last accepted/delivered token: live rows retry-heal on their exact
      streams, packed prefill rows are requeued as ``Preempted``
      records (``reason="ragged_rollback"``) and replay bit-identically;
  (d) pending-admission deadlines keep the chunked-prefill semantics
      (targeted expiry raises before device work, untargeted is skipped);
  (e) ``ServingEngine.run_pass`` routes through the planner (one
      materialized dispatch per pass), budgets stay exact, and streams
      equal the non-ragged engine's;
  (f) the unified ``ragged_row_buckets`` ladder replaces the
      prefill-chunk and spec-width ladders, whose public functions stay
      as behavior-identical deprecated wrappers;
  (g) the ragged package rides the error-paths lint, the host-sync
      walker derives the ``_dispatch_ragged`` region (rename-red), and
      the new telemetry flows.

One tiny-model compile set for the whole module (870s tier-1 budget;
target <20s warm like test_spec_serving.py). Prefix caching stays ON.
"""

import shutil
from pathlib import Path

import numpy as np
import pytest

from conftest import load_nxdi_lint
from neuronx_distributed_inference_tpu import telemetry
from neuronx_distributed_inference_tpu.config import TpuConfig
from neuronx_distributed_inference_tpu.models.application import \
    PagedCausalLMApplication
from neuronx_distributed_inference_tpu.models.llama import (
    LlamaFamily, LlamaInferenceConfig)
from neuronx_distributed_inference_tpu.modules import autobucketing
from neuronx_distributed_inference_tpu.resilience import (
    FAULTS, ConfigurationError, DeadlineExceeded, StepFailure)
from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter
from neuronx_distributed_inference_tpu.serving.engine import ServingEngine
from neuronx_distributed_inference_tpu.serving.speculation import (
    PerturbedSelfDraftProposer, SelfDraftProposer)
from neuronx_distributed_inference_tpu.telemetry import metrics as tmetrics

nxdi_lint = load_nxdi_lint()
analysis = nxdi_lint.load_analysis()

REPO = Path(__file__).resolve().parent.parent
PKG = REPO / "neuronx_distributed_inference_tpu"

HF = dict(model_type="llama", hidden_size=64, intermediate_size=128,
          num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, vocab_size=512, rms_norm_eps=1e-5, rope_theta=10000.0,
          hidden_act="silu", tie_word_embeddings=False,
          torch_dtype="float32")

RNG = np.random.default_rng(31)
P_A = RNG.integers(1, 500, size=9).tolist()
P_B = RNG.integers(1, 500, size=12).tolist()
P_LONG = RNG.integers(1, 500, size=24).tolist()   # 2 chunks of 16


@pytest.fixture(scope="module")
def app():
    tcfg = TpuConfig(batch_size=2, seq_len=64, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     is_block_kv_layout=True, pa_block_size=8,
                     pa_num_blocks=24, is_prefix_caching=True)
    a = PagedCausalLMApplication(None, LlamaInferenceConfig(tcfg, **HF),
                                 LlamaFamily)
    a.init_random_weights(7).init_cache()
    return a


def _eager_stream(app, prompt, n_decode, sid=0):
    """Two-phase reference: prompt's first token + n_decode decode
    tokens through the interleaved (non-ragged) path."""
    eng = PagedEngineAdapter(app)
    out = [eng.add_requests([sid], [prompt])[sid]]
    for _ in range(n_decode):
        out.append(eng.step()[sid])
    eng.release([sid])
    return out


def _collect(eng, sids, want, max_steps=60):
    """Drive a ragged adapter until every stream holds ``want`` tokens;
    returns (streams, steps taken)."""
    got = {s: [] for s in sids}
    steps = 0
    while any(len(got[s]) < want for s in sids):
        for s, toks in eng.step().items():
            if s in got:               # other live rows keep decoding
                got[s].extend(toks)
        steps += 1
        assert steps < max_steps, "ragged decode made no progress"
    return got, steps


# ---------------------------------------------------------------------------
# unified ladder + deprecated wrappers — acceptance (f)
# ---------------------------------------------------------------------------

def test_unified_ladder_and_deprecated_wrappers():
    """ragged_row_buckets spans width 1 up through the chunk-capped ctx
    buckets in ONE ladder; the old prefill-chunk and spec-width ladder
    functions survive as wrappers with their exact historical values."""
    ctx = [16, 32, 64, 128]
    assert autobucketing.ragged_row_buckets(ctx) == \
        [1, 2, 4, 8, 16, 32, 64, 128]
    assert autobucketing.ragged_row_buckets(ctx, 16) == [1, 2, 4, 8, 16]
    # deprecated wrappers: bit-for-bit the pre-ragged return values
    assert autobucketing.prefill_chunk_buckets(ctx) == ctx
    assert autobucketing.prefill_chunk_buckets(ctx, 16) == [16]
    assert autobucketing.prefill_chunk_buckets(ctx, 40) == [16, 32, 64]
    assert autobucketing.spec_width_buckets(4) == [1, 2, 4]
    assert autobucketing.spec_width_buckets(8) == [1, 2, 4, 8]
    assert autobucketing.spec_width_buckets(1) == [1]
    with pytest.raises(ValueError):
        autobucketing.spec_width_buckets(0)


# ---------------------------------------------------------------------------
# bit-identity, no speculation — acceptance (b)
# ---------------------------------------------------------------------------

def test_ragged_matches_eager_cold_then_warm(app):
    """Plain ragged decode (no speculation): deferred admission + unified
    dispatches deliver streams bit-identical to the two-phase path, cold
    AND over the warm prefix cache, with exactly one materialized
    dispatch per engine step and zero standalone prefill dispatches."""
    ref = {0: _eager_stream(app, P_A, 7),
           1: _eager_stream(app, P_B, 7, sid=1)}
    for _ in range(2):                       # cold, then warm prefixes
        eng = PagedEngineAdapter(app, ragged=True)
        assert eng.add_requests([0, 1], [P_A, P_B]) == {}
        got, steps = _collect(eng, [0, 1], 8)
        st = dict(eng.host_stats)
        eng.release([0, 1])
        for s in (0, 1):
            assert got[s][:8] == ref[s][:8]
        # one unified dispatch = one blocking fetch per step; the
        # two-phase path's separate chunk dispatches never run
        assert st["ragged_dispatches"] == steps
        assert st["blocking_fetches"] == steps
        assert st["prefill_dispatches"] == 0
        assert st["prefill_blocking_fetches"] == 0
        assert st["ragged_rows_prefill"] == 2
        assert st["ragged_rows_decode"] > 0


# ---------------------------------------------------------------------------
# bit-identity + accept-rate pins, with speculation — acceptance (b)
# ---------------------------------------------------------------------------

def test_ragged_spec_matches_eager_accept_one(app):
    """Ragged + self-draft k=3: streams bit-identical to eager, accept
    rate pinned at exactly 1.0 (drafted == accepted), and the token
    count arrives in far fewer unified dispatches than eager steps."""
    ref = {0: _eager_stream(app, P_A, 11),
           1: _eager_stream(app, P_B, 11, sid=1)}
    eng = PagedEngineAdapter(app, ragged=True,
                             speculation=SelfDraftProposer(3))
    assert eng.add_requests([0, 1], [P_A, P_B]) == {}
    got, steps = _collect(eng, [0, 1], 12)
    st = dict(eng.host_stats)
    eng.release([0, 1])
    for s in (0, 1):
        assert got[s][:12] == ref[s][:12]
    assert st["spec_drafted_tokens"] == st["spec_accepted_tokens"] > 0
    assert st["ragged_dispatches"] == steps
    assert st["blocking_fetches"] == steps
    assert steps <= 5                  # 12 tokens in <=5 unified steps
    assert st["ragged_rows_verify"] > 0


def test_ragged_perturbed_partial_accept(app):
    """A perturbed draft under ragged keeps the FIXED partial accept
    rate of the standalone spec path (corrupt_at=1 accepts exactly one
    draft + bonus per full-width step) and still delivers bit-identical
    streams — draft quality costs dispatches, never correctness."""
    ref = _eager_stream(app, P_A, 9)
    eng = PagedEngineAdapter(
        app, ragged=True,
        speculation=PerturbedSelfDraftProposer(3, corrupt_at=1))
    eng.add_requests([0], [P_A])
    got, _ = _collect(eng, [0], 10)
    st = dict(eng.host_stats)
    eng.release([0])
    assert got[0][:10] == ref[:10]
    # full-width steps accept exactly 1 of 3 drafts; clamped trailing
    # steps keep the ratio below 1/2 and above 0
    assert 0 < st["spec_accepted_tokens"] < st["spec_drafted_tokens"]


# ---------------------------------------------------------------------------
# mixed load: ONE materialized dispatch per engine step — acceptance (a)
# ---------------------------------------------------------------------------

def test_mixed_load_exactly_one_materialized_dispatch(app):
    """Decode + k>0 verify windows + a COLD 2-chunk pending prefill live
    in the SAME steps: every engine step is exactly one ragged dispatch
    and one blocking fetch (the draft pass stays device-resident), all
    three row kinds ride it, and the late prompt's stream is
    bit-identical to the interleaved path (eager streams are prefix-
    warmth-invariant — pinned by test_chunked_prefill — so the golden is
    computed after the ragged run)."""
    p_mix = RNG.integers(1, 500, size=24).tolist()   # cold: 2 chunks of 16
    eng = PagedEngineAdapter(app, ragged=True,
                             speculation=SelfDraftProposer(3))
    eng.add_requests([0], [P_A])
    got0, _ = _collect(eng, [0], 3)          # row 0 decoding
    eng.add_requests([1], [p_mix])
    long_stream = []
    for step in range(2):                    # chunk 1, then final chunk
        before = dict(eng.host_stats)
        res = eng.step()
        delta = {k: eng.host_stats[k] - before[k] for k in before}
        assert delta["ragged_dispatches"] == 1
        assert delta["blocking_fetches"] == 1
        assert delta["prefill_dispatches"] == 0
        assert delta["prefill_blocking_fetches"] == 0
        assert delta["ragged_rows_prefill"] == 1
        assert delta["ragged_rows_verify"] == 1     # row 0 speculates on
        # the planner's padding: a 16- then 8-token chunk beside row 0's
        # 4-wide verify window, two rows at the chunk's width bucket
        assert (delta["ragged_real_tokens"],
                delta["ragged_padded_tokens"]) == ((20, 32), (12, 16))[step]
        long_stream.extend(res.get(1, []))
        got0[0].extend(res.get(0, []))
    assert len(long_stream) == 1             # first token from final chunk
    while len(long_stream) < 5:
        before = dict(eng.host_stats)
        res = eng.step()
        assert eng.host_stats["ragged_dispatches"] \
            - before["ragged_dispatches"] == 1
        assert eng.host_stats["blocking_fetches"] \
            - before["blocking_fetches"] == 1
        long_stream.extend(res.get(1, []))
    eng.release([0, 1])
    assert long_stream[:5] == _eager_stream(app, p_mix, 4, sid=1)[:5]


# ---------------------------------------------------------------------------
# ragged_step fault: rollback + retry + prefill requeue — acceptance (c)
# ---------------------------------------------------------------------------

def test_ragged_step_fault_rolls_back_and_retry_heals(app):
    """An armed ragged_step fault surfaces as typed StepFailure
    (phase="ragged"): the live row's KV growth is shrunk with its
    position untouched (a plain retry continues the exact stream), and
    the packed prefill row is requeued as a Preempted record whose
    replay admission is bit-identical — the free pool is restored
    exactly."""
    ref0 = _eager_stream(app, P_A, 6)
    ref1 = _eager_stream(app, P_LONG, 2, sid=1)
    eng = PagedEngineAdapter(app, ragged=True)
    eng.add_requests([0], [P_A])
    got0, _ = _collect(eng, [0], 3)
    mgr = app.kv_mgr
    free_before = int(mgr.allocator.num_free)   # pre-admission: the
    # evicted admission must hand back every block it took
    eng.add_requests([1], [P_LONG])
    pos_before = eng.seqs[0].position
    with FAULTS.inject("ragged_step") as fp:
        with pytest.raises(StepFailure) as ei:
            eng.step()
    assert fp.trips == 1
    assert ei.value.phase == "ragged"
    assert ei.value.retry_safe
    # live row untouched; pending admission evicted with a replay record
    assert eng.seqs[0].position == pos_before
    assert 1 not in eng._chunks
    recs = eng.take_preempted()
    assert [r.seq_id for r in recs] == [1]
    assert recs[0].reason == "ragged_rollback"
    assert list(recs[0].tokens) == list(P_LONG)
    assert recs[0].n_generated == 0
    # every block the plan allocated/grew came back
    assert int(mgr.allocator.num_free) == free_before
    # retry heals: row 0 continues its exact stream
    more, _ = _collect(eng, [0], 3)
    got0[0].extend(more[0])
    assert got0[0][:6] == ref0[:6]
    # replaying the record is the ordinary re-admission path
    eng.add_requests([recs[0].seq_id], [list(recs[0].tokens)])
    replay, _ = _collect(eng, [1], 3)
    assert replay[1][:3] == ref1[:3]
    eng.release([0, 1])


# ---------------------------------------------------------------------------
# pending-admission deadlines — acceptance (d)
# ---------------------------------------------------------------------------

def test_pending_deadline_targeted_raises_untargeted_skipped(app):
    """An expired pending admission raises DeadlineExceeded only when
    the step targets it; a step scoped to the healthy running row
    proceeds (zero stall) and packs no expired chunk rows."""
    eng = PagedEngineAdapter(app, ragged=True)
    eng.add_requests([0], [P_A])
    _collect(eng, [0], 2)
    eng.add_requests([1], [P_LONG], deadline_s=[0.0])   # expired at birth
    before = dict(eng.host_stats)
    res = eng.step([0])                  # healthy row only: no raise
    assert 0 in res
    assert eng.host_stats["ragged_rows_prefill"] \
        == before["ragged_rows_prefill"]
    with pytest.raises(DeadlineExceeded) as ei:
        eng.step()                       # targeting all: the expiry fires
    assert list(ei.value.seq_ids) == [1]
    eng.release([0, 1])


# ---------------------------------------------------------------------------
# engine integration — acceptance (e)
# ---------------------------------------------------------------------------

def test_engine_run_pass_routes_through_planner(app):
    """ServingEngine over a ragged adapter: every pass is at most one
    materialized dispatch (prefill + decode + verify all ride it),
    streams are bit-identical to the non-ragged engine, and token
    budgets stay exact."""
    prompts = [P_A, P_B]
    eng = ServingEngine(PagedEngineAdapter(app))
    ref_streams = [eng.submit(p, 6) for p in prompts]
    eng.run_until_drained()
    refs = [s.drain() for s in ref_streams]

    ad = PagedEngineAdapter(app, ragged=True,
                            speculation=SelfDraftProposer(3))
    eng = ServingEngine(ad)
    streams = [eng.submit(p, 6) for p in prompts]
    passes = 0
    while eng.has_work:
        before = dict(ad.host_stats)
        eng.run_pass()
        passes += 1
        assert ad.host_stats["ragged_dispatches"] \
            - before["ragged_dispatches"] <= 1
        assert ad.host_stats["blocking_fetches"] \
            - before["blocking_fetches"] <= 1
        assert ad.host_stats["prefill_dispatches"] \
            - before["prefill_dispatches"] == 0
        assert passes < 50
    got = [s.drain() for s in streams]
    assert got == refs
    assert all(len(g) == 6 for g in got)       # token budget exact
    assert all(s.finish_reason == "length" for s in streams)


def test_engine_heals_ragged_fault_mid_serve(app):
    """A ragged_step fault mid-serve is a retry-safe engine event: live
    rows retry, the packed admission's Preempted record is requeued by
    the next pass, and every stream still finishes bit-identical."""
    prompts = [P_A, P_LONG]
    eng = ServingEngine(PagedEngineAdapter(app))
    ref_streams = [eng.submit(p, 5) for p in prompts]
    eng.run_until_drained()
    refs = [s.drain() for s in ref_streams]

    ad = PagedEngineAdapter(app, ragged=True)
    eng = ServingEngine(ad)
    streams = [eng.submit(p, 5) for p in prompts]
    eng.run_pass()
    with FAULTS.inject("ragged_step"):
        eng.run_pass()                         # retry-safe StepFailure
    eng.run_until_drained()
    assert [s.drain() for s in streams] == refs
    assert eng.stats["step_retries"] >= 1


# ---------------------------------------------------------------------------
# guards, telemetry, lint — acceptance (g)
# ---------------------------------------------------------------------------

def test_ragged_config_guards(app):
    """Unseeded-sampling refusal mirrors speculative serving (seeded
    sampling is supported; do_sample without stream_seed is not);
    token_room stays a unified/speculative hook on the plain adapter."""
    import dataclasses
    from neuronx_distributed_inference_tpu.config import \
        OnDeviceSamplingConfig
    sampled = dataclasses.replace(
        app.tpu_config,
        on_device_sampling_config=OnDeviceSamplingConfig(do_sample=True))
    orig = app.tpu_config
    try:
        app.tpu_config = sampled
        with pytest.raises(ConfigurationError):
            PagedEngineAdapter(app, ragged=True)
    finally:
        app.tpu_config = orig
    with pytest.raises(ConfigurationError):
        PagedEngineAdapter(app).step(token_room={0: 1})


def test_ragged_telemetry_and_debug_state(app):
    """nxdi_ragged_rows_total flows per kind, the pad-waste gauge tracks
    the last dispatch, and debug_state reports ragged mode."""
    reg = telemetry.MetricsRegistry()
    eng = PagedEngineAdapter(app, telemetry=reg, ragged=True,
                             speculation=SelfDraftProposer(3))
    eng.add_requests([0, 1], [P_A, P_LONG])
    _collect(eng, [0, 1], 4)
    state = eng.debug_state()
    eng.release([0, 1])
    assert state["ragged"] is True
    snap = reg.snapshot()["metrics"]
    rows = snap[tmetrics.RAGGED_ROWS_TOTAL]["series"]
    kinds = {s["labels"]["kind"] for s in rows if s["value"] > 0}
    assert {"prefill", "verify"} <= kinds
    waste = snap[tmetrics.RAGGED_PAD_WASTE]["series"]
    assert waste, "pad-waste gauge never set"
    assert all(0.0 <= s["value"] < 1.0 for s in waste)


def test_lint_covers_ragged_package(tmp_path):
    """error-paths lints the three ragged files, the host-sync walker
    derives the _dispatch_ragged region on the live tree, and renaming
    it away from the prefix goes RED by derivation (it still issues the
    dispatch primitive without materializing)."""
    ep = analysis.get_pass("error-paths")
    assert {"neuronx_distributed_inference_tpu/serving/ragged/planner.py",
            "neuronx_distributed_inference_tpu/serving/ragged/path.py",
            "neuronx_distributed_inference_tpu/serving/ragged/__init__.py"
            } <= set(ep.default_paths)
    hs = analysis.get_pass("host-sync")
    import importlib
    mod = importlib.import_module(type(hs).__module__)
    ctx = analysis.LintContext(REPO)
    rel = "neuronx_distributed_inference_tpu/serving/ragged/path.py"
    assert rel in hs.default_paths
    assert "_dispatch_ragged" in mod.region_functions(ctx.source(rel))
    # live tree: green on the ragged files
    findings = hs.run(analysis.LintContext(REPO))
    assert not [f for f in findings if "ragged" in f.file], \
        [f.render() for f in findings]
    # rename-red: the derived guard follows the dispatch work, not a list
    fake_pkg = tmp_path / "neuronx_distributed_inference_tpu" / "serving" \
        / "ragged"
    fake_pkg.mkdir(parents=True)
    doctored = (PKG / "serving" / "ragged" / "path.py").read_text() \
        .replace("_dispatch_ragged", "_issue_ragged")
    (fake_pkg / "path.py").write_text(doctored)
    shutil.copy(PKG / "serving" / "ragged" / "planner.py",
                fake_pkg / "planner.py")
    red = hs.run(analysis.LintContext(tmp_path))
    assert any("_issue_ragged" in f.message and "_dispatch prefix"
               in f.message for f in red), [f.render() for f in red]


def test_spec_ctx_cand_pad_rows_are_row0_clones(app):
    """The spec context handed to proposers must honor the row contract
    (live rows, then ROW-0 CLONES) even when the ragged grid's rows past
    the live prefix are PREFILL chunks: feature-refreshing proposers
    (EAGLE) scatter ``ctx.cand`` at row-0-cloned positions, so duplicate
    writes must stay value-identical — a prefill row leaking into the
    cand padding would corrupt row 0's draft state nondeterministically."""
    seen = {}

    class Probe(SelfDraftProposer):
        name = "probe"

        def on_verify(self, ctx, tokens, n_emit, hidden):
            if ctx.cand is not None:
                seen["cand"] = np.asarray(ctx.cand)
                seen["n_live"] = ctx.b
                seen["padded"] = ctx.padded_batch

    eng = PagedEngineAdapter(app, ragged=True, speculation=Probe(3))
    eng.add_requests([0], [P_A])
    _collect(eng, [0], 2)
    eng.add_requests([1], [RNG.integers(1, 500, size=24).tolist()])
    eng.step()       # mixed grid: 1 verify row + 1 prefill row, pad_to 2
    eng.release([0, 1])
    cand, n_live = seen["cand"], seen["n_live"]
    assert n_live == 1 and seen["padded"] == 2 == cand.shape[0]
    assert (cand[1] == cand[0]).all(), \
        "cand padding leaked a non-row-0 (prefill) row"


def test_ragged_step_many_token_budget(app):
    """step_many(n) on a ragged adapter is a TOKEN budget: every row
    delivers exactly n tokens (speculative widths clamp, never
    overshoot), bit-identical to eager."""
    ref = _eager_stream(app, P_A, 6)
    eng = PagedEngineAdapter(app, ragged=True,
                             speculation=SelfDraftProposer(3))
    eng.add_requests([0], [P_A])
    first = _collect(eng, [0], 1)[0][0]
    out = eng.step_many(6)
    eng.release([0])
    assert len(out[0]) == 6
    assert first + out[0] == ref[:7]
