"""A prefill chunk runs as many rows as it carries (ISSUE 27).

A two-phase prefill-chunk dispatch picks its row count from the paged
application's own ladder, ``prefill_row_buckets`` = ``[r_min, batch_size]``
(``r_min`` is 1, or the "dp" mesh extent): one prompt runs ``r_min`` rows,
anything more the full batch. Decode keeps the full batch. Pins:

  (i)   the dispatched shapes, and that a prompt's tokens and final-chunk
        logits are the same prefilled alone or packed at the full batch
        (dense toy, MoE toy — one row of 16 takes the dense expert path,
        the full batch the ragged one — and a LoRA-built toy);
  (ii)  after ``precompile(app, widths=[...])`` admissions of 1, 2 and
        ``batch`` prompts, multi-chunk and under ``prefill_budget_tokens``,
        compile nothing and raise no incident;
  (iii) a failed one-row chunk rolls back as a full-batch one does;
  (iv)  the harness's contract: ``benchmark/run.py`` labels the programs of
        a trace by the report's ``(kind, bucket)``, so every pair occurs
        once, every entry is a distinct jit signature, ``("paged", w)`` is
        the ``r_min``-row program for ``w > 1`` and the full-batch decode
        step for ``w == 1``.

Tiny synthetic models, CPU, float32.
"""

import numpy as np
import pytest

from neuronx_distributed_inference_tpu import telemetry
from neuronx_distributed_inference_tpu.config import (LoraServingConfig,
                                                      TpuConfig)
from neuronx_distributed_inference_tpu.models.application import \
    PagedCausalLMApplication
from neuronx_distributed_inference_tpu.models.family import get_family
from neuronx_distributed_inference_tpu.parallel.mesh import mesh_from_config
from neuronx_distributed_inference_tpu.resilience import FAULTS, StepFailure
from neuronx_distributed_inference_tpu.serving import (LoraAdapterPool,
                                                       PagedEngineAdapter)
from neuronx_distributed_inference_tpu.serving.warmup import precompile
from neuronx_distributed_inference_tpu.telemetry import metrics as tmetrics

HF = dict(model_type="llama", hidden_size=64, intermediate_size=128,
          num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, vocab_size=512, rms_norm_eps=1e-5, rope_theta=10000.0,
          hidden_act="silu", tie_word_embeddings=False,
          torch_dtype="float32")
HF_MOE = dict(HF, model_type="olmoe", num_experts=4, num_experts_per_tok=2,
              num_key_value_heads=4, norm_topk_prob=False)

BATCH = 4
WIDTHS = [16, 32]          # 4 x 32 tokens > MoESpec.dense_max_tokens >= 1 x 32
FAMILIES = ["dense", "moe", "lora"]
RNG = np.random.default_rng(27)
#: three chunks each at chunk 32 (32 + 32 + a tail in the 16 bucket), so a
#: pair packs EVERY dispatch; SHORT fits one 16-wide chunk
P, Q, R, S = (RNG.integers(1, 500, size=n).tolist() for n in (70, 66, 72, 68))
SHORT = RNG.integers(1, 500, size=5).tolist()


def _make_app(family, **over):
    fam = get_family("olmoe" if family == "moe" else "llama")
    kw = dict(batch_size=BATCH, seq_len=96, dtype="float32",
              enable_bucketing=True, context_encoding_buckets=WIDTHS,
              is_block_kv_layout=True, pa_block_size=8,
              is_prefix_caching=False, output_logits=True)
    if family == "lora":
        kw["lora_config"] = LoraServingConfig(
            max_loras=2, max_lora_rank=4, target_modules=["q_proj", "v_proj"])
    kw.update(over)
    tcfg = TpuConfig(**kw)
    hf = HF_MOE if family == "moe" else HF
    app = PagedCausalLMApplication(None, fam.config_cls(tcfg, **hf), fam,
                                   mesh=mesh_from_config(tcfg))
    app.init_random_weights(5).init_cache()
    return app


def _pool(app):
    """One synthetic adapter ``l0`` on a LoRA build, else no pool."""
    if app.spec.lora is None:
        return None
    pool = LoraAdapterPool(app)
    lw, rng = app.params["layers"], np.random.default_rng(3)
    pool.register_arrays("l0", {
        mod: tuple((rng.standard_normal(
            (lw[f"lora_{ab}_{mod}"].shape[0],)
            + lw[f"lora_{ab}_{mod}"].shape[2:]) * 0.3).astype(np.float32)
            for ab in "AB")
        for mod in app.spec.lora.target_modules})
    return pool


@pytest.fixture(scope="module")
def apps():
    """One (app, pool) per family for the whole module, lazily built."""
    built = {}

    def get(family):
        if family not in built:
            app = _make_app(family)
            built[family] = (app, _pool(app))
        return built[family]
    return get


@pytest.fixture
def paged_calls(monkeypatch):
    """Call it with an app: every ``_run_paged`` from then on is recorded
    as ``{"shape", "adapter_ids", "out"}``."""
    def spy(app):
        calls, real = [], app._run_paged

        def run(input_ids, *a, **kw):
            out = real(input_ids, *a, **kw)
            calls.append({"shape": tuple(input_ids.shape), "out": out,
                          "adapter_ids": kw.get("adapter_ids")})
            return out
        monkeypatch.setattr(app, "_run_paged", run)
        return calls
    return spy


def _meta(pool, n):
    """The first prompt of a group rides adapter ``l0`` on a LoRA build."""
    return None if pool is None else [{"adapter": "l0"}] + [None] * (n - 1)


def _serve(app, pool, prompts, n_decode=3, **adapter_kw):
    """Admit ``prompts`` in one call, decode, release; the streams by
    position in ``prompts``."""
    eng = PagedEngineAdapter(app, lora_pool=pool, **adapter_kw)
    sids = list(range(len(prompts)))
    first = eng.add_requests(sids, prompts, meta=_meta(pool, len(prompts)))
    streams = {s: [first[s]] for s in first}
    while any(len(streams.get(s, ())) < 1 + n_decode for s in sids):
        for s, t in eng.step().items():
            streams.setdefault(s, []).append(t)
    eng.release(sids)
    assert app.kv_mgr.tables == {}
    return streams, eng


def _final_logits(calls, row, n_tail):
    """Logits at the last real position of ``row`` in the last prefill
    dispatch recorded (the final chunk, ``n_tail`` real tokens)."""
    chunk = [c for c in calls if c["shape"][1] > 1][-1]
    return np.asarray(chunk["out"]["logits"])[row, n_tail - 1]


# ---------------------------------------------------------------------------
# (i) shapes follow the prompts packed; the mathematics does not move
# ---------------------------------------------------------------------------

def test_ladder_is_two_rungs(apps):
    app, _ = apps("dense")
    assert app.prefill_row_buckets == [1, BATCH]
    assert app.batch_buckets == [BATCH]          # decode's ladder, untouched


@pytest.mark.parametrize("family", FAMILIES)
def test_rows_follow_prompts_packed(apps, paged_calls, family):
    app, pool = apps(family)
    calls = paged_calls(app)
    alone, _ = _serve(app, pool, [P])
    # 70 tokens = 32 + 32 + 6: one row each, then full-batch decode steps
    assert [c["shape"] for c in calls] == (
        [(1, 32), (1, 32), (1, 16)] + [(BATCH, 1)] * 3)
    if pool is not None:     # the adapter-slot vector shrinks with the rows
        assert [np.shape(c["adapter_ids"]) for c in calls[:3]] == [(1,)] * 3
    logits_alone = _final_logits(calls, 0, 6)
    for group in ([P, Q], [P, Q, R, S]):
        del calls[:]
        packed, eng = _serve(app, pool, group)
        # two up to batch prompts: every chunk dispatch runs the full batch
        assert [c["shape"] for c in calls[:3]] == [
            (BATCH, 32), (BATCH, 32), (BATCH, 16)]
        if pool is not None:
            assert np.shape(calls[0]["adapter_ids"]) == (BATCH,)
        assert eng.host_stats["prefill_padded_tokens"] == BATCH * (32 * 2 + 16)
        assert packed[0] == alone[0]
        np.testing.assert_allclose(_final_logits(calls, 0, 6), logits_alone,
                                   rtol=1e-4, atol=1e-5)


def test_short_prompt_alone_runs_one_row_at_the_small_bucket(apps,
                                                             paged_calls):
    app, pool = apps("dense")
    calls = paged_calls(app)
    _, eng = _serve(app, pool, [SHORT], n_decode=1)
    assert calls[0]["shape"] == (1, 16) and calls[1]["shape"] == (BATCH, 1)
    assert (eng.host_stats["prefill_real_tokens"],
            eng.host_stats["prefill_padded_tokens"]) == (5, 16)


def test_row_choice_is_counted_under_its_own_kind(apps):
    app, pool = apps("dense")
    telemetry.disable()
    reg = telemetry.enable()                   # a fresh registry
    try:
        def picks(kind):
            series = reg.snapshot()["metrics"].get(
                tmetrics.BUCKET_SELECTED_TOTAL, {}).get("series", [])
            return {s["labels"]["bucket"]: s["value"] for s in series
                    if s["labels"]["kind"] == kind}
        _serve(app, pool, [P, SHORT], n_decode=0)
        # dispatch 1 carries both prompts, 2 and 3 carry P alone
        assert picks("prefill_rows") == {"1": 2.0, str(BATCH): 1.0}
        decode_picks = picks("batch")          # decode's series, apart
        _serve(app, pool, [SHORT], n_decode=0)
        assert picks("prefill_rows") == {"1": 3.0, str(BATCH): 1.0}
        assert picks("batch") == decode_picks
    finally:
        telemetry.disable()


def test_r_min_is_the_dp_extent_on_a_mesh(paged_calls):
    """Batch rows shard over "dp": the low rung is the smallest row count
    that axis divides, and a prompt admitted alone runs that many rows."""
    app = _make_app("dense", tp_degree=4, attention_dp_degree=2,
                    context_encoding_buckets=[16], output_logits=False)
    assert (app.mesh.shape["dp"], app.mesh.shape["tp"]) == (2, 2)
    assert app.prefill_row_buckets == [2, BATCH]
    rep = precompile(app, widths=[1, 16])
    assert [(g["kind"], g["bucket"]) for g in rep["graphs"]] == [
        ("ragged", 1), ("paged", 1), ("ragged", 16), ("paged", 16),
        ("paged_pack", 16), ("carry_ids", BATCH)]
    calls = paged_calls(app)
    _serve(app, None, [SHORT], n_decode=1)
    assert [c["shape"] for c in calls] == [(2, 16), (BATCH, 1)]
    assert app.warmup_state()["incidents"] == []


# ---------------------------------------------------------------------------
# (ii) the warm plans cover what the packer can pick
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_steady_state_admissions_compile_nothing(family):
    app = _make_app(family)
    pool = _pool(app)
    precompile(app, widths=[1] + WIDTHS)
    seen = app.warmup_state()["graphs_seen"]
    for budget in (None, 24):          # eager chains; one chunk a step()
        for group in ([P], [SHORT], [P, SHORT], [P, Q, R, S]):
            _serve(app, pool, group, n_decode=2,
                   prefill_budget_tokens=budget)
    ws = app.warmup_state()
    assert ws["steady_state"] and ws["incidents"] == []
    assert ws["graphs_seen"] == seen


def test_app_warmup_covers_both_rungs():
    app = _make_app("dense").warmup()
    seen = app.warmup_state()["graphs_seen"]
    app.declare_steady_state()
    for group in ([P], [P, SHORT], [P, Q, R, S]):
        _serve(app, None, group, n_decode=2)
    ws = app.warmup_state()
    assert ws["incidents"] == [] and ws["graphs_seen"] == seen


# ---------------------------------------------------------------------------
# (iii) a failed chunk rolls back whatever its row count
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("group", [[P], [P, Q]], ids=["rows1", "rows-batch"])
def test_failed_chunk_rolls_back(apps, paged_calls, group):
    app, pool = apps("dense")
    want, _ = _serve(app, pool, group, n_decode=0)
    calls = paged_calls(app)
    free0 = app.kv_mgr.allocator.num_free
    eng = PagedEngineAdapter(app)
    sids = list(range(len(group)))
    with FAULTS.inject("prefill_chunk", nth=2) as fp:
        with pytest.raises(StepFailure) as ei:
            eng.add_requests(sids, group)
    assert fp.trips == 1 and ei.value.phase == "prefill"
    # the first chunk ran at the row count under test, then the fault
    assert [c["shape"] for c in calls] == [
        (1 if len(group) == 1 else BATCH, 32)]
    assert eng.seqs == {} and eng._chunks == {} and eng._ready == {}
    assert app.kv_mgr.tables == {} and eng._unwritten == set()
    assert app.kv_mgr.allocator.num_free == free0
    # a retry serves the clean first tokens: nothing stale was left
    first = eng.add_requests(sids, group)
    assert [first[s] for s in sids] == [want[s][0] for s in sids]
    eng.release(sids)


# ---------------------------------------------------------------------------
# (iv) the report names each program once (benchmark/run.py's contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["dense", "lora"])
def test_precompile_report_names_each_program_once(paged_calls, family):
    app = _make_app(family)
    calls = paged_calls(app)
    rep = precompile(app, widths=[1] + WIDTHS)
    pairs = [(g["kind"], g["bucket"]) for g in rep["graphs"]]
    assert len(set(pairs)) == len(pairs)
    # every entry a jit signature of its own: none was warm when it ran
    assert rep["n_warm_hits"] == 0
    assert app.warmup_state()["graphs_seen"] == rep["n_graphs"]
    # which program each two-phase entry is: ("paged", 1) the full-batch
    # decode step, ("paged", w > 1) the r_min-row chunk program, its
    # full-batch twin a kind of its own; *_lora carry one slot id a row
    two_phase = [p for p in pairs if p[0].startswith("paged")]
    lora = [""] + (["_lora"] if family == "lora" else [])
    assert two_phase == (
        [("paged" + sfx, 1) for sfx in lora]
        + [(kind + sfx, w) for w in WIDTHS
           for kind in ("paged", "paged_pack") for sfx in lora])
    for (kind, w), call in zip(two_phase, calls):
        rows = 1 if w > 1 and not kind.startswith("paged_pack") else BATCH
        assert call["shape"] == (rows, w), (kind, w)
        assert np.shape(call["adapter_ids"]) == (
            (rows,) if kind.endswith("_lora") else ())
    # the benchmark's calibration walks one width at a time: the same
    # programs again, each exactly once, under the same unique pairs
    seen = app.warmup_state()["graphs_seen"]
    walked = []
    for w in [1] + WIDTHS:
        again = precompile(app, widths=[w])
        assert again["n_warm_hits"] == again["n_graphs"]
        walked += [(g["kind"], g["bucket"]) for g in again["graphs"]]
    # (the program that makes a carried step's ids goes with width 1 there
    # and last in the whole plan)
    assert sorted(walked) == sorted(pairs) and walked.index(
        ("carry_ids", BATCH)) < walked.index(("paged", WIDTHS[0]))
    assert app.warmup_state()["graphs_seen"] == seen
