"""A recurrent/hybrid stack on the paged serving path (ISSUE 30).

``granitemoehybrid`` (Mamba-2 mixers interleaved with GQA attention, the
architecture of granite-4.0-h-micro) served through ``PagedEngineAdapter``
with default arguments, at a toy size on the CPU in float32. The recurrent
state is a second per-sequence cache beside the KV pool: one slot a live
sequence, taken at admission, and the rows of a full-batch step ARE the
slots. Every test holds the LOGITS of the served path, at every position a
dispatch computed, to the plain reference
``benchmark/references/granitemoehybrid.py`` (itself held to HF's
``GraniteMoeHybridForCausalLM`` by ``benchmark/tests/test_reference.py`` and
by (f) here):

  (a) a prompt walked in three chunks with a padded last one, then decode;
  (b) two prompts packed as rows of one dispatch beside a decoding row;
  (c) a slot reused by a new request after release gives the logits of a
      fresh engine;
  (d) preempt and resume;
  (e) a rolled-back admission leaves every slot free;
  (f) converted HF weights give HF's logits;

and the edges: what stays refused for such a stack (one table, one
sentence each), the warm-up plan (only the programs it can run, one
``(kind, bucket)`` pair each, nothing compiled afterwards), the slot
counters and metrics.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax.numpy as jnp  # noqa: E402
from harness import build, weights  # noqa: E402

from neuronx_distributed_inference_tpu import telemetry  # noqa: E402
from neuronx_distributed_inference_tpu.config import TpuConfig  # noqa: E402
from neuronx_distributed_inference_tpu.models import model_base  # noqa: E402
from neuronx_distributed_inference_tpu.models.application import \
    PagedCausalLMApplication  # noqa: E402
from neuronx_distributed_inference_tpu.models.family import \
    get_family  # noqa: E402
from neuronx_distributed_inference_tpu.resilience import (  # noqa: E402
    FAULTS, ConfigurationError, StepFailure)
from neuronx_distributed_inference_tpu.serving import \
    PagedEngineAdapter  # noqa: E402
from neuronx_distributed_inference_tpu.serving.warmup import (  # noqa: E402
    memory_ledger, precompile)
from neuronx_distributed_inference_tpu.telemetry import \
    metrics as tmetrics  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "tests", "reference_cases",
                       "granitemoehybrid.json")) as _f:
    HF = json.load(_f)["config"]     # 4 layers: mamba, mamba, attention, mamba

BATCH = 4
SERVE = dict(batch_size=BATCH, seq_len=96, pa_block_size=8, pa_num_blocks=48,
             context_encoding_buckets=[8, 16], enable_bucketing=True,
             is_block_kv_layout=True, is_prefix_caching=False)
RNG = np.random.default_rng(30)
#: 37 = 16 + 16 + 5: three chunks, the last one padded to the 8 bucket
P37, Q29, R21, S12 = (RNG.integers(1, 128, size=n).tolist()
                      for n in (37, 29, 21, 12))
ATOL = 2e-4


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("granitemoehybrid")


@pytest.fixture(scope="module")
def gate_weights(ref):
    return weights.make_weights(ref.weight_shapes(HF), seed=2**31 + 30)


def _app(ref, w, hf=HF, **serve):
    family = get_family("granitemoehybrid")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", output_logits=True,
                     **dict(SERVE, **serve))
    app = PagedCausalLMApplication(None, family.config_cls(tcfg, **hf),
                                   family)
    view = weights.HfView(ref.weight_shapes(hf), w,
                          dtype=np.dtype("float32"))
    app._put_params(family.convert_hf_state_dict(view, app.spec))
    return app.init_cache()


@pytest.fixture()
def app(ref, gate_weights):
    return _app(ref, gate_weights)


class LogitTap:
    """Every logit the served path computes, by sequence and position: wraps
    ``app._run_paged`` and files each real token's logits under the sequence
    whose block table the row carries."""

    def __init__(self, app):
        self.app, self.by_seq, self.shapes = app, {}, []
        self._inner = app._run_paged
        app._run_paged = self._run

    def _run(self, ids, pos, slots, bt, last, *a, **kw):
        out = self._inner(ids, pos, slots, bt, last, *a, **kw)
        self.shapes.append(tuple(np.shape(ids)))
        owner = {blocks[0]: sid
                 for sid, blocks in self.app.kv_mgr.tables.items()}
        logits = np.asarray(out["logits"])
        pos, slots, bt = np.asarray(pos), np.asarray(slots), np.asarray(bt)
        for r in range(logits.shape[0]):
            for t in np.nonzero(slots[r] >= 0)[0]:
                self.by_seq.setdefault(owner[int(bt[r, 0])], {})[
                    int(pos[r, t])] = logits[r, t]
        return out

    def logits(self, sid, n):
        got = self.by_seq[sid]
        assert sorted(got) == list(range(n)), sorted(got)
        return np.stack([got[p] for p in range(n)])


def _want(ref, w, tokens, hf=HF):
    return np.asarray(ref.forward(hf, w, jnp.asarray([tokens])))[0]


def _decode(ad, sids, stream, steps, step="step"):
    for _ in range(steps):
        for sid, tok in getattr(ad, step)(sids).items():
            stream[sid].append(tok)


def _check(tap, ref, w, sid, prompt, stream):
    """Served logits at every position of prompt + delivered tokens but the
    last against the reference's, and the greedy tokens they imply."""
    fed = prompt + stream[:-1]
    want = _want(ref, w, fed)
    got = tap.logits(sid, len(fed))
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=1e-4)
    assert stream == want[len(prompt) - 1:].argmax(-1).tolist()


def test_a_three_chunks_with_a_padded_last_one_then_decode(app, ref,
                                                           gate_weights):
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {7: [ad.add_requests([7], [P37])[7]]}
    # one prompt runs the ONE-ROW chunk program: 16 + 16 + 5 (in the 8 bucket)
    assert tap.shapes == [(1, 16), (1, 16), (1, 8)]
    _decode(ad, [7], stream, 6)
    assert tap.shapes[3:] == [(BATCH, 1)] * 6     # rows of a step = the slots
    _check(tap, ref, gate_weights, 7, P37, stream[7])
    assert ad.host_stats["state_slots_live"] == 1
    ad.release([7])
    assert ad.host_stats["state_slots_live"] == 0
    assert ad._state_free == list(range(BATCH))


#: the same stack with a tile the state-step kernel takes (ISSUE 45):
#: (16, 128) where HF's (16, 16) keeps the XLA step
HF_KERNEL = dict(HF, mamba_d_state=128)


def test_a_chunks_then_decode_on_the_state_kernel(ref):
    """Three chunks through the chunked SSD form (the one-row program),
    then nine decode steps on the state-step kernel, in place on the slots:
    the logits at every position are the float32 reference's, the slot's
    final state is the reference's ``final_states`` at the 1e-4 of its
    largest entry the contract test holds (a bf16 state fails it), a dead
    row's slot (three of the four here) is left as it was, the records say
    which program took which path, and the adapter counted every decode
    dispatch on the kernel."""
    w = weights.make_weights(ref.weight_shapes(HF_KERNEL), seed=2**31 + 45)
    app = _app(ref, w, hf=HF_KERNEL)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = [ad.add_requests([7], [P37])[7]]
    slot = ad._state_slot[7]
    others = [r for r in range(BATCH) if r != slot]
    idle = np.asarray(app.cache["ssm"][:, others])
    _decode(ad, [7], {7: stream}, 9)
    assert tap.shapes == [(1, 16), (1, 16), (1, 8)] + [(BATCH, 1)] * 9
    fed = P37 + stream[:-1]
    want = _want(ref, w, fed, HF_KERNEL)
    np.testing.assert_allclose(tap.logits(7, len(fed)), want, atol=ATOL,
                               rtol=1e-4)
    assert stream == want[len(P37) - 1:].argmax(-1).tolist()
    assert app.cache["ssm"].dtype == jnp.float32
    got = np.asarray(app.cache["ssm"][:, slot])
    want = np.asarray(ref.final_states(
        HF_KERNEL, w, jnp.asarray([fed])))[:, 0]
    assert got.shape == want.shape
    assert float(np.abs(got - want).max() / np.abs(want).max()) < 1e-4
    assert (np.asarray(app.cache["ssm"][:, others]) == idle).all()
    slot_bytes = 3 * (8 * 16 * 128 * 4 + (128 + 2 * 128) * 3 * 4)
    what = f"kind=mamba2 slot_bytes={slot_bytes} chunk=8"
    assert ("recurrent_state", "pallas-interpret",
            f"{what} heads=8 tile=16x128") in app.paged_program_notes(BATCH, 1)
    assert ("recurrent_state", "xla",
            f"{what}: 16 tokens a row: the chunked form") in \
        app.paged_program_notes(1, 16)
    assert ad.host_stats["dispatches"] == 9
    assert ad.host_stats["dispatches_state_kernel"] == 9
    assert ad._state_kernel_shapes == {(BATCH, 1): True}


def test_the_contiguous_decode_phase_steps_on_the_kernel_too(ref,
                                                             monkeypatch):
    """``generate()`` on the contiguous cache: the rows of its ``decode``
    phase are the state's slots, so its T = 1 steps take the kernel as the
    paged step does, and the greedy tokens are the reference's."""
    from neuronx_distributed_inference_tpu.models.application import \
        CausalLMApplication
    from neuronx_distributed_inference_tpu.ops import mamba_state_step
    calls = []
    step = mamba_state_step.mamba_state_step

    def counted(*a, **kw):
        calls.append(a[0].shape)
        return step(*a, **kw)
    monkeypatch.setattr(mamba_state_step, "mamba_state_step", counted)
    w = weights.make_weights(ref.weight_shapes(HF_KERNEL), seed=2**31 + 45)
    family = get_family("granitemoehybrid")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", batch_size=2, seq_len=64)
    app = CausalLMApplication(None, family.config_cls(tcfg, **HF_KERNEL),
                              family)
    view = weights.HfView(ref.weight_shapes(HF_KERNEL), w,
                          dtype=np.dtype("float32"))
    app._put_params(family.convert_hf_state_dict(view, app.spec))
    app.init_cache()
    prompts = [P37[:20], Q29[:20]]
    got = np.asarray(app.generate(np.asarray(prompts, np.int32),
                                  max_new_tokens=6)["generated"])
    assert calls and set(calls) == {(3, 2, 8, 16, 128)}
    for row, prompt in zip(got, prompts):
        seq = list(prompt)
        for _ in range(6):
            seq.append(int(_want(ref, w, seq, HF_KERNEL)[-1].argmax()))
        assert row.tolist() == seq[20:]


def test_b_two_prompts_packed_beside_a_decoding_row(app, ref, gate_weights):
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {1: [ad.add_requests([1], [R21])[1]]}
    _decode(ad, [1], stream, 2)
    n0 = len(tap.shapes)
    # packed at the full batch (12 of 12 and 16 of 29 tokens); the rest of
    # sequence 2 then continues from its slot in the one-row program
    first = ad.add_requests([2, 3], [Q29, S12])
    assert tap.shapes[n0:] == [(BATCH, 16), (1, 16)]
    stream.update({2: [first[2]], 3: [first[3]]})
    # sequence 1's slot was a dead row of the pack: its state is untouched
    _decode(ad, None, stream, 4)
    for sid, prompt in ((1, R21), (2, Q29), (3, S12)):
        _check(tap, ref, gate_weights, sid, prompt, stream[sid])
    # a step of a subset leaves the others' slots as they were
    _decode(ad, [2], stream, 2)
    _decode(ad, None, stream, 1)
    for sid, prompt in ((1, R21), (2, Q29), (3, S12)):
        _check(tap, ref, gate_weights, sid, prompt, stream[sid])


def test_b_a_wide_pack_attends_its_rows_in_groups(app, ref, gate_weights,
                                                  monkeypatch):
    """Where the float32 scores of a full-batch chunk outgrow the budget
    (``_paged_score_budget``: a share of the device's memory) the gathered
    prefill attention takes the rows in groups: same logits."""
    row = 4 * app.spec.gqa.num_q_heads * 16 * app.max_blocks * 8
    monkeypatch.setattr(model_base, "_paged_score_budget", lambda: 2 * row)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    first = ad.add_requests([1, 2, 3], [R21, Q29, S12])
    assert tap.shapes[0] == (BATCH, 16)            # two groups of two rows
    stream = {sid: [tok] for sid, tok in first.items()}
    _decode(ad, None, stream, 2)
    for sid, prompt in ((1, R21), (2, Q29), (3, S12)):
        _check(tap, ref, gate_weights, sid, prompt, stream[sid])


@pytest.mark.parametrize("rows, fit, group", [
    (32, 10, 8),      # granite's 256-wide pack on a 16 GB chip: four groups
    (16, 16, 16),     # everything fits: one group, the program as it was
    (15, 4, 3),       # an odd batch: the largest divisor that fits
    (7, 3, 1),        # a prime batch: row by row, never all seven at once
    (4, 0, 1),        # one row over the budget still goes
])
def test_b_row_groups_divide_the_rows_and_fit_the_budget(monkeypatch, rows,
                                                         fit, group):
    monkeypatch.setattr(model_base, "_paged_score_budget",
                        lambda: fit * 1000 + 999)
    assert model_base._score_row_group(rows, 1000) == group


def test_b_the_score_budget_is_a_share_of_the_device(monkeypatch):
    """A twelfth of what the device reports; 16 GiB where it reports
    nothing (the CPU): both cells of the benchmark keep their groups."""
    class Dev:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats
    for stats, want in (({"bytes_limit": 12 * 2 ** 30}, 2 ** 30),
                        (None, 16 * 2 ** 30 // 12)):
        model_base._paged_score_budget.cache_clear()
        monkeypatch.setattr(model_base.jax, "devices",
                            lambda s=stats: [Dev(s)])
        assert model_base._paged_score_budget() == want
    model_base._paged_score_budget.cache_clear()


def test_c_a_reused_slot_gives_the_logits_of_a_fresh_engine(ref,
                                                            gate_weights):
    def serve(app, warm_with=None):
        ad = PagedEngineAdapter(app)
        if warm_with is not None:
            ad.add_requests([5], [warm_with])
            for _ in range(3):
                ad.step()
            ad.release([5])
        tap = LogitTap(app)
        stream = {9: [ad.add_requests([9], [Q29])[9]]}
        assert ad._state_slot[9] == 0          # the lowest free slot again
        _decode(ad, [9], stream, 4)
        return tap.logits(9, len(Q29) + 4), stream[9], tap

    used, s_used, tap = serve(_app(ref, gate_weights), warm_with=P37)
    fresh, s_fresh, _ = serve(_app(ref, gate_weights))
    np.testing.assert_array_equal(used, fresh)
    assert s_used == s_fresh
    _check(tap, ref, gate_weights, 9, Q29, s_used)


def test_d_preempt_and_resume(app, ref, gate_weights):
    ad = PagedEngineAdapter(app)
    stream = {4: [ad.add_requests([4], [R21])[4]], 6: []}
    stream[6].append(ad.add_requests([6], [S12])[6])
    _decode(ad, None, stream, 3)
    rec = ad.preempt(4)
    assert list(rec.tokens) == R21 + stream[4]
    assert ad.host_stats["state_slots_live"] == 1 and 4 not in ad._state_slot
    _decode(ad, None, stream, 1)               # 6 runs on while 4 is out
    tap = LogitTap(app)
    # recompute preemption: the requeue prefills prompt + generated from 0
    resumed = list(rec.tokens)
    stream[4] = [ad.add_requests([4], [resumed])[4]]
    _decode(ad, None, stream, 3)
    _check(tap, ref, gate_weights, 4, resumed, stream[4])
    want = _want(ref, gate_weights, S12 + stream[6][:-1])
    assert stream[6] == want[len(S12) - 1:].argmax(-1).tolist()


def test_e_a_rolled_back_admission_leaves_every_slot_free(app, ref,
                                                          gate_weights):
    ad = PagedEngineAdapter(app)
    free0 = app.kv_mgr.allocator.num_free
    with FAULTS.inject("prefill_chunk", nth=2) as fp:
        with pytest.raises(StepFailure):
            ad.add_requests([0, 1], [S12, P37])
    assert fp.trips == 1
    assert ad._state_slot == {} and ad._state_free == list(range(BATCH))
    assert ad.seqs == {} and ad._chunks == {}
    assert app.kv_mgr.allocator.num_free == free0
    assert ad.host_stats["state_slot_allocs"] == 2
    assert ad.host_stats["state_slot_frees"] == 2
    assert ad.host_stats["state_slots_live"] == 0
    # a pending (deferred) admission released mid-prefill frees its slot too
    ad2 = PagedEngineAdapter(app, prefill_budget_tokens=16)
    assert ad2.add_requests([2], [P37]) == {}
    ad2.step()
    assert ad2._state_slot == {2: 0}
    ad2.release([2])
    assert ad2._state_free == list(range(BATCH))
    # and the retry serves clean logits
    tap = LogitTap(app)
    stream = {0: [ad.add_requests([0], [S12])[0]]}
    _decode(ad, None, stream, 2)
    _check(tap, ref, gate_weights, 0, S12, stream[0])


def test_pipelined_decode_equals_eager(ref, gate_weights):
    """``step_ahead()`` feeds the previous step's tokens back on the
    device: in slot order they need no re-padding, and the streams are
    those of ``step()`` (a live-set change drains the pipeline first)."""
    def serve(step):
        ad = PagedEngineAdapter(_app(ref, gate_weights))
        stream = {1: [ad.add_requests([1], [R21])[1]]}
        _decode(ad, None, stream, 3, step)
        stream[2] = [ad.add_requests([2], [S12])[2]]
        _decode(ad, None, stream, 4, step)
        ad.release([1])
        _decode(ad, None, stream, 2, step)
        for sid, tok in ad.flush().items():
            stream[sid].append(tok)
        return stream[2]
    eager, piped = serve("step"), serve("step_ahead")
    assert piped == eager[:len(piped)] and len(piped) >= len(eager) - 1


def test_f_converted_hf_weights_give_hfs_logits():
    os.environ.setdefault("USE_TF", "0")
    import torch
    import transformers
    torch.manual_seed(30)
    kw = {k: v for k, v in HF.items() if k != "model_type"}
    model = transformers.GraniteMoeHybridForCausalLM(
        transformers.GraniteMoeHybridConfig(**kw)).float().eval()
    with torch.no_grad():
        for name, p in model.named_parameters():
            if "norm" in name or name.endswith(("dt_bias", ".D")):
                p.add_(0.1 * torch.randn_like(p))
    family = get_family("granitemoehybrid")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", output_logits=True,
                     **SERVE)
    app = PagedCausalLMApplication(None, family.config_cls(tcfg, **HF),
                                   family)
    sd = {k: v.numpy() for k, v in model.state_dict().items()}
    app._put_params(family.convert_hf_state_dict(sd, app.spec))
    app.init_cache()
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {3: [ad.add_requests([3], [P37])[3]]}
    _decode(ad, [3], stream, 4)
    fed = P37 + stream[3][:-1]
    with torch.no_grad():
        want = model(torch.tensor([fed])).logits[0].numpy()
    np.testing.assert_allclose(tap.logits(3, len(fed)), want, atol=ATOL,
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# what stays refused, the warm-up plan, the counters
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw, names", [
    (dict(ragged=True), "ragged dispatch"),
    (dict(speculation=2), "speculation"),
    (dict(kv_spill_tier=object()), "host KV spill / handoff"),
])
def test_adapter_refuses_at_construction(app, kw, names):
    with pytest.raises(ConfigurationError) as ei:
        PagedEngineAdapter(app, **kw)
    assert names in str(ei.value)
    assert model_base.RECURRENT_UNSUPPORTED[names] in str(ei.value)


@pytest.mark.parametrize("serve, name", [
    (dict(is_prefix_caching=True), "prefix caching"),
    (dict(decode_chunk_tokens=4), "fused decode loop"),
    (dict(flash_decoding_enabled=True), "flash decoding"),
])
def test_spec_refuses_with_the_tables_sentence(serve, name):
    family = get_family("granitemoehybrid")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", **dict(SERVE, **serve))
    with pytest.raises(NotImplementedError) as ei:
        family.build_spec(family.config_cls(tcfg, **HF))
    assert f"{name} ({model_base.RECURRENT_UNSUPPORTED[name]})" \
        in str(ei.value)


def test_routed_experts_are_refused_with_a_sentence():
    family = get_family("granitemoehybrid")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", **SERVE)
    with pytest.raises(NotImplementedError, match="num_local_experts > 0"):
        family.build_spec(family.config_cls(
            tcfg, **dict(HF, num_local_experts=8, num_experts_per_tok=2)))


def test_step_many_and_the_ragged_step_are_refused(app):
    ad = PagedEngineAdapter(app)
    ad.add_requests([0], [S12])
    blocks = len(app.kv_mgr.tables[0])
    with pytest.raises(ConfigurationError, match="fused decode loop"):
        ad.step_many(4)
    assert len(app.kv_mgr.tables[0]) == blocks     # growth rolled back
    assert ad.step()                               # and step() still serves
    with pytest.raises(NotImplementedError, match="ragged dispatch"):
        app._run_ragged(np.zeros((BATCH, 1), np.int32),
                        np.zeros((BATCH, 1), np.int32),
                        np.full((BATCH, 1), -1, np.int32),
                        np.zeros((BATCH, 2), np.int32),
                        np.ones((BATCH,), np.int32),
                        np.zeros((BATCH,), np.int32))


def test_warmup_plan_has_only_the_programs_the_stack_runs(app):
    report = precompile(app, widths=[1, 8, 16])
    pairs = [(g["kind"], g["bucket"]) for g in report["graphs"]]
    per_tw = [("paged", 1), ("paged", 8), ("paged_pack", 8), ("paged", 16),
              ("paged_pack", 16)]
    # ... and, last, the program that makes a carried step's ids
    assert pairs == per_tw * len(app._bt_buckets) + [("carry_ids", BATCH)]
    assert len(set(pairs)) == len(per_tw) + 1
    one = precompile(app, widths=[16], bt_widths=[app._bt_buckets[0]])
    assert [(g["kind"], g["bucket"]) for g in one["graphs"]] == \
        [("paged", 16), ("paged_pack", 16)]
    # everything the default adapter dispatches is warm: no incident
    ad = PagedEngineAdapter(app)
    ad.add_requests([0], [P37])
    ad.add_requests([1, 2], [Q29, S12])
    for _ in range(3):
        ad.step()
    warm = app.warmup_state()
    assert warm["steady_state"] and not warm["incidents"]


def test_slot_metrics_and_the_memory_ledger(app):
    reg = telemetry.MetricsRegistry()
    ad = PagedEngineAdapter(app, telemetry=reg)
    ad.add_requests([0, 1], [S12, R21])
    ad.preempt(1)
    ad.release([0])
    snap = reg.snapshot()["metrics"]

    def series(name):
        return {tuple(sorted(s["labels"].items())): s["value"]
                for s in snap[name]["series"]}
    events = series(tmetrics.STATE_SLOT_EVENTS_TOTAL)
    assert events == {
        (("engine", "paged"), ("event", "alloc")): 2,
        (("engine", "paged"), ("event", "preempt")): 1,
        (("engine", "paged"), ("event", "free")): 1}
    slots = series(tmetrics.STATE_SLOTS)
    assert slots == {(("engine", "paged"), ("state", "live")): 0,
                     (("engine", "paged"), ("state", "free")): BATCH}
    assert ad.host_stats["state_slot_allocs"] == 2
    assert ad.host_stats["state_slot_frees"] == 2
    ledger = memory_ledger(ad)
    s = app.spec.ssm
    slot_bytes = app.spec.num_ssm_layers * (
        s.num_heads * s.head_dim * s.d_state * 4
        + (s.d_inner + s.bc_size) * (s.d_conv - 1) * 4)   # fp32 toy
    assert ledger["state"] == {"bytes": slot_bytes * BATCH, "slots": BATCH,
                               "slot_bytes": slot_bytes, "live": 0}
    # the KV pool covers the attention layers only
    assert app.cache["k"].shape[0] == app.spec.num_attn_layers == 1
    assert ledger["kv"]["pool_bytes"] == (app.cache["k"].nbytes
                                          + app.cache["v"].nbytes)
