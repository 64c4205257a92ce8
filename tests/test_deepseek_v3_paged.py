"""DeepSeek-V3 on the paged serving path (ISSUE 47): leading dense layers in
front of expert layers whose sigmoid router is limited to the best groups, a
shared expert, latent attention over the latent pool with yarn past its
original context, one chip's share of the routed experts.

``deepseek_v3`` served through ``PagedEngineAdapter`` with default
arguments, at a toy size on the CPU in float32, in
``tests/test_longcat_flash_paged.py``'s manner: every test holds the LOGITS
of the served path, at every position a dispatch computed, to the plain
reference ``benchmark/references/deepseek_v3.py`` (expanded attention, no
cache; held to ``transformers``' ``DeepseekV3ForCausalLM`` by
``benchmark/tests/test_reference.py``), both holding the SAME share: routed
experts 4..7 of 16 (all of group 1 of 4).

  (a) a prompt walked in three chunks through the one-row program (each
      behind the prefix the earlier ones cached, the positions past the
      toy's original 32), a padded last chunk, then decode through the
      latent pool: on the kernels (interpret mode: the absorbed decode
      attention, the walk over the touched experts) and on XLA, on the
      dense, walk and ragged expert paths; a one-row chunk of more rows
      than a tile on the walk by expert;
  (b) prompts packed as rows of one full-batch dispatch beside a decoding
      row, rows admitted and released mid-stream;
  (c) every control of the benchmark's gate fails (a)'s comparison, and
      ``scripts/gate47.py`` runs at a toy size;
  (d) a share whose group loses every row computes the shared expert alone
      and counts no row as reaching it;
  (e) the 16 shares of an expert layer, the shared expert counted once, add
      up to the uncut reference's layer;

and the edges: the walk's VMEM rule at the published widths and the walk
that holds its rows once against ``experts_ragged``, the counts of a decode
step's routing, the loader (a whole checkpoint's experts, the MTP module's
tensors), the family's refusals, the engagement record.
"""

import dataclasses
import importlib.util
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from harness import build, weights  # noqa: E402

from neuronx_distributed_inference_tpu import telemetry  # noqa: E402
from neuronx_distributed_inference_tpu.config import TpuConfig  # noqa: E402
from neuronx_distributed_inference_tpu.models import model_base  # noqa: E402
from neuronx_distributed_inference_tpu.models.application import \
    PagedCausalLMApplication  # noqa: E402
from neuronx_distributed_inference_tpu.models.family import \
    get_family  # noqa: E402
from neuronx_distributed_inference_tpu.modules import moe  # noqa: E402
from neuronx_distributed_inference_tpu.ops import moe_decode  # noqa: E402
from neuronx_distributed_inference_tpu.serving import \
    PagedEngineAdapter  # noqa: E402
from neuronx_distributed_inference_tpu.telemetry import \
    metrics as tmetrics  # noqa: E402
from test_recurrent_paged import LogitTap, _decode  # noqa: E402

#: three layers at a toy size, the first dense: every key of the published
#: config.json that shapes the model, and the share: the weights hold routed
#: experts 4..7 (group 1) of the 16 the router scores in 4 groups of 4. The
#: latent and the experts are whole vregs (rank 128, 128 x 128), so both
#: kernels engage in interpret mode; yarn's original context is 32
HF = dict(
    model_type="deepseek_v3", vocab_size=128, hidden_size=128,
    intermediate_size=192, moe_intermediate_size=128, num_hidden_layers=3,
    first_k_dense_replace=1, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=32, kv_lora_rank=128, qk_nope_head_dim=16,
    qk_rope_head_dim=16, v_head_dim=16, attention_bias=False,
    hidden_act="silu", rms_norm_eps=1e-6, rope_theta=10000,
    rope_interleave=True,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 4, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 32, "type": "yarn"},
    max_position_embeddings=512, tie_word_embeddings=False,
    n_routed_experts=4, router_n_routed_experts=16, first_expert=4,
    n_shared_experts=1, n_group=4, topk_group=2, num_experts_per_tok=4,
    norm_topk_prob=True, routed_scaling_factor=2.5, scoring_func="sigmoid",
    topk_method="noaux_tc", num_nextn_predict_layers=1)
BATCH = 4
#: a pack of 4 rows x 32 is 128 tokens, over ``dense_max_tokens`` (64) and
#: within a tile of rows; a one-row chunk and a decode step are few tokens
SERVE = dict(batch_size=BATCH, seq_len=256, pa_block_size=8,
             pa_num_blocks=96, context_encoding_buckets=[8, 32],
             enable_bucketing=True, is_block_kv_layout=True,
             is_prefix_caching=True)
RNG = np.random.default_rng(47)
#: 69 = 32 + 32 + 5: three chunks, the last one padded to the 8 bucket
P69, Q45, R21, S12, T200 = (RNG.integers(1, 128, size=n).tolist()
                            for n in (69, 45, 21, 12, 200))
#: float32 on both sides: the served logits (|logit| up to ~1) agree with
#: the reference's to a few 1e-6; the controls move them by 1e-2 and more
ATOL = 1e-4


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("deepseek_v3")


@pytest.fixture(scope="module")
def gate_weights(ref):
    return weights.make_weights(ref.weight_shapes(HF), seed=2**31 + 47)


def _app(ref, w, hf=HF, **serve):
    family = get_family("deepseek_v3")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", output_logits=True,
                     **dict(SERVE, **serve))
    app = PagedCausalLMApplication(None, family.config_cls(tcfg, **hf),
                                   family)
    view = weights.HfView(ref.weight_shapes(hf), w,
                          dtype=np.dtype("float32"))
    app._put_params(family.convert_hf_state_dict(view, app.spec))
    return app.init_cache()


def _want(ref, w, tokens, hf=HF, control=None):
    return np.asarray(ref.forward(hf, w, jnp.asarray([tokens]),
                                  control=control))[0]


def _check(tap, ref, w, sid, prompt, stream, hf=HF):
    fed = prompt + stream[:-1]
    want = _want(ref, w, fed, hf)
    np.testing.assert_allclose(tap.logits(sid, len(fed)), want, atol=ATOL)
    assert stream == want[len(prompt) - 1:].argmax(-1).tolist()


def _respec(monkeypatch, **fields):
    """The family's spec with fields replaced; a dict value replaces fields
    of the sub-spec of that name (``moe``, ``mla``)."""
    family = get_family("deepseek_v3")
    build_spec = family.build_spec.__func__

    def respec(cls, config, tp_degree=None):
        spec = build_spec(cls, config, tp_degree)
        return dataclasses.replace(spec, **{
            k: (dataclasses.replace(getattr(spec, k), **v)
                if isinstance(v, dict) else v) for k, v in fields.items()})
    monkeypatch.setattr(family, "build_spec", classmethod(respec))


def _kernels(app):
    return {(k["site"], k["path"]): k["reason"]
            for k in app.warmup_state()["kernels"]}


@pytest.fixture(scope="module")
def served_p69(ref, gate_weights):
    """P69 walked in three chunks, then 24 decode steps: the tap and the
    stream, shared by (a)'s first case and every control."""
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {7: [ad.add_requests([7], [P69])[7]]}
    _decode(ad, [7], stream, 24)
    return app, tap, stream[7]


# ---------------------------------------------------------------------------
# (a) chunks behind a cached prefix, then decode over the latent pool
# ---------------------------------------------------------------------------

def test_a_three_chunks_then_decode_on_the_kernels(ref, gate_weights,
                                                   served_p69):
    app, tap, stream = served_p69
    assert tap.shapes == [(1, 32), (1, 32), (1, 8)] + [(BATCH, 1)] * 24
    # 93 positions served, the last 61 past yarn's original 32
    _check(tap, ref, gate_weights, 7, P69, stream)
    notes = _kernels(app)
    assert notes[("mla_decode", "pallas-interpret")] == \
        ("latent lanes=256 heads=4 form=absorbed pages=32 "
         "tiles=tokens-held prefetch=across-rows")
    assert notes[("moe_decode", "pallas-interpret")] == "pieces=1 of 128"
    assert notes[("moe_share", "xla")] == \
        "held=4 of 16 from 4 top_k=4 groups=4 top=2"
    # heads of 16 lanes are not the prefill kernel's: the XLA form, and why
    assert "absorbed" in notes[("mla_prefill", "xla")]
    assert notes[("mla_prefill", "xla")].endswith(
        "(a head's nope or value lanes not whole vregs)")
    assert app.spec.first_dense == 1 and app.spec.num_moe_layers == 2
    assert app.params["layers"]["gate_proj"].shape == (1, 128, 192)
    assert app.params["moe_layers"]["expert_gate"].shape == (2, 4, 128, 128)
    assert app.params["moe_layers"]["router"].shape == (2, 128, 16)


@pytest.mark.parametrize("decode, experts", [("xla", "dense"),
                                             ("kernel", "ragged")])
def test_a_the_xla_decode_form_and_the_other_expert_paths(
        ref, gate_weights, monkeypatch, decode, experts):
    fields = {}
    if decode == "xla":
        fields["decode_kernel"] = False
    if experts == "ragged":
        # every dispatch, the decode step too, over the sorted grouped
        # matmuls: picks of absent experts dropped
        fields["moe"] = dict(dense_max_tokens=0)
    else:
        monkeypatch.setattr(moe_decode, "declined",
                            lambda spec, wg, tokens=1: "forced")
    _respec(monkeypatch, **fields)
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {7: [ad.add_requests([7], [P69])[7]]}
    _decode(ad, [7], stream, 6)
    _check(tap, ref, gate_weights, 7, P69, stream[7])
    notes = _kernels(app)
    assert (("mla_decode", "xla") in notes) == (decode == "xla")
    assert any(site == "moe_ragged" for site, _ in notes) == \
        (experts == "ragged")
    if experts == "dense":
        assert notes[("moe_decode", "xla")] == "forced"


def test_a_a_chunk_of_more_rows_than_a_tile_walks_by_expert(ref,
                                                            gate_weights):
    """A one-row chunk of 160 tokens is over a tile of rows: the walk hands
    each touched expert ITS rows (``moe_chunk_experts``, its float32 rows
    and result held once), behind a prefix on the second chunk; a chunk that
    wide expands its prefix."""
    app = _app(ref, gate_weights, context_encoding_buckets=[32, 160])
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {3: [ad.add_requests([3], [T200])[3]]}
    assert tap.shapes == [(1, 160), (1, 160)]
    _decode(ad, [3], stream, 2)
    _check(tap, ref, gate_weights, 3, T200, stream[3])
    reasons = {(k["site"], k["reason"]) for k in app.warmup_state()["kernels"]}
    assert ("moe_decode",
            "pieces=1 of 128 rows=160 by expert in tiles of 128") in reasons
    assert any(site == "mla_prefill" and "width=160 prefix=expanded" in why
               and why.endswith("nope or value lanes not whole vregs)")
               for site, why in reasons)


#: the toy model with heads of whole vregs (nope and value lanes 128): what
#: ``ops/mla_prefill.py`` takes (interpret mode)
HF_KERNEL = dict(HF, qk_nope_head_dim=128, v_head_dim=128)


def test_a_chunks_on_the_prefill_kernel_a_pack_and_a_declined_width(ref):
    """Heads of whole vregs: the 32-wide chunks of P69 run their attention on
    ``mla_prefill_attention`` (the second behind the first's cached prefix,
    own rows read from the pool), the 8-wide last chunk is declined by its
    width and takes the XLA form, a full-batch pack of two prompts takes the
    kernel with the rows as its grid: the reference's logits at every
    position, and ``host_stats`` counts which dispatches the kernel served."""
    w = weights.make_weights(ref.weight_shapes(HF_KERNEL), seed=2**31 + 48)
    app = _app(ref, w, HF_KERNEL)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {7: [ad.add_requests([7], [P69])[7]]}
    assert tap.shapes == [(1, 32), (1, 32), (1, 8)]
    st = ad.host_stats
    assert (st["prefill_dispatches"], st["prefill_dispatches_attn_kernel"]) \
        == (3, 2)
    first = ad.add_requests([1, 2], [Q45, R21])
    stream.update({1: [first[1]], 2: [first[2]]})
    assert tap.shapes[3:] == [(BATCH, 32), (1, 32)]     # Q45's last 13
    assert (st["prefill_dispatches"], st["prefill_dispatches_attn_kernel"]) \
        == (5, 4)
    _decode(ad, [7, 1, 2], stream, 3)
    for sid, prompt in ((7, P69), (1, Q45), (2, R21)):
        _check(tap, ref, w, sid, prompt, stream[sid], HF_KERNEL)
    notes = {(k["site"], k["path"], k["reason"])
             for k in app.warmup_state()["kernels"] if k["site"] == "mla_prefill"}
    plan = ("latent lanes=256 heads=4 form=absorbed tile=4x32 pages=16 "
            "folds and own tokens inside")
    assert notes == {
        ("mla_prefill", "pallas-interpret", f"rows=1 width=32 {plan}"),
        ("mla_prefill", "pallas-interpret", f"rows={BATCH} width=32 {plan}"),
        ("mla_prefill", "xla",
         "rows=1 width=8 prefix=absorbed in groups of 512 tokens, own tokens "
         "expanded (8 queries a row are not whole sublanes)")}


# ---------------------------------------------------------------------------
# (b) packs, admissions and releases mid-stream
# ---------------------------------------------------------------------------

def test_b_rows_admitted_and_released_mid_stream(ref, gate_weights):
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {1: [ad.add_requests([1], [R21])[1]]}
    _decode(ad, [1], stream, 2)
    n0 = len(tap.shapes)
    # packed at the full batch, 4 x 32 = 128 tokens, beside the decoding
    # row; the rest of sequence 2 in the one-row program, behind its prefix
    first = ad.add_requests([2, 3], [Q45, S12])
    assert tap.shapes[n0:] == [(BATCH, 32), (1, 32)]
    stream.update({2: [first[2]], 3: [first[3]]})
    _decode(ad, None, stream, 3)
    ad.release([3])
    _check(tap, ref, gate_weights, 3, S12, stream[3])
    free = app.kv_mgr.allocator.num_free
    stream[4] = [ad.add_requests([4], [P69])[4]]
    assert app.kv_mgr.allocator.num_free < free
    _decode(ad, None, stream, 3)
    for sid, prompt in ((1, R21), (2, Q45), (4, P69)):
        _check(tap, ref, gate_weights, sid, prompt, stream[sid])


# ---------------------------------------------------------------------------
# (c) the controls of the benchmark's gate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("control", [
    "no_groups", "group_max", "no_select_bias", "bias_in_weights",
    "not_renormalised", "no_routed_scaling", "no_shared", "softmax",
    "no_mscale", "no_yarn", "rope_halves"])
def test_c_a_control_fails_the_comparison(ref, gate_weights, served_p69,
                                          control):
    assert control in ref.CONTROLS
    _, tap, stream = served_p69
    fed = P69 + stream[:-1]
    got = tap.logits(7, len(fed))
    assert np.abs(got - _want(ref, gate_weights, fed)).max() < ATOL
    assert np.abs(got - _want(ref, gate_weights, fed,
                              control=control)).max() > 10 * ATOL


def test_c_the_controls_are_all_of_the_references(ref):
    assert len(ref.CONTROLS) == 11


def test_c_fp8_rounded_reference_weights_fail_the_comparison(ref,
                                                             gate_weights):
    """One precision down: the reference on fp8-rounded weights against
    itself."""
    w8 = {k: v.astype(jnp.float8_e4m3fn).astype(v.dtype)
          for k, v in gate_weights.items()}
    fed = P69 + Q45
    assert np.abs(_want(ref, w8, fed)
                  - _want(ref, gate_weights, fed)).max() > 10 * ATOL


def _toy_file():
    """The toy as a configuration file ``scripts/gate47.py`` can build."""
    return dict(
        HF, family="deepseek_v3", tp=1, dtype="float32", serve=SERVE,
        adapter={},
        gate=dict(config={"num_hidden_layers": 2,
                          "first_k_dense_replace": 1},
                  batch=2, prompt_len=24, new_tokens=4, atol=2e-4, rtol=1e-4,
                  min_positions_held=1.0, median_ratio_max=0.5,
                  worst_ratio_max=1.0, excuse_margin_max=0.0))


def test_c_the_builders_chip_check_runs_at_a_toy_size(ref):
    """``scripts/gate47.py`` (what PR 47 ran on the CPU backend and on the
    chip at the published widths) at a toy size: the gate passes, every
    control that 28 positions can show and the fp8-rounded reference fail
    it; the long walk (72 tokens in chunks of 32 behind their prefix, past
    yarn's original 32, then decode over 10 pages) holds every position and
    the rotary controls fail THERE."""
    spec = importlib.util.spec_from_file_location(
        "gate47", os.path.join(ROOT, "scripts", "gate47.py"))
    gate47 = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate47)
    out = gate47.gate_and_controls(_toy_file(), seed=2**31 + 47,
                                   served_precision="highest")
    assert out["sound"]["passed"], out["sound"]
    assert set(out["controls"]) == set(ref.CONTROLS) | {
        "fp8_weights", "fp8_weights_vs_reference"}
    assert not any(v["passed"] for v in out["controls"].values()), out
    assert {n["site"] for n in out["notes"]} == {"latent_cache", "mla_decode",
                                                 "mla_prefill"}
    walk = gate47.long_walk(_toy_file(), seed=2**31 + 47, tokens=72,
                            new_tokens=8, served_precision="highest")
    assert walk["positions_served"] == 80 and walk["held_share"] == 1.0
    assert walk["held_share_past_original"] == 1.0
    assert walk["worst_ratio"] < 0.5
    assert set(walk["controls"]) == set(gate47.LONG_CONTROLS)
    assert all(v["worst_ratio"] > 10 for v in walk["controls"].values())
    assert {site for site, _, _ in walk["notes"]} >= {"mla_decode",
                                                      "mla_prefill",
                                                      "moe_decode",
                                                      "moe_share"}


# ---------------------------------------------------------------------------
# (d) a share whose group loses every row
# ---------------------------------------------------------------------------

def test_d_a_share_whose_group_loses_every_row(ref, gate_weights):
    """With group 1's selection bias far below the others' no row chooses
    the group the held experts are in: the block is the shared expert alone,
    the logits are still the reference's, no row is counted as reaching
    this chip and the walk reads no expert."""
    w = dict(gate_weights)
    name = ref.MLP + "gate.e_score_correction_bias"
    w[name] = w[name].at[:, 4:8].set(-8.0)
    app = _app(ref, w)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {5: [ad.add_requests([5], [R21])[5]]}
    _decode(ad, [5], stream, 4)
    _check(tap, ref, w, 5, R21, stream[5])
    st = ad.host_stats
    assert st["moe_assignments"] == BATCH * 2 * 4 * 4
    assert st["moe_rows_group_hit"] == 0
    assert st["moe_assignments_held"] == st["moe_experts_touched"] == 0
    assert st["moe_experts_read"] == 0
    # ... and dropping the shared expert is then the whole block
    fed = R21 + stream[5][:-1]
    assert np.abs(tap.logits(5, len(fed)) - _want(
        ref, w, fed, control="no_shared")).max() > 10 * ATOL


# ---------------------------------------------------------------------------
# (e) the shares of one layer add up to the whole layer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tokens, path", [(6, "dense"), (40, "ragged")])
def test_e_16_shares_add_up_to_the_uncut_layer(ref, tokens, path):
    """The uncut reference's expert block (all 16 routed experts held) of
    layer 1 against what 16 shares of one expert each give: every share
    computes the shared expert alike (in the deployment a token's own chip
    does), so the sum of the shares' outputs less 15 times the shared
    expert's is the whole block; the group hits of the four shares of a
    group are equal, and over the groups they are rows x topk_group."""
    uncut = dict(HF, num_hidden_layers=2, n_routed_experts=16,
                 router_n_routed_experts=None, first_expert=0)
    table = ref.weight_shapes(uncut)
    w = weights.make_weights(table, seed=2**31 + 48)
    u = jnp.asarray(np.random.default_rng(5).normal(
        size=(2, tokens // 2, 128)), jnp.float32)
    want, _ = ref.moe(uncut, w, 0, u)
    shared = ref.swiglu(u, w[ref.SHARED + "gate_proj.weight"][0],
                        w[ref.SHARED + "up_proj.weight"][0],
                        w[ref.SHARED + "down_proj.weight"][0])
    assert np.abs(np.asarray(shared)).max() > 1e-3

    family = get_family("deepseek_v3")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", **SERVE)
    spec = family.build_spec(family.config_cls(tcfg, **uncut))
    lw = jax.tree.map(lambda a: jnp.asarray(a)[0],
                      family.convert_hf_state_dict(
                          weights.HfView(table, w, dtype=np.dtype("float32")),
                          spec)["moe_layers"])
    whole = dataclasses.replace(spec.moe, dense_max_tokens=16)
    assert whole.num_held == 16 and not whole.holds_share
    assert moe.takes_ragged(whole, tokens) == (path == "ragged")
    np.testing.assert_allclose(moe.moe_block(whole, u, lw), want, atol=2e-5)
    total, tallies = 0, []
    for first in range(16):
        mine = dataclasses.replace(whole, held_experts=1, first_expert=first)
        lw_mine = dict(lw, **{k: lw[k][first:first + 1] for k in
                              ("expert_gate", "expert_up", "expert_down")})
        total = total + moe.moe_block(mine, u, lw_mine, tally=tallies)
    np.testing.assert_allclose(total - 15 * shared, want, atol=2e-5)
    # every pick fell to exactly one share's expert
    assert {int(t[3]) for t in tallies} == {tokens * 4}
    assert sum(int(t[1]) for t in tallies) == tokens * 4
    hits = [int(t[5]) for t in tallies]
    assert all(len(set(hits[g * 4:(g + 1) * 4])) == 1 for g in range(4))
    assert sum(hits[::4]) == tokens * 2


# ---------------------------------------------------------------------------
# the walk's VMEM rule, the counts, the loader, refusals
# ---------------------------------------------------------------------------

def test_the_walk_takes_256_rows_of_7168():
    """ISSUE 47: a one-row chunk at DeepSeek-V3's widths. Its float32 rows
    and result are 14.7 MB; the call's pipeline would hold each twice (46.1
    MiB beside the slots, over the budget LongCat's 39.6 set); held once by
    the kernel they need 32.1 MiB."""
    hf = build.load_json("configs", "deepseek-v3.json")
    spec = build.build_app(hf).spec
    stack = jax.ShapeDtypeStruct((4, 16, 7168, 2048), jnp.bfloat16)
    assert moe_decode.declined(spec.moe, stack, 256) == ""
    assert moe_decode.declined(spec.moe, stack, 32) == ""
    plan = moe_decode.moe_decode_plan(7168, 2048, jnp.bfloat16)
    assert (plan.pieces, plan.ip) == (8, 256)
    need = moe_decode.rows_vmem_bytes(256, 7168, 16, plan, jnp.bfloat16)
    assert round(need / 2 ** 20, 1) == 32.1
    assert need + 2 * 256 * 7168 * 4 > moe_decode.MOE_ROWS_VMEM_BYTES
    assert not moe.takes_ragged(spec.moe, 256, stack)
    assert moe.takes_ragged(spec.moe, 32 * 256, stack)
    # 512 rows of 7168 do not fit, and say so
    assert "512 rows of 7168" in moe_decode.declined(spec.moe, stack, 512)


def test_the_walk_that_holds_its_rows_once_equals_the_grouped_matmuls():
    """512 rows of 4096: in a pipeline's pairs the float32 rows and result
    alone were 33.5 MB and the walk declined; held once it takes them, and
    gives ``experts_ragged``'s sum."""
    spec = moe.MoESpec(num_experts=8, top_k=2, intermediate_size=128,
                       held_experts=4, first_expert=2)
    rng = np.random.default_rng(3)
    n, h = 512, 4096
    wg, wu = (jnp.asarray(rng.normal(size=(1, 4, h, 128)) * 0.02,
                          jnp.float32) for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(1, 4, 128, h)) * 0.02, jnp.float32)
    plan = moe_decode.moe_decode_plan(h, 128, jnp.float32)
    need = moe_decode.rows_vmem_bytes(n, h, 4, plan, jnp.float32)
    assert need <= moe_decode.MOE_ROWS_VMEM_BYTES < need + 2 * n * h * 4
    assert moe_decode.declined(spec, wg, n) == ""
    x = jnp.asarray(rng.normal(size=(1, n, h)), jnp.float32)
    top_idx = jnp.asarray(rng.integers(0, 8, size=(1, n, 2)), jnp.int32)
    top_vals = jnp.asarray(rng.uniform(0.1, 1.0, size=(1, n, 2)),
                           jnp.float32)
    got, read = moe.experts_touched(spec, x, top_vals, top_idx, wg, wu, wd, 0)
    want = moe.experts_ragged(spec, x, top_vals, top_idx, wg[0], wu[0],
                              wd[0])
    assert int(read) == 4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_a_decode_steps_routing_is_counted_on_the_device(ref, gate_weights):
    """``host_stats`` after n decode steps of one live row: every pick,
    those that fell to held experts and the rows whose chosen groups include
    the held experts' group are the reference's routing of the same tokens -
    times the batch, since a pad row of an attention stack's decode step
    clones row 0."""
    reg = telemetry.MetricsRegistry()
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app, telemetry=reg)
    stream = {7: [ad.add_requests([7], [R21])[7]]}
    assert "moe_assignments" not in ad.host_stats       # prefill counts none
    _decode(ad, [7], stream, 5)
    fed = R21 + stream[7][:-1]
    eps = HF["rms_norm_eps"]
    x = gate_weights["model.embed_tokens.weight"][
        jnp.asarray([fed])].astype(jnp.float32)
    held = hit = 0
    for i in range(3):
        x = x + ref.mla(HF, gate_weights, i, ref.rms_norm(
            x, gate_weights[ref.L + "input_layernorm.weight"][i], eps))
        u = ref.rms_norm(
            x, gate_weights[ref.L + "post_attention_layernorm.weight"][i],
            eps)
        if i == 0:
            x = x + ref.swiglu(u, *(gate_weights[ref.MLP + n][0] for n in (
                "gate_proj.weight", "up_proj.weight", "down_proj.weight")))
            continue
        idx = np.asarray(ref.routing(HF, gate_weights, i - 1, u)[1])[
            0, len(R21):]
        held += int(((idx >= 4) & (idx < 8)).sum())
        # the reference's own groups: those its picks were limited to
        logits = ref.linear(u, gate_weights[ref.MLP + "gate.weight"][i - 1])
        sel = np.asarray(jax.nn.sigmoid(logits) + gate_weights[
            ref.MLP + "gate.e_score_correction_bias"][i - 1].astype(
                jnp.float32))[0, len(R21):].reshape(-1, 4, 4)
        rank = np.sort(sel, axis=-1)[..., -2:].sum(-1)
        hit += int((np.argsort(-rank, axis=-1)[:, :2] == 1).any(-1).sum())
        x = x + ref.moe(HF, gate_weights, i - 1, u)[0]
    st = ad.host_stats
    assert st["moe_assignments"] == BATCH * 2 * 5 * 4
    assert st["moe_assignments_held"] == BATCH * held > 0
    assert st["moe_rows_group_hit"] == BATCH * hit
    assert 0 < hit < 2 * 5
    assert st["moe_expert_slots"] == 4 * 2 * 5
    series = {s["labels"]["hit"]: s["value"] for s in reg.snapshot()[
        "metrics"][tmetrics.MOE_GROUP_ROWS_TOTAL]["series"]}
    assert series == {"yes": BATCH * hit, "no": BATCH * (10 - hit)}


def test_the_groups_are_top_ks_without_a_scatter():
    """``chosen_groups`` against the rule it replaces: the indices
    ``top_k`` gives of the groups' scores, ties to the lower index."""
    rng = np.random.default_rng(1)
    spec = moe.MoESpec(num_experts=32, top_k=4, intermediate_size=8,
                       n_group=8, topk_group=3)
    select = rng.normal(size=(3, 5, 32)).astype(np.float32)
    select[0, 0] = 0.25                  # every group ties: the first three
    select[0, 1, 8:] = select[0, 1, :8].repeat(3).reshape(3, 8).T.reshape(-1)
    got = np.asarray(moe.chosen_groups(spec, jnp.asarray(select)))
    score = np.sort(select.reshape(3, 5, 8, 4), axis=-1)[..., -2:].sum(-1)
    _, idx = jax.lax.top_k(jnp.asarray(score), 3)
    want = np.zeros((3, 5, 8), bool)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    np.testing.assert_array_equal(got, want)
    assert got[0, 0].tolist() == [True] * 3 + [False] * 5
    assert (got.sum(-1) == 3).all()


def test_the_loader_reads_a_whole_checkpoint_at_the_share_and_skips_mtp(
        ref, gate_weights):
    """A checkpoint that holds all 16 routed experts (and the MTP module's
    tensors under ``model.layers.3``) gives the share experts 4..7; the
    benchmark's seeded weights, which hold the share alone, are read at
    0..3."""
    family = get_family("deepseek_v3")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", **SERVE)
    spec = family.build_spec(family.config_cls(tcfg, **HF))
    uncut = dict(HF, n_routed_experts=16, router_n_routed_experts=None,
                 first_expert=0)
    table = ref.weight_shapes(uncut)
    w = weights.make_weights(table, seed=2**31 + 49)
    sd = dict(weights.HfView(table, w, dtype=np.dtype("float32")))
    sd.update({"model.layers.3.embed_tokens.weight": np.zeros((128, 128)),
               "model.layers.3.eh_proj.weight": np.zeros((128, 256)),
               "model.layers.3.mlp.experts.15.up_proj.weight":
                   np.zeros((128, 128))})
    host = family.convert_hf_state_dict(sd, spec)
    assert host["moe_layers"]["expert_up"].shape == (2, 4, 128, 128)
    for e in range(4):
        np.testing.assert_array_equal(
            host["moe_layers"]["expert_up"][1, e],
            sd[f"model.layers.2.mlp.experts.{4 + e}.up_proj.weight"].T)
    assert host["moe_layers"]["router"].shape == (2, 128, 16)
    assert host["layers"]["gate_proj"].shape == (1, 128, 192)
    # the share alone, named 0..3
    mine = dict(weights.HfView(ref.weight_shapes(HF), gate_weights,
                               dtype=np.dtype("float32")))
    assert "model.layers.1.mlp.experts.4.up_proj.weight" not in mine
    np.testing.assert_array_equal(
        family.convert_hf_state_dict(mine, spec)["moe_layers"]["expert_up"][
            0, 3], mine["model.layers.1.mlp.experts.3.up_proj.weight"].T)


def _config(serve=None, **hf):
    family = get_family("deepseek_v3")
    tcfg = TpuConfig(dtype="float32",
                     **{**SERVE, "tp_degree": 1, **(serve or {})})
    return family, family.config_cls(tcfg, **dict(HF, **hf))


@pytest.mark.parametrize("serve, hf, error, sentence", [
    (dict(tp_degree=2), {}, NotImplementedError, "served on one chip"),
    (dict(ep_degree=2), {}, NotImplementedError, "served on one chip"),
    ({}, dict(first_expert=13), ValueError, "held of 16"),
])
def test_the_family_refuses_with_a_sentence(serve, hf, error, sentence):
    with pytest.raises(error, match=sentence):
        family, cfg = _config(serve, **hf)
        family.build_spec(cfg)


def test_the_spec_is_the_published_keys():
    family, cfg = _config()
    spec = family.build_spec(cfg)
    assert (spec.num_layers, spec.first_dense, spec.num_moe_layers) == \
        (3, 1, 2)
    assert spec.mla == model_base.MLASpec(
        kv_lora_rank=128, qk_nope_head_dim=16, qk_rope_head_dim=16,
        v_head_dim=16, q_lora_rank=32, q_scale=1.0, kv_scale=1.0)
    m2 = (0.1 * np.log(4) + 1) ** 2
    assert abs(spec.scale - 32 ** -0.5 * m2) < 1e-9 and spec.rope_interleaved
    assert spec.rope.head_dim == 16 and spec.rope.scaling_type == "yarn"
    m = spec.moe
    assert (m.num_experts, m.num_routed, m.num_held, m.first_expert,
            m.top_k, m.n_group, m.topk_group) == (16, 16, 4, 4, 4, 4, 2)
    assert m.normalize_topk and m.routed_scaling == 2.5
    assert m.router_act == "sigmoid" and m.has_router_bias
    assert m.shared_intermediate == 128 and m.holds_share
    # without the share's keys every routed expert is held, sharded or not
    whole = family.build_spec(_config(
        dict(tp_degree=2), n_routed_experts=16,
        router_n_routed_experts=None, first_expert=0)[1], tp_degree=2).moe
    assert whole.num_held == 16 and not whole.holds_share
