"""Ling-3.0-flash's language model on the paged serving path (ISSUE 67): the
delta rule gated BY CHANNEL (Kimi Delta Attention) five layers in six beside
latent attention with a head-wise output gate, a matrix state AND a latent
pool in one cache, one chip's share of a group-limited expert layer.

``ling_kda`` served through ``PagedEngineAdapter`` with default arguments, at
a toy size on the CPU in float32, in ``tests/test_nemotron_h_paged.py``'s
manner: every test holds the LOGITS of the served path, at every position a
dispatch computed, and the FINAL STATES in the slots, to the plain reference
``benchmark/references/ling_kda.py`` (the recurrence token by token; held to
transformers' pieces by ``benchmark/tests/test_reference_ling_kda.py``).

  (a) a prompt in ONE window, then decode through the slots and the latent
      pool;
  (b) prompts walked in chunks of unequal width - one of 1 token, one shorter
      than the conv tail, one padded, one that fills its bucket, one that is
      not a multiple of the chunked form's 16 tokens - each continuing the
      state and the tails the chunk before it left;
  (c) every fault of the reference's ``CONTROLS`` fails (b)'s comparison at a
      tolerance ten times tighter than bf16's;
  (d) the sixteen shares' routed parts and the shared expert counted ONCE add
      up to the uncut layer;

and beside the modules: the chunked form at ``g = -5`` on every channel, the
state-step kernel in interpret mode against ``_kda_step``, a twin whose tiles
the three decode kernels take, the parameter tree and count, the refusals by
name, the records, the scalar-decay and latent families' trees unchanged.
"""

import importlib.util
import json
import math
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

from neuronx_distributed_inference_tpu.config import TpuConfig  # noqa: E402
from neuronx_distributed_inference_tpu.models import model_base  # noqa: E402
from neuronx_distributed_inference_tpu.models.application import \
    PagedCausalLMApplication  # noqa: E402
from neuronx_distributed_inference_tpu.models.family import \
    get_family  # noqa: E402
from neuronx_distributed_inference_tpu.modules import moe, ssm  # noqa: E402
from neuronx_distributed_inference_tpu.ops import \
    delta_state_step  # noqa: E402
from neuronx_distributed_inference_tpu.parallel.layers import \
    ParamSpec  # noqa: E402
from neuronx_distributed_inference_tpu.serving import \
    PagedEngineAdapter  # noqa: E402
from test_recurrent_paged import LogitTap, _decode  # noqa: E402

#: a twin at a toy size: every key of the catalog row's config (the four
#: patch-token ids apart), a period of THREE (K K M K K M K: both dense
#: layers, a linear layer behind an expert block, two latent layers, a
#: linear layer after one), a share of 4 of 16 experts from the fifth, in 4
#: groups of which 2 are chosen
HF = dict(
    model_type="ling_kda", vocab_size=128, hidden_size=64,
    num_hidden_layers=7, intermediate_size=96, first_k_dense_replace=2,
    max_position_embeddings=512, moe_intermediate_size=24,
    num_experts_per_tok=2, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=None, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, head_dim=16, num_experts=4,
    router_num_experts=16, first_expert=4, rope_theta=10000,
    rms_norm_eps=1e-6, partial_rotary_factor=0.5, rotary_dim=8,
    moe_router_enable_expert_bias=True, routed_scaling_factor=2.5, n_group=4,
    topk_group=2, use_qk_norm=True, score_function="sigmoid",
    moe_shared_expert_intermediate_size=24, num_shared_experts=1,
    layer_group_size=3, num_kv_heads_for_linear_attn=0, group_norm_size=1,
    linear_silu=True, use_mla_nope=False, short_conv_kernel_size=4,
    use_nGPT=False, scale_router_input=False, value_norm=False,
    up_proj_norm=False, gated_attention_proj_granularity_type="head_wise",
    mtp_use_kda=False, no_kda_lora=True, use_kda_lora=False,
    kda_safe_gate=True, kda_lower_bound=-5, norm_topk_prob=True,
    expert_swiglu_limit_list=[0] * 7,
    share_expert_swiglu_limit_list=[0] * 7, tie_word_embeddings=False)
#: a twin whose tiles the decode kernels take in interpret mode: a state of
#: (64, 64) a head, a latent of one vreg, heads of 128 nope / value lanes,
#: experts of whole 128-lane tiles
HF_KERNEL = dict(HF, hidden_size=128, num_hidden_layers=3, head_dim=64,
                 num_attention_heads=2, num_key_value_heads=2,
                 kv_lora_rank=128, qk_nope_head_dim=128, qk_rope_head_dim=64,
                 v_head_dim=128, rotary_dim=64, moe_intermediate_size=128,
                 moe_shared_expert_intermediate_size=128,
                 first_k_dense_replace=1, intermediate_size=128,
                 expert_swiglu_limit_list=[0] * 3,
                 share_expert_swiglu_limit_list=[0] * 3)
BATCH = 4
SERVE = dict(batch_size=BATCH, seq_len=128, pa_block_size=8, pa_num_blocks=64,
             context_encoding_buckets=[8, 32], enable_bucketing=True,
             is_block_kv_layout=True, is_prefix_caching=False)
RNG = np.random.default_rng(67)
#: 69 = 32 + 32 + 5 (padded to 8); 65 = 32 + 32 + 1; 66 = 32 + 32 + 2 (fewer
#: than the tail's 3); 40 = 32 + 8 (fills its bucket); 21: one window, not a
#: multiple of the chunked form's 16; 53 = 32 + 21 (in the 32 bucket)
P69, P65, P66, P40, R21, P53 = (RNG.integers(1, 128, size=n).tolist()
                                for n in (69, 65, 66, 40, 21, 53))
#: float32 on both sides: the served logits (|logit| up to ~0.7) agree with
#: the reference's to ~1e-6
ATOL = 2e-5
#: bf16 resolves 2^-8 = 3.9e-3 of a logit of ~1; the controls are held to a
#: tenth of that
CONTROL_TOL = 4e-4

FULL = os.path.join(ROOT, "benchmark", "configs", "ling-3.0-flash.json")


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("ling_kda")


@pytest.fixture(scope="module")
def gate_weights(ref):
    return weights.make_weights(ref.weight_shapes(HF), seed=2**31 + 67)


def _app(ref, w, hf=HF, **serve):
    family = get_family("ling_kda")
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


def _serve(app, prompt, decode=6, sid=7):
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {sid: [ad.add_requests([sid], [prompt])[sid]]}
    _decode(ad, [sid], stream, decode)
    return ad, tap, stream[sid]


def _check(app, ad, tap, ref, w, sid, prompt, stream, hf=HF, atol=ATOL):
    fed = prompt + stream[:-1]
    want = _want(ref, w, fed, hf)
    assert float(np.abs(tap.logits(sid, len(fed)) - want).max()) < atol
    assert stream == want[len(prompt) - 1:].argmax(-1).tolist()
    np.testing.assert_allclose(
        np.asarray(app.cache["ssm"][:, ad._state_slot[sid]]),
        np.asarray(ref.final_states(hf, w, jnp.asarray([fed])))[:, 0],
        atol=atol / 2)


def _notes(app):
    return {(k["site"], k["path"], k["reason"])
            for k in app.warmup_state()["kernels"]}


# ---------------------------------------------------------------------------
# (a), (b): one window, and chunks of unequal width
# ---------------------------------------------------------------------------

def test_a_one_window_then_decode_through_slots_and_latent_pool(
        ref, gate_weights):
    app = _app(ref, gate_weights)
    ad, tap, stream = _serve(app, R21)
    assert tap.shapes == [(1, 32)] + [(BATCH, 1)] * 6
    _check(app, ad, tap, ref, gate_weights, 7, R21, stream)
    # TWO pool layers of latent rows (no V), FIVE state layers, in one cache
    assert app.cache["k"].shape == (2, 65, 8, 1, 128)
    assert app.cache["v"].shape == (2, 65, 8, 1, 0)
    assert app.cache["ssm"].shape == (5, BATCH, 4, 16, 16)
    assert app.cache["conv_x"].shape == (5, BATCH, 3, 3 * 4 * 16)
    assert app.spec.resolved_ssm_pattern == (True, True, False, True, True,
                                             False, True)
    notes = _notes(app)
    assert ("latent_cache", "xla",
            "lanes=128 of 40 values bytes_a_token=1024 sub_blocks=2") in notes
    assert ("moe_share", "xla",
            "held=4 of 16 from 4 top_k=2 groups=4 top=2") in notes
    state = sorted(w for s, _, w in notes if s == "recurrent_state")
    assert all(w.startswith("kind=kda slot_bytes=32000 chunk=16") for w in
               state)
    assert any("32 tokens a row: the chunked form, decay by channel "
               "factored about the middle of a chunk" in w for w in state)
    assert ad.host_stats["state_slots_live"] == 1


@pytest.mark.parametrize("prompt, shapes", [
    (P69, [(1, 32), (1, 32), (1, 8)]),         # a padded last chunk
    (P65, [(1, 32), (1, 32), (1, 8)]),         # ... of ONE token
    (P66, [(1, 32), (1, 32), (1, 8)]),         # ... of 2 < the tail's 3
    (P40, [(1, 32), (1, 8)]),                  # ... that fills its bucket
    (P53, [(1, 32), (1, 32)]),                 # 21 real: 16 + 5 of a chunk
], ids=["padded", "one-token", "shorter-than-the-tail", "full-bucket",
        "not-a-multiple-of-16"])
def test_b_chunks_of_unequal_width_continue_the_carried_state(
        ref, gate_weights, prompt, shapes):
    app = _app(ref, gate_weights)
    ad, tap, stream = _serve(app, prompt)
    assert tap.shapes == shapes + [(BATCH, 1)] * 6
    _check(app, ad, tap, ref, gate_weights, 7, prompt, stream)


def test_b_a_released_slot_and_its_pages_serve_the_next_prompt(
        ref, gate_weights):
    """Two rows beside each other, one released, a NEW prompt in its slot
    (the stale state and tails in it) and its pages: the new row reads what a
    fresh start reads, the row that stayed is not disturbed."""
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {1: [], 2: []}
    for sid, prompt in ((1, P40), (2, R21)):
        stream[sid].append(ad.add_requests([sid], [prompt])[sid])
    _decode(ad, [1, 2], stream, 3)
    slot = ad._state_slot[2]
    ad.release([2])
    assert ad.host_stats["state_slots_live"] == 1
    stream[3] = [ad.add_requests([3], [P53])[3]]
    assert ad._state_slot[3] == slot
    _decode(ad, [1, 3], stream, 3)
    _check(app, ad, tap, ref, gate_weights, 1, P40, stream[1])
    _check(app, ad, tap, ref, gate_weights, 3, P53, stream[3])


def test_b_the_decode_kernels_take_a_twin_of_whole_tiles(ref):
    """A twin whose state tile is (64, 64), whose latent row and heads are
    whole vregs and whose experts are whole tiles: the step runs the state
    kernel with its decay by channel, the latent decode kernel and the walk
    over the touched experts, all in interpret mode, and reads what the XLA
    forms read."""
    w = weights.make_weights(ref.weight_shapes(HF_KERNEL), seed=2**31 + 68)
    app = _app(ref, w, hf=HF_KERNEL)
    ad, tap, stream = _serve(app, R21, decode=4)
    _check(app, ad, tap, ref, w, 7, R21, stream, HF_KERNEL, atol=5e-5)
    notes = _notes(app)
    assert ("recurrent_state", "pallas-interpret",
            "kind=kda slot_bytes=74752 chunk=16 heads=2 tile=64x64 "
            "decay=channel") in notes
    assert any(s == "mla_decode" and p == "pallas-interpret"
               and "heads=2" in w_ for s, p, w_ in notes)
    assert any(s == "moe_decode" and p == "pallas-interpret"
               for s, p, _ in notes)
    assert ad.host_stats["dispatches_state_kernel"] == \
        ad.host_stats["dispatches"] == 4


# ---------------------------------------------------------------------------
# (c) the controls
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served_p69(ref, gate_weights):
    app = _app(ref, gate_weights)
    _, tap, stream = _serve(app, P69)
    fed = P69 + stream[:-1]
    return fed, tap.logits(7, len(fed))


CONTROLS = ("decay_by_head", "no_bound", "no_dt_bias", "decay_after_write",
            "no_beta", "no_qk_norm", "no_conv_silu", "no_conv_tail",
            "no_head_gate_kda", "gate_before_norm", "bf16_state",
            "no_head_gate_mla", "no_rotary", "rope_interleaved",
            "no_latent_norm", "softmax", "no_groups", "no_select_bias",
            "bias_in_weights", "not_renormalised", "no_routed_scaling",
            "no_shared")


def test_c_the_controls_are_the_references_list(ref):
    assert set(ref.CONTROLS) == set(CONTROLS)


@pytest.mark.parametrize("control", CONTROLS)
def test_c_every_control_fails_ten_times_under_bf16s_tolerance(
        ref, gate_weights, served_p69, control):
    fed, got = served_p69
    sound = np.abs(got - _want(ref, gate_weights, fed)).max()
    faulty = np.abs(got - _want(ref, gate_weights, fed,
                                control=control)).max()
    assert sound < ATOL < CONTROL_TOL < faulty, (control, sound, faulty)


# ---------------------------------------------------------------------------
# (d) the share test
# ---------------------------------------------------------------------------

def _group_spec(**kw):
    return moe.MoESpec(**dict(dict(
        num_experts=32, top_k=4, intermediate_size=24, router_act="sigmoid",
        has_router_bias=True, router_bias_mode="select", normalize_topk=True,
        routed_scaling=2.5, shared_intermediate=24, n_group=8, topk_group=4),
        **kw))


def test_d_sixteen_shares_and_the_shared_expert_once_add_up_to_the_layer(
        ref):
    """The sixteen chips' routed parts (2 of 32 experts each, the router, its
    groups and its bias over all 32) plus the shared expert counted ONCE are
    the uncut layer's output, in the program and in the reference."""
    rng = np.random.default_rng(5)
    hid, n_e, inter = 32, 32, 24

    def leaf(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)
    x = leaf(2, 5, hid)
    lw = {"router": leaf(hid, n_e), "router_bias": leaf(n_e) * 0.5,
          "expert_gate": leaf(n_e, hid, inter),
          "expert_up": leaf(n_e, hid, inter),
          "expert_down": leaf(n_e, inter, hid),
          "shared_gate": leaf(hid, inter), "shared_up": leaf(hid, inter),
          "shared_down": leaf(inter, hid)}
    whole = moe.moe_block(_group_spec(), x, lw)
    parts = []
    for chip in range(16):
        held = {k: (v[2 * chip:2 * chip + 2] if k.startswith("expert_")
                    else v) for k, v in lw.items()}
        parts.append(moe.moe_block(
            _group_spec(held_experts=2, first_expert=2 * chip), x, held,
            shared=False))
    total = sum(parts) + moe.shared_experts(_group_spec(), x, lw)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5)
    # ... and the reference's uncut layer is the same function
    cfg = dict(HF, hidden_size=hid, num_experts=n_e, router_num_experts=n_e,
               first_expert=0, num_experts_per_tok=4, n_group=8, topk_group=4)
    t = lambda a: jnp.swapaxes(a, -1, -2)[None]              # noqa: E731
    w = {ref.MLP + "gate.weight": t(lw["router"]),
         ref.MLP + "gate.e_score_correction_bias": lw["router_bias"][None],
         **{ref.EXPERT + n + "_proj.weight": t(lw["expert_" + n])
            for n in ("gate", "up", "down")},
         **{ref.SHARED + n + "_proj.weight": t(lw["shared_" + n])
            for n in ("gate", "up", "down")}}
    want, _ = ref.moe(cfg, w, 0, x)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               atol=1e-5)
    # a share of the reference leaves the absent experts' part out too
    share_cfg = dict(cfg, num_experts=2, first_expert=6)
    share_w = {k: (v[:, 6:8] if k.startswith(ref.EXPERT) else v)
               for k, v in w.items()}
    got, _ = ref.moe(share_cfg, share_w, 0, x, "no_shared")
    np.testing.assert_allclose(np.asarray(got), np.asarray(parts[3]),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# beside the modules: the two forms of the rule, and the kernel
# ---------------------------------------------------------------------------

def _rule_inputs(rng, b, t, h, dk, dv, g=None):
    def unit(*shape):
        a = rng.normal(size=shape)
        return a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = jnp.asarray(unit(b, t, h, dk) * dk ** -0.5, jnp.float32)
    k = jnp.asarray(unit(b, t, h, dk), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, h, dv)), jnp.float32)
    g = jnp.asarray(-5.0 * rng.uniform(size=(b, t, h, dk)) if g is None
                    else np.full((b, t, h, dk), g), jnp.float32)
    beta = jnp.asarray(rng.uniform(size=(b, t, h)), jnp.float32)
    st0 = jnp.asarray(rng.normal(size=(b, h, dk, dv)), jnp.float32)
    return q, k, v, g, beta, st0


def _token_by_token(q, k, v, g, beta, st0):
    outs, st = [], st0
    for i in range(q.shape[1]):
        o, st = ssm._kda_step(q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i],
                              st)
        outs.append(o)
    return jnp.stack(outs, axis=1), st


@pytest.mark.parametrize("tokens, g", [(64, -5.0), (64, None), (21, None),
                                       (3, -5.0)],
                         ids=["all-at-the-bound", "spread", "ragged",
                              "short"])
def test_the_chunked_form_is_the_token_by_token_form(tokens, g):
    """At ``g = -5`` on EVERY channel over 64 tokens the cumulative log decay
    reaches -320: a form that factors the pair weights about one end of a
    chunk of 64 overflows float32; about the middle of 16 it stays under
    exp(40) and is finite and equal."""
    args = _rule_inputs(np.random.default_rng(tokens), 2, tokens, 3, 16, 8,
                        g)
    want_o, want_s = _token_by_token(*args)
    chunk = ssm.kda_chunk_tokens(-5.0)
    assert chunk == 16
    got_o, got_s = ssm._kda_chunked(*args, chunk)
    assert bool(jnp.isfinite(got_o).all()) and bool(jnp.isfinite(got_s).all())
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=2e-5)


def test_the_chunk_follows_the_bound_and_an_unbounded_decay_is_refused():
    assert [ssm.kda_chunk_tokens(b) for b in (-1.0, -5.0, -10.0, -40.0)] == \
        [64, 16, 8, 2]
    with pytest.raises(ValueError, match="decay_lower_bound 0"):
        ssm.kda_chunk_tokens(0.0)


@pytest.mark.parametrize("heads, dk, dv", [(4, 64, 64), (2, 128, 128),
                                           (6, 8, 64)])
def test_the_state_kernel_steps_a_decay_by_channel_in_place(heads, dk, dv):
    """``kda_state_step`` in interpret mode against ``_kda_step`` with a
    column decay: a live row's ``o`` and state, a fresh row (``keep`` false)
    from zeros, a dead row skipped with a zero ``o``, the other layers of the
    stack untouched."""
    rng = np.random.default_rng(heads)
    b, layers, layer = 5, 3, 1
    q, k, v, g, beta, _ = _rule_inputs(rng, b, 1, heads, dk, dv)
    stack = jnp.asarray(rng.normal(size=(layers, b, heads, dk, dv)),
                        jnp.float32)
    keep = jnp.asarray([True, False, True, True, True])
    live = jnp.asarray([True, True, False, True, True])
    assert delta_state_step.declined(stack, b, 1, heads) == ""
    want_o, want_s = ssm._kda_step(
        q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
        jnp.where(keep[:, None, None, None], stack[layer], 0.0))
    got_o, got = delta_state_step.kda_state_step(
        stack, layer, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], keep,
        live, interpret=True)
    rows = np.asarray(live)
    np.testing.assert_allclose(np.asarray(got_o)[rows],
                               np.asarray(want_o)[rows], atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[layer])[rows],
                               np.asarray(want_s)[rows], atol=1e-5)
    assert float(jnp.abs(got_o[2]).max()) == 0.0
    np.testing.assert_array_equal(np.asarray(got[layer, 2]),
                                  np.asarray(stack[layer, 2]))
    for other in (0, 2):
        np.testing.assert_array_equal(np.asarray(got[other]),
                                      np.asarray(stack[other]))
    # a decay taken by head is another result
    by_head = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    off_o, _ = ssm._kda_step(q[:, 0], k[:, 0], v[:, 0], by_head[:, 0],
                             beta[:, 0], stack[layer])
    assert float(jnp.abs(off_o - want_o)[rows].max()) > 1e-2


def test_the_scalar_rule_is_bit_for_bit_what_it_was():
    """The third rule is beside the second, not in its place: the scalar
    rule's operand packing and outputs for a scalar decay are untouched (a
    decay by channel with equal entries a head reads the same to rounding)."""
    rng = np.random.default_rng(3)
    b, heads, dk, dv = 3, 4, 64, 64
    q, k, v, g, beta, _ = _rule_inputs(rng, b, 1, heads, dk, dv)
    g_head = g[:, 0, :, 0]
    stack = jnp.asarray(rng.normal(size=(2, b, heads, dk, dv)), jnp.float32)
    ones = jnp.ones((b,), bool)
    plan = delta_state_step.state_step_plan(heads, heads, dk, dv)
    rows = delta_state_step.delta_rows(q[:, 0], k[:, 0], jnp.exp(g_head),
                                       beta[:, 0], ones, plan)
    assert rows.shape == (b, 1, 2 * heads + heads + 2, dk)
    o_s, s_s = delta_state_step.delta_state_step(
        stack, 0, q[:, 0], k[:, 0], v[:, 0], g_head, beta[:, 0], ones, ones,
        interpret=True)
    want_o, want_s = ssm._delta_step(q[:, 0], k[:, 0], v[:, 0], g_head,
                                     beta[:, 0], stack[0])
    np.testing.assert_allclose(np.asarray(o_s), np.asarray(want_o),
                               atol=1e-5)
    o_c, s_c = delta_state_step.kda_state_step(
        stack, 0, q[:, 0], k[:, 0], v[:, 0],
        jnp.broadcast_to(g_head[..., None], (b, heads, dk)), beta[:, 0],
        ones, ones, interpret=True)
    np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_s), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_c[0]), np.asarray(s_s[0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_s[0]), np.asarray(want_s),
                               atol=1e-5)


def test_declined_names_what_the_channel_rule_declines():
    s = ssm.SSMSpec(kind="kda", d_inner=32 * 128, num_heads=32, head_dim=128,
                    d_state=128, chunk_size=16, decay_lower_bound=-5.0)
    stack = jax.ShapeDtypeStruct((15, 64, 32, 128, 128), jnp.float32)
    assert ssm.state_kernel_declined(s, stack, 64, 1, None) == ""
    assert ssm.state_kernel_note(s, stack) == \
        "heads=32 tile=128x128 decay=channel"
    assert ssm.state_kernel_declined(s, stack, 1, 256, None) == (
        "256 tokens a row: the chunked form, decay by channel factored "
        "about the middle of a chunk, blocked substitution")
    assert ssm.state_kernel_declined(
        s, stack, 1, 1, jnp.zeros((1,), jnp.int32)) == \
        "rows gathered from their slots"
    toy = jax.ShapeDtypeStruct((5, 4, 4, 16, 16), jnp.float32)
    assert "are not whole 8x64 tiles" in ssm.state_kernel_declined(
        s, toy, 4, 1, None)


# ---------------------------------------------------------------------------
# the specs: the tree, the count, the refusals, the neighbours
# ---------------------------------------------------------------------------

def _full_spec():
    with open(FULL) as f:
        cfg = json.load(f)
    return cfg, build.build_app(cfg).spec


def test_the_parameter_tree_and_count_are_the_files():
    cfg, spec = _full_spec()
    specs = model_base.decoder_param_specs(spec)
    assert sorted(specs) == ["attn_layers", "embed", "final_norm", "layers",
                             "lm_head", "moe_layers", "ssm_layers"]
    assert sorted(specs["attn_layers"]) == [
        "g_proj", "kv_a_norm", "kv_a_proj", "kv_b_proj", "o_proj", "q_proj"]
    assert sorted(specs["ssm_layers"]) == [
        "kda_A_log", "kda_conv", "kda_dt_bias", "kda_in", "kda_in_a",
        "kda_in_bg", "kda_norm", "kda_out"]
    assert sorted(specs["layers"]) == ["down_proj", "gate_proj", "input_norm",
                                       "post_norm", "up_proj"]
    for stack, n in (("ssm_layers", 15), ("attn_layers", 3), ("layers", 2),
                     ("moe_layers", 16)):
        assert {ps.shape[0] for ps in specs[stack].values()} == {n}
    assert specs["ssm_layers"]["kda_dt_bias"].shape == (15, 4096)
    assert specs["ssm_layers"]["kda_A_log"].shape == (15, 32)
    assert specs["moe_layers"]["router"].shape == (16, 2560, 512)
    assert specs["moe_layers"]["expert_up"].shape == (16, 32, 2560, 768)
    leaves = jax.tree.leaves(specs,
                             is_leaf=lambda x: isinstance(x, ParamSpec))
    count = sum(math.prod(ps.shape) for ps in leaves)
    pad = 2 * (spec.padded_vocab - cfg["vocab_size"]) * 2560
    assert count - pad == cfg["memory"]["parameters"] == 4_215_902_560
    kda, mla = 52_646_048, 31_965_696
    assert sum(math.prod(ps.shape[1:])
               for ps in specs["ssm_layers"].values()) == kda
    assert sum(math.prod(ps.shape[1:])
               for ps in specs["attn_layers"].values()) == mla
    assert count - pad == (15 * kda + 3 * mla + 18 * 5120 + 2 * 47_185_920
                           + 16 * (33 * 5_898_240 + 1_311_232)
                           + 2 * 19_648 * 2560 + 2560)


def test_the_family_refuses_by_name_what_the_row_does_not_define():
    family = get_family("ling_kda")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", **SERVE)

    def spec(**kw):
        return family.build_spec(family.config_cls(tcfg, **dict(HF, **kw)))
    hot = [0] * 6 + [4]
    with pytest.raises(NotImplementedError,
                       match=r"expert_swiglu_limit_list\[6\] = 4"):
        spec(expert_swiglu_limit_list=hot)
    with pytest.raises(NotImplementedError,
                       match=r"share_expert_swiglu_limit_list\[6\] = 4"):
        spec(share_expert_swiglu_limit_list=hot)
    with pytest.raises(ValueError, match="cut the list with the depth"):
        spec(expert_swiglu_limit_list=[0] * 9)
    for key, value in (("use_nGPT", True), ("value_norm", True),
                       ("up_proj_norm", True), ("scale_router_input", True),
                       ("use_kda_lora", True), ("mtp_use_kda", True),
                       ("q_lora_rank", 64), ("use_mla_nope", True),
                       ("kda_safe_gate", False)):
        with pytest.raises(NotImplementedError, match=f"{key} = "):
            spec(**{key: value})
    with pytest.raises(NotImplementedError,
                       match="num_kv_heads_for_linear_attn = 2"):
        spec(num_kv_heads_for_linear_attn=2)
    assert spec(num_kv_heads_for_linear_attn=4).ssm.num_heads == 4
    with pytest.raises(NotImplementedError, match="whole layer_group_size"):
        spec(num_hidden_layers=2, expert_swiglu_limit_list=[0, 0],
             share_expert_swiglu_limit_list=[0, 0])
    with pytest.raises(NotImplementedError, match="one chip"):
        family.build_spec(family.config_cls(tcfg, **HF), tp_degree=4)
    with pytest.raises(ValueError, match="decay_lower_bound"):
        spec(kda_lower_bound=0)
    # the reference refuses the same
    ref = build.load_reference("ling_kda")
    with pytest.raises(ValueError, match="expert_swiglu_limit_list"):
        ref.weight_shapes(dict(HF, expert_swiglu_limit_list=hot))


def test_what_a_recurrent_stack_refuses_stays_refused_by_name(
        ref, gate_weights):
    """Prefix reuse over a state, speculation, ragged dispatch and spill stay
    refused with a latent pool beside the slots, each by its name."""
    table = model_base.RECURRENT_UNSUPPORTED
    assert "latent pool" in table["prefix caching"]
    assert "kda" in table["paged rglru state"]
    for asked in ("prefix caching", "speculation", "ragged dispatch",
                  "host KV spill / handoff"):
        with pytest.raises(NotImplementedError, match=asked):
            model_base.refuse_recurrent([asked])
    family = get_family("ling_kda")
    with pytest.raises(NotImplementedError, match="prefix caching"):
        tcfg = TpuConfig(tp_degree=1, dtype="float32",
                         **dict(SERVE, is_prefix_caching=True))
        PagedCausalLMApplication(None, family.config_cls(tcfg, **HF), family)


def test_scalar_decay_and_latent_families_trees_are_what_they_were():
    """The channel gate is a kind and the head-wise gate a field, not forks:
    the two scalar-decay families keep their ``gdn_*`` leaves and state
    layout, the two latent ones get no ``g_proj``."""
    for name, leaves in (("olmo-hybrid-7b", 7), ("qwen3-next-80b-a3b", 7)):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               name + ".json")) as f:
            spec = build.build_app(json.load(f)).spec
        stack = model_base.decoder_param_specs(spec)["ssm_layers"]
        assert sorted(stack) == ["gdn_A_log", "gdn_conv", "gdn_dt_bias",
                                 "gdn_in", "gdn_in_ab", "gdn_norm",
                                 "gdn_out"] and len(stack) == leaves
        assert spec.ssm.kind == "gated_delta"
        assert spec.ssm.decay_lower_bound == 0.0
        shapes = ssm.ssm_state_shapes(spec.ssm, 2, 4, jnp.bfloat16)
        assert shapes["conv_x"][0] == (2, 4, spec.ssm.qkv_size, 3)
        big = jax.ShapeDtypeStruct(
            (2, 4, spec.ssm.num_heads, spec.ssm.d_state, spec.ssm.head_dim),
            jnp.float32)
        assert ssm.state_kernel_declined(spec.ssm, big, 4, 1, None) == ""
        assert "decay=" not in ssm.state_kernel_note(spec.ssm, big)
        assert ssm.state_kernel_declined(spec.ssm, big, 1, 64, None) == (
            "64 tokens a row: the chunked form, " + ssm.SOLVE_NOTE)
    for name in ("deepseek-v3", "longcat-flash-omni"):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               name + ".json")) as f:
            spec = build.build_app(json.load(f)).spec
        assert not spec.mla.head_gate
        assert "g_proj" not in model_base.decoder_param_specs(spec)["layers"]


def test_gate67_script_loads_and_cuts_the_limit_lists_with_the_depth():
    spec = importlib.util.spec_from_file_location(
        "gate67", os.path.join(ROOT, "scripts", "gate67.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert module.NAMES == ("model.embed_tokens.weight", "lm_head.weight")
    assert callable(module.long_walk) and callable(module.main)
    with open(FULL) as f:
        cfg = json.load(f)
    twin = module.gate_twin_of(cfg, 7)
    assert twin == cfg["gate"]["config"]
    assert twin["expert_swiglu_limit_list"] == [0] * 7


def test_gate67s_walk_holds_logits_and_final_states_at_a_toy_size():
    """``scripts/gate67.py``'s long walk on the toy twin (float32, the CPU
    backend): three rows of 70 tokens in chunks of 32 and 8, decode, a row
    released and a new prompt in its slot; every served position's logits
    and, for the sequences still in a slot, every linear layer's final state
    against the reference's."""
    spec = importlib.util.spec_from_file_location(
        "gate67", os.path.join(ROOT, "scripts", "gate67.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    toy = dict(HF, family="ling_kda", tp=1, dtype="float32", serve=SERVE,
               adapter={},
               gate=dict(config={}, batch=2, prompt_len=24, new_tokens=8,
                         atol=2e-5, rtol=1e-4, min_positions_held=1.0,
                         median_ratio_max=0.5, worst_ratio_max=1.0,
                         excuse_margin_max=0.0))
    out = module.long_walk(toy, 2**31 + 67, 70, rows=3, new_tokens=6,
                           block=16, second=21, twin={},
                           served_precision="highest")
    assert out["passed"], out
    assert out["slot_reused"] and out["released"] == 1
    # the packs of three rows, the one-row chunk of the late prompt, steps
    assert out["program_shapes"] == [(1, 32), (3, 1), (3, 8), (3, 32)]
    states = out["final_states"]
    assert states["finite"] and states["layers"] == 5
    assert len(states["sequences"]) == 3
    assert sorted(s["tokens"] for s in states["sequences"]) == [27, 76, 76]
    assert states["worst_share"] < 1e-4
    assert all(s["other_slots_share"] > 0.1 for s in states["sequences"])
