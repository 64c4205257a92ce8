"""NVIDIA-Nemotron-3-Nano-30B-A3B on the paged serving path (ISSUE 64): a
stack whose layer is ONE sub-block (Mamba-2 in several B / C groups | GQA with
no rotary | PLAIN ReLU² experts with a shared expert), experts of two
matrices at a width that is not whole vregs, and one chip's share of them.

``nemotron_h`` served through ``PagedEngineAdapter`` with default arguments,
at a toy size on the CPU in float32, in ``tests/test_lfm2_moe_paged.py``'s
manner: every test holds the LOGITS of the served path, at every position a
dispatch computed, to the plain reference
``benchmark/references/nemotron_h.py`` (the sequential recurrence; held to
transformers' pieces by ``benchmark/tests/test_reference_nemotron_h.py``).

  (a) a prompt in ONE window, then decode through the pool and the slots;
  (b) prompts walked in chunks of unequal width - one of 1 token, one
      shorter than ``conv_kernel - 1``, one padded, one that fills its bucket
      - each continuing the state and the tail the chunk before it left;
  (c) every fault of the reference's ``CONTROLS`` fails (b)'s comparison at a
      tolerance ten times tighter than bf16's;
  (d) the eight shares' routed parts and the shared expert counted ONCE add
      up to the uncut layer;

and beside the modules: the plain walk in interpret mode against
``experts_dense`` and ``experts_ragged`` at a published width that is not
whole vregs (both kernel forms, pieces, pad and dead rows), the state step
kernel at 8 groups against the XLA branch, the parameter tree and count, the
engagement records, the gated stacks' tree and ``declined`` text unchanged.
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

from neuronx_distributed_inference_tpu.config import TpuConfig  # noqa: E402
from neuronx_distributed_inference_tpu.models import model_base  # noqa: E402
from neuronx_distributed_inference_tpu.models.application import \
    PagedCausalLMApplication  # noqa: E402
from neuronx_distributed_inference_tpu.models.family import \
    get_family  # noqa: E402
from neuronx_distributed_inference_tpu.modules import moe, ssm  # noqa: E402
from neuronx_distributed_inference_tpu.ops import moe_decode  # noqa: E402
from neuronx_distributed_inference_tpu.parallel.layers import \
    ParamSpec  # noqa: E402
from neuronx_distributed_inference_tpu.serving import \
    PagedEngineAdapter  # noqa: E402
from test_recurrent_paged import LogitTap, _decode  # noqa: E402

#: the gate's twin at a toy size (a prefix of the published pattern with
#: every kind of layer; the attention layer is not the last): every key of
#: the catalog row's config, a share of 4 of 8 experts from the third. The
#: hidden size is ONE vreg so that the served steps run the plain walk
#: (interpret mode), and the expert width 24 is stored as 128
HF = dict(
    model_type="nemotron_h", vocab_size=128, hidden_size=128,
    num_hidden_layers=7, hybrid_override_pattern="MEMEM*E",
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    attention_bias=False, mamba_num_heads=8, mamba_head_dim=8, n_groups=4,
    ssm_state_size=16, conv_kernel=4, chunk_size=128, expand=2,
    mamba_hidden_act="silu", mamba_proj_bias=False, use_conv_bias=True,
    use_bias=False, mlp_bias=False, mlp_hidden_act="relu2",
    intermediate_size=24, layer_norm_epsilon=1e-5, norm_eps=1e-5,
    moe_intermediate_size=24, moe_shared_expert_intermediate_size=48,
    n_routed_experts=4, router_n_routed_experts=8, first_expert=2,
    n_shared_experts=1, num_experts_per_tok=2, norm_topk_prob=True,
    routed_scaling_factor=2.5, n_group=1, topk_group=1, rope_theta=10000,
    partial_rotary_factor=1, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=0.0001, residual_in_fp32=False, sliding_window=None,
    max_position_embeddings=512, tie_word_embeddings=False)
#: an older Nemotron-H row's dense MLP layers ("-") under the same walk
HF_DENSE = dict({k: v for k, v in HF.items()
                 if not k.startswith(("n_routed", "router_n", "first_expert",
                                      "moe_"))},
                hybrid_override_pattern="M-M*-M-", intermediate_size=40)
BATCH = 4
SERVE = dict(batch_size=BATCH, seq_len=128, pa_block_size=8, pa_num_blocks=64,
             context_encoding_buckets=[8, 32], enable_bucketing=True,
             is_block_kv_layout=True, is_prefix_caching=False)
RNG = np.random.default_rng(64)
#: 69 = 32 + 32 + 5 (padded to 8); 65 = 32 + 32 + 1; 66 = 32 + 32 + 2 (fewer
#: than conv_kernel - 1 = 3); 40 = 32 + 8 (fills its bucket); 21: one window
P69, P65, P66, P40, R21 = (RNG.integers(1, 128, size=n).tolist()
                           for n in (69, 65, 66, 40, 21))
#: float32 on both sides: the served logits (|logit| up to ~1) agree with the
#: reference's to ~1e-6; the weakest control (rotary on two kv heads of 8
#: lanes over 69 positions) moves them by 1.2e-3
ATOL = 2e-5
#: bf16 resolves 2^-8 = 3.9e-3 of a logit of ~1; the controls are held to a
#: tenth of that (the weakest, rotary on near-uniform toy attention, reads
#: 1.2e-3)
CONTROL_TOL = 4e-4

FULL = os.path.join(ROOT, "benchmark", "configs",
                    "nemotron-3-nano-30b-a3b.json")


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("nemotron_h")


@pytest.fixture(scope="module")
def gate_weights(ref):
    return weights.make_weights(ref.weight_shapes(HF), seed=2**31 + 64)


def _app(ref, w, hf=HF, **serve):
    family = get_family("nemotron_h")
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


def _check(tap, ref, w, sid, prompt, stream, hf=HF):
    fed = prompt + stream[:-1]
    want = _want(ref, w, fed, hf)
    assert float(np.abs(tap.logits(sid, len(fed)) - want).max()) < ATOL
    assert stream == want[len(prompt) - 1:].argmax(-1).tolist()


def _notes(app):
    return {(k["site"], k["path"], k["reason"])
            for k in app.warmup_state()["kernels"]}


# ---------------------------------------------------------------------------
# (a), (b): one window, and chunks of unequal width
# ---------------------------------------------------------------------------

def test_a_one_window_then_decode_through_pool_and_slots(ref, gate_weights):
    app = _app(ref, gate_weights)
    ad, tap, stream = _serve(app, R21)
    assert tap.shapes == [(1, 32)] + [(BATCH, 1)] * 6
    _check(tap, ref, gate_weights, 7, R21, stream)
    # ONE pool layer (the attention layer), three state layers (the mixers)
    assert app.cache["k"].shape[0] == 1
    assert app.cache["ssm"].shape == (3, BATCH, 8, 8, 16)
    assert app.cache["conv_bc"].shape == (3, BATCH, 2 * 4 * 16, 3)
    np.testing.assert_allclose(
        np.asarray(app.cache["ssm"][:, ad._state_slot[7]]),
        np.asarray(ref.final_states(
            HF, gate_weights, jnp.asarray([R21 + stream[:-1]])))[:, 0],
        atol=1e-5)
    # the records: the stack by kind, the plain walk with both widths, the
    # share
    notes = _notes(app)
    assert ("layer_blocks", "xla", "mamba=3 attention=1 moe=3") in notes
    assert ("moe_decode", "pallas-interpret",
            "plain pieces=1 of 128 (24 of 128 stored)") in notes
    assert ("moe_share", "xla", "held=4 of 8 from 2 top_k=2") in notes


@pytest.mark.parametrize("prompt, shapes", [
    (P69, [(1, 32), (1, 32), (1, 8)]),         # a padded last chunk
    (P65, [(1, 32), (1, 32), (1, 8)]),         # ... of ONE token
    (P66, [(1, 32), (1, 32), (1, 8)]),         # ... of 2 < conv_kernel - 1
    (P40, [(1, 32), (1, 8)]),                  # ... that fills its bucket
], ids=["padded", "one-token", "shorter-than-the-tail", "full-bucket"])
def test_b_chunks_of_unequal_width_continue_the_carried_state(
        ref, gate_weights, prompt, shapes):
    app = _app(ref, gate_weights)
    ad, tap, stream = _serve(app, prompt)
    assert tap.shapes == shapes + [(BATCH, 1)] * 6
    _check(tap, ref, gate_weights, 7, prompt, stream)
    np.testing.assert_allclose(
        np.asarray(app.cache["ssm"][:, ad._state_slot[7]]),
        np.asarray(ref.final_states(
            HF, gate_weights, jnp.asarray([prompt + stream[:-1]])))[:, 0],
        atol=1e-5)


def test_b_dense_mlp_layers_of_the_older_rows_under_the_same_walk(ref):
    """``-`` layers (a dense plain MLP, no expert anywhere): the same walk,
    the stack "layers" beside the mixers' and the attention's."""
    w = weights.make_weights(ref.weight_shapes(HF_DENSE), seed=2**31 + 65)
    app = _app(ref, w, hf=HF_DENSE)
    assert app.spec.moe is None
    assert app.spec.layer_blocks == ("mamba", "mlp", "mamba", "attention",
                                     "mlp", "mamba", "mlp")
    assert sorted(app.params["layers"]) == ["down_proj", "gate_proj",
                                            "input_norm"]
    _, tap, stream = _serve(app, P40)
    _check(tap, ref, w, 7, P40, stream, HF_DENSE)


# ---------------------------------------------------------------------------
# (c) the controls
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served_p69(ref, gate_weights):
    app = _app(ref, gate_weights)
    _, tap, stream = _serve(app, P69)
    fed = P69 + stream[:-1]
    return fed, tap.logits(7, len(fed))


def test_c_the_controls_are_the_references_list(ref):
    assert set(ref.CONTROLS) == {
        "relu_for_relu2", "gated_expert", "norm_whole_width",
        "norm_before_gate", "bc_group0", "bias_dropped", "renorm_dropped",
        "scaling_dropped", "shared_dropped", "softmax_router",
        "rotary_applied"}


@pytest.mark.parametrize("control", [
    "relu_for_relu2", "gated_expert", "norm_whole_width", "norm_before_gate",
    "bc_group0", "bias_dropped", "renorm_dropped", "scaling_dropped",
    "shared_dropped", "softmax_router", "rotary_applied"])
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

def _plain_spec(**kw):
    return moe.MoESpec(**dict(dict(
        num_experts=16, top_k=3, intermediate_size=24, glu_style="plain",
        act="relu2", router_act="sigmoid", has_router_bias=True,
        router_bias_mode="select", normalize_topk=True, topk_norm_eps=1e-20,
        routed_scaling=2.5, shared_intermediate=48), **kw))


def test_d_eight_shares_and_the_shared_expert_once_add_up_to_the_layer(ref):
    """The eight chips' routed parts (2 of 16 experts each, the router over
    all 16) plus the shared expert counted ONCE are the uncut layer's
    output, in the program and in the reference."""
    rng = np.random.default_rng(5)
    hid, n_e, inter = 32, 16, 24

    def leaf(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)
    x = leaf(2, 5, hid)
    lw = {"router": leaf(hid, n_e), "router_bias": leaf(n_e) * 0.5,
          "expert_up": leaf(n_e, hid, inter),
          "expert_down": leaf(n_e, inter, hid),
          "shared_up": leaf(hid, 48), "shared_down": leaf(48, hid)}
    whole = moe.moe_block(_plain_spec(), x, lw)
    parts = []
    for chip in range(8):
        held = {k: (v[2 * chip:2 * chip + 2] if k.startswith("expert_")
                    else v) for k, v in lw.items()}
        parts.append(moe.moe_block(
            _plain_spec(held_experts=2, first_expert=2 * chip), x, held,
            shared=False))
    total = sum(parts) + moe.shared_experts(_plain_spec(), x, lw)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5)
    # ... and the reference's uncut layer is the same function
    cfg = dict(HF, hidden_size=hid, n_routed_experts=n_e,
               router_n_routed_experts=n_e, first_expert=0,
               num_experts_per_tok=3, num_hidden_layers=1,
               hybrid_override_pattern="E")
    w = {ref.MIX + "gate.weight": lw["router"].T[None],
         ref.MIX + "gate.e_score_correction_bias": lw["router_bias"][None],
         ref.EXPERT + "up_proj.weight":
             jnp.swapaxes(lw["expert_up"], 1, 2)[None],
         ref.EXPERT + "down_proj.weight":
             jnp.swapaxes(lw["expert_down"], 1, 2)[None],
         ref.SHARED + "up_proj.weight": lw["shared_up"].T[None],
         ref.SHARED + "down_proj.weight": lw["shared_down"].T[None]}
    want, _ = ref._experts(cfg, w, 0, x, None)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               atol=1e-5)
    # a share of the reference leaves the absent experts' part out too
    share_cfg = dict(cfg, n_routed_experts=2, first_expert=6)
    share_w = dict(w)
    for name in ("up_proj.weight", "down_proj.weight"):
        share_w[ref.EXPERT + name] = w[ref.EXPERT + name][:, 6:8]
    got, _ = ref._experts(share_cfg, share_w, 0, x, "shared_dropped")
    np.testing.assert_allclose(np.asarray(got), np.asarray(parts[3]),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# the plain walk beside the dense and the ragged path
# ---------------------------------------------------------------------------

#: name: (published width, stored width, hidden, held, layers, dtype). The
#: last is walked in TWO column pieces (float32: two slots of two matrices of
#: 2048 x 1280 do not fit the slots' 24 MiB)
PLAIN = {
    "14.5-vregs": (232, 256, 128, 8, 3, jnp.float32),
    "bf16": (232, 256, 256, 8, 3, jnp.bfloat16),
    "two-pieces": (1200, 1280, 2048, 4, 2, jnp.float32),
}
#: (rows, tokens a row): decode steps (dead rows among them), a one-row
#: chunk of one tile, and two that go an expert's rows at a time (one with
#: pad clones, one uneven)
PLAIN_STEPS = [(1, 1), (32, 1), (1, 64), (1, 256), (3, 67)]


def _plain_case(name, rows, tokens, seed=0):
    width, stored, hidden, held, layers, dtype = PLAIN[name]
    spec = _plain_spec(num_experts=2 * held, held_experts=held,
                       first_expert=held // 2, intermediate_size=width,
                       stored_intermediate=stored, shared_intermediate=0)
    rng = np.random.default_rng(seed)

    def leaf(*shape):
        return rng.normal(size=shape) * 0.05
    live = (np.arange(stored) < width)
    up = jnp.asarray(leaf(layers, held, hidden, stored) * live, dtype)
    down = jnp.asarray(leaf(layers, held, stored, hidden)
                       * live[:, None], dtype)
    x = jnp.asarray(rng.normal(size=(rows, tokens, hidden)), dtype)
    router = jnp.asarray(rng.normal(size=(hidden, spec.num_experts)),
                         jnp.float32)
    bias = jnp.asarray(rng.normal(size=(spec.num_experts,)) * 0.1,
                       jnp.float32)
    return spec, x, router, bias, up, down


def _f32(a):
    return np.asarray(a.astype(jnp.float32))


@pytest.mark.parametrize("rows, tokens", PLAIN_STEPS,
                         ids=[f"{r}x{t}" for r, t in PLAIN_STEPS])
@pytest.mark.parametrize("name", ["14.5-vregs", "bf16"])
def test_plain_walk_equals_dense_and_ragged_at_a_width_of_half_vregs(
        name, rows, tokens):
    spec, x, router, bias, up, down = _plain_case(name, rows, tokens,
                                                  seed=rows + tokens)
    layer = 1
    top_vals, top_idx = moe.route(spec, x, router, bias)
    dense = moe.experts_dense(spec, x, top_vals, top_idx, None, up[layer],
                              down[layer])
    ragged = moe.experts_ragged(spec, x, top_vals, top_idx, None, up, down,
                                layer=layer)
    got, read = moe.experts_touched(spec, x, top_vals, top_idx, None, up,
                                    down, layer)
    assert got.dtype == x.dtype and got.shape == x.shape
    scale = float(np.abs(_f32(dense)).max()) or 1.0
    tol = (3e-2 if x.dtype == jnp.bfloat16 else 1e-5) * scale
    np.testing.assert_allclose(_f32(got), _f32(dense), rtol=0, atol=tol)
    np.testing.assert_allclose(_f32(ragged), _f32(dense), rtol=0, atol=tol)
    # the pad is exact zeros: the published width's slice is the same sum
    width = spec.intermediate_size
    cut = moe.experts_dense(
        dataclasses.replace(spec, stored_intermediate=0), x, top_vals,
        top_idx, None, up[layer, :, :, :width], down[layer, :, :width])
    np.testing.assert_allclose(_f32(cut), _f32(dense), rtol=0, atol=tol)
    combine = np.asarray(moe.held_combine(spec, top_vals, top_idx))
    assert int(read) == combine.reshape(-1, spec.num_held).any(axis=0).sum()


def test_plain_walk_in_two_column_pieces():
    spec, x, router, bias, up, down = _plain_case("two-pieces", 16, 1)
    plan = moe_decode.moe_decode_plan(2048, 1280, jnp.float32, 2)
    assert (plan.pieces, plan.ip) == (2, 640)
    # (three matrices of that width would go in three pieces)
    assert moe_decode.moe_decode_plan(2048, 1280, jnp.float32).pieces == 5
    assert moe.walk_note(spec, up, 16) == \
        "plain pieces=2 of 640 (1200 of 1280 stored)"
    top_vals, top_idx = moe.route(spec, x, router, bias)
    want = moe.experts_dense(spec, x, top_vals, top_idx, None, up[1],
                             down[1])
    got, _ = moe.experts_touched(spec, x, top_vals, top_idx, None, up, down,
                                 1)
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=0,
                               atol=1e-5 * float(np.abs(_f32(want)).max()))


def test_plain_walk_reads_no_untouched_expert_and_clones_row_0():
    spec, x, router, bias, up, down = _plain_case("14.5-vregs", 3, 1, seed=9)
    x = jnp.concatenate([x, jnp.repeat(x[:1], 5, axis=0)])       # pad clones
    top_vals, top_idx = moe.route(spec, x, router, bias)
    hit = np.asarray(moe.held_combine(spec, top_vals, top_idx)).reshape(
        -1, spec.num_held).any(axis=0)
    assert 0 < hit.sum() < spec.num_held
    keep = np.zeros((up.shape[0], spec.num_held, 1, 1), bool)
    keep[2, hit] = True
    want, _ = moe.experts_touched(spec, x, top_vals, top_idx, None, up, down,
                                  2)
    got, read = moe.experts_touched(
        spec, x, top_vals, top_idx, None, jnp.where(keep, up, jnp.nan),
        jnp.where(keep, down, jnp.nan), 2)
    assert int(read) == hit.sum()
    np.testing.assert_array_equal(_f32(got), _f32(want))
    for row in range(3, 8):
        np.testing.assert_array_equal(_f32(got)[row], _f32(got)[0])


def test_declined_names_what_the_plain_walk_still_declines():
    spec = _plain_spec()
    stack = jnp.zeros((2, 16, 128, 128), jnp.bfloat16)
    assert moe_decode.declined(spec, stack) == ""
    assert moe_decode.declined(
        dataclasses.replace(spec, act="silu"), stack) == "glu plain/silu"
    # the published width itself is not whole vregs: pieces need the pad
    odd = jnp.zeros((2, 16, 2688, 1856), jnp.bfloat16)
    assert moe_decode.declined(spec, odd) == \
        "experts of 2688 x 1856 are not whole 128-lane tiles"
    assert moe_decode.moe_decode_plan(2688, 1920, jnp.bfloat16, 2) == (3, 640)
    # ... and the gated stacks' sentences are what they were
    gated = moe.MoESpec(num_experts=16, top_k=2, intermediate_size=128)
    assert moe_decode.declined(gated, stack) == ""
    assert moe_decode.declined(
        dataclasses.replace(gated, act="gelu"), stack) == "glu gated/gelu"
    assert moe_decode.declined(
        dataclasses.replace(gated, glu_style="oss_clamp"),
        stack) == "glu oss_clamp/silu"
    assert moe_decode.declined(gated, odd) == \
        "experts of 2688 x 1856 are not whole 128-lane tiles"
    assert moe_decode.moe_decode_plan(2048, 1024, jnp.bfloat16) == (1, 1024)
    assert moe_decode.moe_decode_plan(7168, 2048, jnp.bfloat16) == (8, 256)


# ---------------------------------------------------------------------------
# the state step at 8 groups
# ---------------------------------------------------------------------------

def test_state_step_kernel_at_8_groups_equals_the_xla_branch():
    """``mamba2_mixer``'s decode step with the state left in its stack (the
    kernel, interpret mode: 64 heads of (64, 128) in 8 groups, a head reads
    its group's B / C) against the same step on the layer's rows."""
    s = ssm.SSMSpec(kind="mamba2", d_inner=64 * 64, num_heads=64,
                    head_dim=64, d_state=128, n_groups=8, d_conv=4,
                    gated_norm=True, norm_eps=1e-5)
    rng = np.random.default_rng(8)
    hidden, rows, layers = 64, 4, 2

    def leaf(ps):
        if ps.init != "normal":
            return jnp.asarray(rng.uniform(0.5, 1.5, ps.shape), ps.dtype)
        return jnp.asarray(rng.normal(size=ps.shape) * 0.1, ps.dtype)
    lw = {k: leaf(v)[1] for k, v in ssm.ssm_param_specs(
        s, hidden, layers, jnp.float32).items()}
    x = jnp.asarray(rng.normal(size=(rows, 1, hidden)), jnp.float32)
    state = {
        "conv_x": jnp.asarray(rng.normal(size=(layers, rows, 4096, 3)),
                              jnp.float32),
        "conv_bc": jnp.asarray(rng.normal(size=(layers, rows, 2048, 3)),
                               jnp.float32),
        "ssm": jnp.asarray(rng.normal(size=(layers, rows, 64, 64, 128)),
                           jnp.float32)}
    assert ssm.state_kernel_declined(s, state["ssm"], rows, 1, None) == ""
    assert "groups=8" in ssm.state_kernel_note(s, state["ssm"])
    positions = jnp.asarray([[5], [0], [9], [3]], jnp.int32)
    valid = jnp.asarray([[True], [True], [False], [True]])
    kw = dict(phase="paged", positions=positions, valid=valid)
    want, st_want = ssm.mamba2_mixer(
        s, lw, x, {k: v[1] for k, v in state.items()}, **kw)
    got, st_got = ssm.mamba2_mixer(
        s, lw, x, {"conv_x": state["conv_x"][1],
                   "conv_bc": state["conv_bc"][1],
                   "ssm": ssm.StateStack(state["ssm"], 1)}, **kw)
    scale = float(np.abs(np.asarray(want)).max())
    live = [0, 1, 3]              # (nobody reads what a dead row puts out)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=1e-4 * scale)
    new = np.asarray(st_got["ssm"].stack)
    np.testing.assert_allclose(new[1], np.asarray(st_want["ssm"]), atol=1e-4)
    np.testing.assert_array_equal(new[0], np.asarray(state["ssm"][0]))
    # the dead row's state stays, the row at position 0 starts from zeros
    np.testing.assert_array_equal(new[1, 2], np.asarray(state["ssm"][1, 2]))


# ---------------------------------------------------------------------------
# the parameter tree
# ---------------------------------------------------------------------------

def _full_spec():
    import json
    with open(FULL) as f:
        cfg = json.load(f)
    return cfg, build.build_app(cfg).spec


def test_parameter_count_from_the_specs_is_the_recount():
    cfg, spec = _full_spec()
    specs = model_base.decoder_param_specs(spec)
    leaves = jax.tree.leaves(specs,
                             is_leaf=lambda x: isinstance(x, ParamSpec))
    stored = sum(int(np.prod(ps.shape)) for ps in leaves)
    pad = sum(int(np.prod(ps.shape)) // ps.shape[ps.live[0]]
              * (ps.shape[ps.live[0]] - ps.live[1])
              for ps in leaves if ps.live)
    assert pad == 23 * 16 * 2 * 2688 * (1920 - 1856) == 126_615_552
    assert stored - pad == 5_258_420_544
    assert (spec.num_layers, spec.num_ssm_layers, spec.num_attn_layers,
            spec.num_moe_layers) == (52, 23, 6, 23)
    assert spec.ssm.d_inner == 4096 != cfg["expand"] * cfg["hidden_size"]
    assert spec.moe.stored_intermediate == 1920
    assert (spec.moe.num_experts, spec.moe.num_held) == (128, 16)


def test_no_mlp_leaf_on_a_temporal_layer_and_no_second_norm():
    _, spec = _full_spec()
    specs = model_base.decoder_param_specs(spec)
    assert sorted(specs) == ["attn_layers", "embed", "final_norm", "lm_head",
                             "moe_layers", "ssm_layers"]
    assert sorted(specs["attn_layers"]) == ["input_norm", "o_proj",
                                            "qkv_proj"]
    assert sorted(specs["moe_layers"]) == [
        "expert_down", "expert_up", "input_norm", "router", "router_bias",
        "shared_down", "shared_up"]
    assert all(k == "input_norm" or k.startswith("ssm_")
               for k in specs["ssm_layers"])
    for stack, n in (("ssm_layers", 23), ("attn_layers", 6),
                     ("moe_layers", 23)):
        assert {ps.shape[0] for ps in specs[stack].values()} == {n}
        assert [k for k in specs[stack] if "norm" in k
                and not k.startswith("ssm_")] == ["input_norm"]
    assert specs["moe_layers"]["expert_up"].shape == (23, 16, 2688, 1920)
    assert specs["moe_layers"]["expert_up"].live == (3, 1856)
    assert specs["moe_layers"]["expert_down"].live == (2, 1856)
    # the pad of a random-weight stack is zeros
    up = dataclasses.replace(specs["moe_layers"]["expert_up"],
                             shape=(1, 2, 8, 1920)).initializer(
                                 jax.random.PRNGKey(0))
    assert float(jnp.abs(up[..., 1856:]).max()) == 0.0
    assert float(jnp.abs(up[..., :1856]).min()) > 0.0


def test_gated_stacks_param_trees_are_what_they_were():
    """A gated expert stack keeps its gate leaves, its published width and
    plain normal draws: the third ``glu_style`` is a field, not a fork."""
    for name in ("olmoe-1b-7b", "deepseek-v3", "lfm2-8b-a1b",
                 "qwen3-next-80b-a3b"):
        import json
        with open(os.path.join(ROOT, "benchmark", "configs",
                               name + ".json")) as f:
            spec = build.build_app(json.load(f)).spec
        specs = model_base.decoder_param_specs(spec)
        stack = specs["moe_layers"] if "moe_layers" in specs \
            else specs["layers"]
        assert {"expert_gate", "expert_up", "expert_down"} <= set(stack)
        assert stack["expert_gate"].shape[-1] == spec.moe.intermediate_size
        assert spec.moe.glu_style == "gated"
        assert spec.moe.stored_intermediate == 0
        assert all(ps.live is None for ps in stack.values())
        if spec.moe.shared_intermediate:
            assert "shared_gate" in stack


def test_the_family_refuses_what_it_has_not_walked():
    family = get_family("nemotron_h")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", **SERVE)

    def spec(**kw):
        return family.build_spec(family.config_cls(tcfg, **dict(HF, **kw)))
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        spec(hybrid_override_pattern="MEMEM*")
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        spec(hybrid_override_pattern="MEMEM*X")
    with pytest.raises(NotImplementedError, match="n_group = 2"):
        spec(n_group=2)
    with pytest.raises(NotImplementedError, match="mlp_hidden_act"):
        spec(mlp_hidden_act="silu")
    with pytest.raises(NotImplementedError, match="Mamba-2 layer"):
        spec(hybrid_override_pattern="*E*E*E*")
    with pytest.raises(NotImplementedError,
                       match="contiguous single-block stack"):
        family.build_spec(family.config_cls(
            TpuConfig(tp_degree=1, dtype="float32", batch_size=2,
                      seq_len=64), **HF))
    # granite's refusal names the family that uses the per-group norm
    granite = get_family("granitemoehybrid")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        import json
        hf = build.hf_config(json.load(f))
    with pytest.raises(NotImplementedError, match="nemotron_h"):
        granite.build_spec(granite.config_cls(
            tcfg, **dict(hf, mamba_n_groups=8)))


def test_gate64_script_loads_and_names_the_untied_head():
    spec = importlib.util.spec_from_file_location(
        "gate64", os.path.join(ROOT, "scripts", "gate64.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert module.NAMES == ("backbone.embeddings.weight", "lm_head.weight")
    assert callable(module.long_walk) and callable(module.main)
