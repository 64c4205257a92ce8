"""LFM2-8B-A1B on the paged serving path (ISSUE 61): gated short-convolution
layers whose state is a conv tail alone, carried between chunks and steps;
two leading dense layers under the recurrent walk; sigmoid routing picked by
a bias that does not weigh.

``lfm2_moe`` served through ``PagedEngineAdapter`` with default arguments, at
a toy size on the CPU in float32, in ``tests/test_qwen3_next_paged.py``'s
manner: every test holds the LOGITS of the served path, at every position a
dispatch computed, to the plain reference ``benchmark/references/lfm2_moe.py``
(token-by-token convolution; held to ``transformers``' ``Lfm2ForCausalLM`` by
``benchmark/tests/test_reference_lfm2_moe.py``).

  (a) a prompt in ONE window, then decode through the pool and the slots;
  (b) prompts walked in chunks of unequal width - one of 1 token, one
      shorter than K - 1, one padded, one that fills its bucket - each
      continuing the tail the chunk before it left;
  (c) a dead row leaves its slot; a released slot starts from zeros; row i
      of a dispatch on slot j != i;
  (d) the eight controls each fail (b)'s comparison;

and the edges: the parameter tree of leading dense layers under a mixer, the
scopes and the exact counts of a decode step, the engagement record, the
family's refusals. No share test is owed: every expert is held.
"""

import collections
import dataclasses
import importlib.util
import os
import re
import sys
from functools import partial

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
from neuronx_distributed_inference_tpu.serving import \
    PagedEngineAdapter  # noqa: E402
from neuronx_distributed_inference_tpu.serving.warmup import \
    precompile  # noqa: E402
from test_recurrent_paged import LogitTap, _decode  # noqa: E402

#: the gate's twin at a toy size (both dense layers and one whole period):
#: every key of the catalog row's config
HF = dict(
    model_type="lfm2_moe", vocab_size=128, hidden_size=32,
    intermediate_size=80, num_hidden_layers=6, num_attention_heads=4,
    num_key_value_heads=2, conv_L_cache=3, conv_bias=False,
    layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv"],
    max_position_embeddings=512, moe_intermediate_size=16, norm_eps=1e-5,
    norm_topk_prob=True, num_dense_layers=2, num_experts=8,
    num_experts_per_tok=2, rope_theta=1000000, routed_scaling_factor=1,
    use_expert_bias=True)
#: a wider convolution: a chunk of 2 tokens is shorter than its K - 1 = 3
HF_K4 = dict(HF, conv_L_cache=4)
BATCH = 4
SERVE = dict(batch_size=BATCH, seq_len=128, pa_block_size=8, pa_num_blocks=64,
             context_encoding_buckets=[8, 32], enable_bucketing=True,
             is_block_kv_layout=True, is_prefix_caching=False)
RNG = np.random.default_rng(61)
#: 69 = 32 + 32 + 5 (padded to 8); 65 = 32 + 32 + 1; 66 = 32 + 32 + 2;
#: 40 = 32 + 8 (the last chunk fills its bucket); 21: one window
P69, P65, P66, P40, Q45, R21, S12 = (
    RNG.integers(1, 128, size=n).tolist() for n in (69, 65, 66, 40, 45, 21,
                                                    12))
#: float32 on both sides: the served logits (|logit| up to ~1) agree with
#: the reference's to a few 1e-7; the controls move them by 8e-4 (a bias
#: that weighs: the renormalisation takes most of it back) to 1e-1
ATOL = 2e-5


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("lfm2_moe")


@pytest.fixture(scope="module")
def gate_weights(ref):
    return weights.make_weights(ref.weight_shapes(HF), seed=2**31 + 61)


def _app(ref, w, hf=HF, family="lfm2_moe", **serve):
    family = get_family(family)
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


def _error(tap, ref, w, sid, prompt, stream, hf=HF, control=None):
    fed = prompt + stream[:-1]
    return float(np.abs(tap.logits(sid, len(fed))
                        - _want(ref, w, fed, hf, control)).max())


def _check(tap, ref, w, sid, prompt, stream, hf=HF):
    assert _error(tap, ref, w, sid, prompt, stream, hf) < ATOL
    want = _want(ref, w, prompt + stream[:-1], hf)
    assert stream == want[len(prompt) - 1:].argmax(-1).tolist()


def _serve(app, prompt, decode=6, sid=7):
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {sid: [ad.add_requests([sid], [prompt])[sid]]}
    _decode(ad, [sid], stream, decode)
    return ad, tap, stream[sid]


def _tails(app, ad, sid):
    return np.asarray(app.cache["conv_x"][:, ad._state_slot[sid]])


# ---------------------------------------------------------------------------
# (a), (b): one window, and chunks of unequal width
# ---------------------------------------------------------------------------

def test_a_one_window_then_decode_through_pool_and_slots(ref, gate_weights):
    app = _app(ref, gate_weights)
    ad, tap, stream = _serve(app, R21)
    assert tap.shapes == [(1, 32)] + [(BATCH, 1)] * 6
    _check(tap, ref, gate_weights, 7, R21, stream)
    # the state a row carries is the conv tail alone: no matrix state
    assert sorted(app.cache) == ["conv_x", "k", "v"]
    assert app.cache["conv_x"].shape == (5, BATCH, 2, 32)
    np.testing.assert_allclose(
        _tails(app, ad, 7), np.asarray(ref.final_tails(
            HF, gate_weights, jnp.asarray([R21 + stream[:-1]])))[:, 0],
        atol=1e-6)


@pytest.mark.parametrize("prompt, hf, shapes", [
    (P69, HF, [(1, 32), (1, 32), (1, 8)]),         # a padded last chunk
    (P65, HF, [(1, 32), (1, 32), (1, 8)]),         # ... of ONE token (< K-1)
    (P66, HF_K4, [(1, 32), (1, 32), (1, 8)]),      # ... of 2 < K-1 = 3
    (P40, HF, [(1, 32), (1, 8)]),                  # ... that fills its bucket
], ids=["padded", "one-token", "shorter-than-the-tail", "full-bucket"])
def test_b_chunks_of_unequal_width_continue_the_carried_tail(
        ref, gate_weights, prompt, hf, shapes):
    w = gate_weights if hf is HF else weights.make_weights(
        ref.weight_shapes(hf), seed=2**31 + 62)
    app = _app(ref, w, hf=hf)
    ad, tap, stream = _serve(app, prompt)
    assert tap.shapes == shapes + [(BATCH, 1)] * 6
    _check(tap, ref, w, 7, prompt, stream, hf)
    # the slot holds the products of the LAST REAL tokens, not the bucket's
    np.testing.assert_allclose(
        _tails(app, ad, 7), np.asarray(ref.final_tails(
            hf, w, jnp.asarray([prompt + stream[:-1]])))[:, 0], atol=1e-6)


# ---------------------------------------------------------------------------
# (c) dead rows, released slots, rows on other slots
# ---------------------------------------------------------------------------

def test_c_a_dead_row_of_a_pack_leaves_its_slot(ref, gate_weights):
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {1: [ad.add_requests([1], [R21])[1]]}
    _decode(ad, [1], stream, 2)
    before = _tails(app, ad, 1)
    assert np.abs(before).max() > 0
    n0 = len(tap.shapes)
    # two prompts packed at the full batch: sequence 1's slot is a dead row
    first = ad.add_requests([2, 3], [Q45, S12])
    assert tap.shapes[n0:] == [(BATCH, 32), (1, 32)]
    np.testing.assert_array_equal(_tails(app, ad, 1), before)
    stream.update({2: [first[2]], 3: [first[3]]})
    _decode(ad, None, stream, 4)
    for sid, prompt in ((1, R21), (2, Q45), (3, S12)):
        _check(tap, ref, gate_weights, sid, prompt, stream[sid])


def test_c_a_released_slot_starts_from_zeros_and_a_row_is_not_its_slot(
        ref, gate_weights):
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {}
    for sid, prompt in ((1, R21), (2, Q45)):
        stream[sid] = [ad.add_requests([sid], [prompt])[sid]]
    _decode(ad, None, stream, 2)
    slot1, slot2 = ad._state_slot[1], ad._state_slot[2]
    assert slot1 != slot2
    # the one-row program runs sequence 2's chunks as ROW 0 on its slot
    assert slot2 != 0 or slot1 != 0
    ad.release([1])
    stale = np.asarray(app.cache["conv_x"][:, slot1])
    assert np.abs(stale).max() > 0          # nobody wiped it on release
    stream[3] = [ad.add_requests([3], [P69])[3]]
    assert ad._state_slot[3] == slot1       # ... and a new prompt takes it
    _decode(ad, None, stream, 3)
    for sid, prompt in ((2, Q45), (3, P69)):
        _check(tap, ref, gate_weights, sid, prompt, stream[sid])
    # rows on slots that are not their row index: a one-row chunk (row 0)
    # of whichever of the two sequences sits in slot 1
    assert {slot1, slot2} == {0, 1}


# ---------------------------------------------------------------------------
# (d) the controls
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gate61():
    """``scripts/gate61.py``, what PR 61 ran on the chip: it stays runnable
    (ROADMAP C13) and owns the two faults of the carry."""
    spec = importlib.util.spec_from_file_location(
        "gate61", os.path.join(ROOT, "scripts", "gate61.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("fault", ["zero_tail", "padded_tail"])
def test_d_a_broken_carry_fails_the_comparison(ref, gate_weights,
                                               monkeypatch, gate61, fault):
    monkeypatch.setattr(ssm, *gate61.carry_fault(fault))
    app = _app(ref, gate_weights)
    _, tap, stream = _serve(app, P69, decode=8)
    assert _error(tap, ref, gate_weights, 7, P69, stream) > 10 * ATOL


def test_d_the_reference_names_six_faults_and_the_walk_two(ref):
    assert ref.CONTROLS == (
        "bias_weighs", "bias_dropped", "renorm_dropped", "b_c_exchanged",
        "qk_norm_after_rope", "dense_as_expert")


@pytest.mark.parametrize("control", [
    "bias_weighs", "bias_dropped", "renorm_dropped", "b_c_exchanged",
    "qk_norm_after_rope", "dense_as_expert"])
def test_d_a_faulty_reference_fails_the_comparison(ref, gate_weights,
                                                   control):
    app = _app(ref, gate_weights)
    _, tap, stream = _serve(app, P69, decode=8)
    assert _error(tap, ref, gate_weights, 7, P69, stream) < ATOL
    assert _error(tap, ref, gate_weights, 7, P69, stream,
                  control=control) > 10 * ATOL


def test_d_the_renormalisation_carries_its_epsilon(ref):
    """``w / (sum w + 1e-6)``: the family gives the spec the epsilon, the
    router divides by it, and at weights as small as it the two forms part
    (at the sigmoids a router gives, ~0.5, they agree to 5e-7)."""
    family = get_family("lfm2_moe")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", **SERVE)
    spec = family.build_spec(family.config_cls(tcfg, **HF)).moe
    assert spec.topk_norm_eps == ref.TOPK_NORM_EPS == 1e-6
    h = jnp.asarray(RNG.normal(size=(1, 5, 32)), jnp.float32)
    router = jnp.asarray(RNG.normal(size=(32, 8)), jnp.float32)
    bias = jnp.asarray(RNG.uniform(-0.1, 0.1, size=(8,)), jnp.float32)
    vals, idx = moe.route(spec, h, router, bias)
    scores = jax.nn.sigmoid(h[0] @ router)
    want, picked, _ = ref.route(HF, scores, bias)
    np.testing.assert_array_equal(np.asarray(idx[0]), np.asarray(picked))
    np.testing.assert_allclose(np.asarray(vals[0]), np.asarray(want),
                               rtol=1e-6)
    # every score 1e-6: the picked two weigh 1e-6 / (2e-6 + 1e-6) each
    flat = jnp.full((32, 8), 1 / 32, jnp.float32)
    tiny = jnp.full((1, 1, 32), float(np.log(1e-6)), jnp.float32)
    vals, _ = moe.route(spec, tiny, flat, None)
    plain, _ = moe.route(dataclasses.replace(spec, topk_norm_eps=0.0), tiny,
                         flat, None)
    assert abs(float(vals.sum()) - 2 / 3) < 1e-3
    assert abs(float(plain.sum()) - 1) < 1e-5


# ---------------------------------------------------------------------------
# the parameter tree, the scopes and counts, the record, refusals
# ---------------------------------------------------------------------------

def _config(serve=None, family="lfm2_moe", **hf):
    family = get_family(family)
    tcfg = TpuConfig(dtype="float32", **dict(SERVE, **(serve or {})))
    return family, family.config_cls(tcfg, **dict(HF, **hf))


def test_the_spec_and_the_tree_of_dense_layers_under_a_mixer():
    family, config = _config()
    spec = family.build_spec(config)
    assert spec.resolved_ssm_pattern == (True, True, False, True, True, True)
    assert (spec.first_dense, spec.num_moe_layers, spec.num_attn_layers,
            spec.num_ssm_layers, spec.qk_norm, spec.tie_word_embeddings,
            spec.rms_eps, spec.intermediate_size) == (2, 4, 1, 5, True, True,
                                                      1e-5, 80)
    s, m = spec.ssm, spec.moe
    assert (s.kind, s.d_inner, s.d_conv, s.conv_bias) == ("shortconv", 32, 3,
                                                          False)
    assert (m.num_experts, m.num_held, m.top_k, m.router_act,
            m.has_router_bias, m.router_bias_mode, m.normalize_topk,
            m.routed_scaling, m.shared_intermediate, m.holds_share) == \
        (8, 8, 2, "sigmoid", True, "select", True, 1.0, 0, False)
    params = model_base.decoder_param_specs(spec)
    assert sorted(params) == ["attn_layers", "embed", "final_norm", "layers",
                              "moe_layers", "ssm_layers"]
    assert {k: v.shape for k, v in params["layers"].items()} == {
        "input_norm": (2, 32), "post_norm": (2, 32), "gate_proj": (2, 32, 80),
        "up_proj": (2, 32, 80), "down_proj": (2, 80, 32)}
    assert {k: v.shape for k, v in params["moe_layers"].items()} == {
        "input_norm": (4, 32), "post_norm": (4, 32), "router": (4, 32, 8),
        "router_bias": (4, 8), "expert_gate": (4, 8, 32, 16),
        "expert_up": (4, 8, 32, 16), "expert_down": (4, 8, 16, 32)}
    assert params["moe_layers"]["router"].dtype == jnp.float32
    assert params["attn_layers"]["qkv_proj"].shape == (1, 32, 32 + 2 * 16)
    assert params["ssm_layers"]["sc_conv"].shape == (5, 32, 3)
    assert [model_base.mlp_stack(spec, i) for i in (0, 1, 2, 5)] == [
        ("layers", 0), ("layers", 1), ("moe_layers", 0), ("moe_layers", 3)]
    assert ssm.ssm_state_shapes(s, 5, BATCH, jnp.float32) == {
        "conv_x": ((5, BATCH, 2, 32), jnp.float32)}
    # the dense sibling keeps ONE stack
    dense = get_family("lfm2").build_spec(_config(family="lfm2")[1])
    assert dense.moe is None and model_base.mlp_stack(dense, 5) == (
        "layers", 5)
    assert "moe_layers" not in model_base.decoder_param_specs(dense)


def test_the_scopes_split_the_step_by_kind_of_layer(ref, gate_weights):
    """Conv layers under ``mixer``, the leading dense layers' MLP under
    ``mlp``, the expert layers under ``moe``, attention under ``attn``:
    siblings in the lowered step, so ``trace_scope_ms`` splits it."""
    app = _app(ref, gate_weights)
    b, i32 = BATCH, np.int32
    args = (np.zeros((b, 1), i32), np.zeros((b, 1), i32),
            np.full((b, 1), -1, i32), np.zeros((b, app.max_blocks), i32),
            np.zeros((b,), i32), None, jax.random.PRNGKey(0))
    with app._mesh_ctx():
        text = jax.jit(partial(model_base.paged_forward_step, app.spec,
                               app.tpu_config)).lower(
            app.params, app.cache, *args).as_text(debug_info=True)
    scoped = collections.defaultdict(set)
    for path in set(re.findall(r'loc\("([^"]*)"', text)):
        parts = path.split("/")
        inside = [p for p in parts[:-1]
                  if p in ("attn", "mixer", "mlp", "moe")]
        if inside:
            assert len(inside) == 1, path
            scoped[inside[0]].add("/".join(parts[1:]))
    assert any("top_k" in op for op in scoped["moe"])
    assert not any("top_k" in op for op in scoped["mlp"] | scoped["mixer"])
    assert "mlp/dot_general" in scoped["mlp"]
    assert "mixer/dot_general" in scoped["mixer"]
    assert any("dot_general" in op for op in scoped["attn"])


def test_a_step_counts_the_expert_layers_and_not_the_dense_ones(
        ref, gate_weights):
    app = _app(ref, gate_weights)
    ad, tap, stream = _serve(app, P69, decode=5)
    st = ad.host_stats
    slots = 8 * 4 * 5          # experts x EXPERT layers (4 of 6) x steps
    assert st["moe_expert_slots"] == slots
    assert st["moe_experts_read"] + st["moe_experts_skipped"] == slots
    # one live row: top-2 of each of the 4 expert layers a step
    assert st["moe_assignments"] == 2 * 4 * 5
    assert 0 < st["moe_experts_touched"] <= st["moe_assignments"]
    assert st["state_slot_allocs"] == 1 == st["state_slots_live"]


def test_warmup_plan_and_the_engagement_record(ref, gate_weights):
    app = _app(ref, gate_weights)
    report = precompile(app, widths=[1, 8, 32])
    pairs = [(g["kind"], g["bucket"]) for g in report["graphs"]]
    per_tw = [("paged", 1), ("paged", 8), ("paged_pack", 8), ("paged", 32),
              ("paged_pack", 32)]
    # ... and, last, the program that makes a carried step's ids
    assert pairs == per_tw * len(app._bt_buckets) + [("carry_ids", BATCH)]
    notes = {k["site"]: k for k in report["kernels"]}
    # a slot is 5 conv layers x 32 channels x 2 products x 4 B
    assert notes["recurrent_state"] == {
        "site": "recurrent_state", "path": "xla",
        "reason": f"kind=shortconv slot_bytes={5 * 32 * 2 * 4} chunk=128: "
                  "no matrix state"}
    ad = PagedEngineAdapter(app)
    ad.add_requests([0], [P69])
    ad.add_requests([1, 2], [Q45, S12])
    for _ in range(3):
        ad.step()
    warm = app.warmup_state()
    assert warm["steady_state"] and not warm["incidents"]


@pytest.mark.parametrize("serve, hf, error, sentence", [
    (dict(tp_degree=2), {}, NotImplementedError, "served on one chip"),
    ({}, dict(block_auto_adjust_ff_dim=True), NotImplementedError,
     "block_auto_adjust_ff_dim"),
    ({}, dict(use_expert_bias=False), NotImplementedError,
     "use_expert_bias false"),
    ({}, dict(num_dense_layers=6), ValueError, "leaves no expert layer"),
    ({}, dict(layer_types=["conv"] * 5), ValueError, "layer_types must name"),
    (dict(is_prefix_caching=True), {}, NotImplementedError,
     "prefix caching (" + model_base.RECURRENT_UNSUPPORTED["prefix caching"]),
])
def test_the_family_refuses_with_a_sentence(serve, hf, error, sentence):
    family, config = _config(serve, **hf)
    with pytest.raises(error) as ei:
        family.build_spec(config)
    assert sentence in str(ei.value)


def test_gate61_runs_its_walk_at_a_toy_size(ref, monkeypatch, gate61):
    """Its chunked walk at this file's toy widths holds every position, and a
    walk whose chunks start from a zero tail does not."""
    cfg = dict(
        HF, family="lfm2_moe", dtype="float32", tp=1, chips=1,
        serve=dict(SERVE, seq_len=256, pa_num_blocks=256), adapter={},
        gate=dict(config={}, atol=ATOL, rtol=0.0, min_positions_held=1.0,
                  median_ratio_max=1.0, worst_ratio_max=1.0))
    out = gate61.long_walk(cfg, seed=2**31 + 61, tokens=70, rows=2,
                           new_tokens=4, second=21)
    assert out["passed"] and out["slot_reused"], out
    assert out["blocked_vs_plain_reference"] < 1e-5
    assert out["all"]["positions"] == 2 * 74 + 25
    assert (1, 32) in map(tuple, out["program_shapes"])
    monkeypatch.setattr(ssm, *gate61.carry_fault("zero_tail"))
    broken = gate61.long_walk(cfg, seed=2**31 + 61, tokens=70, rows=2,
                              new_tokens=4, second=21)
    assert not broken["passed"]
    assert broken["first_chunk"]["worst_ratio"] <= 1 < \
        broken["all"]["worst_ratio"]
