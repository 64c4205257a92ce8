"""Command A+ (``cohere2_moe``) on the paged serving path (ISSUE 56): a
PARALLEL block whose attention (three rotary window layers to one NoPE full
layer, a KV pool by layer kind), routed experts (sigmoid top-k renormalised,
one chip's share) and AVERAGED shared experts read one LayerNorm.

``cohere2_moe`` served through ``PagedEngineAdapter`` at a toy size on the
CPU in float32, in ``tests/test_smallthinker_paged.py``'s manner: the LOGITS
of the served path, at every position a dispatch computed, are held to the
plain reference ``benchmark/references/cohere2_moe.py`` (no cache, no kernel;
held to a second writing of the equations by
``benchmark/tests/test_reference_cohere2_moe.py``).

  (a) a prompt walked in five chunks through the one-row program (the ring
      wraps), then decode through both pools on the kernels (interpret
      mode); the scopes and counters of the parallel block;
  (b) a released and re-used batch slot beside a decoding row;
  (c) every control of the benchmark's gate fails (a)'s comparison - the
      half-split rotary, a rotated full layer and an unaveraged shared sum
      among them;
  (d) the share: the sum over all shares of ``r``, with ``a`` and ``c``
      counted once, is the uncut reference's layer; four separately computed
      shared experts averaged are the fused branch; the sigmoid top-k is
      renormalised over held and absent picks alike;
  (e) the loader under the assumed tensor names, and the refusals by name;
  (f) the harness's gate and the builder's chip check at a toy size.
"""

import collections
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
from neuronx_distributed_inference_tpu.modules import moe  # noqa: E402
from neuronx_distributed_inference_tpu.serving import \
    PagedEngineAdapter  # noqa: E402
from test_recurrent_paged import LogitTap, _decode  # noqa: E402

PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
#: one period at a toy size: every key of the published config.json the
#: family or the reference reads. Heads and widths of 128 lanes, so the
#: paged decode, paged prefill and expert-walk kernels engage in interpret
#: mode; a window of 16 tokens and pages of 8, so a ring is 7 pages = 56
#: tokens; 4 held experts of a router over 16 (a share), 2 shared experts
HF = dict(
    model_type="cohere2_moe", vocab_size=128, hidden_size=128, head_dim=128,
    num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=4,
    intermediate_size=128, num_experts=4, router_num_experts=16,
    first_expert=4, num_experts_per_tok=3, num_shared_experts=2,
    norm_topk_prob=True, expert_selection_fn="sigmoid",
    shared_expert_combination_strategy="average", use_parallel_block=True,
    use_gated_activation=True, use_qk_norm=False, first_k_dense_replace=0,
    hidden_act="silu", attention_bias=False, layer_norm_eps=1e-5,
    rms_norm_eps=None, rope_theta=50000, rotary_pct=1,
    position_embedding_type="rope_gptj", logit_scale=0.5,
    max_position_embeddings=512, sliding_window=16, layer_types=PERIOD,
    tie_word_embeddings=True)
#: the same model with every expert held
WHOLE = dict(HF, num_experts=16, router_num_experts=None, first_expert=0)
BATCH = 4
SERVE = dict(batch_size=BATCH, seq_len=256, pa_block_size=8,
             pa_num_blocks=128, context_encoding_buckets=[8, 32],
             enable_bucketing=True, is_block_kv_layout=True,
             is_prefix_caching=False)
RING = 7
RNG = np.random.default_rng(56)
#: 150 = 4 x 32 + 22: five chunks, the last padded to the 32 bucket; with 20
#: decode steps 170 tokens go through a ring of 56: it wraps three times
P150, Q45, S12 = (RNG.integers(1, 128, size=n).tolist()
                  for n in (150, 45, 12))
#: float32 on both sides: served and reference logits (|logit| up to ~0.5)
#: agree to a few 1e-7; the weakest control moves them by 2e-3
ATOL = 1e-4


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("cohere2_moe")


@pytest.fixture(scope="module")
def gate_weights(ref):
    return weights.make_weights(ref.weight_shapes(HF), seed=2**31 + 56)


def _app(ref, w, hf=HF, **serve):
    family = get_family("cohere2_moe")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", output_logits=True,
                     **dict(SERVE, **serve))
    app = PagedCausalLMApplication(None, family.config_cls(tcfg, **hf),
                                   family)
    view = weights.HfView(ref.weight_shapes(hf), w,
                          dtype=np.dtype("float32"))
    app._put_params(family.convert_hf_state_dict(view, app.spec))
    return app.init_cache()


def _want(ref, w, tokens, control=None, hf=HF):
    return np.asarray(ref.forward(hf, w, jnp.asarray([tokens]),
                                  control=control))[0]


def _check(tap, ref, w, sid, prompt, stream):
    fed = prompt + stream[:-1]
    want = _want(ref, w, fed)
    np.testing.assert_allclose(tap.logits(sid, len(fed)), want, atol=ATOL)
    assert stream == want[len(prompt) - 1:].argmax(-1).tolist()


def _kernels(app):
    notes = collections.defaultdict(set)
    for k in app.warmup_state()["kernels"]:
        notes[k["site"]].add((k["path"], k["reason"]))
    return notes


@pytest.fixture(scope="module")
def served_p150(ref, gate_weights):
    """P150 walked in five chunks, then 20 decode steps: the tap and the
    stream, shared by (a) and every control."""
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {7: [ad.add_requests([7], [P150])[7]]}
    _decode(ad, [7], stream, 20)
    return app, ad, tap, stream[7]


# ---------------------------------------------------------------------------
# the spec the family builds
# ---------------------------------------------------------------------------

def test_the_spec_is_a_parallel_block_over_a_pool_by_layer_kind(
        ref, gate_weights):
    app = _app(ref, gate_weights)
    spec = app.spec
    assert spec.block_style == "parallel_shared"
    assert spec.norm_type == "layernorm" and not spec.norm_bias
    assert spec.rope_interleaved and spec.nope_global and spec.window_pool
    assert spec.layer_pattern == (True, True, True, False)
    assert spec.sliding_window == 16 and spec.tie_word_embeddings
    assert spec.logits_divide == 2.0 and abs(spec.rms_eps - 1e-5) < 1e-12
    assert abs(spec.rope.rope_theta - 50000) < 1e-6
    m = spec.moe
    assert (m.num_experts, m.held_experts, m.first_expert, m.top_k) == \
        (16, 4, 4, 3)
    assert m.router_act == "sigmoid" and m.normalize_topk
    assert (m.shared_intermediate, m.shared_mean_of) == (256, 2)
    assert app.cache["k"].shape == (1, 129, 8, 1, 256)
    assert app.cache["k_w"].shape == (3, BATCH * RING, 8, 1, 256)
    # logit_scale 1 (the published value) divides nothing
    one = get_family("cohere2_moe")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", **SERVE)
    assert one.build_spec(one.config_cls(
        tcfg, **dict(HF, logit_scale=1))).logits_divide is None
    # off the paged layout there is no pool to split
    contiguous = one.build_spec(one.config_cls(
        TpuConfig(tp_degree=1, dtype="float32", batch_size=2, seq_len=64),
        **HF))
    assert not contiguous.window_pool


# ---------------------------------------------------------------------------
# (a) chunks behind what the earlier ones cached, then decode; the ring wraps
# ---------------------------------------------------------------------------

def test_a_five_chunks_then_decode_on_the_kernels(ref, gate_weights,
                                                  served_p150):
    app, ad, tap, stream = served_p150
    assert tap.shapes == [(1, 32)] * 5 + [(BATCH, 1)] * 20
    assert (len(P150) + 20) // (RING * 8) == 3          # the ring's wraps
    _check(tap, ref, gate_weights, 7, P150, stream)
    notes = _kernels(app)
    assert {(p, r.split(" stored ")[1]) for p, r in notes["paged_decode"]} \
        == {("pallas-interpret", "prefetch=across-rows window=0"),
            ("pallas-interpret",
             f"prefetch=across-rows window=16 ring={RING}")}
    assert notes["paged_prefill"] == {
        ("pallas-interpret", "rows=1 width=32 pages=16 heads=4 fold=2 "
         "tile=4x32 window=0"),
        ("pallas-interpret", "rows=1 width=32 pages=16 heads=4 fold=2 "
         f"tile=4x32 window=16 ring={RING}")}
    # the walk over the touched experts serves the step and the chunk
    assert {p for p, _ in notes["moe_decode"]} == {"pallas-interpret"}
    # the engagement record of the share names the averaged shared experts
    assert notes["moe_share"] == {
        ("xla", "held=4 of 16 from 4 top_k=3 shared=2 x 128 mean")}
    (_, pool), = notes["kv_window_pool"]
    assert "layers global=1 window=3" in pool and "ring_pages=7" in pool
    # the exact counters, counted under a parallel block as everywhere
    stats = ad.host_stats
    assert stats["prefill_dispatches"] == 5 == \
        stats["prefill_dispatches_paged_attn_kernel"] == \
        stats["prefill_dispatches_moe_walk"]
    pages = [-(-n // 8) for n in range(150, 170)]
    assert stats["kv_window_pages_held"] == 3 * 20 * RING
    assert stats["kv_window_pages_unwindowed"] == 3 * sum(pages)
    assert (stats["kv_tokens_in_window"], stats["kv_tokens_running"]) == \
        (16, 169)
    # 20 fetched steps x 4 layers x 4 held experts; one live row's 3 picks
    # a layer, of which those that fell to experts 4..7 are this chip's
    assert stats["moe_expert_slots"] == 20 * 4 * 4
    assert stats["moe_assignments"] == 20 * 4 * 3
    assert 0 < stats["moe_assignments_held"] < stats["moe_assignments"]
    assert stats["moe_experts_touched"] == stats["moe_assignments_held"]
    assert stats["moe_experts_read"] >= stats["moe_experts_touched"]
    assert stats["state_slot_allocs"] == 1 == stats["state_slots_live"]


def test_a_three_streams_open_three_sibling_scopes(ref, gate_weights):
    """attn, moe (router, walk, combine) and shared (the averaged shared
    experts) are siblings in the lowered step: no operation of the shared
    MLP is filed under moe, none of the walk under shared."""
    app = _app(ref, gate_weights)
    b, i32 = BATCH, np.int32
    args = (np.zeros((b, 1), i32), np.zeros((b, 1), i32),
            np.full((b, 1), -1, i32), np.zeros((b, app.max_blocks), i32),
            np.zeros((b,), i32), None, jax.random.PRNGKey(0))
    from functools import partial
    with app._mesh_ctx():
        text = jax.jit(partial(model_base.paged_forward_step, app.spec,
                               app.tpu_config)).lower(
            app.params, app.cache, *args).as_text(debug_info=True)
    import re
    scoped = collections.defaultdict(set)
    for path in set(re.findall(r'loc\("([^"]*)"', text)):
        parts = path.split("/")
        inside = [p for p in parts[:-1] if p in ("attn", "moe", "shared")]
        if inside:
            # siblings: an operation lies under ONE of the three
            assert len(inside) == 1, path
            scoped[inside[0]].add("/".join(parts[1:]))
    assert {"dot_general", "jit(silu)", "div"} <= scoped["shared"]
    assert not any("top_k" in op or "moe_decode" in op
                   for op in scoped["shared"])
    assert any("top_k" in op for op in scoped["moe"])
    assert "moe_decode_experts/pallas_call" in scoped["moe"]
    assert "div" in scoped["moe"]              # the renormalisation
    assert any("paged_decode_attention" in op for op in scoped["attn"])
    assert not any("moe_decode" in op for op in scoped["attn"])


def test_a_sequential_family_keeps_its_shared_expert_under_moe(monkeypatch):
    """The scope ``shared`` is a parallel block's: ``_mlp_block`` of a
    sequential spec runs the shared branch inside ``moe_block``."""
    calls = []
    block, shared = moe.moe_block, moe.shared_experts
    monkeypatch.setattr(model_base, "moe_block",
                        lambda *a, **kw: calls.append(("block", kw["shared"]))
                        or block(*a, **kw))
    monkeypatch.setattr(moe, "shared_experts",
                        lambda *a: calls.append(("shared",)) or shared(*a))
    spec_moe = moe.MoESpec(num_experts=4, top_k=2, intermediate_size=8,
                           shared_intermediate=8)
    rng = np.random.default_rng(0)

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    lw = dict(router=arr(8, 4), expert_gate=arr(4, 8, 8),
              expert_up=arr(4, 8, 8), expert_down=arr(4, 8, 8),
              shared_gate=arr(8, 8), shared_up=arr(8, 8),
              shared_down=arr(8, 8))
    x = arr(1, 3, 8)
    Spec = collections.namedtuple("Spec", "moe block_style")
    seq = model_base._mlp_block(Spec(spec_moe, "sequential"), x, lw, "moe",
                                None)
    assert calls == [("block", True), ("shared",)]
    calls.clear()
    par = model_base._mlp_block(Spec(spec_moe, "parallel_shared"), x, lw,
                                "moe", None)
    assert calls == [("block", False), ("shared",)]
    np.testing.assert_allclose(seq, par, atol=1e-6)


# ---------------------------------------------------------------------------
# (b) a re-used slot beside a decoding row
# ---------------------------------------------------------------------------

def test_b_rows_admitted_and_released_and_a_slot_reused(ref, gate_weights):
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {1: [ad.add_requests([1], [Q45])[1]]}
    _decode(ad, [1], stream, 2)
    first = ad.add_requests([2, 3], [P150[:40], S12])
    stream.update({2: [first[2]], 3: [first[3]]})
    _decode(ad, None, stream, 3)
    slot = ad._state_slot[3]
    ad.release([3])
    _check(tap, ref, gate_weights, 3, S12, stream[3])
    # the freed slot's rings still hold sequence 3's keys: the next row
    # takes the slot and must see none of them
    stream[4] = [ad.add_requests([4], [P150])[4]]
    assert ad._state_slot[4] == slot
    _decode(ad, None, stream, 3)
    for sid, prompt in ((1, Q45), (2, P150[:40]), (4, P150)):
        _check(tap, ref, gate_weights, sid, prompt, stream[sid])
    assert ad.host_stats["state_slots_live"] == 3


# ---------------------------------------------------------------------------
# (c) the controls of the benchmark's gate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("control", [
    "no_window", "window_plus_one", "rope_on_full", "rope_halves",
    "shared_sum", "no_shared", "not_renormalised", "softmax", "sequential"])
def test_c_a_control_fails_the_comparison(ref, gate_weights, served_p150,
                                          control):
    assert control in ref.CONTROLS
    _, _, tap, stream = served_p150
    fed = P150 + stream[:-1]
    got = tap.logits(7, len(fed))
    assert np.abs(got - _want(ref, gate_weights, fed)).max() < ATOL
    assert np.abs(got - _want(ref, gate_weights, fed,
                              control=control)).max() > 10 * ATOL


def test_c_the_program_rotates_interleaved_pairs_itself(ref, gate_weights,
                                                        monkeypatch):
    """On seeded weights no permutation at load can hide a convention: the
    family with ``rope_interleaved`` off serves the HALF-SPLIT reference
    (the control) and fails the sound one."""
    family = get_family("cohere2_moe")
    build_spec = family.build_spec.__func__
    monkeypatch.setattr(family, "build_spec", classmethod(
        lambda cls, config, tp_degree=None: dataclasses.replace(
            build_spec(cls, config, tp_degree), rope_interleaved=False)))
    app = _app(ref, gate_weights)
    ad = PagedEngineAdapter(app)
    tap = LogitTap(app)
    stream = {7: [ad.add_requests([7], [Q45])[7]]}
    _decode(ad, [7], stream, 4)
    fed = Q45 + stream[7][:-1]
    got = tap.logits(7, len(fed))
    assert np.abs(got - _want(ref, gate_weights, fed,
                              control="rope_halves")).max() < ATOL
    assert np.abs(got - _want(ref, gate_weights, fed)).max() > 10 * ATOL


def test_c_a_bf16_router_picks_other_experts(ref, gate_weights):
    """``router_bf16`` (the router's input and logits rounded to bfloat16):
    at a toy size it moves logits only where it flips a pick, so it is held
    by the picks themselves - the program's routing (float32 from the
    step's activations) is the reference's exactly, the control's is not."""
    rng = np.random.default_rng(3)
    n = jnp.asarray(rng.normal(size=(1, 4096, 128)), jnp.float32)
    _, idx, _ = ref.routing(HF, gate_weights, 0, n)
    _, idx16, _ = ref.routing(HF, gate_weights, 0, n, control="router_bf16")
    assert (np.sort(idx, -1) != np.sort(idx16, -1)).any()
    family = get_family("cohere2_moe")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", **SERVE)
    spec = family.build_spec(family.config_cls(tcfg, **HF))
    router = jnp.asarray(
        gate_weights["model.layers.{i}.mlp.gate.weight"][0], jnp.float32).T
    _, top_idx = moe.route(spec.moe, n, router)
    np.testing.assert_array_equal(np.sort(top_idx, -1), np.sort(idx, -1))


# ---------------------------------------------------------------------------
# (d) the share, the averaged shared experts, the routing
# ---------------------------------------------------------------------------

def _layer_w(ref, w, hf, layer=1):
    family = get_family("cohere2_moe")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", **SERVE)
    spec = family.build_spec(family.config_cls(tcfg, **hf))
    tree = family.convert_hf_state_dict(
        weights.HfView(ref.weight_shapes(hf), w, dtype=np.dtype("float32")),
        spec)
    return spec, jax.tree.map(lambda a: jnp.asarray(a)[layer],
                              tree["layers"])


def test_d_the_shares_add_up_to_the_uncut_layer(ref):
    """The guide's test of a share: the parts ``r`` that all four shares of
    4 experts give, with attention ``a`` and the shared experts ``c``, every
    chip's alike, counted ONCE, add up to what the uncut reference gives for
    the whole layer."""
    w = weights.make_weights(ref.weight_shapes(WHOLE), seed=2**31 + 57)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 9, 128)), jnp.float32)
    want, _ = ref.layer(WHOLE, w, 1, x)
    n = ref.layer_norm(x, w["model.layers.{i}.input_layernorm.weight"][1],
                       1e-5)
    a = ref.attention(WHOLE, w, 1, n)
    c = ref.shared_experts(WHOLE, w, 1, n)
    spec, lw = _layer_w(ref, w, WHOLE)
    whole = spec.moe
    assert not whole.holds_share and whole.num_experts == 16
    total, tallies, ref_total = 0, [], 0
    for first in (0, 4, 8, 12):
        mine = dataclasses.replace(whole, held_experts=4, first_expert=first)
        lw_mine = dict(lw, **{k: lw[k][first:first + 4] for k in
                              ("expert_gate", "expert_up", "expert_down")})
        total = total + moe.moe_block(mine, n, lw_mine, tally=tallies,
                                      shared=False)
        # ... and the reference's own share of the same experts
        cut = dict(WHOLE, num_experts=4, router_num_experts=16,
                   first_expert=first)
        w_cut = dict(w, **{k: v[:, first:first + 4] for k, v in w.items()
                           if ".experts." in k and "shared" not in k})
        ref_total = ref_total + ref.routed_experts(cut, w_cut, 1, n)[0]
    np.testing.assert_allclose(x + a + total + c, want, atol=2e-5)
    np.testing.assert_allclose(x + a + ref_total + c, want, atol=2e-5)
    # every assignment fell to exactly one share
    assert sum(int(t[1]) for t in tallies) == 2 * 9 * 3
    # the whole block of one share is r + c: the shared branch whole on it
    mine = dataclasses.replace(whole, held_experts=4, first_expert=8)
    lw_mine = dict(lw, **{k: lw[k][8:12] for k in
                          ("expert_gate", "expert_up", "expert_down")})
    np.testing.assert_allclose(
        moe.moe_block(mine, n, lw_mine)
        - moe.moe_block(mine, n, lw_mine, shared=False), c, atol=2e-5)


def test_d_four_shared_experts_averaged_are_the_fused_branch(ref):
    hf = dict(WHOLE, num_shared_experts=4)
    w = weights.make_weights(ref.weight_shapes(hf), seed=2**31 + 58)
    rng = np.random.default_rng(6)
    n = jnp.asarray(rng.normal(size=(1, 7, 128)), jnp.float32)
    spec, lw = _layer_w(ref, w, hf, layer=2)
    assert (spec.moe.shared_intermediate, spec.moe.shared_mean_of) == \
        (512, 4)
    assert lw["shared_gate"].shape == (128, 512)
    assert lw["shared_down"].shape == (512, 128)
    fused = moe.shared_experts(spec.moe, n, lw)
    f = {k: np.asarray(v, np.float64) for k, v in w.items()
         if "shared_experts" in k}
    x = np.asarray(n, np.float64)[0]
    each = []
    for s in range(4):
        g = x @ f["model.layers.{i}.mlp.shared_experts.{e}.gate_proj"
                  ".weight"][2, s].T
        u = x @ f["model.layers.{i}.mlp.shared_experts.{e}.up_proj"
                  ".weight"][2, s].T
        each.append((g / (1 + np.exp(-g)) * u)
                    @ f["model.layers.{i}.mlp.shared_experts.{e}.down_proj"
                        ".weight"][2, s].T)
    np.testing.assert_allclose(fused[0], np.mean(each, axis=0), atol=1e-6)
    np.testing.assert_allclose(ref.shared_experts(hf, w, 2, n)[0],
                               np.mean(each, axis=0), atol=1e-6)
    # the sum is another model
    assert np.abs(np.sum(each, axis=0) - np.mean(each, axis=0)).max() > 1e-3
    summed = dataclasses.replace(spec.moe, shared_mean_of=0)
    np.testing.assert_allclose(moe.shared_experts(summed, n, lw)[0],
                               np.sum(each, axis=0), atol=1e-6)


def test_d_sigmoid_top_k_is_renormalised_over_held_and_absent_alike(ref):
    w = weights.make_weights(ref.weight_shapes(HF), seed=2**31 + 59)
    rng = np.random.default_rng(7)
    n = jnp.asarray(rng.normal(size=(1, 11, 128)), jnp.float32)
    spec, lw = _layer_w(ref, w, HF, layer=0)
    vals, idx = moe.route(spec.moe, n, lw["router"])
    logits = np.asarray(n, np.float64)[0] @ np.asarray(
        w["model.layers.{i}.mlp.gate.weight"][0], np.float64).T
    scores = 1 / (1 + np.exp(-logits))
    picked = np.argsort(-scores, axis=-1, kind="stable")[:, :3]
    np.testing.assert_array_equal(np.sort(idx[0], -1), np.sort(picked, -1))
    top = np.take_along_axis(scores, np.asarray(idx[0]), -1)
    np.testing.assert_allclose(vals[0], top / top.sum(-1, keepdims=True),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(vals).sum(-1), 1.0, atol=1e-6)
    held = (np.asarray(idx[0]) >= 4) & (np.asarray(idx[0]) < 8)
    assert held.any() and not held.all()          # picks on both sides
    # the share's weights do NOT sum to one over the held picks
    assert (np.where(held, np.asarray(vals[0]), 0).sum(-1) < 1 - 1e-3).any()


# ---------------------------------------------------------------------------
# (e) the loader's names and the refusals
# ---------------------------------------------------------------------------

def test_e_the_loader_reads_the_assumed_tensor_names(ref, gate_weights):
    table = ref.weight_shapes(HF)
    view = weights.HfView(table, gate_weights, dtype=np.dtype("float32"))
    assert {"model.embed_tokens.weight", "model.norm.weight",
            "model.layers.0.input_layernorm.weight",
            "model.layers.3.self_attn.q_proj.weight",
            "model.layers.2.mlp.gate.weight",
            "model.layers.1.mlp.experts.3.down_proj.weight",
            "model.layers.0.mlp.shared_experts.1.up_proj.weight"} <= set(view)
    assert "lm_head.weight" not in view                 # tied
    assert "model.layers.0.post_attention_layernorm.weight" not in view
    family = get_family("cohere2_moe")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", **SERVE)
    spec = family.build_spec(family.config_cls(tcfg, **HF))
    tree = family.convert_hf_state_dict(view, spec)
    layers = tree["layers"]
    assert layers["router"].shape == (4, 128, 16)
    assert layers["router"].dtype == np.float32
    assert layers["expert_gate"].shape == (4, 4, 128, 128)
    assert layers["shared_gate"].shape == (4, 128, 256)
    assert layers["shared_down"].shape == (4, 256, 128)
    # the second shared expert's gate is the second half of the fused gate
    np.testing.assert_array_equal(
        layers["shared_gate"][2][:, 128:],
        view["model.layers.2.mlp.shared_experts.1.gate_proj.weight"].T)
    np.testing.assert_array_equal(
        layers["shared_down"][2][128:],
        view["model.layers.2.mlp.shared_experts.1.down_proj.weight"].T)
    assert "lm_head" not in tree
    short = {k: view[k] for k in view if "shared_experts.1." not in k}
    with pytest.raises(KeyError, match="shared_experts.1.gate_proj"):
        family.convert_hf_state_dict(short, spec)


@pytest.mark.parametrize("key, value, names", [
    ("first_k_dense_replace", 1, "first_k_dense_replace"),
    ("use_qk_norm", True, "use_qk_norm"),
    ("expert_selection_fn", "softmax", "expert_selection_fn 'softmax'"),
    ("shared_expert_combination_strategy", "sum",
     "shared_expert_combination_strategy 'sum'"),
    ("use_parallel_block", False, "use_parallel_block"),
    ("position_embedding_type", "rope_llama", "position_embedding_type"),
    ("rotary_pct", 0.5, "rotary_pct"),
])
def test_e_refusals_by_name(key, value, names):
    family = get_family("cohere2_moe")
    tcfg = TpuConfig(tp_degree=1, dtype="float32", **SERVE)
    with pytest.raises(NotImplementedError, match=names):
        family.build_spec(family.config_cls(tcfg, **dict(HF, **{key: value})))


def test_e_a_share_and_a_ring_refuse_what_they_cannot_do():
    family = get_family("cohere2_moe")

    def spec_of(hf=HF, **serve):
        tcfg = TpuConfig(tp_degree=serve.pop("tp", 1), dtype="float32",
                         **dict(SERVE, **serve))
        return family.build_spec(family.config_cls(tcfg, **hf))
    with pytest.raises(NotImplementedError, match="served on one chip"):
        spec_of(ep_degree=2)
    with pytest.raises(NotImplementedError, match="prefix caching"):
        spec_of(is_prefix_caching=True)
    with pytest.raises(ValueError, match="layer_types names 3"):
        spec_of(dict(HF, layer_types=PERIOD[:3]))
    with pytest.raises(ValueError, match="held of a router"):
        spec_of(dict(HF, first_expert=14))
    # a stack of one kind has no pool to split and no pattern
    windows = spec_of(dict(HF, layer_types=["sliding_attention"] * 4))
    assert not windows.window_pool and windows.layer_pattern is None
    assert windows.sliding_window == 16 and not windows.no_rope
    full = spec_of(dict(HF, layer_types=["full_attention"] * 4))
    assert not full.window_pool and full.no_rope and not full.sliding_window


# ---------------------------------------------------------------------------
# (f) the harness's gate and the builder's chip check, at a toy size
# ---------------------------------------------------------------------------

def _toy_file():
    """The toy as a configuration file ``scripts/gate56.py`` and the
    harness's gate can build: the twin is the one period."""
    return dict(
        HF, family="cohere2_moe", tp=1, dtype="float32", serve=SERVE,
        adapter={"prefill_budget_tokens": 32},
        gate=dict(config={"num_hidden_layers": 4, "layer_types": PERIOD,
                          "sliding_window": 8},
                  batch=2, prompt_len=24, new_tokens=8, atol=2e-4, rtol=1e-4,
                  min_positions_held=1.0, median_ratio_max=0.5,
                  worst_ratio_max=1.0, excuse_margin_max=0.0))


def test_f_the_harness_gate_runs_the_full_batch_prefill():
    res = build.logit_gate(_toy_file(), seed=2**31 + 56,
                           served_precision="highest")
    assert res["passed"], res
    assert res["compared"] == 2 * 32 * HF["vocab_size"]


def test_f_the_builders_chip_check_runs_at_a_toy_size():
    """``scripts/gate56.py`` (what PR 56 ran on the chip at the published
    widths) at a toy size: the gate passes, every control that moves a
    logit and the fp8-rounded reference fail it, and the long walk at the
    file's own window (two rows of 150 tokens in chunks of 32 through the
    adapter's deferral, then decode: the rings wrap; a slot released and
    re-used) holds every position."""
    spec = importlib.util.spec_from_file_location(
        "gate56", os.path.join(ROOT, "scripts", "gate56.py"))
    gate56 = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate56)
    out = gate56.gate_and_controls(_toy_file(), seed=2**31 + 56,
                                   served_precision="highest")
    assert out["sound"]["passed"], out["sound"]
    ref = build.load_reference("cohere2_moe")
    assert set(out["controls"]) == set(ref.CONTROLS) | {
        "fp8_weights", "fp8_weights_vs_reference"}
    # (a bf16 router moves a toy's logits only where it flips a pick:
    # test_c_a_bf16_router_picks_other_experts)
    passed = {k for k, v in out["controls"].items() if v["passed"]}
    assert passed <= {"router_bf16"}, passed
    walk = gate56.long_walk(_toy_file(), seed=2**31 + 56, tokens=150,
                            rows=2, new_tokens=8, block=32,
                            served_precision="highest")
    assert walk["window"] == 16 and walk["ring_wraps"] == 2
    assert walk["slot_reused"] and walk["released"] == 1
    assert walk["all"]["positions"] == 2 * 158 + 37 + 8
    assert walk["all"]["held_share"] == 1.0 and walk["passed"]
    assert walk["blocked_vs_plain_reference"] < 1e-5
    assert walk["all"]["worst_ratio"] < 0.5
    assert walk["reused_slot"]["held_share"] == 1.0
