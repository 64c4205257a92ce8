"""``references/lfm2_moe.py``'s own check. ``transformers`` 4.57.6 has
``lfm2`` and no ``lfm2_moe`` (so no ``reference_cases/lfm2_moe.json``:
``test_reference`` would look up ``Lfm2MoeForCausalLM``). What it does ship
holds the reference piece by piece:

* with ``num_dense_layers = num_hidden_layers`` the reference IS the dense
  sibling: operator, q/k norms before the rotary embedding, the short
  convolution, ``embedding_norm`` and the tied head against
  ``Lfm2ForCausalLM`` in float32 on the same weights;
* the routed block against a literal transcription of the published
  ``Lfm2MoeSparseMoeBlock.route_tokens_to_experts`` (as recalled, ISSUE 61)
  and against ``DeepseekV3TopkRouter`` at ``n_group = topk_group = 1``, which
  differs by the epsilon alone (``1e-20`` there, ``1e-6`` here);
* the weight table round-trips through ``HfView``.
"""

import numpy as np
import pytest

from harness import build, weights

CFG = dict(
    model_type="lfm2_moe", vocab_size=128, hidden_size=32,
    intermediate_size=64, num_hidden_layers=6, num_attention_heads=4,
    num_key_value_heads=2, conv_L_cache=3, conv_bias=False,
    layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv"],
    max_position_embeddings=512, moe_intermediate_size=16, norm_eps=1e-5,
    norm_topk_prob=True, num_dense_layers=2, num_experts=8,
    num_experts_per_tok=2, rope_theta=1000000, routed_scaling_factor=1,
    use_expert_bias=True)
#: float32 sums in another order; a wrong norm order or tap order is O(0.1)
ATOL = 2e-5


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("lfm2_moe")


@pytest.fixture(scope="module")
def w(ref):
    return weights.make_weights(ref.weight_shapes(CFG), seed=2**31 + 61)


def test_with_every_layer_dense_it_is_transformers_lfm2(ref):
    import jax
    import jax.numpy as jnp
    import torch
    from transformers import Lfm2Config, Lfm2ForCausalLM
    dense = dict(CFG, num_dense_layers=CFG["num_hidden_layers"])
    table = ref.weight_shapes(dense)
    assert not any("experts" in k or "gate" in k for k in table)
    w = weights.make_weights(table, seed=2**31 + 62)
    hf = Lfm2ForCausalLM(Lfm2Config(
        **{k: dense[k] for k in (
            "vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "conv_L_cache", "conv_bias",
            "layer_types", "max_position_embeddings", "norm_eps",
            "rope_theta")},
        block_auto_adjust_ff_dim=False, tie_word_embeddings=True,
        torch_dtype="float32")).eval()
    view = weights.HfView(table, w, dtype=np.dtype("float32"))
    missing, unexpected = hf.load_state_dict(
        {k: torch.tensor(np.ascontiguousarray(v)) for k, v in view.items()},
        strict=False)
    assert not unexpected and set(missing) <= {"lm_head.weight"}
    ids = np.random.default_rng(5).integers(1, 128, (2, 40))
    with torch.no_grad():
        want = hf(torch.tensor(ids)).logits.numpy()
    with jax.default_matmul_precision("highest"):
        got = ref.forward(dense, w, jnp.asarray(ids))
        swapped = ref.forward(dense, w, jnp.asarray(ids),
                              control="qk_norm_after_rope")
        exchanged = ref.forward(dense, w, jnp.asarray(ids),
                                control="b_c_exchanged")
    assert float(np.abs(want).max()) > 0.5
    np.testing.assert_allclose(got, want, atol=ATOL)
    # the two faults of the shared part are ones transformers would catch
    assert float(np.abs(swapped - want).max()) > 100 * ATOL
    assert float(np.abs(exchanged - want).max()) > 100 * ATOL


def _published_route(logits, bias, top_k, norm, scale):
    """``Lfm2MoeSparseMoeBlock.route_tokens_to_experts`` with
    ``use_expert_bias``, line for line (as recalled, ISSUE 61)."""
    import torch
    routing_weights = logits.sigmoid()
    scores_for_routing = routing_weights + bias
    _, selected_experts = torch.topk(scores_for_routing, k=top_k, dim=-1)
    routing_weights = torch.gather(routing_weights, dim=1,
                                   index=selected_experts).type_as(logits)
    if norm:
        routing_weights = routing_weights / (
            routing_weights.sum(dim=-1, keepdim=True) + 1e-6)
    return selected_experts, routing_weights * scale


@pytest.mark.parametrize("norm, scale", [(True, 1.0), (False, 2.5)])
def test_the_routing_is_the_published_transcription(ref, norm, scale):
    import jax.numpy as jnp
    import torch
    rng = np.random.default_rng(61)
    logits = rng.normal(size=(50, 8)).astype(np.float32)
    bias = rng.uniform(-0.1, 0.1, size=(8,)).astype(np.float32)
    cfg = dict(CFG, norm_topk_prob=norm, routed_scaling_factor=scale)
    top, picked, margin = ref.route(
        cfg, 1 / (1 + jnp.exp(-jnp.asarray(logits))), jnp.asarray(bias))
    want_e, want_w = _published_route(torch.tensor(logits),
                                      torch.tensor(bias), 2, norm, scale)
    np.testing.assert_array_equal(np.asarray(picked), want_e.numpy())
    np.testing.assert_allclose(np.asarray(top), want_w.numpy(), rtol=1e-6)
    # the bias picked somewhere it did not weigh
    plain = np.argsort(-logits, axis=-1)[:, :2]
    assert (np.sort(plain, -1) != np.sort(np.asarray(picked), -1)).any()
    assert margin.shape == (50,) and float(margin.min()) >= 0


def test_the_routing_is_deepseek_v3s_up_to_the_epsilon(ref):
    import jax.numpy as jnp
    import torch
    from transformers import DeepseekV3Config
    from transformers.models.deepseek_v3.modeling_deepseek_v3 import \
        DeepseekV3TopkRouter
    router = DeepseekV3TopkRouter(DeepseekV3Config(
        hidden_size=32, n_routed_experts=8, num_experts_per_tok=2, n_group=1,
        topk_group=1, norm_topk_prob=True, routed_scaling_factor=1.0))
    rng = np.random.default_rng(62)
    gate = rng.normal(size=(8, 32)).astype(np.float32) * 0.3
    bias = rng.uniform(-0.1, 0.1, size=(8,)).astype(np.float32)
    x = rng.normal(size=(40, 32)).astype(np.float32)
    with torch.no_grad():
        router.weight.copy_(torch.tensor(gate))
        router.e_score_correction_bias.copy_(torch.tensor(bias))
        want_e, want_w = router(torch.tensor(x))
    scores = 1 / (1 + jnp.exp(-jnp.asarray(x @ gate.T)))
    top, picked, _ = ref.route(CFG, scores, jnp.asarray(bias))
    order = np.argsort(np.asarray(picked), -1)
    want_order = np.argsort(want_e.numpy(), -1)
    np.testing.assert_array_equal(
        np.take_along_axis(np.asarray(picked), order, -1),
        np.take_along_axis(want_e.numpy(), want_order, -1))
    got = np.take_along_axis(np.asarray(top), order, -1)
    want = np.take_along_axis(want_w.numpy(), want_order, -1)
    # the epsilon alone: 1e-6 over a sum of ~1
    np.testing.assert_allclose(got, want, rtol=5e-6)
    assert np.abs(got - want).max() > 0


def test_the_forward_its_margins_and_its_tails(ref, w):
    import jax
    import jax.numpy as jnp
    ids = jnp.asarray(np.random.default_rng(3).integers(1, 128, (2, 40)))
    with jax.default_matmul_precision("highest"):
        logits, margins = ref.forward(CFG, w, ids, with_margins=True)
        tails = ref.final_tails(CFG, w, ids)
        shorter = ref.final_tails(CFG, w, ids[:, :39])
        # causal: a later token changes no earlier logit
        again = ref.forward(CFG, w, ids.at[:, 30].set(5))
        faulty = {c: ref.forward(CFG, w, ids, control=c)
                  for c in ref.CONTROLS}
    assert logits.shape == (2, 40, 128) and logits.dtype == jnp.float32
    assert margins.shape == (2, 40)
    assert np.isfinite(np.asarray(margins)).all() and float(
        margins.min()) >= 0
    # five conv layers, the last two products of every channel, oldest first
    assert tails.shape == (5, 2, 2, 32)
    np.testing.assert_allclose(tails[0, :, 0], shorter[0, :, 1], atol=1e-6)
    np.testing.assert_array_equal(logits[:, :30], again[:, :30])
    assert float(jnp.abs(logits[:, 30:] - again[:, 30:]).max()) > 1e-3
    for control, got in faulty.items():
        assert float(jnp.abs(got - logits).max()) > 5e-4, control
    with pytest.raises(ValueError):
        ref.forward(CFG, w, ids, control="no_such_fault")


def test_hfview_round_trips_the_table(ref, w):
    table = ref.weight_shapes(CFG)
    assert {k: v.shape for k, v in w.items()} == \
        {k: tuple(e["shape"]) for k, e in table.items()}
    view = weights.HfView(table, w)
    conv, full = [0, 1, 3, 4, 5], [2]
    for name, entry in table.items():
        if "{i}" not in name:
            np.testing.assert_array_equal(np.asarray(w[name]), view[name])
            continue
        layers = weights.layers_of(name, entry)
        assert layers == (
            conv if ".conv." in name else full if "self_attn" in name
            else [2, 3, 4, 5] if "experts" in name or "gate" in name
            or "expert_bias" in name
            else [0, 1] if "feed_forward" in name
            else list(range(6))), name
        for row, i in enumerate(layers):
            if "{e}" in name:
                for e in (0, 7):
                    np.testing.assert_array_equal(
                        np.asarray(w[name][row, e]),
                        view[name.format(i=i, e=e)])
            else:
                np.testing.assert_array_equal(np.asarray(w[name][row]),
                                              view[name.format(i=i)])
    assert "model.layers.2.conv.in_proj.weight" not in view
    assert "model.layers.0.feed_forward.gate.weight" not in view
    assert "model.layers.2.feed_forward.w1.weight" not in view
    assert "lm_head.weight" not in view                  # tied
    assert view["model.layers.5.feed_forward.experts.7.w2.weight"].shape \
        == (32, 16)
    assert view["model.layers.1.conv.conv.weight"].shape == (32, 1, 3)
    # the selection bias is drawn non-zero, the taps as nn.Conv1d's
    bias = np.asarray(w["model.layers.{i}.feed_forward.expert_bias"],
                      np.float32)
    assert np.abs(bias).max() <= 0.2 and np.abs(bias).mean() > 0.04
    taps = np.asarray(w["model.layers.{i}.conv.conv.weight"], np.float32)
    assert np.abs(taps).max() <= 3 ** -0.5 + 1e-3 and taps.std() > 0.2
