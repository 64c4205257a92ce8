"""``references/ling_kda.py``'s own check. ``transformers`` 4.57.6 has no Ling
model and this machine no ``fla`` (so no ``reference_cases/ling_kda.json``:
``test_reference`` would look up a class for the whole model). What is here
holds the reference piece by piece:

* the MLA block against ``DeepseekV2Attention`` at ``q_lora_rank=None`` (the
  form the config names). HF rotates INTERLEAVED pairs; the reference rotates
  halves (``assumed`` A6), so HF is given the rope rows of ``q_proj`` and
  ``kv_a_proj_with_mqa`` in the order that makes its pairs the reference's
  (a permutation both dot products ignore), and the reference's own control
  ``rope_interleaved`` is HF's on the rows as they are. The head-wise gate,
  which HF has not, is held apart: with an identity ``o_proj`` the gated
  output is the ungated one times ``sigmoid(W_g u)`` a head;
* the router against ``DeepseekV3TopkRouter`` with groups (4 of 8 chosen by
  the sum of their top two);
* the recurrence against a second form written here: the WY / chunked form at
  chunk 16 in float64 numpy, the pair weights as the ``(C, C, d_k)`` product
  outright and the system by ``numpy.linalg.solve``;
* the share, the margins, the layer pattern, the controls' list and the
  weight table's round trip.
"""

import numpy as np
import pytest

from harness import build, weights

CFG = dict(
    model_type="ling_kda", vocab_size=128, hidden_size=64,
    num_hidden_layers=7, intermediate_size=96, first_k_dense_replace=2,
    max_position_embeddings=512, moe_intermediate_size=24,
    num_experts_per_tok=2, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=None, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, head_dim=16, num_experts=16,
    rope_theta=10000, rms_norm_eps=1e-6, routed_scaling_factor=2.5, n_group=4,
    topk_group=2, moe_shared_expert_intermediate_size=24,
    num_shared_experts=1, layer_group_size=3, short_conv_kernel_size=4,
    kda_lower_bound=-5, norm_topk_prob=True,
    expert_swiglu_limit_list=[0] * 7,
    share_expert_swiglu_limit_list=[0] * 7, tie_word_embeddings=False)
#: float32 sums in another order
ATOL = 2e-5


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("ling_kda")


@pytest.fixture(scope="module")
def w(ref):
    return weights.make_weights(ref.weight_shapes(CFG), seed=2**31 + 67)


def _pairs_as_halves(rows, d):
    """The order of ``d`` rope rows that makes HF's pairs ``(2i, 2i + 1)``
    the half-split layout's ``(i, i + d / 2)``."""
    order = np.empty(d, int)
    order[0::2], order[1::2] = np.arange(d // 2), np.arange(d // 2, d)
    return rows[order]


def test_the_latent_block_is_deepseek_v2s_without_a_query_down_projection(
        ref, w):
    import jax.numpy as jnp
    import torch
    from transformers import DeepseekV2Config
    from transformers.models.deepseek_v2.modeling_deepseek_v2 import (
        DeepseekV2Attention, DeepseekV2RotaryEmbedding)
    nh, nope, rot, dv, rkv, hid = 4, 16, 8, 16, 32, 64
    hf_cfg = DeepseekV2Config(
        hidden_size=hid, num_attention_heads=nh, num_key_value_heads=nh,
        q_lora_rank=None, kv_lora_rank=rkv, qk_nope_head_dim=nope,
        qk_rope_head_dim=rot, v_head_dim=dv, head_dim=rot, rope_theta=10000,
        rms_norm_eps=1e-6, attention_bias=False,
        max_position_embeddings=512)
    hf_cfg._attn_implementation = "eager"
    attn = DeepseekV2Attention(hf_cfg, layer_idx=0).eval()
    assert not hasattr(attn, "q_a_proj")
    view = weights.HfView(ref.weight_shapes(CFG), w,
                          dtype=np.dtype("float32"))
    layer, j = 5, 1                       # the second latent layer

    def tensor(name):
        return np.ascontiguousarray(
            view[f"model.layers.{layer}.self_attn.{name}"])

    def load(permute):
        q = tensor("q_proj.weight").reshape(nh, nope + rot, hid).copy()
        kva = tensor("kv_a_proj_with_mqa.weight").copy()
        if permute:
            q[:, nope:] = np.stack([_pairs_as_halves(h[nope:], rot)
                                    for h in q])
            kva[rkv:] = _pairs_as_halves(kva[rkv:], rot)
        with torch.no_grad():
            attn.q_proj.weight.copy_(torch.tensor(q.reshape(-1, hid)))
            attn.kv_a_proj_with_mqa.weight.copy_(torch.tensor(kva))
            attn.kv_a_layernorm.weight.copy_(
                torch.tensor(tensor("kv_a_layernorm.weight")))
            attn.kv_b_proj.weight.copy_(
                torch.tensor(tensor("kv_b_proj.weight")))
            attn.o_proj.weight.copy_(torch.tensor(tensor("o_proj.weight")))
    rng = np.random.default_rng(67)
    s = 24
    x = rng.normal(size=(2, s, hid)).astype(np.float32)
    mask = torch.full((s, s), float("-inf")).triu(1)[None, None]
    rotary = DeepseekV2RotaryEmbedding(hf_cfg)
    freqs = rotary(torch.tensor(x), torch.arange(s)[None])

    def hf():
        with torch.no_grad():
            return attn(torch.tensor(x), attention_mask=mask,
                        position_embeddings=freqs)[0].numpy()
    load(permute=True)
    ours = np.asarray(ref.mla(CFG, w, j, jnp.asarray(x), "no_head_gate_mla"))
    np.testing.assert_allclose(ours, hf(), atol=ATOL)
    # on the rows as they are HF is the control, and another function
    load(permute=False)
    on_pairs = np.asarray(ref.mla(CFG, w, j, jnp.asarray(x),
                                  "rope_interleaved"))
    gated = np.asarray(ref.mla(CFG, w, j, jnp.asarray(x)))
    # (the control gates; HF does not: compare through the gate's absence)
    assert float(np.abs(ours - hf()).max()) > 5 * ATOL
    assert float(np.abs(on_pairs - gated).max()) > 5 * ATOL
    # the head-wise gate: under an identity o_proj (hidden = heads x v) the
    # gated output is the plain one times ONE sigmoid a head
    eye = dict(w)
    name = ref.ATTN + "o_proj.weight"
    eye[name] = jnp.broadcast_to(jnp.eye(hid, dtype=w[name].dtype),
                                 w[name].shape)
    plain = np.asarray(ref.mla(CFG, eye, j, jnp.asarray(x),
                               "no_head_gate_mla")).reshape(2, s, nh, dv)
    gate = 1 / (1 + np.exp(-x @ np.asarray(
        w[ref.ATTN + "g_proj.weight"][j], np.float32).T))
    np.testing.assert_allclose(
        np.asarray(ref.mla(CFG, eye, j, jnp.asarray(x))).reshape(2, s, nh, dv),
        plain * gate[..., None], atol=ATOL)
    for control in ("no_rotary", "no_latent_norm"):
        other = np.asarray(ref.mla(CFG, w, j, jnp.asarray(x), control))
        assert float(np.abs(other - gated).max()) > 5 * ATOL, control


def test_the_routing_is_deepseek_v3s_with_groups(ref):
    import jax.numpy as jnp
    import torch
    from transformers import DeepseekV3Config
    from transformers.models.deepseek_v3.modeling_deepseek_v3 import \
        DeepseekV3TopkRouter
    n_e, hid, k = 32, 48, 4
    cfg = dict(CFG, hidden_size=hid, num_experts=n_e, num_experts_per_tok=k,
               n_group=8, topk_group=4)
    router = DeepseekV3TopkRouter(DeepseekV3Config(
        hidden_size=hid, n_routed_experts=n_e, num_experts_per_tok=k,
        n_group=8, topk_group=4, norm_topk_prob=True,
        routed_scaling_factor=2.5))
    rng = np.random.default_rng(67)
    gate = rng.normal(size=(n_e, hid)).astype(np.float32) * 0.3
    bias = rng.uniform(-0.2, 0.2, size=(n_e,)).astype(np.float32)
    x = rng.normal(size=(60, hid)).astype(np.float32)
    with torch.no_grad():
        router.weight.copy_(torch.tensor(gate))
        router.e_score_correction_bias.copy_(torch.tensor(bias))
        want_e, want_w = router(torch.tensor(x))
    w = {ref.MLP + "gate.weight": jnp.asarray(gate)[None],
         ref.MLP + "gate.e_score_correction_bias": jnp.asarray(bias)[None]}
    top, picked, margin = ref.routing(cfg, w, 0, jnp.asarray(x))
    order = np.argsort(np.asarray(picked), -1)
    want_order = np.argsort(want_e.numpy(), -1)
    np.testing.assert_array_equal(
        np.take_along_axis(np.asarray(picked), order, -1),
        np.take_along_axis(want_e.numpy(), want_order, -1))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(top), order, -1),
        np.take_along_axis(want_w.numpy(), want_order, -1), rtol=2e-6)
    # the groups kept somewhere a column the plain top k would have dropped
    plain, _, _ = ref.routing(cfg, w, 0, jnp.asarray(x), "no_groups")
    _, plain_idx, _ = ref.routing(cfg, w, 0, jnp.asarray(x), "no_groups")
    assert (np.sort(np.asarray(plain_idx), -1)
            != np.sort(np.asarray(picked), -1)).any()
    assert margin.shape == (60,) and float(margin.min()) >= 0
    for control in ("no_select_bias", "bias_in_weights", "not_renormalised",
                    "no_routed_scaling", "softmax"):
        other, _, _ = ref.routing(cfg, w, 0, jnp.asarray(x), control)
        assert float(np.abs(np.sort(np.asarray(other), -1)
                            - np.sort(np.asarray(top), -1)).max()) > 1e-3


def _chunked_float64(q, k, v, g, beta, chunk=16):
    """The delta rule gated by channel in its WY / chunked form, float64
    numpy, one (sequence, head) at a time: q, k, g (S, dk), v (S, dv), beta
    (S,). The pair weights are the (C, C, dk) product outright (float64 holds
    exp(320)), the system goes to ``numpy.linalg.solve``."""
    s, dk = k.shape
    state = np.zeros((dk, v.shape[1]))
    out = np.zeros_like(v)
    for lo in range(0, s, chunk):
        sl = slice(lo, min(lo + chunk, s))
        qc, kc, vc, bc = q[sl], k[sl], v[sl], beta[sl]
        big = np.cumsum(g[sl], axis=0)                       # (C, dk)
        pair = np.exp(big[:, None, :] - big[None, :, :])     # i, j, d
        kk = np.einsum("id,jd,ijd->ij", kc, kc, pair)
        a = np.tril(bc[:, None] * kk, -1)
        rhs = np.concatenate([bc[:, None] * vc,
                              bc[:, None] * kc * np.exp(big)], axis=1)
        uw = np.linalg.solve(np.eye(len(bc)) + a, rhs)
        new_v = uw[:, :vc.shape[1]] - uw[:, vc.shape[1]:] @ state
        qk = np.tril(np.einsum("id,jd,ijd->ij", qc, kc, pair))
        out[sl] = (qc * np.exp(big)) @ state + qk @ new_v
        state = np.exp(big[-1])[:, None] * state \
            + (kc * np.exp(big[-1] - big)).T @ new_v
    return out, state


def test_the_recurrence_is_the_chunked_form_in_float64(ref, w):
    import jax.numpy as jnp
    rng = np.random.default_rng(67)
    s = 45                                      # two chunks of 16 and 13
    u = jnp.asarray(rng.normal(size=(2, s, CFG["hidden_size"])), jnp.float32)
    q, k, v, g, beta, gate = ref.kda_inputs(CFG, w, 1, u)
    g_np = np.asarray(g)
    # the seeded decay spreads over (-5, 0) and differs by channel of a head
    assert g_np.min() < -4.5 and g_np.max() > -0.5
    assert float((g_np.max(-1) - g_np.min(-1)).min()) > 1.0
    assert np.asarray(gate).shape == (2, s, 4) and 0 < np.asarray(gate).min()
    o, last = ref.kda_rule(q, k, v, g, beta)
    for b in range(2):
        for h in range(4):
            want_o, want_s = _chunked_float64(*(
                np.asarray(a, np.float64)[b, :, h] for a in (q, k, v, g)),
                np.asarray(beta, np.float64)[b, :, h])
            np.testing.assert_allclose(np.asarray(o)[b, :, h], want_o,
                                       atol=ATOL)
            np.testing.assert_allclose(np.asarray(last)[b, h], want_s,
                                       atol=ATOL)
    # the decay lands BEFORE the read through k; after it is another rule
    late, _ = ref.kda_rule(q, k, v, g, beta, control="decay_after_write")
    assert float(np.abs(np.asarray(late) - np.asarray(o)).max()) > 1e-3
    # a state continued from a carried one is the one pass
    half, mid = ref.kda_rule(q[:, :20], k[:, :20], v[:, :20], g[:, :20],
                             beta[:, :20])
    rest, end = ref.kda_rule(q[:, 20:], k[:, 20:], v[:, 20:], g[:, 20:],
                             beta[:, 20:], state=mid)
    np.testing.assert_allclose(np.asarray(end), np.asarray(last), atol=ATOL)
    np.testing.assert_allclose(
        np.concatenate([np.asarray(half), np.asarray(rest)], 1),
        np.asarray(o), atol=ATOL)


def test_the_layer_pattern_the_share_and_the_margins(ref, w):
    import jax.numpy as jnp
    assert ref.linear_layers(CFG) == ([0, 1, 3, 4, 6], [2, 5])
    assert ref.linear_layers(dict(CFG, num_hidden_layers=18,
                                  layer_group_size=6))[1] == [5, 11, 17]
    ids = jnp.asarray(np.random.default_rng(1).integers(1, 128, (2, 19)))
    logits, margins = ref.forward(CFG, w, ids, with_margins=True)
    assert logits.shape == (2, 19, 128) and margins.shape == (2, 19)
    assert bool(jnp.isfinite(margins).all()) and float(margins.min()) >= 0
    states = ref.final_states(CFG, w, ids)
    assert states.shape == (5, 2, 4, 16, 16)
    # a share leaves the absent experts' part out: two halves and the shared
    # expert counted once are the whole layer
    u = jnp.asarray(np.random.default_rng(2).normal(size=(2, 7, 64)),
                    jnp.float32)
    whole, _ = ref.moe(CFG, w, 0, u)
    shared_only, _ = ref.moe(
        dict(CFG, num_experts=0, router_num_experts=16),
        {k: (v[:, :0] if k.startswith(ref.EXPERT) else v)
         for k, v in w.items()}, 0, u)
    parts = []
    for first in (0, 8):
        cfg = dict(CFG, num_experts=8, router_num_experts=16,
                   first_expert=first)
        held = {k: (v[:, first:first + 8] if k.startswith(ref.EXPERT) else v)
                for k, v in w.items()}
        parts.append(ref.moe(cfg, held, 0, u, "no_shared")[0])
    np.testing.assert_allclose(
        np.asarray(parts[0] + parts[1] + shared_only), np.asarray(whole),
        atol=ATOL)
    with pytest.raises(ValueError, match="held"):
        ref.share(dict(CFG, num_experts=8, router_num_experts=16,
                       first_expert=9))


def test_every_control_is_another_function_and_unknown_ones_are_refused(
        ref, w):
    import jax.numpy as jnp
    ids = jnp.asarray(np.random.default_rng(3).integers(1, 128, (1, 40)))
    sound = np.asarray(ref.forward(CFG, w, ids))
    assert len(set(ref.CONTROLS)) == len(ref.CONTROLS) == 22
    for control in ref.CONTROLS:
        other = np.asarray(ref.forward(CFG, w, ids, control=control))
        assert float(np.abs(other - sound).max()) > 1e-4, control
    with pytest.raises(ValueError, match="unknown control"):
        ref.forward(CFG, w, ids, control="nope")
    with pytest.raises(ValueError, match="expert_swiglu_limit_list"):
        ref.forward(dict(CFG, expert_swiglu_limit_list=[0] * 6 + [4]), w, ids)


def test_the_weight_table_round_trips_through_the_published_names(ref, w):
    table = ref.weight_shapes(CFG)
    view = weights.HfView(table, w, dtype=np.dtype("float32"))
    assert view["model.layers.2.self_attn.g_proj.weight"].shape == (4, 64)
    assert view["model.layers.0.linear_attn.f_proj.weight"].shape == (64, 64)
    assert view["model.layers.6.linear_attn.dt_bias"].shape == (64,)
    assert view["model.layers.6.linear_attn.A_log"].shape == (4,)
    assert view["model.layers.1.mlp.gate_proj.weight"].shape == (96, 64)
    assert view["model.layers.2.mlp.gate.weight"].shape == (16, 64)
    assert view["model.layers.6.mlp.experts.15.down_proj.weight"].shape == \
        (64, 24)
    assert "model.layers.2.linear_attn.q_proj.weight" not in view
    assert "model.layers.0.self_attn.q_proj.weight" not in view
    assert "model.layers.1.mlp.gate.weight" not in view
    bias = np.asarray(w[ref.MLP + "gate.e_score_correction_bias"],
                      np.float32)
    assert np.abs(bias).max() > 0.01
