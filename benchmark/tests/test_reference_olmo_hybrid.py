"""``references/olmo_hybrid.py``'s own check. ``transformers`` 4.57.6 has no
``olmo_hybrid`` (so no ``reference_cases/olmo_hybrid.json``: ``test_reference``
would look up ``OlmoHybridForCausalLM``), but it ships the same gated delta
rule as ``Qwen3NextGatedDeltaNet``: the reference's token-by-token recurrence
is held to ``torch_recurrent_gated_delta_rule`` and
``torch_chunk_gated_delta_rule`` in float32, its layer inputs to the
published equations on a hand-computed case, and its weight table is
round-tripped through ``HfView``."""

import numpy as np
import pytest

from harness import build, weights

CFG = dict(
    model_type="olmo_hybrid", vocab_size=128, hidden_size=32,
    intermediate_size=64, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=4, hidden_act="silu", max_position_embeddings=512,
    attention_bias=False, rms_norm_eps=1e-6, tie_word_embeddings=False,
    layer_types=["linear_attention", "linear_attention", "linear_attention",
                 "full_attention"],
    linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None})
#: float32 sums in another order; a wrong decay or correction is O(1)
ATOL = 2e-5


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("olmo_hybrid")


@pytest.fixture(scope="module")
def w(ref):
    return weights.make_weights(ref.weight_shapes(CFG), seed=2**31 + 34)


@pytest.mark.parametrize("from_state", [False, True])
def test_the_recurrence_is_transformers_gated_delta_rule(ref, w, from_state):
    """The inputs of linear layer 1 as the reference computes them from
    seeded weights (beta in (0, 2), alpha spread over decades), through its
    ``delta_rule`` and through both torch forms (handed the unscaled query
    and ``beta`` already doubled)."""
    import jax
    import jax.numpy as jnp
    import torch
    from transformers.models.qwen3_next import modeling_qwen3_next as hf
    rng = np.random.default_rng(34)
    # rms 12: at 32 inputs W_b h then spreads as it does at 3840 with rms 1
    h = jnp.asarray(12 * rng.standard_normal((2, 150, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        q, k, v, alpha, beta, _ = ref.delta_rule_inputs(CFG, w, 1, h)
        st0 = (jnp.asarray(rng.standard_normal((2, 2, 8, 16)), jnp.float32)
               if from_state else None)
        want_o, want_s = ref.delta_rule(q, k, v, alpha, beta, st0)
    assert float(beta.max()) > 1.5 and float(beta.min()) < 0.5
    assert float(alpha.min()) < 0.5 and float(alpha.max()) > 0.99
    np.testing.assert_allclose(jnp.linalg.norm(k, axis=-1), 1.0, atol=1e-3)

    def t(x):
        return torch.tensor(np.asarray(x))
    args = [t(q * 8 ** 0.5), t(k), t(v), t(jnp.log(alpha)), t(beta)]
    init = None if st0 is None else t(st0)
    got_o, got_s = hf.torch_recurrent_gated_delta_rule(
        *args, initial_state=init, output_final_state=True)
    np.testing.assert_allclose(want_o, got_o.numpy(), atol=ATOL)
    np.testing.assert_allclose(want_s, got_s.numpy(), atol=ATOL)
    got_o, got_s = hf.torch_chunk_gated_delta_rule(
        *args, chunk_size=64, initial_state=init, output_final_state=True)
    np.testing.assert_allclose(want_o, got_o.numpy(), atol=ATOL)
    np.testing.assert_allclose(want_s, got_s.numpy(), atol=ATOL)


def test_one_token_by_hand(ref):
    """``S_1 = beta k (v)^T`` from a zero state and ``o_1 = S_1^T q``; the
    second token reads the decayed state back through its key."""
    import jax.numpy as jnp
    q = jnp.asarray([[[[1.0, 0.0]], [[0.0, 1.0]]]])       # (1, 2, 1, 2)
    k = jnp.asarray([[[[1.0, 0.0]], [[1.0, 0.0]]]])
    v = jnp.asarray([[[[3.0, 5.0]], [[7.0, 11.0]]]])
    alpha = jnp.asarray([[[1.0], [0.5]]])
    beta = jnp.asarray([[[2.0], [1.0]]])
    o, s = ref.delta_rule(q, k, v, alpha, beta)
    # t=1: S = 2 k v^T = [[6, 10], [0, 0]], o = S^T q = [6, 10]
    np.testing.assert_allclose(o[0, 0, 0], [6.0, 10.0])
    # t=2: decayed S = [[3, 5], [0, 0]]; read through k = [3, 5];
    # S += k (v - [3, 5])^T = [[7, 11], [0, 0]]; q = e_2 reads row 2 = 0
    np.testing.assert_allclose(s[0, 0], [[7.0, 11.0], [0.0, 0.0]])
    np.testing.assert_allclose(o[0, 1, 0], [0.0, 0.0])


def test_the_forward_and_its_states(ref, w):
    import jax
    import jax.numpy as jnp
    ids = jnp.asarray(np.random.default_rng(3).integers(1, 128, (2, 40)))
    with jax.default_matmul_precision("highest"):
        logits, margins = ref.forward(CFG, w, ids, with_margins=True)
        states = ref.final_states(CFG, w, ids)
        # causal: a later token changes no earlier logit
        again = ref.forward(CFG, w, ids.at[:, 30].set(5))
    assert logits.shape == (2, 40, 128) and logits.dtype == jnp.float32
    assert np.isinf(np.asarray(margins)).all()
    assert states.shape == (3, 2, 2, 8, 16)
    np.testing.assert_array_equal(logits[:, :30], again[:, :30])
    assert float(jnp.abs(logits[:, 30:] - again[:, 30:]).max()) > 1e-3


def test_hfview_round_trips_the_table(ref, w):
    table = ref.weight_shapes(CFG)
    assert {k: v.shape for k, v in w.items()} == \
        {k: tuple(e["shape"]) for k, e in table.items()}
    view = weights.HfView(table, w)
    lin, full = [0, 1, 2], [3]
    for name, entry in table.items():
        if "{i}" not in name:
            np.testing.assert_array_equal(np.asarray(w[name]), view[name])
            continue
        layers = weights.layers_of(name, entry)
        assert layers == (lin if "linear_attn" in name else full
                          if "self_attn" in name else [0, 1, 2, 3])
        for row, i in enumerate(layers):
            np.testing.assert_array_equal(np.asarray(w[name][row]),
                                          view[name.format(i=i)])
    assert "model.layers.3.linear_attn.A_log" not in view
    assert "model.layers.0.self_attn.q_proj.weight" not in view
    assert "lm_head.weight" in view
    # the decay's parameters spread over decades, as the table says
    a_log = np.asarray(w["model.layers.{i}.linear_attn.A_log"], np.float32)
    dt = np.asarray(w["model.layers.{i}.linear_attn.dt_bias"], np.float32)
    assert 0.0 <= a_log.min() and a_log.max() <= 2.77
    assert -6.9 <= dt.min() and dt.max() <= -2.25
