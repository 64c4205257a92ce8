"""``references/cohere2_moe.py``'s own check. ``transformers`` 4.57.6 has
``cohere2`` and no ``cohere2_moe`` class (so no
``reference_cases/cohere2_moe.json``): ``forward`` is held to a SECOND
writing of ISSUE 56's equations, token by token in NumPy float64 (one query
at a time against the keys it may see, one expert at a time, the four shared
experts one at a time and then averaged), a share is held to the whole, the
interleaved rotary and a NoPE row are checked by hand, every control moves
the logits, and the weight table is round-tripped through ``HfView``. What
the dense ``cohere2`` shares with it (LayerNorm, the interleaved rotary, the
window mask, NoPE full layers, the tied scaled head) is held to
``transformers``' ``Cohere2ForCausalLM`` with the expert terms switched
off."""

import numpy as np
import pytest

from harness import build, weights

PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
CFG = dict(
    model_type="cohere2_moe", vocab_size=96, hidden_size=32, head_dim=16,
    num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=4,
    intermediate_size=24, num_experts=8, num_experts_per_tok=3,
    num_shared_experts=4, norm_topk_prob=True, expert_selection_fn="sigmoid",
    shared_expert_combination_strategy="average", use_parallel_block=True,
    use_qk_norm=False, first_k_dense_replace=0, hidden_act="silu",
    layer_norm_eps=1e-5, rms_norm_eps=None, rope_theta=50000, rotary_pct=1,
    position_embedding_type="rope_gptj", logit_scale=0.25,
    max_position_embeddings=256, sliding_window=5, layer_types=PERIOD,
    tie_word_embeddings=True)
#: float32 against float64: sums in another order
ATOL = 2e-5
P = "model.layers.{i}."


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("cohere2_moe")


@pytest.fixture(scope="module")
def w(ref):
    return weights.make_weights(ref.weight_shapes(CFG), seed=2**31 + 56)


@pytest.fixture(scope="module")
def sharp(w):
    """The q / k projections x 8 and the router x 20: at N(0, 0.02) over 32
    inputs the toy's attention scores and router logits are near zero, and
    a rotated full layer or a softmax router moves no logit by 1e-4."""
    return dict(w, **{k: v * (20 if ".mlp.gate." in k else 8)
                      for k, v in w.items()
                      if ".mlp.gate." in k or "q_proj" in k or "k_proj" in k})


def _ln(x, g, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    return (x - mu) / np.sqrt(((x - mu) ** 2).mean(-1, keepdims=True)
                              + eps) * g


def _rope(x, pos, theta, d):
    """Interleaved pairs: lanes (2i, 2i+1) turned by pos * theta^(-2i/d)."""
    out = np.empty_like(x)
    for i in range(d // 2):
        ang = pos * theta ** (-2.0 * i / d)
        a, b = x[2 * i], x[2 * i + 1]
        out[2 * i] = a * np.cos(ang) - b * np.sin(ang)
        out[2 * i + 1] = b * np.cos(ang) + a * np.sin(ang)
    return out


def _swiglu(x, gate, up, down):
    g = gate @ x
    return down @ (g / (1 + np.exp(-g)) * (up @ x))


def token_by_token(cfg, w, ids):
    """Logits (S, vocab) of ONE sequence, float64: the equations of ISSUE 56
    written a token, a head and an expert at a time."""
    f = {k: np.asarray(v, np.float64) for k, v in w.items()}
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    top, reach = cfg["num_experts_per_tok"], cfg["sliding_window"]
    held = cfg["num_experts"]
    first = cfg.get("first_expert") or 0
    x = f["model.embed_tokens.weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        windowed = cfg["layer_types"][i] == "sliding_attention"
        n = _ln(x, f[P + "input_layernorm.weight"][i])
        q = (n @ f[P + "self_attn.q_proj.weight"][i].T).reshape(-1, nq, d)
        k = (n @ f[P + "self_attn.k_proj.weight"][i].T).reshape(-1, nkv, d)
        v = (n @ f[P + "self_attn.v_proj.weight"][i].T).reshape(-1, nkv, d)
        if windowed:                    # a full layer carries NO rotary
            for t in range(len(ids)):
                for h in range(nq):
                    q[t, h] = _rope(q[t, h], t, cfg["rope_theta"], d)
                for h in range(nkv):
                    k[t, h] = _rope(k[t, h], t, cfg["rope_theta"], d)
        out = np.zeros((len(ids), nq, d))
        for t in range(len(ids)):
            lo = max(0, t - reach + 1) if windowed else 0
            for h in range(nq):
                g = h // (nq // nkv)
                s = k[lo:t + 1, g] @ q[t, h] / np.sqrt(d)
                p = np.exp(s - s.max())
                out[t, h] = (p / p.sum()) @ v[lo:t + 1, g]
        a = out.reshape(len(ids), -1) @ f[P + "self_attn.o_proj.weight"][i].T
        logits = n @ f[P + "mlp.gate.weight"][i].T
        scores = 1 / (1 + np.exp(-logits))
        r, c = np.zeros_like(x), np.zeros_like(x)
        for t in range(len(ids)):
            picked = np.argsort(-scores[t], kind="stable")[:top]
            weight = scores[t, picked] / scores[t, picked].sum()
            for e, we in zip(picked, weight):
                if first <= e < first + held:
                    r[t] += we * _swiglu(
                        n[t], f[P + "mlp.experts.{e}.gate_proj.weight"]
                        [i, e - first],
                        f[P + "mlp.experts.{e}.up_proj.weight"][i, e - first],
                        f[P + "mlp.experts.{e}.down_proj.weight"]
                        [i, e - first])
            for s_ in range(cfg["num_shared_experts"]):
                c[t] += _swiglu(
                    n[t], f[P + "mlp.shared_experts.{e}.gate_proj.weight"]
                    [i, s_],
                    f[P + "mlp.shared_experts.{e}.up_proj.weight"][i, s_],
                    f[P + "mlp.shared_experts.{e}.down_proj.weight"][i, s_])
            c[t] /= cfg["num_shared_experts"]
        x = x + a + r + c
    return _ln(x, f["model.norm.weight"]) @ f["model.embed_tokens.weight"].T \
        * cfg["logit_scale"]


def _forward(ref, w, ids, cfg=CFG, **kw):
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(cfg, w, jnp.asarray(ids), **kw))


def test_forward_is_the_token_by_token_writing(ref, w):
    rng = np.random.default_rng(56)
    ids = rng.integers(1, CFG["vocab_size"], size=(2, 23))
    got = _forward(ref, w, ids)
    assert got.shape == (2, 23, CFG["vocab_size"])
    for b in range(2):
        np.testing.assert_allclose(got[b], token_by_token(CFG, w, ids[b]),
                                   atol=ATOL)
    _, margins = ref.forward(CFG, w, ids, with_margins=True)
    assert margins.shape == (2, 23) and float(margins.min()) >= 0.0


@pytest.mark.parametrize("types", [
    ["sliding_attention"] * 4, ["full_attention"] * 4,
    ["full_attention", "sliding_attention"] * 2],
    ids=["all-window", "all-full", "alternating"])
def test_other_layer_types_are_the_same_equations(ref, w, types):
    cfg = dict(CFG, layer_types=types)
    ids = np.random.default_rng(7).integers(1, 96, size=(1, 17))
    np.testing.assert_allclose(_forward(ref, w, ids, cfg)[0],
                               token_by_token(cfg, w, ids[0]), atol=ATOL)


@pytest.mark.parametrize("first", [0, 2, 4, 6])
def test_a_share_is_its_experts_part_of_the_whole(ref, w, first):
    """A share of 2 experts from ``first`` on: the router over all 8, the
    weights renormalised over the 3 picked, held or not."""
    cut = dict(CFG, num_experts=2, router_num_experts=8, first_expert=first)
    w_cut = dict(w, **{k: v[:, first:first + 2] for k, v in w.items()
                       if ".mlp.experts." in k})
    assert {k: tuple(v.shape) for k, v in w_cut.items()} == \
        {k: tuple(e["shape"]) for k, e in ref.weight_shapes(cut).items()}
    ids = np.random.default_rng(9).integers(1, 96, size=(1, 15))
    np.testing.assert_allclose(_forward(ref, w_cut, ids, cut)[0],
                               token_by_token(cut, w_cut, ids[0]), atol=ATOL)
    with pytest.raises(ValueError, match="held"):
        ref.share(dict(cut, first_expert=7))


def test_the_shares_of_a_layer_add_up(ref, w):
    """``r`` over the four shares of 2 experts is ``r`` of the whole layer;
    attention and the shared experts are every share's alike."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(2)
    n = jnp.asarray(rng.standard_normal((1, 9, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.routed_experts(CFG, w, 2, n)
        parts = 0
        for first in (0, 2, 4, 6):
            cut = dict(CFG, num_experts=2, router_num_experts=8,
                       first_expert=first)
            w_cut = dict(w, **{k: v[:, first:first + 2] for k, v in w.items()
                               if ".mlp.experts." in k})
            parts = parts + ref.routed_experts(cut, w_cut, 2, n)[0]
    assert np.abs(np.asarray(whole)).max() > 1e-3
    np.testing.assert_allclose(parts, whole, atol=1e-6)


def test_interleaved_pairs_and_a_nope_row_by_hand(ref):
    """``rotary``: lanes (0, 1) turn by the position, lanes (2, 3) by the
    position x theta^(-1/2); the half-split control pairs (0, 2) and (1, 3).
    ``attend``: a window of 2 sees its own key and the one before."""
    import jax.numpy as jnp
    x = jnp.asarray([1.0, 0.0, 0.0, 1.0])[None, None, None, :]
    x = jnp.broadcast_to(x, (1, 3, 1, 4))
    pos = jnp.arange(3)
    got = np.asarray(ref.rotary(x, pos, 4.0))[0, :, 0]
    for t in range(3):
        np.testing.assert_allclose(
            got[t], [np.cos(t), np.sin(t), -np.sin(t / 2), np.cos(t / 2)],
            atol=1e-6)
    halves = np.asarray(ref.rotary(x, pos, 4.0, halves=True))[0, :, 0]
    np.testing.assert_allclose(
        halves[1], [np.cos(1), -np.sin(0.5), np.sin(1), np.cos(0.5)],
        atol=1e-6)
    k = jnp.asarray([[1.0, 0.0], [0.0, 1.0], [1.0, 1.0]])[None, :, None, :]
    v = jnp.asarray([[1.0, 0.0], [0.0, 2.0], [4.0, 4.0]])[None, :, None, :]
    q = jnp.zeros((1, 3, 1, 1, 2))                        # uniform scores
    win = np.asarray(ref.attend(q, k, v, 2))[0, :, 0, 0]
    np.testing.assert_allclose(win, [[1, 0], [0.5, 1], [2, 3]], atol=1e-6)
    full = np.asarray(ref.attend(q, k, v, None))[0, :, 0, 0]
    np.testing.assert_allclose(full[2], [5 / 3, 2], atol=1e-6)


def test_attend_in_blocks_is_attend_whole(ref):
    import jax.numpy as jnp
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((1, 37, 2, 2, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, 37, 2, 8)), jnp.float32)
            for _ in range(2))
    whole = {reach: np.asarray(ref.attend(q, k, v, reach))
             for reach in (None, 6)}
    block = ref.ATTEND_BLOCK
    ref.ATTEND_BLOCK = 5
    try:
        for reach, want in whole.items():
            np.testing.assert_allclose(ref.attend(q, k, v, reach), want,
                                       atol=1e-6)
    finally:
        ref.ATTEND_BLOCK = block


def test_the_dense_part_is_transformers_cohere2(ref, w):
    """With the routed and shared terms off, the block is the dense
    ``cohere2``'s with its MLP zeroed: LayerNorm, interleaved rotary on the
    sliding layers only, the window mask, the tied head times logit_scale."""
    import torch
    from transformers import Cohere2Config, Cohere2ForCausalLM
    hf_cfg = Cohere2Config(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, num_hidden_layers=4, intermediate_size=24, vocab_size=96,
        sliding_window=5, layer_types=PERIOD, logit_scale=0.25,
        rope_theta=50000, layer_norm_eps=1e-5, attention_dropout=0.0,
        attention_bias=False, torch_dtype="float32")
    hf_cfg._attn_implementation = "eager"
    model = Cohere2ForCausalLM(hf_cfg).eval()
    sd = {"model.embed_tokens.weight": w["model.embed_tokens.weight"],
          "model.norm.weight": w["model.norm.weight"]}
    for i in range(4):
        sd[f"model.layers.{i}.input_layernorm.weight"] = \
            w[P + "input_layernorm.weight"][i]
        for proj in "qkvo":
            sd[f"model.layers.{i}.self_attn.{proj}_proj.weight"] = \
                w[P + f"self_attn.{proj}_proj.weight"][i]
        for proj, shape in (("gate", (24, 32)), ("up", (24, 32)),
                            ("down", (32, 24))):
            sd[f"model.layers.{i}.mlp.{proj}_proj.weight"] = np.zeros(shape)
    missing = model.load_state_dict(
        {k: torch.tensor(np.asarray(v, np.float32)) for k, v in sd.items()},
        strict=False)
    assert set(missing.missing_keys) <= {"lm_head.weight"}
    ids = np.random.default_rng(11).integers(1, 96, size=(2, 19))
    with torch.no_grad():
        want = model(torch.tensor(ids)).logits.numpy()
    bare = dict(CFG, num_shared_experts=0)
    w_off = dict(w, **{k: v * 0 for k, v in w.items()
                       if ".mlp.experts." in k})
    np.testing.assert_allclose(_forward(ref, w_off, ids, bare), want,
                               atol=ATOL)


@pytest.mark.parametrize("control", [
    "no_window", "window_plus_one", "rope_on_full", "rope_halves",
    "shared_sum", "no_shared", "not_renormalised", "softmax", "sequential"])
def test_every_control_moves_the_logits(ref, w, sharp, control):
    assert control in ref.CONTROLS
    ids = np.random.default_rng(5).integers(1, 96, size=(1, 19))
    sound = _forward(ref, sharp, ids)
    np.testing.assert_allclose(sound[0], token_by_token(CFG, sharp, ids[0]),
                               atol=ATOL)
    assert np.abs(_forward(ref, sharp, ids, control=control) - sound).max() \
        > 10 * ATOL
    with pytest.raises(ValueError, match="unknown control"):
        ref.forward(CFG, w, ids, control="nothing")


def test_a_bf16_router_flips_picks(ref, w):
    import jax.numpy as jnp
    n = jnp.asarray(np.random.default_rng(3).standard_normal((1, 4096, 32)),
                    jnp.float32)
    _, idx, _ = ref.routing(CFG, w, 0, n)
    _, idx16, _ = ref.routing(CFG, w, 0, n, control="router_bf16")
    flipped = (np.sort(idx, -1) != np.sort(idx16, -1)).any(-1).mean()
    assert 0 < flipped < 0.2


def test_what_the_reference_refuses(ref, w):
    ids = np.zeros((1, 4), np.int64)
    with pytest.raises(ValueError, match="prefix dense"):
        ref.forward(dict(CFG, first_k_dense_replace=1), w, ids)
    with pytest.raises(ValueError, match="parallel block"):
        ref.forward(dict(CFG, use_parallel_block=False), w, ids)
    with pytest.raises(ValueError, match="sigmoid router"):
        ref.forward(dict(CFG, expert_selection_fn="softmax"), w, ids)
    with pytest.raises(ValueError, match="averages"):
        ref.forward(dict(CFG, shared_expert_combination_strategy="sum"), w,
                    ids)


def test_hfview_round_trips_the_table(ref, w):
    table = ref.weight_shapes(CFG)
    assert {k: v.shape for k, v in w.items()} == \
        {k: tuple(e["shape"]) for k, e in table.items()}
    view = weights.HfView(table, w)
    for name, entry in table.items():
        if "{i}" not in name:
            np.testing.assert_array_equal(np.asarray(w[name]), view[name])
            continue
        assert weights.layers_of(name, entry) == [0, 1, 2, 3]
        for i in range(4):
            if "{e}" in name:
                for e in (0, entry["shape"][1] - 1):
                    np.testing.assert_array_equal(
                        np.asarray(w[name][i, e]),
                        view[name.format(i=i, e=e)])
            else:
                np.testing.assert_array_equal(np.asarray(w[name][i]),
                                              view[name.format(i=i)])
    assert "model.layers.3.mlp.experts.7.down_proj.weight" in view
    assert "model.layers.0.mlp.shared_experts.3.gate_proj.weight" in view
    assert "model.layers.0.mlp.shared_experts.4.gate_proj.weight" not in view
    assert "model.layers.0.mlp.gate.weight" in view
    assert "lm_head.weight" not in view                       # tied
