"""``references/smallthinker.py``'s own check. ``transformers`` 4.57.6 has no
``smallthinker`` class (so no ``reference_cases/smallthinker.json``:
``test_reference`` would look up ``SmallthinkerForCausalLM``): ``forward`` is
held to a SECOND writing of ISSUE 43's equations, token by token in NumPy
float64 (one query at a time against the keys it may see, one expert at a
time), a window row and a NoPE row are checked by hand, every control moves
the logits, and the weight table is round-tripped through ``HfView``."""

import numpy as np
import pytest

from harness import build, weights

CFG = dict(
    model_type="smallthinker", vocab_size=96, hidden_size=32, head_dim=16,
    num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=4,
    moe_ffn_hidden_size=24, moe_num_primary_experts=8,
    moe_num_active_primary_experts=3, moe_primary_router_apply_softmax=True,
    norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1500000,
    rope_scaling=None, max_position_embeddings=256, sliding_window_size=5,
    sliding_window_layout=[0, 1, 1, 1], rope_layout=[0, 1, 1, 1],
    tie_word_embeddings=False)
#: float32 against float64: sums in another order
ATOL = 2e-5
P = "model.layers.{i}."


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("smallthinker")


@pytest.fixture(scope="module")
def w(ref):
    return weights.make_weights(ref.weight_shapes(CFG), seed=2**31 + 43)


def _norm(x, g, eps=1e-6):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta, d):
    inv = 1.0 / theta ** (np.arange(0, d, 2) / d)
    ang = np.concatenate([pos * inv, pos * inv])
    rot = np.concatenate([-x[d // 2:], x[:d // 2]])
    return x * np.cos(ang) + rot * np.sin(ang)


def token_by_token(cfg, w, ids):
    """Logits (S, vocab) of ONE sequence, float64: the equations of ISSUE 43
    written a token, a head and an expert at a time."""
    f = {k: np.asarray(v, np.float64) for k, v in w.items()}
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    top, reach = (cfg["moe_num_active_primary_experts"],
                  cfg["sliding_window_size"])
    x = f["model.embed_tokens.weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        windowed = cfg["sliding_window_layout"][i]
        rotary = cfg["rope_layout"][i]
        a = _norm(x, f[P + "input_layernorm.weight"][i])
        q = (a @ f[P + "self_attn.q_proj.weight"][i].T).reshape(-1, nq, d)
        k = (a @ f[P + "self_attn.k_proj.weight"][i].T).reshape(-1, nkv, d)
        v = (a @ f[P + "self_attn.v_proj.weight"][i].T).reshape(-1, nkv, d)
        if rotary:
            for t in range(len(ids)):
                for h in range(nq):
                    q[t, h] = _rope(q[t, h], t, cfg["rope_theta"], d)
                for h in range(nkv):
                    k[t, h] = _rope(k[t, h], t, cfg["rope_theta"], d)
        out = np.zeros((len(ids), nq, d))
        for t in range(len(ids)):
            first = max(0, t - reach + 1) if windowed else 0
            for h in range(nq):
                g = h // (nq // nkv)
                s = k[first:t + 1, g] @ q[t, h] / np.sqrt(d)
                p = np.exp(s - s.max())
                out[t, h] = (p / p.sum()) @ v[first:t + 1, g]
        hdn = x + out.reshape(len(ids), -1) \
            @ f[P + "self_attn.o_proj.weight"][i].T
        m = _norm(hdn, f[P + "post_attention_layernorm.weight"][i])
        logits = a @ f[P + "block_sparse_moe.primary_router.weight"][i].T
        y = np.zeros_like(hdn)
        for t in range(len(ids)):
            picked = np.argsort(-logits[t], kind="stable")[:top]
            p = np.exp(logits[t, picked] - logits[t, picked].max())
            p /= p.sum()
            for e, pe in zip(picked, p):
                gate = f[P + "block_sparse_moe.experts.{e}.gate.weight"][i, e]
                up = f[P + "block_sparse_moe.experts.{e}.up.weight"][i, e]
                down = f[P + "block_sparse_moe.experts.{e}.down.weight"][i, e]
                y[t] += pe * (down @ (np.maximum(gate @ m[t], 0.0)
                                      * (up @ m[t])))
        x = hdn + y
    return _norm(x, f["model.norm.weight"]) @ f["lm_head.weight"].T


def _forward(ref, w, ids, cfg=CFG, **kw):
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(cfg, w, jnp.asarray(ids), **kw))


def test_forward_is_the_token_by_token_writing(ref, w):
    rng = np.random.default_rng(43)
    ids = rng.integers(1, CFG["vocab_size"], size=(2, 23))
    got = _forward(ref, w, ids)
    assert got.shape == (2, 23, CFG["vocab_size"])
    for b in range(2):
        np.testing.assert_allclose(got[b], token_by_token(CFG, w, ids[b]),
                                   atol=ATOL)
    logits, margins = ref.forward(CFG, w, ids, with_margins=True)
    assert margins.shape == (2, 23) and float(margins.min()) >= 0.0


@pytest.mark.parametrize("layouts", [([1, 1, 1, 1], [1, 1, 1, 1]),
                                     ([0, 0, 0, 0], [0, 0, 0, 0]),
                                     ([0, 1, 0, 1], [0, 1, 0, 1])],
                         ids=["all-window", "all-global", "alternating"])
def test_other_layouts_are_the_same_equations(ref, w, layouts):
    cfg = dict(CFG, sliding_window_layout=layouts[0], rope_layout=layouts[1])
    ids = np.random.default_rng(7).integers(1, 96, size=(1, 17))
    np.testing.assert_allclose(_forward(ref, w, ids, cfg)[0],
                               token_by_token(cfg, w, ids[0]), atol=ATOL)


def test_a_window_row_and_a_nope_row_by_hand(ref):
    """``attend``, one head of two lanes: a window of 2 sees the query's own
    key and the one before it; with ``reach`` None every key at or before
    it. No positional signal enters here: NoPE is rotary left out."""
    import jax.numpy as jnp
    k = jnp.asarray([[1.0, 0.0], [0.0, 1.0], [1.0, 1.0]])[None, :, None, :]
    v = jnp.asarray([[1.0, 0.0], [0.0, 2.0], [4.0, 4.0]])[None, :, None, :]
    q = jnp.asarray([[0.0, 0.0]] * 3)[None, :, None, :]    # uniform scores
    pos = jnp.arange(3)
    win = np.asarray(ref.attend(q, k, v, pos, pos, 2))[0, :, 0]
    np.testing.assert_allclose(win, [[1, 0], [0.5, 1], [2, 3]], atol=1e-6)
    full = np.asarray(ref.attend(q, k, v, pos, pos, None))[0, :, 0]
    np.testing.assert_allclose(full[2], [5 / 3, 2], atol=1e-6)
    # a query that scores: softmax of (q . k) / sqrt(2)
    q2 = jnp.asarray([[0.0, 0.0], [0.0, 0.0], [2.0, 0.0]])[None, :, None, :]
    got = np.asarray(ref.attend(q2, k, v, pos, pos, 2))[0, 2, 0]
    e = np.exp(np.asarray([0.0, 2.0]) / np.sqrt(2))
    np.testing.assert_allclose(got, (e[0] * np.asarray([0, 2.0])
                                     + e[1] * np.asarray([4.0, 4.0]))
                               / e.sum(), atol=1e-6)


def test_the_router_picks_from_the_attention_input(ref, w):
    """``experts``: the routing is ``a``'s, the experts' input ``m``'s; the
    top-k of the logits then the softmax over the k is the softmax over all
    renormalised over the picked."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    a, m = (jnp.asarray(rng.standard_normal((1, 6, 32)), jnp.float32)
            for _ in range(2))
    with jax.default_matmul_precision("highest"):
        y, _ = ref.experts(CFG, w, 2, a, m)
        y_same, _ = ref.experts(CFG, w, 2, m, m)
        y_ctrl, _ = ref.experts(CFG, w, 2, a, m, control="router_post_attn")
    assert np.abs(np.asarray(y - y_same)).max() > 1e-4
    np.testing.assert_allclose(y_ctrl, y_same, atol=1e-7)
    logits = np.asarray(a[0], np.float64) @ np.asarray(
        w[P + "block_sparse_moe.primary_router.weight"][2], np.float64).T
    full = np.exp(logits - logits.max(-1, keepdims=True))
    full /= full.sum(-1, keepdims=True)
    picked = np.argsort(-logits, axis=-1)[:, :3]
    over_k = np.take_along_axis(full, picked, -1)
    over_k /= over_k.sum(-1, keepdims=True)
    direct = np.exp(np.take_along_axis(logits, picked, -1))
    direct /= direct.sum(-1, keepdims=True)
    np.testing.assert_allclose(over_k, direct, atol=1e-12)


@pytest.mark.parametrize("control", ["no_window", "rope_on_global",
                                     "window_plus_one", "router_post_attn",
                                     "silu_gate", "not_renormalised"])
def test_every_control_moves_the_logits(ref, w, control):
    assert control in ref.CONTROLS
    ids = np.random.default_rng(5).integers(1, 96, size=(1, 19))
    sound = _forward(ref, w, ids)
    assert np.abs(_forward(ref, w, ids, control=control) - sound).max() \
        > 10 * ATOL
    with pytest.raises(ValueError, match="unknown control"):
        ref.forward(CFG, w, ids, control="nothing")


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
                for e in (0, 7):
                    np.testing.assert_array_equal(
                        np.asarray(w[name][i, e]),
                        view[name.format(i=i, e=e)])
            else:
                np.testing.assert_array_equal(np.asarray(w[name][i]),
                                              view[name.format(i=i)])
    assert "model.layers.3.block_sparse_moe.experts.7.down.weight" in view
    assert "model.layers.0.block_sparse_moe.primary_router.weight" in view
    assert "model.layers.0.mlp.gate.weight" not in view
    assert "lm_head.weight" in view
