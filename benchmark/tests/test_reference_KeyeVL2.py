"""``references/KeyeVL2.py``'s own check. ``transformers`` 4.57.6 has no
``KeyeVL2`` class (so no ``reference_cases/KeyeVL2.json``: ``test_reference``
would look up ``KeyeVL2ForCausalLM``): ``forward`` is held to a SECOND writing
of ISSUE 50's equations, token by token in NumPy float64 (one query at a time
against the keys it may see, an explicit sort for its top-k, one expert at a
time), the identities a selection has are checked (``topk`` at or over the
length is dense attention; a pass in blocks of queries is the pass at once;
eight shares add up to the uncut block), every control moves the logits, and
the weight table is round-tripped through ``HfView``."""

import numpy as np
import pytest

from harness import build, weights

SA = {"indexer_head_dim": 16, "indexer_num_heads": 3,
      "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
      "topk": 6}
CFG = dict(
    model_type="KeyeVL2", vocab_size=96, hidden_size=32, head_dim=16,
    num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=2,
    moe_intermediate_size=24, num_experts=8, num_experts_per_tok=3,
    norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=10000000,
    rope_scaling={"mrope_section": [2, 3, 3], "rope_type": "default"},
    max_position_embeddings=256, sa_config=SA, tie_word_embeddings=False)
#: float32 against float64: sums in another order
ATOL = 2e-5
P = "model.layers.{i}."
A = P + "self_attn."
X = A + "indexer."


@pytest.fixture(scope="module")
def ref():
    return build.load_reference("KeyeVL2")


@pytest.fixture(scope="module")
def w(ref):
    return weights.make_weights(ref.weight_shapes(CFG), seed=2**31 + 50)


def _norm(x, g, eps=1e-6):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2) / d)
    ang = np.concatenate([pos * inv, pos * inv])
    rot = np.concatenate([-x[d // 2:], x[:d // 2]])
    return x * np.cos(ang) + rot * np.sin(ang)


def token_by_token(cfg, w, ids):
    """Logits (S, vocab) of ONE sequence, float64: the equations of ISSUE 50
    written a token, a head and an expert at a time."""
    f = {k: np.asarray(v, np.float64) for k, v in w.items()}
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    sa, theta = cfg["sa_config"], float(cfg["rope_theta"])
    nj, dj, topk = (sa["indexer_num_heads"], sa["indexer_head_dim"],
                    sa["topk"])
    k_top = cfg["num_experts_per_tok"]
    x = f["model.embed_tokens.weight"][ids]
    s = len(ids)
    for i in range(cfg["num_hidden_layers"]):
        a = _norm(x, f[P + "input_layernorm.weight"][i])
        q = a @ f[A + "q_proj.weight"][i].T
        k = a @ f[A + "k_proj.weight"][i].T
        v = (a @ f[A + "v_proj.weight"][i].T).reshape(s, nkv, d)
        ki = a @ f[X + "wk.weight"][i].T
        mean = ki.mean(-1, keepdims=True)
        ki = ((ki - mean) / np.sqrt(((ki - mean) ** 2).mean(-1, keepdims=True)
                                    + 1e-6)
              * f[X + "k_norm.weight"][i] + f[X + "k_norm.bias"][i])
        ki = np.stack([_rope(ki[t], t, theta) for t in range(s)])
        qn = np.stack([[_rope(_norm(q[t].reshape(nq, d)[h],
                                    f[A + "q_norm.weight"][i]), t, theta)
                        for h in range(nq)] for t in range(s)])
        kn = np.stack([[_rope(_norm(k[t].reshape(nkv, d)[h],
                                    f[A + "k_norm.weight"][i]), t, theta)
                        for h in range(nkv)] for t in range(s)])
        mixed = np.zeros((s, nq * d))
        for t in range(s):
            qi = (a[t] @ f[X + "wq.weight"][i].T).reshape(nj, dj)
            hw = a[t] @ f[X + "weights_proj.weight"][i].T
            score = np.array([sum(
                hw[j] * max(_rope(qi[j], t, theta) @ ki[u], 0.0)
                for j in range(nj)) for u in range(t + 1)])
            # the topk largest, ties to the lower position
            chosen = sorted(range(t + 1), key=lambda u: (-score[u], u))[:topk]
            for h in range(nq):
                kh = h // (nq // nkv)
                logit = np.array([qn[t, h] @ kn[u, kh] for u in chosen]) \
                    / np.sqrt(d)
                p = np.exp(logit - logit.max())
                p /= p.sum()
                mixed[t, h * d:(h + 1) * d] = sum(
                    p[n] * v[u, kh] for n, u in enumerate(chosen))
        h_ = x + mixed @ f[A + "o_proj.weight"][i].T
        m = _norm(h_, f[P + "post_attention_layernorm.weight"][i])
        y = np.zeros_like(m)
        for t in range(s):
            logits = m[t] @ f[P + "mlp.gate.weight"][i].T
            probs = np.exp(logits - logits.max())
            probs /= probs.sum()
            top = np.argsort(-probs, kind="stable")[:k_top]
            for e in top:
                gate = f[P + "mlp.experts.{e}.gate_proj.weight"][i, e]
                up = f[P + "mlp.experts.{e}.up_proj.weight"][i, e]
                down = f[P + "mlp.experts.{e}.down_proj.weight"][i, e]
                g = gate @ m[t]
                y[t] += probs[e] / probs[top].sum() * (
                    down @ (g / (1 + np.exp(-g)) * (up @ m[t])))
        x = h_ + y
    return _norm(x, f["model.norm.weight"]) @ f["lm_head.weight"].T


def _forward(ref, cfg, w, ids, **kw):
    import jax
    with jax.default_matmul_precision("highest"):
        return ref.forward(cfg, w, np.asarray(ids), **kw)


def test_forward_is_the_equations_token_by_token(ref, w):
    ids = np.random.default_rng(0).integers(1, 96, (2, 19))
    got, margins = _forward(ref, CFG, w, ids, with_margins=True)
    got, margins = np.asarray(got), np.asarray(margins)
    assert got.shape == (2, 19, 96) and got.dtype == np.float32
    for b in range(2):
        np.testing.assert_allclose(got[b], token_by_token(CFG, w, ids[b]),
                                   atol=ATOL)
    # everything is selected while t < topk: the margin there is routing's;
    # three index heads of 16 shut their ReLUs together often enough that
    # scores tie at exactly 0 (the tie rule decides, and the margin is 0)
    assert np.isfinite(margins).all() and (margins >= 0).all()
    assert (margins[:, :SA["topk"]] > 0).all()


def test_a_selection_has_its_identities(ref, w):
    ids = np.random.default_rng(1).integers(1, 96, (1, 21))
    sparse = np.asarray(_forward(ref, CFG, w, ids))
    everything = dict(CFG, sa_config=dict(SA, topk=64))
    dense = np.asarray(_forward(ref, CFG, w, ids, control="dense_attention"))
    np.testing.assert_allclose(
        np.asarray(_forward(ref, everything, w, ids)), dense, atol=1e-6)
    # a query under topk sees what dense attention sees; past it, not
    np.testing.assert_allclose(sparse[:, :SA["topk"]], dense[:, :SA["topk"]],
                               atol=1e-6)
    assert np.abs(sparse - dense)[:, SA["topk"]:].max() > 1e-3
    # a pass in blocks of queries is the pass at once
    np.testing.assert_allclose(
        np.asarray(_forward(ref, CFG, w, ids, block=8)), sparse, atol=1e-6)


def test_every_control_moves_the_logits(ref, w):
    ids = np.random.default_rng(2).integers(1, 96, (2, 24))
    want = np.asarray(_forward(ref, CFG, w, ids))
    assert len(ref.CONTROLS) == 8
    for control in ref.CONTROLS:
        moved = np.abs(np.asarray(_forward(ref, CFG, w, ids, control=control))
                       - want).max()
        # rounding this toy's scores to bfloat16 swaps no token of 24: the
        # serving toy of tests/test_keye_vl2_paged.py shows that control
        assert moved > 1e-4 or control == "scores_bf16", (control, moved)
    with pytest.raises(ValueError, match="unknown control"):
        ref.forward(CFG, w, ids, control="nothing")


def test_eight_shares_add_up_to_the_uncut_block(ref, w):
    import jax.numpy as jnp
    m = jnp.asarray(np.random.default_rng(3).normal(size=(2, 7, 32)),
                    jnp.float32)
    whole, _ = ref.experts(CFG, w, 0, m)
    parts = []
    for first in range(8):
        share = dict(CFG, num_experts=1, router_num_experts=8,
                     first_expert=first)
        ws = {k: (v[:, first:first + 1] if "{e}" in k else v)
              for k, v in w.items()}
        assert ref.weight_shapes(share)[
            P + "mlp.experts.{e}.up_proj.weight"]["shape"] == (2, 1, 24, 32)
        parts.append(np.asarray(ref.experts(share, ws, 0, m)[0]))
    np.testing.assert_allclose(sum(parts), np.asarray(whole), atol=1e-6)
    with pytest.raises(ValueError, match="held"):
        ref.share(dict(CFG, num_experts=4, router_num_experts=8,
                       first_expert=5))


def test_hfview_round_trips_the_generated_weights(ref, w):
    table = ref.weight_shapes(CFG)
    assert {k: v.shape for k, v in w.items()} == \
        {k: tuple(e["shape"]) for k, e in table.items()}
    view = weights.HfView(table, w)
    assert X.format(i=1) + "wq.weight" in view
    np.testing.assert_array_equal(
        np.asarray(view[X.format(i=1) + "k_norm.bias"]),
        np.asarray(w[X + "k_norm.bias"][1]))
    np.testing.assert_array_equal(
        np.asarray(view["model.layers.0.mlp.experts.5.down_proj.weight"]),
        np.asarray(w[P + "mlp.experts.{e}.down_proj.weight"][0, 5]))
